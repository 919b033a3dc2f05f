"""BERT encoder (flax.linen) — the flagship benchmark model.

The reference framework is model-agnostic but its headline benchmark is
BERT-base on GLUE/MRPC (reference: examples/nlp_example.py, the
BASELINE.json metric). This is a from-scratch TPU-first implementation:

* weights laid out for the mesh: attention/FFN kernels carry ``tensor``-axis
  sharding rules (Megatron column->row split), embeddings shard vocab over
  ``tensor``, everything FSDP-shardable via the auto rules;
* compute is bf16-friendly (params fp32, matmuls cast by the Accelerator's
  dtype policy);
* optional ``remat`` per encoder layer (activation checkpointing — the
  reference delegates this to FSDP/Megatron flags, SURVEY §5).

Weight import from HF checkpoints is in
:mod:`accelerate_tpu.models.hub` (safetensors -> pytree, torch-free).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.fp8 import policy_dot_general as _pdg
from jax.sharding import PartitionSpec as P

from ..modeling import Model


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    layer_norm_eps: float = 1e-12
    num_labels: int = 2
    remat: bool = False

    @classmethod
    def base(cls, **kw) -> "BertConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "BertConfig":
        """4-layer test-size config for CI meshes."""
        kw.setdefault("vocab_size", 1024)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_hidden_layers", 4)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("max_position_embeddings", 128)
        return cls(**kw)


# Megatron-style tensor-parallel layout: QKV/intermediate are column-split
# (output dim over ``tensor``), attn-out/FFN-down are row-split (input dim
# over ``tensor``), embeddings shard the vocab dim. The reference delegates
# TP entirely to transformers/Megatron (SURVEY §2.2 TP row); here the rules
# ship with the model.
BERT_SHARDING_RULES = [
    (r"embeddings/word_embeddings/embedding", P("tensor", None)),
    (r"attention/(query|key|value)/kernel", P(None, "tensor")),
    (r"attention/out/kernel", P("tensor", None)),
    (r"ffn/intermediate/kernel", P(None, "tensor")),
    (r"ffn/output/kernel", P("tensor", None)),
]


class BertSelfAttention(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, hidden, attention_mask, deterministic: bool = True):
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        dense = lambda name: nn.Dense(cfg.hidden_size, name=name, dtype=hidden.dtype, dot_general=_pdg())
        q = dense("query")(hidden)
        k = dense("key")(hidden)
        v = dense("value")(hidden)

        def split(x):
            return x.reshape(*x.shape[:-1], cfg.num_attention_heads, head_dim)

        q, k, v = split(q), split(k), split(v)
        from ..ops.attention import dot_product_attention

        mask = attention_mask[:, None, None, :]  # [B,1,1,S] additive-ready bool
        out = dot_product_attention(
            q,
            k,
            v,
            mask=mask,
            dropout_rate=0.0 if deterministic else cfg.attention_probs_dropout_prob,
            dropout_rng=None if deterministic else self.make_rng("dropout"),
        )
        out = out.reshape(*out.shape[:-2], cfg.hidden_size)
        out = nn.Dense(cfg.hidden_size, name="out", dtype=hidden.dtype, dot_general=_pdg())(out)
        if not deterministic:
            out = nn.Dropout(cfg.hidden_dropout_prob)(out, deterministic=False)
        return out


class BertLayer(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, hidden, attention_mask, deterministic: bool = True):
        cfg = self.config
        attn_out = BertSelfAttention(cfg, name="attention")(hidden, attention_mask, deterministic)
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="attention_norm", dtype=jnp.float32)(
            hidden + attn_out
        ).astype(hidden.dtype)

        ffn = nn.Dense(cfg.intermediate_size, name="ffn/intermediate", dtype=hidden.dtype, dot_general=_pdg())(hidden)
        ffn = nn.gelu(ffn, approximate=False)
        ffn = nn.Dense(cfg.hidden_size, name="ffn/output", dtype=hidden.dtype, dot_general=_pdg())(ffn)
        if not deterministic:
            ffn = nn.Dropout(cfg.hidden_dropout_prob)(ffn, deterministic=False)
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="ffn_norm", dtype=jnp.float32)(
            hidden + ffn
        ).astype(hidden.dtype)
        return hidden


class BertEncoder(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask, token_type_ids=None, deterministic: bool = True):
        cfg = self.config
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        positions = jnp.arange(input_ids.shape[-1])[None, :]
        emb = (
            nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embeddings/word_embeddings")(input_ids)
            + nn.Embed(cfg.max_position_embeddings, cfg.hidden_size, name="embeddings/position_embeddings")(positions)
            + nn.Embed(cfg.type_vocab_size, cfg.hidden_size, name="embeddings/token_type_embeddings")(token_type_ids)
        )
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="embeddings/norm", dtype=jnp.float32)(emb).astype(
            emb.dtype
        )
        layer_cls = nn.remat(BertLayer, static_argnums=(3,)) if cfg.remat else BertLayer
        for i in range(cfg.num_hidden_layers):
            hidden = layer_cls(cfg, name=f"layer_{i}")(hidden, attention_mask, deterministic)
        return hidden


class BertForSequenceClassification(nn.Module):
    """Encoder + [CLS] pooler + classifier (the MRPC fine-tune head)."""

    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask, token_type_ids=None, deterministic: bool = True):
        cfg = self.config
        hidden = BertEncoder(cfg, name="encoder")(input_ids, attention_mask, token_type_ids, deterministic)
        pooled = nn.tanh(nn.Dense(cfg.hidden_size, name="pooler")(hidden[:, 0]))
        if not deterministic:
            pooled = nn.Dropout(cfg.hidden_dropout_prob)(pooled, deterministic=False)
        return nn.Dense(cfg.num_labels, name="classifier", dtype=jnp.float32)(pooled)


def create_bert_model(
    config: Optional[BertConfig] = None,
    seed: int = 0,
    seq_len: int = 128,
    batch_size: int = 2,
) -> Model:
    """Initialise a :class:`~accelerate_tpu.modeling.Model` wrapping
    BERT-for-classification with its TP sharding rules attached."""
    config = config or BertConfig.base()
    module = BertForSequenceClassification(config)
    dummy = {
        "input_ids": jnp.zeros((batch_size, seq_len), jnp.int32),
        "attention_mask": jnp.ones((batch_size, seq_len), jnp.bool_),
    }
    # one jitted program: an eager init runs the whole forward op-by-op
    params = jax.jit(module.init)(jax.random.key(seed), dummy["input_ids"], dummy["attention_mask"])["params"]

    def apply_fn(p, input_ids, attention_mask, token_type_ids=None, deterministic=True, rngs=None):
        if not deterministic and rngs is None:
            raise ValueError("deterministic=False (dropout on) requires rngs={'dropout': key}")
        return module.apply(
            {"params": p}, input_ids, attention_mask, token_type_ids, deterministic=deterministic, rngs=rngs
        )

    model = Model(apply_fn, params, sharding_rules=BERT_SHARDING_RULES, name="bert")
    model.config = config
    model.module = module
    return model


def bert_classification_loss(params, batch, apply_fn, rng=None):
    """Cross-entropy loss for the fine-tune head (fp32 logits/loss).
    Pass ``rng`` (e.g. from the Accelerator's per-step key) to train with
    dropout; without it the model runs deterministically."""
    logits = apply_fn(
        params,
        batch["input_ids"],
        batch["attention_mask"],
        batch.get("token_type_ids"),
        deterministic=rng is None,
        rngs=None if rng is None else {"dropout": rng},
    )
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    if "loss_mask" in batch:
        mask = batch["loss_mask"].astype(jnp.float32)
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()
