"""GPT-2 decoder (flax.linen): learned positions, pre-LN, GELU MLP, tied head.

Completes the model-family coverage the reference gets via its Megatron
config parsers — bert/gpt2/t5/llama (reference:
src/accelerate/utils/dataclasses.py:2532-2662 parse_bert_config/gpt2/t5/
llama). Same TPU-first layout conventions as the rest of the zoo:
Megatron column/row ``tensor`` splits, activation sharding over
``seq``, attention dispatched through :mod:`accelerate_tpu.ops.attention`.
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
from .llama import rows_at


@dataclasses.dataclass
class GPT2Config:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: Optional[int] = None  # defaults to 4*hidden
    max_position_embeddings: int = 1024
    layer_norm_eps: float = 1e-5
    embd_pdrop: float = 0.1
    tie_word_embeddings: bool = True
    remat: bool = False

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size

    @classmethod
    def small(cls, **kw) -> "GPT2Config":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "GPT2Config":
        kw.setdefault("vocab_size", 256)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_hidden_layers", 2)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("max_position_embeddings", 128)
        return cls(**kw)


GPT2_SHARDING_RULES = [
    (r"wte/embedding", P("tensor", None)),
    (r"layer_\d+/attn/(q|k|v)_proj/kernel", P(None, "tensor")),
    (r"layer_\d+/attn/o_proj/kernel", P("tensor", None)),
    (r"layer_\d+/mlp/fc_in/kernel", P(None, "tensor")),
    (r"layer_\d+/mlp/fc_out/kernel", P("tensor", None)),
    (r"lm_head/kernel", P(None, "tensor")),
]

ACTIVATION_SPEC = P(("data", "fsdp"), "seq", None)


class GPT2Attention(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, hidden, decode: bool = False):
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        q = nn.Dense(cfg.hidden_size, name="q_proj", dtype=hidden.dtype, dot_general=_pdg())(hidden)
        k = nn.Dense(cfg.hidden_size, name="k_proj", dtype=hidden.dtype, dot_general=_pdg())(hidden)
        v = nn.Dense(cfg.hidden_size, name="v_proj", dtype=hidden.dtype, dot_general=_pdg())(hidden)

        def split(x):
            return x.reshape(*x.shape[:-1], cfg.num_attention_heads, head_dim)

        if decode:
            from ..ops.kv_cache import cached_attention

            out = cached_attention(self, split(q), split(k), split(v), cfg.max_position_embeddings)
        else:
            from ..ops.attention import active_mesh, dot_product_attention

            out = dot_product_attention(split(q), split(k), split(v), causal=True, mesh=active_mesh())
        out = out.reshape(*out.shape[:-2], cfg.hidden_size)
        return nn.Dense(cfg.hidden_size, name="o_proj", dtype=hidden.dtype, dot_general=_pdg())(out)


class GPT2MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        h = nn.Dense(cfg.intermediate_size, name="fc_in", dtype=hidden.dtype, dot_general=_pdg())(hidden)
        h = nn.gelu(h, approximate=True)
        return nn.Dense(cfg.hidden_size, name="fc_out", dtype=hidden.dtype, dot_general=_pdg())(h)


class GPT2Block(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, hidden, decode: bool = False):
        cfg = self.config
        hidden = hidden + GPT2Attention(cfg, name="attn")(
            nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="ln_1", dtype=hidden.dtype)(hidden), decode
        )
        hidden = hidden + GPT2MLP(cfg, name="mlp")(
            nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="ln_2", dtype=hidden.dtype)(hidden)
        )
        return hidden


class GPT2Model(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True, decode: bool = False, logits_at=None):
        cfg = self.config
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="wte")
        hidden = wte(input_ids)
        if positions is None:
            positions = jnp.arange(input_ids.shape[-1])[None]
        hidden = hidden + nn.Embed(
            cfg.max_position_embeddings, cfg.hidden_size, name="wpe"
        )(positions)
        from ..parallel.sharding import maybe_shard

        hidden = maybe_shard(hidden, ACTIVATION_SPEC)

        block = nn.remat(GPT2Block, prevent_cse=False, static_argnums=(2,)) if cfg.remat else GPT2Block
        for i in range(cfg.num_hidden_layers):
            hidden = block(cfg, name=f"layer_{i}")(hidden, decode)
        if logits_at is not None:  # the caller reads these positions' logits alone, as ``LlamaModel``'s
            hidden = rows_at(hidden, logits_at)
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="ln_f", dtype=hidden.dtype)(hidden)
        if cfg.tie_word_embeddings:
            return hidden.astype(jnp.float32) @ wte.embedding.T.astype(jnp.float32)
        return nn.Dense(cfg.vocab_size, use_bias=False, name="lm_head", dtype=jnp.float32)(hidden)


def create_gpt2_model(config: Optional[GPT2Config] = None, seed: int = 0, seq_len: int = 64) -> Model:
    config = config or GPT2Config.tiny()
    module = GPT2Model(config)
    dummy = jnp.zeros((2, seq_len), jnp.int32)
    params = module.init(jax.random.key(seed), dummy)["params"]

    def apply_fn(p, input_ids, positions=None, decode=False, cache=None, logits_at=None):
        """decode=True threads the KV cache: pass ``cache`` (or None to
        initialise) and receive ``(logits, new_cache)``. ``logits_at``
        (int32 positions, ``[n]`` or a scalar): these positions' logits
        alone, ``[batch, n, vocab]``; None, every position."""
        if decode:
            variables = {"params": p}
            if cache is not None:
                variables["cache"] = cache
            logits, mutated = module.apply(
                variables, input_ids, positions, decode=True, logits_at=logits_at, mutable=["cache"]
            )
            return logits, mutated["cache"]
        return module.apply({"params": p}, input_ids, positions, logits_at=logits_at)

    model = Model(apply_fn, params, sharding_rules=GPT2_SHARDING_RULES, name="gpt2")
    model.config = config
    model.module = module
    return model
