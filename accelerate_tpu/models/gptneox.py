"""GPT-NeoX decoder (flax.linen): partial rotary, parallel residual,
untied output head.

The reference's big-model-inference benchmark family is GPT-J/GPT-NeoX
(reference: benchmarks/big_model_inference/README.md — the 20B per-token
table); this module gives the zoo that family natively. Architecture per
EleutherAI GPT-NeoX / HF ``GPTNeoXForCausalLM``:

* rotary embedding on the first ``rotary_pct`` of each head's dims, the
  remainder passes through unrotated;
* parallel residual: ``x + attn(ln1(x)) + mlp(ln2(x))`` (one residual
  read, both branches from the same input — the layout GPT-J introduced);
* LayerNorm (with bias), biased projections, untied ``embed_out``.

Same TPU-first conventions as the rest of the zoo: Megatron column/row
``tensor`` splits, activations sharded over ``seq``, attention through
:mod:`accelerate_tpu.ops.attention`, KV-cache decode via
:mod:`accelerate_tpu.ops.kv_cache`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from ..modeling import Model
from ..ops.fp8 import policy_dot_general as _pdg
from .llama import rope, rows_at


@dataclasses.dataclass
class GPTNeoXConfig:
    vocab_size: int = 50432
    hidden_size: int = 6144
    num_hidden_layers: int = 44
    num_attention_heads: int = 64
    intermediate_size: Optional[int] = None  # defaults to 4*hidden
    max_position_embeddings: int = 2048
    rotary_pct: float = 0.25
    rope_theta: float = 10000.0
    layer_norm_eps: float = 1e-5
    use_parallel_residual: bool = True
    remat: bool = False

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size

    @classmethod
    def neox_20b(cls, **kw) -> "GPTNeoXConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "GPTNeoXConfig":
        kw.setdefault("vocab_size", 256)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_hidden_layers", 2)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("max_position_embeddings", 128)
        return cls(**kw)


GPTNEOX_SHARDING_RULES = [
    (r"embed_in/embedding", P("tensor", None)),
    (r"layer_\d+/attn/(q|k|v)_proj/kernel", P(None, "tensor")),
    (r"layer_\d+/attn/o_proj/kernel", P("tensor", None)),
    (r"layer_\d+/mlp/fc_in/kernel", P(None, "tensor")),
    (r"layer_\d+/mlp/fc_out/kernel", P("tensor", None)),
    (r"embed_out/kernel", P(None, "tensor")),
]

ACTIVATION_SPEC = P(("data", "fsdp"), "seq", None)


def partial_rope(x: jax.Array, positions: jax.Array, theta: float, rotary_dims: int) -> jax.Array:
    """Rotary embedding on the first ``rotary_dims`` of the head dim; the
    tail passes through (GPT-NeoX ``rotary_pct``)."""
    if rotary_dims >= x.shape[-1]:
        return rope(x, positions, theta)
    rotated = rope(x[..., :rotary_dims], positions, theta)
    return jnp.concatenate([rotated, x[..., rotary_dims:]], axis=-1)


class GPTNeoXAttention(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, hidden, positions, decode: bool = False):
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        rotary_dims = int(head_dim * cfg.rotary_pct)
        q = nn.Dense(cfg.hidden_size, name="q_proj", dtype=hidden.dtype, dot_general=_pdg())(hidden)
        k = nn.Dense(cfg.hidden_size, name="k_proj", dtype=hidden.dtype, dot_general=_pdg())(hidden)
        v = nn.Dense(cfg.hidden_size, name="v_proj", dtype=hidden.dtype, dot_general=_pdg())(hidden)

        def split(x):
            return x.reshape(*x.shape[:-1], cfg.num_attention_heads, head_dim)

        q = partial_rope(split(q), positions, cfg.rope_theta, rotary_dims)
        k = partial_rope(split(k), positions, cfg.rope_theta, rotary_dims)
        v = split(v)
        if decode:
            from ..ops.kv_cache import cached_attention

            out = cached_attention(self, q, k, v, cfg.max_position_embeddings)
        else:
            from ..ops.attention import active_mesh, dot_product_attention

            out = dot_product_attention(q, k, v, causal=True, mesh=active_mesh())
        out = out.reshape(*out.shape[:-2], cfg.hidden_size)
        return nn.Dense(cfg.hidden_size, name="o_proj", dtype=hidden.dtype, dot_general=_pdg())(out)


class GPTNeoXMLP(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        h = nn.Dense(cfg.intermediate_size, name="fc_in", dtype=hidden.dtype, dot_general=_pdg())(hidden)
        h = nn.gelu(h, approximate=False)
        return nn.Dense(cfg.hidden_size, name="fc_out", dtype=hidden.dtype, dot_general=_pdg())(h)


class GPTNeoXBlock(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, hidden, positions, decode: bool = False):
        cfg = self.config
        attn_out = GPTNeoXAttention(cfg, name="attn")(
            nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="input_norm", dtype=hidden.dtype)(hidden),
            positions,
            decode,
        )
        if cfg.use_parallel_residual:
            # x + attn(ln1(x)) + mlp(ln2(x)) — both branches read the same
            # residual stream (GPT-J layout; one residual add, better fusion)
            mlp_out = GPTNeoXMLP(cfg, name="mlp")(
                nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="post_attn_norm", dtype=hidden.dtype)(hidden)
            )
            return hidden + attn_out + mlp_out
        hidden = hidden + attn_out
        return hidden + GPTNeoXMLP(cfg, name="mlp")(
            nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="post_attn_norm", dtype=hidden.dtype)(hidden)
        )


class GPTNeoXModel(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, decode: bool = False, logits_at=None):
        cfg = self.config
        hidden = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_in")(input_ids)
        if positions is None:
            positions = jnp.arange(input_ids.shape[-1])[None]
        from ..parallel.sharding import maybe_shard

        hidden = maybe_shard(hidden, ACTIVATION_SPEC)

        block = nn.remat(GPTNeoXBlock, prevent_cse=False, static_argnums=(3,)) if cfg.remat else GPTNeoXBlock
        for i in range(cfg.num_hidden_layers):
            hidden = block(cfg, name=f"layer_{i}")(hidden, positions, decode)
        if logits_at is not None:  # the caller reads these positions' logits alone, as ``LlamaModel``'s
            hidden = rows_at(hidden, logits_at)
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="final_norm", dtype=hidden.dtype)(hidden)
        return nn.Dense(cfg.vocab_size, use_bias=False, name="embed_out", dtype=jnp.float32)(hidden)


def create_gptneox_model(config: Optional[GPTNeoXConfig] = None, seed: int = 0, seq_len: int = 64) -> Model:
    config = config or GPTNeoXConfig.tiny()
    module = GPTNeoXModel(config)
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

    model = Model(apply_fn, params, sharding_rules=GPTNEOX_SHARDING_RULES, name="gptneox")
    model.config = config
    model.module = module
    return model
