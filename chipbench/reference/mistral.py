"""Mistral-7B-v0.1 as published (Jiang et al. 2023, arXiv:2310.06825; config.json):
pre-norm decoder, RMSNorm, rotary embedding on adjacent pairs (Su et al. 2021,
arXiv:2104.09864, eq. 34), grouped-query attention under a causal band of
``sliding_window`` keys, SwiGLU feed-forward, untied output head. Float32, one layer
at a time, so that a 16-layer model's float32 copy never exists whole.

Departure: none in the mathematics. Weights come in the benchmark's names (``spec``),
stacked over layers, in the served type, and are widened to float32 a layer at a time.

This file is the family: its seeded weights (``spec``), its plain reference (``logits_at``
for a serve cell, ``loss_fn`` and ``LAYER_NAMES`` for a train cell) and what its work
requires from shapes alone (``train_flops``, ``*_bytes_per_decode_step``, ``attention_shape``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import costs
from chipbench.reference.lowprec import DOTS


def attention_shape(cfg: dict) -> tuple:
    """Query heads, key/value heads, and the size of one."""
    return cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["hidden_size"] // cfg["num_attention_heads"]


def spec(cfg: dict) -> dict:
    layers, hidden, ff, vocab = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    heads, kv_heads, dim = attention_shape(cfg)
    q_out, kv_out = heads * dim, kv_heads * dim
    std = cfg.get("initializer_range", 0.02)
    normal, scale = ("normal", std), ("one_plus", 0.1)
    return {
        "embed": ((vocab, hidden), normal),
        "wq": ((layers, hidden, q_out), normal), "wk": ((layers, hidden, kv_out), normal),
        "wv": ((layers, hidden, kv_out), normal), "wo": ((layers, q_out, hidden), normal),
        "w_gate": ((layers, hidden, ff), normal), "w_up": ((layers, hidden, ff), normal),
        "w_down": ((layers, ff, hidden), normal),
        "norm_attn": ((layers, hidden), scale), "norm_mlp": ((layers, hidden), scale),
        "norm_final": ((hidden,), scale), "lm_head": ((hidden, vocab), normal),
    }


def _layer_matmul_params(cfg: dict) -> int:
    hidden, (heads, kv_heads, d) = cfg["hidden_size"], attention_shape(cfg)
    return hidden * (heads * d + 2 * kv_heads * d) + heads * d * hidden + 3 * hidden * cfg["intermediate_size"]


def train_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward and backward of one step: 6 per matmul parameter and token, plus causal attention."""
    heads, _, d = attention_shape(cfg)
    layers = cfg["num_hidden_layers"]
    matmul = 2.0 * batch * seq * (layers * _layer_matmul_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"])
    return 3.0 * (matmul + layers * costs.flash_attention_flops(batch, seq, heads, d, "fwd"))


def weight_bytes_per_decode_step(cfg: dict, slots: int, itemsize: int = 2) -> float:
    """Every layer's weights, the final norm and the output head, read once; one embedding row a slot."""
    hidden = cfg["hidden_size"]
    params = cfg["num_hidden_layers"] * (_layer_matmul_params(cfg) + 2 * hidden) + hidden + hidden * cfg["vocab_size"]
    return float(itemsize) * (params + slots * hidden)


def cache_bytes_per_decode_step(cfg: dict, live_tokens: float, slots: int, itemsize: int = 2) -> float:
    """Every layer's live keys and values, queries and output: the paged decode kernel's bytes a layer."""
    heads, kv_heads, d = attention_shape(cfg)
    return cfg["num_hidden_layers"] * costs.paged_decode_attention_bytes(heads * d, kv_heads * d, live_tokens, slots, itemsize)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotate(x, positions, theta):
    """x [T, heads, d]; pair (2i, 2i+1) turned by positions * theta**(-2i/d)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def layer(x, w, cfg: dict, dot):
    """One decoder layer over one sequence ``x`` [T, hidden]; ``w`` holds this layer's float32 weights."""
    heads, kv_heads, d = attention_shape(cfg)
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _rms_norm(x, w["norm_attn"], cfg["rms_norm_eps"])
    q = _rotate(dot(h, w["wq"]).reshape(t, heads, d), pos, cfg["rope_theta"])
    k = _rotate(dot(h, w["wk"]).reshape(t, kv_heads, d), pos, cfg["rope_theta"])
    v = dot(h, w["wv"]).reshape(t, kv_heads, d)
    group = heads // kv_heads
    window = cfg.get("sliding_window") or t
    seen = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None] - window)

    def one_kv_head(qkv):  # q [group, T, d], k and v [T, d]: a kv head at a time bounds the score matrix
        qh, kh, vh = qkv
        scores = jnp.einsum("gqd,kd->gqk", qh, kh, precision="highest") * d**-0.5
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->gqd", probs, vh, precision="highest")

    q = q.reshape(t, kv_heads, group, d).transpose(1, 2, 0, 3)
    ctx = jax.lax.map(one_kv_head, (q, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # [kv_heads, group, T, d]
    ctx = ctx.transpose(2, 0, 1, 3).reshape(t, heads * d)
    x = x + dot(ctx, w["wo"])
    h = _rms_norm(x, w["norm_mlp"], cfg["rms_norm_eps"])
    return x + dot(jax.nn.silu(dot(h, w["w_gate"])) * dot(h, w["w_up"]), w["w_down"])


LAYER_NAMES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "norm_attn", "norm_mlp")


@functools.partial(jax.jit, static_argnames=("cfg_key", "dot_name"))
def _layer_at(x, stacked, i, cfg_key, dot_name):
    cfg = dict(cfg_key)
    w = {n: jax.lax.dynamic_index_in_dim(stacked[n], i, 0, keepdims=False).astype(jnp.float32) for n in LAYER_NAMES}
    return layer(x, w, cfg, DOTS[dot_name])


@functools.partial(jax.jit, static_argnames=("eps", "dot_name"))
def _head(x, rows, norm_final, lm_head, eps, dot_name):
    h = _rms_norm(x[rows], norm_final.astype(jnp.float32), eps)
    return DOTS[dot_name](h, lm_head.astype(jnp.float32))


def _cfg_key(cfg: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "hidden_size", "rms_norm_eps", "rope_theta", "sliding_window")
    return tuple((k, cfg.get(k)) for k in keys)


def logits_at(weights: dict, cfg: dict, tokens, rows, dot_name: str = "exact"):
    """Logits [len(rows), vocab] of one sequence of token ids at the positions ``rows``."""
    x = weights["embed"][tokens].astype(jnp.float32)
    stacked = {n: weights[n] for n in LAYER_NAMES}
    for i in range(cfg["num_hidden_layers"]):
        x = _layer_at(x, stacked, i, _cfg_key(cfg), dot_name)
    return _head(x, rows, weights["norm_final"], weights["lm_head"], cfg["rms_norm_eps"], dot_name)


def loss_fn(weights: dict, cfg: dict, input_ids, dot_name: str = "exact"):
    """Mean next-token cross-entropy over rows [B, T] (the last position has no target).
    For training: float32 weights, differentiable, all layers in one program."""
    dot = DOTS[dot_name]

    def one(tokens):
        x = weights["embed"][tokens].astype(jnp.float32)

        def body(x, w):
            return layer(x, w, cfg, dot), None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, {n: weights[n] for n in LAYER_NAMES})
        logits = dot(_rms_norm(x, weights["norm_final"], cfg["rms_norm_eps"]), weights["lm_head"])
        logp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0].mean()

    return jax.lax.map(one, input_ids).mean()
