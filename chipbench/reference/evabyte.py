"""EvaByte as this repository was asked to serve it (EvaByte/EvaByte ``config.json``; ``attention_class``
``eva``): a byte-level pre-norm decoder whose every attention layer is EVA, chunked linearised attention.
Float32, one layer at a time, the whole sequence at once, no cache and no pages: every position's visible
set is built from the two rules below and nothing else.

From the published ``config.json``: hidden 4096; 32 query heads and 32 key/value heads of 128; no bias;
rotary over the whole head, ``rope_theta`` 100,000, no scaling; SwiGLU 11,008 (``silu``); RMSNorm eps 1e-5
with the scale ``1 + w`` (``norm_add_unit_offset``); ``window_size`` W 2048; ``chunk_size`` c 16; vocabulary
320, head untied; residual sums and logits in float32 (``fp32_skip_add``, ``fp32_logits``: everything here is).

Attention of head ``h`` at position ``t``, ``s = 128 ** -0.5``, ``q`` and ``k`` rotated at their positions:

* chunk ``m`` holds positions ``c m .. c m + c - 1``, window ``w`` positions ``W w .. W w + W - 1``;
* the summaries of a chunk: ``a_j = softmax_j(s k_j . mu_h)``, ``K~_m = sum_j a_j k_j``; ``b_j = softmax_j(s k_j .
  phi_h)``, ``V~_m = sum_j b_j v_j``; ``j`` over the chunk's positions, ``mu_h``, ``phi_h`` learned a head;
* the output: ONE softmax over the logits ``s q_t . k_j`` for ``W w <= j <= t`` and ``s q_t . K~_m`` for ``m < (W / c)
  w``, ``w = t // W``; ``o_t = sum_j p_j v_j + sum_m p_m V~_m``. A summary is one column: no count term.

**Not in the published config** (it names the class, the chunk and the window), and so ``assumed`` in the
configuration file: that ``mu`` (``adaptive_mu_k``) pools the keys and ``phi`` (``adaptive_phi``) the values,
both as softmax weights over the chunk's *rotated keys*; that windows are aligned and not sliding; that a
summary enters as a single column. They follow the family's public modeling code (``eva_prep_kv_kernel.py``,
``eva_agg_kernel.py``, ``eva_pt_ref.py``) as the issue's author recalled it, with no network to check; the
builder could not check either and knows of no further point at which the published code differs.

Departures: the published head is ``[hidden, 320 x 8]`` (``num_pred_heads`` 8, head ``i`` predicts byte ``t + 1 +
i``); the first alone is held (the configuration's ``not_served``). ``mu`` and ``phi`` are drawn at 0.8 and not at
the published initialiser (0.01275, clamped), at which both softmaxes are flat and a plain mean, or the two
vectors swapped, would sit inside any tolerance: at 0.8 ``s k_j . mu_h`` has about unit spread over a chunk.
Rotary turns adjacent pairs ``(2i, 2i + 1)`` (Su et al. 2021, eq. 34), as the program does.

This file is the family: its seeded weights (``spec``), its plain reference (``logits_at``) and what its work
requires from shapes alone (``weight_bytes_per_decode_step``, ``rows_read``, ``cache_bytes_per_decode_step``,
``attention_shape``, ``page_bytes``, ``pool_blocks``).
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


def name(i: int, tensor: str) -> str:
    """The benchmark's name of layer ``i``'s ``tensor``: a tensor a layer, so that a builder's unrolled tree holds
    the seeded arrays themselves and no second copy of the weights."""
    return f"L{i:02d}.{tensor}"


def spec(cfg: dict) -> dict:
    hidden, ff, vocab = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    heads, kv_heads, dim = attention_shape(cfg)
    normal = ("normal", cfg.get("initializer_range", 0.02))
    offset = ("normal", 0.1)  # RMSNorm's ``w``: the scale is ``1 + w``
    pool = ("normal", cfg.get("pooling_vector_std", 0.8))
    layer = {
        "wq": ((hidden, heads * dim), normal), "wk": ((hidden, kv_heads * dim), normal),
        "wv": ((hidden, kv_heads * dim), normal), "wo": ((heads * dim, hidden), normal),
        "mu": ((kv_heads, dim), pool), "phi": ((kv_heads, dim), pool),
        "w_gate": ((hidden, ff), normal), "w_up": ((hidden, ff), normal), "w_down": ((ff, hidden), normal),
        "norm_attn": ((hidden,), offset), "norm_mlp": ((hidden,), offset),
    }
    out = {"embed": ((vocab, hidden), normal), "norm_final": ((hidden,), offset), "lm_head": ((hidden, vocab), normal)}
    for i in range(cfg["num_hidden_layers"]):
        out.update({name(i, tensor): how for tensor, how in layer.items()})
    return out


def _layer_params(cfg: dict) -> int:
    """A layer: four attention projections, SwiGLU, two norms and the two pooling vectors a head."""
    hidden, (heads, kv_heads, d) = cfg["hidden_size"], attention_shape(cfg)
    return (hidden * (heads * d + 2 * kv_heads * d) + heads * d * hidden + 3 * hidden * cfg["intermediate_size"]
            + 2 * hidden + 2 * kv_heads * d)


def weight_bytes_per_decode_step(cfg: dict, slots: int, itemsize: int = 2) -> float:
    """Every layer's weights, the final norm and the output head, read once; one embedding row a slot."""
    hidden = cfg["hidden_size"]
    params = cfg["num_hidden_layers"] * _layer_params(cfg) + hidden + hidden * cfg["vocab_size"]
    return float(itemsize) * (params + slots * hidden)


def rows_read(cfg: dict, t: int) -> int:
    """Rows of keys (and of values) the step that writes position ``t`` attends to: a summary for every chunk
    of the windows before its own, and its own window's rows up to itself."""
    window, chunk = cfg["window_size"], cfg["chunk_size"]
    return (window // chunk) * (t // window) + t % window + 1


def cache_bytes_per_decode_step(cfg: dict, rows: float, slots: int, itemsize: int = 2) -> float:
    """Every layer's keys and values of the ``rows`` the decoding slots read together (``rows_read`` summed
    over them: NOT their contexts, of which a closed window leaves a sixteenth), queries and output: the
    paged decode kernel's bytes a layer. The program counts the rows (``attn_rows_read``)."""
    heads, kv_heads, d = attention_shape(cfg)
    return cfg["num_hidden_layers"] * costs.paged_decode_attention_bytes(heads * d, kv_heads * d, rows, slots, itemsize)


def page_bytes(cfg: dict, block: int, itemsize: int = 2) -> int:
    """One page of ``block`` rows (K/V rows or summaries: one shape), keys and values, every layer."""
    _, kv_heads, d = attention_shape(cfg)
    return 2 * block * kv_heads * d * itemsize * cfg["num_hidden_layers"]


def pages_per_slot(cfg: dict, max_len: int, block: int) -> int:
    """The most pages a sequence holds: one window exact and a page of summaries for every ``block`` chunks."""
    return cfg["window_size"] // block + max_len // (block * cfg["chunk_size"])


def pool_blocks(cfg: dict, slots: int, max_len: int, block: int) -> int:
    return slots * pages_per_slot(cfg, max_len, block) + 1  # and the trash sink


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _rotate(x, positions, theta):
    """x [T, heads, d]; pair (2i, 2i+1) turned by positions * theta**(-2i/d)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def summaries(k, v, mu, phi, chunk: int, s: float):
    """``k``, ``v`` ``[T, d]`` of one head, ``mu``, ``phi`` ``[d]``: ``(K~, V~)`` ``[T // chunk, d]`` of its whole chunks."""
    m = k.shape[0] // chunk
    kc, vc = k[: m * chunk].reshape(m, chunk, -1), v[: m * chunk].reshape(m, chunk, -1)
    a = jax.nn.softmax(s * jnp.einsum("mjd,d->mj", kc, mu, precision="highest"), axis=-1)
    b = jax.nn.softmax(s * jnp.einsum("mjd,d->mj", kc, phi, precision="highest"), axis=-1)
    return jnp.einsum("mj,mjd->md", a, kc, precision="highest"), jnp.einsum("mj,mjd->md", b, vc, precision="highest")


def visible(t: int, window: int, chunk: int):
    """``[T, T // chunk + T]`` bool: for every position, which summaries and which rows it attends to."""
    pos = jnp.arange(t)
    first = (pos // window) * window  # its window's first position
    rows = (pos[None, :] >= first[:, None]) & (pos[None, :] <= pos[:, None])
    chunks = jnp.arange(t // chunk)[None, :] < (first // chunk)[:, None]
    return jnp.concatenate([chunks, rows], axis=1)


def attention(q, k, v, mu, phi, cfg: dict):
    """``q``, ``k``, ``v`` ``[T, heads, d]`` (rotated), ``mu``, ``phi`` ``[heads, d]``: EVA, a head at a time."""
    t, _, d = q.shape
    s = d**-0.5
    seen = visible(t, cfg["window_size"], cfg["chunk_size"])

    def one_head(args):  # a head at a time bounds the score matrix: [T, T // chunk + T]
        qh, kh, vh, mu_h, phi_h = args
        pooled_k, pooled_v = summaries(kh, vh, mu_h, phi_h, cfg["chunk_size"], s)
        keys, values = jnp.concatenate([pooled_k, kh]), jnp.concatenate([pooled_v, vh])
        scores = s * jnp.einsum("qd,kd->qk", qh, keys, precision="highest")
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("qk,kd->qd", probs, values, precision="highest")

    heads_first = lambda x: x.transpose(1, 0, 2)
    out = jax.lax.map(one_head, (heads_first(q), heads_first(k), heads_first(v), mu, phi))  # [heads, T, d]
    return out.transpose(1, 0, 2)


def layer(x, w, cfg: dict, dot):
    """One decoder layer over one sequence ``x`` [T, hidden]; ``w`` holds this layer's float32 weights."""
    heads, kv_heads, d = attention_shape(cfg)
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _rms_norm(x, w["norm_attn"], cfg["rms_norm_eps"])
    q = _rotate(dot(h, w["wq"]).reshape(t, heads, d), pos, cfg["rope_theta"])
    k = _rotate(dot(h, w["wk"]).reshape(t, kv_heads, d), pos, cfg["rope_theta"])
    v = dot(h, w["wv"]).reshape(t, kv_heads, d)
    x = x + dot(attention(q, k, v, w["mu"], w["phi"], cfg).reshape(t, heads * d), w["wo"])
    h = _rms_norm(x, w["norm_mlp"], cfg["rms_norm_eps"])
    return x + dot(jax.nn.silu(dot(h, w["w_gate"])) * dot(h, w["w_up"]), w["w_down"])


LAYER_NAMES = ("wq", "wk", "wv", "wo", "mu", "phi", "w_gate", "w_up", "w_down", "norm_attn", "norm_mlp")


@functools.partial(jax.jit, static_argnames=("cfg_key", "dot_name"))
def _layer_at(x, w, cfg_key, dot_name):
    return layer(x, {n: t.astype(jnp.float32) for n, t in w.items()}, dict(cfg_key), DOTS[dot_name])


def layer_weights(weights: dict, i: int) -> dict:
    return {n: weights[name(i, n)] for n in LAYER_NAMES}


@functools.partial(jax.jit, static_argnames=("eps", "dot_name"))
def _head(x, rows, norm_final, lm_head, eps, dot_name):
    h = _rms_norm(x[rows], norm_final.astype(jnp.float32), eps)
    return DOTS[dot_name](h, lm_head.astype(jnp.float32))


def _cfg_key(cfg: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "hidden_size", "rms_norm_eps", "rope_theta", "window_size", "chunk_size")
    return tuple((k, cfg.get(k)) for k in keys)


def logits_at(weights: dict, cfg: dict, tokens, rows, dot_name: str = "exact"):
    """Logits [len(rows), vocab] of one sequence of byte ids at the positions ``rows``."""
    x = weights["embed"][tokens].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer_at(x, layer_weights(weights, i), _cfg_key(cfg), dot_name)
    return _head(x, rows, weights["norm_final"], weights["lm_head"], cfg["rms_norm_eps"], dot_name)
