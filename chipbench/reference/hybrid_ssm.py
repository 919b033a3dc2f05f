"""A decoder whose layers mix through a selective state-space scan (Mamba-1, arXiv:2312.00752,
section 3 and algorithm 2) except one in every ``attn_layer_period``, which attends: the Jamba
layout (arXiv:2403.19887, section 2), as a ``config.json`` of ``model_type`` ``jamba`` with
``num_experts`` 1 states it. The family is named for its mechanisms: a sibling configuration needs
no code.

Every layer: ``x = x + mixer(RMSNorm(x))``, ``x = x + SwiGLU(RMSNorm(x))``; a final RMSNorm; the head
is the embedding (``tie_word_embeddings``).

- *An attention layer* (``i % attn_layer_period == attn_layer_offset``): grouped-query attention,
  ``num_attention_heads`` query heads over ``num_key_value_heads`` key/value heads of ``hidden_size /
  num_attention_heads``, no bias, causal, scale ``head_dim ** -0.5`` and **no position encoding**:
  the ``jamba`` model type has none (positions reach the model through the recurrence).
- *A state-space layer* (every other): ``d_inner = mamba_expand * hidden_size``. ``[u, z] =
  in_proj(x)``; ``u = silu(conv1d(u))``, a causal depthwise convolution of ``mamba_d_conv`` taps with
  a bias; ``[dt, B, C] = x_proj(u)`` (``mamba_dt_rank``, ``mamba_d_state``, ``mamba_d_state`` wide),
  each through an RMSNorm with a learned scale (the ``jamba`` modeling code's ``dt_layernorm``,
  ``b_layernorm``, ``c_layernorm``; no config key names them: the configuration's file lists them
  under ``assumed``); ``delta = softplus(dt_proj(dt) + dt_bias)``; ``A = -exp(A_log)``;
  ``h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) B_t`` from ``h = 0``; ``y_t = h_t C_t + D u_t``;
  ``out_proj(y * silu(z))``. A ``lax.scan`` a token over ``h`` ``[d_inner, d_state]``: no chunks, no
  carried state between windows, no cache, no kernel.

Float32 at ``highest``, one layer at a time. The control's arithmetic (``dot_name``) replaces the
matrix products (projections, MLP, head); the recurrence and the attention products stay float32.

**The constants of the recurrence are drawn through one map, ``ssm_constants``, written here once and
used by this reference and by the builder alike.** ``weights.make`` draws normal tensors only, and
a normal ``A_log`` or ``dt_bias`` at the other weights' scale gives a state that forgets in a few
tokens, which would let a wrong recurrence pass. So ``spec`` draws standard-normal ``dt_bias_raw``
and ``a_raw`` and the map sends them where Mamba's own initialiser puts them: ``softplus(dt_bias)``
log-uniform in [0.001, 0.1], ``A`` from ``-(1 .. d_state)`` (its S4D-real initialiser) towards 0 by
up to a factor of five (about -16 .. -0.2), so that a layer holds memories of ten to several
thousand tokens. ``D`` is ``1 + 0.1 normal`` (Mamba's is 1).

This file is the family: its seeded weights (``spec``), its plain reference (``logits_at``) and what
its work requires from shapes alone (``*_bytes_per_decode_step``, ``attention_shape``,
``state_step_bytes``). It gives no ``loss_fn``: no train cell stands on it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.lowprec import DOTS

COMMON = ("norm_mixer", "norm_mlp", "w_gate", "w_up", "w_down")
ATTENTION = ("wq", "wk", "wv", "wo")
MAMBA = ("in_proj", "conv_w", "conv_b", "x_proj", "norm_dt", "norm_b", "norm_c", "dt_proj", "dt_bias_raw", "a_raw",
         "d_skip", "out_proj")
DT_MIN, DT_MAX = 0.001, 0.1  # Mamba's dt_min, dt_max


def name(layer: int, tensor: str) -> str:
    return f"L{layer:02d}.{tensor}"


def is_attention(cfg: dict, layer: int) -> bool:
    return layer % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def attention_layers(cfg: dict) -> int:
    return sum(is_attention(cfg, i) for i in range(cfg["num_hidden_layers"]))


def mamba_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - attention_layers(cfg)


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def attention_shape(cfg: dict) -> tuple:
    """Query heads, key/value heads and head size of an attention layer."""
    return cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)


def ssm_constants(dt_bias_raw, a_raw):
    """``(dt_bias [d_inner], A_log [d_state, d_inner])`` in float32 from the standard-normal draws of
    ``spec``: the one map from what ``weights.make`` can draw to where Mamba's initialiser puts them."""
    z = dt_bias_raw.astype(jnp.float32)
    share = 0.5 * (1.0 + jax.lax.erf(z / math.sqrt(2.0)))  # uniform in (0, 1)
    step = jnp.exp(math.log(DT_MIN) + share * (math.log(DT_MAX) - math.log(DT_MIN)))
    dt_bias = step + jnp.log(-jnp.expm1(-step))  # softplus^-1
    n = a_raw.shape[0]
    a_log = jnp.log(jnp.arange(1.0, n + 1.0))[:, None] - 0.5 * jnp.abs(a_raw.astype(jnp.float32))
    return dt_bias, a_log


def spec(cfg: dict) -> dict:
    hidden, vocab, ff = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    heads, kv_heads, hd = attention_shape(cfg)
    d_in, n, k, rank = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    normal, scale, raw = ("normal", cfg.get("initializer_range", 0.02)), ("one_plus", 0.1), ("normal", 1.0)
    out = {"embed": ((vocab, hidden), normal), "norm_final": ((hidden,), scale)}
    for i in range(cfg["num_hidden_layers"]):
        layer = {"norm_mixer": ((hidden,), scale), "norm_mlp": ((hidden,), scale), "w_gate": ((hidden, ff), normal),
                 "w_up": ((hidden, ff), normal), "w_down": ((ff, hidden), normal)}
        if is_attention(cfg, i):
            layer.update({"wq": ((hidden, heads * hd), normal), "wk": ((hidden, kv_heads * hd), normal),
                          "wv": ((hidden, kv_heads * hd), normal), "wo": ((heads * hd, hidden), normal)})
        else:
            layer.update({
                "in_proj": ((hidden, 2 * d_in), normal),
                # assumed (the configuration's file says so): Mamba draws the taps uniform in +-k**-0.5
                "conv_w": ((k, d_in), ("normal", cfg.get("mamba_conv_std", 0.3))), "conv_b": ((d_in,), normal),
                "x_proj": ((d_in, rank + 2 * n), normal),
                "norm_dt": ((rank,), scale), "norm_b": ((n,), scale), "norm_c": ((n,), scale),
                # assumed: Mamba draws dt_proj uniform in +-dt_rank**-0.5
                "dt_proj": ((rank, d_in), ("normal", cfg.get("mamba_dt_proj_std", 0.04))),
                "dt_bias_raw": ((d_in,), raw), "a_raw": ((n, d_in), raw), "d_skip": ((d_in,), scale),
                "out_proj": ((d_in, hidden), normal),
            })
        out.update({name(i, t): v for t, v in layer.items()})
    return out


# -- what the work requires, from shapes alone

def _mixer_params(cfg: dict, attention: bool) -> int:
    hidden = cfg["hidden_size"]
    if attention:
        heads, kv_heads, hd = attention_shape(cfg)
        return 2 * hidden * heads * hd + 2 * hidden * kv_heads * hd
    d_in, n, k, rank = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    return (hidden * 2 * d_in + (k + 1) * d_in + d_in * (rank + 2 * n) + rank + 2 * n + (rank + 1) * d_in
            + n * d_in + d_in + d_in * hidden)


def weight_bytes_per_decode_step(cfg: dict, slots: int, itemsize: int = 2) -> float:
    """Every layer's mixer, norms and MLP, the final norm, the embedding once as the head, and one
    embedding row a slot. Each read once."""
    hidden = cfg["hidden_size"]
    per_layer = 2 * hidden + 3 * hidden * cfg["intermediate_size"]
    params = (attention_layers(cfg) * _mixer_params(cfg, True) + mamba_layers(cfg) * _mixer_params(cfg, False)
              + cfg["num_hidden_layers"] * per_layer + hidden + hidden * cfg["vocab_size"])
    return float(itemsize) * (params + slots * hidden)


def state_step_bytes(cfg: dict, slots: float) -> float:
    """One call of the state-step kernel (one layer, one token a slot): ``h`` read and written in
    float32, a slot's ``u`` and ``y`` in the served type, ``delta`` in float32, ``B`` and ``C``; ``A``
    and ``D`` once. The algorithm's count: the kernel as built takes ``u`` and gives ``y`` in float32."""
    d_in, n = d_inner(cfg), cfg["mamba_d_state"]
    return slots * (2.0 * n * d_in * 4 + d_in * (2 + 4 + 2) + 2 * n * 2) + 4.0 * (n * d_in + d_in)


def conv_state_bytes(cfg: dict, slots: float, itemsize: int = 2) -> float:
    """The convolution's carried inputs of one layer, read and written."""
    return 2.0 * slots * (cfg["mamba_d_conv"] - 1) * d_inner(cfg) * itemsize


def cache_bytes_per_decode_step(cfg: dict, live_tokens: float, slots: float, itemsize: int = 2) -> float:
    """The attention layers' live keys and values once (and their queries and outputs), and every
    state-space layer's state: the step kernel's bytes and the convolution's carried inputs."""
    heads, kv_heads, hd = attention_shape(cfg)
    attention = float(itemsize) * (2.0 * live_tokens * kv_heads * hd + 2.0 * slots * heads * hd)
    return attention_layers(cfg) * attention + mamba_layers(cfg) * (state_step_bytes(cfg, slots) + conv_state_bytes(cfg, slots, itemsize))


# -- the plain reference

def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def attention(x, w, cfg: dict, dot):
    """Causal grouped-query attention over one sequence ``x`` [T, hidden], a head at a time, no rotary."""
    heads, kv_heads, hd = attention_shape(cfg)
    t = x.shape[0]
    q = dot(x, w["wq"]).reshape(t, heads, hd)
    k, v = dot(x, w["wk"]).reshape(t, kv_heads, hd), dot(x, w["wv"]).reshape(t, kv_heads, hd)
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def one_head(args):  # a head at a time bounds the score matrix
        q_h, k_h, v_h = args  # [T, hd] each
        scores = jnp.matmul(q_h, k_h.T, precision="highest") * hd ** -0.5
        return jnp.matmul(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v_h, precision="highest")

    group = jnp.arange(heads) // (heads // kv_heads)  # the key/value head of each query head
    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2)[group], v.transpose(1, 0, 2)[group]))
    return dot(ctx.transpose(1, 0, 2).reshape(t, heads * hd), w["wo"])


def mamba(x, w, cfg: dict, dot):
    """The selective state-space mixer over one sequence ``x`` [T, hidden], a token at a time from ``h = 0``."""
    d_in, n, k, rank = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    eps, t = cfg["rms_norm_eps"], x.shape[0]
    xz = dot(x, w["in_proj"])
    u, z = xz[:, :d_in], xz[:, d_in:]
    before = jnp.concatenate([jnp.zeros((k - 1, d_in), u.dtype), u])  # tap k - 1 meets the token itself
    u = jax.nn.silu(sum(before[j : j + t] * w["conv_w"][j] for j in range(k)) + w["conv_b"])
    dbc = dot(u, w["x_proj"])
    step = _rms_norm(dbc[:, :rank], w["norm_dt"], eps)
    b = _rms_norm(dbc[:, rank : rank + n], w["norm_b"], eps)
    c = _rms_norm(dbc[:, rank + n :], w["norm_c"], eps)
    dt_bias, a_log = ssm_constants(w["dt_bias_raw"], w["a_raw"])
    delta = jax.nn.softplus(dot(step, w["dt_proj"]) + dt_bias)  # [T, d_inner]
    a = -jnp.exp(a_log).T  # [d_inner, d_state]

    def one_token(h, inputs):
        delta_t, u_t, b_t, c_t = inputs
        h = jnp.exp(delta_t[:, None] * a) * h + (delta_t * u_t)[:, None] * b_t[None, :]
        return h, jnp.sum(h * c_t[None, :], axis=-1) + w["d_skip"] * u_t

    _, y = jax.lax.scan(one_token, jnp.zeros((d_in, n), jnp.float32), (delta, u, b, c))
    return dot(y * jax.nn.silu(z), w["out_proj"])


def layer(x, w, cfg: dict, dot, attends: bool):
    """One decoder layer over one sequence; ``w`` holds this layer's weights in the served type."""
    w = {n: v.astype(jnp.float32) for n, v in w.items()}
    mixer = attention if attends else mamba
    x = x + mixer(_rms_norm(x, w["norm_mixer"], cfg["rms_norm_eps"]), w, cfg, dot)
    h = _rms_norm(x, w["norm_mlp"], cfg["rms_norm_eps"])
    return x + dot(jax.nn.silu(dot(h, w["w_gate"])) * dot(h, w["w_up"]), w["w_down"])


_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "rms_norm_eps", "mamba_d_state", "mamba_d_conv",
         "mamba_expand", "mamba_dt_rank")


@functools.partial(jax.jit, static_argnames=("cfg_key", "dot_name", "attends"))
def _layer(x, w, cfg_key, dot_name, attends):
    return layer(x, w, dict(cfg_key), DOTS[dot_name], attends)


@functools.partial(jax.jit, static_argnames=("eps", "dot_name"))
def _head(x, rows, norm_final, embed, eps, dot_name):
    h = _rms_norm(x[rows], norm_final.astype(jnp.float32), eps)
    return DOTS[dot_name](h, embed.astype(jnp.float32).T)


def layer_weights(weights: dict, cfg: dict, i: int) -> dict:
    return {n: weights[name(i, n)] for n in COMMON + (ATTENTION if is_attention(cfg, i) else MAMBA)}


def logits_at(weights: dict, cfg: dict, tokens, rows, dot_name: str = "exact"):
    """Logits [len(rows), vocab] of one sequence of token ids at the positions ``rows``."""
    if cfg.get("num_experts", 1) != 1 or not cfg.get("tie_word_embeddings", False):
        raise NotImplementedError("the reference follows dense layers (num_experts 1) and a tied head")
    x = weights["embed"][tokens].astype(jnp.float32)
    cfg_key = tuple((k, cfg[k]) for k in _KEYS)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, layer_weights(weights, cfg, i), cfg_key, dot_name, is_attention(cfg, i))
    return _head(x, rows, weights["norm_final"], weights["embed"], cfg["rms_norm_eps"], dot_name)
