"""A decoder whose layers mix through a gated short convolution except those that attend, with routed
experts in every layer past the leading dense ones: LFM2-MoE (LiquidAI, LFM2-8B-A1B, 2025-10), as a
``config.json`` of ``model_type`` ``lfm2_moe`` states it (``layer_types``, ``conv_L_cache``,
``num_dense_layers``, ``num_experts``, ``use_expert_bias``). The family is named for its model type: a
sibling configuration needs no code.

Every layer: ``h = h + operator(RMSNorm(h))``, ``h = h + feed_forward(RMSNorm(h))`` (``operator_norm``,
``ffn_norm``, ``norm_eps``); a final RMSNorm (``embedding_norm``); the head is the embedding.

- *A ``"conv"`` layer*: ``[B, C, x] = split3(in_proj(u))``, in that order, each ``hidden_size`` wide; ``y =
  C * conv1d(B * x)``, a causal depthwise convolution of ``conv_L_cache`` taps (tap ``L - 1`` meets the
  token itself) with no bias (``conv_bias`` false); ``out_proj(y)``. No activation, no gate besides ``B``
  and ``C``. Here: ``L`` shifted copies of ``B * x`` times their taps, from zeros before the sequence; no
  carried state, no cache.
- *A ``"full_attention"`` layer*: grouped-query attention, ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``hidden_size / num_attention_heads``, no bias; an RMSNorm with
  a learned scale over each head's values on q and on k (``q_layernorm``, ``k_layernorm``) before rotary;
  rotary turns the halves ``(j, j + D/2)`` by ``position * rope_theta**(-2j/D)`` (the published
  ``rotate_half``); scale ``D ** -0.5``; causal. A head at a time over the whole sequence.
- *The first ``num_dense_layers`` layers*: a SwiGLU of ``intermediate_size``.
- *Every later layer*: ``s = sigmoid(x @ gate)`` in float32; a token's ``num_experts_per_tok`` experts are
  the top of ``s + expert_bias`` (``use_expert_bias``: the bias chooses and does not weigh); their weights
  are ``s`` there, divided by ``sum + 1e-6`` (``norm_topk_prob``; the constant is the published code's),
  times ``routed_scaling_factor``; ``y = sum_i w_i E_i(x)``, every expert a SwiGLU of
  ``moe_intermediate_size``, no shared expert. A loop over the experts, each over every token, under the
  weight of who chose it (0 for the others): no token is dropped and nothing is sorted.

Float32 at ``highest``, one layer at a time and one expert at a time, so that a 2,304-token sequence fits
beside 10.8 GB of served weights. The control's arithmetic (``dot_name``) replaces the matrix products
(projections, experts, MLP, head); the router, the convolution and the attention products stay float32.

Departures of the program from this file, none in the mathematics: the program's core turns adjacent
pairs ``(2i, 2i + 1)`` in its rotary embedding, so its builder re-pairs the columns of ``wq`` / ``wk`` and
the two norms' scales as an importer of checkpoints does (``builders/lfm2_moe_serve.py`` ``re_paired``);
the program's routing adds ``1e-20`` to the sum where this file adds the published ``1e-6`` (5e-7 of a
weight, under bfloat16's step and under every limit here). Weights come in the benchmark's names
(``spec``), one tensor a layer, in the served type, and are widened to float32 where used (an expert at
a time).

This file is the family: its seeded weights (``spec``), its plain reference (``logits_at``) and what its
work requires from shapes alone (``*_bytes_per_decode_step``, ``attention_shape``, the expert products'
bytes and operations). It gives no ``loss_fn``: no train cell stands on it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.lowprec import DOTS

COMMON = ("norm_operator", "norm_ffn")
CONV = ("in_proj", "conv_w", "out_proj")
ATTENTION = ("wq", "wk", "wv", "wo", "norm_q", "norm_k")
DENSE = ("w_gate", "w_up", "w_down")
ROUTED = ("router", "router_bias", "experts_gate", "experts_up", "experts_down")
NORM_TOPK_EPS = 1e-6  # the published normaliser: weights / (sum + 1e-6)


def name(layer: int, tensor: str) -> str:
    return f"L{layer:02d}.{tensor}"


def is_attention(cfg: dict, layer: int) -> bool:
    kind = cfg["layer_types"][layer]
    if kind not in ("conv", "full_attention"):
        raise NotImplementedError(f"layer_types[{layer}] = {kind!r}: the family has conv and full_attention layers")
    return kind == "full_attention"


def is_routed(cfg: dict, layer: int) -> bool:
    return layer >= cfg["num_dense_layers"]


def attention_layers(cfg: dict) -> int:
    return sum(is_attention(cfg, i) for i in range(cfg["num_hidden_layers"]))


def conv_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - attention_layers(cfg)


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - min(cfg["num_dense_layers"], cfg["num_hidden_layers"])


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def attention_shape(cfg: dict) -> tuple:
    """Query heads, key/value heads and head size of an attention layer."""
    return cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)


def spec(cfg: dict) -> dict:
    hidden, vocab = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv_heads, hd = attention_shape(cfg)
    experts, ff, taps = cfg["num_experts"], cfg["moe_intermediate_size"], cfg["conv_L_cache"]
    if cfg.get("conv_bias", False):
        raise NotImplementedError("the reference follows conv_bias false")
    normal, scale = ("normal", cfg.get("initializer_range", 0.02)), ("one_plus", 0.1)
    out = {"embed": ((vocab, hidden), normal), "norm_final": ((hidden,), scale)}
    for i in range(cfg["num_hidden_layers"]):
        layer = {"norm_operator": ((hidden,), scale), "norm_ffn": ((hidden,), scale)}
        if is_attention(cfg, i):
            layer.update({"wq": ((hidden, heads * hd), normal), "wk": ((hidden, kv_heads * hd), normal),
                          "wv": ((hidden, kv_heads * hd), normal), "wo": ((heads * hd, hidden), normal),
                          "norm_q": ((hd,), scale), "norm_k": ((hd,), scale)})
        else:
            layer.update({"in_proj": ((hidden, 3 * hidden), normal),
                          # assumed (the configuration's file says so): the taps' scale
                          "conv_w": ((taps, hidden), ("normal", cfg.get("conv_std", 0.3))),
                          "out_proj": ((hidden, hidden), normal)})
        if is_routed(cfg, i):
            layer.update({
                # assumed (the configuration's file gives the rule): logits of unit spread over a normed input,
                # and a learned bias whose size no config states
                "router": ((hidden, experts), ("normal", cfg.get("router_std", hidden ** -0.5))),
                "router_bias": ((experts,), ("normal", cfg.get("expert_bias_std", 0.0))),
                "experts_gate": ((experts, hidden, ff), normal), "experts_up": ((experts, hidden, ff), normal),
                "experts_down": ((experts, ff, hidden), normal),
            })
        else:
            width = cfg["intermediate_size"]
            layer.update({"w_gate": ((hidden, width), normal), "w_up": ((hidden, width), normal), "w_down": ((width, hidden), normal)})
        out.update({name(i, t): v for t, v in layer.items()})
    return out


# -- what the work requires, from shapes alone

def _operator_params(cfg: dict, attention: bool) -> int:
    hidden = cfg["hidden_size"]
    if attention:
        heads, kv_heads, hd = attention_shape(cfg)
        return 2 * hidden * heads * hd + 2 * hidden * kv_heads * hd + 2 * hd
    return hidden * 3 * hidden + cfg["conv_L_cache"] * hidden + hidden * hidden


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expected_experts_touched(cfg: dict, tokens: float) -> float:
    """Distinct experts that ``tokens`` tokens reach in one layer if each picks its ``k`` of ``E`` evenly."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def weight_bytes_per_decode_step(cfg: dict, slots: int, itemsize: int = 2) -> float:
    """Every layer's operator and norms; the leading layers' MLP; each later layer's router and the routed
    experts that ``slots`` tokens are expected to reach; the final norm, the embedding once as the head, and
    one embedding row a slot. Each read once."""
    hidden, routed = cfg["hidden_size"], expert_layers(cfg)
    params = (attention_layers(cfg) * _operator_params(cfg, True) + conv_layers(cfg) * _operator_params(cfg, False)
              + cfg["num_hidden_layers"] * 2 * hidden
              + (cfg["num_hidden_layers"] - routed) * 3 * hidden * cfg["intermediate_size"]
              + routed * (hidden * cfg["num_experts"] + cfg["num_experts"] + expected_experts_touched(cfg, slots) * expert_params(cfg))
              + hidden + hidden * cfg["vocab_size"])
    return float(itemsize) * (params + slots * hidden)


def conv_state_bytes(cfg: dict, slots: float, itemsize: int = 2) -> float:
    """The convolution's carried inputs of one layer (``conv_L_cache - 1`` rows of ``B * x`` a slot), read and written."""
    return 2.0 * slots * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * itemsize


def cache_bytes_per_decode_step(cfg: dict, live_tokens: float, slots: float, itemsize: int = 2) -> float:
    """The attention layers' live keys and values once (and their queries and outputs), and every
    convolution layer's carried inputs read and written."""
    heads, kv_heads, hd = attention_shape(cfg)
    attention = float(itemsize) * (2.0 * live_tokens * kv_heads * hd + 2.0 * slots * heads * hd)
    return attention_layers(cfg) * attention + conv_layers(cfg) * conv_state_bytes(cfg, slots, itemsize)


def expert_products_bytes(cfg: dict, experts_touched: float, pairs: float, itemsize: int = 2) -> float:
    """The three grouped products of the routed experts over ``pairs`` token-expert pairs that reach
    ``experts_touched`` experts (summed over layers): those experts' weights once, each pair's input
    and output (``hidden``) and its two intermediates (``moe_intermediate_size``, written and read)."""
    return float(itemsize) * (experts_touched * expert_params(cfg)
                              + pairs * (2 * cfg["hidden_size"] + 4 * cfg["moe_intermediate_size"]))


def expert_products_flops(cfg: dict, pairs: float) -> float:
    return 2.0 * pairs * expert_params(cfg)


# -- the plain reference

def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotate_half(x, positions, theta):
    """x [T, heads, D]; the pair ``(j, j + D/2)`` turned by ``positions * theta**(-2j/D)``."""
    d = x.shape[-1]
    angles = positions[:, None].astype(jnp.float32) * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(x, w, cfg: dict, dot):
    """Causal grouped-query attention over one sequence ``x`` [T, hidden], a head at a time."""
    heads, kv_heads, hd = attention_shape(cfg)
    t, eps = x.shape[0], cfg["norm_eps"]
    pos = jnp.arange(t)
    q = _rotate_half(_rms_norm(dot(x, w["wq"]).reshape(t, heads, hd), w["norm_q"], eps), pos, cfg["rope_theta"])
    k = _rotate_half(_rms_norm(dot(x, w["wk"]).reshape(t, kv_heads, hd), w["norm_k"], eps), pos, cfg["rope_theta"])
    v = dot(x, w["wv"]).reshape(t, kv_heads, hd)
    seen = pos[None, :] <= pos[:, None]

    def one_head(args):  # a head at a time bounds the score matrix
        q_h, k_h, v_h = args  # [T, hd] each
        scores = jnp.matmul(q_h, k_h.T, precision="highest") * hd ** -0.5
        return jnp.matmul(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v_h, precision="highest")

    group = jnp.arange(heads) // (heads // kv_heads)  # the key/value head of each query head
    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2)[group], v.transpose(1, 0, 2)[group]))
    return dot(ctx.transpose(1, 0, 2).reshape(t, heads * hd), w["wo"])


def short_conv(x, w, cfg: dict, dot):
    """The gated short convolution over one sequence ``x`` [T, hidden], from zeros before it."""
    hidden, taps, t = cfg["hidden_size"], cfg["conv_L_cache"], x.shape[0]
    bcx = dot(x, w["in_proj"])
    b, c, u = bcx[:, :hidden], bcx[:, hidden : 2 * hidden], bcx[:, 2 * hidden :]
    before = jnp.concatenate([jnp.zeros((taps - 1, hidden), x.dtype), b * u])  # tap L - 1 meets the token itself
    return dot(c * sum(before[j : j + t] * w["conv_w"][j] for j in range(taps)), w["out_proj"])


def _swiglu(h, gate, up, down, dot):
    return dot(jax.nn.silu(dot(h, gate)) * dot(h, up), down)


def routing(h, w, cfg: dict):
    """``[T, E]`` float32: a token's weight for each expert, 0 where it did not choose it."""
    scores = jax.nn.sigmoid(jnp.matmul(h, w["router"], precision="highest"))
    choose = scores + w["router_bias"] if cfg.get("use_expert_bias", True) else scores
    _, chosen = jax.lax.top_k(choose, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        picked = picked / (picked.sum(axis=-1, keepdims=True) + NORM_TOPK_EPS)
    picked = picked * cfg["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], chosen].set(picked)


def routed_ffn(h, w, cfg: dict, dot):
    """Every expert over every token, weighted by who chose it."""
    weights = routing(h, w, cfg)

    def one_expert(y, e):
        take = lambda n: jax.lax.dynamic_index_in_dim(w[n], e, 0, keepdims=False).astype(jnp.float32)  # noqa: E731
        out = _swiglu(h, take("experts_gate"), take("experts_up"), take("experts_down"), dot)
        return y + jax.lax.dynamic_index_in_dim(weights, e, 1, keepdims=True) * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(cfg["num_experts"]))
    return y


def layer(x, w, cfg: dict, dot, attends: bool, routed: bool):
    """One decoder layer over one sequence; ``w`` holds this layer's weights (the experts in the served type)."""
    f32 = {n: (v if n.startswith("experts_") else v.astype(jnp.float32)) for n, v in w.items()}
    operator = attention if attends else short_conv
    x = x + operator(_rms_norm(x, f32["norm_operator"], cfg["norm_eps"]), f32, cfg, dot)
    h = _rms_norm(x, f32["norm_ffn"], cfg["norm_eps"])
    if routed:
        return x + routed_ffn(h, f32, cfg, dot)
    return x + _swiglu(h, f32["w_gate"], f32["w_up"], f32["w_down"], dot)


_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "norm_eps", "rope_theta", "conv_L_cache",
         "num_experts", "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor", "use_expert_bias")


@functools.partial(jax.jit, static_argnames=("cfg_key", "dot_name", "attends", "routed"))
def _layer(x, w, cfg_key, dot_name, attends, routed):
    return layer(x, w, dict(cfg_key), DOTS[dot_name], attends, routed)


@functools.partial(jax.jit, static_argnames=("eps", "dot_name"))
def _head(x, rows, norm_final, embed, eps, dot_name):
    h = _rms_norm(x[rows], norm_final.astype(jnp.float32), eps)
    return DOTS[dot_name](h, embed.astype(jnp.float32).T)


def layer_weights(weights: dict, cfg: dict, i: int) -> dict:
    names = COMMON + (ATTENTION if is_attention(cfg, i) else CONV) + (ROUTED if is_routed(cfg, i) else DENSE)
    return {n: weights[name(i, n)] for n in names}


def logits_at(weights: dict, cfg: dict, tokens, rows, dot_name: str = "exact"):
    """Logits [len(rows), vocab] of one sequence of token ids at the positions ``rows``."""
    x = weights["embed"][tokens].astype(jnp.float32)
    cfg_key = tuple((k, cfg[k]) for k in _KEYS if k in cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, layer_weights(weights, cfg, i), cfg_key, dot_name, is_attention(cfg, i), is_routed(cfg, i))
    return _head(x, rows, weights["norm_final"], weights["embed"], cfg["norm_eps"], dot_name)
