"""A decoder whose attention layers are of two kinds that differ in more than the band, with routed experts in
every layer but the first: Laguna (poolside, Laguna-XS.2, 33.4B-A3B), as a ``config.json`` of ``model_type``
``laguna`` states it (``layer_types``, ``num_attention_heads_per_layer``, ``rope_parameters``, ``gating``,
``mlp_layer_types``). The family is named for its model type: a sibling configuration needs no code.

Layer ``l`` over one sequence ``x`` ``[T, hidden]`` (no bias anywhere; ``eps`` ``rms_norm_eps``):

1. ``h = RMSNorm(x)``. ``H = num_attention_heads_per_layer[l]`` query heads (48 on a ``full_attention`` layer, 64 on a
   ``sliding_attention`` one) over ``num_key_value_heads`` key/value heads of ``head_dim``: ``q = h W_q``, ``k = h W_k``,
   ``v = h W_v``; ``q`` and ``k`` each through an RMSNorm over the head's values with a learned scale (*assumed*).
2. Rotary by ``rope_parameters[layer_types[l]]``: the first ``partial_rotary_factor`` of each head's values is
   turned, the rest left as it is. ``rope_type`` ``default``: angles ``position * rope_theta ** (-2j / d)`` over the
   turned width ``d``. ``yarn``: the frequencies of the turned width blended between ``1 / f`` and ``1 / (factor f)`` by
   the ramp between the dimensions that turn ``beta_fast`` and ``beta_slow`` times in
   ``original_max_position_embeddings`` positions (floor and ceiling taken), cosines and sines times
   ``attention_factor``.
3. ``a = softmax(q k^T / sqrt(head_dim) + mask) v``, grouped (``H / num_key_value_heads`` query heads a key/value
   head), causal; on a ``sliding_attention`` layer query ``i`` sees keys ``i - sliding_window + 1 .. i``. The band is a
   mask over the whole sequence: no cache, no ring, no page.
4. ``gating``: ``g = h W_g`` ``[T, H]``; head ``j`` of ``a`` times ``softplus(g_j)`` (*assumed*: one scalar a head,
   ``softplus``, from the normed input).
5. ``y = x + concat(a) W_o``; ``z = RMSNorm(y)``.
6. ``mlp_layer_types[l]`` ``dense``: a SwiGLU of ``intermediate_size``. ``sparse``: ``s = sigmoid(z W_r)`` in float32
   over the router's experts; a token's ``num_experts_per_tok`` experts are the top of ``s + b`` (the bias chooses
   and does not weigh); their ``s``, divided by their sum and times ``moe_routed_scaling_factor``, weigh their
   SwiGLU outputs (``moe_apply_router_weight_on_input`` false); one shared SwiGLU of
   ``shared_expert_intermediate_size`` is added for every token (*assumed*: sigmoid, bias, normalisation).
7. ``x' = y + ffn(z)``; after the last layer an RMSNorm and the untied head.

**A share of the experts.** ``num_experts`` counts the experts held here, ``router_experts`` the router's columns
(``expert_shares`` x ``num_experts``), ``expert_share`` which share: a token-expert pair whose expert is held
elsewhere adds nothing here, in this file as in the program (no exchange, nothing stands in for it). The shares'
routed parts and the shared expert counted once add up to the uncut layer.

Rotary turns adjacent pairs ``(2i, 2i + 1)`` (Su et al. 2021, eq. 34), as the program's core does; the published
code turns halves, and an importer of checkpoints re-pairs the turned columns. With seeded weights nothing
depends on which. Float32 at ``highest``, a layer at a time, a head at a time and an expert at a time, so that a
5,120-token sequence fits beside 6.8 GB of served weights. The control's arithmetic (``dot_name``) replaces the
matrix products (projections, gate, experts, MLP, head); the router, the norms and the attention products stay
float32.

This file is the family: its seeded weights (``spec``), its plain reference (``logits_at``) and what its work
requires from shapes alone (``attention_shape``, the byte counts the per-layer readers ask).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from chipbench.reference.lowprec import DOTS

NORM_TOPK_EPS = 1e-20  # the normaliser's guard: weights / (sum + 1e-20)
FULL, WINDOW = "full_attention", "sliding_attention"


def name(layer: int, tensor: str) -> str:
    return f"L{layer:02d}.{tensor}"


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def layer_type(cfg: dict, i: int) -> str:
    kind = cfg["layer_types"][i]  # the lists may be the published 40 entries: the first num_hidden_layers count
    if kind not in (FULL, WINDOW):
        raise NotImplementedError(f"layer_types[{i}] = {kind!r}: the family has full_attention and sliding_attention layers")
    return kind


def is_sparse(cfg: dict, i: int) -> bool:
    return cfg["mlp_layer_types"][i] == "sparse"


def layers_of(cfg: dict, kind: str) -> int:
    return sum(layer_type(cfg, i) == kind for i in range(layers(cfg)))


def expert_layers(cfg: dict) -> int:
    return sum(is_sparse(cfg, i) for i in range(layers(cfg)))


def heads_of(cfg: dict, i: int) -> int:
    return cfg["num_attention_heads_per_layer"][i]


def attention_shape(cfg: dict) -> tuple:
    """Query heads (a FULL layer's: ``num_attention_heads``), key/value heads and head size."""
    return cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]


def held_experts(cfg: dict) -> tuple:
    """``(first, held, router's)``: the experts this configuration holds, among the router's columns."""
    held, shares = cfg["num_experts"], cfg.get("expert_shares", 1)
    total = cfg.get("router_experts", held * shares)
    if held * shares != total or not 0 <= cfg.get("expert_share", 0) < shares:
        raise ValueError(f"{held} experts held in share {cfg.get('expert_share', 0)} of {shares} of the router's {total}")
    return cfg.get("expert_share", 0) * held, held, total


def spec(cfg: dict) -> dict:
    hidden, vocab, kv_heads, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["num_key_value_heads"], cfg["head_dim"]
    ff, shared = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    _, held, total = held_experts(cfg)
    if cfg.get("tie_word_embeddings", False) or cfg.get("attention_bias", False) or not cfg.get("gating", True):
        raise NotImplementedError("the reference follows an untied head, projections without a bias and a gate a head")
    normal, scale = ("normal", cfg.get("initializer_range", 0.02)), ("one_plus", 0.1)
    out = {"embed": ((vocab, hidden), normal), "norm_final": ((hidden,), scale), "lm_head": ((hidden, vocab), normal)}
    for i in range(layers(cfg)):
        heads = heads_of(cfg, i)
        layer = {
            "norm_attn": ((hidden,), scale), "norm_ffn": ((hidden,), scale),
            "wq": ((hidden, heads * hd), normal), "wk": ((hidden, kv_heads * hd), normal),
            "wv": ((hidden, kv_heads * hd), normal), "wo": ((heads * hd, hidden), normal),
            "norm_q": ((hd,), scale), "norm_k": ((hd,), scale),
            # assumed (the configuration's file gives the rule): gates that spread over about 0.3-2
            "wg": ((hidden, heads), ("normal", cfg.get("gate_std", 0.017))),
        }
        if is_sparse(cfg, i):
            layer.update({
                # assumed (the configuration's file gives the rule): logits of unit spread over a normed input,
                # and a learned bias whose size no config states
                "router": ((hidden, total), ("normal", cfg.get("router_std", hidden ** -0.5))),
                "router_bias": ((total,), ("normal", cfg.get("expert_bias_std", 0.0))),
                "experts_gate": ((held, hidden, ff), normal), "experts_up": ((held, hidden, ff), normal),
                "experts_down": ((held, ff, hidden), normal),
                "shared_gate": ((hidden, shared), normal), "shared_up": ((hidden, shared), normal),
                "shared_down": ((shared, hidden), normal),
            })
        else:
            width = cfg["intermediate_size"]
            layer.update({"w_gate": ((hidden, width), normal), "w_up": ((hidden, width), normal), "w_down": ((width, hidden), normal)})
        out.update({name(i, t): v for t, v in layer.items()})
    return out


# -- what the work requires, from shapes alone

def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_params(cfg: dict, i: int) -> int:
    """Layer ``i``'s attention block: q, k, v, o, the gate, the two head norms."""
    hidden, kv, hd, heads = cfg["hidden_size"], cfg["num_key_value_heads"], cfg["head_dim"], heads_of(cfg, i)
    return 2 * hidden * heads * hd + 2 * hidden * kv * hd + hidden * heads + 2 * hd


def params_outside_experts(cfg: dict) -> int:
    """Everything a decode step reads whatever it routes: every layer's attention block and norms, the dense
    layers' MLP, each sparse layer's router, bias and shared expert, the final norm and the head."""
    hidden = cfg["hidden_size"]
    _, _, total = held_experts(cfg)
    out = hidden + hidden * cfg["vocab_size"]
    for i in range(layers(cfg)):
        out += attention_params(cfg, i) + 2 * hidden
        if is_sparse(cfg, i):
            out += hidden * total + total + 3 * hidden * cfg["shared_expert_intermediate_size"]
        else:
            out += 3 * hidden * cfg["intermediate_size"]
    return out


def params(cfg: dict) -> int:
    _, held, _ = held_experts(cfg)
    return params_outside_experts(cfg) + cfg["hidden_size"] * cfg["vocab_size"] + expert_layers(cfg) * held * expert_params(cfg)


def weight_bytes_per_decode_step(cfg: dict, slots: float, experts_touched: float = None, itemsize: int = 2) -> float:
    """The weights outside the experts once, one embedding row a slot, and the held experts a step touches: the
    program's own count (``experts_touched`` of one step, summed over layers) where given, else every held one."""
    _, held, _ = held_experts(cfg)
    touched = expert_layers(cfg) * held if experts_touched is None else experts_touched
    return float(itemsize) * (params_outside_experts(cfg) + slots * cfg["hidden_size"] + touched * expert_params(cfg))


def attention_bytes(cfg: dict, kind: str, rows: float, steps: float, itemsize: int = 2) -> float:
    """What the layers of ``kind`` move through the paged kernel for ``steps`` slot-steps that read ``rows`` rows a
    layer in all: keys and values of every row once, and each slot-step's queries and outputs at that kind's heads."""
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    per_layer = [2.0 * rows * kv * hd + 2.0 * steps * heads_of(cfg, i) * hd for i in range(layers(cfg)) if layer_type(cfg, i) == kind]
    return float(itemsize) * sum(per_layer)


def cache_bytes_per_decode_step(cfg: dict, context_rows: float, window_rows: float, steps: float, itemsize: int = 2) -> float:
    """Both kinds: the full layers read the contexts (``context_rows``), the window layers the band (``window_rows``)."""
    return attention_bytes(cfg, FULL, context_rows, steps, itemsize) + attention_bytes(cfg, WINDOW, window_rows, steps, itemsize)


def page_bytes(cfg: dict, kind: str, block: int, itemsize: int = 2) -> int:
    """One page of the pools of the layers of ``kind``: ``block`` rows of keys and values in each of them."""
    return layers_of(cfg, kind) * block * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def expert_products_bytes(cfg: dict, experts_touched: float, pairs: float, itemsize: int = 2) -> float:
    """The grouped products of the held routed experts: the weights of the ``experts_touched`` that got a pair
    (summed over layers), and the activations of the pairs that reach a held expert: of ``pairs`` routed over all
    the router's experts, the held share (``num_experts / router_experts``); each such pair's input and output
    (``hidden``) and its two intermediates (``moe_intermediate_size``, written and read)."""
    _, held, total = held_experts(cfg)
    return float(itemsize) * (experts_touched * expert_params(cfg)
                              + pairs * held / total * (2 * cfg["hidden_size"] + 4 * cfg["moe_intermediate_size"]))


def expert_products_flops(cfg: dict, pairs: float) -> float:
    _, held, total = held_experts(cfg)
    return 2.0 * pairs * held / total * expert_params(cfg)


# -- the plain reference

class Rule(NamedTuple):
    """What a layer's place decides: its query heads, its band (None: none), its rotary rule (the items of
    ``rope_parameters[layer_types[l]]``, sorted) and its gate's activation."""

    heads: int
    window: object
    rotary: tuple
    gate: Callable


def rule_of(cfg: dict, i: int) -> Rule:
    kind = layer_type(cfg, i)
    return Rule(heads_of(cfg, i), cfg["sliding_window"] if kind == WINDOW else None,
                tuple(sorted(cfg["rope_parameters"][kind].items())), jax.nn.softplus)


def rotary_frequencies(rotary: dict, width: int):
    """``(inverse frequencies [width / 2], factor on cosines and sines)`` of a rule over ``width`` turned values."""
    theta = float(rotary["rope_theta"])
    freqs = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    if rotary.get("rope_type", "default") == "default":
        return freqs, 1.0
    if rotary["rope_type"] != "yarn":
        raise NotImplementedError(f"rope_type {rotary['rope_type']!r}")
    factor, orig = float(rotary["factor"]), float(rotary["original_max_position_embeddings"])

    def turns_at(rotations):  # the dimension whose wavelength fits ``rotations`` times into the original context
        return width * math.log(orig / (rotations * 2.0 * math.pi)) / (2.0 * math.log(theta))

    low = max(math.floor(turns_at(float(rotary["beta_fast"]))), 0)
    high = min(math.ceil(turns_at(float(rotary["beta_slow"]))), width - 1)
    ramp = jnp.clip((jnp.arange(width // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp), float(rotary["attention_factor"])


def rotate(x, positions, rotary: dict):
    """``x`` ``[T, heads, D]``: the first ``partial_rotary_factor`` of ``D``, in adjacent pairs, turned by the rule."""
    width = int(x.shape[-1] * float(rotary.get("partial_rotary_factor", 1.0)))
    freqs, by = rotary_frequencies(rotary, width)
    angles = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = (jnp.cos(angles) * by)[:, None, :], (jnp.sin(angles) * by)[:, None, :]
    a, b = x[..., 0:width:2], x[..., 1:width:2]
    turned = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(*x.shape[:-1], width)
    return jnp.concatenate([turned, x[..., width:]], axis=-1)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def attention(h, w, cfg: dict, dot, rule: Rule):
    """Causal grouped-query attention over one sequence ``h`` [T, hidden], a head at a time, gated a head."""
    kv_heads, hd, heads, eps = cfg["num_key_value_heads"], cfg["head_dim"], rule.heads, cfg["rms_norm_eps"]
    t = h.shape[0]
    pos, rotary = jnp.arange(t), dict(rule.rotary)
    q = rotate(_rms_norm(dot(h, w["wq"]).reshape(t, heads, hd), w["norm_q"], eps), pos, rotary)
    k = rotate(_rms_norm(dot(h, w["wk"]).reshape(t, kv_heads, hd), w["norm_k"], eps), pos, rotary)
    v = dot(h, w["wv"]).reshape(t, kv_heads, hd)
    seen = pos[None, :] <= pos[:, None]
    if rule.window is not None:
        seen &= pos[None, :] > pos[:, None] - rule.window

    def one_head(args):  # a head at a time bounds the score matrix
        q_h, k_h, v_h = args  # [T, hd] each
        scores = jnp.matmul(q_h, k_h.T, precision="highest") * hd ** -0.5
        return jnp.matmul(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v_h, precision="highest")

    group = jnp.arange(heads) // (heads // kv_heads)  # the key/value head of each query head
    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2)[group], v.transpose(1, 0, 2)[group]))
    gated = ctx.transpose(1, 0, 2) * rule.gate(dot(h, w["wg"]))[:, :, None]
    return dot(gated.reshape(t, heads * hd), w["wo"])


def _swiglu(h, gate, up, down, dot):
    return dot(jax.nn.silu(dot(h, gate)) * dot(h, up), down)


def routing(h, w, cfg: dict):
    """``[T, router's experts]`` float32: a token's weight for each expert, 0 where it did not choose it."""
    scores = jax.nn.sigmoid(jnp.matmul(h, w["router"], precision="highest"))
    _, chosen = jax.lax.top_k(scores + w["router_bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (picked.sum(axis=-1, keepdims=True) + NORM_TOPK_EPS) * cfg["moe_routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], chosen].set(picked)


def routed_ffn(h, w, cfg: dict, dot):
    """Every held expert over every token, weighted by who chose it."""
    first, held, _ = held_experts(cfg)
    weights = routing(h, w, cfg)

    def one_expert(y, e):
        take = lambda n: jax.lax.dynamic_index_in_dim(w[n], e, 0, keepdims=False).astype(jnp.float32)  # noqa: E731
        out = _swiglu(h, take("experts_gate"), take("experts_up"), take("experts_down"), dot)
        return y + jax.lax.dynamic_index_in_dim(weights, first + e, 1, keepdims=True) * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(held))
    return y


def layer(x, w, cfg: dict, dot, rule: Rule, sparse: bool):
    """One decoder layer over one sequence; ``w`` holds this layer's weights (the experts in the served type)."""
    f32 = {n: (v if n.startswith("experts_") else v.astype(jnp.float32)) for n, v in w.items()}
    eps = cfg["rms_norm_eps"]
    x = x + attention(_rms_norm(x, f32["norm_attn"], eps), f32, cfg, dot, rule)
    z = _rms_norm(x, f32["norm_ffn"], eps)
    if sparse:
        return x + routed_ffn(z, f32, cfg, dot) + _swiglu(z, f32["shared_gate"], f32["shared_up"], f32["shared_down"], dot)
    return x + _swiglu(z, f32["w_gate"], f32["w_up"], f32["w_down"], dot)


_KEYS = ("hidden_size", "num_key_value_heads", "head_dim", "rms_norm_eps", "num_experts", "router_experts", "expert_share",
         "expert_shares", "num_experts_per_tok", "moe_routed_scaling_factor")


@functools.partial(jax.jit, static_argnames=("cfg_key", "dot_name", "rule", "sparse"))
def _layer(x, w, cfg_key, dot_name, rule, sparse):
    return layer(x, w, dict(cfg_key), DOTS[dot_name], rule, sparse)


@functools.partial(jax.jit, static_argnames=("eps", "dot_name"))
def _head(x, rows, norm_final, lm_head, eps, dot_name):
    return DOTS[dot_name](_rms_norm(x[rows], norm_final.astype(jnp.float32), eps), lm_head.astype(jnp.float32))


_COMMON = ("norm_attn", "norm_ffn", "wq", "wk", "wv", "wo", "wg", "norm_q", "norm_k")
_DENSE = ("w_gate", "w_up", "w_down")
_SPARSE = ("router", "router_bias", "experts_gate", "experts_up", "experts_down", "shared_gate", "shared_up", "shared_down")


def layer_weights(weights: dict, cfg: dict, i: int) -> dict:
    return {n: weights[name(i, n)] for n in _COMMON + (_SPARSE if is_sparse(cfg, i) else _DENSE)}


def logits_at(weights: dict, cfg: dict, tokens, rows, dot_name: str = "exact", rule_of=rule_of):
    """Logits [len(rows), vocab] of one sequence of token ids at the positions ``rows``. ``rule_of(cfg, i)``: what
    layer ``i``'s place decides (the tests put wrong rules there)."""
    x = weights["embed"][tokens].astype(jnp.float32)
    cfg_key = tuple((k, cfg[k]) for k in _KEYS if k in cfg)
    for i in range(layers(cfg)):
        x = _layer(x, layer_weights(weights, cfg, i), cfg_key, dot_name, rule_of(cfg, i), is_sparse(cfg, i))
    return _head(x, rows, weights["norm_final"], weights["lm_head"], cfg["rms_norm_eps"], dot_name)
