"""A decoder whose layers mix through a Mamba-2 state-space recurrence (arXiv:2405.21060) except those
that attend, with routed experts and a shared one in every layer and Granite's four multipliers:
Granite 4.0-H (ibm-granite, granite-4.0-h-small, 2025-10), as a ``config.json`` of ``model_type``
``granitemoehybrid`` states it (``layer_types``, ``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
``num_local_experts``, ``shared_intermediate_size``, ``embedding_multiplier``, ``residual_multiplier``,
``attention_multiplier``, ``logits_scaling``). The family is named for its mechanisms: a sibling
configuration needs no code.

Model: ``x = embedding_multiplier * E[token]``; the layers; ``logits = RMSNorm(x) E^T / logits_scaling``
(the head is the embedding). Every layer: ``x = x + residual_multiplier * mixer(RMSNorm(x))``, then with
``u = RMSNorm(x)``, ``x = x + residual_multiplier * (routed(u) + shared(u))``.

- *An ``"attention"`` layer*: grouped-query attention, ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``hidden_size / num_attention_heads``, no bias, causal, **no
  position encoding** (``position_embedding_type`` ``"nope"``) and the score scale ``attention_multiplier``
  itself (1/128 at head size 128, not ``128 ** -0.5``). A head at a time over the whole sequence.
- *A ``"mamba"`` layer* (``d_inner = mamba_n_heads * mamba_d_head = mamba_expand * hidden_size``, ``N =
  mamba_d_state``, one group): ``[z | xBC | dt] = x W_in`` (``d_inner``, ``d_inner + 2 N``, ``mamba_n_heads``
  wide, no bias); ``xBC = silu(conv1d(xBC))``, a causal depthwise convolution of ``mamba_d_conv`` taps (tap
  ``K - 1`` meets the token itself) with a bias; ``[x' | B | C] = xBC``, ``x'`` as ``[heads, d_head]``; ``delta
  = softplus(dt + dt_bias)`` a head, no clamp; ``A = -exp(A_log)`` a head; ``h_t[n, p, s] = exp(delta_t[n]
  A[n]) h_{t-1}[n, p, s] + delta_t[n] x'_t[n, p] B_t[s]`` from ``h = 0``; ``y_t[n, p] = sum_s h_t[n, p, s]
  C_t[s] + D[n] x'_t[n, p]``; ``y = RMSNorm_w(y * silu(z))`` over all of ``d_inner`` (the gate before the
  norm); ``y W_out``. Here: a ``lax.scan`` a token over ``h`` ``[heads, d_head, N]``: no chunks, no carried
  state between windows, no cache, no kernel.
- *The feed-forward of every layer*: ``logits = u W_r`` in float32 over ALL the router's experts
  (``router_experts``: the published ``num_local_experts``); a token's ``num_experts_per_tok`` experts are
  its largest raw logits, their weights a softmax over those logits alone; expert ``e``: ``W_down,e
  (silu(u W_gate,e) * (u W_up,e))`` at width ``intermediate_size``; the sum of weight x expert; plus one
  shared SwiGLU of ``shared_intermediate_size`` at weight 1. A loop over the experts, each over every
  token, under the weight of who chose it (0 for the others): nothing is sorted or dropped.

**The chip's share.** The configuration may hold a share of the experts: ``num_local_experts`` counts the
experts *held here*, ``router_experts`` the router's columns, ``expert_share`` of ``expert_shares`` says
which (share ``i`` holds experts ``i * held .. (i + 1) * held - 1``). The router and the softmax are over
all of them; the loop runs over the held experts alone, so the layer's routed part is the held experts'
part of the sum: what one chip of an expert-parallel pair computes before the exchange, and what goes on
to the next layer here, in the program and in this reference alike. ``vocab_size`` is the slice of the
vocabulary held: a smaller vocabulary.

Float32 at ``highest``, one layer at a time and one expert at a time. The control's arithmetic
(``dot_name``) replaces the matrix products (projections, experts, shared expert, head); the router, the
convolution, the recurrence and the attention products stay float32.

**The constants of the recurrence are drawn through one map, ``ssd_constants``**, written here once and
used by this reference and by the builder alike, as ``reference/hybrid_ssm.py`` ``ssm_constants`` is for
Mamba-1: ``weights.make`` draws normal tensors only, and a normal ``A_log`` or ``dt_bias`` at the other
weights' scale gives a state that forgets in a few tokens, which would let a wrong recurrence pass. So
``spec`` draws standard-normal ``dt_bias_raw`` and ``a_raw`` a head and the map sends them where Mamba-2's
own initialiser puts them: ``softplus(dt_bias)`` log-uniform in [0.001, 0.1], ``A`` uniform in [1, 16]: a
head remembers from under a token to a thousand. ``D`` is ``1 + 0.1 normal`` (Mamba-2's is 1).

**The embedding is drawn at ``initializer_range / embedding_multiplier``**, so that the stream's input
``embedding_multiplier * E[token]`` has the scale the other families of the benchmark feed their layers
(``initializer_range``). Drawn at ``initializer_range`` itself, the tied head would read ``12 |E[token]|^2``, eleven
standard deviations of the other logits, at the token just fed: every sequence would repeat its last token for
ever, the reference's best logit would be that token's at any precision, and the comparison that decides
``correct`` would pass whatever the program computed (read so on the chip: the int8 control's gaps were 0.0).

Departures of the program from this file, none in the mathematics: the program carries ``h`` as ``[N,
heads * d_head]`` and a prefill runs the chunked form; its experts' ``gate`` and ``up`` are two tensors
where the published ``input_linear`` is one (so are they here).

This file is the family: its seeded weights (``spec``), its plain reference (``logits_at``) and what its
work requires from shapes alone (``*_bytes_per_decode_step``, ``attention_shape``, the expert products'
bytes, ``ssd_state_step_bytes``). It gives no ``loss_fn``: no train cell stands on it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.lowprec import DOTS

COMMON = ("norm_mixer", "norm_ffn", "router", "experts_gate", "experts_up", "experts_down", "shared_gate", "shared_up",
          "shared_down")
ATTENTION = ("wq", "wk", "wv", "wo")
MAMBA = ("in_proj", "conv_w", "conv_b", "dt_bias_raw", "a_raw", "d_skip", "norm_gate", "out_proj")
DT_MIN, DT_MAX = 0.001, 0.1  # Mamba-2's dt_min, dt_max
A_MIN, A_MAX = 1.0, 16.0  # its A_init_range


def name(layer: int, tensor: str) -> str:
    return f"L{layer:02d}.{tensor}"


def is_attention(cfg: dict, layer: int) -> bool:
    kind = cfg["layer_types"][layer]
    if kind not in ("mamba", "attention"):
        raise NotImplementedError(f"layer_types[{layer}] = {kind!r}: the family has mamba and attention layers")
    return kind == "attention"


def attention_layers(cfg: dict) -> int:
    return sum(is_attention(cfg, i) for i in range(cfg["num_hidden_layers"]))


def mamba_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - attention_layers(cfg)


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]  # every layer routes


def d_inner(cfg: dict) -> int:
    d = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    if d != cfg["mamba_expand"] * cfg["hidden_size"] or cfg.get("mamba_n_groups", 1) != 1:
        raise NotImplementedError("the reference follows one group of B / C and heads x d_head = expand x hidden")
    return d


def conv_dim(cfg: dict) -> int:
    return d_inner(cfg) + 2 * cfg["mamba_d_state"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def attention_shape(cfg: dict) -> tuple:
    """Query heads, key/value heads and head size of an attention layer."""
    return cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)


def held_experts(cfg: dict) -> tuple:
    """``(first, held, router's)``: the experts this configuration holds, among the router's columns."""
    held, shares = cfg["num_local_experts"], cfg.get("expert_shares", 1)
    total = cfg.get("router_experts", held * shares)
    if held * shares != total or not 0 <= cfg.get("expert_share", 0) < shares:
        raise ValueError(f"{held} experts held in share {cfg.get('expert_share', 0)} of {shares} of the router's {total}")
    return cfg.get("expert_share", 0) * held, held, total


def ssd_constants(dt_bias_raw, a_raw):
    """``(dt_bias [heads], A_log [heads])`` in float32 from the standard-normal draws of ``spec``: the one
    map from what ``weights.make`` can draw to where Mamba-2's initialiser puts them."""
    uniform = lambda z: 0.5 * (1.0 + jax.lax.erf(z.astype(jnp.float32) / math.sqrt(2.0)))  # noqa: E731  in (0, 1)
    step = jnp.exp(math.log(DT_MIN) + uniform(dt_bias_raw) * (math.log(DT_MAX) - math.log(DT_MIN)))
    dt_bias = step + jnp.log(-jnp.expm1(-step))  # softplus^-1
    return dt_bias, jnp.log(A_MIN + uniform(a_raw) * (A_MAX - A_MIN))


def spec(cfg: dict) -> dict:
    hidden, vocab, ff, shared = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"], cfg["shared_intermediate_size"]
    heads, kv_heads, hd = attention_shape(cfg)
    d_in, n, k, m_heads = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_n_heads"]
    _, held, total = held_experts(cfg)
    if not cfg.get("mamba_conv_bias", True) or cfg.get("mamba_proj_bias", False) or not cfg.get("tie_word_embeddings", False):
        raise NotImplementedError("the reference follows a convolution with a bias, projections without, and a tied head")
    normal, scale, raw = ("normal", cfg.get("initializer_range", 0.02)), ("one_plus", 0.1), ("normal", 1.0)
    # assumed (the configuration's file says why): the embedding at the matrices' scale over ``embedding_multiplier``
    embed = ("normal", cfg.get("embed_std", normal[1] / cfg["embedding_multiplier"]))
    out = {"embed": ((vocab, hidden), embed), "norm_final": ((hidden,), scale)}
    for i in range(cfg["num_hidden_layers"]):
        layer = {
            "norm_mixer": ((hidden,), scale), "norm_ffn": ((hidden,), scale),
            # assumed (the configuration's file gives the rule): logits of unit spread over a normed input
            "router": ((hidden, total), ("normal", cfg.get("router_std", hidden ** -0.5))),
            "experts_gate": ((held, hidden, ff), normal), "experts_up": ((held, hidden, ff), normal),
            "experts_down": ((held, ff, hidden), normal),
            "shared_gate": ((hidden, shared), normal), "shared_up": ((hidden, shared), normal), "shared_down": ((shared, hidden), normal),
        }
        if is_attention(cfg, i):
            layer.update({"wq": ((hidden, heads * hd), normal), "wk": ((hidden, kv_heads * hd), normal),
                          "wv": ((hidden, kv_heads * hd), normal), "wo": ((heads * hd, hidden), normal)})
        else:
            layer.update({
                "in_proj": ((hidden, 2 * d_in + 2 * n + m_heads), normal),
                # assumed (the configuration's file says so): Mamba draws the taps uniform in +-k**-0.5
                "conv_w": ((k, d_in + 2 * n), ("normal", cfg.get("mamba_conv_std", 0.3))), "conv_b": ((d_in + 2 * n,), normal),
                "dt_bias_raw": ((m_heads,), raw), "a_raw": ((m_heads,), raw), "d_skip": ((m_heads,), scale),
                "norm_gate": ((d_in,), scale), "out_proj": ((d_in, hidden), normal),
            })
        out.update({name(i, t): v for t, v in layer.items()})
    return out


# -- what the work requires, from shapes alone

def _mixer_params(cfg: dict, attention: bool) -> int:
    hidden = cfg["hidden_size"]
    if attention:
        heads, kv_heads, hd = attention_shape(cfg)
        return 2 * hidden * heads * hd + 2 * hidden * kv_heads * hd
    d_in, n, k, m_heads = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_n_heads"]
    return hidden * (2 * d_in + 2 * n + m_heads) + (k + 1) * (d_in + 2 * n) + 3 * m_heads + d_in + d_in * hidden


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expected_experts_touched(cfg: dict, tokens: float) -> float:
    """Distinct *held* experts that ``tokens`` tokens reach in one layer if each picks its ``k`` of the
    router's experts evenly."""
    _, held, total = held_experts(cfg)
    return held * (1.0 - (1.0 - cfg["num_experts_per_tok"] / total) ** tokens)


def weight_bytes_per_decode_step(cfg: dict, slots: int, itemsize: int = 2) -> float:
    """Every layer's mixer, norms, router and shared expert, the held routed experts that ``slots`` tokens
    are expected to reach; the final norm, the embedding once as the head, and one embedding row a slot.
    Each read once."""
    hidden = cfg["hidden_size"]
    _, _, total = held_experts(cfg)
    per_layer = (2 * hidden + hidden * total + 3 * hidden * cfg["shared_intermediate_size"]
                 + expected_experts_touched(cfg, slots) * expert_params(cfg))
    params = (attention_layers(cfg) * _mixer_params(cfg, True) + mamba_layers(cfg) * _mixer_params(cfg, False)
              + cfg["num_hidden_layers"] * per_layer + hidden + hidden * cfg["vocab_size"])
    return float(itemsize) * (params + slots * hidden)


def ssd_state_step_bytes(cfg: dict, slots: float) -> float:
    """One call of the state-step kernel (one layer, one token a slot): ``h`` read and written in float32
    (``2 x 4 x N x d_inner``: 8,388,608 B a slot at the published widths), a slot's ``x'`` and ``y`` in the
    served type, ``delta`` a head in float32, ``B`` and ``C``; ``A`` and ``D`` a head once. The algorithm's
    count: the kernel as built takes the decays and ``delta x'`` spread over the lanes in float32."""
    d_in, n, m_heads = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_n_heads"]
    return slots * (2.0 * n * d_in * 4 + d_in * (2 + 2) + m_heads * 4 + 2 * n * 2) + 4.0 * 2 * m_heads


def conv_state_bytes(cfg: dict, slots: float, itemsize: int = 2) -> float:
    """The convolution's carried inputs of one layer, read and written."""
    return 2.0 * slots * (cfg["mamba_d_conv"] - 1) * conv_dim(cfg) * itemsize


def state_bytes_per_slot(cfg: dict, itemsize: int = 2) -> int:
    """What a sequence holds whatever its length: a float32 state and the convolution's carried inputs a
    state-space layer."""
    return mamba_layers(cfg) * (cfg["mamba_d_state"] * d_inner(cfg) * 4 + (cfg["mamba_d_conv"] - 1) * conv_dim(cfg) * itemsize)


def cache_bytes_per_decode_step(cfg: dict, live_tokens: float, slots: float, itemsize: int = 2) -> float:
    """The attention layers' live keys and values once (and their queries and outputs), and every
    state-space layer's state read and written for the ``slots`` that decode: the step kernel's bytes and
    the convolution's carried inputs."""
    heads, kv_heads, hd = attention_shape(cfg)
    attention = float(itemsize) * (2.0 * live_tokens * kv_heads * hd + 2.0 * slots * heads * hd)
    return attention_layers(cfg) * attention + mamba_layers(cfg) * (ssd_state_step_bytes(cfg, slots) + conv_state_bytes(cfg, slots, itemsize))


def expert_products_bytes(cfg: dict, experts_touched: float, pairs: float, itemsize: int = 2) -> float:
    """The three grouped products of the held experts: the weights of the ``experts_touched`` that got a
    pair (summed over layers), and the activations of the pairs that reach a held expert: of ``pairs``
    routed over all the router's experts, the held share (``num_local_experts / router_experts``); each
    such pair's input and output (``hidden``) and its two intermediates (``intermediate_size``, written
    and read)."""
    _, held, total = held_experts(cfg)
    return float(itemsize) * (experts_touched * expert_params(cfg)
                              + pairs * held / total * (2 * cfg["hidden_size"] + 4 * cfg["intermediate_size"]))


def expert_products_flops(cfg: dict, pairs: float) -> float:
    _, held, total = held_experts(cfg)
    return 2.0 * pairs * held / total * expert_params(cfg)


# -- the plain reference

def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def attention(x, w, cfg: dict, dot):
    """Causal grouped-query attention over one sequence ``x`` [T, hidden], a head at a time, no positions,
    the scores times ``attention_multiplier``."""
    heads, kv_heads, hd = attention_shape(cfg)
    t = x.shape[0]
    q = dot(x, w["wq"]).reshape(t, heads, hd)
    k, v = dot(x, w["wk"]).reshape(t, kv_heads, hd), dot(x, w["wv"]).reshape(t, kv_heads, hd)
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def one_head(args):  # a head at a time bounds the score matrix
        q_h, k_h, v_h = args  # [T, hd] each
        scores = jnp.matmul(q_h, k_h.T, precision="highest") * cfg["attention_multiplier"]
        return jnp.matmul(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v_h, precision="highest")

    group = jnp.arange(heads) // (heads // kv_heads)  # the key/value head of each query head
    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2)[group], v.transpose(1, 0, 2)[group]))
    return dot(ctx.transpose(1, 0, 2).reshape(t, heads * hd), w["wo"])


def mamba2(x, w, cfg: dict, dot):
    """The Mamba-2 mixer over one sequence ``x`` [T, hidden], a token at a time from ``h = 0``."""
    d_in, n, k, m_heads, p = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    t = x.shape[0]
    zxd = dot(x, w["in_proj"])
    z, xbc, dt = zxd[:, :d_in], zxd[:, d_in : d_in + conv_dim(cfg)], zxd[:, d_in + conv_dim(cfg) :]
    before = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc])  # tap k - 1 meets the token itself
    xbc = jax.nn.silu(sum(before[j : j + t] * w["conv_w"][j] for j in range(k)) + w["conv_b"])
    u, b, c = xbc[:, :d_in].reshape(t, m_heads, p), xbc[:, d_in : d_in + n], xbc[:, d_in + n :]
    dt_bias, a_log = ssd_constants(w["dt_bias_raw"], w["a_raw"])
    delta = jax.nn.softplus(dt + dt_bias)  # [T, heads]
    a = -jnp.exp(a_log)  # [heads]

    def one_token(h, inputs):
        delta_t, u_t, b_t, c_t = inputs  # [heads], [heads, p], [N], [N]
        h = jnp.exp(delta_t * a)[:, None, None] * h + (delta_t[:, None] * u_t)[:, :, None] * b_t[None, None, :]
        return h, jnp.sum(h * c_t[None, None, :], axis=-1) + w["d_skip"][:, None] * u_t

    _, y = jax.lax.scan(one_token, jnp.zeros((m_heads, p, n), jnp.float32), (delta, u, b, c))
    gated = y.reshape(t, d_in) * jax.nn.silu(z)
    return dot(_rms_norm(gated, w["norm_gate"], cfg["rms_norm_eps"]), w["out_proj"])


def _swiglu(h, gate, up, down, dot):
    return dot(jax.nn.silu(dot(h, gate)) * dot(h, up), down)


def routing(h, w, cfg: dict):
    """``[T, router's experts]`` float32: a token's weight for each expert, 0 where it did not choose it."""
    logits = jnp.matmul(h, w["router"], precision="highest")
    top, chosen = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    return jnp.zeros_like(logits).at[jnp.arange(h.shape[0])[:, None], chosen].set(jax.nn.softmax(top, axis=-1))


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


def feed_forward(h, w, cfg: dict, dot):
    return routed_ffn(h, w, cfg, dot) + _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"], dot)


def layer(x, w, cfg: dict, dot, attends: bool):
    """One decoder layer over one sequence; ``w`` holds this layer's weights (the experts in the served type)."""
    f32 = {n: (v if n.startswith("experts_") else v.astype(jnp.float32)) for n, v in w.items()}
    mixer = attention if attends else mamba2
    by = cfg["residual_multiplier"]
    x = x + by * mixer(_rms_norm(x, f32["norm_mixer"], cfg["rms_norm_eps"]), f32, cfg, dot)
    return x + by * feed_forward(_rms_norm(x, f32["norm_ffn"], cfg["rms_norm_eps"]), f32, cfg, dot)


_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "rms_norm_eps", "mamba_d_state", "mamba_d_conv",
         "mamba_expand", "mamba_n_heads", "mamba_d_head", "mamba_n_groups", "num_local_experts", "router_experts", "expert_share",
         "expert_shares", "num_experts_per_tok", "attention_multiplier", "residual_multiplier")


@functools.partial(jax.jit, static_argnames=("cfg_key", "dot_name", "attends"))
def _layer(x, w, cfg_key, dot_name, attends):
    return layer(x, w, dict(cfg_key), DOTS[dot_name], attends)


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "dot_name"))
def _head(x, rows, norm_final, embed, eps, scaling, dot_name):
    h = _rms_norm(x[rows], norm_final.astype(jnp.float32), eps)
    return DOTS[dot_name](h, embed.astype(jnp.float32).T) / scaling


def layer_weights(weights: dict, cfg: dict, i: int) -> dict:
    return {n: weights[name(i, n)] for n in COMMON + (ATTENTION if is_attention(cfg, i) else MAMBA)}


def logits_at(weights: dict, cfg: dict, tokens, rows, dot_name: str = "exact"):
    """Logits [len(rows), vocab] of one sequence of token ids at the positions ``rows``."""
    x = cfg["embedding_multiplier"] * weights["embed"][tokens].astype(jnp.float32)
    cfg_key = tuple((k, cfg[k]) for k in _KEYS if k in cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, layer_weights(weights, cfg, i), cfg_key, dot_name, is_attention(cfg, i))
    return _head(x, rows, weights["norm_final"], weights["embed"], cfg["rms_norm_eps"], cfg["logits_scaling"], dot_name)
