"""A decoder with multi-head latent attention and routed experts, as the DeepSeek-V3-style
checkpoints publish it (DeepSeek-V2, arXiv:2405.04434, section 2.1 for the attention;
DeepSeek-V3, arXiv:2412.19437, section 2.1.2 for the routing) and as a ``config.json`` with
``kv_lora_rank``, ``n_routed_experts`` and ``topk_method`` ``noaux_tc`` states it. The family
is named for its mechanisms: a sibling configuration needs no code.

Pre-norm residual blocks, RMSNorm, SiLU, untied head; rotary pairs are ``(2i, 2i+1)``
(``rope_interleave``) of the ``qk_rope_head_dim`` rotary dimensions.

- *Latent attention, every layer.* ``c_q = RMSNorm(W_qa h)``; ``q = W_qb c_q``, per head
  ``[q_nope ; q_rope]``, ``q_rope`` rotated. ``[c_kv ; k_r] = W_kva h``; ``c_kv =
  RMSNorm(c_kv)``; ``k_rope = rotate(k_r)``, one vector shared by all heads. ``[k_nope ; v] =
  W_kvb c_kv`` per head. ``score = (q_nope k_nope + q_rope k_rope) / sqrt(nope + rope)``,
  causal softmax, ``o = sum p v``, ``out = W_o o``. Never absorbed, never cached: a head at a
  time over the whole sequence.
- *The first ``first_k_dense_replace`` layers*: a SwiGLU MLP of ``intermediate_size``.
- *Every later layer*: ``s = sigmoid(W_g h)``; the ``num_experts_per_tok`` experts of a token
  are the top of ``s + b`` (``e_score_correction_bias``; ``n_group`` 1, so no group limit);
  their weights are ``s`` there (without ``b``), divided by their sum (``norm_topk_prob``),
  times ``routed_scaling_factor``; ``y = sum_i w_i E_i(h) + E_shared(h)``, every expert a
  SwiGLU of ``moe_intermediate_size``. A loop over the experts, each over every token, under
  the mask of who chose it: no token is dropped and nothing is sorted.

Float32 at ``highest``, one layer at a time and one expert at a time, so that a 5,120-token
sequence fits beside 11 GB of served weights.

Departures: the multi-token-prediction module (``num_nextn_predict_layers``) is left out, as
the published serving code leaves it out (a training objective and an optional self-draft).
Weights come in the benchmark's names (``spec``), one tensor a layer (a stacked expert tensor
would be drawn in float32 whole), in the served type, and are widened to float32 where used.

This file is the family: its seeded weights (``spec``), its plain reference (``logits_at``)
and what its work requires from shapes alone (``*_bytes_per_decode_step``,
``attention_shape``, the latent decode kernel's bytes, the expert products' bytes and
operations). It gives no ``loss_fn``: no train cell stands on it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.lowprec import DOTS

ATTENTION = ("wq_a", "norm_q", "wq_b", "wkv_a", "norm_kv", "wkv_b", "wo", "norm_attn", "norm_mlp")
DENSE = ("w_gate", "w_up", "w_down")
ROUTED = ("router", "router_bias", "experts_gate", "experts_up", "experts_down", "shared_gate", "shared_up", "shared_down")


def name(layer: int, tensor: str) -> str:
    return f"L{layer:02d}.{tensor}"


def is_routed(cfg: dict, layer: int) -> bool:
    return layer >= cfg["first_k_dense_replace"]


def latent_width(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attention_shape(cfg: dict) -> tuple:
    """Query heads, key/value heads and head size as the decode step sees them: every head against
    one shared latent row (multi-query attention at ``kv_lora_rank + qk_rope_head_dim``)."""
    return cfg["num_attention_heads"], 1, latent_width(cfg)


def spec(cfg: dict) -> dict:
    hidden, vocab, heads = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rot, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    experts, ff = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    shared = ff * cfg["n_shared_experts"]
    normal, scale = ("normal", cfg.get("initializer_range", 0.02)), ("one_plus", 0.1)
    out = {"embed": ((vocab, hidden), normal), "norm_final": ((hidden,), scale), "lm_head": ((hidden, vocab), normal)}
    for i in range(cfg["num_hidden_layers"]):
        layer = {
            "wq_a": ((hidden, q_rank), normal), "norm_q": ((q_rank,), scale), "wq_b": ((q_rank, heads * (nope + rot)), normal),
            "wkv_a": ((hidden, kv_rank + rot), normal), "norm_kv": ((kv_rank,), scale),
            "wkv_b": ((kv_rank, heads, nope + vd), normal), "wo": ((heads * vd, hidden), normal),
            "norm_attn": ((hidden,), scale), "norm_mlp": ((hidden,), scale),
        }
        if is_routed(cfg, i):
            layer.update({
                "router": ((hidden, experts), normal),
                # assumed (the configuration's file says so): the bias is learned, and no config states its size
                "router_bias": ((experts,), ("normal", cfg.get("e_score_correction_bias_std", 0.0))),
                "experts_gate": ((experts, hidden, ff), normal), "experts_up": ((experts, hidden, ff), normal),
                "experts_down": ((experts, ff, hidden), normal),
                "shared_gate": ((hidden, shared), normal), "shared_up": ((hidden, shared), normal),
                "shared_down": ((shared, hidden), normal),
            })
        else:
            width = cfg["intermediate_size"]
            layer.update({"w_gate": ((hidden, width), normal), "w_up": ((hidden, width), normal), "w_down": ((width, hidden), normal)})
        out.update({name(i, k): v for k, v in layer.items()})
    return out


# -- what the work requires, from shapes alone

def _attention_params(cfg: dict) -> int:
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rot, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (hidden * cfg["q_lora_rank"] + cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * (nope + rot)
            + hidden * latent_width(cfg) + cfg["kv_lora_rank"] + cfg["kv_lora_rank"] * heads * (nope + vd)
            + heads * vd * hidden + 2 * hidden)


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def expected_experts_touched(cfg: dict, tokens: float) -> float:
    """Distinct experts that ``tokens`` tokens reach in one layer if each picks its ``k`` of ``E`` evenly."""
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def weight_bytes_per_decode_step(cfg: dict, slots: int, itemsize: int = 2) -> float:
    """Every layer's attention, norms, dense MLP or router, shared experts and the routed experts
    that ``slots`` tokens are expected to reach; the final norm and the output head; one embedding
    row a slot. Each read once."""
    hidden, dense_layers = cfg["hidden_size"], cfg["num_hidden_layers"] - expert_layers(cfg)
    routed = (hidden * cfg["n_routed_experts"] + cfg["n_routed_experts"] + cfg["n_shared_experts"] * expert_params(cfg)
              + expected_experts_touched(cfg, slots) * expert_params(cfg))
    params = (cfg["num_hidden_layers"] * _attention_params(cfg) + dense_layers * 3 * hidden * cfg["intermediate_size"]
              + expert_layers(cfg) * routed + hidden + hidden * cfg["vocab_size"])
    return float(itemsize) * (params + slots * hidden)


def latent_decode_bytes(cfg: dict, live_tokens: float, slots: float, itemsize: int = 2) -> float:
    """One call of the latent paged decode kernel (one layer, one token a slot): the live latent rows
    once (they are keys and values both), the absorbed queries (``W`` wide a head) and the output
    (``kv_lora_rank`` wide a head)."""
    heads = cfg["num_attention_heads"]
    return float(itemsize) * (live_tokens * latent_width(cfg) + slots * heads * (latent_width(cfg) + cfg["kv_lora_rank"]))


def cache_bytes_per_decode_step(cfg: dict, live_tokens: float, slots: int, itemsize: int = 2) -> float:
    return cfg["num_hidden_layers"] * latent_decode_bytes(cfg, live_tokens, slots, itemsize)


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


def _rotate(x, positions, theta):
    """x [T, d]; pair (2i, 2i+1) turned by positions * theta**(-2i/d)."""
    d = x.shape[-1]
    angles = positions[:, None].astype(jnp.float32) * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def attention(x, w, cfg: dict, dot):
    """Latent attention over one sequence ``x`` [T, hidden], a head at a time, nothing absorbed."""
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rot, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    t = x.shape[0]
    pos = jnp.arange(t)
    q = dot(_rms_norm(dot(x, w["wq_a"]), w["norm_q"], cfg["rms_norm_eps"]), w["wq_b"]).reshape(t, heads, nope + rot)
    kv_a = dot(x, w["wkv_a"])
    c_kv = _rms_norm(kv_a[:, :rank], w["norm_kv"], cfg["rms_norm_eps"])
    k_rope = _rotate(kv_a[:, rank:], pos, cfg["rope_theta"])  # [T, rot], shared by the heads
    seen = pos[None, :] <= pos[:, None]

    def one_head(args):  # a head at a time bounds the score matrix
        q_h, w_h = args  # [T, nope + rot], [rank, nope + vd]
        kv = dot(c_kv, w_h)
        k_nope, v = kv[:, :nope], kv[:, nope:]
        scores = (jnp.matmul(q_h[:, :nope], k_nope.T, precision="highest")
                  + jnp.matmul(_rotate(q_h[:, nope:], pos, cfg["rope_theta"]), k_rope.T, precision="highest"))
        probs = jax.nn.softmax(jnp.where(seen, scores * (nope + rot) ** -0.5, -jnp.inf), axis=-1)
        return jnp.matmul(probs, v, precision="highest")

    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2), w["wkv_b"].transpose(1, 0, 2)))  # [heads, T, vd]
    return dot(ctx.transpose(1, 0, 2).reshape(t, heads * vd), w["wo"])


def _swiglu(h, gate, up, down, dot):
    return dot(jax.nn.silu(dot(h, gate)) * dot(h, up), down)


def routing(h, w, cfg: dict, dot):
    """``[T, E]`` float32: a token's weight for each expert, 0 where it did not choose it."""
    k = cfg["num_experts_per_tok"]
    if cfg.get("scoring_func", "sigmoid") != "sigmoid" or cfg.get("n_group", 1) != 1:
        raise NotImplementedError("the reference follows sigmoid scores with one group (noaux_tc)")
    scores = jax.nn.sigmoid(dot(h, w["router"]))
    _, chosen = jax.lax.top_k(scores + w["router_bias"], k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    picked = picked * cfg["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], chosen].set(picked)


def routed_ffn(h, w, cfg: dict, dot):
    """Every expert over every token, weighted by who chose it, plus the shared experts."""
    weights = routing(h, w, cfg, dot)

    def one_expert(y, e):
        take = lambda n: jax.lax.dynamic_index_in_dim(w[n], e, 0, keepdims=False).astype(jnp.float32)  # noqa: E731
        out = _swiglu(h, take("experts_gate"), take("experts_up"), take("experts_down"), dot)
        return y + jax.lax.dynamic_index_in_dim(weights, e, 1, keepdims=True) * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(cfg["n_routed_experts"]))
    return y + _swiglu(h, *(w[n].astype(jnp.float32) for n in ("shared_gate", "shared_up", "shared_down")), dot)


def layer(x, w, cfg: dict, dot, routed: bool):
    """One decoder layer over one sequence; ``w`` holds this layer's weights (the experts in the served type)."""
    f32 = {n: (v if n.startswith("experts_") else v.astype(jnp.float32)) for n, v in w.items()}
    x = x + attention(_rms_norm(x, f32["norm_attn"], cfg["rms_norm_eps"]), f32, cfg, dot)
    h = _rms_norm(x, f32["norm_mlp"], cfg["rms_norm_eps"])
    if routed:
        return x + routed_ffn(h, f32, cfg, dot)
    return x + _swiglu(h, f32["w_gate"], f32["w_up"], f32["w_down"], dot)


_KEYS = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
         "rope_theta", "num_experts_per_tok", "n_routed_experts", "routed_scaling_factor", "norm_topk_prob",
         "scoring_func", "n_group")


@functools.partial(jax.jit, static_argnames=("cfg_key", "dot_name", "routed"))
def _layer(x, w, cfg_key, dot_name, routed):
    return layer(x, w, dict(cfg_key), DOTS[dot_name], routed)


@functools.partial(jax.jit, static_argnames=("eps", "dot_name"))
def _head(x, rows, norm_final, lm_head, eps, dot_name):
    h = _rms_norm(x[rows], norm_final.astype(jnp.float32), eps)
    return DOTS[dot_name](h, lm_head.astype(jnp.float32))


def layer_weights(weights: dict, cfg: dict, i: int) -> dict:
    return {n: weights[name(i, n)] for n in ATTENTION + (ROUTED if is_routed(cfg, i) else DENSE)}


def logits_at(weights: dict, cfg: dict, tokens, rows, dot_name: str = "exact"):
    """Logits [len(rows), vocab] of one sequence of token ids at the positions ``rows``."""
    if cfg.get("rope_scaling"):
        raise NotImplementedError("the reference follows unscaled rotary embedding (rope_scaling null)")
    x = weights["embed"][tokens].astype(jnp.float32)
    cfg_key = tuple((k, cfg.get(k)) for k in _KEYS if k in cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, layer_weights(weights, cfg, i), cfg_key, dot_name, is_routed(cfg, i))
    return _head(x, rows, weights["norm_final"], weights["lm_head"], cfg["rms_norm_eps"], dot_name)
