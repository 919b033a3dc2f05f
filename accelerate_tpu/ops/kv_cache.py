"""Shared KV-cache incremental attention for the model zoo's decode path.

The cache is a flax ``cache`` collection: fixed-size ``[B, max_len, H_kv,
D]`` buffers updated in place with ``dynamic_update_slice`` — static
shapes, so the whole decode loop jits into one XLA program
(:mod:`accelerate_tpu.generation`). The reference has no in-framework
decode (it delegates generation to transformers); on TPU the cache layout
and the single-program loop ARE the per-token latency story.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import BATCH_AXES

# Mesh layout of the cache buffers [B, max_len, H(_kv), D]: batch over the
# data-parallel axes (same BATCH_AXES as the training data path), heads
# over ``tensor`` — the TP decode layout. With q/k/v projections
# column-split over ``tensor`` (the zoo's Megatron rules) this keeps the
# whole decode loop partitioned: each tensor shard attends with its own
# heads against its own cache slice and only the o_proj row-parallel
# reduction communicates. ``maybe_shard`` drops axes that don't divide
# (e.g. GQA with fewer kv heads than tensor shards) or that the active
# mesh doesn't have, and is a no-op when no mesh is active.
CACHE_KV_SPEC = P(BATCH_AXES, None, "tensor", None)


def _leaf_name(path) -> str:
    return str(path[-1].key) if hasattr(path[-1], "key") else str(path[-1])


def leaf_names(cache) -> set:
    """The names of ``cache``'s leaves (``key``, ``latent``, ``index``, ...)."""
    return {_leaf_name(p) for p, _ in jax.tree_util.tree_flatten_with_path(cache)[0]}


def _constrain(x):
    from ..parallel.sharding import maybe_shard

    return maybe_shard(x, CACHE_KV_SPEC)


def cached_attention(
    module, q, k, v, max_len: int, scale=None, bias_fn=None, sliding_window=None, logit_softcap=None,
    keep_rows_before=None,
):
    """Incremental causal attention against a growing cache.

    ``module``: the calling flax module (owns the ``cache`` variables).
    ``q`` [B, S_new, H, D]; ``k``/``v`` [B, S_new, H_kv, D] (GQA when
    H_kv < H). Returns [B, S_new, H, D]. Prefill (S_new = prompt) and
    per-token decode (S_new = 1) share this path.

    ``scale``: logit multiplier (default ``1/sqrt(D)``; T5 passes 1.0).
    ``bias_fn(q_pos [S_new], key_pos [max_len]) -> [1, H, S_new, max_len]``
    adds a position-dependent logit bias (T5's relative bias) — computed
    from ABSOLUTE positions so prefill and steps agree.
    ``sliding_window``: Mistral-style band — each query attends only the
    last ``sliding_window`` keys (the cache still stores ``max_len`` rows;
    out-of-window rows are masked, matching the non-decode band mask).
    ``keep_rows_before``: the first new token of the window (a model with
    state-space layers names it): the rows of the tokens before it, the
    overlapped head of an end-aligned chunk window, stay as the cache has
    them. Their hidden states came through layers whose state did not
    advance, so here the head recomputes nothing it could write back.
    """
    from . import paged_kv

    pcfg = paged_kv.active_paged_config()
    if pcfg is not None:
        if logit_softcap is not None:
            raise NotImplementedError(
                "attention logit softcapping (Gemma2) is not supported by the paged "
                "cache kernel yet; serve with the dense engine layout"
            )
        # serving engine's paged mode: block-pool cache layout instead of
        # dense per-row buffers (trace-time switch; see ops/paged_kv.py)
        return paged_kv.paged_cached_attention(
            module, q, k, v, max_len, scale=scale, bias_fn=bias_fn,
            sliding_window=sliding_window, cfg=pcfg,
        )
    b, s_new, h_kv, d = k.shape
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    ck = module.variable("cache", "key", jnp.zeros, (b, max_len, h_kv, d), k.dtype)
    cv = module.variable("cache", "value", jnp.zeros, (b, max_len, h_kv, d), v.dtype)
    idx = module.variable("cache", "index", lambda: jnp.zeros((), jnp.int32))
    cur = idx.value
    if keep_rows_before is not None and s_new > 1:
        head = (jnp.arange(s_new) < keep_rows_before)[None, :, None, None]
        k = jnp.where(head, jax.lax.dynamic_slice(ck.value, (0, cur, 0, 0), k.shape), k)
        v = jnp.where(head, jax.lax.dynamic_slice(cv.value, (0, cur, 0, 0), v.shape), v)
    ck.value = _constrain(jax.lax.dynamic_update_slice(ck.value, k, (0, cur, 0, 0)))
    cv.value = _constrain(jax.lax.dynamic_update_slice(cv.value, v, (0, cur, 0, 0)))
    idx.value = cur + s_new

    k_all, v_all = ck.value, cv.value
    groups = q.shape[2] // h_kv
    # causal over absolute positions: new token i attends to <= cur+i;
    # with a sliding window, also to > cur+i - W (the Mistral band)
    key_pos = jnp.arange(max_len)
    q_pos = cur + jnp.arange(s_new)
    live = key_pos[None, :] <= q_pos[:, None]  # [S_new, max_len]
    if sliding_window is not None:
        live &= key_pos[None, :] > q_pos[:, None] - sliding_window
    bias = bias_fn(q_pos, key_pos) if bias_fn is not None else None
    def cap(scores):
        if logit_softcap is None:
            return scores
        from .attention import softcap  # Gemma2: tanh-bound BEFORE the mask

        return softcap(scores, logit_softcap)

    if groups > 1:
        # GQA: contract grouped queries against the UN-repeated cache —
        # materializing jnp.repeat over [B, max_len, H, D] would 4x the
        # cache's memory traffic on every decode step
        qg = q.reshape(b, s_new, h_kv, groups, d)
        scores = cap(jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_all).astype(jnp.float32) * scale)
        if bias is not None:
            scores = scores + bias.reshape(1, h_kv, groups, s_new, max_len)
        mask = live[None, None, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1).astype(q.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v_all)
        return out.reshape(b, s_new, h_kv * groups, d)
    scores = cap(jnp.einsum("bqhd,bkhd->bhqk", q, k_all).astype(jnp.float32) * scale)
    if bias is not None:
        scores = scores + bias
    mask = live[None, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v_all)


def start_cache(module, k, v, max_len: int) -> None:
    """Declare ``module``'s dense cache (the leaves :func:`cached_attention` declares) holding the rows ``k`` /
    ``v`` ``[B, S, H_kv, D]`` of a call that starts it, the frontier at ``S``: for a caller that attends over
    those rows itself."""
    b, s, h_kv, d = k.shape
    ck = module.variable("cache", "key", jnp.zeros, (b, max_len, h_kv, d), k.dtype)
    cv = module.variable("cache", "value", jnp.zeros, (b, max_len, h_kv, d), v.dtype)
    idx = module.variable("cache", "index", lambda: jnp.zeros((), jnp.int32))
    ck.value = _constrain(jax.lax.dynamic_update_slice(ck.value, k, (0, 0, 0, 0)))
    cv.value = _constrain(jax.lax.dynamic_update_slice(cv.value, v, (0, 0, 0, 0)))
    idx.value = jnp.asarray(s, jnp.int32)


def cached_latent_attention(module, q_lat, rows, max_len: int, *, value_width: int, scale: float):
    """Incremental absorbed latent attention (MLA) against a growing dense
    cache of latent rows ``[B, max_len, W]`` (``W = kv_lora_rank +
    qk_rope_head_dim``; one row a token, shared by every head).

    ``q_lat`` ``[B, S_new, H, W]`` are the queries with ``W_UK`` absorbed,
    ``rows`` ``[B, S_new, W]`` the new tokens' ``[c_kv ; k_rope]``. Returns
    ``sum_j p_j c_kv_j`` ``[B, S_new, H, value_width]``; the caller applies
    ``W_UV``. Under the serving engine's paged layout the pool takes the
    place of the dense buffer (:func:`.paged_kv.paged_latent_attention`)."""
    from . import paged_kv

    pcfg = paged_kv.active_paged_config()
    if pcfg is not None:
        return paged_kv.paged_latent_attention(
            module, q_lat, rows, max_len, value_width=value_width, scale=scale, cfg=pcfg
        )
    cache, idx = latent_cache_variables(module, rows.shape[0], max_len, rows.shape[-1], rows.dtype)
    cur = idx.value
    s_new = rows.shape[1]
    cache.value = jax.lax.dynamic_update_slice(cache.value, rows, (0, cur, 0))
    idx.value = cur + s_new
    live = jnp.arange(max_len)[None, :] <= (cur + jnp.arange(s_new))[:, None]  # [S_new, max_len]
    scores = jnp.einsum("bqhw,bkw->bhqk", q_lat, cache.value).astype(jnp.float32) * scale
    probs = jax.nn.softmax(jnp.where(live[None, None], scores, -jnp.inf), axis=-1).astype(q_lat.dtype)
    return jnp.einsum("bhqk,bkc->bqhc", probs, cache.value[..., :value_width])


def latent_cache_variables(module, batch: int, max_len: int, width: int, dtype):
    """The dense latent cache of ``module``: ``latent`` ``[B, max_len, W]`` and the scalar ``index``."""
    cache = module.variable("cache", "latent", jnp.zeros, (batch, max_len, width), dtype)
    idx = module.variable("cache", "index", lambda: jnp.zeros((), jnp.int32))
    return cache, idx


def reset_cache_index(cache, new_index):
    """Set every ``index`` leaf of a cache pytree to ``new_index`` — the
    frontier reset shared by the serving engine's padded prefill and
    speculative decoding's accept/reject step: rows past the new frontier
    are stale but sit beyond the causal mask until overwritten."""
    def fix(path, leaf):
        if _leaf_name(path) == "index":
            return jnp.full(leaf.shape, new_index, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


def cached_cross_kv(module, kv, num_heads: int, head_dim: int, make_k, make_v, prime: bool):
    """Cross-attention K/V cache shared by the encoder-decoder zoo: project
    the encoder output ONCE at prefill (``prime=True``) and reuse the
    stored projections on every decode step. ``make_k``/``make_v`` are
    zero-arg closures running the projection submodules (only invoked when
    priming, so step traces skip the projection entirely)."""
    b, s_enc = kv.shape[:2]
    ck = module.variable("cache", "cross_key", jnp.zeros, (b, s_enc, num_heads, head_dim), jnp.float32)
    cv = module.variable("cache", "cross_value", jnp.zeros, (b, s_enc, num_heads, head_dim), jnp.float32)
    if prime:
        ck.value = _constrain(make_k().astype(jnp.float32))
        cv.value = _constrain(make_v().astype(jnp.float32))
    return ck.value, cv.value
