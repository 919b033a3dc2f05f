"""Context parallelism: ring attention and all-to-all (Ulysses-style)
sequence-parallel attention over the ``seq`` mesh axis.

The reference has **no** long-context mechanism (SURVEY §5: repo-wide grep
finds no ring attention / Ulysses / context parallel; its only lever is the
Megatron-LM ``sequence_parallelism`` flag). This module is the parity-plus
subsystem the TPU build treats as first-class: activations are sharded over
the ``seq`` axis so sequence length scales with the number of chips, and
attention — the one op that mixes positions — runs either

* **ring**: K/V blocks rotate around the ring via ``lax.ppermute`` (ICI
  neighbour exchange, bandwidth-optimal, overlappable), with the
  flash-attention online-softmax merge across ring steps; or
* **all_to_all** (Ulysses): two ``lax.all_to_all`` calls re-shard
  [seq-sharded, all heads] -> [all seq, head-sharded], run ordinary local
  attention, and shard back — cheaper at moderate sequence lengths when the
  head count divides the axis.

Both are differentiable (AD through ``ppermute``/``all_to_all`` yields the
reversed collectives) and run inside ``shard_map``, so XLA sees only
neighbour traffic — no O(S^2) global tensor ever exists.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _block_update(qf, k_blk, v_blk, acc, m, l, q_pos, k_pos, causal: bool, window=None):
    """One online-softmax accumulation step (the flash-attention merge).

    qf: [B,Sq,Hkv,G,D] pre-scaled queries; k_blk/v_blk: [B,Sk,Hkv,D];
    acc: [B,Sq,Hkv,G,D] fp32; m/l: [B,Hkv,G,Sq] fp32 running max/normaliser;
    q_pos/k_pos: absolute positions for causal masking; ``window`` adds
    the sliding-window band (keys older than ``window`` below the query
    are off) — positions are absolute, so the band composes with the
    ring rotation for free.
    """
    # precision="highest": fp32 operands would otherwise decompose to
    # bf16 MXU passes at DEFAULT precision (~1e-3 relative error in the
    # logits — same rationale as _xla_attention); bf16 operands are a
    # single pass either way, so training speed is unaffected
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k_blk, precision="highest").astype(jnp.float32)
    if causal or window is not None:
        valid = q_pos[:, None] >= k_pos[None, :]  # [Sq, Sk_blk]
        if window is not None:
            valid &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(valid[None, None, None], s, -jnp.inf)
    m_blk = s.max(axis=-1)
    m_new = jnp.maximum(m, m_blk)
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    correction = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    l_new = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_blk.dtype), v_blk, precision="highest").astype(jnp.float32)
    acc_new = acc * correction.transpose(0, 3, 1, 2)[..., None] + pv
    return acc_new, m_new, l_new


def _ring_attention_local(q, k, v, axis_name: str, causal: bool, scale: Optional[float], window=None):
    """Per-shard body (runs under shard_map). q/k/v: [B, S_loc, H(.kv), D]
    contiguous sequence blocks; block i of the ring lives on mesh position i
    of ``axis_name``."""
    b, s_loc, h, d = q.shape
    h_kv = k.shape[-2]
    g = h // h_kv
    scale = scale if scale is not None else d**-0.5
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)

    qf = (q * scale).reshape(b, s_loc, h_kv, g, d)
    q_pos = my_idx * s_loc + jnp.arange(s_loc)

    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(carry, t):
        acc, m, l, k_blk, v_blk = carry
        # at step t this device holds the KV block originating on (my_idx - t)
        src = (my_idx - t) % n
        k_pos = src * s_loc + jnp.arange(s_loc)
        # fully-masked blocks (above the diagonal / below the band) are
        # masked, not skipped — every ring step computes, like the
        # full-causal schedule; a cond-skip is a future FLOP optimisation
        acc, m, l = _block_update(qf, k_blk, v_blk, acc, m, l, q_pos, k_pos, causal, window)
        # rotate AFTER computing so the last step needs no extra hop
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (acc, m, l, k_blk, v_blk), None

    acc0 = jnp.zeros((b, s_loc, h_kv, g, d), jnp.float32)
    m0 = jnp.full((b, h_kv, g, s_loc), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h_kv, g, s_loc), jnp.float32)
    (acc, m, l, _, _), _ = lax.scan(
        jax.checkpoint(body), (acc0, m0, l0, k, v), jnp.arange(n)
    )
    l = jnp.maximum(l, 1e-37)
    out = acc / l.transpose(0, 3, 1, 2)[..., None]
    return out.reshape(b, s_loc, h, d).astype(q.dtype)


def _ulysses_attention_local(q, k, v, axis_name: str, causal: bool, scale: Optional[float], window=None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style): re-shard
    seq->heads, run full-sequence local attention on 1/n of the heads,
    re-shard back. Requires n | H_kv. The band (``window``) applies in
    the full-sequence local attention."""
    from ..ops.attention import dot_product_attention

    # [B, S/n, H, D] -> all_to_all over head dim -> [B, S, H/n, D]
    q = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    k = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    v = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    out = dot_product_attention(q, k, v, causal=causal, scale=scale, use_flash=False, window=window)
    # back: [B, S, H/n, D] -> [B, S/n, H, D]
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis_name", "causal", "scale", "method", "batch_axis", "window")
)
def context_parallel_attention(
    q: jax.Array,  # [B, S, H, D] global view, S sharded over `axis_name`
    k: jax.Array,  # [B, S, H_kv, D]
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "seq",
    causal: bool = False,
    scale: Optional[float] = None,
    method: str = "ring",  # "ring" | "all_to_all"
    batch_axis=("data", "fsdp"),  # axis name or tuple of names for the batch dim
    window: Optional[int] = None,  # Mistral band over absolute positions
) -> jax.Array:
    """Sequence-parallel attention entry point. Takes/returns the *global*
    [B, S, H, D] arrays; S is laid out over the mesh ``axis_name`` (and B
    over ``batch_axis`` when that axis exists), and the per-shard body only
    ever touches S/n positions at once."""
    if window is not None and not causal:
        raise ValueError("window requires causal=True (sliding-window is a causal band)")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    axis_size = mesh.shape[axis_name]
    if axis_size == 1:
        from ..ops.attention import dot_product_attention

        return dot_product_attention(q, k, v, causal=causal, scale=scale, window=window)
    if q.shape[1] % axis_size != 0:
        raise ValueError(f"sequence length {q.shape[1]} must divide over {axis_name}={axis_size}")
    if method == "all_to_all" and k.shape[-2] % axis_size != 0:
        raise ValueError(f"all_to_all needs {axis_name}={axis_size} to divide H_kv={k.shape[-2]}")

    bspec = _batch_spec(mesh, batch_axis)
    spec = P(bspec, axis_name, None, None)
    local = _ring_attention_local if method == "ring" else _ulysses_attention_local

    fn = jax.shard_map(
        functools.partial(local, axis_name=axis_name, causal=causal, scale=scale, window=window),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


from .mesh import axis_spec as _batch_spec  # shared normaliser (mesh.py)


def sequence_sharding(mesh: Mesh, axis_name: str = "seq", batch_axis=("data", "fsdp")) -> NamedSharding:
    """The activation sharding matching :func:`context_parallel_attention`:
    [B, S, ...] with S over the seq axis."""
    return NamedSharding(mesh, P(_batch_spec(mesh, batch_axis), axis_name))
