"""Pallas TPU kernel: paged-attention decode over a shared block pool.

The XLA paged path (:func:`accelerate_tpu.ops.paged_kv.paged_gather_attention`)
gathers each row's pages into a contiguous ``[B, L, H_kv, D]`` copy
every step — the gather WRITES a full cache-sized array and the two
attention einsums read it back, roughly tripling the per-step HBM
traffic of the (bandwidth-bound) decode attention. This kernel reads
each LIVE page once and nothing else:

* one grid step is one row (slot), not one table entry. The pools stay
  in HBM; inside the step a loop walks the row's live pages, ``first ..
  cur // block_size`` (``first`` is 0, or under a sliding window the page
  that holds the band's oldest key), a *chunk* of pages at a time. Its
  trip count comes from the scalar-prefetched frontier, so table entries
  past the frontier (blocks reserved for tokens not yet decoded, pad
  entries at the trash sink) are neither visited nor fetched, and a row
  handed a frontier below zero (a slot that stores into the sink, in
  which nobody decodes: ``paged_kv.NO_KEYS``) has no keys: it costs no
  copy and no fold, and its output is zeros. A row at frontier 0 has one
  key and is walked;
* a chunk is fetched with one async copy a page, addressed through the
  scalar-prefetched table, into one of two VMEM buffers: chunk ``i + 1``
  is in flight while chunk ``i`` is folded, and before a row is finished
  it starts the first chunk of the next row that has keys, however many
  rows without lie between, so the copies' latency is paid once a call
  and not once a row;
* the fold is one MXU-shaped product a chunk with no relayout. A page
  ``[bs, H_kv, D]`` is, byte for byte, ``[bs * H_kv, D]``; ``q [H, D]``
  against a chunk of them gives scores ``[H, pages * bs * H_kv]`` in
  which head ``h`` keeps the columns of its own key/value head (``col %
  H_kv == h // G``); the rest are masked with the positions past the
  frontier or before the band, and the masked probabilities contract
  against the value chunk in the same view. ``H_kv`` times the
  arithmetic of a grouped product, all of it on full tiles, and K/V are
  never repeated, transposed or cast;
* operands go to the MXU in the pool's type with float32 accumulation:
  queries cast to it, and the probabilities as two terms of it, what
  rounding keeps and what it drops, stacked into one product so that the
  value chunk is loaded once (``paged_gather_attention`` keeps the first
  term alone; a token whose two best logits lie closer than that
  rounding moves them then goes the other way, which the benchmark's toy
  cell counts). Scores, running maximum, sum and accumulator are float32;
* the decode contract matches the XLA branch in masking: keys at
  positions ``> cur - W`` and ``<= cur``;
* a pool whose heads are under the lane width comes lane-folded
  (``paged_kv.pool_lane_fold``: ``[NB, bs, H_kv / f, f * D]``, ``f`` heads of
  a token side by side in 128 lanes). The kernel runs on it as it is, at
  ``H_kv / f`` heads of ``f * D``: :func:`paged_decode_attention` puts each
  query into its own head's lanes with zeros in the others', so a score is
  the query against its own keys alone, and keeps a head's own lanes of the
  ``f * D`` wide values.

The walk (the first two points) is :mod:`.paged_walk`'s, which the latent
kernel (:mod:`.pallas_latent_attention`) makes too; the fold (the next two)
is this kernel's own. How many pages a chunk holds follows from the shapes
the kernel is traced with (:func:`_pages_per_chunk`), never from an argument. The
public paged-attention kernel in ``jax.experimental`` walks its pages
the same way (``pages_per_compute_block``); this one is written for
THIS engine's layout (token-major pages, trash-sink block 0, per-row
frontiers, optional band, a flattened layer stack addressed as ``table +
layer * NB``) and is dispatched from ``paged_cached_attention`` on TPU.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .paged_walk import (
    CHUNK_TOKENS,
    CHUNK_VMEM_BYTES,
    newest_position,
    online_softmax,
    online_softmax_init,
    walk_live_pages,
)


def _pages_per_chunk(block_size: int, kv_heads: int, head_dim: int, dtype) -> int:
    """Pages fetched and folded together. The flat view stacks a chunk's pages ``[pages, bs * H_kv, D]``
    into ``[pages * bs * H_kv, D]``, which is free only where a page is whole tiles (8 rows of 32 bits:
    16 of bf16): other shapes take one page a chunk, which needs no stacking."""
    itemsize = jnp.dtype(dtype).itemsize
    rows = block_size * kv_heads
    if rows % (8 * max(1, 4 // itemsize)):
        return 1
    fit = CHUNK_VMEM_BYTES // (4 * rows * head_dim * itemsize)  # two K and two V buffers
    return max(1, min(CHUNK_TOKENS // block_size, fit))


def _kernel(
    tbl_ref,  # [B, MB] int32 (scalar prefetch)
    cur_ref,  # [B] int32 (scalar prefetch)
    q_ref,  # [1, H, D]
    k_hbm,  # [NB, bs * Hkv, D], left in HBM
    v_hbm,  # [NB, bs * Hkv, D], left in HBM
    o_ref,  # [1, H, D]
    k_buf,  # [2, pages, bs * Hkv, D] VMEM
    v_buf,  # [2, pages, bs * Hkv, D] VMEM
    sems,  # DMA semaphores [2 (K, V), 2 (buffer)]
    side_ref,  # [1] int32 SMEM: the buffer that holds this row's first chunk
    *,
    pages: int,
    block_size: int,
    kv_heads: int,
    window: Optional[int],
    scale: float,
    ring: bool,
):
    from jax.experimental.pallas import tpu as pltpu

    max_blocks = tbl_ref.shape[1]
    heads, dim = q_ref.shape[1:]
    cols = pages * block_size * kv_heads

    def page_copies(page, side, i):
        return (
            pltpu.make_async_copy(k_hbm.at[page], k_buf.at[side, i], sems.at[0, side]),
            pltpu.make_async_copy(v_hbm.at[page], v_buf.at[side, i], sems.at[1, side]),
        )

    def zero_buffers():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    def make_fold(cur, first):
        q = q_ref[0].astype(k_buf.dtype)
        # column c of a chunk is token c // Hkv of it and key/value head c % Hkv: head h reads its own
        col = jax.lax.broadcasted_iota(jnp.int32, (heads, cols), 1)
        own = jax.lax.rem(col, kv_heads) == jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (heads, cols), 0), heads // kv_heads
        )
        token = jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1), kv_heads)
        newest = cur if ring else newest_position(cur, max_blocks, block_size)

        def fold(j, side, carry):
            m_prev, l_prev, acc = carry
            k = k_buf[side].reshape(cols, dim)
            v = v_buf[side].reshape(cols, dim)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
            pos = (first + j * pages) * block_size + token  # [1, cols]
            live = pos <= newest
            if window is not None:
                live &= pos > cur - window
            s = jnp.where(own & live, s, -jnp.inf)  # [H, cols]
            m_new, alpha, p, l_new = online_softmax(s, m_prev, l_prev)
            # the probabilities go to the MXU in the pool's type as two terms, what rounding keeps and what
            # it drops (nothing, for a float32 pool), stacked so that the value chunk is loaded once for both
            kept = p.astype(v.dtype)
            if v.dtype == jnp.float32:
                pv = jnp.dot(kept, v, preferred_element_type=jnp.float32)
            else:
                terms = jnp.concatenate([kept, (p - kept.astype(jnp.float32)).astype(v.dtype)], axis=0)
                both = jnp.dot(terms, v, preferred_element_type=jnp.float32)
                pv = both[:heads] + both[heads:]
            return m_new, l_new, acc * alpha + pv

        return fold

    _, l, acc = walk_live_pages(
        tbl_ref, cur_ref, side_ref, pages=pages, block_size=block_size, window=window,
        page_copies=page_copies, zero_buffers=zero_buffers, make_fold=make_fold, init=online_softmax_init(heads, dim),
        ring=ring,
    )
    # l is 0 for a row with nothing live (a slot at the sink, handed no keys: the walk's first carry; a
    # long-retired slot whose windowed frontier moved past its table): zeros out, which nothing reads, where
    # an unguarded 0/0 would trip jax_debug_nans
    o_ref[0] = (acc / jnp.maximum(l, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sliding_window", "scale", "interpret", "ring"))
def paged_decode_attention(
    q: jax.Array,  # [B, H, D]
    key_pool: jax.Array,  # [NB, bs, Hkv, D], or lane-folded [NB, bs, Hkv / f, f * D]
    value_pool: jax.Array,  # as key_pool
    block_table: jax.Array,  # [B, MB] int32
    cur: jax.Array,  # [B] int32 — per-row frontier (attend to <= cur)
    *,
    sliding_window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
    ring: bool = False,
) -> jax.Array:
    """One decode step of attention for every row against its paged KV.

    Returns ``[B, H, D]`` in ``q.dtype``. The caller has already written
    the step's K/V into the pool at position ``cur`` (the engine's
    scatter), so the frontier key is included; a row whose ``cur`` is below
    zero has no keys and comes back zeros. ``ring`` (with a
    ``sliding_window``): ``block_table`` is a ring of ``MB`` entries, page
    ``p`` at entry ``p % MB`` (``paged_kv`` ``window_table``); the call's
    device name then carries the window (``paged_decode_attention_w512``),
    so that a trace tells a model's two kinds of layer apart.
    """
    from jax.experimental.pallas import tpu as pltpu

    scale = (1.0 / math.sqrt(q.shape[-1])) if scale is None else scale
    fold = key_pool.shape[-1] // q.shape[-1]
    if fold > 1:
        # a lane-folded pool (``paged_kv.pool_lane_fold``): ``fold`` key/value heads of a token side by side in
        # one row. Each query sits in its own head's lanes with zeros in the others', so the kernel, run as it
        # is at ``H_kv / fold`` heads of ``fold * D``, scores it against its own keys alone; the values come
        # out ``fold * D`` wide and a head keeps its own lanes.
        b, heads, d = q.shape
        lane = (jnp.arange(heads) // (heads // (key_pool.shape[2] * fold))) % fold  # [H]: where a head's K/V lie
        mine = lane[:, None] == jnp.arange(fold)[None, :]  # [H, fold]
        wide = jnp.where(mine[None, :, :, None], q[:, :, None, :], 0).reshape(b, heads, fold * d)
        out = paged_decode_attention(wide, key_pool, value_pool, block_table, cur, sliding_window=sliding_window,
                                     scale=scale, interpret=interpret, ring=ring)
        return jnp.sum(jnp.where(mine[None, :, :, None], out.reshape(b, heads, fold, d), 0), axis=2)
    b, heads, dim = q.shape
    nb, block_size, kv_heads, _ = key_pool.shape
    pages = _pages_per_chunk(block_size, kv_heads, dim, key_pool.dtype)
    rows = block_size * kv_heads

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, heads, dim), lambda b, tbl, cur: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, heads, dim), lambda b, tbl, cur: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages, rows, dim), key_pool.dtype),
            pltpu.VMEM((2, pages, rows, dim), value_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _kernel,
        pages=pages,
        block_size=block_size,
        kv_heads=kv_heads,
        window=sliding_window,
        scale=scale,
        ring=ring,
    )
    if ring and sliding_window is None:
        raise ValueError("a ring table holds a sliding window's pages: ring=True needs sliding_window")
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, heads, dim), q.dtype),
        grid_spec=grid_spec,
        # rows run in order on one core: each that has keys starts the first copies of the next that has
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=f"paged_decode_attention_w{sliding_window}" if ring else "paged_decode_attention",
    )(
        block_table.astype(jnp.int32),
        cur.astype(jnp.int32),
        q,
        # a page as rows of the flat view: the same bytes in the same order
        key_pool.reshape(nb, rows, dim),
        value_pool.reshape(nb, rows, dim),
    )
