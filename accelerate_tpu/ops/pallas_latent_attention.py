"""Pallas TPU kernel: paged decode over a pool of latent rows (MLA).

Multi-head latent attention caches one row a token, ``[c_kv ; k_rope]``
(``W = kv_lora_rank + qk_rope_head_dim`` values), shared by all heads.
With ``W_UK`` absorbed into the queries and ``W_UV`` into the output, a
decode step is multi-query attention at head size ``W``: every head
scores against the same row (all ``W`` columns) and sums the same row's
first ``value_width`` columns. So each page is read **once** from the
pool and used twice, as keys and as values. A page is ``[W, block_size]``,
its tokens along the lanes (``ops/paged_kv.py`` says why): the scores are a
plain product and the values contract over the lanes of both operands.

The walk is :mod:`.paged_walk`'s, the one :mod:`.pallas_paged_attention` makes:
one grid step a row (slot), the pool left in HBM, and inside the step a loop
over the row's live pages ``0 .. min(cur // block_size, MB - 1)``, its trip
count from the scalar-prefetched frontier, a chunk of pages at a time into one
of two VMEM buffers with one async copy a page addressed through the
scalar-prefetched table; chunk ``i + 1`` in flight while chunk ``i`` folds, the
first chunk of the next row that has keys started before a row ends. Table
entries past the frontier are neither visited nor fetched, and a row handed a
frontier below zero (a slot that stores into the sink: ``paged_kv.NO_KEYS``)
has no keys: no copy, no fold, zeros out. The fold is this kernel's own: a
chunk buffer is ``[W, pages * block_size]``, each page copied into its
``block_size`` lanes, so a chunk folds as one product for the scores
(``[H, W] x [W, pages * bs]``) and one for the values (contracting the lanes of
the probabilities and of the buffer's first ``value_width`` rows), with no
relayout; an online softmax folds every chunk into a ``[H, value_width]``
accumulator. Pages and queries go to the MXU in the pool's type for both
products (float32 accumulation); the probabilities are rounded to it before
the second, as the XLA path does. Scores, running maximum, sum and accumulator
are float32. How many pages a chunk holds follows from the traced shapes
(:func:`_pages_per_chunk`), never from an argument.
"""

from __future__ import annotations

import functools

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


def _pages_per_chunk(width: int, block_size: int, dtype) -> int:
    """Pages fetched and folded together. A page lands in its own ``block_size`` lanes of the chunk
    buffer, which a copy can address only where a page is whole lane tiles (128 tokens): other shapes
    take one page a chunk, which fills the buffer whole."""
    if block_size % 128:
        return 1
    fit = CHUNK_VMEM_BYTES // (2 * width * block_size * jnp.dtype(dtype).itemsize)  # two buffers
    return max(1, min(CHUNK_TOKENS // block_size, fit))


def _kernel(
    tbl_ref,  # [B, MB] int32 (scalar prefetch)
    cur_ref,  # [B] int32 (scalar prefetch)
    q_ref,  # [1, H, W]
    pool_hbm,  # [NB, W, bs], left in HBM: a page holds its tokens as columns
    o_ref,  # [1, H, C]
    buf,  # [2, W, pages * bs] VMEM
    sems,  # DMA semaphores [2 (buffer)]
    side_ref,  # [1] int32 SMEM: the buffer that holds this row's first chunk
    *,
    pages: int,
    block_size: int,
    value_width: int,
    scale: float,
):
    from jax.experimental.pallas import tpu as pltpu

    max_blocks = tbl_ref.shape[1]
    heads = q_ref.shape[1]
    cols = pages * block_size

    def page_copies(page, side, i):
        lanes = slice(None) if pages == 1 else pl.ds(pl.multiple_of(i * block_size, block_size), block_size)
        return (pltpu.make_async_copy(pool_hbm.at[page], buf.at[side, :, lanes], sems.at[side]),)

    def zero_buffers():
        buf[...] = jnp.zeros_like(buf)

    def make_fold(cur, first):
        q = q_ref[0].astype(buf.dtype)  # [H, W]
        token = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        newest = newest_position(cur, max_blocks, block_size)

        def fold(j, side, carry):
            m_prev, l_prev, acc = carry
            chunk = buf[side]  # [W, cols]: keys over all its rows, values over the first value_width
            s = jax.lax.dot_general(q, chunk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32) * scale
            live = (first + j * pages) * block_size + token <= newest
            s = jnp.where(live, s, -jnp.inf)  # [H, cols]
            m_new, alpha, p, l_new = online_softmax(s, m_prev, l_prev)
            pv = jax.lax.dot_general(
                p.astype(chunk.dtype), chunk[:value_width], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            return m_new, l_new, acc * alpha + pv

        return fold

    _, l, acc = walk_live_pages(
        tbl_ref, cur_ref, side_ref, pages=pages, block_size=block_size, window=None,
        page_copies=page_copies, zero_buffers=zero_buffers, make_fold=make_fold,
        init=online_softmax_init(heads, value_width),
    )
    # a live row's sum is at least 1 (its best key counts 1): the guard is for a row with no keys (the
    # walk's first carry: zeros out)
    o_ref[0] = (acc / jnp.maximum(l, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("value_width", "scale", "interpret"))
def latent_paged_decode(
    q: jax.Array,  # [B, H, W]: queries with W_UK absorbed
    latent_pool: jax.Array,  # [NB, W, bs]
    block_table: jax.Array,  # [B, MB] int32
    cur: jax.Array,  # [B] int32: per-row frontier (attend to <= cur)
    *,
    value_width: int,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """One decode step of absorbed latent attention for every row against
    its pages: ``[B, H, value_width]`` in ``q.dtype``. The caller has
    already stored the step's row at position ``cur``; a row whose ``cur``
    is below zero has no keys and comes back zeros."""
    from jax.experimental.pallas import tpu as pltpu

    b, heads, width = q.shape
    _, _, block_size = latent_pool.shape
    pages = _pages_per_chunk(width, block_size, latent_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, heads, width), lambda b, tbl, cur: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, heads, value_width), lambda b, tbl, cur: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, width, pages * block_size), latent_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(_kernel, pages=pages, block_size=block_size, value_width=value_width, scale=scale)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, heads, value_width), q.dtype),
        grid_spec=grid_spec,
        # rows run in order on one core: each that has keys starts the first copies of the next that has
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_paged_decode",
    )(block_table.astype(jnp.int32), cur.astype(jnp.int32), q, latent_pool)
