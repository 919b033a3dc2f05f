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

Built as :mod:`.pallas_paged_attention` was until it took to walking a row's live pages inside one grid step:
the grid walks ``(row, table_entry)``, the block table is a scalar-prefetch operand, pages
beyond a row's frontier are skipped with ``pl.when`` (and their index
stays on the last live page, so the repeated index elides the DMA too),
and an online softmax folds every page into a ``[H, value_width]``
accumulator. Pages stay in the pool's type for both products (float32
accumulation); the probabilities are rounded to it before the second,
as the XLA path does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(
    tbl_ref,  # [B, MB] int32 (scalar prefetch)
    cur_ref,  # [B] int32 (scalar prefetch)
    q_ref,  # [1, H, W]
    page_ref,  # [1, W, bs]: a page holds its tokens as columns
    o_ref,  # [1, H, C]
    m_ref,  # [H, 1] f32 scratch
    l_ref,  # [H, 1] f32 scratch
    acc_ref,  # [H, C] f32 scratch
    *,
    block_size: int,
    value_width: int,
    scale: float,
):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    cur = cur_ref[b]
    lo = j * block_size

    @pl.when(lo <= cur)  # some key of this page is at or before the frontier
    def _page():
        q = q_ref[0]  # [H, W]
        page = page_ref[0]  # [W, bs]
        s = jax.lax.dot_general(q, page, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32) * scale
        pos = lo + jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
        s = jnp.where(pos <= cur, s, -jnp.inf)  # [H, bs]
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))  # finite: a live page has a live key
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(page.dtype), page[:value_width], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


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
    already stored the step's row at position ``cur``."""
    from jax.experimental.pallas import tpu as pltpu

    b, heads, width = q.shape
    _, _, block_size = latent_pool.shape
    mb = block_table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, heads, width), lambda b, j, tbl, cur: (b, 0, 0)),
            # past the frontier the index stays on the last live page: a repeated index elides the DMA,
            # also over blocks that are reserved for tokens not yet decoded
            pl.BlockSpec(
                (1, width, block_size), lambda b, j, tbl, cur: (tbl[b, jnp.minimum(j, cur[b] // block_size)], 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, heads, value_width), lambda b, j, tbl, cur: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((heads, 1), jnp.float32),
            pltpu.VMEM((heads, 1), jnp.float32),
            pltpu.VMEM((heads, value_width), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, block_size=block_size, value_width=value_width, scale=scale)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, heads, value_width), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="latent_paged_decode",
    )(block_table.astype(jnp.int32), cur.astype(jnp.int32), q, latent_pool)
