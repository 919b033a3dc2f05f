"""Pallas TPU kernel: the grouped products of dropless routed experts.

``m`` rows sorted by group (a token-expert pair a row, an expert a
group) meet ``E`` stacked matrices: rows ``offsets[g] .. offsets[g+1]``
are multiplied by matrix ``g``. XLA's lowering of
``jax.lax.ragged_dot`` tiles 512 rows to a group whatever the group
holds, so a decode step (2-4 rows an expert) spends its time on masked
rows. Here the **row tile follows the rows an expert gets**
(:func:`row_tile`: 16 at a decode tick) and the grid walks the
``(group, row tile)`` *visits* and nothing else:

* a group with no row costs no grid step, so its matrix is never read;
* a row tile that straddles several groups is visited once for each,
  every visit storing only its own group's rows;
* a group's matrix is one block (``[d, ff]`` whole), so the visits of one
  group are consecutive grid steps with the same block index and the
  pipeline fetches the matrix **once**, however many row tiles it spans.

Offsets and the visit-to-(group, tile) map are scalar-prefetch operands
(:func:`visit_plan`); the grid's length is the number of visits, a value
the program computes. Operands stay in their type (bfloat16 on the chip),
products accumulate in float32. ``gate`` and ``up`` share a call: the
rows are read once and ``silu(x @ gate) * (x @ up)`` is formed in float32
before it is rounded.

Both calls are named ``ragged-dot-*``: the device operations carry the
``name=``, and ``chipbench/layers/routed_experts_roofline.py`` finds the
experts' products by ``ragged-dot``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_PACKED_ROWS = 16  # a bfloat16 vreg holds 16 sublanes: the smallest row tile that wastes none
_MAX_ROW_TILE = 128  # the MXU's edge


def row_tile(pairs: int, groups: int) -> int:
    """The row tile for ``pairs`` sorted rows over ``groups`` groups: the
    power of two at or above four times the mean rows a group, held to
    16..128. A decode tick's 512 pairs over 256 experts get 16, the prefill
    buckets' 2,048 / 8,192 / 32,768 get 32 / 128 / 128. Four times, not
    once: on a v5e a visit costs about the same at any tile up to the
    MXU's 128 rows (the matrix passes through it once either way), so what
    a larger tile masks is cheaper than the visits a smaller one adds."""
    mean = max(1, -(-pairs // groups))
    return min(_MAX_ROW_TILE, max(_PACKED_ROWS, 1 << (4 * mean - 1).bit_length()))


def tile_visits(group_sizes: jax.Array, tile: int) -> jax.Array:
    """``[E]`` int32: how many row tiles each group's rows lie in (0 for an empty group)."""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    return jnp.where(group_sizes > 0, (ends - 1) // tile - starts // tile + 1, 0).astype(jnp.int32)


def visit_plan(group_sizes: jax.Array, rows: int, tile: int):
    """``(offsets [E + 1], group_of [V], tile_of [V], visits)``: visit ``v <
    visits`` multiplies row tile ``tile_of[v]`` by group ``group_of[v]``,
    groups in order and a group's tiles in order. ``V = rows / tile + E - 1``
    bounds the visits (every group but the first can straddle into one
    tile more); entries past ``visits`` repeat the last visit. Rows past
    the last group (``group_sizes`` may sum to less than ``rows``) lie in
    no visit; with no grouped row at all ``visits`` is 0, the grid has no
    step and the entries name nothing (the serving engine runs no tick in
    which no slot decodes)."""
    e = group_sizes.shape[0]
    per_group = tile_visits(group_sizes, tile)
    first = jnp.cumsum(per_group) - per_group  # the first visit of each group
    visits = jnp.sum(per_group)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(group_sizes.astype(jnp.int32))])
    v = jnp.minimum(jnp.arange(rows // tile + e - 1, dtype=jnp.int32), visits - 1)
    # one [V, E] comparison, not a binary search: a loop of tiny steps is the slow way on this device
    group_of = jnp.searchsorted(first + per_group, v, side="right", method="compare_all").astype(jnp.int32)
    tile_of = offsets[group_of] // tile + v - first[group_of]
    return offsets, group_of, tile_of.astype(jnp.int32), visits


def _own_rows(offsets_ref, group_of_ref, tile_of_ref, shape, tile: int):
    """Mask ``shape`` (``[tile, n]``): the rows of this visit's tile that belong to its group."""
    v = pl.program_id(0)
    g = group_of_ref[v]
    row = tile_of_ref[v] * tile + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])


def _swiglu_kernel(offsets_ref, group_of_ref, tile_of_ref, x_ref, gate_ref, up_ref, h_ref, *, tile: int):
    x = x_ref[...]
    g = jnp.dot(x, gate_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(h_ref.dtype)
    own = _own_rows(offsets_ref, group_of_ref, tile_of_ref, h.shape, tile)
    h_ref[...] = jnp.where(own, h, h_ref[...])


def _product_kernel(offsets_ref, group_of_ref, tile_of_ref, x_ref, w_ref, y_ref, *, tile: int):
    y = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32).astype(y_ref.dtype)
    own = _own_rows(offsets_ref, group_of_ref, tile_of_ref, y.shape, tile)
    y_ref[...] = jnp.where(own, y, y_ref[...])


def _grouped_call(kernel, name, plan, x, mats, tile: int, interpret: bool):
    """``kernel`` over the visits of ``plan``: rows ``x [rows, k]`` against
    the stacked ``mats`` (each ``[E, k, n]``), into ``[rows, n]``."""
    from jax.experimental.pallas import tpu as pltpu

    offsets, group_of, tile_of, visits = plan
    rows, k = x.shape
    n = mats[0].shape[2]
    item = x.dtype.itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(visits,),
        in_specs=[pl.BlockSpec((tile, k), lambda v, off, grp, til: (til[v], 0))]
        + [pl.BlockSpec((None, k, n), lambda v, off, grp, til: (grp[v], 0, 0))] * len(mats),
        out_specs=pl.BlockSpec((tile, n), lambda v, off, grp, til: (til[v], 0)),
    )
    # two buffers a block (the pipeline's), the float32 products, and room for the compiler's own
    blocks = 2 * item * (len(mats) * k * n + tile * (k + n)) + 4 * (len(mats) + 1) * tile * n
    return pl.pallas_call(
        functools.partial(kernel, tile=tile),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # a straddled tile is revisited: the visits run in order
            vmem_limit_bytes=min(100 * 2**20, blocks + 8 * 2**20),
        ),
        interpret=interpret,
        name=name,
    )(offsets, group_of, tile_of, x, *mats)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_swiglu_ffn(
    xs: jax.Array,  # [m, d]: rows sorted by group
    wi_gate: jax.Array,  # [E, d, ff]
    wi_up: jax.Array,  # [E, d, ff]
    wo: jax.Array,  # [E, ff, d]
    group_sizes: jax.Array,  # [E] int32, summing to m or less: the rows past the last group belong to none
    *,
    interpret: bool = False,
) -> jax.Array:
    """``(silu(xs @ gate[g]) * (xs @ up[g])) @ down[g]`` for the rows of each
    group ``g``: ``[m, d]`` in ``xs.dtype``. What three
    ``jax.lax.ragged_dot`` calls give, in two kernels, for the rows of a
    group. ``group_sizes`` may sum to less than ``m`` (``ops.moe``
    sorts the pairs of tokens that do not count behind every group): the
    rows past the last group are never visited and never stored, so a
    tile that holds none but them costs no grid step, and those rows of
    the result are whatever the output buffer held (``ragged_dot`` gives
    zeros there): the caller discards them."""
    m, _ = xs.shape
    e = wi_gate.shape[0]
    tile = row_tile(m, e)
    rows = -(-m // tile) * tile
    if rows != m:  # rows past m belong to no group: no visit stores them, and they are cut off again
        xs = jnp.pad(xs, ((0, rows - m), (0, 0)))
    plan = visit_plan(group_sizes, rows, tile)
    dt = xs.dtype
    gate_up = (wi_gate.astype(dt), wi_up.astype(dt))
    h = _grouped_call(_swiglu_kernel, "ragged-dot-swiglu", plan, xs, gate_up, tile, interpret)
    ys = _grouped_call(_product_kernel, "ragged-dot-down", plan, h, (wo.astype(dt),), tile, interpret)
    return ys[:m]
