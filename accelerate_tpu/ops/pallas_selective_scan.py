"""Pallas TPU kernel: one selective-scan step for the slots that decode, the state in place.

The decode step of a Mamba layer reads and writes its state, ``h``
``[slots, d_state, d_inner]`` float32 (84 MB a layer at 128 slots of
Jamba-3B's widths), and does about two operations a byte of it: bandwidth
or nothing. The kernel makes it ONE pass: a block of ``h`` comes into VMEM,
``h' = exp(delta A) h + (delta u) B`` and ``y = h' C + D u`` are formed
there, and ``h'`` goes back over the block it came from
(``input_output_aliases``), so the tick holds one copy of the state and
XLA never gets to split the step into passes over it.

``d_inner`` lies along the lanes (:mod:`.selective_scan` says why), so the
grid walks ``(lane block, slot block)``, slots innermost: ``A`` and ``D``
of a lane block stay in VMEM while every slot passes under them. Inside a
block the slots are taken one at a time as ``[d_state, lanes]`` tiles:
``delta`` and ``u`` of a slot are one row spread over the sublanes, ``B``
and ``C`` one column spread over the lanes.

Without ``row_valid`` every slot is stepped, and the pipeline that brings
``h`` is Pallas's own. With it (a serving tick that knows which slots
decode) the slot blocks, the row operands and the arithmetic are the same,
but ``h`` stays in HBM and the kernel makes the walk :mod:`.paged_walk`
makes over pages, over slots: a grid step's *live* slots come by one async
copy each (a slot's whole ``[d_state, d_inner]``, contiguous, where
``d_inner`` fits :data:`LIVE_LANE_BLOCK`) into one of two VMEM buffers, the
next step's copies in flight while this one's slots are stepped, and each
``h'`` goes back by a copy of its own from one of two more. A slot the
mask leaves out is neither fetched, stepped nor written (its ``h`` is bit
for bit what it was) and its ``y`` is zeros; a block with no live slot
costs an empty grid step and its rows of ``u``, ``delta`` and ``y``. The
mask is read where it lies: no list of live slots is built and no operand
is gathered or scattered around the call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SLOT_BLOCK = 8  # slots a grid step: the float32 sublane tile of the [slots, d_inner] operands
LANE_BLOCK = 2560  # d_inner values a grid step
# ... of the walk over live slots: Jamba-3B's whole d_inner, so a slot's state is one contiguous copy of 320 KB
# and the grid has half the steps (on the chip 3-4 us a call under 2560; four buffers of 8 slots: 10.5 MB of VMEM)
LIVE_LANE_BLOCK = 5120


def _step_slot(i, h, u, delta, a, d_skip, b_ref, c_ref):
    """Slot ``i`` of the block: ``(h' [N, L], y [1, L])`` from its ``h`` ``[N, L]``."""
    dt, x = delta[i : i + 1], u[i : i + 1]  # [1, L]
    h = jnp.exp(dt * a) * h + (dt * x) * b_ref[i]  # [N, L]; b_ref[i] is [N, 1]
    return h, jnp.sum(h * c_ref[i], axis=0, keepdims=True) + d_skip * x


def _kernel(h_ref, u_ref, delta_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_out_ref):
    a, d_skip = a_ref[...], d_ref[...]  # [N, L], [1, L]
    u, delta = u_ref[...], delta_ref[...]  # [S, L] float32
    rows = []
    for i in range(h_ref.shape[0]):
        h_out_ref[i], y = _step_slot(i, h_ref[i], u, delta, a, d_skip, b_ref, c_ref)
        rows.append(y)
    y_ref[...] = jnp.concatenate(rows, axis=0)


def _live_kernel(
    live_ref,  # [S] int32 (scalar prefetch): 1 where the slot decodes
    h_hbm,  # [S, N, D], left in HBM
    u_ref, delta_ref, b_ref, c_ref, a_ref, d_ref,  # the blocks _kernel takes
    y_ref,  # [sb, L]
    h_out_hbm,  # h_hbm's own buffer (input_output_aliases)
    h_in,  # [2, sb, N, L] VMEM: the live slots of this grid step and of the next
    h_new,  # [2, sb, N, L] VMEM: h' of this grid step and of the one before, on their way back
    in_sems, out_sems,  # DMA semaphores [2 (buffer)]
):
    from jax.experimental.pallas import tpu as pltpu

    _, sb, _, lb = h_in.shape
    slot_blocks = pl.num_programs(1)
    steps = pl.num_programs(0) * slot_blocks
    t = pl.program_id(0) * slot_blocks + pl.program_id(1)  # the grid runs in this order on one core
    side = jax.lax.rem(t, 2)

    def live_copies(step, act, back: bool):
        """``act`` (start or wait) on the copy of every live slot of grid step ``step``: ``h`` in, or ``h'`` back."""
        lane_block, slot_block = jax.lax.div(step, slot_blocks), jax.lax.rem(step, slot_blocks)
        lanes = slice(None) if lb == h_hbm.shape[2] else pl.ds(pl.multiple_of(lane_block * lb, 128), lb)
        buf = jax.lax.rem(step, 2)
        for i in range(sb):
            slot = slot_block * sb + i

            @pl.when(live_ref[slot] != 0)
            def _one():
                if back:
                    act(pltpu.make_async_copy(h_new.at[buf, i], h_out_hbm.at[slot, :, lanes], out_sems.at[buf]))
                else:
                    act(pltpu.make_async_copy(h_hbm.at[slot, :, lanes], h_in.at[buf, i], in_sems.at[buf]))

    start = functools.partial(live_copies, act=lambda copy: copy.start())
    wait = functools.partial(live_copies, act=lambda copy: copy.wait())

    @pl.when(t == 0)
    def _first():
        start(t, back=False)

    @pl.when(t + 1 < steps)
    def _next():
        start(t + 1, back=False)

    wait(t, back=False)

    @pl.when(t >= 2)
    def _buffer_free():  # h' of two steps ago left from the buffer this step fills
        wait(t - 2, back=True)

    a, d_skip = a_ref[...], d_ref[...]
    u, delta = u_ref[...], delta_ref[...]
    y_ref[...] = jnp.zeros_like(y_ref)  # an idle slot's row: zeros, never what the buffer held
    for i in range(sb):

        @pl.when(live_ref[pl.program_id(1) * sb + i] != 0)
        def _step():
            h_new[side, i], y_ref[i : i + 1] = _step_slot(i, h_in[side, i], u, delta, a, d_skip, b_ref, c_ref)

    start(t, back=True)

    @pl.when(t + 1 == steps)
    def _last():
        wait(t, back=True)

        @pl.when(t >= 1)
        def _():
            wait(t - 1, back=True)


def _block(size: int, want: int, tile: int) -> int:
    """The largest divisor of ``size`` that is a multiple of ``tile`` and at most ``want``; else ``size`` whole."""
    for cand in range(min(want, size) // tile * tile, 0, -tile):
        if size % cand == 0:
            return cand
    return size


@functools.partial(jax.jit, static_argnames=("slot_block", "lane_block", "interpret"))
def ssm_state_step(h, u, delta, b_t, c_t, a, d_skip, row_valid=None, *, slot_block: int = SLOT_BLOCK,
                   lane_block: int | None = None, interpret: bool = False):
    """``h`` ``[S, N, D]`` float32; ``u``, ``delta`` ``[S, D]``; ``b_t``, ``c_t`` ``[S, N]``; ``a`` ``[N, D]``
    (negative); ``d_skip`` ``[D]``; ``row_valid`` ``[S]`` bool, the slots to step (None: every one). Returns
    ``(y [S, D] float32, h' [S, N, D])``; ``h'`` takes ``h``'s buffer where the caller donates it. For a
    slot that is stepped, the same numbers as :func:`.selective_scan.state_step`; for any other, ``y`` is
    zeros and ``h'`` is ``h``, untouched."""
    s, n, d = h.shape
    f32 = jnp.float32
    sb = _block(s, slot_block, 8)
    lb = _block(d, lane_block or (LANE_BLOCK if row_valid is None else LIVE_LANE_BLOCK), 128)
    grid = (d // lb, s // sb)
    out_shape = (jax.ShapeDtypeStruct((s, d), f32), jax.ShapeDtypeStruct((s, n, d), f32))
    args = (h.astype(f32), u.astype(f32), delta.astype(f32), b_t.astype(f32)[:, :, None], c_t.astype(f32)[:, :, None],
            a.astype(f32), d_skip.astype(f32)[None, :])
    # index maps take the scalar-prefetched mask last where there is one
    row = pl.BlockSpec((sb, lb), lambda j, i, *_: (i, j))
    col = pl.BlockSpec((sb, n, 1), lambda j, i, *_: (i, 0, 0))
    lane_rows = [pl.BlockSpec((n, lb), lambda j, i, *_: (0, j)), pl.BlockSpec((1, lb), lambda j, i, *_: (0, j))]
    if row_valid is None:
        state = pl.BlockSpec((sb, n, lb), lambda j, i: (i, 0, j))
        return pl.pallas_call(
            _kernel,
            out_shape=out_shape,
            grid=grid,
            in_specs=[state, row, row, col, col, *lane_rows],
            out_specs=(row, state),
            input_output_aliases={0: 1},
            interpret=interpret,
            name="ssm_state_step",
        )(*args)
    from jax.experimental.pallas import tpu as pltpu

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[in_hbm, row, row, col, col, *lane_rows],
        out_specs=(row, in_hbm),
        scratch_shapes=[
            pltpu.VMEM((2, sb, n, lb), f32),
            pltpu.VMEM((2, sb, n, lb), f32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        _live_kernel,
        out_shape=out_shape,
        grid_spec=grid_spec,
        input_output_aliases={1: 1},  # operands count the prefetched mask
        # the grid runs in order on one core: each step starts the next one's copies
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_state_step",
    )(row_valid.astype(jnp.int32), *args)
