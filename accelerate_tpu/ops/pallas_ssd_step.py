"""Pallas TPU kernel: one Mamba-2 (SSD) step for the slots that decode, the state in place.

The decode step of a Mamba-2 layer reads and writes its state, ``h``
``[slots, N, H * P]`` float32: 4 MB a slot a layer at
granite-4.0-h-small's widths (``N`` 128, 128 heads of 64), thirteen times
a Mamba-1 layer's of Jamba-3B, and does about one operation a byte of it:
bandwidth or nothing. Beside :mod:`.pallas_selective_scan`'s step the
arithmetic is poorer, not richer: the decay is ONE scalar a head
(``exp(delta A)``, 128 exponentials a slot where a Mamba-1 step takes one a
state element), the input an outer product ``(delta x)[lane] B[state]``,
the output a contraction over the state axis, which lies along the
sublanes (:mod:`.ssd_scan` says why): a sum of whole vregs.

The walk is the one PR 39 gave the Mamba-1 kernel, a slot at a time: ``h``
stays in HBM, the ``[slots]`` mask of decoding slots is scalar-prefetched,
and a *live* slot's whole state (contiguous, 4 MB) comes by one async copy
into one of two VMEM buffers while the slot before it is stepped; ``h'``
goes back over the slot it came from (``input_output_aliases``) from one
of two more. A slot the mask leaves out is neither fetched, stepped nor
written (its ``h`` is bit for bit what it was) and its ``y`` is zeros.
Without a mask every slot is live: the same program, an all-ones mask.
The rows a slot needs beside its state (the decay and ``delta x`` spread
over the lanes, ``B`` and ``C`` as two columns of one operand) come by BlockSpec, eight slots
a grid step; the exponentials, the spreading of a head's scalar over its
64 lanes and the skip term ``D x`` are a few KB a slot and stay XLA's,
inside the same jitted call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SLOT_BLOCK = 8  # slots a grid step: the float32 sublane tile of the [slots, lanes] operands
LANE_CHUNK = 512  # lanes stepped at a time inside a slot: [N, 512] float32 is 64 vregs at N 128
# two buffers in, two out, of one slot's state; the row blocks; the compiler's own
VMEM_ROOM = 12 * 2**20


def _kernel(
    live_ref,  # [S] int32 (scalar prefetch): 1 where the slot decodes
    h_hbm,  # [S, N, L], left in HBM
    decay_ref, fed_ref,  # [sb, L]: exp(delta A) and delta x, a head's scalar spread over its lanes
    bc_ref,  # [sb, N, 2]: B and C as columns, side by side (a minor axis of 1 or 2 is padded to 128 lanes alike)
    y_ref,  # [sb, L]
    h_out_hbm,  # h_hbm's own buffer (input_output_aliases)
    h_in,  # [2, N, L] VMEM: the live slot being stepped and the next one's, on its way in
    h_new,  # [2, N, L] VMEM: h' of this slot and of the one before, on their way back
    in_sems, out_sems,  # DMA semaphores [2 (buffer)]
):
    from jax.experimental.pallas import tpu as pltpu

    sb, lanes = y_ref.shape
    slots = h_hbm.shape[0]
    first = pl.program_id(0) * sb  # the grid runs in order on one core: slot u is stepped after slot u - 1

    def copy_in(slot, buf):
        return pltpu.make_async_copy(h_hbm.at[slot], h_in.at[buf], in_sems.at[buf])

    def copy_back(slot, buf):
        return pltpu.make_async_copy(h_new.at[buf], h_out_hbm.at[slot], out_sems.at[buf])

    def when_live(slot, act):
        @pl.when(jnp.logical_and(jnp.logical_and(slot >= 0, slot < slots), live_ref[jnp.clip(slot, 0, slots - 1)] != 0))
        def _():
            act()

    @pl.when(jnp.logical_and(first == 0, live_ref[0] != 0))
    def _first():
        copy_in(0, 0).start()

    y_ref[...] = jnp.zeros_like(y_ref)  # an idle slot's row: zeros, never what the buffer held
    for i in range(sb):
        slot, buf = first + i, i % 2  # sb is even or the grid has one step: a slot's buffer is its parity
        when_live(slot + 1, lambda slot=slot, buf=buf: copy_in(slot + 1, 1 - buf).start())
        when_live(slot, lambda slot=slot, buf=buf: copy_in(slot, buf).wait())
        when_live(slot - 2, lambda slot=slot, buf=buf: copy_back(slot - 2, buf).wait())  # h' of two slots ago has left this buffer

        def step(i=i, slot=slot, buf=buf):
            bc = bc_ref[i]
            b, c = bc[:, 0:1], bc[:, 1:2]  # [N, 1]
            for j in range(0, lanes, LANE_CHUNK):
                at = slice(j, min(j + LANE_CHUNK, lanes))
                h = decay_ref[i : i + 1, at] * h_in[buf, :, at] + fed_ref[i : i + 1, at] * b
                h_new[buf, :, at] = h
                y_ref[i : i + 1, at] = jnp.sum(h * c, axis=0, keepdims=True)
            copy_back(slot, buf).start()

        when_live(slot, step)

    @pl.when(first + sb == slots)
    def _last():
        when_live(slots - 2, lambda: copy_back(slots - 2, (sb - 2) % 2).wait())
        when_live(slots - 1, lambda: copy_back(slots - 1, (sb - 1) % 2).wait())


def _slot_block(slots: int) -> int:
    """Eight slots a grid step where eight divides the slots, else every slot in one step."""
    return SLOT_BLOCK if slots % SLOT_BLOCK == 0 else slots


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_state_step(h, x, delta, a, b_t, c_t, d_skip, row_valid=None, *, interpret: bool = False):
    """``h`` ``[S, N, H * P]`` float32; ``x`` ``[S, H, P]``; ``delta`` ``[S, H]`` (after softplus); ``a`` ``[H]``
    (negative); ``b_t``, ``c_t`` ``[S, N]``; ``d_skip`` ``[H]``; ``row_valid`` ``[S]`` bool, the slots to step
    (None: every one). Returns ``(y [S, H, P] float32, h' [S, N, H * P])``; ``h'`` takes ``h``'s buffer
    where the caller donates it. For a slot that is stepped, the same numbers as
    :func:`.ssd_scan.ssd_state_step_plain`; for any other, ``y`` is zeros and ``h'`` is ``h``, untouched."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    s, n, lanes = h.shape
    _, heads, p = x.shape
    sb = _slot_block(s)
    x, delta = x.astype(f32), delta.astype(f32)
    live = jnp.ones((s,), jnp.int32) if row_valid is None else row_valid.astype(jnp.int32)
    decay = jnp.repeat(jnp.exp(delta * a.astype(f32)), p, axis=-1)  # [S, H * P]
    fed = (delta[:, :, None] * x).reshape(s, lanes)
    row = pl.BlockSpec((sb, lanes), lambda i, *_: (i, 0))
    cols = pl.BlockSpec((sb, n, 2), lambda i, *_: (i, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s // sb,),
        in_specs=[in_hbm, row, row, cols],
        out_specs=(row, in_hbm),
        scratch_shapes=[
            pltpu.VMEM((2, n, lanes), f32),
            pltpu.VMEM((2, n, lanes), f32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    y, h = pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct((s, lanes), f32), jax.ShapeDtypeStruct((s, n, lanes), f32)),
        grid_spec=grid_spec,
        input_output_aliases={1: 1},  # operands count the prefetched mask
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # the grid runs in order on one core: a slot starts the next one's copy
            vmem_limit_bytes=min(100 * 2**20, 4 * n * lanes * 4 + VMEM_ROOM),
        ),
        interpret=interpret,
        name="ssd_state_step",
    )(live, h.astype(f32), decay, fed, jnp.stack([b_t.astype(f32), c_t.astype(f32)], axis=-1))
    skip = d_skip.astype(f32)[:, None] * x
    if row_valid is not None:
        skip = jnp.where(row_valid[:, None, None], skip, 0.0)
    return y.reshape(s, heads, p) + skip, h
