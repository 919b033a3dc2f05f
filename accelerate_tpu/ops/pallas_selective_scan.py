"""Pallas TPU kernel: one selective-scan step for every slot, the state in place.

The decode step of a Mamba layer reads and writes its whole state, ``h``
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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SLOT_BLOCK = 8  # slots a grid step: the float32 sublane tile of the [slots, d_inner] operands
LANE_BLOCK = 2560  # d_inner values a grid step


def _kernel(h_ref, u_ref, delta_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_out_ref):
    a, d_skip = a_ref[...], d_ref[...]  # [N, L], [1, L]
    u, delta = u_ref[...], delta_ref[...]  # [S, L] float32
    rows = []
    for i in range(h_ref.shape[0]):
        dt, x = delta[i : i + 1], u[i : i + 1]  # [1, L]
        h = jnp.exp(dt * a) * h_ref[i] + (dt * x) * b_ref[i]  # [N, L]; b_ref[i] is [N, 1]
        h_out_ref[i] = h
        rows.append(jnp.sum(h * c_ref[i], axis=0, keepdims=True) + d_skip * x)
    y_ref[...] = jnp.concatenate(rows, axis=0)


def _block(size: int, want: int, tile: int) -> int:
    """The largest divisor of ``size`` that is a multiple of ``tile`` and at most ``want``; else ``size`` whole."""
    for cand in range(min(want, size) // tile * tile, 0, -tile):
        if size % cand == 0:
            return cand
    return size


@functools.partial(jax.jit, static_argnames=("slot_block", "lane_block", "interpret"))
def ssm_state_step(h, u, delta, b_t, c_t, a, d_skip, *, slot_block: int = SLOT_BLOCK, lane_block: int = LANE_BLOCK,
                   interpret: bool = False):
    """``h`` ``[S, N, D]`` float32; ``u``, ``delta`` ``[S, D]``; ``b_t``, ``c_t`` ``[S, N]``; ``a`` ``[N, D]``
    (negative); ``d_skip`` ``[D]``. Returns ``(y [S, D] float32, h' [S, N, D])``; ``h'`` takes ``h``'s buffer
    where the caller donates it. The same numbers as :func:`.selective_scan.state_step`."""
    s, n, d = h.shape
    f32 = jnp.float32
    sb, lb = _block(s, slot_block, 8), _block(d, lane_block, 128)
    grid = (d // lb, s // sb)
    row = pl.BlockSpec((sb, lb), lambda j, i: (i, j))
    col = pl.BlockSpec((sb, n, 1), lambda j, i: (i, 0, 0))
    state = pl.BlockSpec((sb, n, lb), lambda j, i: (i, 0, j))
    y, h_new = pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct((s, d), f32), jax.ShapeDtypeStruct((s, n, d), f32)),
        grid=grid,
        in_specs=[state, row, row, col, col, pl.BlockSpec((n, lb), lambda j, i: (0, j)),
                  pl.BlockSpec((1, lb), lambda j, i: (0, j))],
        out_specs=(row, state),
        input_output_aliases={0: 1},
        interpret=interpret,
        name="ssm_state_step",
    )(h.astype(f32), u.astype(f32), delta.astype(f32), b_t.astype(f32)[:, :, None], c_t.astype(f32)[:, :, None],
      a.astype(f32), d_skip.astype(f32)[None, :])
    return y, h_new
