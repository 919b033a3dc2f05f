"""The control's arithmetic: the reference's matrix multiplications a step below bfloat16.

bfloat16 is what the configurations state, so the step below is int8 or fp8. ``int8_dot``
(the serve cells' control: the v5e's own 393 TOP/s path, what a decode PR would be tempted
by) rounds both operands to 8-bit integers with one absmax scale per row of the left
operand and per column of the right. ``fp8_dot`` (the train cells' control: the recipe of
the program's own ``ops/fp8.py``) rounds operands to e4m3 in the forward and gradients to
e5m2 in the backward, one absmax scale a tensor. Per-row int8 keeps about as many bits of
a well-scaled activation as bfloat16 does, and did not move a training step three times
further than bfloat16 moves it (PERF.md, PR 23); fp8's three bits do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def fake_int8(x, axis: int):
    """``x`` rounded to 255 levels, one absmax scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def exact_dot(x, w):
    return jnp.matmul(x, w, precision="highest")


@jax.custom_vjp
def int8_dot(x, w):
    """``x @ w`` for ``x`` [..., k] and ``w`` [k, n], both operands in int8."""
    return jnp.matmul(fake_int8(x, -1), fake_int8(w, 0), precision="highest")


def _int8_dot_fwd(x, w):
    return int8_dot(x, w), (x, w)


def _int8_dot_bwd(res, dy):
    x, w = res
    dyq = fake_int8(dy, -1)
    dx = jnp.matmul(dyq, fake_int8(w, 1).T, precision="highest")
    x2, dy2 = x.reshape(-1, x.shape[-1]), dy.reshape(-1, dy.shape[-1])
    dw = jnp.matmul(fake_int8(x2, 0).T, fake_int8(dy2, 0), precision="highest")
    return dx, dw


int8_dot.defvjp(_int8_dot_fwd, _int8_dot_bwd)

def fake_fp8(x, dtype):
    """``x`` rounded to ``dtype`` (an 8-bit float), scaled so that its largest magnitude is the type's."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.max(jnp.abs(x)) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def fp8_dot(x, w):
    return jnp.matmul(fake_fp8(x, jnp.float8_e4m3fn), fake_fp8(w, jnp.float8_e4m3fn), precision="highest")


def _fp8_dot_fwd(x, w):
    return fp8_dot(x, w), (x, w)


def _fp8_dot_bwd(res, dy):
    x, w = res
    dyq = fake_fp8(dy, jnp.float8_e5m2)
    dx = jnp.matmul(dyq, fake_fp8(w, jnp.float8_e4m3fn).T, precision="highest")
    x2, dy2 = fake_fp8(x, jnp.float8_e4m3fn).reshape(-1, x.shape[-1]), dyq.reshape(-1, dy.shape[-1])
    return dx, jnp.matmul(x2.T, dy2, precision="highest")


fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)

DOTS = {"exact": exact_dot, "int8": int8_dot, "fp8": fp8_dot}
