"""Selective state-space scan (Mamba-1, arXiv:2312.00752) over a window of
tokens, and the causal depthwise convolution in front of it, both with the
state they carry from one window to the next.

A Mamba layer keeps no row a token. Its state a sequence is fixed in size:
``h`` ``[d_state, d_inner]`` float32 and the last ``d_conv - 1`` inputs of
the convolution. A window of tokens advances both::

    h_t = exp(delta_t * A) * h_{t-1} + (delta_t * u_t) B_t
    y_t = h_t C_t + D * u_t

``h`` is laid out ``[..., d_state, d_inner]``, ``d_inner`` along the TPU's
128 lanes: a minor axis of 16 would be padded to 128, eight times the bytes.

**Which tokens count.** A window may hold tokens that are not new: the
right pad of a prompt bucket, and the head of an end-aligned chunk window,
which overlaps tokens the carried state already holds (``serving.py``).
K/V rows shrug both off (a pad row lies beyond the causal frontier, an
overlapped row is written again as it was, or kept); a recurrence would
count them.
So both functions take the window's span of new tokens ``[lo, hi)``: a
token outside it leaves ``h`` and the convolution's carried inputs as they
were (``delta`` 0 and no shift), and its output is never read.

The scan is chunked: a ``lax.scan`` over chunks of tokens carries ``h``, and
inside a chunk the steps are unrolled, so that XLA fuses a chunk's
recurrence into elementwise loops over ``[d_state, d_inner]`` and no
``[tokens, d_state, d_inner]`` array of a whole window (335 MB a layer at
1024 tokens of Jamba-3B's widths) is ever in HBM. One token a slot, the
decode step, is :func:`state_step`; on the chip the serving tick runs it as
the Pallas kernel in :mod:`.pallas_selective_scan`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SCAN_CHUNK = 16  # tokens a chunk: the unrolled steps of one loop body


def causal_conv1d(u, weight, bias, carried, lo, hi):
    """Depthwise causal convolution over a window, from carried inputs.

    ``u`` ``[B, T, D]`` the window's inputs; ``weight`` ``[K, D]`` (tap
    ``K - 1`` meets the token itself); ``bias`` ``[D]`` or None;
    ``carried`` ``[B, (K - 1) * D]`` the inputs of the last ``K - 1`` tokens
    before the window's first new one, oldest first (one lane-dense row a
    sequence: a ``[.., K - 1, D]`` leaf would be padded to a tile of 8 or
    16 rows, and the TPU compiler re-laid it out around every step);
    ``[lo, hi)`` the new tokens. Returns the outputs ``[B, T, D]`` (those
    of tokens outside the span are never read) and the carried inputs
    after token ``hi - 1``.

    The carried inputs are laid over the window at ``[lo - (K - 1), lo)``:
    whatever stood there (an overlapped head, or nothing) is what they are.
    One token that is new, the decode step, never leaves two dimensions.
    """
    b, t, d = u.shape
    k = weight.shape[0]
    w = weight.astype(u.dtype)
    if t == 1 and (lo, hi) == (0, 1):
        x = u[:, 0]
        out = x * w[k - 1] + sum(carried[:, j * d : (j + 1) * d] * w[j] for j in range(k - 1))
        if bias is not None:
            out = out + bias.astype(u.dtype)
        return out[:, None], jnp.concatenate([carried[:, d:], x], axis=-1)
    ext = jnp.concatenate([jnp.zeros((b, k - 1, d), u.dtype), u], axis=1)  # window position p at p + K - 1
    ext = jax.lax.dynamic_update_slice(ext, carried.reshape(b, k - 1, d).astype(u.dtype), (0, lo, 0))
    out = sum(ext[:, j : j + t] * w[j] for j in range(k))
    if bias is not None:
        out = out + bias.astype(u.dtype)
    return out, jax.lax.dynamic_slice(ext, (0, hi, 0), (b, k - 1, d)).reshape(b, (k - 1) * d)


def state_step(h, u, delta, b_t, c_t, a, d_skip):
    """One token a sequence: ``h`` ``[B, N, D]`` float32, ``u`` and ``delta``
    ``[B, D]``, ``b_t`` and ``c_t`` ``[B, N]``, ``a`` ``[N, D]`` (negative),
    ``d_skip`` ``[D]``. Returns ``y`` ``[B, D]`` float32 and the new ``h``."""
    f32 = jnp.float32
    u, delta = u.astype(f32), delta.astype(f32)
    h = jnp.exp(delta[:, None, :] * a) * h + (delta * u)[:, None, :] * b_t.astype(f32)[:, :, None]
    y = jnp.sum(h * c_t.astype(f32)[:, :, None], axis=1) + d_skip.astype(f32) * u
    return y, h


def selective_scan(u, delta, a, b, c, d_skip, h0, lo, hi, chunk: int = SCAN_CHUNK):
    """The recurrence over a window.

    ``u`` ``[B, T, D]``; ``delta`` ``[B, T, D]`` (after softplus, float32);
    ``a`` ``[N, D]`` float32, negative; ``b``, ``c`` ``[B, T, N]``;
    ``d_skip`` ``[D]``; ``h0`` ``[B, N, D]`` float32; ``[lo, hi)`` the
    window's new tokens. Returns ``y`` ``[B, T, D]`` float32 and ``h`` after
    token ``hi - 1``. ``exp`` and ``h`` are float32 whatever the inputs are.
    """
    bsz, t, _ = u.shape
    f32 = jnp.float32
    pos = jnp.arange(t)
    new = ((pos >= lo) & (pos < hi))[None, :, None]
    delta = jnp.where(new, delta.astype(f32), 0.0)  # exp(0 * A) = 1 and 0 * u = 0: h passes through
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:  # padded tokens have delta 0 as well
        u, delta, b, c = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (u, delta, b, c))
    n_chunks = (t + pad) // chunk

    def by_chunk(x):  # [B, T, W] -> [chunks, L, B, W]
        return x.reshape(bsz, n_chunks, chunk, x.shape[-1]).transpose(1, 2, 0, 3)

    def one_chunk(h, xs):
        u_c, delta_c, b_c, c_c = xs  # [L, B, W] each
        ys = []
        for i in range(chunk):
            y, h = state_step(h, u_c[i], delta_c[i], b_c[i], c_c[i], a, d_skip)
            ys.append(y)
        return h, jnp.stack(ys)

    h, y = jax.lax.scan(one_chunk, h0.astype(f32), (by_chunk(u), by_chunk(delta), by_chunk(b), by_chunk(c)))
    y = y.transpose(2, 0, 1, 3).reshape(bsz, t + pad, -1)  # [chunks, L, B, D] -> [B, T, D]
    return y[:, :t], h
