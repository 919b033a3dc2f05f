"""State-space duality scan (Mamba-2, arXiv:2405.21060) over a window of
tokens, with the state it carries from one window to the next.

A Mamba-2 layer has ``H`` heads of ``P`` channels and a state of ``N``
values a channel. Its decay is ONE scalar a head a token, and ``B`` and
``C`` are shared by every head of a group (one group here)::

    h_t[s, n, p] = exp(delta_t[n] A[n]) h_{t-1}[s, n, p] + delta_t[n] x_t[n, p] B_t[s]
    y_t[n, p]    = sum_s h_t[s, n, p] C_t[s] + D[n] x_t[n, p]

``h`` is laid out ``[..., N, H * P]`` float32, the layout of
:mod:`.selective_scan`'s state with the heads' channels side by side along
the lanes: a decay or an input is then a row spread over the sublanes, ``B``
and ``C`` a column spread over the lanes, and the contraction over the
state axis a sum of whole vregs (a state axis along the lanes would make
every ``y`` a lane reduction and every decay a lane-sparse column).

**Why not :func:`.selective_scan.selective_scan`.** That scan carries ``h``
token by token. At granite-4.0-h-small's widths ``h`` is 4 MB a sequence a
layer (128 x 8192 float32): 8 MB of traffic a token, 8 GB a layer for a
1024-token prefill. The published chunked form does the work of a chunk
(``mamba_chunk_size`` tokens) as matrix products and touches the state once
a chunk. With ``L`` the running sum of ``delta A`` inside a chunk:

* inside the chunk, ``y_t += sum_{s <= t} (C_t . B_s) exp(L_t - L_s) delta_s x_s``:
  the ``C_t . B_s`` scores are ONE ``[Q, Q]`` product (one group), weighted
  a head by the decay between the two tokens;
* the carried state enters as ``exp(L_t) C_t . h_prev``
* and leaves as ``exp(L_end) h_prev + sum_s exp(L_end - L_s) delta_s x_s (x) B_s``.

Every exponent is a sum of non-positive terms taken forward in time, so
nothing overflows. The products that meet the float32 state or the float32
decays run at ``highest``: they are a few GFLOP a chunk, and a TPU's default
float32 product would round the carried state to bfloat16 each chunk.

**Which tokens count** is :mod:`.selective_scan`'s rule: a token outside
the window's span of new tokens ``[lo, hi)`` gets ``delta`` 0, so it
neither decays nor feeds the state, and its output is never read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 256  # tokens a chunk: the published ``mamba_chunk_size``


def ssd_state_step_plain(h, x, delta, a, b_t, c_t, d_skip):
    """One token a sequence, plainly: ``h`` ``[B, N, H * P]`` float32, ``x``
    ``[B, H, P]``, ``delta`` ``[B, H]`` (after softplus), ``a`` ``[H]``
    (negative), ``b_t`` and ``c_t`` ``[B, N]``, ``d_skip`` ``[H]``. Returns
    ``y`` ``[B, H, P]`` float32 and the new ``h``."""
    f32 = jnp.float32
    bsz, heads, p = x.shape
    x, delta = x.astype(f32), delta.astype(f32)
    lanes = lambda per_head: jnp.repeat(per_head, p, axis=-1)  # noqa: E731  [.., H] -> [.., H * P]
    decay = lanes(jnp.exp(delta * a.astype(f32)))[:, None, :]
    fed = (delta[:, :, None] * x).reshape(bsz, 1, heads * p)
    h = decay * h + fed * b_t.astype(f32)[:, :, None]
    y = jnp.sum(h * c_t.astype(f32)[:, :, None], axis=1).reshape(bsz, heads, p)
    return y + d_skip.astype(f32)[:, None] * x, h


def ssd_scan(x, delta, a, b, c, d_skip, h0, lo, hi, chunk: int = CHUNK):
    """The recurrence over a window, a chunk at a time.

    ``x`` ``[B, T, H, P]``; ``delta`` ``[B, T, H]`` (after softplus);
    ``a`` ``[H]`` float32, negative; ``b``, ``c`` ``[B, T, N]``; ``d_skip``
    ``[H]``; ``h0`` ``[B, N, H * P]`` float32; ``[lo, hi)`` the window's new
    tokens. Returns ``y`` ``[B, T, H, P]`` float32 and ``h`` after token
    ``hi - 1``. The state, the decays and the products that meet them are
    float32 whatever the inputs are.
    """
    f32 = jnp.float32
    bsz, t, heads, p = x.shape
    n = b.shape[-1]
    pos = jnp.arange(t)
    new = ((pos >= lo) & (pos < hi))[None, :, None]
    delta = jnp.where(new, delta.astype(f32), 0.0)  # exp(0 * A) = 1 and 0 * x = 0: h passes through
    q = min(chunk, t)
    pad = -t % q
    x, b, c = x.astype(f32), b.astype(f32), c.astype(f32)
    if pad:  # padded tokens have delta 0 as well
        x, delta, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) for v in (x, delta, b, c))
    n_chunks = (t + pad) // q

    def by_chunk(v):  # [B, T, ...] -> [chunks, B, Q, ...]
        return jnp.moveaxis(v.reshape(bsz, n_chunks, q, *v.shape[2:]), 1, 0)

    a = a.astype(f32)
    earlier = jnp.tril(jnp.ones((q, q), bool))  # [t, s]: s <= t
    dot = lambda spec, *ops: jnp.einsum(spec, *ops, precision="highest")  # noqa: E731

    def one_chunk(h, xs):
        x_c, delta_c, b_c, c_c = xs  # [B, Q, H, P], [B, Q, H], [B, Q, N], [B, Q, N]
        run = jnp.cumsum(delta_c * a, axis=1)  # L: [B, Q, H], non-increasing along Q
        fed = delta_c[..., None] * x_c  # delta_s x_s: [B, Q, H, P]
        # inside the chunk: one [Q, Q] product of scores, a head's decay between the two tokens
        scores = dot("btn,bsn->bts", c_c, b_c)
        between = jnp.exp(jnp.where(earlier[None, :, :, None], run[:, :, None, :] - run[:, None, :, :], -jnp.inf))
        y = dot("btsh,bshp->bthp", scores[..., None] * between, fed)
        # the carried state enters ...
        h_heads = h.reshape(bsz, n, heads, p)
        y = y + jnp.exp(run)[..., None] * dot("btn,bnhp->bthp", c_c, h_heads)
        # ... and leaves
        to_end = jnp.exp(run[:, -1:, :] - run)  # exp(L_end - L_s): [B, Q, H]
        h_heads = jnp.exp(run[:, -1, :])[:, None, :, None] * h_heads + dot("bsn,bshp->bnhp", b_c, to_end[..., None] * fed)
        return h_heads.reshape(bsz, n, heads * p), y + d_skip.astype(f32)[:, None] * x_c

    if n_chunks == 1:
        h, y = one_chunk(h0.astype(f32), (x, delta, b, c))
        return y[:, :t], h
    h, y = jax.lax.scan(one_chunk, h0.astype(f32), (by_chunk(x), by_chunk(delta), by_chunk(b), by_chunk(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, t + pad, heads, p)
    return y[:, :t], h
