"""The walk over a slot's live pages that both paged decode kernels make.

:mod:`.pallas_paged_attention` (K/V pools) and :mod:`.pallas_latent_attention`
(one pool of latent rows) are the same algorithm around two different folds.
One grid step is one row (slot); the pools stay in HBM. Inside the step:

* the row's live pages are ``first .. min(cur // block_size, MB - 1)``, both
  read from scalar-prefetched operands (``first`` is 0, or under a sliding
  window the page that holds the band's oldest key). Table entries past the
  frontier (blocks reserved for tokens not yet decoded, pad entries at the
  trash sink) are neither visited nor fetched;
* a row whose frontier is below zero has no keys: what ``ops/paged_kv.py``
  hands the kernels for a slot that stores into the sink (idle, or finished
  and overshooting). Its grid step starts no copy, waits for none, folds
  nothing and leaves the fold's first carry, which the kernels write out as
  zeros; a row at frontier 0 has one key and is walked;
* the pages are taken a *chunk* at a time, one async copy a page addressed
  through the table, into one of two VMEM buffers: chunk ``i + 1`` is in
  flight while chunk ``i`` is folded, and before a row is finished it starts
  the first chunk of the next row *that has pages to walk*, whichever row that
  is (the grid runs in order on one core:
  ``dimension_semantics=("arbitrary",)``), so the copies' latency is paid once
  a call and not once a row; row 0's prologue starts the first such row's. A
  row without pages touches neither buffer nor the SMEM word that carries,
  from one walked row to the next, which buffer holds its first chunk; a call
  whose rows all lack pages starts no copy, and every copy started is waited
  for by the row it was started for;
* a frontier that overshot the table (a slot that finished mid-tick) is
  clamped to the row's own last entry, and its newest position to the table's
  last token.

What differs is a kernel's own: which copies fetch a page and where they land
(``page_copies``), and what a fetched chunk is folded into (``make_fold``).
How many pages a chunk holds follows from the shapes a kernel is traced with,
never from an argument; both aim at :data:`CHUNK_TOKENS` tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# A chunk aims at this many tokens: large enough that a chunk's copies and its two products pay for the
# loop around them, small enough that a row of a few hundred tokens does not fold mostly padding.
CHUNK_TOKENS = 256
# A kernel's chunk buffers together stay under this much VMEM (of 16 MiB scoped by default).
CHUNK_VMEM_BYTES = 4 << 20
# Running maximum before any key: finite, so a chunk with no live key folds to zeros and not to NaN.
_M_INIT = -1e30


def newest_position(cur, max_blocks: int, block_size: int):
    """The newest position a row attends to: its frontier, or the table's last token where the frontier
    overshot the table."""
    return jnp.minimum(cur, max_blocks * block_size - 1)


def online_softmax_init(heads: int, width: int):
    """The running softmax before any key: maximum, sum and ``[heads, width]`` accumulator, float32."""
    return (
        jnp.full((heads, 1), _M_INIT, jnp.float32),
        jnp.zeros((heads, 1), jnp.float32),
        jnp.zeros((heads, width), jnp.float32),
    )


def online_softmax(s, m_prev, l_prev):
    """One step of the running softmax over masked scores ``s`` ``[H, cols]`` (float32, ``-inf`` where a
    column is not live): the new maximum, the factor that rescales what was summed under the old one,
    the probabilities under the new one, and the new sum."""
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    return m_new, alpha, p, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)


def walk_live_pages(tbl_ref, cur_ref, side_ref, *, pages, block_size, window, page_copies, zero_buffers, make_fold,
                    init, ring: bool = False):
    """Fold the live pages of this grid step's row, a chunk of ``pages`` at a time, and return the fold's
    last carry: ``init`` itself for a row without pages.

    ``tbl_ref`` ``[B, MB]`` and ``cur_ref`` ``[B]`` are the scalar-prefetched table and frontiers (below zero:
    a row with no keys), ``side_ref`` one int32 of SMEM. ``page_copies(page, side, i)`` gives the async copies
    that bring pool block ``page`` to place ``i`` of chunk buffer ``side``, each on a semaphore of that side.
    ``zero_buffers()`` clears the chunk buffers once a call: a place no copy has filled yet is folded
    under a zero probability and must hold numbers. ``make_fold(cur, first)`` is called for a row that has
    pages, with its frontier and first live page, and returns ``fold(j, side, carry)``, which folds chunk
    ``j`` (pages ``first + j * pages ...``, waiting in buffer ``side``) into the carry that starts at ``init``."""
    b, nrows = pl.program_id(0), pl.num_programs(0)
    max_blocks = tbl_ref.shape[1]

    def span(row):
        """First live page of ``row`` and how many follow it: none below frontier 0 (said outright: a
        division truncates toward zero and a ring clamps nothing, so -1 would come out as one page). The
        clamp keeps a frontier that overshot the table (a slot that finished mid-tick) on the row's own
        last entry."""
        cur = cur_ref[row]
        last = jax.lax.div(cur, block_size)
        if not ring:
            last = jnp.minimum(last, max_blocks - 1)
        first = 0 if window is None else jax.lax.div(jnp.maximum(cur - window + 1, 0), block_size)
        return first, jnp.where(cur < 0, 0, jnp.maximum(last - first + 1, 0))

    def next_walked(after):
        """The first row past ``after`` that has pages to walk; ``nrows`` where none has."""
        return jax.lax.while_loop(
            lambda row: (row < nrows) & (span(jnp.minimum(row, nrows - 1))[1] == 0), lambda row: row + 1, after + 1
        )

    def chunk_copies(row, chunk, side, act):
        """``act`` (start or wait) on the copy of every live page of ``row``'s chunk ``chunk``."""
        first, count = span(row)
        at = chunk * pages

        def one(i, _):
            page = first + at + i
            for copy in page_copies(tbl_ref[row, jax.lax.rem(page, max_blocks) if ring else page], side, i):
                act(copy)

        jax.lax.fori_loop(0, jnp.clip(count - at, 0, pages), one, None)

    start = functools.partial(chunk_copies, act=lambda copy: copy.start())
    wait = functools.partial(chunk_copies, act=lambda copy: copy.wait())

    def start_first_chunk(row, side):
        """Start ``row``'s first chunk into ``side``, unless the call has no such row."""

        @pl.when(row < nrows)
        def _():
            start(row, 0, side)

    @pl.when(b == 0)
    def _first_row():
        zero_buffers()
        side_ref[0] = 0
        start_first_chunk(next_walked(-1), 0)

    cur = cur_ref[b]
    first, count = span(b)
    chunks = pl.cdiv(count, pages)

    def walk():
        side0 = side_ref[0]
        fold = make_fold(cur, first)

        def step(j, carry):
            side = jax.lax.rem(side0 + j, 2)

            @pl.when(j + 1 < chunks)
            def _next_chunk():
                start(b, j + 1, 1 - side)

            @pl.when(j + 1 == chunks)
            def _next_row():
                start_first_chunk(next_walked(b), 1 - side)

            wait(b, j, side)
            return fold(j, side, carry)

        carry = jax.lax.fori_loop(0, chunks, step, init)
        side_ref[0] = jax.lax.rem(side0 + chunks, 2)
        return carry

    # a row without pages takes no turn: its grid step is its q and out blocks and this branch
    return jax.lax.cond(chunks > 0, walk, lambda: init)
