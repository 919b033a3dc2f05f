"""Paged-attention decode kernel (ops/pallas_paged_attention.py) vs an
XLA gather reference, in Pallas interpret mode on CPU: MHA/GQA, ragged
per-row frontiers, trash-sink pad entries, sliding-window bands, bf16
inputs, and the walk itself: frontiers at page and chunk edges, rows of
one chunk and of several, pages it must never read (reserved past the
frontier, or before the band) holding NaN, a row of one key between long ones,
rows handed "no keys" (a frontier below zero: no copy, no fold, zeros out, the
rows beside them to the bit what they are without them), the copies the walk
starts and waits for counted one by one,
a flattened layer stack addressed as ``table + layer * NB``, and a pool whose
heads are under the lane width, folded two to a row of 128 lanes (LFM2's
head size 64: ``paged_kv.pool_lane_fold``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental import pallas as pl

from accelerate_tpu.ops.pallas_paged_attention import _pages_per_chunk, paged_decode_attention


def _reference(q, kp, vp, tbl, cur, window=None):
    """The XLA paged math from ops/paged_kv.py, inlined: gather pages,
    mask to (cur - W, cur], softmax, weighted sum."""
    b, h, d = q.shape
    nb, bs, hkv, _ = kp.shape
    mb = tbl.shape[1]
    pos = jnp.arange(mb * bs)
    live = pos[None, :] <= cur[:, None]
    if window is not None:
        live &= pos[None, :] > cur[:, None] - window
    # a key that is not live counts for nothing, whatever its page holds (0 x NaN is NaN)
    k_all = jnp.where(live[:, :, None, None], kp[tbl].reshape(b, mb * bs, hkv, d).astype(jnp.float32), 0.0)
    v_all = jnp.where(live[:, :, None, None], vp[tbl].reshape(b, mb * bs, hkv, d).astype(jnp.float32), 0.0)
    g = h // hkv
    qg = q.astype(jnp.float32).reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_all) / np.sqrt(d)
    s = jnp.where(live[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_all)
    return out.reshape(b, h, d).astype(q.dtype)


def _setup(rng, b, h, hkv, d, bs, mb, nb, max_cur, dtype=jnp.float32):
    keys = jax.random.split(rng, 4)
    q = jax.random.normal(keys[0], (b, h, d), dtype)
    kp = jax.random.normal(keys[1], (nb, bs, hkv, d), dtype)
    vp = jax.random.normal(keys[2], (nb, bs, hkv, d), dtype)
    # each row gets a distinct random set of non-trash blocks for its
    # live region; entries beyond are the trash sink (0), as the engine
    # builds them
    rng_np = np.random.default_rng(0)
    cur = rng_np.integers(0, max_cur + 1, size=b).astype(np.int32)
    tbl = np.zeros((b, mb), np.int32)
    avail = list(range(1, nb))
    for i in range(b):
        used = cur[i] // bs + 1
        picks = rng_np.choice(avail, size=used, replace=False)
        for blk in picks:
            avail.remove(blk)
        tbl[i, :used] = picks
    return q, kp, vp, jnp.asarray(tbl), jnp.asarray(cur)


CASES = [
    # b, h, hkv, d, bs, mb, window
    pytest.param(3, 4, 4, 32, 8, 4, None, id="mha"),
    pytest.param(3, 4, 2, 32, 8, 4, None, id="gqa"),
    pytest.param(2, 4, 2, 32, 8, 4, 5, id="gqa-window"),
    pytest.param(4, 2, 1, 16, 4, 8, None, id="many-pages"),
    pytest.param(2, 4, 2, 32, 8, 4, 100, id="window-wider-than-history"),
]


@pytest.mark.parametrize("b,h,hkv,d,bs,mb,window", CASES)
def test_kernel_matches_gather_reference(b, h, hkv, d, bs, mb, window):
    nb = b * mb + 1
    q, kp, vp, tbl, cur = _setup(jax.random.PRNGKey(1), b, h, hkv, d, bs, mb, nb, max_cur=mb * bs - 1)
    out = paged_decode_attention(q, kp, vp, tbl, cur, sliding_window=window, interpret=True)
    want = _reference(q, kp, vp, tbl, cur, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_zero_frontier_rows():
    """cur=0 (a fresh or inactive slot): only position 0 attends —
    never a NaN from an empty softmax."""
    b, h, hkv, d, bs, mb = 2, 2, 2, 16, 4, 2
    q, kp, vp, tbl, _ = _setup(jax.random.PRNGKey(2), b, h, hkv, d, bs, mb, b * mb + 1, max_cur=0)
    cur = jnp.zeros((b,), jnp.int32)
    out = paged_decode_attention(q, kp, vp, tbl, cur, interpret=True)
    want = _reference(q, kp, vp, tbl, cur)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_bf16_inputs():
    b, h, hkv, d, bs, mb = 2, 4, 2, 32, 8, 3
    q, kp, vp, tbl, cur = _setup(
        jax.random.PRNGKey(3), b, h, hkv, d, bs, mb, b * mb + 1, max_cur=mb * bs - 1, dtype=jnp.bfloat16
    )
    out = paged_decode_attention(q, kp, vp, tbl, cur, interpret=True)
    want = _reference(q, kp, vp, tbl, cur)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=2e-2, rtol=2e-2
    )


def test_window_excludes_old_pages_exactly():
    """A hand-checkable case: window 4 at cur=10 keeps positions 7..10
    only — the kernel must match a dense softmax over exactly those."""
    b, h, hkv, d, bs, mb = 1, 2, 2, 16, 4, 3
    q, kp, vp, tbl, _ = _setup(jax.random.PRNGKey(4), b, h, hkv, d, bs, mb, b * mb + 1, max_cur=11)
    cur = jnp.asarray([10], jnp.int32)
    out = paged_decode_attention(q, kp, vp, tbl, cur, sliding_window=4, interpret=True)
    k_all = kp[tbl].reshape(1, mb * bs, hkv, d)
    v_all = vp[tbl].reshape(1, mb * bs, hkv, d)
    sl = slice(7, 11)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), k_all[:, sl].astype(jnp.float32)) / np.sqrt(d)
    p = jax.nn.softmax(s, axis=-1)
    want = jnp.einsum("bhk,bkhd->bhd", p, v_all[:, sl].astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


NO_KEYS = "no-keys"  # a row of ``curs`` the kernel is handed a frontier below zero for: a slot that stores into the sink


def _walk(curs, *, h, hkv, d, bs, mb, window=None, reserve=0, poison=(), layers=1, layer=0, dtype=jnp.float32, fold=1,
          no_keys_as=-1):
    """Rows with the frontiers ``curs`` (None: a row of one key, frontier 0, every entry at the sink;
    ``NO_KEYS``: a row at the sink handed the frontier ``no_keys_as``, its reference zeros), each
    holding real blocks for ``reserve`` tokens past its frontier as the engine reserves prompt + max_new.
    ``poison`` fills with NaN what the kernel must never fold: ``"reserved"`` the blocks wholly past a
    frontier, ``"before_band"`` those wholly before the window's band, ``"unwalked"`` the sink and every
    block no row holds. With ``layers`` > 1 the pool is a
    flattened stack and the rows address ``table + layer * NB``; the other layers hold NaN throughout.
    With ``fold`` > 1 the kernel is handed the pools lane-folded, ``[NB, bs, hkv / fold, fold * d]`` (the
    same bytes), as ``paged_kv.pool_lane_fold`` declares them; the reference reads them unfolded.
    Returns the kernel's output and the reference's."""
    (q, kp, vp, tbl, cur), want = _walk_inputs(
        curs, h=h, hkv=hkv, d=d, bs=bs, mb=mb, window=window, reserve=reserve, poison=poison, layers=layers, layer=layer,
        dtype=dtype, fold=fold, no_keys_as=no_keys_as)
    return paged_decode_attention(q, kp, vp, tbl, cur, sliding_window=window, interpret=True), want


def _walk_inputs(curs, *, h, hkv, d, bs, mb, window, reserve, poison, layers, layer, dtype, fold, no_keys_as):
    """:func:`_walk`'s arguments of the kernel ``(q, key pool, value pool, table, frontiers)`` and the reference."""
    b = len(curs)
    nb = b * mb + 1
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (b, h, d), dtype)
    kp = np.array(jax.random.normal(keys[1], (nb, bs, hkv, d), jnp.float32))
    vp = np.array(jax.random.normal(keys[2], (nb, bs, hkv, d), jnp.float32))
    tbl = np.zeros((b, mb), np.int32)
    cur = np.zeros((b,), np.int32)
    free = list(range(1, nb))
    for i, c in enumerate(curs):
        if c is None:
            continue
        if c is NO_KEYS:
            cur[i] = no_keys_as
            continue
        cur[i] = c
        live_pages = c // bs + 1
        held = min(mb, (c + reserve) // bs + 1)
        tbl[i, :held] = [free.pop() for _ in range(held)]
        if "reserved" in poison:
            kp[tbl[i, live_pages:held]] = vp[tbl[i, live_pages:held]] = np.nan
        if "before_band" in poison:
            dead = max(c - window + 1, 0) // bs
            kp[tbl[i, :dead]] = vp[tbl[i, :dead]] = np.nan
    if "unwalked" in poison:
        kp[[0] + free] = vp[[0] + free] = np.nan
    tbl, cur = jnp.asarray(tbl), jnp.asarray(cur)
    kp, vp = jnp.asarray(kp, dtype), jnp.asarray(vp, dtype)
    want = _reference(q, kp, vp, tbl, jnp.maximum(cur, 0), window=window)
    want = jnp.where(jnp.asarray([c is NO_KEYS for c in curs])[:, None, None], 0, want)
    if layers > 1:
        stack = jnp.full((layers, nb, bs, hkv, d), jnp.nan, dtype)
        kp = stack.at[layer].set(kp).reshape(layers * nb, bs, hkv, d)
        vp = stack.at[layer].set(vp).reshape(layers * nb, bs, hkv, d)
        tbl = tbl + layer * nb
    if fold > 1:
        kp, vp = (x.reshape(x.shape[0], bs, hkv // fold, fold * d) for x in (kp, vp))
    return (q, kp, vp, tbl, cur), want


# float32 pages of 16 tokens x 2 heads are whole tiles: a chunk is 16 pages, 256 tokens
CHUNKED = dict(h=4, hkv=2, d=32, bs=16, mb=40)

HEAD64 = dict(h=32, hkv=8, d=64, bs=16, mb=40, fold=2)

WALKS = [
    pytest.param([15, 16, 31, 32], CHUNKED, id="frontier-on-last-token-of-a-page-and-first-of-the-next"),
    pytest.param([255, 254, 256], CHUNKED, id="row-of-exactly-one-chunk-and-one-token-either-side"),
    pytest.param([300, 639, 5], CHUNKED, id="rows-of-two-and-three-chunks"),
    pytest.param([100, 290, 7], dict(CHUNKED, reserve=200, poison=("reserved",)), id="reserved-blocks-past-the-frontier-hold-nan"),
    pytest.param(
        [400, 620, 130], dict(CHUNKED, window=200, poison=("before_band", "reserved"), reserve=20),
        id="band-starts-mid-chunk-after-nan-pages",
    ),
    pytest.param([500, None, 333], CHUNKED, id="idle-row-between-two-long-rows"),
    pytest.param([None, None], CHUNKED, id="every-row-idle"),
    pytest.param([500, NO_KEYS, 333], dict(CHUNKED, poison=("unwalked",)), id="row-with-no-keys-between-two-long-rows-the-sink-nan"),
    pytest.param([NO_KEYS, NO_KEYS], dict(CHUNKED, poison=("unwalked",)), id="every-row-with-no-keys"),
    pytest.param([NO_KEYS, 270, None], dict(CHUNKED, layers=2, layer=1), id="row-with-no-keys-in-the-second-layer-of-a-stack"),
    pytest.param([270, 40], dict(CHUNKED, layers=2, layer=1, reserve=30, poison=("reserved",)), id="second-layer-of-a-flattened-stack"),
    pytest.param([270, 17, None], dict(h=4, hkv=1, d=32, bs=8, mb=40), id="one-kv-head"),
    pytest.param([270, 17, None], dict(h=2, hkv=2, d=32, bs=16, mb=20), id="one-query-head-a-kv-head"),
    pytest.param([21, 9], dict(h=2, hkv=1, d=16, bs=4, mb=8, reserve=6, poison=("reserved",)), id="page-under-a-tile-takes-one-page-a-chunk"),
    # head size 64 on 8 key/value heads (LFM2-8B-A1B), two heads to a row of 128 lanes
    pytest.param([15, 16, 255, 256], HEAD64, id="head64-folded-frontiers-at-page-and-chunk-edges"),
    pytest.param([300, None, 639], dict(HEAD64, reserve=200, poison=("reserved",)), id="head64-folded-idle-row-and-nan-past-the-frontier"),
    pytest.param([400, 130], dict(HEAD64, window=200, poison=("before_band",)), id="head64-folded-band"),
    pytest.param([270, 40], dict(HEAD64, layers=2, layer=1), id="head64-folded-second-layer-of-a-stack"),
    pytest.param([70, 5], dict(h=8, hkv=4, d=32, bs=8, mb=12, fold=4), id="head32-folded-four-to-a-row"),
]


@pytest.mark.parametrize("curs,shape", WALKS)
def test_walk_follows_the_live_pages(curs, shape):
    out, want = _walk(curs, **shape)
    assert np.isfinite(np.asarray(out)).all(), "a page that is not live reached the fold"
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


SKIPS = [
    pytest.param([NO_KEYS, 500, 333], CHUNKED, id="first-row"),
    pytest.param([500, 333, NO_KEYS], CHUNKED, id="last-row"),
    pytest.param([500, NO_KEYS, NO_KEYS, 333, 20], CHUNKED, id="two-in-a-row-between-long-rows"),
    pytest.param([NO_KEYS, NO_KEYS, 270, NO_KEYS, 639, NO_KEYS], dict(CHUNKED, reserve=40), id="around-every-live-row"),
    pytest.param([NO_KEYS, NO_KEYS, NO_KEYS], CHUNKED, id="every-row"),
    pytest.param([400, NO_KEYS, 620, NO_KEYS, 130], dict(CHUNKED, window=200, reserve=20), id="under-a-band"),
    pytest.param([300, NO_KEYS, NO_KEYS, 639, NO_KEYS, 16], dict(HEAD64, reserve=200), id="head64-folded"),
    pytest.param([NO_KEYS, 0, NO_KEYS, 300, 0], CHUNKED, id="a-live-row-of-one-key-is-still-walked"),
    pytest.param([21, NO_KEYS, 9, NO_KEYS], dict(h=2, hkv=1, d=16, bs=4, mb=8, reserve=6), id="one-page-a-chunk"),
]


@pytest.mark.parametrize("curs,shape", SKIPS)
def test_a_row_with_no_keys_is_not_walked(curs, shape):
    """A row handed a frontier below zero (a slot that stores into the sink: ``paged_kv.NO_KEYS``) starts no
    copy and folds nothing: the sink page and every block no row holds are NaN and its output is zeros.
    The rows beside it are, to the bit, what the same call gives with those rows at frontier 0 (what the
    callers handed the kernel before) and what a call of the live rows alone gives."""
    live = [i for i, c in enumerate(curs) if c is not NO_KEYS]
    shape = dict(dict(window=None, reserve=0, layers=1, layer=0, dtype=jnp.float32, fold=1), **shape)
    (q, kp, vp, tbl, cur), want = _walk_inputs(curs, poison=("unwalked", "reserved"), no_keys_as=-1, **shape)
    run = functools.partial(paged_decode_attention, sliding_window=shape["window"], interpret=True)
    out = np.asarray(run(q, kp, vp, tbl, cur))
    assert not out[[i for i in range(len(curs)) if i not in live]].any(), "a row with no keys returns zeros"
    assert np.isfinite(out).all(), "a page that is not live reached the fold"
    np.testing.assert_allclose(out, np.asarray(want), atol=2e-5, rtol=2e-5)
    if 0 in curs:
        assert np.abs(out[curs.index(0)]).max() > 1e-3, "a live row at frontier 0 attends to its one key"
    if live:
        at_frontier_zero = run(q, kp.at[0].set(1.0), vp.at[0].set(1.0), tbl, jnp.maximum(cur, 0))
        np.testing.assert_array_equal(out[live], np.asarray(at_frontier_zero)[live])
        alone = run(q[jnp.asarray(live)], kp, vp, tbl[jnp.asarray(live)], cur[jnp.asarray(live)])
        np.testing.assert_array_equal(out[live], np.asarray(alone))


def _copies_of_a_walk(curs, *, pages, bs, mb, window=None, ring=False):
    """Run :func:`paged_walk.walk_live_pages` alone over rows with the frontiers ``curs`` (their table
    entry ``e`` names block ``1000 * row + e + 1``), with copies that do nothing but count: the blocks
    started in order, and how many copies were started and waited for on each buffer."""
    from jax.experimental.pallas import tpu as pltpu

    from accelerate_tpu.ops.paged_walk import walk_live_pages

    b, room = len(curs), 4096

    class Counted:
        def __init__(self, refs, page, side):
            self.refs, self.page, self.side = refs, page, side

        def start(self):
            log, n, started, _ = self.refs
            log[n[0]] = self.page
            n[0] += 1
            started[self.side] += 1

        def wait(self):
            self.refs[3][self.side] += 1

    def kernel(tbl_ref, cur_ref, log, n, started, waited, folds, side_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            n[0] = 0
            folds[0] = 0
            for side in range(2):
                started[side] = waited[side] = 0

        def make_fold(cur, first):
            def fold(j, side, carry):
                folds[0] += 1
                return carry + 1

            return fold

        walk_live_pages(
            tbl_ref, cur_ref, side_ref, pages=pages, block_size=bs, window=window, ring=ring, init=jnp.int32(0),
            page_copies=lambda page, side, i: (Counted((log, n, started, waited), page, side),),
            zero_buffers=lambda: None, make_fold=make_fold,
        )

    tbl = 1000 * np.arange(b)[:, None] + np.arange(mb)[None, :] + 1
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    log, n, started, waited, folds = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(s, jnp.int32) for s in ((room,), (1,), (2,), (2,), (1,))],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,), in_specs=[], out_specs=[smem] * 5,
            scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        ),
        interpret=True,
    )(jnp.asarray(tbl, jnp.int32), jnp.asarray(curs, jnp.int32))
    return [int(x) for x in log[: int(n[0])]], np.asarray(started), np.asarray(waited), int(folds[0])


COPIES = [
    pytest.param([40, -1, -1, 7, -1], dict(pages=2, bs=4, mb=16), id="rows-without-keys-between-and-after"),
    pytest.param([-1, -1, 3, 100], dict(pages=2, bs=4, mb=16), id="rows-without-keys-first-and-a-frontier-past-the-table"),
    pytest.param([-1, -1, -1], dict(pages=2, bs=4, mb=16), id="every-row-without-keys"),
    pytest.param([0, -1, 0], dict(pages=2, bs=4, mb=16), id="rows-of-one-key"),
    pytest.param([90, -1, 33, -1, -1, 200], dict(pages=3, bs=4, mb=9, window=20, ring=True), id="a-band-through-a-ring"),
    pytest.param([70, -1, 90], dict(pages=2, bs=4, mb=16, window=8), id="a-band-that-left-its-table-has-no-pages"),
]


@pytest.mark.parametrize("curs,walk", COPIES)
def test_every_copy_the_walk_starts_is_a_live_page_and_is_waited_for(curs, walk):
    """The walk's copies counted one by one: the blocks started are exactly the live pages of the rows that
    have keys, row by row in order, every one waited for on the buffer it was started on, one fold a chunk;
    a row handed a frontier below zero starts none, and a call of such rows alone starts none at all."""
    pages, bs, mb, window, ring = walk["pages"], walk["bs"], walk["mb"], walk.get("window"), walk.get("ring", False)
    want, chunks = [], 0
    for row, cur in enumerate(curs):
        if cur < 0:
            continue
        last = cur // bs if ring else min(cur // bs, mb - 1)
        first = 0 if window is None else max(cur - window + 1, 0) // bs
        held = range(first, last + 1)
        want += [1000 * row + (p % mb if ring else p) + 1 for p in held]
        chunks += -(-len(held) // pages)
    log, started, waited, folds = _copies_of_a_walk(curs, **walk)
    assert log == want
    np.testing.assert_array_equal(started, waited)
    assert started.sum() == len(want) and folds == chunks


def test_cell_widths_bf16():
    """The chat cell's own widths (32 query heads over 8 key/value heads of 128, pages of 16 tokens,
    bf16) at a tiny table: two chunks, a reserved tail that holds NaN, an idle row."""
    out, want = _walk(
        [300, None, 47], h=32, hkv=8, d=128, bs=16, mb=24, reserve=40, poison=("reserved",), dtype=jnp.bfloat16
    )
    assert out.dtype == jnp.bfloat16 and np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32), atol=2e-2, rtol=2e-2)


def test_lfm2_cell_widths_bf16_folded():
    """The ``lfm2-8b-a1b-serve-longanswer`` cell's widths (32 query heads over 8 key/value heads of 64,
    pages of 16 tokens, bf16, the pool folded two heads to a row) at a tiny table; and the same pool
    handed over unfolded gives the same numbers (interpreted: the chip's compiler refuses a minor axis of 64)."""
    shape = dict(h=32, hkv=8, d=64, bs=16, mb=24, reserve=40, poison=("reserved",), dtype=jnp.bfloat16)
    out, want = _walk([300, None, 47, 2303 % 384], fold=2, **shape)
    assert out.dtype == jnp.bfloat16 and out.shape == (4, 32, 64) and np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32), atol=2e-2, rtol=2e-2)
    unfolded, _ = _walk([300, None, 47, 2303 % 384], **shape)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(unfolded, np.float32), atol=1e-2)


def test_pool_is_folded_only_where_a_head_is_under_the_lane_width():
    from accelerate_tpu.ops.paged_kv import pool_lane_fold

    assert (pool_lane_fold(8, 64), pool_lane_fold(8, 128), pool_lane_fold(1, 128), pool_lane_fold(2, 16)) == (2, 1, 1, 1)
    assert pool_lane_fold(8, 32) == 4 and pool_lane_fold(1, 64) == 1 and pool_lane_fold(8, 96) == 1


def test_chunk_follows_from_the_shapes():
    """The chunk is the kernel's own business: pages of whole tiles stack to 256 tokens, within the
    buffers' VMEM; a page that is no whole tile (8 rows of 32 bits) goes one a chunk."""
    assert _pages_per_chunk(16, 8, 128, jnp.bfloat16) == 16  # the chat cell: 2048 rows, 512 KiB a buffer
    assert _pages_per_chunk(128, 8, 128, jnp.bfloat16) == 2
    assert _pages_per_chunk(16, 32, 256, jnp.float32) == 2  # 512 KiB a page: VMEM bounds it, not the tokens
    assert _pages_per_chunk(4, 1, 16, jnp.float32) == 1 and _pages_per_chunk(8, 1, 128, jnp.bfloat16) == 1
    assert _pages_per_chunk(16, 4, 128, jnp.bfloat16) == 16  # the lfm2 cell, folded: 1024 rows of 128 lanes
