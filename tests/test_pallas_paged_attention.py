"""Paged-attention decode kernel (ops/pallas_paged_attention.py) vs an
XLA gather reference, in Pallas interpret mode on CPU: MHA/GQA, ragged
per-row frontiers, trash-sink pad entries, sliding-window bands, bf16
inputs, and the walk itself: frontiers at page and chunk edges, rows of
one chunk and of several, pages it must never read (reserved past the
frontier, or before the band) holding NaN, an idle row between long ones,
a flattened layer stack addressed as ``table + layer * NB``, and a pool whose
heads are under the lane width, folded two to a row of 128 lanes (LFM2's
head size 64: ``paged_kv.pool_lane_fold``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


def _walk(curs, *, h, hkv, d, bs, mb, window=None, reserve=0, poison=(), layers=1, layer=0, dtype=jnp.float32, fold=1):
    """Rows with the frontiers ``curs`` (None: an idle row, frontier 0, every entry at the sink), each
    holding real blocks for ``reserve`` tokens past its frontier as the engine reserves prompt + max_new.
    ``poison`` fills with NaN what the kernel must never fold: ``"reserved"`` the blocks wholly past a
    frontier, ``"before_band"`` those wholly before the window's band. With ``layers`` > 1 the pool is a
    flattened stack and the rows address ``table + layer * NB``; the other layers hold NaN throughout.
    With ``fold`` > 1 the kernel is handed the pools lane-folded, ``[NB, bs, hkv / fold, fold * d]`` (the
    same bytes), as ``paged_kv.pool_lane_fold`` declares them; the reference reads them unfolded.
    Returns the kernel's output and the reference's."""
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
        cur[i] = c
        live_pages = c // bs + 1
        held = min(mb, (c + reserve) // bs + 1)
        tbl[i, :held] = [free.pop() for _ in range(held)]
        if "reserved" in poison:
            kp[tbl[i, live_pages:held]] = vp[tbl[i, live_pages:held]] = np.nan
        if "before_band" in poison:
            dead = max(c - window + 1, 0) // bs
            kp[tbl[i, :dead]] = vp[tbl[i, :dead]] = np.nan
    tbl, cur = jnp.asarray(tbl), jnp.asarray(cur)
    kp, vp = jnp.asarray(kp, dtype), jnp.asarray(vp, dtype)
    want = _reference(q, kp, vp, tbl, cur, window=window)
    if layers > 1:
        stack = jnp.full((layers, nb, bs, hkv, d), jnp.nan, dtype)
        kp = stack.at[layer].set(kp).reshape(layers * nb, bs, hkv, d)
        vp = stack.at[layer].set(vp).reshape(layers * nb, bs, hkv, d)
        tbl = tbl + layer * nb
    if fold > 1:
        kp, vp = (x.reshape(x.shape[0], bs, hkv // fold, fold * d) for x in (kp, vp))
    return paged_decode_attention(q, kp, vp, tbl, cur, sliding_window=window, interpret=True), want


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
