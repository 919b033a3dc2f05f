"""Latent paged decode kernel (ops/pallas_latent_attention.py) vs the XLA gather reference
(``paged_latent_gather_attention``), in Pallas interpret mode on CPU: the walk over a slot's live
pages (frontiers at page and chunk edges, a row of one token, a row of one key at the sink between long
ones, rows handed "no keys", a frontier past the table, pages it must never read holding NaN), float32 and
bf16 pools, a value width under and at the row width, and the frontier ``paged_latent_attention`` hands
the kernel for a slot at the sink."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops import paged_kv
from accelerate_tpu.ops.paged_kv import PagedConfig, paged_latent_attention, paged_latent_gather_attention
from accelerate_tpu.ops.pallas_latent_attention import _pages_per_chunk, latent_paged_decode

SCALE = 0.25


NO_KEYS = "no-keys"  # a row of ``curs`` the kernel is handed a frontier below zero for: a slot that stores into the sink


def _walk(curs, **shape):
    """:func:`_walk_inputs` through the kernel: its output and the reference's."""
    (q, pool, tbl, cur), want, c = _walk_inputs(curs, **shape)
    return latent_paged_decode(q, pool, tbl, cur, value_width=c, scale=SCALE, interpret=True), want


def _walk_inputs(curs, *, h=4, w=48, c=32, bs=128, mb=6, reserve=0, poison=(), dtype=jnp.float32):
    """Rows with the frontiers ``curs`` (None: a row of one key, frontier 0, every entry at the sink;
    ``NO_KEYS``: a row at the sink handed frontier -1, its reference zeros), each
    holding real blocks for ``reserve`` tokens past its frontier as the engine reserves prompt + max_new.
    ``poison`` fills with NaN, after the reference has read the clean pool, what the kernel must never
    fold: ``"reserved"`` the blocks wholly past a frontier, ``"sink_tail"`` a block that an idle row's
    entries beyond the first are pointed at, ``"unwalked"`` the sink and every block no row holds.
    Returns the kernel's arguments ``(q, pool, table, frontiers)``, the reference's output and ``c``."""
    b = len(curs)
    nb = b * mb + 2
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    q = jax.random.normal(keys[0], (b, h, w), dtype)
    pool = np.array(jax.random.normal(keys[1], (nb, w, bs), jnp.float32))
    tbl = np.zeros((b, mb), np.int32)
    cur = np.zeros((b,), np.int32)
    free = list(range(1, nb - 1))
    never = []
    for i, f in enumerate(curs):
        if f is None or f is NO_KEYS:
            cur[i] = 0 if f is None else -1
            if "sink_tail" in poison:
                tbl[i, 1:] = nb - 1
                never.append(nb - 1)
            continue
        cur[i] = f
        live_pages = min(f // bs + 1, mb)
        held = min(mb, (f + reserve) // bs + 1)
        tbl[i, :held] = [free.pop() for _ in range(held)]
        if "reserved" in poison:
            never.extend(tbl[i, live_pages:held])
    tbl, cur = jnp.asarray(tbl), jnp.asarray(cur)
    # the reference in float32 over the values the pool's type holds: it rounds the scores to that type
    as_held = jnp.asarray(pool, dtype).astype(jnp.float32)
    want = paged_latent_gather_attention(
        q.astype(jnp.float32)[:, None], as_held, tbl, jnp.maximum(cur, 0), value_width=c, scale=SCALE
    )[:, 0].astype(dtype)
    want = jnp.where(jnp.asarray([f is NO_KEYS for f in curs])[:, None, None], 0, want)
    pool[never] = np.nan
    if "unwalked" in poison:
        pool[[0] + free] = np.nan
    return (q, jnp.asarray(pool, dtype), tbl, cur), want, c


# pages of 128 tokens go two a chunk (256 tokens): a table of 6 entries is three chunks
WALKS = [
    pytest.param([127, 128, 255, 256], {}, id="frontier-on-last-token-of-a-page-and-first-of-the-next"),
    pytest.param([255, 256, 511, 512], {}, id="frontier-at-chunk-edges"),
    pytest.param([0, 700, 0], {}, id="rows-of-one-token-around-a-long-row"),
    pytest.param([300, 767, 5], {}, id="rows-of-two-and-three-chunks-and-a-full-table"),
    pytest.param([700, None, 333], {}, id="idle-row-between-two-long-rows"),
    pytest.param([None, None], {}, id="every-row-idle"),
    pytest.param([700, NO_KEYS, 333], dict(poison=("unwalked",)), id="row-with-no-keys-between-two-long-rows-the-sink-nan"),
    pytest.param([NO_KEYS, NO_KEYS], dict(poison=("unwalked",)), id="every-row-with-no-keys"),
    pytest.param([768, 3000, 140], {}, id="frontier-past-the-table-overshoot"),
    pytest.param([100, 290, 7], dict(reserve=400, poison=("reserved",)), id="reserved-blocks-past-the-frontier-hold-nan"),
    pytest.param([600, None, None, 130], dict(poison=("sink_tail",)), id="idle-rows-entries-beyond-the-first-hold-nan"),
    pytest.param([400, None, 129], dict(dtype=jnp.bfloat16, reserve=200, poison=("reserved", "sink_tail")), id="bf16-pool"),
    pytest.param([700, 17], dict(c=48), id="value-width-is-the-row-width"),
    pytest.param([70, 31, None, 16], dict(bs=16, mb=8, reserve=20, poison=("reserved",)), id="page-under-128-lanes-takes-one-page-a-chunk"),
    pytest.param([900, 40], dict(bs=256, mb=4, reserve=100, poison=("reserved",)), id="page-of-a-whole-chunk"),
]


@pytest.mark.parametrize("curs,shape", WALKS)
def test_walk_follows_the_live_pages(curs, shape):
    out, want = _walk(curs, **shape)
    bf16 = shape.get("dtype") == jnp.bfloat16
    assert out.dtype == want.dtype and out.shape == want.shape
    assert np.isfinite(np.asarray(out, np.float32)).all(), "a page that is not live reached the fold"
    tol = 2e-2 if bf16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol)


SKIPS = [
    pytest.param([NO_KEYS, 700, 333], {}, id="first-row"),
    pytest.param([700, 333, NO_KEYS], {}, id="last-row"),
    pytest.param([700, NO_KEYS, NO_KEYS, 333, 20], {}, id="two-in-a-row-between-long-rows"),
    pytest.param([NO_KEYS, NO_KEYS, 270, NO_KEYS, 767, NO_KEYS], dict(reserve=40), id="around-every-live-row"),
    pytest.param([NO_KEYS, NO_KEYS, NO_KEYS], {}, id="every-row"),
    pytest.param([NO_KEYS, 0, NO_KEYS, 300, 0], {}, id="a-live-row-of-one-key-is-still-walked"),
    pytest.param([400, NO_KEYS, 129, 3000], dict(dtype=jnp.bfloat16, reserve=200, poison=("sink_tail",)), id="bf16-pool"),
    pytest.param([70, NO_KEYS, 31, NO_KEYS], dict(bs=16, mb=8, reserve=20), id="one-page-a-chunk"),
]


@pytest.mark.parametrize("curs,shape", SKIPS)
def test_a_row_with_no_keys_is_not_walked(curs, shape):
    """A row handed a frontier below zero (a slot that stores into the sink: ``paged_kv.NO_KEYS``) starts no
    copy and folds nothing: the sink page and every block no row holds are NaN and its output is zeros.
    The rows beside it are, to the bit, what the same call gives with those rows at frontier 0 (what the
    caller handed the kernel before) and what a call of the live rows alone gives."""
    live = [i for i, f in enumerate(curs) if f is not NO_KEYS]
    shape = dict(shape, poison=shape.get("poison", ()) + ("unwalked", "reserved"))
    (q, pool, tbl, cur), want, c = _walk_inputs(curs, **shape)
    run = functools.partial(latent_paged_decode, value_width=c, scale=SCALE, interpret=True)
    out = np.asarray(run(q, pool, tbl, cur), np.float32)
    assert not out[[i for i in range(len(curs)) if i not in live]].any(), "a row with no keys returns zeros"
    assert np.isfinite(out).all(), "a page that is not live reached the fold"
    tol = 2e-2 if shape.get("dtype") == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out, np.asarray(want, np.float32), atol=tol, rtol=tol)
    if 0 in curs:
        assert np.abs(out[curs.index(0)]).max() > 1e-3, "a live row at frontier 0 attends to its one key"
    if live:
        at_frontier_zero = run(q, pool.at[0].set(1.0), tbl, jnp.maximum(cur, 0))
        np.testing.assert_array_equal(out[live], np.asarray(at_frontier_zero, np.float32)[live])
        alone = run(q[jnp.asarray(live)], pool, tbl[jnp.asarray(live)], cur[jnp.asarray(live)])
        np.testing.assert_array_equal(out[live], np.asarray(alone, np.float32))


def test_cell_widths_bf16():
    """The longchat cell's own widths (32 heads against one row of 512 + 64, pages of 128 tokens, bf16)
    at a tiny table: two chunks, a reserved tail that holds NaN, an idle row."""
    out, want = _walk([300, None, 47], h=32, w=576, c=512, mb=4, reserve=150, poison=("reserved",), dtype=jnp.bfloat16)
    assert out.dtype == jnp.bfloat16 and np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32), atol=2e-2, rtol=2e-2)


def test_chunk_follows_from_the_shapes():
    """The chunk is the kernel's own business: pages of whole lane tiles go 256 tokens a chunk, within
    the buffers' VMEM; a page that is no whole lane tile goes one a chunk."""
    assert _pages_per_chunk(576, 128, jnp.bfloat16) == 2  # the longchat cell: 288 KiB a buffer
    assert _pages_per_chunk(576, 256, jnp.bfloat16) == 1 and _pages_per_chunk(576, 512, jnp.float32) == 1
    assert _pages_per_chunk(8192, 128, jnp.float32) == 1  # 4 MiB a page: VMEM bounds it, not the tokens
    assert _pages_per_chunk(576, 16, jnp.bfloat16) == 1 and _pages_per_chunk(48, 64, jnp.float32) == 1


class _Layer(nn.Module):
    max_len: int
    cfg: PagedConfig

    @nn.compact
    def __call__(self, q_lat, row):
        return paged_latent_attention(self, q_lat, row, self.max_len, value_width=32, scale=SCALE, cfg=self.cfg)


@pytest.mark.parametrize("grown", [3000, 5119, 9000], ids=["mid-table", "last-entry", "past-the-table"])
def test_a_slot_at_the_sink_is_handed_no_keys(monkeypatch, grown):
    """An idle slot's index grows a token a step after ``clear_slot``; its table row is the sink's. The
    kernel is handed "no keys" for it (``paged_kv.NO_KEYS``), whatever the index has grown to: the sink
    holds NaN everywhere but the column the step just stored and the slot's output is zeros; the live slot
    beside it attends to its own pages as the reference does. Off the kernel the gather is given the
    slot's own frontier, as before."""
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", True)
    h, w, bs, max_len = 4, 48, 128, 5120
    cfg = PagedConfig(block_size=bs, num_blocks=5)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q_lat = jax.random.normal(keys[0], (2, 1, h, w))
    row = jax.random.normal(keys[1], (2, 1, w))
    table = np.zeros((2, max_len // bs), np.int32)
    table[0, :3] = [3, 1, 4]
    cache = {
        "latent_pool": jax.random.normal(keys[2], (5, w, bs)).at[0].set(jnp.nan),
        "block_table": jnp.asarray(table),
        "index": jnp.asarray([300, grown], jnp.int32),
    }
    out, new = _Layer(max_len, cfg).apply({"cache": cache}, q_lat, row, mutable=["cache"])
    pool = new["cache"]["latent_pool"]
    np.testing.assert_array_equal(np.asarray(new["cache"]["index"]), [301, grown + 1])
    np.testing.assert_array_equal(np.asarray(pool[0, :, grown % bs]), np.asarray(row[1, 0]))  # stored in the sink
    want = paged_latent_gather_attention(
        q_lat[:1], pool.at[0].set(0.0), cache["block_table"][:1], jnp.asarray([300], jnp.int32), value_width=32, scale=SCALE
    )  # the gather reads the pad entries' page too, under a zero probability: numbers, then
    np.testing.assert_allclose(np.asarray(out[:1]), np.asarray(want), atol=2e-5, rtol=2e-5)
    assert not np.asarray(out[1]).any(), "a slot at the sink reads nothing and returns zeros"
