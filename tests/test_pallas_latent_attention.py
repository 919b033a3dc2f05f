"""Latent paged decode kernel (ops/pallas_latent_attention.py) vs the XLA gather reference
(``paged_latent_gather_attention``), in Pallas interpret mode on CPU: the walk over a slot's live
pages (frontiers at page and chunk edges, a row of one token, an idle row between long ones, a frontier
past the table, pages it must never read holding NaN), float32 and bf16 pools, a value width under and
at the row width, and the frontier ``paged_latent_attention`` hands the kernel for a slot at the sink."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops import paged_kv
from accelerate_tpu.ops.paged_kv import PagedConfig, paged_latent_attention, paged_latent_gather_attention
from accelerate_tpu.ops.pallas_latent_attention import _pages_per_chunk, latent_paged_decode

SCALE = 0.25


def _walk(curs, *, h=4, w=48, c=32, bs=128, mb=6, reserve=0, poison=(), dtype=jnp.float32):
    """Rows with the frontiers ``curs`` (None: an idle row, frontier 0, every entry at the sink), each
    holding real blocks for ``reserve`` tokens past its frontier as the engine reserves prompt + max_new.
    ``poison`` fills with NaN, after the reference has read the clean pool, what the kernel must never
    fold: ``"reserved"`` the blocks wholly past a frontier, ``"sink_tail"`` a block that an idle row's
    entries beyond the first are pointed at. Returns the kernel's output and the reference's."""
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
        if f is None:
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
        q.astype(jnp.float32)[:, None], as_held, tbl, cur, value_width=c, scale=SCALE
    )[:, 0].astype(dtype)
    pool[never] = np.nan
    out = latent_paged_decode(q, jnp.asarray(pool, dtype), tbl, cur, value_width=c, scale=SCALE, interpret=True)
    return out, want


# pages of 128 tokens go two a chunk (256 tokens): a table of 6 entries is three chunks
WALKS = [
    pytest.param([127, 128, 255, 256], {}, id="frontier-on-last-token-of-a-page-and-first-of-the-next"),
    pytest.param([255, 256, 511, 512], {}, id="frontier-at-chunk-edges"),
    pytest.param([0, 700, 0], {}, id="rows-of-one-token-around-a-long-row"),
    pytest.param([300, 767, 5], {}, id="rows-of-two-and-three-chunks-and-a-full-table"),
    pytest.param([700, None, 333], {}, id="idle-row-between-two-long-rows"),
    pytest.param([None, None], {}, id="every-row-idle"),
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
def test_a_slot_at_the_sink_is_handed_frontier_zero(monkeypatch, grown):
    """An idle slot's index grows a token a step after ``clear_slot``; its table row is the sink's. The
    kernel is handed frontier 0 for it, so it attends to the sink's first column alone, whatever the
    index has grown to, and the live slot beside it to its own pages as the reference does."""
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", True)
    h, w, bs, max_len = 4, 48, 128, 5120
    cfg = PagedConfig(block_size=bs, num_blocks=5)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q_lat = jax.random.normal(keys[0], (2, 1, h, w))
    row = jax.random.normal(keys[1], (2, 1, w))
    table = np.zeros((2, max_len // bs), np.int32)
    table[0, :3] = [3, 1, 4]
    cache = {
        "latent_pool": jax.random.normal(keys[2], (5, w, bs)),
        "block_table": jnp.asarray(table),
        "index": jnp.asarray([300, grown], jnp.int32),
    }
    out, new = _Layer(max_len, cfg).apply({"cache": cache}, q_lat, row, mutable=["cache"])
    pool = new["cache"]["latent_pool"]
    np.testing.assert_array_equal(np.asarray(new["cache"]["index"]), [301, grown + 1])
    np.testing.assert_array_equal(np.asarray(pool[0, :, grown % bs]), np.asarray(row[1, 0]))  # stored in the sink
    want = paged_latent_gather_attention(
        q_lat, pool, cache["block_table"], jnp.asarray([300, 0], jnp.int32), value_width=32, scale=SCALE
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
    first_column = np.broadcast_to(np.asarray(pool[0, :32, 0]), (h, 32))
    np.testing.assert_allclose(np.asarray(out[1, 0]), first_column, atol=1e-6)
