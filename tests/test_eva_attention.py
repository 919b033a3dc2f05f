"""EVA, chunked linearised attention (``ops/eva_attention.py``), and what the serving engine does for a model that
has it (``models/evabyte.py``): the pooling against a plain loop, the dense cache against the forward pass, the
table a decode step gathers through the interpreted kernel against XLA's gather, the pages a request reserves, what
a window's close gives back, the counts a tick carries, and every refusal over summary pages, each by name. The
program against its plain reference (logits) is ``tests/chipbench/test_chipbench_evabyte.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import EvaByteConfig, create_evabyte_model
from accelerate_tpu.ops import eva_attention, paged_kv
from accelerate_tpu.scheduling import SchedulerConfig
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.telemetry.trace import phase_log

WINDOW, CHUNK = 32, 4


@pytest.fixture(scope="module")
def model():
    m = create_evabyte_model(EvaByteConfig.tiny(), seed=3, seq_len=16)
    # the zoo's initialiser (0.02) makes both pooling softmaxes flat: a wrong pooling would pass. Draw them wide.
    keys = iter(jax.random.split(jax.random.key(9), 8))
    for layer in ("layer_0", "layer_1"):
        for name in ("adaptive_mu_k", "adaptive_phi"):
            m.params[layer]["attn"][name] = 2.0 * jax.random.normal(next(keys), m.params[layer]["attn"][name].shape)
    return m


def _engine(model, **kw):
    kw = {"num_slots": 3, "prompt_buckets": (8, 32, 64), "max_len": 128, "tick_block": 8, "paged_block_size": CHUNK, **kw}
    return ServingEngine(model, **kw)


def test_pooling_is_the_plain_loop():
    """A chunk's summaries, written out: for every head, softmax weights over the chunk's own sixteen (here four)
    keys against ``mu`` pool the keys, and against ``phi`` the values. Float32 sums in another order: 1e-6."""
    rng = np.random.default_rng(0)
    k, v = rng.normal(size=(2, 12, 3, 8)).astype(np.float32), rng.normal(size=(2, 12, 3, 8)).astype(np.float32)
    mu, phi = rng.normal(size=(3, 8)).astype(np.float32), rng.normal(size=(3, 8)).astype(np.float32)
    got_k, got_v = eva_attention.pool_chunks(jnp.asarray(k), jnp.asarray(v), jnp.asarray(mu), jnp.asarray(phi), chunk=4, scale=0.35)
    assert got_k.shape == got_v.shape == (2, 3, 3, 8)
    for b in range(2):
        for m in range(3):
            for h in range(3):
                rows = slice(4 * m, 4 * m + 4)
                a = np.exp(0.35 * k[b, rows, h] @ mu[h])
                bb = np.exp(0.35 * k[b, rows, h] @ phi[h])
                np.testing.assert_allclose(got_k[b, m, h], (a / a.sum()) @ k[b, rows, h], atol=1e-6)
                np.testing.assert_allclose(got_v[b, m, h], (bb / bb.sum()) @ v[b, rows, h], atol=1e-6)
    with pytest.raises(ValueError, match="whole number of chunks"):
        eva_attention.pool_chunks(jnp.asarray(k[:, :10]), jnp.asarray(v[:, :10]), mu, phi, chunk=4, scale=1.0)


@pytest.mark.parametrize("rows", [24, 32, 64, 80], ids=["inside_window", "one_window", "two_windows", "into_a_third"])
def test_prefill_through_the_flash_kernel_is_the_masked_product(rows, monkeypatch):
    """``[summaries | the window's rows, causal]`` with the summaries ahead is a causal mask aligned bottom-right: the
    flash kernel (interpreted here) forms no score matrix and gives the masked product's numbers (float32, another
    order of sums). A window of ``FLASH_MIN_ROWS`` rows runs it, a shorter tail the masked product; off the chip
    nothing does unasked."""
    rng = np.random.default_rng(rows)
    q, k, v = (jnp.asarray(rng.normal(size=(1, rows, 4, 16)).astype(np.float32)) for _ in range(3))
    mu, phi = (jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32)) for _ in range(2))
    prefill = lambda: eva_attention.eva_prefill_attention(q, k, v, mu, phi, window=WINDOW, chunk=CHUNK, scale=0.25)  # noqa: E731
    import accelerate_tpu.ops.attention as attention

    calls, kernel = [], attention.sharded_pallas_attention
    monkeypatch.setattr(attention, "sharded_pallas_attention", lambda *a, **kw: calls.append((a[0].shape[1], kw["interpret"])) or kernel(*a, **kw))
    monkeypatch.setattr(eva_attention, "FLASH_MIN_ROWS", WINDOW)
    plain, sk, sv = prefill()
    assert not calls, "off the chip the choice is the masked product"
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", True)
    flash, fk, fv = prefill()
    assert calls == [(WINDOW, True)] * (rows // WINDOW)
    np.testing.assert_allclose(flash, plain, atol=2e-6)
    np.testing.assert_array_equal(fk, sk), np.testing.assert_array_equal(fv, sv)


def test_dense_cache_prefill_then_steps_are_the_forward_pass(model):
    """``generate``'s path: a prefill that starts the cache runs by windows, every later token one masked product over
    ``[every summary | every row]``; both are the cache-free forward pass (which is the reference's: the chipbench test)."""
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 320, size=(1, 100)))
    full = model.apply_fn(model.params, ids)
    logits, cache = model.apply_fn(model.params, ids[:, :37], positions=jnp.arange(37)[None], decode=True, cache=None)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, :37]), atol=2e-5)
    assert cache["layer_0"]["attn"]["summary_key"].shape == (1, 128 // CHUNK, 4, 16)
    for t in range(37, 100):
        step, cache = model.apply_fn(model.params, ids[:, t : t + 1], positions=jnp.full((1, 1), t), decode=True, cache=cache)
        np.testing.assert_allclose(np.asarray(step[0, 0]), np.asarray(full[0, t]), atol=2e-5)
    assert float(jnp.abs(full).max()) > 1.0


def test_gathered_table_and_frontier():
    """A slot at position 70 of windows of 32 in pages of 4: two windows closed, so four summary pages (two a window),
    then the open window's pages 16.., and the frontier counts 16 summaries and 70 - 64 rows; a slot in its first
    window reads its block table from entry 0; one that overshot the cache stays in the last window."""
    block_table = jnp.arange(100, 132)[None].repeat(3, 0)
    summary_table = jnp.arange(200, 208)[None].repeat(3, 0)
    table, frontier = eva_attention.gather_table(
        block_table, summary_table, jnp.asarray([70, 9, 131]), block_size=4, window=32, chunk=4, max_len=128)
    assert table.shape == (3, eva_attention.gathered_width(128, 4, 32, 4)) == (3, 2 * 3 + 8)
    assert table[0].tolist()[:12] == [200, 201, 202, 203, 116, 117, 118, 119, 120, 121, 122, 123]  # what follows is never reached
    assert table[1].tolist()[:8] == list(range(100, 108)) and frontier.tolist() == [16 + 6, 9, 24 + 35]
    assert table[2].tolist()[:8] == [200, 201, 202, 203, 204, 205, 124, 125]


def test_interpreted_kernel_over_the_gathered_table_is_the_gather_path():
    """What a decode step hands ``paged_decode_attention``: whole summary pages and then the open window's pages, a
    frontier in rows of that table, no mask of its own. The interpreted kernel against XLA's gather over the same
    table, slots before and past a close and an idle one (frontier 0), at 8 key/value heads."""
    from accelerate_tpu.ops.pallas_paged_attention import paged_decode_attention

    rng = np.random.default_rng(1)
    pool_k = jnp.asarray(rng.normal(size=(40, 4, 8, 16)).astype(np.float32))
    pool_v = jnp.asarray(rng.normal(size=(40, 4, 8, 16)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(3, 8, 16)).astype(np.float32))
    block_table = jnp.asarray(rng.permutation(np.arange(1, 33))[None].repeat(3, 0).astype(np.int32))
    summary_table = jnp.asarray(np.arange(33, 39)[None].repeat(3, 0).astype(np.int32))
    table, frontier = eva_attention.gather_table(
        block_table, summary_table, jnp.asarray([70, 9, 0]), block_size=4, window=32, chunk=4, max_len=96)
    want = paged_kv.paged_gather_attention(q[:, None], pool_k, pool_v, table, frontier, scale=0.25)[:, 0]
    got = paged_decode_attention(q, pool_k, pool_v, table, frontier, scale=0.25, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("grown", [8, 70, 200], ids=["first_window", "past_two_closes", "past_the_context"])
def test_a_slot_at_the_sink_is_handed_no_keys(monkeypatch, grown):
    """``eva_paged_attention``: an idle slot's tables are the sink's, its row is stored there, and the kernel is handed
    ``paged_kv.NO_KEYS`` in place of the gathered table's frontier, whatever the index has grown to: the sink is NaN but
    for the row just stored, the slot's output zeros, the slot beside it (past two closes) what the gather path gives
    for it over a clean sink."""
    import flax.linen as nn

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, q, k, v, mu, phi):
            cfg = paged_kv.PagedConfig(4, 40)
            return eva_attention.eva_paged_attention(self, q, k, v, mu, phi, 96, window=32, chunk=4, scale=0.25, cfg=cfg)

    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 1, 8, 16)), jnp.float32) for _ in range(3))
    mu, phi = (jnp.asarray(rng.normal(size=(8, 16)), jnp.float32) for _ in range(2))
    block_table = np.zeros((2, 24), np.int32)
    block_table[0] = rng.permutation(np.arange(1, 25))
    summary_table = np.zeros((2, eva_attention.summary_pages(96, 4, 4)), np.int32)
    summary_table[0] = np.arange(33, 33 + summary_table.shape[1])

    def cache(sink):
        pools = [jnp.asarray(rng_.normal(size=(40, 4, 8, 16)), jnp.float32).at[0].set(sink) for rng_ in (np.random.default_rng(3), np.random.default_rng(4))]
        return {"key_pool": pools[0], "value_pool": pools[1], "block_table": jnp.asarray(block_table), "summary_table": jnp.asarray(summary_table),
                "index": jnp.asarray([70, grown], jnp.int32)}

    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", True)
    out, new = Layer().apply({"cache": cache(jnp.nan)}, q, k, v, mu, phi, mutable=["cache"])
    np.testing.assert_array_equal(np.asarray(new["cache"]["key_pool"][0, grown % 4]), np.asarray(k[1, 0]))  # stored in the sink
    assert not np.asarray(out[1]).any(), "a slot at the sink reads nothing and returns zeros"
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", False)
    want, _ = Layer().apply({"cache": cache(0.0)}, q, k, v, mu, phi, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want[0]), atol=2e-6)


@pytest.mark.parametrize("total,max_new,exact,summary", [
    (5, 10, 4, 0),      # positions 5..13 kept: pages 0..3 of window 0, no window closed
    (5, 40, 8, 2),      # crosses into window 1: a whole window of pages, and window 0's summaries are read
    (32, 20, 5, 2),     # the prompt ends on a window's edge: nothing exact is pasted, window 1 holds 32..50
    (45, 60, 8, 6),     # 45..103: windows 1, 2, 3; windows 0-2 are read past
    (64, 1, 0, 2),      # one token, no decode step: the summaries of what a step at 63 read stay reserved
    (30, 3, 8, 0),      # 30, 31 kept: window 0 is filled to its end and never read past
])
def test_reservation_rule(model, total, max_new, exact, summary):
    """``min(total + max_new - 1, window) / block`` exact pages from the first window held (``total // window``) and two
    summary pages a window the request will close AND read past: ``_aligned_pages``, which ``submit``'s feasibility
    check and the admission share."""
    engine = _engine(model)
    assert engine._aligned_pages(total, max_new) == (exact, summary)
    assert engine._new_blocks_for(0, total, max_new) == exact + summary
    free = engine.pool_free_blocks
    owned, shared, table, write_row = engine._reserve_aligned(total, max_new)
    held, summary_row, last = engine._reserved_summary
    assert engine.pool_free_blocks == free - exact - summary and shared == {} and last == total + max_new - 2
    assert len(owned) == exact and len(held) == summary and (summary_row != 0).sum() == summary
    first = 8 * (total // WINDOW)
    assert sorted(owned) == list(range(first, first + exact)) and (write_row != 0).sum() == exact
    live = np.flatnonzero(table)
    assert live.tolist() == list(range(first, max(first, last // CHUNK + 1))) if exact else not live.size
    assert all(table[i] == owned[first + (i - first) % 8] for i in live), "a later window's entry names the block of the window before"


def test_a_close_gives_blocks_back_and_the_counts_say_what_was_read(model):
    """One request of 5 + 40 tokens alone: window 0 closes when position 31 is written, inside the fourth tick of
    eight steps. After that tick the slot keeps the 3 blocks the rest of the request fills (32..43) of the 8 it
    held and 5 go back to the allocator; its two summary pages stay; the tick's counts are the arithmetic of the
    two rules; at the end every page is free again."""
    engine = _engine(model)
    free = engine.pool_free_blocks
    uid = engine.submit(np.arange(5, dtype=np.int32) + 7, max_new_tokens=40)
    engine.step()  # admission, first token (position 5 is fed), steps 5..12
    assert engine.pool_free_blocks == free - 10 and engine.metrics.windows_closed == 0
    assert (engine.metrics.exact_pages_held, engine.metrics.summary_pages_held) == (8, 2)
    engine.step()  # 13..20
    engine.step()  # 21..28
    rows, context = engine.metrics.attn_rows_read, engine.metrics.context_rows
    assert rows == context == sum(t + 1 for t in range(5, 29)), "no window closed: every row of the context is read"
    engine.step()  # 29..36: the close is in the middle of this tick
    assert engine.metrics.windows_closed == 1 and engine.pool_free_blocks == free - 3 - 2
    assert (engine.metrics.exact_pages_held, engine.metrics.summary_pages_held) == (3, 2)
    assert sorted(engine._slot_blocks[0]) == [8, 9, 10]

    assert engine.metrics.context_rows - context == sum(t + 1 for t in range(29, 37))
    assert engine.metrics.attn_rows_read - rows == 30 + 31 + 32 + sum(8 + t - 32 + 1 for t in range(32, 37))
    engine.run()
    assert engine.pool_free_blocks == free and len(engine.partial(uid)) == 40
    assert engine.metrics.chunks_pooled == sum(t % CHUNK == CHUNK - 1 for t in range(5, 44))
    records = [r for r in phase_log().roots("engine.tick") if "engine.window.close" in r.children]
    assert records and records[-1].done["windows_closed"] == 1 and records[-1].done["summary_pages"] == 2


def test_every_refusal_over_summary_pages_is_by_name(model):
    """What is not built over the second table says so: prefix reuse, chunk windows (a prompt past the largest
    bucket), preemption with resume, KV hand-off and export with KV; and a page that is no chunk."""
    engine = _engine(model)
    with pytest.raises(NotImplementedError, match="prefix reuse .*summary pages"):
        engine.register_prefix(np.arange(8))
    with pytest.raises(NotImplementedError, match="chunk windows .*largest prefill bucket 64"):
        engine.submit(np.arange(70, dtype=np.int32), max_new_tokens=4)
    with pytest.raises(NotImplementedError, match="preemption with resume"):
        _engine(model, scheduler=SchedulerConfig(enable_preemption=True))
    with pytest.raises(NotImplementedError, match="KV hand-off .*summary pages"):
        engine.kv_handoff_dims()
    with pytest.raises(NotImplementedError, match="KV hand-off .*summary pages"):
        engine.prefill_detached(np.arange(6, dtype=np.int32))
    engine.submit(np.arange(6, dtype=np.int32), max_new_tokens=20)
    engine.step()
    dense = ServingEngine(model, num_slots=2, prompt_buckets=(8,), max_len=128)  # the layout whose export carries rows
    uid = dense.submit(np.arange(6, dtype=np.int32), max_new_tokens=20)
    dense.step()
    with pytest.raises(NotImplementedError, match=r"export_inflight\(include_kv=True\) .*summary pages"):
        dense.export_inflight(include_kv=True)
    dense.run()
    engine.run()
    assert dense.partial(uid).tolist() == engine.partial(0).tolist(), "the dense layout serves the same tokens"
    engine.submit(np.arange(6, dtype=np.int32), max_new_tokens=20)
    engine.step()
    snaps = engine.export_inflight(include_kv=True)  # a paged slot fails over by recompute: no rows, nothing refused yet
    other = _engine(model)
    with pytest.raises(NotImplementedError, match="import_inflight of a request that has decoded .*summary pages"):
        other.import_inflight(snaps[0])
    assert other.import_inflight(dict(snaps[0], out_tokens=[], out_lps=[])) == 0, "one that has not started is a fresh request"
    with pytest.raises(NotImplementedError, match="a chunk to a page"):
        _engine(model, paged_block_size=8)
    with pytest.raises(NotImplementedError, match="scan_layers=False"):
        create_evabyte_model(EvaByteConfig.tiny(scan_layers=True), seq_len=8)


def test_other_models_engines_carry_no_window_state(model):
    """An engine over a model without EVA: no summary table in its cache, the four counts and the pages by kind stay 0."""
    from accelerate_tpu.models import LlamaConfig, create_llama_model

    engine = ServingEngine(create_llama_model(LlamaConfig.tiny(), seq_len=8), num_slots=2, prompt_buckets=(8,), max_len=64, paged_block_size=4)
    assert engine._aligned is None and "summary_table" not in {str(p[-1].key) for p, _ in jax.tree_util.tree_flatten_with_path(engine.slot_caches)[0]}
    engine.submit(np.arange(5, dtype=np.int32), max_new_tokens=10)
    engine.run()
    m = engine.metrics
    assert (m.attn_rows_read, m.context_rows, m.chunks_pooled, m.windows_closed, m.exact_pages_held, m.summary_pages_held) == (0,) * 6


def test_a_tick_that_admits_many_waits_for_room_for_another_row_cache(model):
    """The tick's programs are queued back to back and a prefill's row cache lives until its paste has run: with
    room for one (the cap is read from the device's free memory; a CPU reports none and has no cap), the second and
    third admission of a tick each wait for the device first (``engine.prefill.room.sync``), and serve what they
    would have served."""
    prompts = [np.arange(n, dtype=np.int32) + 9 for n in (5, 12, 30)]
    served = []
    for cap in (None, 1):
        engine = _engine(model)
        assert engine._row_cache_cap() == float("inf"), "no memory reported on a CPU: no cap"
        if cap:
            engine._row_cap = cap
        uids = [engine.submit(p, max_new_tokens=6) for p in prompts]
        engine.step()
        waits = phase_log().roots("engine.tick", n=1)[0].children.get("engine.prefill.room.sync", [0])[0]
        assert waits == (2 if cap else 0)
        engine.run()
        served.append([engine.partial(u).tolist() for u in uids])
    assert served[0] == served[1]
