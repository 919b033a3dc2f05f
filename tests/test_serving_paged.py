"""Paged KV-cache serving (ops/paged_kv.py + ServingEngine paged mode):
token-exact parity with the dense engine and with static generate(),
block-pool accounting, admission control under a tight pool, and shared
prefix blocks. The tiny llama fixture is GQA (4 heads / 2 KV heads), so
the grouped paged-attention branch runs in every test here."""

import numpy as np
import pytest

from accelerate_tpu.generation import generate
from accelerate_tpu.models import LlamaConfig, create_llama_model
from accelerate_tpu.ops.paged_kv import BlockAllocator
from accelerate_tpu.serving import ServingEngine


@pytest.fixture(scope="module")
def tiny_llama():
    return create_llama_model(LlamaConfig.tiny(), seq_len=16)


def _reference(model, prompt, n):
    return np.asarray(generate(model, np.asarray(prompt, np.int32)[None], max_new_tokens=n))[0]


def test_paged_matches_generate_mixed_lengths(tiny_llama):
    """8 mixed-length prompts through 2 slots with a 4-row block pool:
    every output equals static generate(), and every block returns to the
    free list after the queue drains."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 250, size=n).astype(np.int32) for n in (3, 8, 5, 12, 2, 7, 9, 4)]
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(4, 8, 16), paged_block_size=4)
    free0 = eng.pool_free_blocks
    outs = eng.generate_many(prompts, max_new_tokens=5)
    for prompt, got in zip(prompts, outs):
        np.testing.assert_array_equal(got, _reference(tiny_llama, prompt, 5))
    assert eng.pool_free_blocks == free0


def test_paged_matches_dense_engine(tiny_llama):
    """The paged tick (one batched program) and the dense tick (vmapped
    per-row programs) emit identical tokens — the layouts are
    numerically interchangeable, not just both-plausible."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 250, size=n).astype(np.int32) for n in (6, 11, 2, 9)]
    dense = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(4, 8, 16))
    paged = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(4, 8, 16), paged_block_size=8)
    for d, p in zip(dense.generate_many(prompts, 6), paged.generate_many(prompts, 6)):
        np.testing.assert_array_equal(d, p)


def test_tight_pool_admission_control(tiny_llama):
    """A pool too small for all slots at once serializes admission
    instead of corrupting: 4 slots but only ~1 request's worth of
    blocks — outputs stay exact and the pool drains back."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 250, size=n).astype(np.int32) for n in (3, 8, 5, 12)]
    eng = ServingEngine(
        tiny_llama, num_slots=4, prompt_buckets=(4, 8, 16), paged_block_size=4, pool_blocks=8
    )
    outs = eng.generate_many(prompts, max_new_tokens=5)
    for prompt, got in zip(prompts, outs):
        np.testing.assert_array_equal(got, _reference(tiny_llama, prompt, 5))
    assert eng.pool_free_blocks == 7  # block 0 is the trash sink


def test_pool_capacity_win_vs_dense(tiny_llama):
    """The point of paging: pool bytes are set by tokens in flight, not
    slots x max_len. 8 slots x max_len=128 dense rows would need 8*32
    4-row blocks; a 24-block pool (~1/10th) still serves 8 slots."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 250, size=5).astype(np.int32) for _ in range(8)]
    eng = ServingEngine(
        tiny_llama, num_slots=8, prompt_buckets=(8,), paged_block_size=4, pool_blocks=24
    )
    dense_equivalent_blocks = 8 * (128 // 4)
    assert eng._pcfg.num_blocks < dense_equivalent_blocks // 10
    outs = eng.generate_many(prompts, max_new_tokens=4)
    for prompt, got in zip(prompts, outs):
        np.testing.assert_array_equal(got, _reference(tiny_llama, prompt, 4))


def test_midstream_submit(tiny_llama):
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8,), paged_block_size=4)
    a = eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=8)
    eng.step()
    b = eng.submit(np.arange(20, 25, dtype=np.int32), max_new_tokens=4)
    eng.run()
    np.testing.assert_array_equal(eng.poll(a), _reference(tiny_llama, np.arange(1, 7), 8))
    np.testing.assert_array_equal(eng.poll(b), _reference(tiny_llama, np.arange(20, 25), 4))


def test_shared_prefix_blocks(tiny_llama):
    """Requests sharing a registered prefix alias its FULL blocks
    (refcounted) instead of re-allocating; outputs equal full-prompt
    generate(); unregister returns the shared blocks."""
    prefix = (np.arange(9) % 250 + 3).astype(np.int32)  # 2 full 4-blocks + 1 tail row
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(4, 8), paged_block_size=4)
    pid = eng.register_prefix(prefix)
    held = eng.pool_free_blocks
    a = eng.submit(np.asarray([5, 6], np.int32), max_new_tokens=4, prefix_id=pid)
    b = eng.submit(np.asarray([9], np.int32), max_new_tokens=4, prefix_id=pid)
    eng.run()
    for uid, sfx in ((a, [5, 6]), (b, [9])):
        full = np.concatenate([prefix, np.asarray(sfx, np.int32)])
        np.testing.assert_array_equal(eng.poll(uid), _reference(tiny_llama, full, 4))
    assert eng.pool_free_blocks == held  # per-request blocks freed, prefix still held
    eng.unregister_prefix(pid)
    assert eng.pool_free_blocks == held + 2  # the 2 shared full blocks came back


def test_paged_validation(tiny_llama):
    with pytest.raises(ValueError, match="paged_block_size"):
        ServingEngine(tiny_llama, pool_blocks=8)
    with pytest.raises(ValueError, match="paged_block_size"):
        ServingEngine(tiny_llama, paged_block_size=0)
    eng = ServingEngine(
        tiny_llama, num_slots=1, prompt_buckets=(8,), paged_block_size=4, pool_blocks=4
    )
    with pytest.raises(ValueError, match="pool blocks"):
        eng.submit(np.ones((8,), np.int32), max_new_tokens=8)  # needs more than 3 usable


def test_unsatisfiable_request_raises_not_busyloops(tiny_llama):
    """A request that passes the static submit check but can never be
    admitted (registered prefixes hold too much of the pool) raises from
    run() instead of spinning forever."""
    eng = ServingEngine(
        tiny_llama, num_slots=1, prompt_buckets=(4, 8), paged_block_size=4, pool_blocks=8
    )
    eng.register_prefix((np.arange(16) % 250 + 1).astype(np.int32))  # holds 4 blocks
    eng.submit(np.ones((8,), np.int32), max_new_tokens=8)  # needs 4, only 3 ever free
    with pytest.raises(RuntimeError, match="pool blocks"):
        eng.run()


def test_paged_with_smaller_max_len(tiny_llama):
    """An engine max_len below the model's horizon still pages correctly:
    the block table follows the MODEL's cache width while reservations
    follow max_len (regression: the first cut sized the table by max_len
    and crashed in paste)."""
    prompt = (np.arange(7) % 250 + 1).astype(np.int32)
    eng = ServingEngine(
        tiny_llama, num_slots=2, prompt_buckets=(8,), max_len=64, paged_block_size=4
    )
    [got] = eng.generate_many([prompt], max_new_tokens=4)
    np.testing.assert_array_equal(got, _reference(tiny_llama, prompt, 4))
    pid = eng.register_prefix((np.arange(9) % 250 + 2).astype(np.int32))
    uid = eng.submit(np.asarray([5], np.int32), max_new_tokens=3, prefix_id=pid)
    eng.run()
    full = np.concatenate([(np.arange(9) % 250 + 2).astype(np.int32), [5]])
    np.testing.assert_array_equal(eng.poll(uid), _reference(tiny_llama, full, 3))


def test_busy_slots_then_drain_is_not_deadlock(tiny_llama):
    """All slots busy at admit time + every active request finishing
    within the same tick must NOT trip the unsatisfiable-head guard
    (regression: the first cut keyed on 'nothing admitted' instead of
    'pool-blocked' and raised here — and crashed dense engines)."""
    for kwargs in ({}, {"paged_block_size": 4}):
        eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(8,), tick_block=8, **kwargs)
        a = eng.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=10)  # > tick_block
        b = eng.submit(np.arange(5, 9, dtype=np.int32), max_new_tokens=3)
        eng.run()  # must complete without RuntimeError/AttributeError
        np.testing.assert_array_equal(eng.poll(a), _reference(tiny_llama, np.arange(1, 5), 10))
        np.testing.assert_array_equal(eng.poll(b), _reference(tiny_llama, np.arange(5, 9), 3))


def test_kernel_in_engine_matches_dense(tiny_llama):
    """The exact composition TPU serving runs — ServingEngine paged tick
    through the Pallas paged-attention kernel — in interpret mode on
    CPU, token-exact vs the paged engine on the XLA gather path and vs
    the dense engine (tiny shapes: interpret mode executes the grid in
    Python). Three requests over two slots, pages of 4 tokens: each
    decodes across a page boundary, and the short one retires while the
    long one is mid-page — its row goes back to the sink, the third
    request takes the slot, and the kernel walks the new row beside the
    old one's growing frontier."""
    import accelerate_tpu.ops.paged_kv as pkv

    prompts = [np.arange(1, 1 + n, dtype=np.int32) for n in (3, 6, 5)]
    new_tokens = (3, 9, 4)

    def serve(**kwargs):
        eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8,), tick_block=2, **kwargs)
        uids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new_tokens)]
        eng.run()
        return [eng.poll(uid) for uid in uids]

    dense = serve()
    gather = serve(paged_block_size=4)
    pkv.FORCE_KERNEL_INTERPRET = True
    try:
        got = serve(paged_block_size=4)
    finally:
        pkv.FORCE_KERNEL_INTERPRET = False
    for d, x, g, p, n in zip(dense, gather, got, prompts, new_tokens):
        assert len(g) == len(p) + n
        np.testing.assert_array_equal(x, g)
        np.testing.assert_array_equal(d, g)


def test_idle_rows_hand_the_kernel_no_keys(monkeypatch):
    """The static tick computes every slot, so an idle slot's frontier grows a step a token after
    ``clear_slot`` zeroed it, its whole row at the sink. A row that stores its token in the sink (idle, or
    finished and overshooting) is handed ``NO_KEYS``, a frontier below zero: the kernel walks no page of it
    (the sink holds NaN but for the rows the step stored) and its output is zeros; a live row keeps its own
    frontier and its result. The gather path is given every row's own frontier, as before."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    import accelerate_tpu.ops.paged_kv as pkv
    import accelerate_tpu.ops.pallas_paged_attention as kernel_file

    cfg = pkv.PagedConfig(block_size=4, num_blocks=9)

    class Attend(nn.Module):
        @nn.compact
        def __call__(self, q, k, v):
            return pkv.paged_cached_attention(self, q, k, v, max_len=16, sliding_window=None, cfg=cfg)

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (3, 1, 2, 8))
    k, v = jax.random.normal(keys[1], (3, 1, 2, 8)), jax.random.normal(keys[2], (3, 1, 2, 8))
    pool = jax.random.normal(keys[3], (9, 4, 2, 8))
    cache = {
        "key_pool": pool, "value_pool": pool[::-1],
        # row 0 decodes at position 6 of blocks 3, 5; row 1 has been idle for 1000 steps; row 2
        # finished at the end of its one block and overshoots into the pad entries
        "block_table": jnp.asarray([[3, 5, 0, 0], [0, 0, 0, 0], [7, 0, 0, 0]], jnp.int32),
        "index": jnp.asarray([6, 1000, 5], jnp.int32),
    }
    want, _ = Attend().apply({"cache": cache}, q, k, v, mutable=["cache"])

    seen = []
    real = kernel_file.paged_decode_attention

    def spy(q, key_pool, value_pool, block_table, cur, **kwargs):
        seen.append(np.asarray(cur))
        return real(q, key_pool, value_pool, block_table, cur, **kwargs)

    monkeypatch.setattr(kernel_file, "paged_decode_attention", spy)
    monkeypatch.setattr(pkv, "FORCE_KERNEL_INTERPRET", True)
    poisoned = dict(cache, key_pool=cache["key_pool"].at[0].set(jnp.nan), value_pool=cache["value_pool"].at[0].set(jnp.nan))
    got, _ = Attend().apply({"cache": poisoned}, q, k, v, mutable=["cache"])
    np.testing.assert_array_equal(seen[0], [6, pkv.NO_KEYS, pkv.NO_KEYS])
    assert pkv.NO_KEYS < 0
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[1:]).any(), "a row at the sink reads nothing and returns zeros"


@pytest.mark.parametrize("family", ["llama_scanned", "llama_unrolled", "routed_experts"])
def test_idle_slots_beside_decoding_ones_change_no_token_and_are_counted(family, monkeypatch):
    """Through ``ServingEngine`` with the kernel interpreted: one, two and three requests of different lengths in four
    slots, so every tick has slots at the sink beside decoding ones, a slot retires mid-tick and overshoots, and a freed
    slot is taken again. Handed ``NO_KEYS`` for the slots at the sink the engine serves, to the bit, the tokens and
    logprobs it serves with those slots at frontier 0 (what the callers handed the kernel before), and the gather path's
    tokens. ``attention_rows_skipped`` of ``engine.tick.done`` is, tick by tick, (slots - decoding) x ``tick_block``,
    and ``ServingMetrics`` sums it; the dense layout counts nothing."""
    import accelerate_tpu.ops.paged_kv as pkv
    from accelerate_tpu.telemetry.trace import phase_log

    if family == "routed_experts":
        from accelerate_tpu.models.lfm2_moe import Lfm2MoeConfig, create_lfm2_moe_model

        model = create_lfm2_moe_model(Lfm2MoeConfig.tiny(), seed=3, seq_len=16)
    else:
        model = create_llama_model(LlamaConfig.tiny(scan_layers=family == "llama_scanned"), seed=0, seq_len=8)
    prompts = [np.arange(2, 2 + n, dtype=np.int32) for n in (5, 3, 7)]
    new_tokens = (9, 3, 6)

    def serve(**kwargs):
        eng = ServingEngine(model, num_slots=4, prompt_buckets=(8,), max_len=32, tick_block=2, **kwargs)
        uids = [eng.submit(prompts[0], max_new_tokens=new_tokens[0])]
        ticks, passes, decode_pass = [], [], eng._decode_pass
        # the slots that decode when a tick's decode program is dispatched (None: a tick without one)
        eng._decode_pass = lambda: (passes.append(int(eng._decoding_slots().sum())), decode_pass())
        while eng.queue or eng.active_count:
            if eng._tick == 2:  # two more arrive while the first decodes
                uids += [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts[1:], new_tokens[1:])]
            passes.clear()
            eng.step()
            ticks.append((passes[0] if passes else None, phase_log().roots("engine.tick", n=1)[0].done["attention_rows_skipped"]))
        return eng, [eng.poll(u) for u in uids], [eng.logprobs(u) for u in uids], ticks

    monkeypatch.setattr(pkv, "FORCE_KERNEL_INTERPRET", True)
    eng, tokens, lps, ticks = serve(paged_block_size=4)
    assert all(len(t) == len(p) + n for t, p, n in zip(tokens, prompts, new_tokens))
    for decoding, skipped in ticks:
        assert skipped == (0 if decoding is None else (4 - decoding) * 2)
    assert {d for d, _ in ticks} >= {1, 2, 3}, "the occupancy the test fixes: one, two and three slots decoding of four"
    assert eng.metrics.attention_rows_skipped == sum(s for _, s in ticks) > 0
    monkeypatch.setattr(pkv, "NO_KEYS", 0)  # the parent's callers: an idle slot walks the sink's first page
    _, tokens_before, lps_before, _ = serve(paged_block_size=4)
    for a, b, la, lb in zip(tokens, tokens_before, lps, lps_before):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(la, lb)
    monkeypatch.setattr(pkv, "FORCE_KERNEL_INTERPRET", False)
    _, tokens_gather, lps_gather, _ = serve(paged_block_size=4)
    for a, b, la, lb in zip(tokens, tokens_gather, lps, lps_gather):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_allclose(la, lb, atol=2e-5)
    if family != "routed_experts":  # which the dense layout does not serve
        dense, _, _, dense_ticks = serve()
        assert dense.metrics.attention_rows_skipped == 0 and {s for _, s in dense_ticks} == {0}


def test_kernel_in_engine_tp_sharded(tiny_llama):
    """TP-sharded paged serving through the kernel: the pool is sharded
    over `tensor` (heads), the kernel runs per-shard under shard_map
    (a pallas_call can't be auto-partitioned), and tokens equal the
    unsharded dense engine's."""
    import jax

    import accelerate_tpu.ops.paged_kv as pkv
    from accelerate_tpu.big_modeling import shard_model
    from accelerate_tpu.parallel.mesh import MeshConfig

    prompt = (np.arange(8) % 250).astype(np.int32)
    dense = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8,), tick_block=2)
    [want] = dense.generate_many([prompt], max_new_tokens=3)

    model = create_llama_model(LlamaConfig.tiny(), seq_len=16)
    shard_model(model, MeshConfig(data=1, tensor=2).build(jax.devices()[:2]))
    pkv.FORCE_KERNEL_INTERPRET = True
    try:
        eng = ServingEngine(model, num_slots=2, prompt_buckets=(8,), tick_block=2, paged_block_size=4)
        [got] = eng.generate_many([prompt], max_new_tokens=3)
    finally:
        pkv.FORCE_KERNEL_INTERPRET = False
    np.testing.assert_array_equal(got, want)


def test_paged_engine_on_data_sharded_mesh(tiny_llama):
    """A mesh with data > 1: GSPMD propagates shardings onto the pool
    between pastes, so the tick must adapt instead of pinning the
    shardings it saw at construction (regression: the eagerly-compiled
    tick rejected the runtime arrays with a sharding mismatch)."""
    import jax

    from accelerate_tpu.big_modeling import shard_model
    from accelerate_tpu.parallel.mesh import MeshConfig

    prompts = [np.arange(1, 1 + n, dtype=np.int32) for n in (3, 6, 9)]
    dense = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8, 16), tick_block=2)
    want = dense.generate_many(prompts, max_new_tokens=3)

    model = create_llama_model(LlamaConfig.tiny(), seq_len=16)
    shard_model(model, MeshConfig(data=2, tensor=2).build(jax.devices()[:4]))
    eng = ServingEngine(model, num_slots=2, prompt_buckets=(8, 16), tick_block=2, paged_block_size=4)
    got = eng.generate_many(prompts, max_new_tokens=3)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)


@pytest.fixture(scope="module")
def tiny_mistral():
    from accelerate_tpu.models import MistralConfig, create_mistral_model

    return create_mistral_model(MistralConfig.tiny(sliding_window=4), seq_len=32)


def test_windowed_request_pool_cost_is_window_bound(tiny_mistral):
    """A windowed model's request reserves only O(window + max_new)
    blocks: a 24-token prompt fits a 3-usable-block pool (the
    unwindowed plan would need 8 blocks) and stays token-exact."""
    from accelerate_tpu.generation import generate

    prompt = (np.arange(24) % 250 + 1).astype(np.int32)
    eng = ServingEngine(
        tiny_mistral, num_slots=1, prompt_buckets=(8,), paged_block_size=4, pool_blocks=4
    )
    [got] = eng.generate_many([prompt], max_new_tokens=6)
    want = np.asarray(generate(tiny_mistral, prompt[None], max_new_tokens=6))[0]
    np.testing.assert_array_equal(got, want)
    assert eng.pool_free_blocks == 3


def test_window_recycles_blocks_mid_decode(tiny_mistral):
    """Blocks behind the moving frontier return to the pool WHILE the
    request is still decoding (the long-generation capacity win)."""
    eng = ServingEngine(
        tiny_mistral, num_slots=1, prompt_buckets=(8,), paged_block_size=4, tick_block=2
    )
    eng.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=16)
    eng.step()  # admit + first tick
    after_admit = eng.pool_free_blocks
    recovered = False
    while eng.active_count:
        eng.step()
        if eng.active_count and eng.pool_free_blocks > after_admit:
            recovered = True
    assert recovered  # freed behind the frontier before retirement
    assert eng.pool_free_blocks == eng._pcfg.num_blocks - 1  # all drained


def test_windowed_prefix_token_exact(tiny_mistral):
    """Prefix sharing under a window: below-band prefix blocks are never
    aliased (they start as trash) and outputs still equal full-prompt
    generate()."""
    from accelerate_tpu.generation import generate

    prefix = (np.arange(9) % 250 + 3).astype(np.int32)
    eng = ServingEngine(tiny_mistral, num_slots=2, prompt_buckets=(4, 8), paged_block_size=4)
    pid = eng.register_prefix(prefix)
    uids = [eng.submit(np.asarray(s, np.int32), max_new_tokens=4, prefix_id=pid) for s in ([5, 6], [9])]
    eng.run()
    for uid, sfx in zip(uids, ([5, 6], [9])):
        full = np.concatenate([prefix, np.asarray(sfx, np.int32)])
        want = np.asarray(generate(tiny_mistral, full[None], max_new_tokens=4))[0]
        np.testing.assert_array_equal(eng.poll(uid), want)
    eng.unregister_prefix(pid)
    assert eng.pool_free_blocks == eng._pcfg.num_blocks - 1


def test_windowed_shared_prefix_alias_and_expiry():
    """Prefix blocks INSIDE the band are aliased and then expire
    mid-decode (refcount drop, not free) while another slot still
    shares them — the refcount path the plain prefix test never enters
    (its aliases fall below the band)."""
    from accelerate_tpu.generation import generate
    from accelerate_tpu.models import MistralConfig, create_mistral_model

    m = create_mistral_model(MistralConfig.tiny(sliding_window=8), seq_len=32)
    prefix = (np.arange(8) % 250 + 3).astype(np.int32)  # 2 full in-band blocks
    eng = ServingEngine(m, num_slots=2, prompt_buckets=(4, 16), paged_block_size=4, tick_block=2)
    pid = eng.register_prefix(prefix)
    assert len(eng._prefixes[pid]["block_ids"]) == 2  # both registered (in band)
    uids = [eng.submit(np.asarray([s], np.int32), max_new_tokens=10, prefix_id=pid) for s in (5, 9)]
    eng.step()
    assert any(eng._slot_shared[s] for s in range(2))  # in-band aliases installed
    eng.run()
    for uid, sfx in zip(uids, (5, 9)):
        full = np.concatenate([prefix, [sfx]]).astype(np.int32)
        want = np.asarray(generate(m, full[None], max_new_tokens=10))[0]
        np.testing.assert_array_equal(eng.poll(uid), want)
    # all request blocks drained; the prefix still holds its own refs
    assert all(v == 1 for v in eng._shared_refs.values())
    eng.unregister_prefix(pid)
    assert eng.pool_free_blocks == eng._pcfg.num_blocks - 1


def test_windowed_prefix_registration_is_band_capped():
    """A long prefix on a windowed model registers only in-band blocks:
    O(window), not O(prefix)."""
    from accelerate_tpu.models import MistralConfig, create_mistral_model

    m = create_mistral_model(MistralConfig.tiny(sliding_window=4), seq_len=32)
    prefix = (np.arange(24) % 250 + 1).astype(np.int32)  # 6 full 4-blocks
    eng = ServingEngine(m, num_slots=1, prompt_buckets=(8,), paged_block_size=4, pool_blocks=4)
    pid = eng.register_prefix(prefix)  # unwindowed would need 6 > 3 usable
    assert len(eng._prefixes[pid]["block_ids"]) <= 2
    eng.unregister_prefix(pid)
    assert eng.pool_free_blocks == 3


def test_paged_sampling_matches_dense_chain(tiny_llama):
    """Temperature sampling: the paged batched tick splits per-row keys
    in the same order as the dense vmapped tick, so sampled outputs are
    identical for the same seed."""
    prompts = [np.arange(1, 6, dtype=np.int32), np.arange(7, 10, dtype=np.int32)]
    kw = dict(num_slots=2, prompt_buckets=(8,), temperature=0.9, top_k=5, seed=11)
    dense = ServingEngine(tiny_llama, **kw)
    paged = ServingEngine(tiny_llama, paged_block_size=4, **kw)
    for d, p in zip(dense.generate_many(prompts, 6), paged.generate_many(prompts, 6)):
        np.testing.assert_array_equal(d, p)


def test_block_allocator():
    alloc = BlockAllocator(5)
    assert alloc.free_count == 4
    got = alloc.alloc(3)
    assert len(got) == 3 and 0 not in got
    assert alloc.alloc(2) is None  # only 1 left
    alloc.free(got)
    assert alloc.free_count == 4
    with pytest.raises(ValueError):
        alloc.free([0])  # the trash sink is never allocatable/freeable
    with pytest.raises(ValueError):
        BlockAllocator(1)


# --------------------------------------------------------------------- #
# the pool is one buffer: carried through the layer loop, donated at
# every program boundary
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def tiny_models():
    """Tiny llama (``window=None``) or mistral by (scan_layers, window), built once."""
    from accelerate_tpu.models import MistralConfig, create_mistral_model

    built = {}

    def get(scan_layers, window=None):
        if (scan_layers, window) not in built:
            if window is None:
                model = create_llama_model(LlamaConfig.tiny(scan_layers=scan_layers), seq_len=16)
            else:
                cfg = MistralConfig.tiny(sliding_window=window, scan_layers=scan_layers)
                model = create_mistral_model(cfg, seq_len=32)
            built[scan_layers, window] = model
        return built[scan_layers, window]

    return get


def _drain(eng, uids):
    eng.run()
    return [(eng.poll(u), eng.logprobs(u)) for u in uids]


def _readmit(eng):
    # one slot: admit -> decode -> retire -> the next request into the same slot
    prompts = [np.arange(1, 1 + n, dtype=np.int32) for n in (5, 9, 3)]
    return _drain(eng, [eng.submit(p, max_new_tokens=6) for p in prompts])


def _prefix(eng):
    pid = eng.register_prefix((np.arange(9) % 250 + 3).astype(np.int32))
    return _drain(eng, [eng.submit(np.asarray(s, np.int32), max_new_tokens=5, prefix_id=pid) for s in ([5, 6], [9])])


def _window_expiry(eng):
    # window 4, 16 new tokens: blocks expire behind the frontier while decoding
    return _drain(eng, [eng.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=16)])


def _preempt_resume(eng):
    victim = eng.submit((np.arange(6) % 250 + 1).astype(np.int32), max_new_tokens=10, priority=1)
    eng.step()
    urgent = eng.submit(np.asarray([3, 1, 4, 1, 5], np.int32), max_new_tokens=4, priority=0)
    out = _drain(eng, [victim, urgent])
    assert eng.metrics.decode_preemptions == 1 and eng.metrics.resumes == 1
    return out


_SCENARIOS = {
    "readmit": (_readmit, dict(num_slots=1, prompt_buckets=(4, 8, 16)), None),
    "prefix": (_prefix, dict(num_slots=2, prompt_buckets=(4, 8)), None),
    "window_expiry": (_window_expiry, dict(num_slots=1, prompt_buckets=(8,)), 4),
    "preempt_resume": (_preempt_resume, dict(num_slots=1, prompt_buckets=(8,)), None),
}


@pytest.mark.parametrize("sampling", [False, True], ids=["greedy", "sampling"])
@pytest.mark.parametrize("scan_layers", [True, False], ids=["scan", "unrolled"])
@pytest.mark.parametrize("scenario", list(_SCENARIOS))
def test_paged_tokens_and_logprobs_equal_dense(tiny_models, scenario, scan_layers, sampling):
    """The in-place paged engine against the dense engine, through every
    path that hands the pool to a donated program (paste, tick, clear,
    paste_blocks, set_table_row): tokens AND logprobs are the same bits."""
    from accelerate_tpu.scheduling import SchedulerConfig

    drive, kw, window = _SCENARIOS[scenario]
    kw = dict(kw, tick_block=2, **(dict(temperature=0.9, top_k=5, seed=11) if sampling else {}))
    if scenario == "preempt_resume":
        kw["scheduler"] = SchedulerConfig(enable_preemption=True)
    model = tiny_models(scan_layers, window)
    dense = drive(ServingEngine(model, **kw))
    paged = drive(ServingEngine(model, paged_block_size=4, **kw))
    for (d_tok, d_lp), (p_tok, p_lp) in zip(dense, paged, strict=True):
        np.testing.assert_array_equal(p_tok, d_tok)
        np.testing.assert_array_equal(p_lp, d_lp)


def _backend_donates():
    import jax
    import jax.numpy as jnp

    x = jnp.zeros((8,))
    jax.jit(lambda a: a + 1, donate_argnums=0)(x)
    return x.is_deleted()


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scan", "unrolled"])
def test_every_cache_program_donates_the_pool_and_updates_it_in_place(tiny_models, scan_layers):
    """Admission (paste_row), the tick, expiry (set_table_row), retirement
    (clear_slot) and prefix registration (paste_blocks) each delete the
    cache they were given, and the pools stay in the buffers they were
    born in."""
    import jax

    if not _backend_donates():
        pytest.skip("this backend does not donate")
    eng = ServingEngine(tiny_models(scan_layers, 4), num_slots=1, prompt_buckets=(8,), paged_block_size=4, tick_block=2)

    def pools(cache):
        flat = jax.tree_util.tree_flatten_with_path(cache)[0]
        return [leaf for path, leaf in flat if str(path[-1].key).endswith("_pool")]

    born = [p.unsafe_buffer_pointer() for p in pools(eng.slot_caches)]
    seen = set()
    for name in ("_paste", "_paste_blocks", "_clear_slots", "_set_table", "_decode_tick"):
        program = getattr(eng, name)

        def checked(*args, _program=program, _name=name):
            given = args[1] if _name == "_decode_tick" else args[0]
            out = _program(*args)
            assert all(leaf.is_deleted() for leaf in jax.tree.leaves(given)), _name
            seen.add(_name)
            return out

        setattr(eng, name, checked)
    pid = eng.register_prefix((np.arange(8) % 250 + 3).astype(np.int32))
    eng.submit(np.asarray([5], np.int32), max_new_tokens=12, prefix_id=pid)
    before = eng.slot_caches
    eng.step()
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
    eng.run()
    assert seen == {"_paste", "_paste_blocks", "_clear_slots", "_set_table", "_decode_tick"}
    assert [p.unsafe_buffer_pointer() for p in pools(eng.slot_caches)] == born


def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    import jax

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def _tick_jaxpr(eng):
    """The engine's decode tick traced with the arguments the engine builds for it: ``(jaxpr, arguments)``."""
    import jax

    tick = eng._perf_programs["decode_tick"]
    args = tick.args(None)
    with tick.traced():
        return jax.make_jaxpr(tick.fn)(*args).jaxpr, args


@pytest.mark.parametrize("family", ["dense_unrolled", "dense_scanned", "state_space", "routed_experts"])
def test_only_a_tick_with_experts_or_a_stepped_state_is_told_which_slots_decode(tiny_models, family):
    """The tick of a dense model is the program it was before the row mask: five arguments, no boolean
    enters it, and the model's ``apply_fn`` is called with the keywords it always was. A model with routed
    experts, or with state-space layers (an ``ssm_state`` leaf in its row cache, which the step kernel
    visits by slot), gets one ``[slots]`` bool more, handed down as ``row_valid``. The choice follows the
    config's ``n_routed_experts`` and the cache template's leaves alone."""
    import copy

    import jax.numpy as jnp

    if family == "state_space":
        from accelerate_tpu.models.jamba import JambaConfig, create_jamba_model

        model = create_jamba_model(JambaConfig.tiny(), seed=3, seq_len=16)
    elif family == "routed_experts":
        from accelerate_tpu.models.joyai_llm_flash import JoyAIFlashConfig, create_joyai_flash_model

        model = create_joyai_flash_model(JoyAIFlashConfig.tiny(), seed=3, seq_len=16)
    else:
        model = tiny_models(family == "dense_scanned")
    routed = family in ("routed_experts", "state_space")  # told which slots decode
    assert (getattr(model.config, "n_routed_experts", None) is not None) == (family == "routed_experts")
    keywords = []

    def spy(*args, **kwargs):
        keywords.append(set(kwargs))
        return model.apply_fn(*args, **kwargs)

    spied = copy.copy(model)
    spied.apply_fn = spy
    eng = ServingEngine(spied, num_slots=3, prompt_buckets=(8,), paged_block_size=4, tick_block=2)
    keywords.clear()
    jaxpr, args = _tick_jaxpr(eng)
    bools = [v.aval.shape for v in jaxpr.invars if v.aval.dtype == jnp.bool_]
    assert keywords == [{"positions", "decode", "cache", "row_valid"} if routed else {"positions", "decode", "cache"}]
    assert len(args) == (6 if routed else 5) and len(eng._decoding_arg()) == int(routed)
    assert bools == ([(3,)] if routed else [])
    if routed:
        assert args[-1].dtype == jnp.bool_ and not args[-1].any(), "no slot decodes in a fresh engine"


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scan", "unrolled"])
def test_pools_are_loop_state_in_the_tick_never_scanned_over(tiny_models, scan_layers):
    """On the tick's jaxpr: whatever has the shape of a layer's pool or of
    the stack enters and leaves every loop as carry, never as a scanned
    input (a slice per iteration) or a stacked output (a second stack)."""
    model = tiny_models(scan_layers)
    eng = ServingEngine(model, num_slots=2, prompt_buckets=(8,), paged_block_size=4, tick_block=2)
    jaxpr, _ = _tick_jaxpr(eng)
    cfg, pcfg = model.config, eng._pcfg
    block = (pcfg.block_size, cfg.num_key_value_heads, cfg.hidden_size // cfg.num_attention_heads)

    def is_pool(var):
        shape = tuple(var.aval.shape)
        return shape[-3:] == block and len(shape) >= 4 and shape[-4] % pcfg.num_blocks == 0

    carried = []
    for eqn in _scans(jaxpr):
        n_const, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        xs, ys = eqn.invars[n_const + n_carry:], eqn.outvars[n_carry:]
        assert not [v for v in (*xs, *ys) if is_pool(v)], f"a pool is scanned over by a loop of {eqn.params['length']}"
        carried.append((eqn.params["length"], sum(map(is_pool, eqn.invars[n_const:n_const + n_carry]))))
    layers = cfg.num_hidden_layers
    # the tick's loop carries K and V (per layer when unrolled); so does the layer loop
    assert (eng.tick_block, 2 if scan_layers else 2 * layers) in carried
    assert ((layers, 2) in carried) == scan_layers


def test_tick_compiled_for_a_v5e_keeps_the_pool_in_one_buffer(monkeypatch):
    """The engine's own tick (its jit object, with its donation) compiled
    for a described v5e at Mistral-7B widths, depth 2: nothing in the
    program copies, slices out or writes back an array the size of a
    layer's pool, the pool's bytes are aliased to the output, and the
    temporaries stay under one layer's pool."""
    import os
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from accelerate_tpu.models import MistralConfig
    from accelerate_tpu.models.llama import LlamaModel, _wrap_llama

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    layers, blocks, block_size, slots = 2, 4096, 16, 32
    cfg = MistralConfig(num_hidden_layers=layers, max_position_embeddings=4096)  # 7B widths are the defaults
    module = LlamaModel(cfg)
    shapes = jax.eval_shape(lambda: module.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), shapes)
    eng = ServingEngine(
        _wrap_llama(module, shapes, cfg), num_slots=slots, prompt_buckets=(64,), max_len=4096,
        paged_block_size=block_size, pool_blocks=blocks,
    )
    tick = eng._perf_programs["decode_tick"]
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tick.args(None))
    # the program asks the default backend whether to lower the Pallas kernel or interpret it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # a program compiled for a described chip cannot be read back from the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with tick.traced():
            compiled = eng._decode_tick.__wrapped__.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the paged decode kernel is in the tick"
    kv_heads, head_dim = cfg.num_key_value_heads, cfg.hidden_size // cfg.num_attention_heads
    layer_pool = blocks * block_size * kv_heads * head_dim  # elements of one layer's K (or V) pool
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = bf16\[([\d,]+)\]\S* (copy|dynamic-slice|dynamic-update-slice)\(", line)
        if m:
            dims = [int(d) for d in m.group(1).split(",")]
            if dims[-3:] == [block_size, kv_heads, head_dim] and np.prod(dims) >= layer_pool:
                moved.append(line.strip()[:160])
    assert not moved, "the tick moves a whole pool:\n" + "\n".join(moved)
    mem = compiled.memory_analysis()
    pool_bytes = 2 * layers * layer_pool * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 2 * layer_pool * 2, f"{mem.temp_size_in_bytes / 2**20:.0f} MiB of temporaries"


def test_train_step_jaxpr_does_not_see_the_paged_branch(tiny_models, monkeypatch):
    """The layer scan serves training too: its jaxpr is the same with the
    paged hooks made to raise, and the same again while a paged layout is
    active (only a decode step asks for the pools)."""
    import jax
    import jax.numpy as jnp

    import accelerate_tpu.ops.paged_kv as pkv
    from accelerate_tpu.models.llama import causal_lm_loss

    model = tiny_models(True)
    batch = {"input_ids": jnp.ones((2, 16), jnp.int32)}

    def train_jaxpr():
        step = lambda p, b: jax.value_and_grad(causal_lm_loss)(p, b, model.apply_fn)  # noqa: E731
        return str(jax.make_jaxpr(step)(model.params, batch))

    base = train_jaxpr()
    with pkv.paged_mode(pkv.PagedConfig(block_size=4, num_blocks=9)):
        assert train_jaxpr() == base

    def never(*a, **k):
        raise AssertionError("the paged branch was taken")

    monkeypatch.setattr(pkv, "declare_pool_stack", never)
    monkeypatch.setattr(pkv, "layer_view", never)
    assert train_jaxpr() == base


def test_setup_log_shows_the_tick_aliasing_its_pool(tiny_llama, tmp_path):
    """The ProgramCache event of a compiled program carries the
    executable's alias_bytes / temp_bytes: the tick's covers the pool."""
    import jax

    from accelerate_tpu.telemetry.eventlog import EventLog, read_events

    log_path = str(tmp_path / "serve.jsonl")
    log = EventLog(log_path, rank=0)
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8,), paged_block_size=4, telemetry_log=log)
    pool_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(eng.slot_caches) if leaf.ndim >= 4)
    eng.generate_many([np.arange(1, 6, dtype=np.int32)], max_new_tokens=3)
    log.close()
    compiled = {e["program"]: e for e in read_events(log_path) if e.get("name") == "compile_cache_miss"}
    if "alias_bytes" not in compiled["paged_decode_tick"]:
        pytest.skip("this backend gives no memory analysis")
    for program in ("paged_decode_tick", "paste_row", "clear_slots"):
        assert compiled[program]["alias_bytes"] >= pool_bytes, program
        assert compiled[program]["temp_bytes"] >= 0


# -- first tokens read behind the decode dispatch: what a deferred token may end, and chunk windows

LAYOUTS = pytest.mark.parametrize("paged", [None, 4], ids=["dense", "paged"])


@LAYOUTS
def test_a_first_token_that_is_eos_retires_its_request_in_that_tick(tiny_llama, paged):
    """The request joins the tick's decode pass, since the host has not yet seen its token when the pass is
    dispatched; the rows that pass computes for it reach no caller, and its blocks are free after the tick."""
    ends, goes_on = np.ones((4,), np.int32), np.arange(20, 25, dtype=np.int32)
    eos = int(_reference(tiny_llama, ends, 1)[-1])
    want = _reference(tiny_llama, goes_on, 6)
    assert eos not in want[len(goes_on):], "the other request has to outlive the tick"
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(4, 8), paged_block_size=paged, tick_block=2, eos_token_id=eos)
    free0 = eng.pool_free_blocks
    a, b = eng.submit(ends, 6), eng.submit(goes_on, 6)
    eng.step()
    assert not eng._first_pending and eng.active_count == 1
    assert eng.poll(a).tolist() == ends.tolist() + [eos] and eng.partial(a).tolist() == [eos]
    assert eng.slot_req[0] is None and eng.slot_pos[0] == 0 and len(eng.partial(b)) == 3
    if paged:
        assert eng.pool_free_blocks == free0 - len(eng._slot_blocks[1]) and not eng._slot_blocks[0]
    # the freed slot and blocks serve the next request, whose rows the thrown-away pass never touched
    c = eng.submit(goes_on[:3], 4)
    eng.run()
    np.testing.assert_array_equal(eng.poll(b), want)
    np.testing.assert_array_equal(eng.poll(c), _reference(tiny_llama, goes_on[:3], 4))
    assert eng.metrics.first_tokens_deferred == 3 and eng.pool_free_blocks == free0


@LAYOUTS
def test_a_chunk_window_admission_defers_its_first_token_too(paged):
    """A prompt longer than the largest bucket runs as chunk windows and samples with ``sample_at``: that
    token is fed to the decode pass on the device like a fused prefill's, beside one in the same tick."""
    model = create_llama_model(LlamaConfig.tiny(), seq_len=64)
    long, short = (np.arange(30) * 7 % 250).astype(np.int32), np.arange(20, 25, dtype=np.int32)
    eng = ServingEngine(model, num_slots=2, prompt_buckets=(8,), paged_block_size=paged, max_len=64, tick_block=2)
    a, b = eng.submit(long, 5), eng.submit(short, 5)
    eng.step()
    assert not eng._first_pending and eng.metrics.first_tokens_deferred == 2
    assert len(eng.partial(a)) == len(eng.partial(b)) == 3
    eng.run()
    np.testing.assert_array_equal(eng.poll(a), _reference(model, long, 5))
    np.testing.assert_array_equal(eng.poll(b), _reference(model, short, 5))
