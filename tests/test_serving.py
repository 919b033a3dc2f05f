"""Continuous-batching serving engine (serving.py): token-exact parity
with generate(), slot reuse, mixed lengths, EOS retirement."""

import numpy as np
import pytest

from accelerate_tpu.generation import generate
from accelerate_tpu.models import LlamaConfig, create_llama_model
from accelerate_tpu.serving import ServingEngine


@pytest.fixture(scope="module")
def tiny_llama():
    return create_llama_model(LlamaConfig.tiny(), seq_len=16)


def _reference(model, prompt, n):
    out = generate(model, np.asarray(prompt, np.int32)[None], max_new_tokens=n)
    return np.asarray(out)[0]


def test_single_request_matches_generate(tiny_llama):
    prompt = (np.arange(8) % 250).astype(np.int32)
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8, 16))
    [got] = eng.generate_many([prompt], max_new_tokens=6)
    np.testing.assert_array_equal(got, _reference(tiny_llama, prompt, 6))


def test_mixed_lengths_and_more_requests_than_slots(tiny_llama):
    """8 prompts of different lengths through 2 slots: every output equals
    the static generate() result — slots are reused and prompts hit
    different prefill buckets."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 250, size=n).astype(np.int32) for n in (3, 8, 5, 12, 2, 7, 9, 4)]
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(4, 8, 16))
    outs = eng.generate_many(prompts, max_new_tokens=5)
    for prompt, got in zip(prompts, outs):
        np.testing.assert_array_equal(got, _reference(tiny_llama, prompt, 5))


def test_incremental_submit_midstream(tiny_llama):
    """Requests submitted while others decode still come out token-exact
    (the point of continuous batching)."""
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8,))
    a = eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=8)
    eng.step()
    eng.step()
    b = eng.submit(np.arange(20, 25, dtype=np.int32), max_new_tokens=4)
    eng.run()
    np.testing.assert_array_equal(eng.poll(a), _reference(tiny_llama, np.arange(1, 7), 8))
    np.testing.assert_array_equal(eng.poll(b), _reference(tiny_llama, np.arange(20, 25), 4))


def test_eos_retires_slot(tiny_llama):
    prompt = np.ones((4,), np.int32)
    full = _reference(tiny_llama, prompt, 8)
    eos = int(full[6])  # a token generate actually emits
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(4,), eos_token_id=eos)
    [got] = eng.generate_many([prompt], max_new_tokens=8)
    # engine stops AT the eos; generate() freezes and pads with eos after it
    assert len(got) <= len(full)
    np.testing.assert_array_equal(got, full[: len(got)])
    assert got[-1] == eos
    assert eng.active_count == 0


def test_partial_streams_and_cancel(tiny_llama):
    """partial() exposes the growing suffix mid-decode; cancel() frees
    the slot immediately and the surviving request stays token-exact."""
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8,), tick_block=2)
    a = eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=8)
    b = eng.submit(np.arange(20, 25, dtype=np.int32), max_new_tokens=8)
    assert eng.partial(a).size == 0  # queued: nothing yet
    eng.step()
    grew = eng.partial(a).size
    assert 0 < grew < 8 and eng.poll(a) is None  # mid-decode prefix of the answer
    got = eng.cancel(b)
    assert got.size >= 1  # b had started too
    eng.run()
    np.testing.assert_array_equal(eng.poll(a), _reference(tiny_llama, np.arange(1, 7), 8))
    # partial stays suffix-only after completion: a delta streamer never
    # re-emits prompt tokens on the finishing tick
    np.testing.assert_array_equal(eng.partial(a), eng.poll(a)[6:])
    assert eng.poll(b) is None  # cancelled ids never resolve
    with pytest.raises(KeyError):
        eng.partial(b)
    with pytest.raises(ValueError, match="finished"):
        eng.cancel(a)
    c = eng.submit(np.ones(3, np.int32), max_new_tokens=4)
    assert eng.cancel(c).size == 0  # cancelled straight out of the queue
    with pytest.raises(KeyError):
        eng.cancel(999)


def test_cancel_frees_paged_blocks(tiny_llama):
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(8,), tick_block=2, paged_block_size=4)
    free0 = eng.pool_free_blocks
    uid = eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=12)
    eng.step()
    assert eng.pool_free_blocks < free0
    eng.cancel(uid)
    assert eng.pool_free_blocks == free0  # blocks returned immediately
    # slot is reusable and exact afterwards
    [out] = eng.generate_many([np.arange(9, 12, dtype=np.int32)], max_new_tokens=4)
    np.testing.assert_array_equal(out, _reference(tiny_llama, np.arange(9, 12), 4))


def test_validation_errors(tiny_llama):
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(4,), max_len=16)
    with pytest.raises(ValueError, match="cache"):
        eng.submit(np.ones((4,), np.int32), max_new_tokens=99)
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="max_position_embeddings"):
        ServingEngine(tiny_llama, max_len=999)


def test_long_prompt_chunked_prefill(tiny_llama):
    """A prompt longer than the largest bucket streams through end-aligned
    chunk windows — output still token-exact vs static generate()."""
    prompt = (np.arange(12) % 250 + 1).astype(np.int32)
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(4, 8))
    [got] = eng.generate_many([prompt], max_new_tokens=4)
    np.testing.assert_array_equal(got, _reference(tiny_llama, prompt, 4))


def test_long_prompt_unaligned_overlap(tiny_llama):
    """Length not a multiple of the chunk: the final window overlaps the
    previous one (end-aligned) and recomputes identical K/V."""
    prompt = (np.arange(13) % 250 + 1).astype(np.int32)  # C=8 -> windows [0,8), [5,13)
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(8,))
    [got] = eng.generate_many([prompt], max_new_tokens=3)
    np.testing.assert_array_equal(got, _reference(tiny_llama, prompt, 3))


def test_prefix_cache_token_exact(tiny_llama):
    """Two requests share a registered prefix: each copies the prefix KV
    row and prefills only its suffix; outputs equal full-prompt generate()."""
    prefix = (np.arange(6) % 250 + 3).astype(np.int32)
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(4, 8))
    pid = eng.register_prefix(prefix)
    sufa = np.asarray([9, 8, 7], np.int32)
    sufb = np.asarray([11, 12], np.int32)
    a = eng.submit(sufa, max_new_tokens=5, prefix_id=pid)
    b = eng.submit(sufb, max_new_tokens=5, prefix_id=pid)
    eng.run()
    np.testing.assert_array_equal(
        eng.poll(a), _reference(tiny_llama, np.concatenate([prefix, sufa]), 5))
    np.testing.assert_array_equal(
        eng.poll(b), _reference(tiny_llama, np.concatenate([prefix, sufb]), 5))


def test_prefix_with_overlapping_window_into_prefix(tiny_llama):
    """A short suffix after a mid-length prefix: the single warm window
    starts INSIDE the prefix region and rewrites identical K/V there."""
    prefix = (np.arange(5) + 1).astype(np.int32)
    suffix = (np.arange(9) + 40).astype(np.int32)  # 5+9=14, C=8: windows [5,13)->[6,14)
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(8,))
    pid = eng.register_prefix(prefix)
    uid = eng.submit(suffix, max_new_tokens=2, prefix_id=pid)
    eng.run()
    np.testing.assert_array_equal(
        eng.poll(uid), _reference(tiny_llama, np.concatenate([prefix, suffix]), 2))


def test_prefix_validation_and_eviction(tiny_llama):
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(4,), max_len=16)
    with pytest.raises(ValueError, match="unknown prefix_id"):
        eng.submit(np.ones((2,), np.int32), prefix_id=7)
    with pytest.raises(ValueError, match="empty"):
        eng.register_prefix(np.zeros((0,), np.int32))
    pid = eng.register_prefix(np.ones((6,), np.int32))
    with pytest.raises(ValueError, match="cache"):
        eng.submit(np.ones((4,), np.int32), max_new_tokens=8, prefix_id=pid)
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.zeros((0,), np.int32), prefix_id=pid)
    # eviction: refused while a queued request references it, ok after drain
    uid = eng.submit(np.asarray([3, 4], np.int32), max_new_tokens=2, prefix_id=pid)
    with pytest.raises(ValueError, match="still referenced"):
        eng.unregister_prefix(pid)
    eng.run()
    assert eng.poll(uid) is not None
    eng.unregister_prefix(pid)
    assert pid not in eng._prefixes
    with pytest.raises(ValueError, match="unknown prefix_id"):
        eng.unregister_prefix(pid)


def test_gpt2_family_works_too():
    from accelerate_tpu.models import GPT2Config, create_gpt2_model

    model = create_gpt2_model(GPT2Config.tiny(), seq_len=16)
    prompt = (np.arange(6) % 200).astype(np.int32)
    eng = ServingEngine(model, num_slots=2, prompt_buckets=(8,))
    [got] = eng.generate_many([prompt], max_new_tokens=4)
    np.testing.assert_array_equal(got, _reference(model, prompt, 4))


def test_sampling_deterministic_per_seed(tiny_llama):
    """Temperature sampling: same seed -> identical outputs, different
    seed -> different; greedy engines are unaffected by seed."""
    prompts = [np.arange(1, 7, dtype=np.int32), np.arange(30, 38, dtype=np.int32)]

    def run(seed, temperature=1.0):
        eng = ServingEngine(
            tiny_llama, num_slots=2, prompt_buckets=(8,), temperature=temperature, top_k=8, seed=seed
        )
        return eng.generate_many(prompts, max_new_tokens=6)

    a, b, c = run(1), run(1), run(2)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert any(not np.array_equal(x, y) for x, y in zip(a, c))


def test_top_k1_collapses_to_greedy(tiny_llama):
    prompt = (np.arange(8) % 250).astype(np.int32)
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(8,), temperature=5.0, top_k=1)
    [got] = eng.generate_many([prompt], max_new_tokens=5)
    np.testing.assert_array_equal(got, _reference(tiny_llama, prompt, 5))


def test_serving_with_tp_sharded_model(tiny_llama):
    """The engine composes with mesh-sharded params (serving a model too
    big for one chip): TP-sharded slots produce the single-device tokens."""
    import jax

    from accelerate_tpu.big_modeling import shard_model
    from accelerate_tpu.models import LlamaConfig, create_llama_model
    from accelerate_tpu.parallel.mesh import MeshConfig

    prompt = (np.arange(8) % 250).astype(np.int32)
    want = _reference(tiny_llama, prompt, 5)

    model = create_llama_model(LlamaConfig.tiny(), seq_len=16)
    shard_model(model, MeshConfig(data=1, tensor=4).build(jax.devices()[:4]))
    eng = ServingEngine(model, num_slots=2, prompt_buckets=(8,))
    [got] = eng.generate_many([prompt], max_new_tokens=5)
    np.testing.assert_array_equal(got, want)


def test_params_update_after_construction_is_used(tiny_llama):
    """decode ticks read self.model.params at call time — swapping weights
    after engine construction changes outputs (no stale closure)."""
    import jax

    prompt = (np.arange(8) % 250).astype(np.int32)
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(8,))
    [before] = eng.generate_many([prompt], max_new_tokens=5)
    old = tiny_llama.params
    try:
        tiny_llama.params = jax.tree.map(lambda p: p * 1.5, old)
        [after] = eng.generate_many([prompt], max_new_tokens=5)
    finally:
        tiny_llama.params = old
    assert not np.array_equal(before, after)


def test_bucket_and_budget_validation(tiny_llama):
    import pytest as _pytest

    with _pytest.raises(ValueError, match="bucket"):
        ServingEngine(tiny_llama, prompt_buckets=(8, 999))
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(8,))
    with _pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.ones((4,), np.int32), max_new_tokens=0)


def test_gptneox_family_works_too():
    from accelerate_tpu.models import GPTNeoXConfig, create_gptneox_model

    model = create_gptneox_model(GPTNeoXConfig.tiny(), seq_len=16)
    prompt = (np.arange(6) % 200).astype(np.int32)
    eng = ServingEngine(model, num_slots=2, prompt_buckets=(8,))
    [got] = eng.generate_many([prompt], max_new_tokens=4)
    np.testing.assert_array_equal(got, _reference(model, prompt, 4))


def test_stop_sequences_end_generation(tiny_llama):
    """Per-request stop sequences (vLLM `stop` analogue at the token
    level): generation ends when the generated tail matches, the matched
    tokens stay in the output, other requests are unaffected."""
    prompt = np.ones((4,), np.int32)
    full = _reference(tiny_llama, prompt, 8)
    gen = full[len(prompt):]
    stop = [int(gen[3]), int(gen[4])]  # a 2-token run generate actually emits
    # first place the pair occurs (the engine must stop there, which is
    # positions 3-4 unless the pair also shows up earlier in this output)
    first = next(i for i in range(len(gen) - 1) if [int(gen[i]), int(gen[i + 1])] == stop)
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(4, 8))
    u_stop = eng.submit(prompt, max_new_tokens=8, stop_sequences=[stop])
    u_free = eng.submit(prompt, max_new_tokens=8)
    while eng.poll(u_stop) is None or eng.poll(u_free) is None:
        eng.step()
    got_stop, got_free = eng.poll(u_stop), eng.poll(u_free)
    np.testing.assert_array_equal(got_free, full)       # no stop: full output
    assert len(got_stop) == len(prompt) + first + 2     # ends right at the match
    np.testing.assert_array_equal(got_stop, full[: len(got_stop)])
    assert list(got_stop[-2:]) == stop                  # stop tokens retained
    assert eng.active_count == 0


def test_stop_sequence_validation(tiny_llama):
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(4,))
    with pytest.raises(ValueError, match="empty stop sequence"):
        eng.submit(np.ones((2,), np.int32), stop_sequences=[[]])


def test_logprobs_match_full_context_forward(tiny_llama):
    """Per-token logprobs (vLLM-style surface): for greedy decoding they
    must equal the f32 log-softmax of a FULL-context forward at each
    generated position — one reference computation, both cache layouts."""
    import jax

    prompt = (np.arange(6) % 250).astype(np.int32)
    for kwargs in ({}, {"paged_block_size": 4}):
        eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8, 16), **kwargs)
        uid = eng.submit(prompt, max_new_tokens=5)
        while eng.poll(uid) is None:
            eng.step()
        full = eng.poll(uid)
        lps = eng.logprobs(uid)
        assert lps.shape == (5,) and lps.dtype == np.float32

        logits = tiny_llama.apply_fn(tiny_llama.params, full[None, :-1].astype(np.int32))
        ref_rows = np.asarray(logits[0], np.float32)
        for i in range(5):
            ctx = len(prompt) + i  # tokens seen before generating full[ctx]
            row = ref_rows[ctx - 1]
            want = row[full[ctx]] - np.log(np.exp(row - row.max()).sum()) - row.max()
            np.testing.assert_allclose(lps[i], want, atol=2e-3, err_msg=f"{kwargs} token {i}")


def test_logprobs_lifecycle(tiny_llama):
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(8,))
    u1 = eng.submit(np.ones((4,), np.int32), max_new_tokens=3)
    u2 = eng.submit(np.ones((5,), np.int32), max_new_tokens=3)  # queued behind u1
    assert eng.logprobs(u2).shape == (0,)  # queued: empty
    while eng.poll(u1) is None:
        eng.step()
    assert len(eng.logprobs(u1)) == 3
    with pytest.raises(KeyError):
        eng.logprobs(999)


# --------------------------------------------------------------------- #
# serving metrics (telemetry/serving_metrics.py, wired by the engine)
# --------------------------------------------------------------------- #


def test_serving_metrics_counters_and_latency(tiny_llama):
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 250, size=n).astype(np.int32) for n in (3, 8, 5)]
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8, 16))
    eng.generate_many(prompts, max_new_tokens=5)
    snap = eng.metrics.snapshot()
    assert snap["requests_submitted"] == 3
    assert snap["requests_completed"] == 3
    assert snap["requests_cancelled"] == 0
    assert snap["prefills"] == 3
    assert snap["tokens_generated"] == 15  # 3 requests x 5 tokens, no overshoot counted
    assert snap["queue_depth"] == 0 and snap["active_slots"] == 0
    assert snap["ttft_ms_p50"] > 0 and snap["ttft_ms_p95"] >= snap["ttft_ms_p50"]
    assert snap["e2e_ms_p50"] >= snap["ttft_ms_p50"]
    assert snap["tokens_per_sec"] > 0
    assert snap["kv_block_utilization"] is None  # dense mode


def test_serving_metrics_cancel_and_queue_depth(tiny_llama):
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(8,))
    u1 = eng.submit(np.ones((4,), np.int32), max_new_tokens=4)
    u2 = eng.submit(np.ones((4,), np.int32), max_new_tokens=4)
    assert eng.metrics.queue_depth == 2
    eng.step()  # u1 admitted+decoding, u2 queued
    eng.cancel(u2)
    assert eng.metrics.requests_cancelled == 1
    eng.run()
    snap = eng.metrics.snapshot()
    assert snap["requests_submitted"] == 2
    assert snap["requests_completed"] == 1
    assert snap["requests_cancelled"] == 1


def test_serving_metrics_kv_utilization_and_preemptions(tiny_llama):
    # pool sized so request 1 takes EVERY usable block and request 2 must
    # wait; tick_block small so request 1 stays in flight across steps
    eng = ServingEngine(
        tiny_llama, num_slots=2, prompt_buckets=(8,), paged_block_size=4,
        pool_blocks=5, tick_block=2,
    )
    u1 = eng.submit(np.ones((4,), np.int32), max_new_tokens=10)
    u2 = eng.submit(np.ones((4,), np.int32), max_new_tokens=10)
    eng.step()
    util = eng.metrics.kv_block_utilization
    assert util is not None and 0.0 < util <= 1.0
    eng.run()
    assert eng.metrics.preemptions >= 1  # admission blocked at least once
    assert eng.metrics.requests_completed == 2
    assert eng.metrics.kv_block_utilization == 0.0  # all blocks returned


def test_serving_metrics_prometheus_exposition(tiny_llama):
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8,))
    eng.generate_many([np.ones((4,), np.int32)], max_new_tokens=3)
    text = eng.metrics.prometheus_text()
    assert "# HELP accelerate_tpu_serving_ttft_ms" in text
    assert "# TYPE accelerate_tpu_serving_requests_submitted_total counter" in text
    assert "accelerate_tpu_serving_requests_completed_total 1" in text
    assert "accelerate_tpu_serving_tokens_generated_total 3" in text
    assert 'accelerate_tpu_serving_ttft_ms{quantile="0.5"}' in text
    # every sample line parses as "name[{labels}] value"
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        name, value = line.rsplit(" ", 1)
        float(value)


def test_serving_metrics_replica_label(tiny_llama):
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8,))
    eng.metrics.replica = "r7"
    eng.generate_many([np.ones((4,), np.int32)], max_new_tokens=3)
    text = eng.metrics.prometheus_text()
    assert 'accelerate_tpu_serving_requests_completed_total{replica="r7"} 1' in text
    assert 'accelerate_tpu_serving_ttft_ms{replica="r7",quantile="0.5"}' in text
    assert 'accelerate_tpu_serving_ttft_ms_count{replica="r7"} 1' in text
    assert eng.metrics.snapshot()["replica"] == "r7"


def test_serving_metrics_merge_aggregates_fleet_view(tiny_llama):
    from accelerate_tpu.telemetry.serving_metrics import ServingMetrics, fleet_prometheus_text

    engines = []
    for name in ("r0", "r1"):
        eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8,))
        eng.metrics.replica = name
        eng.generate_many([np.ones((4,), np.int32)], max_new_tokens=3)
        engines.append(eng)
    merged = ServingMetrics.merge([e.metrics for e in engines])
    assert merged.requests_completed == 2
    assert merged.tokens_generated == 6
    # pooled latency windows: fleet percentiles see every replica's samples
    assert len(merged.ttft_ms) == 2
    snap = merged.snapshot()
    assert snap["replica"] == "fleet" and snap["requests_completed"] == 2
    text = merged.prometheus_text()
    assert 'accelerate_tpu_serving_tokens_generated_total{replica="fleet"} 6' in text
    # one scrape body for the whole fleet: ONE HELP/TYPE block per metric,
    # one labeled sample per replica
    fleet_text = fleet_prometheus_text([e.metrics for e in engines])
    assert fleet_text.count("# TYPE accelerate_tpu_serving_requests_completed_total counter") == 1
    assert 'requests_completed_total{replica="r0"} 1' in fleet_text
    assert 'requests_completed_total{replica="r1"} 1' in fleet_text
    for line in fleet_text.splitlines():
        if line.startswith("#") or not line:
            continue
        name, value = line.rsplit(" ", 1)
        float(value)


def test_serving_metrics_mirror_to_event_log(tiny_llama, tmp_path):
    from accelerate_tpu.telemetry import EventLog, read_events

    log = EventLog(str(tmp_path / "serve.jsonl"), rank=0)
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8,), telemetry_log=log)
    eng.generate_many([np.ones((4,), np.int32)], max_new_tokens=3)
    eng.metrics.emit()
    log.close()
    events = read_events(str(tmp_path / "serve.jsonl"))
    names = {e["name"] for e in events}
    assert "serving.requests_completed" in names and "serving.tokens_generated" in names
    # and the summarize CLI surface understands them
    from accelerate_tpu.telemetry import render_text, summarize

    report = summarize(events)
    assert report["serving"]["requests_completed"] == 1
    assert "tokens_generated" in render_text(report)


# -- a tick's programs queued back to back: first tokens read behind the decode dispatch

LAYOUTS = pytest.mark.parametrize("paged", [None, 8], ids=["dense", "paged"])

# what the engine gave before first tokens were deferred (commit 513a685, CPU): five sampled requests
# (temperature 0.8, seed 5, tick_block 3; prompts of 8, 6, 5, 12, 20 tokens from ``default_rng(0)``), the same
# in both layouts. Three of them are admitted in one tick beside a request that decodes
RECORDED_TOKENS = [
    [130, 230, 241, 136, 107, 246, 90, 241, 90], [109, 45, 79, 46, 240, 74, 15], [36, 131, 94, 65, 234, 109, 246],
    [63, 255, 9, 225, 236, 62, 91], [11, 79, 28, 121, 21, 222, 6],
]
RECORDED_LOGPROBS = [
    [-4.5971, -4.2934, -4.5173, -4.0374, -6.0532, -6.8456, -4.6754, -4.8493, -5.6424],
    [-4.4046, -6.4540, -3.8084, -5.8701, -2.9244, -6.4326, -5.1898],
    [-4.9667, -3.5736, -5.4003, -5.0630, -3.3387, -3.2828, -3.6970],
    [-4.5048, -4.5741, -4.5004, -4.5689, -4.2270, -4.7444, -5.5905],
    [-4.3890, -6.3168, -5.5113, -4.3119, -5.5242, -4.3124, -5.4492],
]


@pytest.fixture(scope="module")
def tiny_llama64():
    return create_llama_model(LlamaConfig.tiny(), seq_len=64)


def _last_tick():
    from accelerate_tpu.telemetry.trace import phase_log

    return phase_log().roots("engine.tick", 1)[0]


def _step_whole(eng):
    """One tick, then what holds between any two: no first token is pending, and every request that has
    been admitted and prefilled shows its first token to ``partial`` and to ``export_inflight``."""
    eng.step()
    assert not eng._first_pending
    exported = {snap["uid"]: snap for snap in eng.export_inflight(include_kv=False)}
    for slot, req in enumerate(eng.slot_req):
        if req is not None and eng.slot_phase[slot] == "decode":
            assert len(eng.partial(req.uid)) >= 1
            assert exported[req.uid]["out_tokens"] == eng.partial(req.uid).tolist()
    return _last_tick().done


@LAYOUTS
def test_admissions_of_one_tick_give_the_recorded_tokens_and_logprobs(tiny_llama64, paged):
    eng = ServingEngine(tiny_llama64, num_slots=4, prompt_buckets=(8, 16), paged_block_size=paged, max_len=64,
                        temperature=0.8, seed=5, tick_block=3)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(5, 250, size=n).astype(np.int32) for n in (8, 6, 5, 12, 20)]
    uids = [eng.submit(prompts[0], 9)]
    assert _step_whole(eng)["first_tokens_deferred"] == 1
    _step_whole(eng)
    uids += [eng.submit(p, 7) for p in prompts[1:]]
    done = _step_whole(eng)  # three free slots: three admissions, queued behind one another, and one decode pass
    assert done["admitted"] == done["first_tokens_deferred"] == 3
    assert [len(eng.partial(u)) for u in uids] == [9, 1 + 3, 1 + 3, 1 + 3, 0]
    while eng.queue or eng.active_count:
        _step_whole(eng)
    for u, prompt, tokens, lps in zip(uids, prompts, RECORDED_TOKENS, RECORDED_LOGPROBS):
        assert eng.done[u][len(prompt):].tolist() == tokens
        np.testing.assert_allclose(eng.logprobs(u), lps, atol=2e-4)
    assert eng.metrics.first_tokens_deferred == eng.metrics.prefills == 5


@LAYOUTS
def test_a_request_of_one_token_never_joins_a_decode_pass(tiny_llama64, paged):
    eng = ServingEngine(tiny_llama64, num_slots=2, prompt_buckets=(8,), paged_block_size=paged, max_len=64, tick_block=2)
    one, more = np.arange(1, 7, dtype=np.int32), np.arange(20, 25, dtype=np.int32)
    a, b = eng.submit(one, 1), eng.submit(more, 4)
    done = _step_whole(eng)
    assert done["admitted"] == 2 and done["first_tokens_deferred"] == 1 and done["retired"] == 1
    np.testing.assert_array_equal(eng.poll(a), _reference(tiny_llama64, one, 1))
    assert len(eng.partial(b)) == 3  # its first token and the pass's two
    eng.run()
    np.testing.assert_array_equal(eng.poll(b), _reference(tiny_llama64, more, 4))
    # alone in its tick: no decode pass follows, nothing is deferred, the token is there when step() returns
    c = eng.submit(one, 1)
    done = _step_whole(eng)
    assert done["first_tokens_deferred"] == 0 and "engine.decode.dispatch" not in _last_tick().children
    np.testing.assert_array_equal(eng.poll(c), eng.poll(a))


@LAYOUTS
def test_a_hand_off_and_a_resumed_admission_beside_a_fresh_one(tiny_llama64, paged):
    kwargs = dict(prompt_buckets=(8,), max_len=64, tick_block=2)
    prompts = [np.arange(1, 7, dtype=np.int32), np.arange(20, 25, dtype=np.int32), np.arange(40, 48, dtype=np.int32)]
    want = [_reference(tiny_llama64, p, 6) for p in prompts]
    handoff = ServingEngine(tiny_llama64, num_slots=1, **kwargs).prefill_detached(prompts[0], 6)
    eng = ServingEngine(tiny_llama64, num_slots=3, paged_block_size=paged, **kwargs)
    resumed = eng.submit(prompts[1], 6)
    _step_whole(eng)
    eng._preempt(0)  # between ticks, as a scheduler's eviction leaves it: queued, three tokens carried
    assert len(eng.partial(resumed)) == 3 and eng.active_count == 0
    handed, fresh = eng.submit_prefilled(handoff), eng.submit(prompts[2], 6)
    done = _step_whole(eng)
    # the hand-off's token came on the host and the resumed request samples none: one token was on the device
    assert done["admitted"] == 3 and done["first_tokens_deferred"] == 1
    assert [len(eng.partial(u)) for u in (handed, resumed, fresh)] == [3, 5, 3]
    eng.run()
    for uid, ref in zip((handed, resumed, fresh), want):
        np.testing.assert_array_equal(eng.poll(uid), ref)
    assert eng.metrics.first_tokens_deferred == 2 and eng.metrics.prefills == 3 and eng.metrics.resumes == 1


@LAYOUTS
def test_preempting_a_slot_admitted_in_the_same_tick_reads_its_token_first(tiny_llama64, paged):
    """The stock policy evicts only a request less important than the queue's head, which the queue's order
    keeps behind that head; a policy of the caller's own may name any decoding slot, one this tick admitted too."""
    from accelerate_tpu.scheduling import Scheduler, SchedulerConfig

    class EvictOnce(Scheduler):
        evicted = False

        def pick_victim(self, incoming_priority, decoding):
            if self.evicted or not decoding:
                return None
            self.evicted = True
            return decoding[-1][0]

    eng = ServingEngine(tiny_llama64, num_slots=1, prompt_buckets=(8,), paged_block_size=paged, max_len=64, tick_block=2,
                        scheduler=EvictOnce(SchedulerConfig(enable_preemption=True)))
    prompts = [np.arange(1, 7, dtype=np.int32), np.arange(20, 25, dtype=np.int32)]
    first, second = eng.submit(prompts[0], 5), eng.submit(prompts[1], 5)
    # admits ``first``; evicts it for ``second`` with its token read and not lost; the queue's order puts it
    # back ahead of ``second``, so it resumes in the same tick with that token carried, and decodes
    done = _step_whole(eng)
    assert done["admitted"] == 2 and done["first_tokens_deferred"] == 0
    assert eng.metrics.decode_preemptions == eng.metrics.resumes == eng.metrics.prefills == 1
    assert eng.partial(first).tolist() == _reference(tiny_llama64, prompts[0], 3)[-3:].tolist()
    eng.run()
    for uid, prompt in zip((first, second), prompts):
        np.testing.assert_array_equal(eng.poll(uid), _reference(tiny_llama64, prompt, 5))


@LAYOUTS
def test_a_tick_cut_short_at_a_crash_point_leaves_no_first_token_on_the_device(tiny_llama64, paged):
    from accelerate_tpu.ft.crashpoints import set_crash_hook

    eng = ServingEngine(tiny_llama64, num_slots=2, prompt_buckets=(8,), paged_block_size=paged, max_len=64, tick_block=2)
    prompt = np.arange(1, 7, dtype=np.int32)
    uid = eng.submit(prompt, 5)

    def crash(label, **_):
        if label == "mid_decode":
            raise RuntimeError("chaos")

    set_crash_hook(crash)
    try:
        with pytest.raises(RuntimeError, match="chaos"):
            eng.step()
    finally:
        set_crash_hook(None)
    assert not eng._first_pending
    (snap,) = eng.export_inflight(include_kv=False)
    assert snap["out_tokens"] == eng.partial(uid).tolist() == _reference(tiny_llama64, prompt, 1)[-1:].tolist()
