"""Token-budget continuous-batching scheduler (scheduling.py + the
ServingEngine tick loop): budget-interleaved prefill chunks stay
token-exact, priority admission orders the queue, SLO shedding raises
structured rejections, decode preemption + recompute-resume is
token-exact (logprobs to float32 rounding), and the uid index keeps
streaming accessors O(1)."""

import numpy as np
import pytest

from accelerate_tpu.generation import generate
from accelerate_tpu.models import LlamaConfig, create_llama_model
from accelerate_tpu.scheduling import Scheduler, SchedulerConfig, ShedError
from accelerate_tpu.serving import ServingEngine


@pytest.fixture(scope="module")
def tiny_llama():
    return create_llama_model(LlamaConfig.tiny(), seq_len=32)


def _reference(model, prompt, n):
    out = generate(model, np.asarray(prompt, np.int32)[None], max_new_tokens=n)
    return np.asarray(out)[0]


# --------------------------------------------------------------------- #
# policy unit tests (no jax, no engine)
# --------------------------------------------------------------------- #


def test_scheduler_config_validation():
    with pytest.raises(ValueError, match="token_budget"):
        SchedulerConfig(token_budget=0)
    with pytest.raises(ValueError, match="max_queue_depth"):
        SchedulerConfig(max_queue_depth=0)
    with pytest.raises(ValueError, match="shed_action"):
        SchedulerConfig(shed_action="drop")


def test_scheduler_policy_decisions():
    s = Scheduler(SchedulerConfig(token_budget=64, max_queue_depth=2,
                                  max_queue_wait_s=1.0, enable_preemption=True))
    # ordering: class first, then submission order
    assert s.order_key(0, 7) < s.order_key(1, 3)
    assert s.order_key(1, 3) < s.order_key(1, 4)
    # budget: decodes claim theirs first
    assert s.tick_budget(4, 8) == 32
    assert s.tick_budget(100, 8) == 0
    # shedding: floor protects priority 0; thresholds gate
    assert s.shed_on_submit(0, 99) is None
    assert s.shed_on_submit(1, 2) is not None
    assert s.shed_on_submit(1, 1) is None
    assert s.shed_on_wait(1, 2.0) is not None
    assert s.shed_on_wait(0, 2.0) is None
    # victim: youngest of the least-important class, strictly below incoming
    decoding = [(0, 1, 5), (1, 2, 6), (2, 2, 9), (3, 0, 2)]
    assert s.pick_victim(0, decoding) == 2  # priority 2, uid 9
    assert s.pick_victim(2, decoding) is None  # nothing strictly below
    off = Scheduler(SchedulerConfig())
    assert off.pick_victim(0, decoding) is None  # preemption disabled
    # the default config: one class admits in submission order, no budget, no shedding
    assert sorted([7, 3, 5], key=lambda uid: off.order_key(0, uid)) == [3, 5, 7]
    assert off.tick_budget(100, 8) == float("inf")
    assert off.shed_on_submit(1, 10**6) is None and off.shed_on_wait(1, 1e9) is None


def test_serving_scheduler_kwargs_handler():
    from accelerate_tpu.utils import ServingSchedulerKwargs

    kw = ServingSchedulerKwargs(token_budget=128, enable_preemption=True)
    cfg = kw.to_scheduler_config()
    assert isinstance(cfg, SchedulerConfig)
    assert cfg.token_budget == 128 and cfg.enable_preemption
    assert kw.to_kwargs() == {"token_budget": 128, "enable_preemption": True}


# --------------------------------------------------------------------- #
# budget-interleaved prefill
# --------------------------------------------------------------------- #


def test_budget_interleaves_long_prefill_token_exact(tiny_llama):
    """A 20-token prompt under a 12-token budget streams one chunk window
    per tick while the short request keeps decoding — and both outputs
    still equal static generate()."""
    short = (np.arange(4) % 250 + 1).astype(np.int32)
    long = (np.arange(20) % 250 + 1).astype(np.int32)
    eng = ServingEngine(
        tiny_llama, num_slots=2, prompt_buckets=(4, 8), tick_block=1,
        scheduler=SchedulerConfig(token_budget=12),
    )
    a = eng.submit(short, max_new_tokens=8)
    b = eng.submit(long, max_new_tokens=4)
    eng.step()
    # the short request produced tokens; the long prefill is mid-stream
    assert eng.partial(a).size >= 1
    assert eng.partial(b).size == 0 and eng.poll(b) is None
    state_b, _ = eng._locate(b)
    assert state_b == "active"  # holds a slot in the prefill phase
    eng.run()
    np.testing.assert_array_equal(eng.poll(a), _reference(tiny_llama, short, 8))
    np.testing.assert_array_equal(eng.poll(b), _reference(tiny_llama, long, 4))


def test_tiny_budget_cannot_livelock(tiny_llama):
    """token_budget=1 is below every window width: forced progress still
    drains the queue and outputs stay exact."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 250, size=n).astype(np.int32) for n in (3, 11, 6)]
    eng = ServingEngine(
        tiny_llama, num_slots=2, prompt_buckets=(4, 8), tick_block=2,
        scheduler=SchedulerConfig(token_budget=1),
    )
    outs = eng.generate_many(prompts, max_new_tokens=4)
    for p, got in zip(prompts, outs):
        np.testing.assert_array_equal(got, _reference(tiny_llama, p, 4))


def test_priority_orders_admission(tiny_llama):
    """With one slot, a later high-priority submission admits before
    earlier low-priority ones."""
    p_lo = np.asarray([5, 6, 7], np.int32)
    p_hi = np.asarray([9, 9], np.int32)
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(4,))
    eng.submit(np.ones(3, np.int32), max_new_tokens=2)  # occupies the slot
    lo = eng.submit(p_lo, max_new_tokens=2, priority=1)
    hi = eng.submit(p_hi, max_new_tokens=2, priority=0)
    order = []
    while eng.queue or eng.active_count:
        eng.step()
        for uid in (lo, hi):
            if eng.poll(uid) is not None and uid not in order:
                order.append(uid)
    assert order == [hi, lo]


# --------------------------------------------------------------------- #
# SLO load shedding
# --------------------------------------------------------------------- #


def test_submit_depth_shed_is_structured(tiny_llama):
    eng = ServingEngine(
        tiny_llama, num_slots=1, prompt_buckets=(4,),
        scheduler=SchedulerConfig(max_queue_depth=1),
    )
    eng.submit(np.ones(3, np.int32), max_new_tokens=2, priority=1)
    with pytest.raises(ShedError) as ei:
        eng.submit(np.ones(3, np.int32), max_new_tokens=2, priority=1)
    assert ei.value.queue_depth == 1 and ei.value.priority == 1
    assert "max_queue_depth" in ei.value.reason
    # priority 0 is below the shed floor: never rejected
    ok = eng.submit(np.ones(3, np.int32), max_new_tokens=2, priority=0)
    assert isinstance(ok, int)
    assert eng.metrics.requests_shed == 1


def test_queue_wait_shed_surfaces_via_poll(tiny_llama):
    eng = ServingEngine(
        tiny_llama, num_slots=1, prompt_buckets=(4,),
        scheduler=SchedulerConfig(max_queue_wait_s=0.0),
    )
    keep = eng.submit(np.ones(3, np.int32), max_new_tokens=3, priority=0)
    stale = eng.submit(np.ones(4, np.int32), max_new_tokens=3, priority=1)
    eng.run()
    np.testing.assert_array_equal(eng.poll(keep), _reference(tiny_llama, np.ones(3), 3))
    with pytest.raises(ShedError) as ei:
        eng.poll(stale)
    assert ei.value.uid == stale and ei.value.queue_wait_ms >= 0.0
    with pytest.raises(ShedError):
        eng.partial(stale)
    assert eng.metrics.requests_shed == 1


def test_deprioritize_action_demotes_instead_of_rejecting(tiny_llama):
    eng = ServingEngine(
        tiny_llama, num_slots=1, prompt_buckets=(4,),
        scheduler=SchedulerConfig(max_queue_depth=1, shed_action="deprioritize"),
    )
    eng.submit(np.ones(3, np.int32), max_new_tokens=2, priority=1)
    demoted = eng.submit(np.ones(3, np.int32), max_new_tokens=2, priority=1)
    _, req = eng._locate(demoted)
    assert req.priority == 99  # deprioritize_to default
    eng.run()
    assert eng.poll(demoted) is not None  # still served, just later
    assert eng.metrics.requests_deprioritized == 1


# --------------------------------------------------------------------- #
# decode preemption + recompute resume
# --------------------------------------------------------------------- #


def test_preempt_resume_token_and_logprob_exact(tiny_llama):
    """A high-priority arrival evicts the decoding low-priority request
    (dense slot pressure); the victim resumes by recompute and its FULL
    output equals an unpreempted control run, its logprobs to float32
    rounding: the resumed stream decodes over K/V rows a chunk-window
    program rebuilt, the control over rows prefill and the tick wrote."""
    p_victim = (np.arange(6) % 250 + 1).astype(np.int32)
    p_urgent = np.asarray([3, 1, 4, 1, 5], np.int32)
    eng = ServingEngine(
        tiny_llama, num_slots=1, prompt_buckets=(8,), tick_block=2,
        scheduler=SchedulerConfig(enable_preemption=True),
    )
    victim = eng.submit(p_victim, max_new_tokens=10, priority=1)
    eng.step()
    streamed = eng.partial(victim).copy()
    assert streamed.size >= 1
    urgent = eng.submit(p_urgent, max_new_tokens=4, priority=0)
    eng.step()
    # the victim was evicted and requeued with its generated-so-far tokens
    state, req = eng._locate(victim)
    assert state == "queued" and req.preempted
    np.testing.assert_array_equal(eng.partial(victim), streamed)  # nothing lost
    assert eng.metrics.decode_preemptions == 1
    eng.run()
    assert eng.metrics.resumes == 1
    np.testing.assert_array_equal(eng.poll(urgent), _reference(tiny_llama, p_urgent, 4))
    np.testing.assert_array_equal(eng.poll(victim), _reference(tiny_llama, p_victim, 10))
    # logprobs vs an unpreempted control engine (same uid -> same chain):
    # what streamed before the eviction is untouched, the rest agrees to rounding
    control = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(8,), tick_block=2)
    c = control.submit(p_victim, max_new_tokens=10, priority=1)
    control.run()
    n = streamed.size
    np.testing.assert_array_equal(eng.logprobs(victim)[:n], control.logprobs(c)[:n])
    np.testing.assert_allclose(eng.logprobs(victim), control.logprobs(c), rtol=1e-6)


def test_preempt_resume_exact_under_sampling(tiny_llama):
    """Temperature sampling across a preemption: the carried key chain
    makes the resumed stream identical to the unpreempted control."""
    p_victim = (np.arange(5) % 250 + 2).astype(np.int32)
    kwargs = dict(num_slots=1, prompt_buckets=(8,), tick_block=2,
                  temperature=1.0, top_k=8, seed=7)
    eng = ServingEngine(
        tiny_llama, scheduler=SchedulerConfig(enable_preemption=True), **kwargs
    )
    victim = eng.submit(p_victim, max_new_tokens=9, priority=1)
    eng.step()
    eng.submit(np.ones(4, np.int32), max_new_tokens=3, priority=0)
    eng.run()
    assert eng.metrics.decode_preemptions == 1  # the scenario actually fired
    control = ServingEngine(tiny_llama, **kwargs)
    c = control.submit(p_victim, max_new_tokens=9, priority=1)
    control.run()
    np.testing.assert_array_equal(eng.poll(victim), control.poll(c))
    np.testing.assert_allclose(eng.logprobs(victim), control.logprobs(c), rtol=1e-6)


def test_paged_pool_pressure_preempts_youngest_low_priority(tiny_llama):
    """Pool exhaustion with a more important request waiting evicts the
    low-priority decode, frees its blocks NOW, and both finish exact."""
    p1 = (np.arange(4) % 250 + 1).astype(np.int32)
    p2 = np.asarray([8, 7, 6, 5], np.int32)
    eng = ServingEngine(
        tiny_llama, num_slots=2, prompt_buckets=(4, 8), tick_block=2,
        max_len=16, paged_block_size=4, pool_blocks=5,
        scheduler=SchedulerConfig(enable_preemption=True),
    )
    victim = eng.submit(p1, max_new_tokens=10, priority=1)
    eng.step()  # victim decodes, holding all 4 usable blocks
    assert eng.pool_free_blocks == 0
    urgent = eng.submit(p2, max_new_tokens=4, priority=0)
    eng.step()
    state, req = eng._locate(victim)
    assert state == "queued" and req.preempted  # evicted for the pool, not a slot
    eng.run()
    np.testing.assert_array_equal(eng.poll(urgent), _reference(tiny_llama, p2, 4))
    np.testing.assert_array_equal(eng.poll(victim), _reference(tiny_llama, p1, 10))
    assert eng.pool_free_blocks == 4  # every block returned


def test_cancel_preempted_and_requeued_request(tiny_llama):
    """Cancelling a preempted request returns its carried tokens and
    fully forgets the id (poll never resolves, accessors raise)."""
    eng = ServingEngine(
        tiny_llama, num_slots=1, prompt_buckets=(8,), tick_block=2,
        scheduler=SchedulerConfig(enable_preemption=True),
    )
    victim = eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=10, priority=1)
    eng.step()
    eng.submit(np.ones(4, np.int32), max_new_tokens=3, priority=0)
    eng.step()
    state, _ = eng._locate(victim)
    assert state == "queued"  # preempted-and-requeued
    carried = eng.cancel(victim)
    assert carried.size >= 1  # generated-so-far tokens come back
    eng.run()
    assert eng.poll(victim) is None
    with pytest.raises(KeyError):
        eng.partial(victim)
    with pytest.raises(KeyError):
        eng.cancel(victim)


# --------------------------------------------------------------------- #
# stop sequences across a tick-block boundary
# --------------------------------------------------------------------- #


def test_stop_sequence_spans_tick_block_boundary(tiny_llama):
    """tick_block=2 delivers generated positions as [0] (prefill), [1,2],
    [3,4], ... — a stop pair at positions (2,3) straddles two device
    ticks, so the match logic must see across the block boundary."""
    prompt = np.ones((4,), np.int32)
    full = _reference(tiny_llama, prompt, 8)
    gen = full[len(prompt):]
    stop = [int(gen[2]), int(gen[3])]
    first = next(i for i in range(len(gen) - 1) if [int(gen[i]), int(gen[i + 1])] == stop)
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(4,), tick_block=2)
    uid = eng.submit(prompt, max_new_tokens=8, stop_sequences=[stop])
    eng.run()
    got = eng.poll(uid)
    assert len(got) == len(prompt) + first + 2
    np.testing.assert_array_equal(got, full[: len(got)])
    assert list(got[-2:]) == stop


def test_stop_sequence_on_resumed_request(tiny_llama):
    """preempt -> resume preserves the generated tail, so a stop sequence
    completed after the resume still fires at the exact position."""
    prompt = (np.arange(6) % 250 + 1).astype(np.int32)
    full = _reference(tiny_llama, prompt, 10)
    gen = full[len(prompt):]
    stop = [int(gen[6]), int(gen[7])]
    first = next(i for i in range(len(gen) - 1) if [int(gen[i]), int(gen[i + 1])] == stop)
    eng = ServingEngine(
        tiny_llama, num_slots=1, prompt_buckets=(8,), tick_block=2,
        scheduler=SchedulerConfig(enable_preemption=True),
    )
    victim = eng.submit(prompt, max_new_tokens=10, priority=1, stop_sequences=[stop])
    eng.step()  # 3 tokens streamed, stop not yet reachable
    eng.submit(np.ones(4, np.int32), max_new_tokens=3, priority=0)
    eng.run()
    assert eng.metrics.decode_preemptions == 1
    got = eng.poll(victim)
    assert len(got) == len(prompt) + first + 2
    np.testing.assert_array_equal(got, full[: len(got)])


# --------------------------------------------------------------------- #
# O(1) uid index + scheduler telemetry
# --------------------------------------------------------------------- #


def test_uid_index_tracks_lifecycle(tiny_llama):
    eng = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(4,))
    u1 = eng.submit(np.ones(3, np.int32), max_new_tokens=2)
    u2 = eng.submit(np.ones(3, np.int32), max_new_tokens=2)
    assert eng._locate(u1)[0] == "queued" and eng._locate(u2)[0] == "queued"
    eng.step()
    assert eng._locate(u1)[0] in ("active", "done")
    eng.run()
    assert eng._locate(u1) == ("done", None) and eng._locate(u2) == ("done", None)
    with pytest.raises(KeyError):
        eng._locate(999)
    # cancelled ids leave the index entirely
    u3 = eng.submit(np.ones(3, np.int32), max_new_tokens=2)
    eng.cancel(u3)
    with pytest.raises(KeyError):
        eng._locate(u3)


def test_itl_and_queue_wait_metrics_exposed(tiny_llama):
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(8,), tick_block=2)
    eng.generate_many([np.ones(4, np.int32), np.ones(6, np.int32)], max_new_tokens=6)
    snap = eng.metrics.snapshot()
    assert snap["itl_ms_p50"] is not None and snap["itl_ms_p95"] >= snap["itl_ms_p50"]
    assert snap["queue_wait_ms_p50"] is not None
    assert snap["requests_shed"] == 0 and snap["decode_preemptions"] == 0
    text = eng.metrics.prometheus_text()
    assert 'accelerate_tpu_serving_itl_ms{quantile="0.95"}' in text
    assert 'accelerate_tpu_serving_queue_wait_ms{quantile="0.5"}' in text
    assert "accelerate_tpu_serving_decode_preemptions_total 0" in text


def test_scheduler_events_land_in_telemetry_and_summarize(tiny_llama, tmp_path):
    from accelerate_tpu.telemetry import EventLog, read_events, render_text, summarize

    log = EventLog(str(tmp_path / "sched.jsonl"), rank=0)
    eng = ServingEngine(
        tiny_llama, num_slots=1, prompt_buckets=(8,), tick_block=2,
        telemetry_log=log,
        scheduler=SchedulerConfig(enable_preemption=True, max_queue_wait_s=30.0),
    )
    victim = eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=10, priority=1)
    eng.step()
    eng.submit(np.ones(4, np.int32), max_new_tokens=3, priority=0)
    eng.run()
    assert eng.poll(victim) is not None
    log.close()
    events = read_events(str(tmp_path / "sched.jsonl"))
    names = [e["name"] for e in events if e.get("kind") == "event"]
    assert "admit" in names and "preempt_decode" in names and "resume" in names
    admit = next(e for e in events if e.get("name") == "admit")
    assert "priority" in admit and "queue_wait_ms" in admit
    report = summarize(events)
    sched = report["scheduler"]
    assert sched["admitted"] >= 2 and sched["preempted"] == 1 and sched["resumed"] == 1
    assert "scheduler:" in render_text(report)
