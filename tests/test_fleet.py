"""Fleet-scale serving (serving_fleet.py): radix prefix cache semantics,
router policy, disaggregated KV handoff exactness + cost-model byte
accounting, fleet SLO shedding, and zero-compile replica spin-up."""

import os
import subprocess
import sys

import numpy as np
import pytest

from accelerate_tpu.generation import generate
from accelerate_tpu.models import LlamaConfig, create_llama_model
from accelerate_tpu.scheduling import FleetRoutingPolicy, RoutingConfig, ShedError
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.serving_fleet import (
    FleetConfig,
    FleetRequestError,
    FleetRouter,
    HandoffCodec,
    RadixPrefixCache,
)
from accelerate_tpu.test_utils.fault_injection import ReplicaChaos, SimulatedCrash

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny_llama():
    return create_llama_model(LlamaConfig.tiny(), seq_len=16)


@pytest.fixture(autouse=True)
def bound_live_executables_per_test():
    """This module builds several engines (= many resident programs) per
    test; clearing per TEST keeps the process-wide live-executable set
    tiny (the conftest-documented XLA:CPU late-fresh-compile segfault
    class). Cross-test recompiles hit the persistent disk cache."""
    yield
    import jax

    jax.clear_caches()


def _reference(model, prompt, n):
    return np.asarray(generate(model, np.asarray(prompt, np.int32)[None], max_new_tokens=n))[0]


def _engine(model, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("prompt_buckets", (4, 8))
    return ServingEngine(model, **kw)


# --------------------------------------------------------------------- #
# routing policy (scheduling.py)
# --------------------------------------------------------------------- #


def test_routing_policy_least_loaded_and_round_robin():
    p = FleetRoutingPolicy(RoutingConfig(policy="least_loaded"))
    assert p.pick_replica([3, 1, 2], [0, 1, 2]) == 1
    assert p.pick_replica([1, 1, 2], [0, 1, 2]) == 0  # tie -> lowest index
    assert p.pick_replica([0, 9, 0], [1, 2]) == 2  # eligibility filters
    rr = FleetRoutingPolicy(RoutingConfig(policy="round_robin"))
    picks = [rr.pick_replica([0, 0, 0], [0, 1, 2]) for _ in range(6)]
    assert picks == [0, 1, 2, 0, 1, 2]


def test_routing_policy_fleet_shed_respects_priority_floor():
    p = FleetRoutingPolicy(RoutingConfig(max_fleet_queue_depth=4))
    assert p.shed_on_submit(0, 100) is None  # priority 0 unsheddable
    assert p.shed_on_submit(1, 3) is None
    assert "fleet queue depth" in p.shed_on_submit(1, 4)


def test_routing_config_validation():
    with pytest.raises(ValueError, match="policy"):
        RoutingConfig(policy="random")
    with pytest.raises(ValueError, match="max_fleet_queue_depth"):
        RoutingConfig(max_fleet_queue_depth=0)
    with pytest.raises(ValueError, match="roles"):
        FleetConfig(roles=("mixed", "oracle"))
    with pytest.raises(ValueError, match="handoff"):
        FleetConfig(handoff="sometimes")


# --------------------------------------------------------------------- #
# radix prefix cache
# --------------------------------------------------------------------- #


def test_radix_promotes_shared_preamble_and_reuse_is_exact(tiny_llama):
    eng = _engine(tiny_llama)
    rad = RadixPrefixCache(eng, min_prefix_tokens=4, promote_after=2)
    pre = (np.arange(1, 7) % 250).astype(np.int32)
    p1 = np.concatenate([pre, [41, 42]]).astype(np.int32)
    p2 = np.concatenate([pre, [51, 52, 53]]).astype(np.int32)
    assert rad.lookup(p1) is None and rad.observe(p1) is None
    assert rad.lookup(p2) is None
    pid = rad.observe(p2)  # second prompt through the shared preamble
    assert pid is not None
    assert rad.lookup(p2) == (pid, 6)  # the 6-token divergence point
    # engine-path exactness: suffix prefill over the registered cache
    uid = eng.submit(p2[6:], max_new_tokens=4, prefix_id=pid)
    eng.run()
    np.testing.assert_array_equal(eng.poll(uid), _reference(tiny_llama, p2, 4))
    st = rad.stats()
    assert st["hits"] == 1 and st["registrations"] == 1
    assert eng.metrics.prefix_hits == 1 and eng.metrics.prefix_tokens_reused == 6


def test_radix_min_tokens_and_proper_prefix_rules(tiny_llama):
    eng = _engine(tiny_llama)
    rad = RadixPrefixCache(eng, min_prefix_tokens=8, promote_after=2)
    short = np.arange(1, 6, dtype=np.int32)  # 5-token LCP < min 8
    rad.observe(np.concatenate([short, [9]]))
    assert rad.observe(np.concatenate([short, [10]])) is None
    # a prompt EQUAL to a registered prefix must not match (no suffix)
    rad2 = RadixPrefixCache(eng, min_prefix_tokens=4, promote_after=2)
    pre = np.arange(20, 29, dtype=np.int32)
    rad2.observe(np.concatenate([pre, [1]]))
    pid = rad2.observe(np.concatenate([pre, [2]]))
    assert pid is not None
    assert rad2.lookup(pre) is None  # nothing left to prefill
    assert rad2.lookup(np.concatenate([pre, [3]])) == (pid, 9)


def test_radix_lru_eviction_frees_engine_prefix(tiny_llama):
    eng = _engine(tiny_llama)
    rad = RadixPrefixCache(eng, min_prefix_tokens=4, promote_after=2, max_entries=1)
    pre_a = np.arange(1, 6, dtype=np.int32)
    pre_b = np.arange(30, 36, dtype=np.int32)
    rad.observe(np.concatenate([pre_a, [7]]))
    pid_a = rad.observe(np.concatenate([pre_a, [8]]))
    assert pid_a is not None and len(eng._prefixes) == 1
    rad.observe(np.concatenate([pre_b, [7]]))
    pid_b = rad.observe(np.concatenate([pre_b, [8]]))
    assert pid_b is not None
    # budget 1: the older entry was unregistered from the engine too
    assert rad.stats()["evictions"] == 1 and len(rad.entries) == 1
    assert pid_a not in eng._prefixes and pid_b in eng._prefixes
    assert eng.metrics.prefix_evictions == 1
    assert rad.lookup(np.concatenate([pre_a, [9]])) is None


def test_radix_eviction_skips_referenced_entry(tiny_llama):
    eng = _engine(tiny_llama)
    rad = RadixPrefixCache(eng, min_prefix_tokens=4, promote_after=2, max_entries=1)
    pre_a = np.arange(1, 6, dtype=np.int32)
    rad.observe(np.concatenate([pre_a, [7]]))
    pid_a = rad.observe(np.concatenate([pre_a, [8]]))
    m = rad.lookup(np.concatenate([pre_a, [9]]))
    eng.submit(np.asarray([9], np.int32), max_new_tokens=2, prefix_id=m[0])
    # a queued request pins pid_a: the new registration may not evict it
    pre_b = np.arange(30, 36, dtype=np.int32)
    rad.observe(np.concatenate([pre_b, [7]]))
    rad.observe(np.concatenate([pre_b, [8]]))
    assert pid_a in eng._prefixes  # still registered (referenced)
    assert len(rad.entries) == 2  # over budget until the reference drains
    eng.run()
    pre_c = np.arange(60, 66, dtype=np.int32)
    rad.observe(np.concatenate([pre_c, [7]]))
    rad.observe(np.concatenate([pre_c, [8]]))
    assert len(rad.entries) <= 2  # eviction caught up after the drain


def test_radix_invalidate(tiny_llama):
    eng = _engine(tiny_llama)
    rad = RadixPrefixCache(eng, min_prefix_tokens=4, promote_after=2)
    pre = np.arange(1, 7, dtype=np.int32)
    rad.observe(np.concatenate([pre, [1]]))
    pid = rad.observe(np.concatenate([pre, [2]]))
    assert rad.invalidate(pid) == 1
    assert rad.lookup(np.concatenate([pre, [3]])) is None
    assert pid not in eng._prefixes
    with pytest.raises(ValueError, match="unknown prefix_id"):
        rad.invalidate(pid)


# --------------------------------------------------------------------- #
# KV handoff (engine surface)
# --------------------------------------------------------------------- #


def test_handoff_token_and_logprob_exact_dense_and_paged(tiny_llama):
    prompt = (np.arange(1, 10) % 250).astype(np.int32)
    ref = _reference(tiny_llama, prompt, 5)
    src = _engine(tiny_llama)
    h = src.prefill_detached(prompt, max_new_tokens=5, uid_key=3)
    for dst_kw in ({}, {"paged_block_size": 4}):
        dst = _engine(tiny_llama, **dst_kw)
        uid = dst.submit_prefilled(dict(h))
        dst.run()
        np.testing.assert_array_equal(dst.poll(uid), ref)
        # logprob-exact vs a local submit on a fresh engine
        local = _engine(tiny_llama)
        lu = local.submit(prompt, max_new_tokens=5)
        local.run()
        np.testing.assert_array_equal(dst.logprobs(uid), local.logprobs(lu))


def test_handoff_sampled_stream_matches_local_submit(tiny_llama):
    """temperature>0: the handoff carries the advanced sampling chain, so
    a disaggregated request's sampled stream equals the single-engine
    stream for the same (seed, uid)."""
    prompt = (np.arange(1, 9) % 250).astype(np.int32)
    local = _engine(tiny_llama, temperature=0.9, seed=5, num_slots=1)
    lu = local.submit(prompt, max_new_tokens=6)
    local.run()
    src = _engine(tiny_llama, temperature=0.9, seed=5, num_slots=1)
    dst = _engine(tiny_llama, temperature=0.9, seed=5, num_slots=1)
    uid = dst.submit_prefilled(src.prefill_detached(prompt, max_new_tokens=6, uid_key=lu))
    dst.run()
    np.testing.assert_array_equal(dst.poll(uid), local.poll(lu))
    np.testing.assert_array_equal(dst.logprobs(uid), local.logprobs(lu))


def test_handoff_bytes_match_costmodel_prediction(tiny_llama):
    from accelerate_tpu.analysis.costmodel import price_kv_handoff

    eng = _engine(tiny_llama)
    per_tok, fixed = eng.kv_handoff_dims()
    assert per_tok > 0
    for n in (3, 8, 11):
        prompt = (np.arange(1, n + 1) % 250).astype(np.int32)
        h = eng.prefill_detached(prompt, max_new_tokens=2, uid_key=n)
        pred = price_kv_handoff(per_tok, n, fixed_bytes=fixed, generation="cpu")
        assert pred["bytes"] == h["wire_bytes"] == per_tok * n + fixed
        assert pred["time_us"] > 0


def test_handoff_validation(tiny_llama):
    eng = _engine(tiny_llama)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.prefill_detached(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="exceeds the slot cache"):
        eng.prefill_detached(np.ones((8,), np.int32), max_new_tokens=150)
    h = eng.prefill_detached(np.ones((4,), np.int32), max_new_tokens=4)
    bad = dict(h)
    bad["total"] = 3
    with pytest.raises(ValueError, match="handoff total"):
        eng.submit_prefilled(bad)
    big = dict(h)
    big["max_new_tokens"] = 150
    with pytest.raises(ValueError, match="exceeds the slot cache"):
        eng.submit_prefilled(big)


def test_handoff_request_survives_preemption(tiny_llama):
    """A handed-off request evicted mid-decode resumes by ordinary
    recompute (the handoff is consumed at first admission) and stays
    token-exact."""
    from accelerate_tpu.scheduling import SchedulerConfig

    prompt = (np.arange(1, 9) % 250).astype(np.int32)
    ref = _reference(tiny_llama, prompt, 8)
    src = _engine(tiny_llama)
    dst = ServingEngine(
        tiny_llama, num_slots=1, prompt_buckets=(4, 8), tick_block=2,
        scheduler=SchedulerConfig(enable_preemption=True),
    )
    uid = dst.submit_prefilled(
        src.prefill_detached(prompt, max_new_tokens=8, uid_key=0), priority=1
    )
    dst.step()  # handoff admitted, decoding
    assert dst.partial(uid).size > 0
    hi = dst.submit(np.asarray([5, 6], np.int32), max_new_tokens=2, priority=0)
    dst.run()  # priority-0 arrival preempts the handoff decode
    assert dst.metrics.decode_preemptions >= 1
    np.testing.assert_array_equal(dst.poll(uid), ref)
    assert dst.poll(hi) is not None


# --------------------------------------------------------------------- #
# the router
# --------------------------------------------------------------------- #


def test_fleet_outputs_exact_and_prefix_affinity(tiny_llama):
    fr = FleetRouter.from_model(
        tiny_llama, num_replicas=2,
        config=FleetConfig(min_prefix_tokens=4, promote_after=2),
        num_slots=2, prompt_buckets=(4, 8),
    )
    pre = (np.arange(1, 7) % 250).astype(np.int32)
    prompts = [np.concatenate([pre, [40 + i]]).astype(np.int32) for i in range(6)]
    uids = [fr.submit(p, max_new_tokens=4) for p in prompts]
    out = fr.run()
    for u, p in zip(uids, prompts):
        np.testing.assert_array_equal(out[u], _reference(tiny_llama, p, 4))
    stats = fr.radix_stats()
    # after promotion, affinity routes every preamble-sharing request to
    # the owning replica: exactly one replica holds the entry + the hits
    owners = [n for n, s in stats.items() if s["entries"] > 0]
    assert len(owners) == 1
    assert stats[owners[0]]["hits"] >= 1
    merged = fr.metrics_merged()
    assert merged.prefix_hits == sum(s["hits"] for s in stats.values())
    assert merged.requests_completed == len(prompts)


def test_fleet_no_reuse_config(tiny_llama):
    fr = FleetRouter.from_model(
        tiny_llama, num_replicas=2, config=FleetConfig(prefix_reuse=False),
        num_slots=2, prompt_buckets=(4, 8),
    )
    assert all(r.radix is None for r in fr.replicas)
    p = (np.arange(1, 9) % 250).astype(np.int32)
    u = fr.submit(p, max_new_tokens=3)
    out = fr.run()
    np.testing.assert_array_equal(out[u], _reference(tiny_llama, p, 3))


def test_fleet_level_shed(tiny_llama):
    fr = FleetRouter.from_model(
        tiny_llama, num_replicas=2,
        config=FleetConfig(routing=RoutingConfig(max_fleet_queue_depth=1), prefix_reuse=False),
        num_slots=1, prompt_buckets=(4, 8),
    )
    fr.submit(np.ones((4,), np.int32), max_new_tokens=2)
    fr.submit(np.ones((4,), np.int32), max_new_tokens=2)
    # aggregate queue depth (minus in-flight) crosses the fleet SLO for a
    # sheddable class; priority 0 stays admissible
    with pytest.raises(ShedError, match="fleet queue depth"):
        while True:
            fr.submit(np.ones((4,), np.int32), max_new_tokens=2, priority=1)
    fr.submit(np.ones((4,), np.int32), max_new_tokens=2, priority=0)
    assert fr.fleet_shed == 1
    fr.run()


def test_fleet_disaggregated_exact_and_accounted(tiny_llama):
    fr = FleetRouter.from_model(
        tiny_llama, num_replicas=2,
        config=FleetConfig(roles=("prefill", "decode"), handoff="always", prefix_reuse=False),
        num_slots=2, prompt_buckets=(4, 8),
    )
    prompts = [(np.arange(1, 8 + i) % 250).astype(np.int32) for i in range(3)]
    uids = [fr.submit(p, max_new_tokens=4) for p in prompts]
    out = fr.run()
    for u, p in zip(uids, prompts):
        np.testing.assert_array_equal(out[u], _reference(tiny_llama, p, 4))
    acct = fr.handoff_accounting()
    assert acct["handoffs"] == 3
    assert acct["bytes_predicted"] == acct["bytes_moved"] > 0
    # decode replica did all the decoding; prefill replica served no slots
    assert fr.replicas[1].engine.metrics.requests_completed == 3
    assert fr.replicas[0].engine.metrics.requests_completed == 0


def test_fleet_disaggregated_auto_decision(tiny_llama):
    """auto mode prices every candidate transfer BEFORE it happens and
    takes exactly one decision per request (handoff or local re-prefill),
    and handoff=never pins the local path."""
    fr = FleetRouter.from_model(
        tiny_llama, num_replicas=2,
        config=FleetConfig(roles=("prefill", "decode"), handoff="auto", prefix_reuse=False),
        num_slots=2, prompt_buckets=(4, 8),
    )
    u = fr.submit((np.arange(1, 9) % 250).astype(np.int32), max_new_tokens=3)
    out = fr.run()
    assert u in out
    acct = fr.handoff_accounting()
    assert acct["handoffs"] + acct["handoffs_local"] == 1
    fr2 = FleetRouter.from_model(
        tiny_llama, num_replicas=2,
        config=FleetConfig(roles=("prefill", "decode"), handoff="never", prefix_reuse=False),
        num_slots=2, prompt_buckets=(4, 8),
    )
    u2 = fr2.submit((np.arange(1, 9) % 250).astype(np.int32), max_new_tokens=3)
    out2 = fr2.run()
    np.testing.assert_array_equal(out2[u2], _reference(tiny_llama, (np.arange(1, 9) % 250), 3))
    assert fr2.handoff_accounting() == {
        "handoffs": 0, "handoffs_local": 1, "bytes_predicted": 0,
        "bytes_moved": 0, "time_us_predicted": 0.0,
    }


def test_fleet_partial_logprobs_cancel(tiny_llama):
    fr = FleetRouter.from_model(
        tiny_llama, num_replicas=2, config=FleetConfig(prefix_reuse=False),
        num_slots=1, prompt_buckets=(4, 8), tick_block=2,
    )
    p = (np.arange(1, 9) % 250).astype(np.int32)
    u1 = fr.submit(p, max_new_tokens=6)
    u2 = fr.submit(p, max_new_tokens=6)
    assert fr.partial(u1).size == 0 and fr.poll(u1) is None
    fr.step()
    got = fr.cancel(u2)
    assert isinstance(got, np.ndarray)
    with pytest.raises(KeyError):
        fr.partial(u2)
    fr.run()
    assert fr.poll(u1) is not None
    assert fr.logprobs(u1).shape[0] == len(fr.partial(u1))
    with pytest.raises(KeyError, match="unknown request id"):
        fr.poll(10_000)


def test_fleet_drain_threaded_matches_sequential(tiny_llama):
    prompts = [(np.arange(1, 5 + i) % 250).astype(np.int32) for i in range(8)]
    outs = {}
    for mode in ("seq", "thr"):
        fr = FleetRouter.from_model(
            tiny_llama, num_replicas=2, config=FleetConfig(prefix_reuse=False),
            num_slots=2, prompt_buckets=(4, 8),
        )
        uids = [fr.submit(p, max_new_tokens=3) for p in prompts]
        if mode == "thr":
            fr.drain_threaded()
        out = fr.run()  # seq drive / collect
        outs[mode] = [out[u] for u in uids]
    for a, b in zip(outs["seq"], outs["thr"]):
        np.testing.assert_array_equal(a, b)


def test_fleet_watchdog_silent_across_radix_hits_and_misses(tiny_llama):
    """Post-warmup compile count stays 0 across prefix registrations,
    hits, misses, and evictions — the recompile-watchdog discipline at
    fleet level."""
    fr = FleetRouter.from_model(
        tiny_llama, num_replicas=1,
        config=FleetConfig(min_prefix_tokens=4, promote_after=2, max_prefix_entries=1),
        num_slots=2, prompt_buckets=(4, 8),
    )
    eng = fr.replicas[0].engine
    rng = np.random.default_rng(0)
    # warm every width: buckets, chunk windows, prefix-suffix windows
    for n in (4, 8, 10, 13):
        eng.submit(rng.integers(1, 250, size=n).astype(np.int32), max_new_tokens=2)
    eng.run()
    pid = eng.register_prefix(rng.integers(1, 250, size=9).astype(np.int32))
    for b in (4, 8):
        eng.submit(rng.integers(1, 250, size=b).astype(np.int32), max_new_tokens=2, prefix_id=pid)
    eng.run()
    eng.unregister_prefix(pid)
    c0 = eng.program_cache.misses
    pre_a = rng.integers(1, 250, size=6).astype(np.int32)
    pre_b = rng.integers(1, 250, size=7).astype(np.int32)
    uids = []
    for pre in (pre_a, pre_a, pre_a, pre_b, pre_b, pre_b):
        sfx = rng.integers(1, 250, size=int(rng.integers(2, 5))).astype(np.int32)
        uids.append(fr.submit(np.concatenate([pre, sfx]), max_new_tokens=3))
    out = fr.run()
    assert len(out) == len(uids)
    stats = fr.radix_stats()["r0"]
    assert stats["registrations"] >= 2 and stats["hits"] >= 2
    assert eng.program_cache.misses - c0 == 0, "radix traffic must not compile"


def test_fleet_spin_up_warm_starts_from_shared_store(tiny_llama, tmp_path, no_persistent_compile_cache):
    """In-process spin-up over a shared store: every program either
    deserializes or is a reject-and-heal recompile — never a silent cold
    compile. (The STRICT 0-compile contract holds for fresh-process
    replicas — the subprocess test below — and
    in-process under a single-device backend; under the suite's 8-device
    fake mesh XLA:CPU can emit non-self-contained blobs from a long-lived
    process, the PR-7-documented class the reject path heals.)"""
    fr = FleetRouter.from_model(
        tiny_llama, num_replicas=1, config=FleetConfig(prefix_reuse=False),
        store_dir=str(tmp_path / "fleet_store"),
        num_slots=2, prompt_buckets=(4, 8),
    )
    cold = fr.spin_up(warm_prompt_lens=(4,))
    assert cold["compiles"] > 0 and cold["deserialized"] == 0
    warm = fr.spin_up(warm_prompt_lens=(4,))
    pc = fr.replicas[2].engine.program_cache
    assert warm["deserialized"] > 0
    assert warm["compiles"] == pc.rejected, "only healed rejects may recompile"
    assert warm["deserialized"] + warm["compiles"] == cold["compiles"]
    assert len(fr.replicas) == 3
    # the spun-up replica serves real traffic
    p = (np.arange(1, 6) % 250).astype(np.int32)
    u = fr.submit(p, max_new_tokens=3)
    out = fr.run()
    np.testing.assert_array_equal(out[u], _reference(tiny_llama, p, 3))


# --------------------------------------------------------------------- #
# fault tolerance: health machine, token-exact failover, chaos matrix
# --------------------------------------------------------------------- #

_FT_PROMPTS = [(np.arange(1, 6 + i) % 250).astype(np.int32) for i in range(6)]
_FT_NEW = 4


def _ft_fleet(model, *, failover="auto", tick_block=8, **cfg_kw):
    cfg_kw.setdefault("prefix_reuse", False)
    return FleetRouter.from_model(
        model, num_replicas=2, config=FleetConfig(failover=failover, **cfg_kw),
        num_slots=2, prompt_buckets=(4, 8), tick_block=tick_block,
    )


@pytest.fixture(scope="module")
def ft_control(tiny_llama):
    """No-fault control run of the chaos workload: per-submission-index
    full token streams and logprobs every chaos arm must reproduce."""
    fr = _ft_fleet(tiny_llama)
    uids = [fr.submit(p, max_new_tokens=_FT_NEW) for p in _FT_PROMPTS]
    out = fr.run()
    ctl = [(np.asarray(out[u]), np.asarray(fr.logprobs(u))) for u in uids]
    import jax

    jax.clear_caches()
    return ctl


@pytest.mark.parametrize("failover", ["recompute", "handoff"])
@pytest.mark.parametrize("label", ["pre_tick", "mid_prefill", "mid_decode"])
def test_chaos_crash_matrix_token_and_logprob_exact(tiny_llama, ft_control, label, failover):
    """The crash-at-every-point failover matrix: kill replica r0 at each
    labeled serving point with requests queued, mid-prefill, and
    mid-decode; every in-flight request must complete on the survivor
    token- AND logprob-exact vs the no-fault control, zero lost, zero
    duplicated — whichever migration path the router is pinned to."""
    fr = _ft_fleet(tiny_llama, failover=failover)
    uids = [fr.submit(p, max_new_tokens=_FT_NEW) for p in _FT_PROMPTS]
    fr.step()  # some requests decoding on r0, one still queued
    with ReplicaChaos(label, replica="r0", action="crash") as chaos:
        out = fr.run()
    assert chaos.fired
    assert fr.health()["r0"]["health"] == "dead"
    assert sorted(out) == sorted(uids)  # all complete, none duplicated
    for u, (ref_toks, ref_lps) in zip(uids, ft_control):
        np.testing.assert_array_equal(out[u], ref_toks)
        np.testing.assert_array_equal(fr.logprobs(u), ref_lps)
    acct = fr.failover_accounting()
    assert acct["failovers"] >= 1 and acct["failovers_lost"] == 0
    if failover == "recompute":
        assert acct["failovers_kv"] == 0


def test_chaos_pre_handoff_disaggregated_fails_over(tiny_llama):
    """Killing the prefill replica at the pre_handoff dispatch point must
    not lose the pending requests: the dispatcher requeues them, marks
    the prefill replica dead, and the decode replica self-prefills with
    the same uid_key — token-exact."""
    fr = FleetRouter.from_model(
        tiny_llama, num_replicas=2,
        config=FleetConfig(roles=("prefill", "decode"), handoff="always", prefix_reuse=False),
        num_slots=2, prompt_buckets=(4, 8),
    )
    prompts = [(np.arange(1, 8 + i) % 250).astype(np.int32) for i in range(3)]
    uids = [fr.submit(p, max_new_tokens=_FT_NEW) for p in prompts]
    with ReplicaChaos("pre_handoff", replica="r0", action="crash") as chaos:
        out = fr.run()
    assert chaos.fired
    assert fr.health()["r0"]["health"] == "dead"
    assert fr.failover_accounting()["failovers_lost"] == 0
    for u, p in zip(uids, prompts):
        np.testing.assert_array_equal(out[u], _reference(tiny_llama, p, _FT_NEW))


def test_chaos_poison_quarantines_and_never_ships_kv(tiny_llama, ft_control):
    """A non-finite watchdog trip quarantines (numerics suspect, the
    replica itself may be fine) and fails over by recompute ONLY — the
    poisoned KV must never be pasted into a survivor."""
    fr = _ft_fleet(tiny_llama, failover="auto")
    uids = [fr.submit(p, max_new_tokens=_FT_NEW) for p in _FT_PROMPTS]
    fr.step()
    with ReplicaChaos("mid_decode", replica="r0", action="poison") as chaos:
        out = fr.run()
    assert chaos.fired
    h = fr.health()["r0"]
    assert h["health"] == "quarantined" and "NonFinitePoison" in h["last_error"]
    acct = fr.failover_accounting()
    assert acct["failovers"] >= 1 and acct["failovers_kv"] == 0
    assert acct["failovers_lost"] == 0 and acct["bytes_moved"] == 0
    for u, (ref_toks, ref_lps) in zip(uids, ft_control):
        np.testing.assert_array_equal(out[u], ref_toks)
        np.testing.assert_array_equal(fr.logprobs(u), ref_lps)


@pytest.mark.parametrize("failover", ["recompute", "handoff"])
def test_chaos_sampled_failover_exact(tiny_llama, failover):
    """temperature>0: the exported key_data pins each request's sampling
    chain, so a failed-over sampled stream equals the no-fault control —
    over the KV-paste path AND the full recompute path."""
    prompts = [(np.arange(1, 7 + i) % 250).astype(np.int32) for i in range(4)]

    def build():
        return FleetRouter.from_model(
            tiny_llama, num_replicas=2,
            config=FleetConfig(prefix_reuse=False, failover=failover),
            num_slots=2, prompt_buckets=(4, 8), tick_block=2, temperature=0.9, seed=7,
        )

    ctl = build()
    cu = [ctl.submit(p, max_new_tokens=_FT_NEW) for p in prompts]
    ctl_out = ctl.run()
    fr = build()
    uids = [fr.submit(p, max_new_tokens=_FT_NEW) for p in prompts]
    fr.step()
    with ReplicaChaos("pre_tick", replica="r0", action="crash") as chaos:
        out = fr.run()
    assert chaos.fired and fr.failover_accounting()["failovers"] >= 1
    for u, c in zip(uids, cu):
        np.testing.assert_array_equal(out[u], ctl_out[c])
        np.testing.assert_array_equal(fr.logprobs(u), ctl.logprobs(c))


def test_chaos_survivor_serves_with_zero_new_compiles(tiny_llama):
    """The recompile-watchdog discipline survives a replica death: after
    warming fused buckets, chunk windows, and the decode tick on the
    survivor, absorbing r0's failed-over load compiles NOTHING new."""
    fr = _ft_fleet(tiny_llama, failover="handoff")
    rng = np.random.default_rng(3)
    for rep in fr.replicas:  # warm both so pre-crash traffic is covered too
        for n in (4, 8, 10, 13):
            rep.engine.submit(rng.integers(1, 250, size=n).astype(np.int32), max_new_tokens=2)
        rep.engine.run()
        # the KV paste sees host-resident arrays — a distinct signature
        h = fr.replicas[0].engine.prefill_detached(
            rng.integers(1, 250, size=4).astype(np.int32), max_new_tokens=2, uid_key=2**30
        )
        rep.engine.submit_prefilled(dict(h))
        rep.engine.run()
    survivor = fr.replicas[1].engine
    c0 = survivor.program_cache.misses
    uids = [fr.submit(p, max_new_tokens=_FT_NEW) for p in _FT_PROMPTS]
    fr.step()
    with ReplicaChaos("pre_tick", replica="r0", action="crash"):
        out = fr.run()
    assert sorted(out) == sorted(uids)
    assert fr.failover_accounting()["failovers"] >= 1
    assert survivor.program_cache.misses - c0 == 0, "failover absorption must not compile"


def test_failover_priced_before_it_happens_and_pinned(tiny_llama):
    """The router prices every KV failover with the costmodel BEFORE
    moving bytes; the accounting pins prediction == actual bytes moved
    (and carries the recompute alternative it was judged against)."""
    fr = _ft_fleet(tiny_llama, failover="handoff", tick_block=2)
    uids = [fr.submit(p, max_new_tokens=6) for p in _FT_PROMPTS[:4]]
    fr.step()  # decode phase on both replicas -> exports carry KV rows
    with ReplicaChaos("pre_tick", replica="r0", action="crash"):
        out = fr.run()
    assert sorted(out) == sorted(uids)
    acct = fr.failover_accounting()
    assert acct["failovers_kv"] >= 1
    assert acct["bytes_predicted"] == acct["bytes_moved"] > 0
    assert acct["time_us_predicted"] > 0


def test_price_failover_costmodel():
    from accelerate_tpu.analysis.costmodel import price_failover

    p = price_failover(4096, 512, 100, 7_000_000_000)
    assert p["rows"] == 611 and p["handoff"]["bytes"] >= 4096 * 611
    assert p["path"] in ("handoff", "recompute")
    # KV not exportable (paged / speculative / poisoned) -> recompute,
    # even when the wire would have been cheaper
    assert price_failover(4096, 512, 100, 7_000_000_000, kv_exportable=False)["path"] == "recompute"
    # a zero-generated failover still re-prefills the full prompt
    assert price_failover(4096, 16, 0, 7_000_000_000)["rows"] == 16


def test_hang_degrades_then_quarantines_and_heals(tiny_llama):
    """Tick-timeout state machine: one slow tick degrades, consecutive
    slow ticks quarantine (work migrates with KV intact — the tick
    finished, just late); a degraded replica heals after clean ticks."""
    fr = _ft_fleet(tiny_llama, tick_block=2, quarantine_after_timeouts=2, heal_after_ticks=3)
    rng = np.random.default_rng(11)
    for rep in fr.replicas:  # every program compiles OUTSIDE the timeout window
        for n in (4, 8, 10, 13):
            rep.engine.submit(rng.integers(1, 250, size=n).astype(np.int32), max_new_tokens=2)
        rep.engine.run()
    uids = [fr.submit(p, max_new_tokens=8) for p in _FT_PROMPTS[:4]]
    fr.step()
    fr.config.tick_timeout_s = 0.05
    with ReplicaChaos("pre_tick", replica="r0", action="hang", hang_s=0.2, repeat=True):
        fr.step()
        assert fr.health()["r0"]["health"] == "degraded"
        out = fr.run()  # second slow tick -> quarantined, work migrates
    assert fr.health()["r0"]["health"] == "quarantined"
    assert sorted(out) == sorted(uids)
    assert fr.failover_accounting()["failovers_lost"] == 0
    for u, p in zip(uids, _FT_PROMPTS):
        np.testing.assert_array_equal(out[u], _reference(tiny_llama, p, 8))
    # heal: a single hiccup degrades, then clean BUSY ticks restore healthy
    fr2 = FleetRouter.from_model(
        tiny_llama, num_replicas=2,
        config=FleetConfig(prefix_reuse=False, heal_after_ticks=2),
        num_slots=2, prompt_buckets=(4, 8), tick_block=2,
    )
    warm = fr2.replicas[0].engine
    warm.submit((np.arange(1, 5) % 250).astype(np.int32), max_new_tokens=4)
    warm.run()  # prefill + decode programs compiled OUTSIDE the window
    fr2.submit((np.arange(1, 5) % 250).astype(np.int32), max_new_tokens=10)
    fr2.step()
    fr2.config.tick_timeout_s = 0.05
    with ReplicaChaos("pre_tick", replica="r0", action="hang", hang_s=0.2):
        fr2.step()
    assert fr2.health()["r0"]["health"] == "degraded"
    fr2.step()  # tick_block=2: plenty of clean busy ticks left
    fr2.step()
    assert fr2.health()["r0"]["health"] == "healthy"


def test_drain_under_load_and_unique_respawn_names(tiny_llama):
    """drain() migrates every in-flight request and removes the replica
    without losing a token; a later add_replica must never reuse a
    retired name."""
    fr = _ft_fleet(tiny_llama)
    uids = [fr.submit(p, max_new_tokens=_FT_NEW) for p in _FT_PROMPTS[:4]]
    fr.step()
    res = fr.drain("r0")
    assert res["replica"] == "r0" and res["lost"] == 0
    assert [r.name for r in fr.replicas] == ["r1"]
    out = fr.run()
    assert sorted(out) == sorted(uids)
    for u, p in zip(uids, _FT_PROMPTS):
        np.testing.assert_array_equal(out[u], _reference(tiny_llama, p, _FT_NEW))
    info = fr.add_replica(warm_prompt_lens=(4,))
    names = [r.name for r in fr.replicas]
    assert names == ["r1", "r2"], "retired names must never be reused"
    assert info["replica"] == "r2"
    u = fr.submit(_FT_PROMPTS[0], max_new_tokens=2)
    assert u in fr.run()
    fr.drain("r1")
    with pytest.raises(ValueError, match="no other serving replica"):
        fr.drain("r2")


def test_capacity_lost_sheds_until_add_replica(tiny_llama):
    """Killing the last serving replica sheds new submissions at the
    fleet edge with a structured ShedError; add_replica restores
    admission (the zero-compile spin-up path) and the fleet serves
    again."""
    fr = FleetRouter.from_model(
        tiny_llama, num_replicas=1, config=FleetConfig(prefix_reuse=False),
        num_slots=2, prompt_buckets=(4, 8),
    )
    u_doomed = fr.submit(_FT_PROMPTS[0], max_new_tokens=2)
    fr.fail_replica("r0")
    assert fr.health()["r0"]["health"] == "dead"
    # nowhere to migrate: the in-flight request is honestly LOST
    assert fr.failover_accounting()["failovers_lost"] == 1
    with pytest.raises(KeyError, match="lost"):
        fr.poll(u_doomed)
    with pytest.raises(ShedError, match="capacity lost"):
        fr.submit(_FT_PROMPTS[1], max_new_tokens=2)
    fr.add_replica(warm_prompt_lens=(4,))
    p = (np.arange(1, 6) % 250).astype(np.int32)
    u = fr.submit(p, max_new_tokens=3)
    out = fr.run()
    np.testing.assert_array_equal(out[u], _reference(tiny_llama, p, 3))


def test_chaos_poison_sole_replica_capacity_lost(tiny_llama):
    """Poisoning the ONLY replica quarantines it with nowhere to migrate:
    the in-flight request is honestly lost (allow_kv=False — nothing is
    pasted anywhere), the breaker sheds new submissions, and add_replica
    restores service. Pins the model checker's poison/capacity_lost
    path (analysis.fleet_rules.CHAOS_COVERAGE)."""
    fr = FleetRouter.from_model(
        tiny_llama, num_replicas=1, config=FleetConfig(prefix_reuse=False),
        num_slots=2, prompt_buckets=(4, 8),
    )
    u_doomed = fr.submit(_FT_PROMPTS[0], max_new_tokens=2)
    fr.fail_replica("r0", error=RuntimeError("nonfinite logits from watchdog"))
    h = fr.health()["r0"]
    assert h["health"] == "quarantined" and "nonfinite" in h["last_error"]
    acct = fr.failover_accounting()
    assert acct["failovers_lost"] == 1 and acct["failovers_kv"] == 0
    with pytest.raises(KeyError, match="lost"):
        fr.poll(u_doomed)
    with pytest.raises(ShedError, match="capacity lost"):
        fr.submit(_FT_PROMPTS[1], max_new_tokens=2)
    fr.add_replica(warm_prompt_lens=(4,))
    p = (np.arange(1, 6) % 250).astype(np.int32)
    u = fr.submit(p, max_new_tokens=3)
    out = fr.run()
    np.testing.assert_array_equal(out[u], _reference(tiny_llama, p, 3))


def test_chaos_hang_sole_replica_capacity_lost(tiny_llama):
    """Repeated tick timeouts on the ONLY replica quarantine it with no
    survivor to take the work: lost-with-reason, breaker sheds, and
    add_replica recovers. Pins the model checker's timeout/capacity_lost
    path (analysis.fleet_rules.CHAOS_COVERAGE)."""
    fr = FleetRouter.from_model(
        tiny_llama, num_replicas=1,
        config=FleetConfig(prefix_reuse=False, quarantine_after_timeouts=2),
        num_slots=2, prompt_buckets=(4, 8), tick_block=2,
    )
    warm = fr.replicas[0].engine
    warm.submit((np.arange(1, 5) % 250).astype(np.int32), max_new_tokens=4)
    warm.run()  # prefill + decode compiled OUTSIDE the timeout window
    u_doomed = fr.submit((np.arange(1, 5) % 250).astype(np.int32), max_new_tokens=10)
    fr.step()
    fr.config.tick_timeout_s = 0.05
    with ReplicaChaos("pre_tick", replica="r0", action="hang", hang_s=0.2, repeat=True):
        fr.step()
        assert fr.health()["r0"]["health"] == "degraded"
        fr.step()
    assert fr.health()["r0"]["health"] == "quarantined"
    assert fr.failover_accounting()["failovers_lost"] == 1
    with pytest.raises(KeyError, match="lost"):
        fr.poll(u_doomed)
    with pytest.raises(ShedError, match="capacity lost"):
        fr.submit(_FT_PROMPTS[1], max_new_tokens=2)
    fr.add_replica(warm_prompt_lens=(4,))
    p = (np.arange(1, 6) % 250).astype(np.int32)
    u = fr.submit(p, max_new_tokens=3)
    out = fr.run()
    np.testing.assert_array_equal(out[u], _reference(tiny_llama, p, 3))


def test_drain_threaded_health_writes_hold_replica_lock(tiny_llama):
    """Regression for the dogfooded TPU902: _set_health mutates
    Replica.health under rep.lock and the drain_threaded workers read
    is_serving under the same lock, so a mid-drain failover can't tear a
    transition. Hammer a threaded drain with a mid-flight crash — the
    pre-fix race window — and hold the PR-15 exactness claims."""
    fr = _ft_fleet(tiny_llama)
    uids = [fr.submit(p, max_new_tokens=_FT_NEW) for p in _FT_PROMPTS[:4]]
    with ReplicaChaos("pre_tick", replica="r0", action="crash") as chaos:
        fr.drain_threaded()
    assert chaos.fired
    assert fr.health()["r0"]["health"] == "dead"
    out = {u: fr.poll(u) for u in uids}
    for u, p in zip(uids, _FT_PROMPTS):
        np.testing.assert_array_equal(out[u], _reference(tiny_llama, p, _FT_NEW))
    # the static gate that keeps the fix fixed
    from accelerate_tpu.analysis.hostsim import host_check_file

    fleet_src = os.path.join(REPO, "accelerate_tpu", "serving_fleet.py")
    assert [f.rule for f in host_check_file(fleet_src)] == []


def test_fleet_request_error_surfaces(tiny_llama, monkeypatch):
    """poll/partial/logprobs/cancel on unknown or failed-over ids raise
    the structured error naming the last known state; cancel on a dead
    replica succeeds WITHOUT touching the dead engine."""
    fr = _ft_fleet(tiny_llama)
    with pytest.raises(FleetRequestError, match="unknown request id"):
        fr.poll(12345)
    with pytest.raises(KeyError):  # it is still a KeyError for old callers
        fr.logprobs(12345)
    # lost: export dies with the replica -> nothing to salvage
    u1 = fr.submit(_FT_PROMPTS[0], max_new_tokens=_FT_NEW)
    monkeypatch.setattr(
        fr.replicas[0].engine, "export_inflight",
        lambda **kw: (_ for _ in ()).throw(RuntimeError("export channel down")),
    )
    fr.fail_replica("r0", error=RuntimeError("host unreachable"))
    with pytest.raises(FleetRequestError, match="no snapshot recovered"):
        fr.partial(u1)
    got = fr.cancel(u1)  # cancelling a lost request succeeds, once
    assert isinstance(got, np.ndarray) and got.size == 0
    with pytest.raises(FleetRequestError, match="unknown request id"):
        fr.cancel(u1)
    # stranded on a dead replica (white-box: dodge the auto-migration)
    fr2 = _ft_fleet(tiny_llama)
    u2 = fr2.submit(_FT_PROMPTS[0], max_new_tokens=_FT_NEW)
    fr2.step()
    fr2.replicas[0].health = "dead"
    fr2.replicas[0].last_error = "RuntimeError: kernel panic"
    with pytest.raises(FleetRequestError, match="dead replica 'r0'"):
        fr2.poll(u2)
    called = []
    monkeypatch.setattr(fr2.replicas[0].engine, "cancel",
                        lambda uid: called.append(uid))
    got2 = fr2.cancel(u2)
    assert got2.size == 0 and called == [], "must not touch the dead engine"
    # done requests refuse cancel with a pointer to poll()
    fr3 = _ft_fleet(tiny_llama)
    u3 = fr3.submit(_FT_PROMPTS[0], max_new_tokens=2)
    fr3.run()
    fr3.drain("r0") if fr3._map[u3][1] == 0 else fr3.drain("r1")
    with pytest.raises(ValueError, match="poll"):
        fr3.cancel(u3)


def test_handoff_codec_roundtrip_exact(tiny_llama):
    """The wire codec: a prefill_detached payload serializes to ONE bytes
    blob and back (dtype-agnostic — the receiving engine's row template
    is the source of truth) with the decoded handoff token- and
    logprob-exact, greedy and sampled."""
    prompt = (np.arange(1, 10) % 250).astype(np.int32)
    for kw in ({}, {"temperature": 0.9, "seed": 5}):
        src = _engine(tiny_llama, **kw)
        local = _engine(tiny_llama, **kw)
        lu = local.submit(prompt, max_new_tokens=5)
        local.run()
        h = src.prefill_detached(prompt, max_new_tokens=5, uid_key=lu)
        blob = HandoffCodec.encode(h)
        assert isinstance(blob, bytes) and len(blob) >= h["wire_bytes"]
        dst = _engine(tiny_llama, **kw)
        h2 = HandoffCodec.decode(blob, dst)
        assert h2["total"] == h["total"] and h2["wire_bytes"] == h["wire_bytes"]
        uid = dst.submit_prefilled(h2)
        dst.run()
        np.testing.assert_array_equal(dst.poll(uid), local.poll(lu))
        np.testing.assert_array_equal(dst.logprobs(uid), local.logprobs(lu))


def test_drain_threaded_surfaces_and_survives_worker_crash(tiny_llama):
    """drain_threaded must never hang on a worker death: with a survivor
    the fleet completes via failover (the fault surfaces through health
    + metrics); with NO survivor the first captured exception is
    re-raised on the caller's thread after join."""
    fr = _ft_fleet(tiny_llama)
    uids = [fr.submit(p, max_new_tokens=_FT_NEW) for p in _FT_PROMPTS[:4]]
    with ReplicaChaos("pre_tick", replica="r0", action="crash") as chaos:
        fr.drain_threaded()
    assert chaos.fired
    assert fr.health()["r0"]["health"] == "dead"
    for u, p in zip(uids, _FT_PROMPTS):
        np.testing.assert_array_equal(fr.poll(u), _reference(tiny_llama, p, _FT_NEW))
    solo = FleetRouter.from_model(
        tiny_llama, num_replicas=1, config=FleetConfig(prefix_reuse=False),
        num_slots=2, prompt_buckets=(4, 8),
    )
    solo.submit(_FT_PROMPTS[0], max_new_tokens=2)
    with ReplicaChaos("pre_tick", replica="r0", action="crash"):
        with pytest.raises(SimulatedCrash):
            solo.drain_threaded()


def test_failover_metrics_and_prometheus(tiny_llama):
    fr = _ft_fleet(tiny_llama, tick_block=2)
    uids = [fr.submit(p, max_new_tokens=_FT_NEW) for p in _FT_PROMPTS[:4]]
    fr.step()
    with ReplicaChaos("pre_tick", replica="r0", action="crash"):
        out = fr.run()
    assert sorted(out) == sorted(uids)
    m = fr.metrics_merged()
    snap = m.snapshot()
    assert snap["failovers_out"] >= 1 and snap["failovers_in"] >= 1
    assert snap["failovers_lost"] == 0 and snap["replica_errors"] == 1
    assert snap["replica_state"] == 3  # merged gauge: worst replica (dead)
    text = m.prometheus_text()
    for needle in ("failovers_in_total", "failovers_out_total", "failovers_lost_total",
                   "replica_errors_total", 'replica_state{replica="fleet"} 3'):
        assert needle in text, needle


def test_failover_handoff_leg_retries_transient_io(tiny_llama, monkeypatch):
    """The KV import leg rides utils.retry: one transient OSError on the
    destination must not lose the request or downgrade it to recompute."""
    fr = _ft_fleet(tiny_llama, failover="handoff", tick_block=2, failover_retry_base_delay_s=0.001)
    uids = [fr.submit(p, max_new_tokens=6) for p in _FT_PROMPTS[:4]]
    fr.step()
    dst = fr.replicas[1].engine
    real = dst.import_inflight
    flaky = {"left": 1}

    def import_flaky(snap):
        if snap.get("cache") is not None and flaky["left"]:
            flaky["left"] -= 1
            raise OSError("transient transport failure")
        return real(snap)

    monkeypatch.setattr(dst, "import_inflight", import_flaky)
    with ReplicaChaos("pre_tick", replica="r0", action="crash"):
        out = fr.run()
    assert sorted(out) == sorted(uids)
    assert flaky["left"] == 0  # the fault actually fired
    acct = fr.failover_accounting()
    assert acct["failovers_kv"] >= 1 and acct["failovers_lost"] == 0
    for u, p in zip(uids, _FT_PROMPTS):
        np.testing.assert_array_equal(out[u], _reference(tiny_llama, p, 6))


# --------------------------------------------------------------------- #
# fleet-level cross-process warm spin-up (promotes the PR-7 test)
# --------------------------------------------------------------------- #

_CHILD_FLEET_REPLICA = """
import sys
sys.path.insert(0, {repo!r})
import numpy as np
from accelerate_tpu.utils.environment import force_host_platform
force_host_platform(1)
from accelerate_tpu.models import LlamaConfig, create_llama_model
from accelerate_tpu.serving_fleet import FleetConfig, FleetRouter

model = create_llama_model(LlamaConfig.tiny(), seq_len=16)
router = FleetRouter.from_model(
    model, num_replicas=1,
    config=FleetConfig(min_prefix_tokens=4, promote_after=2),
    store_dir={store!r}, num_slots=2, prompt_buckets=(4, 8),
)
pre = (np.arange(1, 7) % 250).astype(np.int32)
prompts = [np.concatenate([pre, [40 + i]]).astype(np.int32) for i in range(4)]
uids = [router.submit(p, max_new_tokens=3) for p in prompts]
out = router.run()
eng = router.replicas[0].engine
radix = router.radix_stats()["r0"]
toks = " ".join(str(t) for t in np.concatenate([out[u] for u in uids]))
print("FLEETREP", eng.program_cache.misses, eng.program_cache.deserialized,
      radix["hits"], radix["registrations"], toks)
"""


@pytest.mark.slow
def test_fleet_warm_replica_subprocess_zero_compiles(tmp_path):
    """The fleet-level warm-replica assertion: a FRESH SUBPROCESS builds
    a replica over the shared ExecutableStore and serves shared-preamble
    traffic with 0 XLA compiles — with its radix cache starting COLD
    (prefix registration replays the chunk programs from the store too).
    Promotes the PR-7 two-subprocess engine test to the fleet layer."""
    store = str(tmp_path / "store")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)

    def replica():
        out = subprocess.run(
            [sys.executable, "-c", _CHILD_FLEET_REPLICA.format(repo=REPO, store=store)],
            capture_output=True, text=True, env=env, timeout=600,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        tag, misses, deser, hits, regs, *tokens = out.stdout.strip().splitlines()[-1].split()
        assert tag == "FLEETREP"
        return int(misses), int(deser), int(hits), int(regs), tokens

    cold_misses, cold_deser, cold_hits, cold_regs, ref = replica()
    assert cold_misses >= 1 and cold_deser == 0
    assert cold_regs == 1 and cold_hits >= 1  # radix promoted + reused

    warm_misses, warm_deser, warm_hits, warm_regs, got = replica()
    assert warm_misses == 0, "warm fleet replica must not compile"
    assert warm_deser == cold_misses  # every program came from the store
    assert warm_regs == 1 and warm_hits == cold_hits  # radix started cold, re-promoted
    assert got == ref  # token-exact across processes
