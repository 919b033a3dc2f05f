"""Fleet-wide request tracing (telemetry/trace.py), the crash flight
recorder (telemetry/flightrec.py), priced critical-path decomposition
(telemetry/critpath.py), and the HTTP telemetry endpoint
(telemetry/httpd.py) — plus their router/engine/CLI wiring."""

import json
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

from accelerate_tpu.scheduling import ShedError
from accelerate_tpu.telemetry.critpath import CritPathMonitor, decompose, render_critpath
from accelerate_tpu.telemetry.eventlog import EventLog, merge_events, read_events
from accelerate_tpu.telemetry.flightrec import FlightRecorder, read_dump, render_dump
from accelerate_tpu.telemetry.httpd import TelemetryHTTPD
from accelerate_tpu.telemetry.trace import (
    TraceConfig,
    Tracer,
    chrome_trace,
    traces_from_events,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _ticking_clock(step_s=0.010):
    t = [0.0]

    def clock():
        t[0] += step_s
        return t[0]

    return clock


# --------------------------------------------------------------------- #
# tracer
# --------------------------------------------------------------------- #


def test_tracer_segments_are_frontier_contiguous():
    tr = Tracer(clock=_ticking_clock())
    tid = tr.start(fuid=7)
    tr.seg(tid, "queue_wait", accounted_ms=10.0)
    tr.seg(tid, "admit")
    tr.seg(tid, "prefill", tokens=8)
    tr.window(tid, "decode", tokens=2)
    tr.window(tid, "decode", tokens=2)
    tr.finish(tid, status="ok")
    (done,) = tr.completed()
    assert done["status"] == "ok"
    assert done["meta"]["fuid"] == 7
    # frontier-contiguous spans: each span starts where the previous one
    # ended, so the only time outside any span is the finish() call
    # itself (exactly one 10ms tick of the fake clock)
    frontier = 0.0
    for sp in done["spans"]:
        assert sp["t0_ms"] == pytest.approx(frontier)
        frontier = sp["t0_ms"] + sp["dur_ms"]
    seg_sum = sum(sp["dur_ms"] for sp in done["spans"])
    assert done["dur_ms"] - seg_sum == pytest.approx(10.0)
    names = [sp["name"] for sp in done["spans"]]
    assert names == ["queue_wait", "admit", "prefill", "decode"]
    decode = done["spans"][-1]
    assert decode["tokens"] == 4  # consecutive windows merged + summed


def test_tracer_seg_breaks_a_window_merge():
    tr = Tracer(clock=_ticking_clock())
    tid = tr.start()
    tr.window(tid, "decode", tokens=1)
    tr.seg(tid, "preempt")
    tr.window(tid, "decode", tokens=1)
    tr.finish(tid)
    (done,) = tr.completed()
    assert [sp["name"] for sp in done["spans"]] == ["decode", "preempt", "decode"]


def test_tracer_noops_on_none_unknown_and_finished_ids():
    tr = Tracer(clock=_ticking_clock())
    tr.seg(None, "prefill")
    tr.window(None, "decode")
    tr.finish(None)
    tr.seg(12345, "prefill")  # never started
    tid = tr.start()
    tr.finish(tid, status="ok")
    tr.seg(tid, "decode")  # already sealed: must not raise or mutate
    tr.finish(tid, status="failed")
    (done,) = tr.completed()
    assert done["status"] == "ok"
    assert done["spans"] == []


def test_tracer_ring_trims_completed():
    tr = Tracer(max_traces=4, clock=_ticking_clock())
    for i in range(10):
        tid = tr.start(i=i)
        tr.finish(tid)
    done = tr.completed()
    assert len(done) == 4
    assert [t["meta"]["i"] for t in done] == [6, 7, 8, 9]


def test_tracer_discard_and_shed_status():
    tr = Tracer(clock=_ticking_clock())
    a = tr.start()
    tr.discard(a)
    b = tr.start()
    tr.finish(b, status="shed", reason="queue full")
    done = tr.completed()
    assert [t["id"] for t in done] == [b]
    assert done[0]["status"] == "shed"
    assert done[0]["meta"]["reason"] == "queue full"


def test_trace_jsonl_emission_and_reconstruction(tmp_path):
    path = str(tmp_path / "run.jsonl")
    log = EventLog(path, rank=0)
    tr = Tracer(clock=_ticking_clock(), log=log)
    tid = tr.start(fuid=3)
    tr.seg(tid, "queue_wait")
    tr.seg(tid, "prefill", tokens=4)
    tr.window(tid, "decode", tokens=2)
    tr.finish(tid, status="ok")
    log.close()
    events = read_events(path)
    spans = [e for e in events if e.get("kind") == "span" and e["name"].startswith("trace.")]
    completes = [e for e in events if e.get("name") == "trace_complete"]
    assert len(spans) == 3 and len(completes) == 1
    assert all(e.get("trace") == tid for e in spans + completes)
    # eventlog-compatible: reconstruction recovers the same decomposition
    (rec,) = traces_from_events(events)
    assert rec["id"] == tid and rec["status"] == "ok"
    assert [sp["name"] for sp in rec["spans"]] == ["queue_wait", "prefill", "decode"]
    # one fake-clock tick (finish) is the only time outside the spans
    assert rec["dur_ms"] - sum(sp["dur_ms"] for sp in rec["spans"]) == pytest.approx(10.0)


def test_chrome_trace_export_loads_in_perfetto_shape():
    tr = Tracer(clock=_ticking_clock())
    for i in range(2):
        tid = tr.start(fuid=i)
        tr.seg(tid, "prefill")
        tr.window(tid, "decode", tokens=1)
        tr.finish(tid)
    doc = chrome_trace(tr.completed())
    assert isinstance(doc["traceEvents"], list)
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 4  # 2 traces x 2 spans
    assert all({"name", "ts", "dur", "pid", "tid"} <= set(e) for e in xs)
    json.dumps(doc)  # must be plain-JSON serializable for the viewer


# --------------------------------------------------------------------- #
# critical path
# --------------------------------------------------------------------- #


def _mk_trace(segs, status="ok", tid=1, meta=None):
    spans, t0 = [], 0.0
    for name, dur, extra in segs:
        spans.append({"name": name, "t0_ms": t0, "dur_ms": dur, **extra})
        t0 += dur
    return {"id": tid, "status": status, "dur_ms": t0, "spans": spans, "meta": meta or {}}


def test_decompose_percentiles_and_share():
    traces = [
        _mk_trace([("prefill", 10.0, {}), ("decode", 30.0, {})], tid=1),
        _mk_trace([("prefill", 20.0, {}), ("decode", 40.0, {})], tid=2),
    ]
    rep = decompose(traces)
    assert rep["count"] == 2 and rep["completed"] == 2
    assert rep["by_class"]["prefill"]["p50_ms"] == 10.0
    assert rep["by_class"]["prefill"]["p95_ms"] == 20.0
    assert rep["by_class"]["decode"]["total_ms"] == 70.0
    assert rep["by_class"]["decode"]["share"] == pytest.approx(0.7)
    text = render_critpath(rep)
    assert "prefill" in text and "decode" in text


def test_critpath_latches_once_per_class_and_resets():
    mon = CritPathMonitor()
    bad = _mk_trace(
        [("kv_handoff", 1.0, {"moved_bytes": 100, "predicted_bytes": 200})], tid=1
    )
    mon.observe(bad)
    mon.observe(_mk_trace(
        [("kv_handoff", 1.0, {"moved_bytes": 1, "predicted_bytes": 999})], tid=2
    ))
    assert list(mon.drift_events) == ["kv_handoff"]
    assert mon.drift_events["kv_handoff"]["trace"] == 1  # first excursion wins
    mon.reset()
    assert mon.drift_events == {}


def test_critpath_skips_paste_and_recompute_spans():
    mon = CritPathMonitor()
    # decode-side paste span has no byte pair; recompute failovers move
    # no KV by design — neither may latch
    mon.observe(_mk_trace([("kv_handoff", 1.0, {"phase": "paste", "rows": 3})]))
    mon.observe(_mk_trace(
        [("failover", 1.0, {"path": "recompute", "moved_bytes": 0, "predicted_bytes": 999})]
    ))
    assert mon.drift_events == {}


def test_critpath_queue_wait_vs_scheduler_accounting():
    mon = CritPathMonitor()
    mon.observe(_mk_trace([("queue_wait", 50.0, {"accounted_ms": 10.0})], tid=9))
    assert list(mon.drift_events) == ["queue_wait"]
    assert mon.drift_events["queue_wait"]["check"] == "scheduler_accounting"
    # tiny absolute gaps never latch (coarse-clock noise floor)
    mon2 = CritPathMonitor()
    mon2.observe(_mk_trace([("queue_wait", 1.8, {"accounted_ms": 0.2})]))
    assert mon2.drift_events == {}


def test_critpath_prefill_vs_injected_price():
    mon = CritPathMonitor(price_prefill_us=lambda tokens: tokens * 1000.0)
    mon.observe(_mk_trace(
        [("prefill", 500.0, {"tokens": 8, "compute_ms": 100.0})], tid=4
    ))  # predicted 8 ms vs computed 100 ms: > 2x threshold
    assert list(mon.drift_events) == ["prefill"]
    assert mon.drift_events["prefill"]["check"] == "prefill_compute_us"
    # a chunk window has no sync to end a compute time at: it carries its
    # enqueue time as dispatch_ms, which is not held against a compute price
    mon2 = CritPathMonitor(price_prefill_us=lambda tokens: tokens * 1000.0)
    mon2.observe(_mk_trace([("prefill", 500.0, {"tokens": 8, "dispatch_ms": 100.0})], tid=5))
    assert mon2.drift_events == {}


# --------------------------------------------------------------------- #
# flight recorder
# --------------------------------------------------------------------- #


def test_flightrec_ring_keeps_last_n_in_order():
    fr = FlightRecorder(8, name="r0")
    for i in range(20):
        fr.record({"kind": "event", "name": f"e{i}", "seq": i})
    tail = fr.tail()
    assert [e["name"] for e in tail] == [f"e{i}" for i in range(12, 20)]
    assert fr.tail(2)[-1]["name"] == "e19"


def test_flightrec_dump_write_read_render(tmp_path):
    fr = FlightRecorder(8, name="r1")
    fr.record({"kind": "event", "name": "replica_state", "state": "dead"})
    path = str(tmp_path / "flight.json")
    doc = fr.dump(
        reason="dead: boom", inflight=[{"uid": 1, "state": "active"}],
        open_spans=[{"trace": 5, "name": "decode"}], path=path,
    )
    assert doc["path"] == path
    back = read_dump(path)
    assert back["reason"] == "dead: boom"
    assert back["events"][-1]["name"] == "replica_state"
    assert back["inflight"][0]["uid"] == 1
    text = render_dump(back)
    assert "dead: boom" in text and "replica_state" in text


def test_flightrec_dump_never_raises_on_hostile_payloads(tmp_path):
    fr = FlightRecorder(8, name="r2")
    fr.record({"kind": "event", "name": "weird", "payload": object()})
    # deep path: parents are created on demand
    ok = fr.dump(reason="x", path=str(tmp_path / "deep" / "dir" / "f.json"))
    assert ok["path"] and read_dump(ok["path"])["reason"] == "x"  # object() coerced
    # unwritable path (a file where a directory is needed): reported, not raised
    (tmp_path / "blocker").write_text("")
    doc = fr.dump(reason="x", path=str(tmp_path / "blocker" / "f.json"))
    assert doc["reason"] == "x" and "write_error" in doc and "path" not in doc


# --------------------------------------------------------------------- #
# eventlog: per-process sequence numbers + deterministic merge
# --------------------------------------------------------------------- #


def test_eventlog_seq_monotonic_and_taps(tmp_path):
    log = EventLog(str(tmp_path / "a.jsonl"), rank=0)
    seen = []
    log.add_tap(seen.append)
    log.event("one")
    log.event("two")
    log.close()
    recs = read_events(str(tmp_path / "a.jsonl"))
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert [r["name"] for r in seen] == ["one", "two"]  # tap saw every record
    log2 = EventLog(None, rank=0)  # taps fire even with no sink
    log2.add_tap(seen.append)
    log2.event("three")
    assert seen[-1]["name"] == "three"
    log2.remove_tap(seen.append)
    log2.event("four")
    assert seen[-1]["name"] == "three"


def test_merge_events_deterministic_and_tolerates_old_logs(tmp_path):
    log = EventLog(str(tmp_path / "new.jsonl"), rank=0, clock=lambda: 100.0)
    log.event("n1")
    log.event("n2")
    log.close()
    new = read_events(str(tmp_path / "new.jsonl"))
    old = [{"v": 1, "ts": 100.0, "rank": 0, "kind": "event", "name": "legacy"}]  # no seq
    merged = merge_events(old, new)
    # same ts: the legacy record (no seq -> -1) sorts first, then by seq
    assert [r["name"] for r in merged] == ["legacy", "n1", "n2"]
    assert merge_events(new, old) == merged  # input order can't change the result


# --------------------------------------------------------------------- #
# HTTP endpoint
# --------------------------------------------------------------------- #


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.status, resp.read(), resp.headers
    except urllib.error.HTTPError as e:  # non-2xx still carries a body
        return e.code, e.read(), e.headers


def test_httpd_metrics_healthz_traces_and_404():
    metrics = 'fleet_up{replica="r0"} 1\n'
    health = {"r0": {"health": "healthy"}, "r1": {"health": "dead"}}
    with TelemetryHTTPD(
        metrics_fn=lambda: metrics,
        health_fn=lambda: health,
        traces_fn=lambda n: [{"id": i} for i in range(min(n, 5))],
    ) as srv:
        status, body, headers = _get(srv.url("/metrics"))
        assert status == 200
        assert body == metrics.encode("utf-8")  # byte-identical exposition
        assert headers["Content-Type"].startswith("text/plain; version=0.0.4")
        status, body, _ = _get(srv.url("/healthz"))
        assert status == 200 and json.loads(body)["serving"] is True
        status, body, _ = _get(srv.url("/traces?n=2"))
        assert status == 200 and len(json.loads(body)["traces"]) == 2
        status, _, _ = _get(srv.url("/nope"))
        assert status == 404
    # all replicas down -> 503 (load balancers must stop routing here)
    with TelemetryHTTPD(
        metrics_fn=lambda: "", health_fn=lambda: {"r0": {"health": "dead"}}
    ) as srv:
        status, body, _ = _get(srv.url("/healthz"))
        assert status == 503 and json.loads(body)["serving"] is False


# --------------------------------------------------------------------- #
# knobs + error surfaces
# --------------------------------------------------------------------- #


def test_telemetry_kwargs_trace_config():
    from accelerate_tpu.utils.dataclasses import TelemetryKwargs

    assert TelemetryKwargs().trace_config() is None
    cfg = TelemetryKwargs(
        trace_requests=True, flight_capacity=64, flight_dump_dir="/tmp/fd"
    ).trace_config()
    assert isinstance(cfg, TraceConfig)
    assert cfg.flight_capacity == 64 and cfg.flight_dump_dir == "/tmp/fd"
    with pytest.raises(ValueError):
        TelemetryKwargs(flight_capacity=2)


def test_shed_error_carries_trace_id():
    e = ShedError("queue full", priority=1, queue_depth=9, trace_id=42)
    assert e.trace_id == 42 and "trace=42" in str(e)
    assert ShedError("queue full").trace_id is None


def test_fleet_request_error_names_trace():
    from accelerate_tpu.serving_fleet import FleetRequestError

    e = FleetRequestError(3, "lost", "no snapshot", trace_id=17)
    assert e.trace_id == 17 and "(trace 17)" in str(e)
    assert FleetRequestError(3, "unknown").trace_id is None


# --------------------------------------------------------------------- #
# summarize integration
# --------------------------------------------------------------------- #


def _traced_run_jsonl(tmp_path, *, drift=False):
    path = str(tmp_path / "traced.jsonl")
    log = EventLog(path, rank=0)
    mon = CritPathMonitor(log)
    tr = Tracer(clock=_ticking_clock(), log=log, on_finish=mon.observe)
    for i in range(3):
        tid = tr.start(fuid=i)
        tr.seg(tid, "queue_wait", accounted_ms=10.0)
        tr.seg(tid, "prefill", tokens=8)
        moved = 100 if (drift and i == 0) else 4096
        tr.seg(tid, "kv_handoff", tokens=8, moved_bytes=moved, predicted_bytes=4096)
        tr.window(tid, "decode", tokens=4)
        tr.finish(tid, status="ok")
    log.event("flight_dump", replica="r0", reason="dead: boom", events=5)
    log.close()
    return path


def test_summarize_traces_section_and_render(tmp_path):
    from accelerate_tpu.telemetry import render_text, summarize_file

    report = summarize_file(_traced_run_jsonl(tmp_path, drift=True))
    traces = report["traces"]
    assert traces["count"] == 3 and traces["completed"] == 3
    assert set(traces["by_class"]) == {"queue_wait", "prefill", "kv_handoff", "decode"}
    assert len(traces["drift_events"]) == 1
    assert traces["drift_events"][0]["segment"] == "kv_handoff"
    assert traces["flight_dumps"] == 1
    assert report["warnings"] >= 1  # the latched trace_drift counts
    text = render_text(report)
    assert "traces:" in text and "kv_handoff" in text and "DRIFT" in text
    assert "flight dumps" in text
    clean = summarize_file(_traced_run_jsonl(tmp_path, drift=False))
    assert clean["traces"]["drift_events"] == []


def test_cli_trace_summarize_export_flightdump_selfcheck(tmp_path):
    path = _traced_run_jsonl(tmp_path, drift=True)

    def cli(*argv):
        return subprocess.run(
            [sys.executable, "-m", "accelerate_tpu.commands.cli", "trace", *argv],
            capture_output=True, text=True, env=CPU_ENV, timeout=240, cwd=REPO,
        )

    out = cli("summarize", path)
    assert out.returncode == 0, out.stderr
    assert "kv_handoff" in out.stdout and "DRIFT" in out.stdout
    out = cli("summarize", path, "--format", "json")
    assert json.loads(out.stdout)["completed"] == 3
    assert cli("summarize", path, "--strict").returncode == 1  # drift latched
    chrome = str(tmp_path / "chrome.json")
    out = cli("export", path, "-o", chrome)
    assert out.returncode == 0, out.stderr
    doc = json.load(open(chrome))
    assert any(e.get("ph") == "X" for e in doc["traceEvents"])
    fr = FlightRecorder(8, name="r0")
    fr.record({"kind": "event", "name": "replica_state", "state": "dead"})
    dpath = str(tmp_path / "flight.json")
    fr.dump(reason="dead: boom", path=dpath)
    out = cli("flight-dump", dpath)
    assert out.returncode == 0 and "dead: boom" in out.stdout
    out = cli("selfcheck")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout


def test_fleet_check_clean_over_threaded_telemetry_modules():
    out = subprocess.run(
        [
            sys.executable, "-m", "accelerate_tpu.commands.cli", "fleet-check",
            "accelerate_tpu/telemetry/httpd.py",
            "accelerate_tpu/telemetry/flightrec.py",
            "accelerate_tpu/telemetry/trace.py",
        ],
        capture_output=True, text=True, env=CPU_ENV, timeout=240, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 finding(s)" in out.stdout


# --------------------------------------------------------------------- #
# handoff codec v2: the trace id rides the wire blob
# --------------------------------------------------------------------- #


def test_handoff_codec_trace_roundtrip_and_v1_compat():
    from accelerate_tpu.serving_fleet import HandoffCodec

    class _Eng:
        _row_template = {
            "k": np.zeros((2, 3), np.float32), "v": np.zeros((2, 3), np.float32)
        }

    handoff = {
        "prompt": np.arange(4, dtype=np.int32), "total": 4, "max_new_tokens": 2,
        "next_tok": 7, "lp": -1.25, "key_data": np.zeros(2, np.uint32),
        "cache": {"k": np.ones((2, 3), np.float32), "v": np.full((2, 3), 2.0, np.float32)},
        "wire_bytes": 48, "reused_prefix_tokens": 0, "trace": 42,
    }
    dec = HandoffCodec.decode(HandoffCodec.encode(handoff), _Eng())
    assert dec["trace"] == 42
    np.testing.assert_array_equal(dec["cache"]["v"], handoff["cache"]["v"])
    # v1 blob (no trace key at all) must still decode — trace comes back None
    v1 = {k: v for k, v in handoff.items() if k != "trace"}
    assert HandoffCodec.decode(HandoffCodec.encode(v1), _Eng())["trace"] is None


# --------------------------------------------------------------------- #
# fleet integration (jax, CPU)
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def tiny_llama():
    from accelerate_tpu.models import LlamaConfig, create_llama_model

    return create_llama_model(LlamaConfig.tiny(), seq_len=16)


@pytest.fixture(autouse=True)
def bound_live_executables_per_test():
    yield
    import sys as _sys

    jax = _sys.modules.get("jax")
    if jax is not None:
        jax.clear_caches()


def _traced_fleet(model, *, roles=None, handoff="auto", **cfg_kw):
    from accelerate_tpu.serving_fleet import FleetConfig, FleetRouter

    cfg_kw.setdefault("prefix_reuse", False)
    return FleetRouter.from_model(
        model, num_replicas=2,
        config=FleetConfig(roles=roles, handoff=handoff, **cfg_kw),
        trace=True, num_slots=2, prompt_buckets=(4, 8), tick_block=2,
    )


def _warm(router, rng, lens=(4, 8, 10)):
    for rep in router.replicas:
        for n in lens:
            rep.engine.submit(rng.integers(1, 250, size=n).astype(np.int32), max_new_tokens=2)
        rep.engine.run()


def test_traced_disaggregated_fleet_end_to_end(tiny_llama):
    """One trace per request across the prefill->handoff->decode hop:
    frontier-contiguous segments reconcile with e2e latency, the handoff
    span's bytes match the pre-priced prediction, and no drift latches."""
    fr = _traced_fleet(tiny_llama, roles=("prefill", "decode"), handoff="always")
    emitted = []
    for rep in fr.replicas:
        rep.engine._log.add_tap(emitted.append)
    rng = np.random.default_rng(3)
    prompts = [(np.arange(1, 7) % 250 + i).astype(np.int32) for i in range(3)]
    uids = [fr.submit(p, max_new_tokens=4) for p in prompts]
    out = fr.run()
    assert sorted(out) == sorted(uids)
    traces = [t for t in fr.tracer.completed() if "fuid" in t["meta"]]
    assert len(traces) == len(uids)
    for tr in traces:
        assert tr["status"] == "ok"
        names = {sp["name"] for sp in tr["spans"]}
        assert {"prefill", "kv_handoff", "queue_wait", "admit", "decode"} <= names
        seg_sum = sum(sp["dur_ms"] for sp in tr["spans"])
        assert abs(tr["dur_ms"] - seg_sum) / tr["dur_ms"] <= 0.05
        (ho,) = [
            sp for sp in tr["spans"]
            if sp["name"] == "kv_handoff" and sp.get("moved_bytes") is not None
        ]
        assert ho["moved_bytes"] == ho["predicted_bytes"] > 0
        # prefill_detached streams chunk windows: each reports its enqueue
        # time as dispatch_ms, never under a compute name
        prefill = [sp for sp in tr["spans"] if sp["name"] == "prefill"]
        assert prefill and all("dispatch_ms" in sp and "compute_ms" not in sp for sp in prefill)
        decode = [sp for sp in tr["spans"] if sp["name"] == "decode"]
        # the FIRST generated token is minted during prefill and rides
        # the handoff blob; decode windows cover the remaining three
        assert sum(sp["tokens"] for sp in decode) == 4 - 1
    assert fr.critpath.drift_events == {}
    # the kv_handoff fleet event carries the trace id (satellite: events
    # are joinable against traces)
    ho_events = [e for e in emitted if e.get("name") == "kv_handoff"]
    assert ho_events and all(e.get("trace") is not None for e in ho_events)


@pytest.mark.parametrize("action", ["crash", "poison", "hang"])
def test_every_chaos_fault_class_dumps_the_flight_recorder(tiny_llama, action):
    """ISSUE 18 acceptance: crash, poison, AND hang must each leave a
    flight-recorder dump on the faulted replica whose tail contains the
    injected fault's event."""
    from accelerate_tpu.test_utils.fault_injection import ReplicaChaos

    fr = _traced_fleet(tiny_llama, quarantine_after_timeouts=1)
    emitted = []
    for rep in fr.replicas:
        rep.engine._log.add_tap(emitted.append)
    rng = np.random.default_rng(5)
    _warm(fr, rng)
    uids = [
        fr.submit((np.arange(1, 6) % 250 + i).astype(np.int32), max_new_tokens=6)
        for i in range(4)
    ]
    fr.step()
    if action == "hang":
        fr.config.tick_timeout_s = 0.05
        chaos_kw = {"action": "hang", "hang_s": 0.2, "repeat": True}
    else:
        chaos_kw = {"action": action}
    with ReplicaChaos("pre_tick", replica="r0", **chaos_kw) as chaos:
        out = fr.run()
    assert chaos.fired
    assert sorted(out) == sorted(uids)  # failover saved every request
    rep = next(r for r in fr.replicas if r.name == "r0")
    expected = {"crash": "dead", "poison": "quarantined", "hang": "quarantined"}[action]
    assert fr.health()["r0"]["health"] == expected
    dump = rep.flightrec.last_dump
    assert dump is not None and dump["reason"].startswith(expected)
    tail = dump["events"]
    if action == "hang":
        assert any(e.get("name") == "replica_timeout" for e in tail)
        assert any(
            e.get("name") == "replica_state" and "timeout" in str(e.get("reason", ""))
            for e in tail
        )
    else:
        marker = {"crash": "SimulatedCrash", "poison": "NonFinitePoison"}[action]
        assert any(
            e.get("name") == "replica_state" and marker in str(e.get("reason", ""))
            for e in tail
        )
    # the dump is a flight_dump event too, so offline summarize counts it
    assert any(e.get("name") == "flight_dump" for e in emitted)


def test_httpd_serves_router_bytes_and_survives_chaos_scrape(tiny_llama):
    """/metrics on a real port is byte-identical to fleet_prometheus_text,
    and a replica crash WHILE the endpoint is being scraped never breaks
    a request (the ISSUE 18 regression: formatting happens outside any
    lock the failover path needs)."""
    from accelerate_tpu.test_utils.fault_injection import ReplicaChaos

    fr = _traced_fleet(tiny_llama)
    rng = np.random.default_rng(7)
    _warm(fr, rng)
    with TelemetryHTTPD.for_router(fr) as srv:
        status, body, _ = _get(srv.url("/metrics"))
        assert status == 200
        assert body == fr.prometheus_text().encode("utf-8")
        uids = [
            fr.submit((np.arange(1, 6) % 250 + i).astype(np.int32), max_new_tokens=6)
            for i in range(4)
        ]
        fr.step()
        scrape_errors, stop = [], threading.Event()

        def scraper():
            while not stop.is_set():
                try:
                    s1, b1, _ = _get(srv.url("/metrics"))
                    s2, b2, _ = _get(srv.url("/healthz"))
                    assert s1 == 200 and b1
                    assert s2 in (200, 503) and json.loads(b2)["replicas"]
                except Exception as e:  # noqa: BLE001 — the regression under test
                    scrape_errors.append(e)
                    return

        t = threading.Thread(target=scraper, daemon=True)
        t.start()
        with ReplicaChaos("pre_tick", replica="r0", action="crash") as chaos:
            out = fr.run()
        stop.set()
        t.join(timeout=10)
        assert chaos.fired and sorted(out) == sorted(uids)
        assert not scrape_errors, scrape_errors
        # post-crash scrape reflects the transition and completed traces
        status, body, _ = _get(srv.url("/healthz"))
        health = json.loads(body)
        assert health["replicas"]["r0"]["health"] == "dead"
        assert health["serving"] is True  # r1 still serves -> keep routing
        status, body, _ = _get(srv.url("/traces?n=100"))
        got = json.loads(body)["traces"]
        assert status == 200 and len([t for t in got if "fuid" in t["meta"]]) == len(uids)
