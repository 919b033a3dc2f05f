"""The reduction from a trace to the program's phases: on hand-made spans, on one small trace
recorded on the host's CPU with the program's own spans (``data/cpu_program_phases.xplane.pb.gz``,
by ``record_program_trace.py``), and through a traced rehearsal of a cell whose manifest lists
the metrics that read them."""

import gzip
import json
import os
import shutil

import pytest

from chipbench import program_trace as pt
from chipbench.generators import open_loop_rounds, train_steps

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RECORDED = os.path.join(HERE, "data", "cpu_program_phases.xplane.pb.gz")
BENCHMARK_SPANS = open_loop_rounds.SPANS + train_steps.SPANS


def span(name, start, end, **stats):
    return {"name": name, "start": start, "end": end, "stats": stats}


def hand_made():
    """One ``step`` of the benchmark holding one tick that admits request 7 (256 prompt tokens) and
    decodes, then a ``wait``; the device idles 0-1 (schedule), 3-3.4 (paste), 9-9.5 (walk), 10-12 (wait)."""
    spans = [
        span("window", 0.0, 12.0), span("step", 0.0, 10.0), span("wait", 10.0, 12.0),
        span("engine.tick", 0.0, 9.8, tick=1, mono_ns=5_000_000_000),
        span("engine.schedule", 0.0, 1.0),
        span("engine.prefill.dispatch", 1.0, 2.0, uid=7, tokens=256, prompt_tokens=200),
        span("engine.prefill.paste", 2.0, 3.5, uid=7),
        span("engine.prefill.sync", 3.5, 4.0, uid=7),
        span("engine.decode.dispatch", 4.0, 5.0, decoding=3, tick_block=8, live_tokens=900),
        span("engine.decode.sync", 5.0, 9.0),
        span("engine.decode.walk", 9.0, 9.6),
        span("engine.tick.done", 9.6, 9.6, admitted=1),
    ]
    ops = [("fusion.1", 1.0, 2.0), ("copy.2", 3.4, 5.6), ("fusion.3", 9.5, 0.5)]
    modules = [("jit_prefill_b256(123)", 1.0, 2.0), ("jit_paste_row(9)", 4.0, 0.5), ("jit_paged_decode_tick(77)", 4.5, 4.5)]
    return {"spans": spans, "devices": {0: ops}, "modules": {0: modules}, "profile_start_ns": 1_000}


def test_nesting_and_self_time():
    phases = pt.reduce(hand_made())
    spans = phases["spans"]
    by_name = {s["name"]: s for s in spans}
    tick = spans.index(by_name["engine.tick"])
    assert by_name["engine.tick"]["parent"] == spans.index(by_name["step"]) and by_name["step"]["parent"] is None
    assert [spans[j]["name"] for j in by_name["engine.tick"]["children"]] == [
        "engine.schedule", "engine.prefill.dispatch", "engine.prefill.paste", "engine.prefill.sync",
        "engine.decode.dispatch", "engine.decode.sync", "engine.decode.walk", "engine.tick.done"]
    assert all(spans[j]["parent"] == tick for j in by_name["engine.tick"]["children"])
    assert by_name["engine.tick"]["self_s"] == pytest.approx(9.8 - 9.6) and by_name["step"]["self_s"] == pytest.approx(0.2)
    assert by_name["engine.decode.sync"]["self_s"] == pytest.approx(4.0)
    assert pt.ancestor(spans, spans.index(by_name["engine.prefill.sync"]), "step") == spans.index(by_name["step"])
    assert phases["window"] == (0.0, 12.0)


def test_idle_goes_to_the_innermost_span_over_the_gap():
    phases = pt.reduce(hand_made())
    assert phases["idle_by_phase"] == pytest.approx(
        {"engine.schedule": 1.0, "engine.prefill.paste": 0.4, "engine.decode.walk": 0.5, "wait": 2.0})
    note = pt.summary(phases)
    assert note["idle_in_step_s"] == pytest.approx(1.9) and note["idle_in_leaf_share"] == pytest.approx(1.0)
    assert note["children_cover"]["engine.tick"]["min"] == pytest.approx(9.6 / 9.8)
    assert note["phase_seconds"]["engine.decode.sync"] == pytest.approx(4.0) and note["phase_calls"]["engine.tick"] == 1


def test_idle_outside_every_leaf_goes_to_the_tick_then_the_step_then_no_span():
    raw = hand_made()
    raw["spans"] = [s for s in raw["spans"] if s["name"] in ("window", "step", "engine.tick", "engine.decode.sync")]
    raw["devices"] = {0: [("fusion.1", 0.5, 8.5), ("fusion.2", 9.7, 0.15), ("fusion.3", 10.5, 1.0)]}
    phases = pt.reduce(raw)
    # 0-0.5 and 9-9.7: the tick itself; 9.85-10.5 (midpoint 10.175) and 11.5-12: no span at all
    assert phases["idle_by_phase"] == pytest.approx({"engine.tick": 1.2, "_no_span_": 1.15})
    note = pt.summary(phases)
    assert note["idle_in_step_s"] == pytest.approx(1.2) and note["idle_in_leaf_share"] == pytest.approx(0.0)


def test_program_seconds_by_the_names_the_program_cache_logs():
    assert pt.program_name("jit_prefill_b256(6074760096634504725)") == "prefill_b256"
    assert pt.program_name("jit_paged_decode_tick") == "paged_decode_tick" and pt.program_name("jit__lambda(5)") == "_lambda"
    phases = pt.reduce(hand_made())
    assert phases["program_seconds"] == pytest.approx({"prefill_b256": 2.0, "paste_row": 0.5, "paged_decode_tick": 4.5})
    assert pt.program_share(phases, 5.0, ("prefill_b", "chunk_", "paste_row")) == pytest.approx(50.0)


def test_what_the_readers_compute():
    phases = pt.reduce(hand_made())
    assert pt.first_token_hold_ms(phases) == pytest.approx((9.8 - 4.0) * 1e3)
    assert pt.prefill_ms_per_ktok(phases) == pytest.approx((4.0 - 1.0) * 1e3 / 0.2)
    assert pt.decode_step_ms(phases) == pytest.approx((9.0 - 4.0) * 1e3 / 8)
    assert pt.tick_host_ms(phases) == pytest.approx((9.8 - 0.5 - 4.0) * 1e3)
    assert pt.span_ms(phases, "train.step") is None and pt.idle_share_within(phases, "train.step") == 0.0
    assert pt.idle_share_within(phases, "engine.") == pytest.approx(100.0 * 1.9 / 12.0)


def test_a_benchmark_span_under_the_programs_prefix_is_refused(recorded):
    with pytest.raises(ValueError, match="engine.tick"):
        pt.load(recorded, BENCHMARK_SPANS + ("engine.tick",))
    pt.check_names(BENCHMARK_SPANS)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("recorded") / "cpu_program_phases.xplane.pb"
    with gzip.open(RECORDED, "rb") as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(path)


def test_recorded_trace_holds_the_programs_phases_inside_the_benchmarks_spans(recorded):
    from accelerate_tpu.telemetry.trace import PHASES

    phases = pt.reduce(pt.load(recorded, BENCHMARK_SPANS))
    spans = phases["spans"]
    assert {s["name"] for s in spans if pt.is_program_span(s["name"])} == set(PHASES)
    for s in spans:
        if s["name"] in ("engine.tick", "train.step"):
            assert spans[s["parent"]]["name"] == "step"
        elif s["name"] == "engine.submit":
            assert spans[s["parent"]]["name"] == "submit"
        elif pt.is_program_span(s["name"]):
            assert spans[s["parent"]]["name"] in ("engine.tick", "train.step")
    assert phases["profile_start_ns"] > 1_600_000_000 * 10**9, "unix nanoseconds"
    ticks = [spans[i] for i in pt.named(phases, "engine.tick")]
    assert len(ticks) == 3 and [t["stats"]["prefilling"] for t in ticks] == [0, 0, 0]
    offsets = [t["stats"]["mono_ns"] * 1e-9 - t["start"] for t in ticks + [spans[i] for i in pt.named(phases, "train.step")]]
    assert max(offsets) - min(offsets) < 2e-3, "mono_ns less the span's start is one offset for the whole trace"
    # the 20-token prompt went through two chunk windows and the sampling dispatch
    windows = [spans[i]["stats"]["tokens"] for i in pt.named(phases, "engine.prefill.dispatch") if spans[i]["stats"]["prompt_tokens"] == 20]
    assert windows == [16, 8, 0]


def test_recorded_trace_reduces_to_every_span_read_metric(recorded):
    phases = pt.reduce(pt.load(recorded, BENCHMARK_SPANS))
    note = pt.summary(phases)
    assert json.loads(json.dumps(note)) == note, "the note is plain JSON"
    assert note["children_cover"]["engine.tick"]["min"] > 0.9 and note["children_cover"]["train.step"]["min"] > 0.9
    assert note["idle_in_step_s"] > 0 and note["idle_in_leaf_share"] > 0.9
    assert note["program_seconds"] == {}, "XLA:CPU writes no XLA Modules line"
    for value in (pt.first_token_hold_ms(phases), pt.prefill_ms_per_ktok(phases), pt.decode_step_ms(phases),
                  pt.tick_host_ms(phases), pt.span_ms(phases, "train.step"), pt.idle_share_within(phases, "train.step")):
        assert value is not None and value > 0
    assert len(pt.named(phases, "engine.prefill.sync")) == 3
    assert pt.first_token_hold_ms(phases) < max(s["end"] - s["start"] for s in phases["spans"] if s["name"] == "engine.tick") * 1e3


def test_a_trace_of_a_program_without_spans_keeps_the_benchmarks_thread_and_the_modules():
    """``data/v5e_small.xplane.pb`` was recorded on a chip before the program had spans."""
    phases = pt.reduce(pt.load(os.path.join(HERE, "data", "v5e_small.xplane.pb"), ("step", "feed")))
    assert [s["name"] for s in phases["spans"]].count("step") == 6 and not any(pt.is_program_span(s["name"]) for s in phases["spans"])
    assert phases["idle_by_phase"].get("feed", 0.0) > 0.05 and list(phases["program_seconds"]) == ["_lambda"]
    assert 0 < phases["program_seconds"]["_lambda"] < phases["window"][1] - phases["window"][0]


NEW_METRICS = [
    ("first_token_hold_p50_ms", "ms", "program_span", "engine host loop", "ttft_p90_ms", "tiny-serve-chat"),
    ("engine_prefill_ms_per_ktok", "ms", "program_span", "jitted programs", "ttft_p90_ms", "tiny-serve-chat"),
    ("tick_host_ms", "ms", "program_span", "engine host loop", "tpot_p90_ms", "tiny-serve-chat"),
    ("chat_prefill_device_share", "%", "device_trace", "jitted programs", "tpot_p90_ms", "tiny-serve-chat"),
    ("train_dispatch_ms", "ms", "program_span", "jitted programs", "train_tokens_per_s", "tiny-train"),
    ("train_idle_in_dispatch_share", "%", "device_trace", "device", "train_tokens_per_s", "tiny-train"),
]


@pytest.fixture
def rehearse_under(tmp_path, monkeypatch, capsys):
    """A traced rehearsal through a manifest in ``tmp_path`` that lists the new metrics, with the
    harness's root there too: several test workers write traces at once, and the readers take
    the newest under the root."""
    import jax

    from accelerate_tpu.ops import paged_kv
    from chipbench import run

    with open(os.path.join(HERE, "rehearsal.json")) as f:
        manifest = json.load(f)
    manifest["paths"] = [os.path.join(ROOT, p) for p in manifest["paths"]]
    for c in manifest["configs"]:
        c["file"] = os.path.join(ROOT, c["file"])
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name, unit, source, layer, moves, cell in NEW_METRICS:
        if name not in listed:  # the toy docqa cell lists ``engine_prefill_ms_per_ktok`` already
            listed[name] = {"name": name, "unit": unit, "better": "lower", "source": source, "layer": layer,
                            "moves": moves, "workloads": []}
            manifest["per_layer"].append(listed[name])
        listed[name]["workloads"].append(cell)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", paged_kv.FORCE_KERNEL_INTERPRET)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    cache_was = jax.config.jax_enable_compilation_cache

    def go(workload):
        capsys.readouterr()
        code = run.main(["--manifest", str(path), "--rehearsal", "--workload", workload, "--seed", "5", "--seconds", "3",
                         "--trace", "1"])
        assert code == 0
        return [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]

    yield go
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize("workload,reported,absent", [
    ("tiny-serve-chat", ["first_token_hold_p50_ms", "engine_prefill_ms_per_ktok", "engine_decode_step_ms", "tick_host_ms"],
     ["chat_prefill_device_share", "train_dispatch_ms"]),
    ("tiny-train", ["train_dispatch_ms", "train_idle_in_dispatch_share"], ["tick_host_ms"]),
])
def test_traced_rehearsal_reports_the_span_read_metrics(rehearse_under, tmp_path, workload, reported, absent):
    lines = rehearse_under(workload)
    last = lines[-1]
    assert last["correct"] is True and last["device"]["platform"] == "cpu"
    for name in reported:
        assert last["metrics"][name]["value"] > 0, name
    assert not set(absent) & set(last["metrics"]), "no module names on a CPU; another cell's metric is not this cell's"
    assert {"warm_programs", "chat_idle_share" if "serve" in workload else "train_idle_share"} <= set(last["metrics"]), \
        "the metrics that were there read what they read"
    notes = [l for l in lines if l.get("note") == "program_phases"]
    assert len(notes) == 1, "one reduction a process, shared by the readers"
    root = "engine.tick" if "serve" in workload else "train.step"
    assert notes[0]["children_cover"][root]["median"] > 0.9 and notes[0]["phase_calls"][root] >= 1
    assert set(last["breakdown"]["idle_gaps"][0][0:1]) <= set(BENCHMARK_SPANS) | {"_no_span_"}
    assert os.path.isdir(tmp_path / ".cache" / "chipbench_trace"), "the trace was written under the patched root"
