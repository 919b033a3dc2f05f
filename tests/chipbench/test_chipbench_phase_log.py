"""The readers of the program's phase log (``layers/_phase_log.py``): the longest tick of the whole
window with its sync and CPU time, and set-up's seconds of lowering and loading; each gives nothing,
and raises nothing, where the program keeps no log (the parent of the PR that brought it) or where the
log's ticks are not the generator's; a traced rehearsal of a toy serve cell and a toy train cell made
of a manifest of its own (``rehearsal-phaselog.json``) reports them with both notes."""

import json
import os
import time

import pytest

from chipbench import run

from test_chipbench_run import rehearse, result  # noqa: F401  the fixture that runs one cell in this process

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PHASELOG = os.path.join(HERE, "rehearsal-phaselog.json")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    M = json.load(f)
TICK_METRICS = ("tick_longest_ms", "tick_longest_sync_ms", "tick_longest_cpu_ms")
SETUP_METRICS = ("setup_lower_s", "setup_load_s")
SERVE_CELLS = ["mistral7b-serve-chat", "joyai-flash-serve-longchat", "jamba2-3b-serve-longanswer"]


@pytest.fixture
def own_log(monkeypatch):
    from accelerate_tpu.telemetry import trace

    log = trace.PhaseLog()
    monkeypatch.setattr(trace, "_LOG", log)
    return log


def ticks_of(walls_ms, log, sync_ms=0.0):
    """A tick a wall, made through ``phase()`` with the clock stepped by hand; what the generator would list."""
    from accelerate_tpu.telemetry.trace import phase

    listed, at = [], -0.5
    for i, wall in enumerate(walls_ms):
        with phase("engine.tick", tick=i + 1) as p:
            with phase("engine.decode.sync"):
                pass
            with phase("engine.tick.done", emitted=8):
                pass
        record = p.record
        record.wall_ns, record.cpu_ns = int(wall * 1e6), int(wall * 1e6 / 4)
        record.children["engine.decode.sync"][1] = int(sync_ms * 1e6)
        listed.append({"start": at, "end": at + wall / 1e3})
        at += wall / 1e3 + 0.001
    return listed


def observed(ticks, setup_s=12.5, closes=51.0):
    return {"ticks": ticks, "traced": (closes - 4.0, closes), "end_to_end": {"setup_s": setup_s}}


@pytest.mark.parametrize("reader,want", [("tick_longest_ms", 480.0), ("tick_longest_sync_ms", 30.0), ("tick_longest_cpu_ms", 120.0)])
def test_tick_readers_take_the_windows_longest_tick(reader, want, own_log, capsys):
    # the first tick began before the window opened (a warm round's) and the last after it closed (the drain's)
    ticks = ticks_of([900.0, 60.0, 480.0, 70.0, 2000.0], own_log, sync_ms=30.0)
    ticks[-1] = {"start": 51.2, "end": 53.2}
    module = run.load(M, "layers", reader)
    seen = observed(ticks)
    assert module.read(seen) == pytest.approx(want) and module.read(seen) == pytest.approx(want)
    (note,) = [json.loads(l) for l in capsys.readouterr().out.splitlines() if '"slow_ticks"' in l]  # once a run
    assert note["ticks_in_window"] == 3 and note["ticks_after_warm_up"] == 5 and note["tick_ms_median"] == 70.0 and note["pairs_apart"] == 0
    assert [t["wall_ms"] for t in note["longest"]] == [480.0, 70.0, 60.0] and note["flagged_slow"] == 0
    assert note["longest"][0]["children_ms"] == {"engine.decode.sync": 30.0} and note["longest"][0]["done"] == {"emitted": 8}
    assert {"cpu_ms", "gap_ms", "counts", "longest_child", "programs"} <= set(note["longest"][0])


@pytest.mark.parametrize("reader", TICK_METRICS + SETUP_METRICS)
def test_readers_give_nothing_where_there_is_nothing_to_read(reader, own_log, monkeypatch, capsys):
    from accelerate_tpu.telemetry import trace

    module = run.load(M, "layers", reader)
    assert module.read(observed([], setup_s=2.0)) is None, "a train cell lists no tick; no program span before the window"
    ticks = ticks_of([60.0, 80.0, 70.0], own_log)
    for t in ticks[1:]:
        t["end"] += 0.005  # the generator's step() and the log's tick disagree by 5 ms, twice: not the same ticks
    if reader in TICK_METRICS:
        assert module.read(observed(ticks, setup_s=3.0)) is None
        assert module.read(observed(ticks + ticks, setup_s=4.0)) is None, "more steps listed than the log holds"
    monkeypatch.delattr(trace, "phase_log")  # a program from before the log, as the parent commit is
    assert module.read(observed(ticks[:1], setup_s=5.0)) is None
    assert "note" not in capsys.readouterr().out


def test_setup_readers_sum_what_was_before_the_window(own_log, monkeypatch, capsys):
    from accelerate_tpu.telemetry.trace import phase

    monkeypatch.setattr(run, "_T_START", time.perf_counter())
    with phase("engine.init"):
        with phase("program.lower", program="toy_tick"):
            time.sleep(0.02)
        with phase("program.load", program="toy_tick", source="disk"):
            time.sleep(0.03)
    with phase("engine.tick", tick=1):
        with phase("engine.prefill.dispatch"):
            with phase("program.load", program="prefill_b8", source="compiled"):
                time.sleep(0.01)
    own_log._on_jax_duration("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.004, fun_name="jit(draw)")
    own_log._on_jax_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.001)
    own_log._on_jax_duration("/jax/core/compile/backend_compile_duration", 0.002, fun_name="jit(draw)")
    setup_s = time.perf_counter() - run._T_START
    time.sleep(0.002)
    with phase("program.load", program="reference", source="compiled"):  # after the window opened: the reference's
        time.sleep(0.01)
    seen = observed([], setup_s=setup_s)
    lower, load = run.load(M, "layers", "setup_lower_s").read(seen), run.load(M, "layers", "setup_load_s").read(seen)
    assert 0.024 <= lower < 0.04 and 0.042 <= load < 0.06
    (note,) = [json.loads(l) for l in capsys.readouterr().out.splitlines() if '"setup_programs"' in l]
    assert note["lower_s"] == lower and note["load_s"] == load and note["programs"] == 3 and "reference" not in note["by_program"]
    assert set(note["load_s_by_source"]) == {"disk", "compiled"} and note["load_s_by_source"]["disk"] >= 0.032
    assert note["by_program"]["toy_tick"]["source"] == "disk" and note["by_program"]["jit(draw)"] == {"lower_s": 0.004, "load_s": 0.002, "source": "disk"}
    assert note["engine_init_s"] >= 0.05 and note["programs_in_engine_init_s"] == pytest.approx(0.05, abs=0.01)
    assert note["roots_before_window"] == 1 and 0.01 <= note["programs_in_roots_s"] < 0.03


# -- the toy cells, end to end on the CPU

def test_traced_rehearsal_of_a_serve_cell_reports_the_five_and_both_notes(rehearse):  # noqa: F811
    lines = rehearse("tiny-serve-chat", "--trace", "1", manifest=PHASELOG, seconds="3")
    last = result(lines)
    assert last["correct"] is True
    for name in TICK_METRICS + SETUP_METRICS:
        assert last["metrics"][name]["value"] > 0, name
    assert last["metrics"]["tick_longest_sync_ms"]["value"] < last["metrics"]["tick_longest_ms"]["value"]
    assert last["metrics"]["tick_longest_cpu_ms"]["value"] < last["metrics"]["tick_longest_ms"]["value"] * 1.5
    assert {"warm_programs", "tick_host_ms", "chat_idle_share"} <= set(last["metrics"]), "the readers that were there read on"
    (ticks,) = [l for l in lines if l.get("note") == "slow_ticks"]
    said = next(l for l in lines if l.get("note") == "requests")
    assert 0 < ticks["ticks_in_window"] <= ticks["ticks_after_warm_up"] == said["ticks"]
    assert ticks["longest"][0]["wall_ms"] == pytest.approx(last["metrics"]["tick_longest_ms"]["value"], abs=1e-3)
    assert ticks["flagged_slow"] == 0 and ticks["longest"][0]["done"]["emitted"] >= 0
    (setup,) = [l for l in lines if l.get("note") == "setup_programs"]
    assert setup["lower_s"] == last["metrics"]["setup_lower_s"]["value"] and setup["load_s"] == last["metrics"]["setup_load_s"]["value"]
    assert {"paged_decode_tick", "prefill_b8"} <= set(setup["by_program"]) and setup["engine_init_s"] > 0
    assert setup["lower_s"] + setup["load_s"] < setup["setup_s"], "a part of set-up, on the same clock"


def test_traced_rehearsal_of_a_train_cell_reports_set_up_alone(rehearse):  # noqa: F811
    lines = rehearse("tiny-train", "--trace", "1", manifest=PHASELOG, seconds="3")
    last = result(lines)
    assert last["correct"] is True
    for name in SETUP_METRICS:
        assert last["metrics"][name]["value"] > 0, name
    assert not set(TICK_METRICS) & set(last["metrics"]) and "train_dispatch_ms" in last["metrics"]
    (setup,) = [l for l in lines if l.get("note") == "setup_programs"]
    assert setup["roots_before_window"] >= 5, "the checked steps and the two timed together are train.step roots"
    assert not [l for l in lines if l.get("note") == "slow_ticks"]


# -- the manifests

def test_toy_manifest_states_the_metrics_as_the_real_one_does():
    with open(PHASELOG) as f:
        stated = json.load(f)
    real = {m["name"]: m for m in M["per_layer"]}
    for m in stated["per_layer"]:
        assert {k: v for k, v in m.items() if k != "workloads"} == {k: v for k, v in real[m["name"]].items() if k != "workloads"}
    assert set(TICK_METRICS + SETUP_METRICS) <= {m["name"] for m in stated["per_layer"]}
    assert [w["name"] for w in stated["workloads"]] == ["tiny-serve-chat", "tiny-train"]


def test_manifest_gained_the_five_metrics_at_its_end():
    names = [m["name"] for m in M["per_layer"]]
    at = names.index("tick_longest_ms")
    assert at >= 22 and names[at:at + 5] == list(TICK_METRICS + SETUP_METRICS)
    for m in M["per_layer"][at:at + 3]:
        assert m == {"name": m["name"], "unit": "ms", "better": "lower", "source": "program_span", "layer": "engine host loop",
                     "moves": "tpot_p90_ms", "workloads": m["workloads"]} and m["workloads"][:3] == SERVE_CELLS
    for m in M["per_layer"][at + 3:at + 5]:  # every cell has a set-up: no list, so a later cell reports them too
        assert m == {"name": m["name"], "unit": "s", "better": "lower", "source": "program_span", "layer": "compile caches",
                     "moves": "setup_s"}
    assert {m["layer"] for m in M["per_layer"][:at]} >= {"engine host loop", "compile caches"}, "layers the manifest had"
