"""The phase log (``telemetry.trace.PhaseLog``): every phase stamped on the host's clock whether or
not a profiler runs. One record a tick and a train step, tiled by its children, with the ``.done``
marker's counts; bounded rings that keep their order; a record joins its traced event by ``mono_ns``;
a slow tick says so once (event, warning line, ``ServingMetrics``); set-up's programs are spans."""

import logging
import time

import numpy as np
import pytest

from accelerate_tpu.telemetry import trace
from accelerate_tpu.telemetry.trace import PhaseLog, phase, phase_log
from chipbench import program_trace
from chipbench.generators import open_loop_rounds, train_steps

BENCHMARK_SPANS = ("window",) + open_loop_rounds.SPANS + train_steps.SPANS
PROMPTS = (5, 12, 7, 3, 14)
NEW_TOKENS = 9


@pytest.fixture(scope="module")
def tiny_llama():
    from accelerate_tpu.models import LlamaConfig, create_llama_model

    return create_llama_model(LlamaConfig.tiny(), seq_len=64)


@pytest.fixture
def fresh_log(monkeypatch):
    """A log of this test's own behind ``phase()`` and ``phase_log()``."""
    log = PhaseLog()
    monkeypatch.setattr(trace, "_LOG", log)
    return log


def toy_engine(model, **kwargs):
    from accelerate_tpu.serving import ServingEngine

    return ServingEngine(model, num_slots=2, prompt_buckets=(8, 16), max_len=64, paged_block_size=8, tick_block=4,
                         temperature=0.7, seed=3, **kwargs)


def offer(engine, prompts=PROMPTS):
    rng = np.random.default_rng(1)
    return [engine.submit(rng.integers(5, 200, size=n).astype(np.int32), NEW_TOKENS) for n in prompts]


def profiled(tmp_path, body):
    import jax

    from chipbench import trace as bench_trace

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return program_trace.reduce(program_trace.load(bench_trace.newest_xplane(str(tmp_path)), BENCHMARK_SPANS))


# -- one record a root

def test_children_tile_each_tick_and_the_done_counts_land_on_it_once(tiny_llama, fresh_log):
    engine = toy_engine(tiny_llama)
    offer(engine)
    engine.run()
    ticks = fresh_log.roots("engine.tick")
    assert len(ticks) == engine._tick == len(fresh_log.roots()) and [r.counts["tick"] for r in ticks] == list(range(1, len(ticks) + 1))
    assert [r.seq for r in ticks] == sorted(r.seq for r in ticks)
    for r in ticks:
        assert r.name == "engine.tick" and r.t0_ns == r.counts["mono_ns"] and 0 < r.cpu_ns and r.wall_ns > 0
        assert "engine.tick.done" not in r.children and set(r.children) <= set(trace.PHASES)
        assert {"admitted", "prefill_tokens", "emitted", "retired", "pool_blocked", "free_blocks", "queue_len"} <= set(r.done)
        assert r.children["engine.schedule"][0] >= 1 and r.children["engine.expire"][0] == 1
        assert r.children.get("engine.admit", [0])[0] == r.done["admitted"]
        assert sum(ns for _, ns in r.children.values()) <= r.wall_ns
        fields = r.fields()
        assert fields["longest_child"] in r.children and "mono_ns" not in fields["counts"] and fields["done"] == r.done
        assert fields["wall_ms"] == pytest.approx(r.wall_ns / 1e6, abs=1e-3) and r.child_ms(".sync") <= fields["wall_ms"]
    covered = sum(ns for r in ticks for _, ns in r.children.values())
    assert covered / sum(r.wall_ns for r in ticks) >= 0.95, "the children tile their ticks"
    assert sum(r.done["emitted"] for r in ticks) == len(PROMPTS) * NEW_TOKENS, "each marker's counts once"
    assert all(a.t0_ns + a.wall_ns <= b.t0_ns and b.gap_ns == b.t0_ns - (a.t0_ns + a.wall_ns) for a, b in zip(ticks, ticks[1:]))
    assert ticks[0].gap_ns == 0, "no root before the first on this thread"
    # the ticks that compiled say so; what the engine counted of its host loop is the log's
    assert ticks[0].programs > 0 and ticks[-1].programs == 0
    snap = engine.metrics.snapshot()
    assert snap["ticks"] == len(ticks) and snap["slow_ticks"] == 0
    assert snap["tick_ms_max"] == pytest.approx(max(r.wall_ns for r in ticks) / 1e6)
    # outside a root: the submits and the engine's construction, whole
    submits = fresh_log.spans("engine.submit")
    assert [s["uid"] for s in submits] == list(range(len(PROMPTS))) and all(s["wall_ns"] > 0 for s in submits)
    (init,) = fresh_log.spans("engine.init")
    assert init["t0_ns"] + init["wall_ns"] <= submits[0]["t0_ns"]


def test_a_tick_reads_its_first_tokens_behind_the_decode_dispatch(tiny_llama, fresh_log, monkeypatch):
    """No profiler: the annotation a session would get records the spans' order instead. In a tick that
    admits and decodes the programs are queued back to back (prefill, paste, prefill, paste, decode tick)
    and only then does the host wait for each admission's first token, in admission order."""
    seen = []

    class Recorded:
        def __init__(self, name, **counts):
            self.name, self.counts = name, counts

        def __enter__(self):
            seen.append(("open", self.name, self.counts.get("uid")))

        def __exit__(self, *exc):
            seen.append(("close", self.name, self.counts.get("uid")))

    monkeypatch.setattr(trace, "_annotation", Recorded)
    engine = toy_engine(tiny_llama)
    uids = offer(engine, PROMPTS[:3])  # two slots: two admissions in the first tick, the third later
    engine.step()
    (tick,) = fresh_log.roots("engine.tick")
    assert tick.done["admitted"] == tick.done["first_tokens_deferred"] == 2
    names = [(what, name) for what, name, _ in seen]
    dispatched = names.index(("close", "engine.decode.dispatch"))
    assert dispatched < names.index(("open", "engine.prefill.sync")) < names.index(("open", "engine.decode.sync"))
    assert max(i for i, n in enumerate(names) if n == ("close", "engine.prefill.paste")) < names.index(("open", "engine.decode.dispatch"))
    assert [uid for what, name, uid in seen if (what, name) == ("open", "engine.prefill.sync")] == uids[:2]
    order = list(tick.children)  # a record keeps its children in the order they first closed
    assert order.index("engine.prefill.paste") < order.index("engine.decode.dispatch") < order.index("engine.prefill.sync") \
        < order.index("engine.decode.sync") < order.index("engine.decode.walk")
    assert tick.children["engine.prefill.sync"][0] == 2 and tick.children["engine.decode.dispatch"][0] == 1
    for uid in uids[:2]:  # a request's spans keep their order
        assert [name for what, name, u in seen if what == "open" and u == uid] == [
            "engine.submit", "engine.admit", "engine.prefill.dispatch", "engine.prefill.paste", "engine.prefill.sync"]
    engine.run()
    ticks = fresh_log.roots("engine.tick")
    assert sum(r.done["first_tokens_deferred"] for r in ticks) == engine.metrics.first_tokens_deferred == 3
    assert sum(r.children.get("engine.prefill.sync", [0])[0] for r in ticks) == 3
    assert list(trace.PHASES).index("engine.decode.dispatch") < list(trace.PHASES).index("engine.prefill.sync")


def test_a_train_step_is_a_root_too(fresh_log):
    with phase("train.step", step=4, do_sync=1):
        with phase("train.step.args"):
            pass
        with phase("train.step.call"):
            with phase("program.lower", program="toy"):  # deeper than a child: kept whole, and counted on the root
                pass
        with phase("train.step.swap"):
            pass
    (r,) = fresh_log.roots("train.step")
    assert list(r.children) == ["train.step.args", "train.step.call", "train.step.swap"] and r.done == {} and r.programs == 1
    assert [s["name"] for s in fresh_log.spans()] == ["program.lower"] and fresh_log.roots("engine.tick") == []


def test_rings_wrap_and_keep_their_order(monkeypatch):
    monkeypatch.setattr(trace, "ROOT_CAPACITY", 8)
    monkeypatch.setattr(trace, "SPAN_CAPACITY", 4)
    log = PhaseLog()
    monkeypatch.setattr(trace, "_LOG", log)
    for i in range(20):
        with phase("engine.submit", uid=i):
            pass
        with phase("engine.tick", tick=i):
            with phase("engine.schedule"):
                pass
    assert [r.counts["tick"] for r in log.roots()] == list(range(12, 20))
    assert [r.counts["tick"] for r in log.roots("engine.tick", n=3)] == [17, 18, 19] and log.roots("train.step") == []
    assert [s["uid"] for s in log.spans("engine.")] == [16, 17, 18, 19]
    assert all(r.children == {"engine.schedule": [1, r.children["engine.schedule"][1]]} for r in log.roots())


def test_two_threads_keep_their_own_roots(fresh_log):
    import threading

    both = threading.Barrier(2)  # alive at once, so their idents differ

    def tick(n):
        both.wait()
        for i in range(50):
            with phase("engine.tick", tick=n * 100 + i):
                with phase("engine.schedule"):
                    pass
        both.wait()

    threads = [threading.Thread(target=tick, args=(n,)) for n in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    roots = fresh_log.roots()
    assert len(roots) == 100 and len({r.thread for r in roots}) == 2
    for ident in {r.thread for r in roots}:
        mine = [r.counts["tick"] for r in roots if r.thread == ident]
        assert mine == sorted(mine) and len(mine) == 50
    assert all(r.children["engine.schedule"][0] == 1 for r in roots)


# -- one clock, one stamp

def test_every_traced_tick_joins_a_record_by_mono_ns(tiny_llama, fresh_log, tmp_path):
    engine = toy_engine(tiny_llama)

    def body():
        offer(engine)
        engine.run()

    phases = profiled(tmp_path, body)
    spans = phases["spans"]
    traced = [spans[i] for i in program_trace.named(phases, "engine.tick")]
    records = {r.t0_ns: r for r in fresh_log.roots("engine.tick")}
    assert len(traced) == len(records) == engine._tick
    for t in traced:
        record = records[t["stats"]["mono_ns"]]
        assert abs((t["end"] - t["start"]) * 1e3 - record.wall_ns / 1e6) < 0.2
        assert record.counts["tick"] == t["stats"]["tick"]
        done = next(spans[j] for j in t["children"] if spans[j]["name"] == "engine.tick.done")
        assert done["stats"] == record.done, "the profiler's trace and the log hold the same counts"
        calls = {}
        for j in t["children"]:
            calls[spans[j]["name"]] = calls.get(spans[j]["name"], 0) + 1
        calls.pop("engine.tick.done")
        assert calls == {name: n for name, (n, _) in record.children.items()}, "the same children, as often"


def test_every_traced_train_step_joins_a_record_by_mono_ns(fresh_log, tmp_path):
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.test_utils.training import RegressionDataset, RegressionModel
    from accelerate_tpu.utils import CompileKwargs

    acc = Accelerator(kwargs_handlers=[CompileKwargs(persistent_xla_cache=False, executable_store=False)])
    model = acc.prepare_model(RegressionModel())
    acc.prepare_optimizer(optax.sgd(0.1))
    batch = next(iter(acc.prepare_data_loader(RegressionDataset(length=16, seed=0), batch_size=16)))
    step = acc.build_train_step(lambda p, b: ((model.apply_fn(p, b["x"]) - b["y"]) ** 2).mean())
    float(step(batch))
    (first,) = fresh_log.roots("train.step")
    assert first.programs >= 2 and not first.slow, "the first step lowered and compiled its program"
    lowered = [s for s in fresh_log.spans("program.lower") if s["program"] == "train_step"]
    loaded = [s for s in fresh_log.spans("program.load") if s["program"] == "train_step"]
    assert len(lowered) == len(loaded) == 1 and loaded[0]["source"] == "compiled"
    assert first.t0_ns <= lowered[0]["t0_ns"] and loaded[0]["t0_ns"] + loaded[0]["wall_ns"] <= first.t0_ns + first.wall_ns
    phases = profiled(tmp_path, lambda: [float(step(batch)) for _ in range(3)])
    spans = phases["spans"]
    records = {r.t0_ns: r for r in fresh_log.roots("train.step")}
    assert len(records) == 4
    for i in program_trace.named(phases, "train.step"):
        record = records[spans[i]["stats"]["mono_ns"]]
        assert abs((spans[i]["end"] - spans[i]["start"]) * 1e3 - record.wall_ns / 1e6) < 0.2
        assert list(record.children) == ["train.step.args", "train.step.call", "train.step.swap"] and record.programs == 0
    assert len(fresh_log.spans("program.lower")) == len(lowered), "no later step lowered anything"


# -- a slow tick says so, once

def test_a_slow_tick_says_so_once_and_a_sound_run_never(tiny_llama, fresh_log, monkeypatch, caplog):
    from accelerate_tpu.telemetry.eventlog import EventLog
    from accelerate_tpu.telemetry.flightrec import FlightRecorder

    log, recorder = EventLog(None), FlightRecorder(64, name="r0")
    log.add_tap(recorder.record)
    engine = toy_engine(tiny_llama, telemetry_log=log)
    with caplog.at_level(logging.WARNING, logger="accelerate_tpu.serving"):
        offer(engine, PROMPTS * 2)  # enough ticks for a median
        engine.run()
        sound = engine._tick
        assert sound >= trace.SLOW_MEDIAN_AT_LEAST + 2 and engine.metrics.slow_ticks == 0
        assert not [e for e in recorder.tail() if e["name"] == "tick_slow"] and not caplog.records, "a sound run says nothing"
        offer(engine, (5, 6))
        engine.step()
        expire = engine._expire_window_blocks
        monkeypatch.setattr(engine, "_expire_window_blocks", lambda: (time.sleep(trace.SLOW_ROOT_OVER_NS / 1e9 + 0.1), expire()))
        engine.step()  # this one sleeps through its expire phase
        monkeypatch.setattr(engine, "_expire_window_blocks", expire)
        engine.run()
    events = [e for e in recorder.tail() if e["name"] == "tick_slow"]
    assert len(events) == 1 and engine.metrics.slow_ticks == 1 and engine.metrics.ticks == engine._tick
    (event,) = events
    assert event["kind"] == "event" and event["severity"] == "warning" and event["phase"] == "engine.tick"
    assert event["counts"]["tick"] == sound + 2 and event["longest_child"] == "engine.expire"
    assert event["children_ms"]["engine.expire"] > trace.SLOW_ROOT_OVER_NS / 1e6 and event["wall_ms"] >= event["children_ms"]["engine.expire"]
    assert event["cpu_ms"] < event["wall_ms"] / 4, "it slept: wall far above CPU"
    assert event["gap_ms"] >= 0 and event["done"]["emitted"] > 0 and event["programs"] == 0
    assert engine.metrics.tick_ms_max >= event["wall_ms"] - 1e-3
    slow = [r for r in fresh_log.roots("engine.tick") if r.slow]
    assert len(slow) == 1 and slow[0].counts["tick"] == sound + 2
    lines = [r.getMessage() for r in caplog.records if r.levelno == logging.WARNING]
    assert len(lines) == 1 and lines[0].startswith(f"tick_slow: tick {sound + 2} took ") and "engine.expire" in lines[0]
    text = engine.metrics.prometheus_text()
    assert "slow_ticks_total 1" in text and f"ticks_total {engine._tick}" in text and "tick_ms_max " in text


def test_a_tick_that_compiled_is_not_judged(fresh_log, monkeypatch):
    monkeypatch.setattr(trace, "SLOW_ROOT_OVER_NS", 20_000_000)

    def tick(i, seconds=0.0, program=False):
        with phase("engine.tick", tick=i) as p:
            with phase("engine.decode.dispatch"):
                if program:
                    with phase("program.load", program="toy", source="compiled"):
                        time.sleep(seconds)
                else:
                    time.sleep(seconds)
        return p.record

    assert not tick(0, 0.05).slow, "no median yet"
    for i in range(1, trace.SLOW_MEDIAN_AT_LEAST + 1):
        assert not tick(i).slow
    assert not tick(20, 0.05, program=True).slow and tick(21, 0.05).slow and not tick(22).slow


def test_merged_metrics_sum_the_ticks_and_keep_the_longest():
    from accelerate_tpu.telemetry.serving_metrics import ServingMetrics

    a, b = ServingMetrics(None), ServingMetrics(None)
    a.on_tick(12.0)
    a.on_tick(900.0, slow=True)
    b.on_tick(40.0)
    fleet = ServingMetrics.merge([a, b])
    assert (fleet.ticks, fleet.slow_ticks, fleet.tick_ms_max) == (3, 1, 900.0)
    assert {"ticks": 3, "slow_ticks": 1, "tick_ms_max": 900.0}.items() <= fleet.snapshot().items()


# -- set-up's programs are spans of the same log

def test_program_spans_name_where_each_executable_came_from(tmp_path, no_persistent_compile_cache):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.aot import ExecutableStore, ProgramCache

    def toy(x, w):
        return jnp.tanh(x @ w).sum()

    avals = (jax.ShapeDtypeStruct((8, 16), jnp.float32), jax.ShapeDtypeStruct((16, 16), jnp.float32))
    log, began = phase_log(), time.monotonic_ns()
    cache = ProgramCache(store=ExecutableStore(str(tmp_path)))
    cache.compile(toy, *avals, name="toy_program")
    cache.compile(toy, *avals, name="toy_program")
    ProgramCache(store=ExecutableStore(str(tmp_path))).compile(toy, *avals, name="toy_program")
    mine = [s for s in log.spans("program.") if s["t0_ns"] >= began and s.get("program") == "toy_program"]
    assert [s["name"] for s in mine] == ["program.lower", "program.load"] * 3
    assert [s["source"] for s in mine if s["name"] == "program.load"] == ["compiled", "memory", "disk"]
    compiled, memory, disk = (s for s in mine if s["name"] == "program.load")
    assert compiled["wall_ns"] > memory["wall_ns"] and "temp_bytes" in compiled and "temp_bytes" in disk and "temp_bytes" not in memory
    assert all(a["t0_ns"] + a["wall_ns"] <= b["t0_ns"] for a, b in zip(mine, mine[1:]))
    # jax's own events inside those spans are theirs: none is in the log a second time
    own = [s for s in log.spans("program.jax") if s["t0_ns"] >= began and s["program"] in ("toy", "jit_toy", "toy_program")]
    assert own == []


def test_what_jax_compiles_outside_the_caches_is_in_the_log(no_persistent_compile_cache):
    import jax
    import jax.numpy as jnp

    log, began = phase_log(), time.monotonic_ns()
    log.listen()  # as ``import accelerate_tpu`` has already: once a process

    @jax.jit
    def outside_any_cache(x):
        return jnp.cumsum(jax.jit(lambda y: y * 3.0)(x))

    outside_any_cache(jnp.ones((7,))).block_until_ready()
    outside_any_cache(jnp.ones((7,))).block_until_ready()  # the second call compiles nothing
    mine = [s for s in log.spans("program.jax") if s["t0_ns"] >= began - 1_000_000]
    named = {s["stage"]: s for s in mine if s["program"] in ("outside_any_cache", "jit(outside_any_cache)")}
    assert named["compile"]["source"] == "compiled" and named["compile"]["wall_ns"] > 0
    assert {"lower", "compile"} <= set(named) or {"trace", "compile"} <= set(named)
    assert len([s for s in mine if s["stage"] == "compile" and "outside_any_cache" in s["program"]]) == 1
    total = sum(s["wall_ns"] for s in mine)
    assert 0 < total <= time.monotonic_ns() - began, "nested events are counted once"


def test_phase_costs_microseconds_with_no_profiler(fresh_log):
    """Two clock reads and a slot write a phase: far under the 0.15 ms a tick that ``tick_host_ms`` may
    rise by (the chip's number is PERF.md's; this is the host's, with room for a loaded test machine)."""
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        with phase("engine.tick", tick=i):
            for _ in range(20):
                with phase("engine.schedule"):
                    pass
            with phase("engine.tick.done", emitted=1):
                pass
    per_tick_us = (time.perf_counter() - t0) / n * 1e6
    assert per_tick_us < 1000 and len(fresh_log.roots()) == n
