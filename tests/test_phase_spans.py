"""The program's phase spans (``telemetry.trace.phase`` / ``PHASES``): they change nothing the
engine or the train step computes, tile a tick, link a request by ``uid``, carry the monotonic
clock's reading, and the jitted programs bear the names ``ProgramCache`` logs."""

import re

import numpy as np
import pytest

from accelerate_tpu.telemetry.trace import PHASES, SEGMENTS, Tracer, phase
from chipbench import program_trace
from chipbench.generators import open_loop_rounds, train_steps

BENCHMARK_SPANS = ("window",) + open_loop_rounds.SPANS + train_steps.SPANS
PROMPTS = (5, 12, 7, 3, 14)


@pytest.fixture(scope="module")
def tiny_llama():
    from accelerate_tpu.models import LlamaConfig, create_llama_model

    return create_llama_model(LlamaConfig.tiny(), seq_len=64)


def serve(model, tmp_path=None, tracer=None):
    """Five requests through a two-slot paged engine; under a profiler session when ``tmp_path``
    is given. Returns what the engine produced and the programs its cache was asked for."""
    import jax

    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.telemetry.eventlog import EventLog

    log, events = EventLog(None), []
    log.add_tap(events.append)
    engine = ServingEngine(model, num_slots=2, prompt_buckets=(8, 16), max_len=64, paged_block_size=8, tick_block=4,
                           temperature=0.7, seed=3, telemetry_log=log, tracer=tracer)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(5, 200, size=n).astype(np.int32) for n in PROMPTS]
    if tmp_path is not None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        uids = [engine.submit(p, 9) for p in prompts]
        engine.run()
    finally:
        if tmp_path is not None:
            jax.profiler.stop_trace()
    cache = engine.program_cache
    return {
        "tokens": [engine.done[u].tolist() for u in uids], "logprobs": [engine.logprobs(u).tolist() for u in uids],
        "counters": (cache.hits, cache.misses, cache.deserialized, cache.rejected),
        "programs": sorted({e["program"] for e in events if e["name"].startswith("compile_cache_")}),
        "modules": sorted({re.search(r"HloModule (\S+?)[,\s]", c.as_text()).group(1) for c in cache._mem.values()}),
        "uids": uids, "ticks": engine._tick,
    }


def phases_under(tmp_path):
    from chipbench import trace

    return program_trace.reduce(program_trace.load(trace.newest_xplane(str(tmp_path)), BENCHMARK_SPANS))


@pytest.fixture(scope="module")
def served(tiny_llama, tmp_path_factory):
    where = tmp_path_factory.mktemp("profile")
    plain = serve(tiny_llama)
    tracer = Tracer()
    profiled = serve(tiny_llama, where, tracer=tracer)
    return plain, profiled, phases_under(where), tracer


def test_phase_names_keep_clear_of_segments_and_of_the_benchmark():
    assert all(name.startswith(("engine.", "train.")) for name in PHASES)
    assert not set(PHASES) & (set(BENCHMARK_SPANS) | set(SEGMENTS))
    program_trace.check_names(BENCHMARK_SPANS)
    with phase("engine.tick", tick=1):  # no session: nothing records, nothing raises
        pass


def test_a_profiler_session_changes_nothing_the_engine_produces(served):
    plain, profiled, _, _ = served
    for what in ("tokens", "logprobs", "counters", "programs", "ticks"):
        assert plain[what] == profiled[what], what


def test_modules_bear_the_names_the_program_cache_logs(served):
    plain = served[0]
    assert plain["modules"] == ["jit_" + name for name in plain["programs"]]
    assert {"paged_decode_tick", "prefill_b8", "prefill_b16", "paste_row"} <= set(plain["programs"])


def test_children_tile_each_tick_within_the_span_budget(served):
    _, profiled, phases, _ = served
    spans = phases["spans"]
    ticks = program_trace.named(phases, "engine.tick")
    assert len(ticks) == profiled["ticks"] and [spans[i]["stats"]["tick"] for i in ticks] == list(range(1, len(ticks) + 1))
    covered = total = 0.0
    for i in ticks:
        tick, children = spans[i], [spans[j] for j in spans[i]["children"]]
        assert all(a["end"] <= b["start"] + 1e-9 for a, b in zip(children, children[1:])), "children do not overlap"
        done = children[-1]
        assert done["name"] == "engine.tick.done" and len(children) <= 12 + 4 * done["stats"]["admitted"]
        assert done["stats"]["prefill_tokens"] == sum(c["stats"]["tokens"] for c in children if c["name"] == "engine.prefill.dispatch")
        covered, total = covered + sum(c["end"] - c["start"] for c in children), total + tick["end"] - tick["start"]
    assert covered / total >= 0.95
    emitted = sum(spans[j]["stats"]["emitted"] for j in program_trace.named(phases, "engine.tick.done"))
    assert emitted == len(PROMPTS) * 9


def test_uid_links_submit_admit_and_first_token(served):
    _, profiled, phases, _ = served
    by_uid = {}
    for s in phases["spans"]:
        if "uid" in s["stats"]:
            by_uid.setdefault(s["stats"]["uid"], []).append(s["name"])
    assert sorted(by_uid) == profiled["uids"]
    for names in by_uid.values():
        assert names == ["engine.submit", "engine.admit", "engine.prefill.dispatch", "engine.prefill.paste", "engine.prefill.sync"]


def test_traced_ticks_show_the_decode_dispatch_ahead_of_the_first_token_syncs(served):
    _, profiled, phases, _ = served
    spans = phases["spans"]
    deferred = 0
    for i in program_trace.named(phases, "engine.tick"):
        children = [spans[j] for j in spans[i]["children"]]
        done = children[-1]["stats"]
        syncs = [c for c in children if c["name"] == "engine.prefill.sync"]
        dispatch = [c for c in children if c["name"] == "engine.decode.dispatch"]
        assert done["first_tokens_deferred"] == done["admitted"] == len(syncs), "every admission here is a fresh one"
        if syncs:
            assert len(dispatch) == 1 and dispatch[0]["end"] <= syncs[0]["start"] + 1e-9
            after = [c["name"] for c in children if c["start"] >= syncs[-1]["end"] - 1e-9]
            assert after[:2] == ["engine.decode.sync", "engine.decode.walk"]
        deferred += done["first_tokens_deferred"]
    assert deferred == len(PROMPTS)
    # the benchmark's readers still pair each dispatch with the sync behind it
    assert program_trace.decode_step_ms(phases) > 0 and program_trace.prefill_ms_per_ktok(phases) > 0
    assert program_trace.first_token_hold_ms(phases) >= 0


def test_mono_ns_places_a_tracer_window_inside_the_ticks_that_decoded_it(served):
    _, _, phases, tracer = served
    spans = phases["spans"]
    ticks = [spans[i] for i in program_trace.named(phases, "engine.tick")]
    # one offset for the whole trace: the monotonic clock less the profiler's
    offsets = [t["stats"]["mono_ns"] * 1e-9 - t["start"] for t in ticks]
    assert max(offsets) - min(offsets) < 2e-3
    offset = offsets[0]
    decodes = [(tr["t0"] + sp["t0_ms"] * 1e-3, sp["dur_ms"] * 1e-3) for tr in tracer.completed() for sp in tr["spans"]
               if sp["name"] == "decode"]
    assert len(decodes) == len(PROMPTS)
    walks = [spans[i] for i in program_trace.named(phases, "engine.decode.walk")]
    for t0, dur in decodes:
        end = t0 + dur - offset  # a decode window closes in the walk of the last tick that decoded it
        assert any(w["start"] - 1e-3 <= end <= w["end"] + 1e-3 for w in walks)
        assert ticks[0]["start"] - 1e-3 <= t0 - offset and end <= ticks[-1]["end"] + 1e-3


def test_fused_prefill_compute_ends_at_the_first_token_sync(served):
    _, _, phases, tracer = served
    spans = phases["spans"]
    syncs = {spans[i]["stats"]["uid"]: spans[i] for i in program_trace.named(phases, "engine.prefill.sync")}
    dispatches = {spans[i]["stats"]["uid"]: spans[i] for i in program_trace.named(phases, "engine.prefill.dispatch")}
    for tr in tracer.completed():
        prefill = [sp for sp in tr["spans"] if sp["name"] == "prefill"]
        assert len(prefill) == 1 and "dispatch_ms" not in prefill[0]
        uid = tr["meta"]["uid"]
        whole = (syncs[uid]["end"] - dispatches[uid]["start"]) * 1e3
        assert prefill[0]["compute_ms"] == pytest.approx(whole, abs=1.0)
        assert prefill[0]["compute_ms"] >= (dispatches[uid]["end"] - dispatches[uid]["start"]) * 1e3


def test_chunk_windows_report_the_enqueue_time_under_its_own_name(tiny_llama):
    from accelerate_tpu.serving import ServingEngine

    tracer = Tracer()
    engine = ServingEngine(tiny_llama, num_slots=1, prompt_buckets=(8,), max_len=64, tick_block=2, tracer=tracer)
    uid = engine.submit(np.arange(5, 25, dtype=np.int32), 3)  # 20 tokens: three windows of 8
    engine.run()
    (tr,) = tracer.completed()
    prefill = [sp for sp in tr["spans"] if sp["name"] == "prefill"]
    assert tr["meta"]["uid"] == uid and len(prefill) == 3
    assert all("dispatch_ms" in sp and "compute_ms" not in sp for sp in prefill)


def test_train_step_phases_and_its_module_name(tmp_path):
    import jax
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.test_utils.training import RegressionDataset, RegressionModel
    from accelerate_tpu.utils import CompileKwargs

    acc = Accelerator(kwargs_handlers=[CompileKwargs(persistent_xla_cache=False, executable_store=False)])
    model = acc.prepare_model(RegressionModel())
    acc.prepare_optimizer(optax.sgd(0.1))
    batch = next(iter(acc.prepare_data_loader(RegressionDataset(length=16, seed=0), batch_size=16)))
    step = acc.build_train_step(lambda p, b: ((model.apply_fn(p, b["x"]) - b["y"]) ** 2).mean())
    first = float(step(batch))
    modules = [re.search(r"HloModule (\S+?)[,\s]", c.as_text()).group(1) for c in acc.program_cache._mem.values()]
    assert modules == ["jit_train_step"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        losses = [float(step(batch)) for _ in range(3)]
    finally:
        jax.profiler.stop_trace()
    assert losses[0] < first and losses == sorted(losses, reverse=True)
    phases = phases_under(tmp_path)
    spans = phases["spans"]
    steps = program_trace.named(phases, "train.step")
    assert [spans[i]["stats"]["step"] for i in steps] == [1, 2, 3] and all(spans[i]["stats"]["do_sync"] == 1 for i in steps)
    for i in steps:
        assert [spans[j]["name"] for j in spans[i]["children"]] == ["train.step.args", "train.step.call", "train.step.swap"]
    offsets = [spans[i]["stats"]["mono_ns"] * 1e-9 - spans[i]["start"] for i in steps]
    assert max(offsets) - min(offsets) < 2e-3
