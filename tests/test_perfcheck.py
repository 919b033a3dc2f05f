"""Static performance analyzer (``analysis.perfmodel`` +
``analysis.perf_rules``): roofline math against hand-computed
FLOPs/bytes, the TPU501-505 rules with their clean twins, the
``perf_model_drift`` telemetry cross-check, and the CLI surfaces
(text/json/sarif/selfcheck/baseline-diff)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from accelerate_tpu.analysis.costmodel import (
    BANDWIDTH_TABLE,
    HBM_BW_TABLE,
    PEAK_FLOPS_TABLE,
    device_generation,
    hbm_bandwidth,
    peak_flops,
)
from accelerate_tpu.analysis.perfmodel import PerfReport, perf_check
from accelerate_tpu.parallel.mesh import MeshConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(report: PerfReport):
    return sorted({f.rule for f in report.findings})


@pytest.fixture
def mesh1():
    return MeshConfig(data=1).build(jax.devices()[:1])


# --------------------------------------------------------------------- #
# cost tables: v6e + explicit cpu rows (deterministic host backend)
# --------------------------------------------------------------------- #


def test_tables_have_v6e_and_cpu_rows():
    for table in (BANDWIDTH_TABLE, PEAK_FLOPS_TABLE, HBM_BW_TABLE):
        assert "v6e" in table and "cpu" in table
    # the cpu row is explicit, not a silent v5e alias
    assert peak_flops("cpu") == 1e12
    assert hbm_bandwidth("cpu") == 100e9
    assert peak_flops("cpu") != peak_flops("v5e")
    # a generation the tables do not know is an error, not v5e's row
    for lookup in (peak_flops, hbm_bandwidth):
        with pytest.raises(ValueError, match="weird-future-chip"):
            lookup("weird-future-chip")


def test_device_generation_maps_cpu_backend():
    # the suite runs under JAX_PLATFORMS=cpu, so the attached device kind
    # must resolve to the explicit cpu row
    assert device_generation() == "cpu"
    assert device_generation(jax.devices()[0]) == "cpu"


# --------------------------------------------------------------------- #
# roofline math (hand-computed reference)
# --------------------------------------------------------------------- #


def test_matmul_over_mesh_exact_flops_bytes_wire(mesh8):
    """The acceptance-criterion fixture: FLOPs, HBM bytes, and psum wire
    bytes must match hand computation EXACTLY."""
    M, K, N = 64, 256, 128

    def ref_step(x, w):
        return jax.lax.psum(x @ w, "data")

    report = perf_check(
        ref_step,
        jax.ShapeDtypeStruct((M, K), jnp.float32),
        jax.ShapeDtypeStruct((K, N), jnp.float32),
        mesh=mesh8,
        generation="v5e",
    )
    [dot] = [o for o in report.ops if o.primitive == "dot_general"]
    [psum] = [o for o in report.ops if o.primitive == "psum"]
    assert dot.flops == 2 * M * K * N
    assert dot.hbm_bytes == (M * K + K * N + M * N) * 4
    assert psum.wire_bytes == int(M * N * 4 * 2 * 7 / 8)  # ring all-reduce
    assert psum.transport == "ici"
    assert report.total_flops == dot.flops
    assert report.predicted_step_ms > 0
    assert 0 < report.mfu_upper_bound <= 1
    assert not report.findings


def test_roofline_bound_classification(mesh1):
    """A big square matmul is compute-bound; a matvec is memory-bound."""

    def big(x, w):
        return x @ w

    sq = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    r = perf_check(big, sq, sq, mesh=mesh1, generation="v5e")
    [dot] = [o for o in r.ops if o.primitive == "dot_general"]
    assert dot.bound == "compute"

    vec = jax.ShapeDtypeStruct((1, 1024), jnp.float32)
    r = perf_check(big, vec, sq, mesh=mesh1, generation="v5e")
    [dot] = [o for o in r.ops if o.primitive == "dot_general"]
    assert dot.bound == "memory"


def test_scan_multiplies_op_counts(mesh1):
    def looped(x):
        def body(c, _):
            return jnp.tanh(c @ c), None

        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    r = perf_check(looped, jax.ShapeDtypeStruct((64, 64), jnp.float32), mesh=mesh1)
    dots = [o for o in r.ops if o.primitive == "dot_general"]
    assert dots and all(o.count == 5 for o in dots)
    assert dots[0].flops == 5 * 2 * 64**3


def test_sharded_output_divides_per_device_flops(mesh8):
    """A batch-sharded matmul parallelises over the data axis: per-device
    FLOPs are 1/8 of the global count."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    def step(x, w):
        return x @ w

    x = jax.device_put(np.zeros((64, 32), np.float32), NamedSharding(mesh8, P("data")))
    w = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    r = perf_check(step, x, w, mesh=mesh8)
    [dot] = [o for o in r.ops if o.primitive == "dot_general"]
    assert dot.flops == 2 * 64 * 32 * 16 // 8


def test_report_dict_and_text_surfaces(mesh8):
    def step(x, w):
        return jax.lax.psum(x @ w, "data")

    r = perf_check(
        step,
        jax.ShapeDtypeStruct((64, 256), jnp.float32),
        jax.ShapeDtypeStruct((256, 128), jnp.float32),
        mesh=mesh8,
        generation="v6e",
    )
    d = r.as_dict()
    assert d["generation"] == "v6e"
    assert d["totals"]["flops_per_device"] == r.total_flops
    assert d["totals"]["predicted_step_ms"] == pytest.approx(r.predicted_step_ms, abs=1e-4)
    assert d["totals"]["wire_bytes_by_transport"]["ici"] > 0
    assert len(d["ops"]) == len(r.ops)
    text = r.render_text()
    assert "MFU upper bound" in text and "v6e roofline" in text
    by_bound = r.time_by_bound()
    assert by_bound["comms"] > 0


# --------------------------------------------------------------------- #
# TPU501-505: defect fires, clean twin silent
# --------------------------------------------------------------------- #


def test_tpu501_misaligned_matmul_and_clean_twin(mesh1):
    def step(x, w):
        return x @ w

    bad = perf_check(
        step,
        jax.ShapeDtypeStruct((256, 100), jnp.float32),
        jax.ShapeDtypeStruct((100, 512), jnp.float32),
        mesh=mesh1,
    )
    assert "TPU501" in _rules(bad)
    [f] = [f for f in bad.findings if f.rule == "TPU501"]
    assert "21.9%" in f.message  # waste is priced: 1 - 100/128
    assert "128" in f.message  # the covering bucket is named

    clean = perf_check(
        step,
        jax.ShapeDtypeStruct((256, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 512), jnp.float32),
        mesh=mesh1,
    )
    assert clean.findings == []


def test_tpu501_memory_bound_matvec_sublane_not_flagged(mesh1):
    """Decode-style matvec (M=1) is memory-bound: sublane padding costs
    nothing there, so a lane-aligned matvec must stay clean."""

    def step(x, w):
        return x @ w

    r = perf_check(
        step,
        jax.ShapeDtypeStruct((1, 1024), jnp.float32),
        jax.ShapeDtypeStruct((1024, 512), jnp.float32),
        mesh=mesh1,
    )
    assert "TPU501" not in _rules(r)


def test_tpu502_redundant_collective_and_clean_twin(mesh8):
    def bad_step(x):
        g = jax.lax.psum(x, "data")
        return jax.lax.psum(g * 0.5, "data")  # uniformity survives the scale

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    bad = perf_check(bad_step, x, mesh=mesh8)
    assert "TPU502" in _rules(bad)
    assert any(f.is_error for f in bad.findings)  # the strict-gate rule

    def clean_step(x, y):
        # two reduces of DIFFERENT values: nothing redundant
        return jax.lax.psum(x, "data"), jax.lax.pmax(y, "data")

    clean = perf_check(clean_step, x, x, mesh=mesh8)
    assert clean.findings == []


def test_tpu502_mixed_operand_breaks_uniformity(mesh8):
    """f(uniform, sharded) is not uniform — re-reducing it is legitimate
    and must NOT fire."""

    def step(x, y):
        g = jax.lax.psum(x, "data")
        return jax.lax.psum(g * y, "data")  # y differs per shard

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    r = perf_check(step, x, x, mesh=mesh8)
    assert "TPU502" not in _rules(r)


def test_tpu503_small_dcn_collectives_and_clean_twin(mesh8):
    def two_small(a, b):
        return jax.lax.psum(a, "data"), jax.lax.psum(b, "data")

    small = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    bad = perf_check(two_small, small, small, mesh=mesh8, dcn=("data",))
    assert "TPU503" in _rules(bad)

    # same collectives on ICI: no finding
    assert "TPU503" not in _rules(perf_check(two_small, small, small, mesh=mesh8))

    # ONE small DCN collective: nothing to coalesce with
    def one_small(a):
        return jax.lax.psum(a, "data")

    assert "TPU503" not in _rules(perf_check(one_small, small, mesh=mesh8, dcn=("data",)))

    # one BIG DCN collective: bandwidth-bound, not latency-bound
    big = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    assert "TPU503" not in _rules(perf_check(one_small, big, mesh=mesh8, dcn=("data",)))


def test_tpu504_missed_overlap_and_clean_twin(mesh8):
    a = jax.ShapeDtypeStruct((1024, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)

    def bad(a, b):
        g = jax.lax.psum(a, "data")
        h = g + 1.0  # consumed immediately
        c = b @ b  # independent compute stranded after the consumer
        return h, c

    report = perf_check(bad, a, b, mesh=mesh8, generation="v5e")
    assert "TPU504" in _rules(report)
    [f] = [f for f in report.findings if f.rule == "TPU504"]
    assert "us" in f.message  # the hideable time is priced

    def good(a, b):
        g = jax.lax.psum(a, "data")
        c = b @ b  # fills the collective's window
        h = g + 1.0
        return h, c

    assert "TPU504" not in _rules(perf_check(good, a, b, mesh=mesh8, generation="v5e"))


def test_tpu505_f32_matmul_with_bf16_provenance_and_clean_twin(mesh1):
    xb = jax.ShapeDtypeStruct((256, 128), jnp.bfloat16)
    wb = jax.ShapeDtypeStruct((128, 512), jnp.bfloat16)

    def upcast(x, w):
        return x.astype(jnp.float32) @ w.astype(jnp.float32)

    assert "TPU505" in _rules(perf_check(upcast, xb, wb, mesh=mesh1))

    # destination form: f32 matmul narrowed straight back to bf16
    xf = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    wf = jax.ShapeDtypeStruct((128, 512), jnp.float32)

    def narrowed(x, w):
        return (x @ w).astype(jnp.bfloat16)

    assert "TPU505" in _rules(perf_check(narrowed, xf, wf, mesh=mesh1))

    # genuine f32 data staying f32: clean
    def native(x, w):
        return x @ w

    assert "TPU505" not in _rules(perf_check(native, xf, wf, mesh=mesh1))

    # the fix itself: bf16 inputs, f32 accumulation — clean
    def fixed(x, w):
        return jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    assert "TPU505" not in _rules(perf_check(fixed, xb, wb, mesh=mesh1))


def test_perf_findings_anchor_to_source_and_inline_suppression(tmp_path, mesh1):
    """TPU5xx findings carry real path:line, so # tpu-lint: disable works."""
    import importlib.util
    import textwrap

    mod = tmp_path / "padded.py"
    mod.write_text(
        textwrap.dedent(
            '''
            """Fixture: misaligned matmul, suppressed inline."""
            import jax.numpy as jnp


            def step(x, w):
                return x @ w  # tpu-lint: disable=TPU501
            '''
        )
    )
    spec = importlib.util.spec_from_file_location("padded", mod)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    r = perf_check(
        m.step,
        jax.ShapeDtypeStruct((256, 100), jnp.float32),
        jax.ShapeDtypeStruct((100, 512), jnp.float32),
        mesh=mesh1,
    )
    assert "TPU501" not in _rules(r)


def test_select_ignore_filtering(mesh1):
    def step(x, w):
        return x @ w

    x = jax.ShapeDtypeStruct((256, 100), jnp.float32)
    w = jax.ShapeDtypeStruct((100, 512), jnp.float32)
    assert _rules(perf_check(step, x, w, mesh=mesh1, ignore=("TPU501",))) == []
    assert _rules(perf_check(step, x, w, mesh=mesh1, select=("TPU501",))) == ["TPU501"]


# --------------------------------------------------------------------- #
# selfcheck (the executable spec)
# --------------------------------------------------------------------- #


def test_run_perf_selfcheck_passes(mesh8):
    from accelerate_tpu.analysis.selfcheck import run_perf_selfcheck

    ok, lines = run_perf_selfcheck(mesh8)
    assert ok, "\n".join(lines)
    for rule in ("TPU501", "TPU502", "TPU503", "TPU504", "TPU505"):
        assert f"{rule} fixture: detected" in "\n".join(lines)
        assert f"{rule} clean twin: zero findings" in "\n".join(lines)
    assert any("roofline reference" in line and "exact" in line for line in lines)


# --------------------------------------------------------------------- #
# perf_model_drift telemetry cross-check
# --------------------------------------------------------------------- #


class _FakeClock:
    """Deterministic clock: every reading advances by ``dt_s``."""

    def __init__(self, dt_s=0.001):
        self.t = 0.0
        self.dt = dt_s

    def __call__(self):
        self.t += self.dt
        return self.t


def _drive(st, n=8):
    f = st.wrap(lambda x: x)
    for _ in range(n):
        f(1.0)


def test_perf_model_drift_fires_once_on_mismatch(tmp_path):
    from accelerate_tpu.telemetry import StepTelemetry
    from accelerate_tpu.telemetry.eventlog import EventLog, read_events

    path = str(tmp_path / "run.jsonl")
    log = EventLog(path, rank=0)
    # fake clock: every step's busy time is exactly 2ms (dispatch+execute)
    st = StepTelemetry(log, warmup_steps=1, watchdog=False, fence=False, clock=_FakeClock(0.001))
    st.set_static_step_estimate(0.5)  # predicted 0.5ms vs observed 2ms: 300% off
    _drive(st, 8)
    assert st.perf_drift_event is not None
    assert st.perf_drift_event["rel_error"] == pytest.approx(3.0, rel=0.01)
    _drive(st, 8)  # fires ONCE, not per step
    log.close()
    events = read_events(path)
    drift = [e for e in events if e.get("name") == "perf_model_drift"]
    static = [e for e in events if e.get("name") == "perf_static_estimate"]
    assert len(drift) == 1 and len(static) == 1
    assert drift[0]["predicted_ms"] == 0.5
    assert drift[0]["observed_busy_ms"] == pytest.approx(2.0, rel=0.01)
    summary = st.summary()
    assert summary["static_step_ms"] == 0.5
    assert summary["perf_model_drift"] is True


def test_perf_model_drift_silent_on_matched_run(tmp_path):
    from accelerate_tpu.telemetry import StepTelemetry
    from accelerate_tpu.telemetry.eventlog import EventLog

    log = EventLog(str(tmp_path / "run.jsonl"), rank=0)
    st = StepTelemetry(log, warmup_steps=1, watchdog=False, fence=False, clock=_FakeClock(0.001))
    st.set_static_step_estimate(2.0)  # exactly the observed busy time
    _drive(st, 20)
    assert st.perf_drift_event is None
    assert st.summary()["perf_model_drift"] is False
    log.close()


def test_drift_needs_min_steady_records(tmp_path):
    from accelerate_tpu.telemetry import StepTelemetry

    st = StepTelemetry(warmup_steps=1, watchdog=False, fence=False, clock=_FakeClock(0.001))
    st.set_static_step_estimate(0.1)
    _drive(st, 4)  # 3 steady records < perf_drift_min_steady (5)
    assert st.perf_drift_event is None
    _drive(st, 4)
    assert st.perf_drift_event is not None


def test_summarize_renders_drift(tmp_path):
    from accelerate_tpu.telemetry import StepTelemetry
    from accelerate_tpu.telemetry.eventlog import EventLog
    from accelerate_tpu.telemetry.summarize import render_text, summarize_file

    path = str(tmp_path / "run.jsonl")
    log = EventLog(path, rank=0)
    st = StepTelemetry(log, warmup_steps=1, watchdog=False, fence=False, clock=_FakeClock(0.001))
    st.set_static_step_estimate(0.5)
    _drive(st, 8)
    log.close()
    report = summarize_file(path)
    assert report["steps"]["static_step_ms"] == 0.5
    assert len(report["steps"]["perf_drift_events"]) == 1
    text = render_text(report)
    assert "static prediction" in text and "DRIFT" in text


def test_accelerator_perf_check_seeds_telemetry(tmp_path):
    from accelerate_tpu import Accelerator
    from accelerate_tpu.utils import TelemetryKwargs

    path = str(tmp_path / "run.jsonl")
    acc = Accelerator(kwargs_handlers=[TelemetryKwargs(output_path=path)])
    tel = acc.telemetry  # telemetry live before the check

    def step(x, w):
        return (x @ w).sum()

    report = acc.perf_check(
        step,
        jax.ShapeDtypeStruct((64, 256), jnp.float32),
        jax.ShapeDtypeStruct((256, 128), jnp.float32),
    )
    assert report.predicted_step_ms > 0
    assert report.generation == "cpu"  # attached backend resolves the row
    assert tel.steps.static_step_ms == pytest.approx(report.predicted_step_ms)


# --------------------------------------------------------------------- #
# ServingEngine dogfood: roofline the real prefill/decode programs
# --------------------------------------------------------------------- #


def test_serving_engine_perf_check_dogfood():
    from accelerate_tpu.models import LlamaConfig, create_llama_model
    from accelerate_tpu.serving import ServingEngine

    model = create_llama_model(LlamaConfig.tiny(), seq_len=16)
    eng = ServingEngine(model, num_slots=2, prompt_buckets=(8, 16))
    reports = eng.perf_check()
    # resume_recompute = the preempt->resume warm chunk window: the
    # analysis stack covers every program the scheduler can launch
    assert set(reports) == {"prefill", "decode_tick", "resume_recompute"}
    for name, rep in reports.items():
        assert rep.total_flops > 0, name
        assert rep.predicted_step_ms > 0, name
        # the strict-gate rule must be clean on the repo's own programs;
        # TPU501 warnings are expected here — the TINY test config's
        # 64-wide dims are deliberately sub-tile (real configs are
        # 128-multiples), which is exactly what the rule prices
        assert not any(f.rule == "TPU502" for f in rep.findings), name
        assert {f.rule for f in rep.findings} <= {"TPU501"}, name
    # the decode tick runs tick_block scan steps per call
    decode = reports["decode_tick"]
    assert any(o.count >= eng.tick_block for o in decode.ops)


# --------------------------------------------------------------------- #
# CLI: text / json / sarif / selfcheck / baseline diff
# --------------------------------------------------------------------- #

CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _run_cli(*args, timeout=240):
    return subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.cli", *args],
        capture_output=True, text=True, env=CPU_ENV, timeout=timeout, cwd=REPO,
    )


@pytest.mark.slow
def test_cli_perf_check_selfcheck():
    result = _run_cli("perf-check", "--selfcheck")
    assert result.returncode == 0, result.stderr
    for rule in ("TPU501", "TPU502", "TPU503", "TPU504", "TPU505"):
        assert f"{rule} fixture: detected" in result.stdout
        assert f"{rule} clean twin: zero findings" in result.stdout
    assert "roofline reference" in result.stdout and "exact" in result.stdout


@pytest.mark.slow
def test_cli_perf_check_example_step_text():
    result = _run_cli(
        "perf-check", "examples/by_feature/flight_check.py::train_step", "--mesh", "data=8",
    )
    assert result.returncode == 0, result.stderr
    assert "predicted step time" in result.stdout
    assert "MFU upper bound" in result.stdout
    # dogfood: the example tree is TPU5xx-clean (head matmul suppressed inline)
    assert "findings: none" in result.stdout


@pytest.mark.slow
def test_cli_perf_check_json_sarif_and_baseline(tmp_path):
    target = ("perf-check", "examples/by_feature/flight_check.py::train_step", "--mesh", "data=8")
    result = _run_cli(*target, "--format", "json")
    assert result.returncode == 0, result.stderr
    payload = json.loads(result.stdout)
    assert payload["totals"]["predicted_step_ms"] > 0
    assert payload["ops"] and all("time_us" in op for op in payload["ops"])

    sarif = _run_cli(*target, "--format", "sarif")
    assert sarif.returncode == 0, sarif.stderr
    doc = json.loads(sarif.stdout)
    assert doc["version"] == "2.1.0"

    base = tmp_path / "base.json"
    base.write_text(result.stdout)
    diff = _run_cli(*target, "--baseline", str(base))
    assert diff.returncode == 0, diff.stderr
    assert "ok: predicted step time +0.0%" in diff.stdout

    # a seeded 2x regression trips the threshold and the exit code
    slow = json.loads(result.stdout)
    slow["totals"]["predicted_step_ms"] /= 2  # pretend the past was 2x faster
    regress = tmp_path / "regress.json"
    regress.write_text(json.dumps(slow))
    diff = _run_cli(*target, "--baseline", str(regress))
    assert diff.returncode == 1
    assert "REGRESSION" in diff.stdout
    # a generous threshold lets the same diff pass
    diff = _run_cli(*target, "--baseline", str(regress), "--regress-pct", "150")
    assert diff.returncode == 0, diff.stdout


@pytest.mark.slow
def test_cli_perf_check_strict_gate_on_tpu502(tmp_path):
    """The error-severity rule fails the CLI without --strict — the
    mechanism that promotes TPU502 into the make lint gate."""
    import textwrap

    mod = tmp_path / "redundant.py"
    mod.write_text(
        textwrap.dedent(
            '''
            """Fixture: redundant psum-of-psum."""
            import jax
            import jax.numpy as jnp


            def step(x):
                g = jax.lax.psum(x, "data")
                return jax.lax.psum(g, "data")


            def step_sample_args():
                return (jax.ShapeDtypeStruct((64, 64), jnp.float32),)
            '''
        )
    )
    result = _run_cli("perf-check", f"{mod}::step", "--mesh", "data=8")
    assert result.returncode == 1
    assert "TPU502" in result.stdout
