"""Pipeline-schedule analyzer tests: the per-stage roofline / bubble
model (``analysis.pipemodel``), the TPU80x rules
(``analysis.pipe_rules``), the ``accelerate-tpu pipe-check`` CLI, the
searchspace/tuner pipeline knobs, and — the wire-unit pin — byte-exact
agreement between ``costmodel.price_collective`` and the HLO collective
counters (``telemetry.wire``) on a real compiled ``pipeline_apply``
program."""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from accelerate_tpu.analysis.costmodel import (
    BANDWIDTH_TABLE,
    hbm_bandwidth,
    peak_flops,
    price_collective,
)
from accelerate_tpu.analysis.pipe_rules import (
    PIPE_BUBBLE_THRESHOLD,
    covering_microbatches,
)
from accelerate_tpu.analysis.pipemodel import (
    PipelineSpec,
    analyze_pipeline,
    from_pipelined_model,
    pipe_check,
)
from accelerate_tpu.parallel.mesh import MeshConfig

CPU_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
}

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(*args, env=None, timeout=420):
    return subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.cli", *args],
        capture_output=True, text=True, env=env or CPU_ENV, timeout=timeout,
    )


def _mm(p, h):
    return h @ p


def _pipe_mesh(s):
    return MeshConfig(pipe=s, data=8 // s).build()


def _spec(layer_fn, s, *, m, width=16, batch=16, layers=None, **kw):
    """A declared S-stage single-matmul-per-layer schedule (the selfcheck
    fixture family): stacked [L, W, W] params, [B, W] activations."""
    L = layers if layers is not None else 2 * s
    params = jax.ShapeDtypeStruct((L, width, width), jnp.float32)
    x = jax.ShapeDtypeStruct((batch, width), jnp.float32)
    return PipelineSpec(layer_fn, params, x, _pipe_mesh(s), num_microbatches=m, **kw)


def _hand(s, m, *, width=16, batch=16, layers_per_stage=2, interleave=1):
    """Hand-computed reference for the _spec family, straight from the
    costmodel tables (mirrors the selfcheck's pinned arithmetic)."""
    b_mb = batch // m
    b_blk = b_mb // interleave
    flops = 2 * b_blk * width * width
    hbm = (b_blk * width + width * width + b_blk * width) * 4
    t_layer = max(
        flops / (peak_flops("cpu", "bf16") / 2.0) * 1e6,  # f32 matmul class
        hbm / hbm_bandwidth("cpu") * 1e6,
    )
    stage_c = interleave * layers_per_stage * t_layer
    act = batch * width * 4 // m
    block_us = (act // interleave) / BANDWIDTH_TABLE["cpu"]["ici"] * 1e6
    block_c = stage_c / interleave
    exposed = block_us + (interleave - 1) * max(0.0, block_us - block_c)
    ticks = m + s - 1
    tick = stage_c + exposed
    return {
        "stage_compute_us": stage_c,
        "exposed_us": exposed,
        "hidden_us": interleave * block_us - exposed,
        "step_us": ticks * tick,
        "bubble": 1.0 - (m * s * stage_c) / (s * ticks * tick),
    }


def _close(a, b):
    assert math.isclose(a, b, rel_tol=1e-9, abs_tol=1e-12), (a, b)


# --------------------------------------------------------------------- #
# the bubble / roofline model, pinned against hand arithmetic
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("s,m", [(2, 4), (4, 8)])
def test_declared_schedule_exact_bubble(s, m):
    r = analyze_pipeline(_spec(_mm, s, m=m), generation="cpu")
    ref = _hand(s, m)
    assert r.n_stages == s and r.num_microbatches == m
    assert r.ticks == m + s - 1
    _close(r.ideal_bubble_fraction, (s - 1) / (m + s - 1))
    _close(r.stages[0].compute_us, ref["stage_compute_us"])
    _close(r.exposed_permute_us, ref["exposed_us"])
    _close(r.predicted_step_us, ref["step_us"])
    _close(r.bubble_fraction, ref["bubble"])


def test_bubble_shrinks_with_microbatches():
    bubbles = [
        analyze_pipeline(_spec(_mm, 4, m=m), generation="cpu").bubble_fraction
        for m in (1, 2, 4, 8, 16)
    ]
    assert bubbles == sorted(bubbles, reverse=True)
    # predict_step_us_at: identity at its own M, and the covering M
    # (what TPU803 prices) beats a full-bubble schedule
    r1 = analyze_pipeline(_spec(_mm, 4, m=1), generation="cpu")
    _close(r1.predict_step_us_at(1), r1.predicted_step_us)
    assert r1.predict_step_us_at(covering_microbatches(4)) < r1.predicted_step_us


def test_imbalanced_cut_inflates_max_tick():
    bal = analyze_pipeline(_spec(_mm, 4, m=8), generation="cpu")
    imb = analyze_pipeline(
        _spec(_mm, 4, m=8, stage_layers=(5, 1, 1, 1)), generation="cpu"
    )
    assert [s.layers for s in imb.stages] == [5, 1, 1, 1]
    # the fat stage paces every tick: 5/2 the balanced per-stage compute
    _close(imb.max_tick_us - imb.exposed_permute_us,
           2.5 * (bal.max_tick_us - bal.exposed_permute_us))
    assert imb.predicted_step_us > bal.predicted_step_us
    assert imb.bubble_fraction > bal.bubble_fraction


def test_interleave_overlap_accounting():
    r1 = analyze_pipeline(_spec(_mm, 4, m=4), generation="cpu")
    r4 = analyze_pipeline(_spec(_mm, 4, m=4, interleave=4), generation="cpu")
    assert r1.interleave == 1 and r4.interleave == 4
    # k=1: single block, nothing to hide behind
    _close(r1.exposed_permute_us, r1.permute_block_us)
    _close(r1.hidden_permute_us, 0.0)
    # blocks split the activation: block handoff is 1/4 the full one
    _close(r4.permute_block_us, r1.permute_block_us / 4)
    # conservation: every block's permute is either exposed or hidden
    _close(r4.exposed_permute_us + r4.hidden_permute_us, 4 * r4.permute_block_us)
    ref = _hand(4, 4, interleave=4)
    _close(r4.exposed_permute_us, ref["exposed_us"])
    _close(r4.hidden_permute_us, ref["hidden_us"])
    _close(r4.predicted_step_us, ref["step_us"])
    # an interleave that does not divide the microbatch degrades to k=1
    r3 = analyze_pipeline(_spec(_mm, 4, m=4, interleave=3), generation="cpu")
    assert r3.interleave == 1


def test_per_stage_hbm_vs_flight_check():
    """Each stage holds 1/S of the stacked params: per-stage peaks sit
    under the whole-program flight-check peak, and the per-stage param
    bytes sum back to the full stack."""
    from accelerate_tpu.analysis.flightcheck import flight_check
    from accelerate_tpu.parallel.pipeline import pipeline_apply

    s, m, width, batch, L = 4, 4, 16, 32, 8
    mesh = _pipe_mesh(s)
    params = jax.ShapeDtypeStruct((L, width, width), jnp.float32)
    x = jax.ShapeDtypeStruct((batch, width), jnp.float32)
    spec = PipelineSpec(_mm, params, x, mesh, num_microbatches=m)
    r = analyze_pipeline(spec, generation="cpu")
    assert sum(st.param_bytes for st in r.stages) == L * width * width * 4

    def step(p, xx):
        return pipeline_apply(_mm, p, xx, mesh=mesh, num_microbatches=m).sum()

    fl = flight_check(step, params, x, mesh=mesh, generation="cpu")
    assert fl.peak_hbm_bytes > 0
    for st in r.stages:
        assert st.peak_hbm_bytes < fl.peak_hbm_bytes


def test_remat_keeps_stage_boundary_only():
    full = analyze_pipeline(_spec(_mm, 4, m=8), generation="cpu")
    re = analyze_pipeline(_spec(_mm, 4, m=8, remat=True), generation="cpu")
    # 2 layers/stage saved -> 1 boundary activation saved
    saved_delta = 8 * (2 - 1) * full.activation_bytes
    assert full.stages[0].peak_hbm_bytes - re.stages[0].peak_hbm_bytes == saved_delta


def test_traced_path_matches_declared():
    """The traced recognizer prices the real ``pipeline_apply`` program
    to the same schedule shape the declared spec gives."""
    from accelerate_tpu.parallel.pipeline import pipeline_apply

    s, m, width, batch = 4, 4, 16, 32
    mesh = _pipe_mesh(s)

    def step(p, xx):
        return pipeline_apply(_mm, p, xx, mesh=mesh, num_microbatches=m).sum()

    params = jax.ShapeDtypeStruct((8, width, width), jnp.float32)
    x = jax.ShapeDtypeStruct((batch, width), jnp.float32)
    r = pipe_check(step, params, x, mesh=mesh, rules=False, generation="cpu")
    assert r.source == "traced"
    assert r.n_stages == s and r.num_microbatches == m
    assert r.ticks == m + s - 1
    # per-shard (data=2) microbatch activation: (batch/2/m) x width f32
    assert r.activation_bytes == (batch // 2 // m) * width * 4
    assert r.predicted_step_us > 0


def test_pipelined_model_entry():
    from accelerate_tpu.parallel.pipeline import PipelinedModel

    width, batch = 16, 32
    mesh = _pipe_mesh(4)
    pm = PipelinedModel(
        pre_fn=lambda p, x: (x, ()),
        layer_fn=_mm,
        post_fn=lambda p, h: h.sum(),
        params={
            "pre": (),
            "layers": jax.ShapeDtypeStruct((8, width, width), jnp.float32),
            "post": (),
        },
        mesh=mesh,
        num_microbatches=4,
    )
    spec = from_pipelined_model(pm, jax.ShapeDtypeStruct((batch, width), jnp.float32))
    assert spec.x.shape == (batch // 2, width)  # one data shard's batch
    r = analyze_pipeline(spec, generation="cpu")
    assert r.n_stages == 4 and r.num_microbatches == 4


# --------------------------------------------------------------------- #
# TPU80x rules: each fires on its seeded defect, stays quiet on the twin
# --------------------------------------------------------------------- #


def _rules(report_args, **kw):
    r = pipe_check(report_args, generation="cpu", **kw)
    return r, {f.rule for f in r.findings}


def test_tpu801_pipe_on_ici_with_dcn_present():
    r, ids = _rules(_spec(_mm, 4, m=16, width=64), dcn=("data",))
    assert "TPU801" in ids
    msg = next(f.message for f in r.findings if f.rule == "TPU801")
    assert "us/step" in msg  # re-placement delta is priced
    _, ids = _rules(_spec(_mm, 4, m=16, width=64), dcn=("pipe",))
    assert not ids  # cut already on DCN: clean


def test_tpu802_stage_imbalance_names_worst_stage():
    r, ids = _rules(_spec(_mm, 4, m=16, stage_layers=(5, 1, 1, 1)))
    assert "TPU802" in ids
    msg = next(f.message for f in r.findings if f.rule == "TPU802")
    assert "stage 0" in msg
    _, ids = _rules(_spec(_mm, 4, m=16))
    assert "TPU802" not in ids


def test_tpu803_bubble_names_covering_microbatches():
    r, ids = _rules(_spec(_mm, 4, m=1))
    assert "TPU803" in ids
    m_cover = covering_microbatches(4, PIPE_BUBBLE_THRESHOLD)
    assert m_cover == 9
    msg = next(f.message for f in r.findings if f.rule == "TPU803")
    assert f"num_microbatches={m_cover}" in msg
    _, ids = _rules(_spec(_mm, 4, m=16))
    assert "TPU803" not in ids


def test_tpu804_collective_over_pipe_in_tick_body_is_error():
    def pipe_psum(p, h):
        return jax.lax.psum(h @ p, "pipe")

    r, ids = _rules(_spec(pipe_psum, 4, m=16))
    assert "TPU804" in ids
    assert not r.ok  # error severity: the strict gate
    r, ids = _rules(_spec(_mm, 4, m=16))
    assert "TPU804" not in ids and r.ok


def test_tpu805_stage_activations_over_budget():
    kw = dict(width=64, batch=4096)
    _, ids = _rules(_spec(_mm, 4, m=16, **kw), hbm_gb=0.0005)
    assert "TPU805" in ids
    _, ids = _rules(_spec(_mm, 4, m=16, remat=True, **kw), hbm_gb=0.0005)
    assert "TPU805" not in ids  # remat keeps stage boundaries only


def test_covering_microbatches_formula():
    for s in (2, 4, 8):
        m = covering_microbatches(s)
        assert (s - 1) / (m + s - 1) <= PIPE_BUBBLE_THRESHOLD
        if m > 1:
            assert (s - 1) / ((m - 1) + s - 1) > PIPE_BUBBLE_THRESHOLD
    assert covering_microbatches(1) == 1


# --------------------------------------------------------------------- #
# the wire-unit pin: costmodel prediction == compiled-HLO counters
# --------------------------------------------------------------------- #


def test_permute_and_scatter_wire_bytes_match_hlo():
    """``price_collective`` and the HLO counter must agree BYTE-EXACTLY
    on the real compiled pipeline program: the tick handoff
    (collective-permute) and the output reduction (reduce-scatter over
    ``pipe``) are both priced through the shared ring formulas."""
    from accelerate_tpu.parallel.pipeline import pipeline_apply
    from accelerate_tpu.telemetry.wire import hlo_wire_bytes

    s, m, width, batch = 4, 4, 16, 32
    mesh = _pipe_mesh(s)

    def step(p, xx):
        return pipeline_apply(_mm, p, xx, mesh=mesh, num_microbatches=m).sum()

    params = jax.ShapeDtypeStruct((8, width, width), jnp.float32)
    x = jax.ShapeDtypeStruct((batch, width), jnp.float32)
    hlo = jax.jit(step).lower(params, x).compile().as_text()
    measured = hlo_wire_bytes(hlo)
    sites = {st["prim"]: st for st in measured["sites"]}
    assert "ppermute" in sites and "reduce_scatter" in sites

    # tick handoff: one [B/data/M, W] f32 block crosses the pipe ring
    block_bytes = (batch // 2 // m) * width * 4
    predicted = price_collective("ppermute", ("pipe",), block_bytes, mesh)
    assert predicted.wire_bytes == sites["ppermute"]["wire_bytes"]
    assert sites["ppermute"]["result_bytes"] == block_bytes
    assert sites["ppermute"]["group_size"] == s

    # output reduction: the [M, k, B_blk, W] buffer reduce-scattered
    buf_bytes = m * (batch // 2 // m) * width * 4
    predicted = price_collective("psum_scatter", ("pipe",), buf_bytes, mesh)
    assert predicted.wire_bytes == sites["reduce_scatter"]["wire_bytes"]
    assert sites["reduce_scatter"]["group_size"] == s


# --------------------------------------------------------------------- #
# searchspace + tuner: the pipeline knobs close the loop
# --------------------------------------------------------------------- #


def test_searchspace_pipeline_knobs():
    from accelerate_tpu.analysis.searchspace import (
        ConfigPoint,
        SearchSpace,
        prune_reason,
    )

    p = ConfigPoint(mesh="pipe=4,data=2", num_microbatches=8, interleave=2, remat=True)
    assert p.has_pipeline_knobs
    assert p.pipeline_kwargs() == {"num_microbatches": 8, "interleave": 2, "remat": True}
    assert "mb=8" in p.label() and "interleave=2" in p.label() and "remat" in p.label()
    assert ConfigPoint.from_dict(p.as_dict()) == p
    assert prune_reason(p) is None
    # pipeline knobs without a pipe axis cannot run
    assert "pipe axis" in prune_reason(ConfigPoint(mesh="data=8", num_microbatches=8))
    assert "num_microbatches" in prune_reason(
        ConfigPoint(mesh="pipe=4,data=2", num_microbatches=0)
    )

    space = SearchSpace(
        meshes=("pipe=4,data=2",), microbatch_counts="2,8", remats=(False, True)
    )
    points = [p for p, reason in space.enumerate_points() if reason is None]
    assert len(points) == 4
    assert {pt.num_microbatches for pt in points} == {2, 8}
    assert SearchSpace.from_spec(
        {"meshes": ["pipe=4,data=2"], "microbatches": [2, 8], "remats": [False, True]}
    ).size() == 4


def test_tuner_scores_pipeline_knobs_with_bubble_model():
    """The loop the tentpole closes: enumerate num_microbatches, score
    each candidate with pipemodel's bubble-adjusted step time, and rank
    the full-bubble M=1 schedule last."""
    from accelerate_tpu.analysis.searchspace import SearchSpace
    from accelerate_tpu.analysis.tuner import tune
    from accelerate_tpu.parallel.pipeline import pipeline_apply

    width, batch = 16, 32

    def workload(point):
        mesh = MeshConfig(**point.mesh_shape).build()
        kw = point.pipeline_kwargs()

        def step(p, xx):
            return pipeline_apply(_mm, p, xx, mesh=mesh, **kw).sum()

        params = jax.ShapeDtypeStruct((8, width, width), jnp.float32)
        x = jax.ShapeDtypeStruct((batch, width), jnp.float32)
        return step, (params, x)

    workload.tune_factory = True
    space = SearchSpace(meshes=("pipe=4,data=2",), microbatch_counts=(1, 4, 16))
    report = tune(workload, space, generation="cpu")
    assert len(report.ranked) == 3
    assert all(c.bubble_fraction is not None for c in report.ranked)
    by_m = {c.point.num_microbatches: c for c in report.ranked}
    # the bubble model, not the serial roofline, must drive the ranking:
    # M=1 (75% bubble) is strictly slower than M=4 under pipemodel while
    # the serial roofline would call them equal-ish
    assert by_m[1].predicted_step_us > by_m[4].predicted_step_us
    assert by_m[1].bubble_fraction > by_m[4].bubble_fraction
    assert report.winner.point.num_microbatches != 1
    payload = report.winner.as_dict()
    assert "bubble_fraction" in payload


def test_accelerator_pipe_check_seeds_step_estimate():
    """``Accelerator.pipe_check`` hands the bubble-adjusted prediction to
    StepTelemetry as the static step estimate."""
    from accelerate_tpu import Accelerator

    acc = Accelerator()
    spec = _spec(_mm, 4, m=16)
    report = acc.pipe_check(spec)
    assert report.n_stages == 4
    assert report.ok


# --------------------------------------------------------------------- #
# the pipe selfcheck + CLI surface
# --------------------------------------------------------------------- #


def test_pipe_selfcheck_green():
    from accelerate_tpu.analysis.selfcheck import run_pipe_selfcheck

    ok, lines = run_pipe_selfcheck()
    assert ok, "\n".join(lines)
    assert sum("detected" in ln for ln in lines) == 5
    assert sum("clean twin: zero findings" in ln for ln in lines) == 5
    assert any("exact" in ln for ln in lines)


def test_cli_pipe_check_json():
    result = run_cli(
        "pipe-check",
        os.path.join(REPO, "examples", "by_feature", "pipe_check.py") + "::train_step",
        "--mesh", "pipe=4,data=2", "--generation", "cpu", "--format", "json",
    )
    assert result.returncode == 0, result.stdout + result.stderr
    doc = json.loads(result.stdout)
    assert doc["schedule"] == {
        "n_stages": 4, "num_microbatches": 2, "interleave": 1,
        "remat": False, "ticks": 5,
    }
    assert any(f["rule"] == "TPU803" for f in doc["findings"])
    # warning severity: exit 0 non-strict, 1 under --strict
    strict = run_cli(
        "pipe-check",
        os.path.join(REPO, "examples", "by_feature", "pipe_check.py") + "::train_step",
        "--mesh", "pipe=4,data=2", "--generation", "cpu", "--strict",
    )
    assert strict.returncode == 1


def test_cli_pipe_check_sarif():
    result = run_cli(
        "pipe-check",
        os.path.join(REPO, "examples", "by_feature", "pipe_check.py") + "::train_step",
        "--mesh", "pipe=4,data=2", "--generation", "cpu", "--format", "sarif",
    )
    assert result.returncode == 0, result.stdout + result.stderr
    doc = json.loads(result.stdout)
    assert doc["version"] == "2.1.0"
    assert "TPU803" in {r["ruleId"] for r in doc["runs"][0]["results"]}


@pytest.mark.slow
def test_cli_pipe_selfcheck():
    result = run_cli("pipe-check", "--selfcheck")
    assert result.returncode == 0, result.stdout + result.stderr
    assert result.stdout.count("detected") == 5
    assert "exact" in result.stdout
