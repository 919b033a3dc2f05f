"""Autotuner tests: the typed search space (``analysis.searchspace``),
the analyzer-oracle tuner (``analysis.tuner``), the TPU7xx configuration
rules (``analysis.tune_rules``), the ``accelerate-tpu tune`` CLI, and —
the pinned oracle contract — the perfmodel ranking TRUST test: on two
toy workloads with four configs each, the statically predicted
step-time ordering must match the StepTelemetry-measured ordering
(top-1 agreement + Spearman >= 0.8 on CPU)."""

import json
import os
import subprocess
import sys
import textwrap
import warnings

import pytest

from accelerate_tpu.analysis.searchspace import (
    ConfigPoint,
    SearchSpace,
    chosen_toml,
    default_space,
    format_mesh_spec,
    load_chosen,
    load_tune_section,
    parse_mesh_spec,
    prune_reason,
)

CPU_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
}

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(*args, env=None, timeout=420, cwd=None):
    return subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.cli", *args],
        capture_output=True, text=True, env=env or CPU_ENV, timeout=timeout, cwd=cwd,
    )


# --------------------------------------------------------------------- #
# searchspace: ConfigPoint / SearchSpace / pruning / [tune.chosen]
# --------------------------------------------------------------------- #


def test_configpoint_normalization_and_label():
    p = ConfigPoint(mesh="data=4,tensor=2", buckets="32,128", compression="none")
    assert p.mesh_shape == {"data": 4, "tensor": 2}
    assert p.mesh_devices == 8
    assert p.buckets == (32, 128)
    assert p.compression is None  # "none" normalises away
    assert "data=4,tensor=2" in p.label() and "buckets=32,128" in p.label()
    # hashable (dedup in enumeration relies on it)
    assert hash(p) == hash(ConfigPoint(mesh={"data": 4, "tensor": 2}, buckets=(32, 128)))


def test_configpoint_dict_roundtrip():
    p = ConfigPoint(mesh="data=8", zero_stage=1, compression="int8",
                    token_budget=64, routing="least_loaded")
    q = ConfigPoint.from_dict(p.as_dict())
    assert q == p


def test_parse_mesh_spec():
    assert parse_mesh_spec("data=8") == {"data": 8}
    assert parse_mesh_spec({"data": 2, "tensor": 4}) == {"data": 2, "tensor": 4}
    assert format_mesh_spec({"data": 2, "tensor": 4}) == "data=2,tensor=4"
    with pytest.raises(ValueError):
        parse_mesh_spec("data8")


@pytest.mark.parametrize(
    "point,fragment",
    [
        (dict(mesh="data=16"), "devices"),
        (dict(mesh="banana=8"), "unknown mesh axis"),
        (dict(mesh="data=1", zero_stage=1), "needs a data axis"),
        (dict(mesh="data=4,tensor=2", zero_stage=1), "batch axes only"),
        (dict(mesh="data=8", dcn_axes="expert"), "not a mesh axis"),
        (dict(mesh="data=1", compression="int8"), "no data axis to compress"),
        (dict(compression="zstd"), "unknown compression"),
        (dict(buckets=(64, 32)), "ascending"),
        (dict(token_budget=8, tick_block=8, num_slots=4), "starves decode"),
        (dict(routing="random"), "unknown routing"),
        (dict(handoff="maybe"), "unknown handoff"),
        (dict(token_budget=0), "positive"),
    ],
)
def test_prune_constraints(point, fragment):
    reason = prune_reason(ConfigPoint(**point), max_devices=8)
    assert reason is not None and fragment in reason


def test_prune_accepts_valid_points():
    for kw in (
        dict(mesh="data=8", zero_stage=1, compression="int8"),
        dict(buckets=(32, 128), token_budget=64, tick_block=8, num_slots=4),
        dict(mesh="data=4,tensor=2", dcn_axes="data"),
    ):
        assert prune_reason(ConfigPoint(**kw), max_devices=8) is None


def test_searchspace_enumeration_and_dedup():
    space = SearchSpace(
        meshes=("data=8", "data=4,tensor=2"),
        zero_stages=(0, 1),
        compressions=("none", "int8"),
        max_devices=8,
    )
    pts = space.enumerate_points()
    assert len(pts) == space.size() == 8
    valid = space.valid_points()
    assert len(valid) == 6  # zero1-on-tensor-mesh combos pruned
    assert len({p for p, _ in pts}) == len(pts)
    reasons = [r for _, r in pts if r]
    assert all("batch axes only" in r for r in reasons)


def test_searchspace_from_spec_string_forms():
    space = SearchSpace.from_spec(
        {"meshes": ["data=8"], "bucket_sets": ["32,128", "64,256"],
         "token_budgets": [64, 128], "slots": 4},
        max_devices=8,
    )
    assert space.bucket_sets == ((32, 128), (64, 256))
    assert space.slot_counts == (4,)
    assert space.size() == 4


def test_default_space_prunes_to_runnable(mesh8):
    space = default_space(8)
    valid = space.valid_points()
    assert len(valid) >= 4
    assert all(prune_reason(p, max_devices=8) is None for p in valid)


def test_chosen_toml_roundtrip(tmp_path, monkeypatch):
    p = ConfigPoint(mesh="data=8", zero_stage=1, compression="int8", buckets=(32, 128))
    block = chosen_toml(p, predicted_step_ms=1.25)
    assert block.startswith("[tune.chosen]")
    (tmp_path / ".tpulint.toml").write_text("[tune]\ntop_k = 2\n\n" + block + "\n")
    monkeypatch.chdir(tmp_path)
    loaded = load_chosen()
    assert loaded == p
    section = load_tune_section()
    assert section["top_k"] == 2
    assert section["chosen"]["mesh"] == "data=8"


def test_chosen_feeds_parallelism_plugin(tmp_path, monkeypatch):
    (tmp_path / ".tpulint.toml").write_text(
        '[tune.chosen]\nmesh = "data=2,tensor=4"\nzero_stage = 0\ncompression = "int8"\n'
        'buckets = [32, 128]\ntoken_budget = 64\ntick_block = 8\n'
    )
    monkeypatch.chdir(tmp_path)
    point = load_chosen()
    kwargs = point.parallelism_kwargs()
    assert kwargs["zero_stage"] == 0 and kwargs["grad_compression"] == "int8"
    assert kwargs["mesh_config"].data == 2 and kwargs["mesh_config"].tensor == 4
    serving = point.serving_kwargs()
    assert serving["prompt_buckets"] == (32, 128)
    assert serving["scheduler"] == {"token_budget": 64, "tick_block": 8}


# --------------------------------------------------------------------- #
# TPU7xx configuration rules
# --------------------------------------------------------------------- #


def test_tpu703_waste_math():
    from accelerate_tpu.analysis.tune_rules import check_bucket_waste, padding_waste

    waste, detail = padding_waste((32,), {24: 100})
    assert waste == pytest.approx(8 / 24)
    assert detail[24] == (32, 800)
    assert check_bucket_waste((32,), {24: 100}, threshold=0.25)  # 33% > 25%
    assert not check_bucket_waste((32,), {24: 100}, threshold=0.40)
    # sizes above the largest bucket pad to it (honest denominator)
    waste_over, _ = padding_waste((32,), {64: 10})
    assert waste_over == 0.0


def test_tpu704_measured_sites_path():
    from accelerate_tpu.analysis.tune_rules import check_wire_upcast

    sites = [{"prim": "psum", "result_bytes": 4096, "group_size": 8,
              "dtypes": {"f32": 4096}}]
    hits = check_wire_upcast("bf16", sites=sites)
    assert hits and hits[0].rule == "TPU704" and "f32" in hits[0].message
    narrow = [{"prim": "psum", "result_bytes": 1024, "group_size": 8,
               "dtypes": {"s8": 1024}}]
    assert not check_wire_upcast("int8", sites=narrow)


def test_tpu705_structural_probe_real_optax():
    optax = pytest.importorskip("optax")
    from accelerate_tpu.analysis.tune_rules import check_zero1_optimizer

    fired = check_zero1_optimizer(1, optax.adafactor(1e-3))
    assert fired and fired[0].rule == "TPU705"
    assert not check_zero1_optimizer(1, optax.adamw(1e-3))
    assert not check_zero1_optimizer(0, optax.adafactor(1e-3))


def test_run_tune_selfcheck(mesh8):
    from accelerate_tpu.analysis.selfcheck import run_tune_selfcheck

    ok, lines = run_tune_selfcheck(mesh8)
    assert ok, "\n".join(lines)
    assert sum("detected" in line for line in lines) == 5
    assert sum("zero findings" in line for line in lines) == 5


# --------------------------------------------------------------------- #
# the tuner: static scoring, pruning, ranking, findings
# --------------------------------------------------------------------- #


def _token_factory(hidden=128):
    """Workload whose compute scales with the candidate's token budget —
    predictable ordering by construction."""
    import jax
    import jax.numpy as jnp

    def factory(point):
        tokens = point.token_budget or 32

        def step(w, x):
            return jnp.tanh(jnp.tanh(x @ w) @ w).sum()

        args = (
            jax.ShapeDtypeStruct((hidden, hidden), jnp.float32),
            jax.ShapeDtypeStruct((tokens, hidden), jnp.float32),
        )
        return step, args

    factory.tune_factory = True
    factory.__name__ = "token_workload"
    return factory


def test_tune_ranks_by_predicted_time(mesh8):
    from accelerate_tpu.analysis.tuner import tune

    space = SearchSpace(token_budgets=(256, 32, 128, 64))
    report = tune(_token_factory(), space, base_mesh=mesh8, generation="cpu")
    assert [c.point.token_budget for c in report.ranked] == [32, 64, 128, 256]
    assert report.winner.point.token_budget == 32
    assert report.ok
    # every scored candidate carries the full oracle output
    for c in report.ranked:
        assert c.predicted_step_us > 0 and c.peak_hbm_bytes > 0 and c.bound in (
            "compute", "memory", "comms"
        )


def test_tune_hbm_feasibility_prune(mesh8):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.analysis.tuner import tune

    def fat_step(w):
        return jnp.tanh(w @ w).sum()

    args = (jax.ShapeDtypeStruct((512, 512), jnp.float32),)
    space = SearchSpace(meshes=({"data": 1},))
    report = tune(fat_step, space, *args, generation="cpu", hbm_gb=0.0005)
    assert report.winner is None and report.infeasible_count == 1
    assert any(f.rule == "TPU701" for f in report.findings)
    assert not report.ok
    # the same candidate under a real budget is feasible and clean
    ok_report = tune(fat_step, space, *args, generation="cpu", hbm_gb=16.0)
    assert ok_report.ok and not ok_report.findings


def test_tune_search_run_keeps_tpu701_off_toplevel(mesh8):
    """In a multi-candidate search with a feasible winner, an infeasible
    candidate is a successful prune: status + per-candidate finding, but
    no top-level error gate."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.analysis.tuner import tune

    def factory(point):
        tokens = point.token_budget or 32

        def step(x):
            return jnp.tanh(x @ x.T).sum()

        return step, (jax.ShapeDtypeStruct((tokens, 64), jnp.float32),)

    factory.tune_factory = True
    space = SearchSpace(token_budgets=(16, 4096))
    report = tune(factory, space, generation="cpu", hbm_gb=0.001, base_mesh=mesh8)
    assert report.winner is not None and report.infeasible_count == 1
    assert not any(f.rule == "TPU701" for f in report.findings)
    infeasible = [c for c in report.candidates if c.status == "infeasible"]
    assert infeasible and any(f.rule == "TPU701" for f in infeasible[0].findings)
    assert report.ok


def test_tune_tpu702_dominated_in_real_search(mesh8):
    """A comms-bound candidate strictly dominated by a neighbor gets the
    TPU702 finding naming the winner."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.analysis.tuner import tune

    def psum_step(x):
        return jax.lax.psum(x, "data")

    args = (jax.ShapeDtypeStruct((1024, 1024), jnp.float32),)
    space = SearchSpace(meshes=("data=8", "data=2"), max_devices=8)
    report = tune(psum_step, space, *args, generation="cpu")
    assert report.winner.point.mesh_shape == {"data": 2}
    tpu702 = [f for f in report.findings if f.rule == "TPU702"]
    assert tpu702 and "data=2" in tpu702[0].message


def test_tune_plain_step_bucket_adapter(mesh8):
    """For a plain step fn, the buckets knob pads the leading batch dim
    to the covering bucket — bigger bucket, more predicted work."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.analysis.tuner import tune

    def step(x, w):
        return jnp.tanh(x @ w).sum()

    args = (
        jax.ShapeDtypeStruct((24, 64), jnp.float32),
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
    )
    space = SearchSpace(bucket_sets=("32", "256"))
    report = tune(step, space, *args, base_mesh=mesh8, generation="cpu")
    assert report.winner.point.buckets == (32,)
    times = {c.point.buckets: c.predicted_step_us for c in report.ranked}
    assert times[(256,)] > times[(32,)]


def test_tune_report_surfaces(mesh8):
    from accelerate_tpu.analysis.tuner import tune

    space = SearchSpace(token_budgets=(32, 64))
    report = tune(_token_factory(), space, base_mesh=mesh8, generation="cpu",
                  shape_histogram={24: 10})
    as_dict = report.as_dict()
    json.dumps(as_dict)  # fully serializable
    assert as_dict["winner"]["label"] == report.winner.label
    assert as_dict["chosen_toml"].startswith("[tune.chosen]")
    text = report.render_text()
    assert "winner:" in text and "[tune.chosen]" in text
    block = report.chosen_toml()
    assert f"token_budget = {report.winner.point.token_budget}" in block


def test_spearman_helper():
    from accelerate_tpu.analysis.tuner import spearman

    assert spearman([1, 2, 3], [10, 20, 30]) == pytest.approx(1.0)
    assert spearman([1, 2, 3], [30, 20, 10]) == pytest.approx(-1.0)
    assert spearman([1, 2], [5]) is None
    assert spearman([1, 1, 1], [1, 1, 1]) == pytest.approx(1.0)


def test_accelerator_tune(mesh8):
    from accelerate_tpu import Accelerator

    acc = Accelerator()
    report = acc.tune(_token_factory(), space=SearchSpace(token_budgets=(32, 64)),
                      generation="cpu")
    assert report.winner.point.token_budget == 32
    assert report.ok


# --------------------------------------------------------------------- #
# the ORACLE CONTRACT, pinned: predicted ordering == measured ordering
# on >=2 toy workloads with >=4 configs each (top-1 + Spearman >= 0.8)
# --------------------------------------------------------------------- #


def _bucket_factory(hidden=512, true_batch=96):
    """Trust workload 1 (train-shaped): the batch pads to the candidate
    bucket, so compute scales ~4x across the config set."""
    import jax
    import jax.numpy as jnp

    def factory(point):
        batch = point.buckets[0] if point.buckets else true_batch

        def step(w, x):
            return jnp.tanh(jnp.tanh(x @ w) @ w).sum()

        args = (
            jax.ShapeDtypeStruct((hidden, hidden), jnp.float32),
            jax.ShapeDtypeStruct((batch, hidden), jnp.float32),
        )
        return step, args

    factory.tune_factory = True
    factory.__name__ = "bucket_trust_workload"
    return factory


TRUST_WORKLOADS = pytest.mark.parametrize(
    "factory_builder,space_kwargs",
    [
        (_bucket_factory, dict(bucket_sets=("128", "256", "512", "1024"))),
        (lambda: _token_factory(hidden=512), dict(token_budgets=(128, 256, 512, 1024))),
    ],
    ids=["bucket-padding", "token-budget"],
)


def _confirmed(mesh8, factory_builder, space_kwargs):
    from accelerate_tpu.analysis.tuner import tune

    return tune(
        factory_builder(), SearchSpace(**space_kwargs),
        base_mesh=mesh8, generation="cpu",
        top_k=4, confirm=True, confirm_steps=6,
    )


@TRUST_WORKLOADS
def test_perfmodel_ranking_trust(mesh8, factory_builder, space_kwargs):
    """What of the tuner's oracle contract does not depend on the machine's load: four arms are ranked and
    confirmed, no arm recompiles while it is measured, and the static predicted step time rises with the padded
    size (the batch's bucket, the token budget). That the measured ordering agrees is
    ``test_perfmodel_ranking_matches_wall_time``."""
    report = _confirmed(mesh8, factory_builder, space_kwargs)
    assert len(report.ranked) == 4
    assert report.confirm["rank_agreement"]["n"] == 4, report.confirm
    assert report.confirm["recompiles"] == 0
    padded = [c.point.buckets[0] if c.point.buckets else c.point.token_budget for c in report.ranked]
    assert padded == [128, 256, 512, 1024]
    predicted = [c.predicted_step_us for c in report.ranked]
    assert predicted == sorted(predicted) and predicted[0] < predicted[-1]


@pytest.mark.slow  # XLA:CPU wall time: run alone (``-m slow``), not beside five loaded xdist workers
@TRUST_WORKLOADS
def test_perfmodel_ranking_matches_wall_time(mesh8, factory_builder, space_kwargs):
    """The tuner's oracle contract: static predicted-step-time ordering
    matches the StepTelemetry-measured ordering — top-1 agreement and
    Spearman >= 0.8 — on CPU, where the knobs change real compute."""
    ra = _confirmed(mesh8, factory_builder, space_kwargs).confirm["rank_agreement"]
    assert ra["top1"] is True
    assert ra["spearman"] >= 0.8


# --------------------------------------------------------------------- #
# CLI surface
# --------------------------------------------------------------------- #


def test_cli_tune_selfcheck():
    result = run_cli("tune", "--selfcheck")
    assert result.returncode == 0, result.stdout + result.stderr
    assert result.stdout.count("detected") == 5
    assert result.stdout.count("zero findings") == 5


def test_cli_tune_json_and_emit(tmp_path):
    emit = tmp_path / "chosen.toml"
    result = run_cli(
        "tune", os.path.join(REPO, "examples", "by_feature", "tune.py") + "::serving_workload",
        "--mesh", "data=8", "--bucket-sets", "32,128;64,256", "--token-budgets", "32,64",
        "--generation", "cpu", "--format", "json", "--emit", str(emit),
        cwd=REPO,
    )
    assert result.returncode == 0, result.stdout + result.stderr
    payload = result.stdout[: result.stdout.rindex("}") + 1]
    doc = json.loads(payload)
    assert doc["winner"] is not None
    assert len(doc["candidates"]) == 4
    assert emit.read_text().startswith("[tune.chosen]")


def test_cli_tune_reads_tune_section(tmp_path):
    """[tune] in .tpulint.toml specs the search space (typo'd sections
    would warn — the loader satellite)."""
    (tmp_path / ".tpulint.toml").write_text(
        '[tune]\ntoken_budgets = [32, 64]\ngeneration = "cpu"\n'
    )
    (tmp_path / "wl.py").write_text(textwrap.dedent('''
        """Tune workload fixture."""
        import jax
        import jax.numpy as jnp


        def wl(point):
            tokens = point.token_budget or 16

            def step(x):
                return jnp.tanh(x @ x.T).sum()

            return step, (jax.ShapeDtypeStruct((tokens, 32), jnp.float32),)


        wl.tune_factory = True
    '''))
    result = run_cli("tune", "wl.py::wl", "--mesh", "data=1", "--format", "json",
                     cwd=tmp_path)
    assert result.returncode == 0, result.stdout + result.stderr
    doc = json.loads(result.stdout[: result.stdout.rindex("}") + 1])
    budgets = {c["config"].get("token_budget") for c in doc["candidates"]}
    assert budgets == {32, 64}


def test_cli_tune_sarif_format():
    result = run_cli(
        "tune", os.path.join(REPO, "examples", "by_feature", "tune.py") + "::train_workload",
        "--mesh", "data=8", "--meshes", "data=8", "--compressions", "none",
        "--generation", "cpu", "--format", "sarif",
        cwd=REPO,
    )
    assert result.returncode == 0, result.stdout + result.stderr
    doc = json.loads(result.stdout)
    assert doc["version"] == "2.1.0"
    assert doc["runs"][0]["tool"]["driver"]["name"] == "accelerate-tpu-lint"


def test_example_workloads_are_dogfood_clean():
    """The repo's own example workloads must tune without errors (the
    make tune-selfcheck gate)."""
    import importlib.util

    from accelerate_tpu.analysis.tuner import tune

    spec = importlib.util.spec_from_file_location(
        "tune_example", os.path.join(REPO, "examples", "by_feature", "tune.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    report = tune(
        mod.train_workload,
        SearchSpace(meshes=("data=8", "data=4,tensor=2"), compressions=("none", "int8"),
                    max_devices=8),
        generation="cpu",
    )
    assert report.ok, [f.as_dict() for f in report.findings]
    assert not any(f.is_error for f in report.findings)


# --------------------------------------------------------------------- #
# satellites: loader warnings, telemetry default path, shared SARIF
# --------------------------------------------------------------------- #


def test_project_config_warns_on_unknown_names(tmp_path):
    from accelerate_tpu.analysis.project_config import load_project_config

    (tmp_path / ".tpulint.toml").write_text(
        '[tunne]\nmeshes = ["data=8"]\n\n[lint]\nformt = "json"\n'
    )
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        load_project_config(str(tmp_path))
    messages = [str(w.message) for w in caught]
    assert any("[tunne]" in m and "'tune'" in m for m in messages), messages
    assert any("'formt'" in m and "'format'" in m for m in messages), messages


def test_project_config_valid_schema_is_silent(tmp_path):
    from accelerate_tpu.analysis.project_config import load_project_config

    (tmp_path / ".tpulint.toml").write_text(
        '[lint]\nformat = "text"\ndisable = []\n\n[tune]\ntop_k = 3\n\n'
        '[tune.chosen]\nmesh = "data=8"\n\n[[suppress]]\npath = "examples/*"\n'
    )
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        cfg = load_project_config(str(tmp_path))
    assert [str(w.message) for w in caught] == []
    assert cfg.format == "text"


def test_telemetry_default_path_under_runs():
    from accelerate_tpu.telemetry import default_path

    assert default_path(None) == os.path.join("runs", "telemetry.jsonl")
    assert default_path("proj/logs") == os.path.join("proj/logs", "telemetry.jsonl")


def test_checkpoints_describe_sarif(tmp_path):
    """describe --format sarif goes through the shared reporter: an
    uncommitted checkpoint is a CKPT001 error result."""
    ckpt = tmp_path / "checkpoint_0"
    (ckpt / "model").mkdir(parents=True)
    (ckpt / "model" / "data.bin").write_bytes(b"x" * 64)
    result = run_cli("checkpoints", "describe", str(ckpt), "--format", "sarif")
    assert result.returncode == 1
    doc = json.loads(result.stdout)
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "accelerate-tpu-checkpoints"
    assert run["results"][0]["ruleId"] == "CKPT001"
    assert run["results"][0]["level"] == "error"


def test_fleet_price_handoff_sarif():
    result = run_cli(
        "fleet", "price-handoff", "--layers", "4", "--kv-heads", "2", "--head-dim", "16",
        "--tokens", "128", "--params", "1e6", "--transport", "dcn", "--format", "sarif",
    )
    assert result.returncode == 0, result.stdout + result.stderr
    doc = json.loads(result.stdout)
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "accelerate-tpu-fleet"
    assert run["results"][0]["ruleId"] == "FLEET001"


def test_merge_sarif_spans_all_surfaces(tmp_path):
    """Every CLI analysis surface merges into ONE artifact: a lint-tier
    run, a checkpoints run, and a fleet run."""
    from accelerate_tpu.analysis import Finding, render_sarif

    (tmp_path / "lint.sarif").write_text(render_sarif([Finding("TPU703", "waste")]))
    fleet = run_cli("fleet", "price-handoff", "--layers", "2", "--kv-heads", "2",
                    "--head-dim", "8", "--tokens", "16", "--format", "sarif")
    (tmp_path / "fleet.sarif").write_text(fleet.stdout)
    ckpt = tmp_path / "checkpoint_0"
    (ckpt / "model").mkdir(parents=True)
    desc = run_cli("checkpoints", "describe", str(ckpt), "--format", "sarif")
    (tmp_path / "ckpt.sarif").write_text(desc.stdout)
    merged_path = tmp_path / "merged.sarif"
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "merge_sarif.py"),
         str(tmp_path / "lint.sarif"), str(tmp_path / "fleet.sarif"),
         str(tmp_path / "ckpt.sarif"), "-o", str(merged_path)],
        capture_output=True, text=True, env=CPU_ENV,
    )
    assert result.returncode == 0, result.stderr
    merged = json.loads(merged_path.read_text())
    names = [r["tool"]["driver"]["name"] for r in merged["runs"]]
    assert names == ["accelerate-tpu-lint", "accelerate-tpu-fleet", "accelerate-tpu-checkpoints"]
