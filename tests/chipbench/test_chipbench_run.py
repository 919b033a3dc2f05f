"""The harness end to end on the host's CPU: the rehearsal manifest's toy cells, a cell made
only of new files, a timed path broken underneath, the lower-precision control, and no TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "rehearsal.json")


@pytest.fixture
def rehearse(monkeypatch, capsys, tmp_path):
    """Run one cell in this process with ``--rehearsal`` and give back its printed lines; what
    the rehearsal switches on in the process is switched back. The harness's root is ``tmp_path``:
    test workers write traces at once, and the span readers take the newest under the root."""
    import jax

    from accelerate_tpu.ops import paged_kv
    from chipbench import run

    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", paged_kv.FORCE_KERNEL_INTERPRET)
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    cache_was = jax.config.jax_enable_compilation_cache

    def go(workload, *extra, manifest=REHEARSAL, seconds="1.5", seed="5"):
        with open(manifest) as f:
            stated = json.load(f)
        stated["paths"] = [os.path.join(ROOT, p) for p in stated["paths"]]  # one that is absolute stays as it is
        for c in stated["configs"]:
            c["file"] = os.path.join(ROOT, c["file"])
        (tmp_path / "absolute.json").write_text(json.dumps(stated))
        capsys.readouterr()
        code = run.main(["--manifest", str(tmp_path / "absolute.json"), "--rehearsal", "--workload", workload, "--seed", seed,
                         "--seconds", seconds, *extra])
        lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
        assert code == 0
        return lines

    yield go
    jax.config.update("jax_enable_compilation_cache", cache_was)


def result(lines):
    last = lines[-1]
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["device"]["platform"] == "cpu", "a rehearsal names the CPU it ran on"
    return last


@pytest.mark.parametrize("workload,metric", [
    ("tiny-serve-chat", "ttft_p90_ms"), ("tiny-serve-docqa", "serve_tokens_per_s"),
    ("tiny-train", "train_tokens_per_s"), ("tiny-train-mesh4", "train_tokens_per_s"),
])
def test_rehearsal_runs_each_kind_of_cell(rehearse, workload, metric):
    lines = rehearse(workload, "--trace", "0")
    last = result(lines)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"][metric]["value"] > 0 and last["metrics"]["setup_s"]["value"] > 0
    checks = {l["check"]: l for l in lines if "check" in l}
    assert checks["compiles_in_window"]["value"] == 0
    assert all("limit" in c and "value" in c for c in checks.values()), "each number beside its limit"
    assert list(last)[-1] == "compared" and {k: v["value"] for k, v in last["compared"].items()} == \
        {k: c["value"] for k, c in checks.items()}, "and in the result's line, last"


@pytest.mark.parametrize("workload,has", [
    ("tiny-serve-chat", "engine_decode_step_ms"), ("tiny-serve-docqa", "engine_prefill_ms_per_ktok"),
    ("tiny-train", "train_step_ms"),
])
def test_traced_rehearsal_reports_per_layer_metrics_and_a_breakdown(rehearse, workload, has):
    last = result(rehearse(workload, "--trace", "1", seconds="3"))
    assert has in last["metrics"] and "warm_programs" in last["metrics"]
    assert not any(name.endswith("_roofline") or "mfu" in name for name in last["metrics"]), \
        "no share of a peak from a CPU"
    assert last["device"]["busy_s"] > 0 and last["device"]["window_s"] > last["device"]["busy_s"] * 0.5
    assert last["breakdown"]["device_ops"] and len(last["breakdown"]["idle_gaps"]) <= 10


def test_lower_precision_control_fails_the_serve_limits(rehearse):
    lines = rehearse("tiny-serve-chat", "--trace", "0", "--control", "1", seconds="3")
    control = next(l for l in lines if l.get("note") == "control")
    assert result(lines)["correct"] is True and control["would_pass"] is False


def test_lower_precision_control_fails_the_train_limits(rehearse):
    lines = rehearse("tiny-train-mesh4", "--trace", "0", "--control", "1")
    control = next(l for l in lines if l.get("note") == "control")
    assert result(lines)["correct"] is True and control["would_pass"] is False


def test_altered_token_makes_the_serve_run_incorrect(rehearse, monkeypatch):
    """The timed path broken where a token is produced: the sampler picks the worst token."""
    import jax.numpy as jnp

    from accelerate_tpu import generation

    monkeypatch.setattr(generation, "_make_sampler", lambda *_: (lambda logits, key: jnp.argmin(logits, axis=-1)))
    lines = rehearse("tiny-serve-chat", "--trace", "0")
    assert result(lines)["correct"] is False
    assert not next(l for l in lines if l.get("check") == "logit_gap_max")["ok"]


def test_step_that_leaves_its_state_unchanged_makes_the_train_run_incorrect(rehearse, monkeypatch):
    import optax

    real = optax.adamw
    monkeypatch.setattr(optax, "adamw", lambda *a, **k: optax.chain(real(*a, **k), optax.scale(0.0)))
    lines = rehearse("tiny-train", "--trace", "0")
    assert result(lines)["correct"] is False
    assert not next(l for l in lines if l.get("check") == "change_matrix_gap")["ok"]


def test_a_cell_made_only_of_new_files_runs(rehearse, tmp_path):
    """One configuration, one traffic file, one per-layer reader, one manifest entry each:
    nothing of the harness is edited."""
    new = tmp_path / "newcell"
    (new / "traffic").mkdir(parents=True)
    (new / "layers").mkdir()
    (new / "configs").mkdir()
    shutil.copy(os.path.join(HERE, "configs", "mistral-tiny.json"), new / "configs" / "another-tiny.json")
    with open(os.path.join(HERE, "traffic", "chat-tiny.json")) as f:
        traffic = json.load(f)
    traffic.update(prompt_tokens=[6, 12], new_tokens=[5, 7], rate_per_s=15.0,
                   limits={"another-tiny": {"logit_gap_max": 0.05, "logit_gap_mean": 0.01}})
    (new / "traffic" / "burst.json").write_text(json.dumps(traffic))
    (new / "layers" / "ticks_total.py").write_text("def read(observed):\n    return len(observed['ticks']) or None\n")
    with open(REHEARSAL) as f:
        manifest = json.load(f)
    manifest["paths"].append(str(new))
    manifest["configs"].append({"name": "another-tiny", "source": "toy", "file": str(new / "configs" / "another-tiny.json"),
                                "reduced": [], "why": "new files only"})
    manifest["workloads"].append({"name": "another-burst", "config": "another-tiny", "traffic": "burst", "chips": 1,
                                  "why": "new files only"})
    for m in manifest["end_to_end"]:
        if m["name"] in ("ttft_p90_ms", "tpot_p90_ms"):
            m["workloads"].append("another-burst")
    manifest["per_layer"].append({"name": "ticks_total", "unit": "count", "better": "lower", "source": "program_counter",
                                  "layer": "engine host loop", "moves": "tpot_p90_ms", "workloads": ["another-burst"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    last = result(rehearse("another-burst", "--trace", "1", manifest=str(path), seconds="3"))
    assert last["correct"] is True and last["metrics"]["ticks_total"]["value"] > 0
    assert "warm_programs" in last["metrics"], "a metric with no workloads key is reported in the new cell too"


# the new family's tensors are an old one's under other names, ``t_<name>``
NEW_FAMILY = """from chipbench.reference import {base} as base

LAYER_NAMES = tuple("t_" + n for n in base.LAYER_NAMES)
COUNTS = ("train_flops", "weight_bytes_per_decode_step", "cache_bytes_per_decode_step", "attention_shape")
globals().update({{n: getattr(base, n) for n in COUNTS if hasattr(base, n)}})  # the cost counts are the old family's


def spec(cfg):
    return {{"t_" + name: drawn for name, drawn in base.spec(cfg).items()}}


def {function}(weights, cfg, *rest, **more):
    return base.{function}({{name[2:]: w for name, w in weights.items()}}, cfg, *rest, **more)
"""
NEW_BUILDER = """from chipbench.builders import {base} as base

TABLE = [("t_" + name, path, per_layer) for name, path, per_layer in base.TABLE]


def build(config, traffic, seed, make_weights):
    built = base.build(config, traffic, seed, lambda *a: {{name[2:]: w for name, w in make_weights(*a).items()}})
    built._table = TABLE  # a trainer names its leaves by its table; a server has none and ignores it
    return built
"""


def new_family_manifest(tmp_path, kind, function="logits_at"):
    """A manifest in ``tmp_path`` with one more cell, whose family, builder, configuration and traffic are
    files there: the family ``other`` is ``kind``'s under other tensor names and gives ``function``."""
    base_family, base_builder, config_file, traffic_file, cell = {
        "serve": ("mistral", "llama_core_serve", "mistral-tiny", "chat-tiny", "tiny-serve-chat"),
        "train": ("bert", "bert", "bert-tiny", "seq-tiny", "tiny-train")}[kind]
    new = tmp_path / "newfamily"
    for sub in ("reference", "builders", "configs", "traffic"):
        (new / sub).mkdir(parents=True)
    (new / "reference" / "other.py").write_text(NEW_FAMILY.format(base=base_family, function=function))
    (new / "builders" / "other_builder.py").write_text(NEW_BUILDER.format(base=base_builder))
    with open(os.path.join(HERE, "configs", config_file + ".json")) as f:
        config = json.load(f)
    config["bench"].update(reference="other", builder="other_builder")
    (new / "configs" / "other-tiny.json").write_text(json.dumps(config))
    with open(os.path.join(HERE, "traffic", traffic_file + ".json")) as f:
        traffic = json.load(f)
    traffic["limits"] = {"other-tiny": traffic["limits"][config_file]}
    (new / "traffic" / "other-mix.json").write_text(json.dumps(traffic))
    with open(REHEARSAL) as f:
        manifest = json.load(f)
    manifest["paths"].append(str(new))
    manifest["configs"].append({"name": "other-tiny", "source": "toy", "file": str(new / "configs" / "other-tiny.json"),
                                "reduced": [], "why": "new files only"})
    manifest["workloads"].append({"name": "other-cell", "config": "other-tiny", "traffic": "other-mix", "chips": 1,
                                  "why": "new files only"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if cell in m.get("workloads", ()):
            m["workloads"].append("other-cell")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)


@pytest.mark.parametrize("kind,function,check", [("serve", "logits_at", "logit_gap_max"), ("train", "loss_fn", "change_matrix_gap")])
def test_a_family_made_only_of_new_files_runs(rehearse, tmp_path, kind, function, check):
    """A family the checkout does not have: its weights spec, reference and cost counts, its builder, a
    configuration, a traffic file and a manifest entry each. Nothing of the harness is edited."""
    lines = rehearse("other-cell", "--trace", "1", manifest=new_family_manifest(tmp_path, kind, function), seconds="3")
    last = result(lines)
    assert last["correct"] is True and last["attempted"] > 0
    assert next(l for l in lines if l.get("check") == check)["ok"]
    if kind == "train":
        worst = next(l for l in lines if l.get("note") == "compared")["worst_leaf"]
        assert all(leaf.startswith("t_") for leaf in worst.values()), "leaves are compared under the new names"
    assert ("engine_decode_step_ms" if kind == "serve" else "train_step_ms") in last["metrics"]


def test_a_family_without_the_path_its_cell_takes_is_refused_before_any_weights(rehearse, tmp_path, monkeypatch, capsys):
    from chipbench import weights

    monkeypatch.setattr(weights, "make", lambda *a, **k: pytest.fail("weights were made"))
    manifest = new_family_manifest(tmp_path, "serve", function="loss_fn")  # a serve cell on a family with no logits_at
    with pytest.raises(SystemExit) as refused:
        rehearse("other-cell", "--trace", "0", manifest=manifest)
    assert "'other'" in str(refused.value) and "logits_at" in str(refused.value) and "other-cell" in str(refused.value)
    assert '"correct"' not in capsys.readouterr().out


def test_no_tpu_is_a_non_zero_exit_and_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "-m", "chipbench", "--workload", "bert-base-train-seq128", "--seed", "1",
                           "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0 and '"correct"' not in done.stdout and "no accelerator" in done.stderr


def test_without_the_program_there_is_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench", ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH", "JAX_PLATFORMS")}
    done = subprocess.run([sys.executable, "-m", "chipbench", "--workload", "bert-base-train-seq128", "--seed", "1",
                           "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0 and '"correct"' not in done.stdout
