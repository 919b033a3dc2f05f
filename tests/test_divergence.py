"""The multi-host divergence analyzer (``analysis.ranksim`` +
``analysis.divergence``): taint propagation through the multi-rank
interpreter, per-rank trace diffing into the TPU4xx rules, the
Accelerator/collectives effect-summary tables, ``.tpulint.toml`` project
configuration, and the CLI/SARIF surface."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from accelerate_tpu.analysis.divergence import analyze_file, analyze_paths, analyze_source
from accelerate_tpu.analysis.project_config import (
    ProjectConfig,
    _parse_minimal_toml,
    find_project_config,
    load_project_config,
)
from accelerate_tpu.analysis.ranksim import (
    ACCELERATOR_EFFECTS,
    COLLECTIVE_EFFECTS,
    DIVERGENT,
    UNIFORM,
    ModuleSimulator,
    Value,
    join_values,
)
from accelerate_tpu.analysis.rules import ERROR, RULES, WARNING

import ast

CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def run_cli(*args, cwd=None, timeout=240):
    return subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.cli", *args],
        capture_output=True,
        text=True,
        env=CPU_ENV,
        cwd=cwd,
        timeout=timeout,
    )


def _rules(findings):
    return [f.rule for f in findings]


def _analyze(src, **kw):
    return analyze_source(textwrap.dedent(src), path="fix.py", **kw)


def _sim(src, n_ranks=3):
    return ModuleSimulator(ast.parse(textwrap.dedent(src)), n_ranks=n_ranks)


# --------------------------------------------------------------------- #
# the taint lattice
# --------------------------------------------------------------------- #


def test_join_values_divergent_wins():
    u, d = Value(UNIFORM), Value(DIVERGENT, None, "process_index")
    assert not join_values(u, u).divergent
    joined = join_values(u, d, u)
    assert joined.divergent and joined.origin == "process_index"


def test_taint_propagates_through_arithmetic():
    """rank-derived values stay divergent through computation; a guard on
    one sends synthetic ranks down different branches (trace diff)."""
    findings = _analyze(
        """
        def f(accelerator, x):
            shifted = accelerator.process_index + 1
            if shifted * 2 > 2:
                accelerator.wait_for_everyone()
        """
    )
    assert "TPU401" in _rules(findings)


def test_uniform_computation_stays_uniform():
    """pure computation over uniform values never diverges — a config
    branch around a barrier is fine (both worlds run it or skip it on
    EVERY rank)."""
    findings = _analyze(
        """
        def f(accelerator, cfg):
            n = cfg.batch_size * 2
            if n > 64:
                accelerator.wait_for_everyone()
            accelerator.gather(n)
        """
    )
    assert findings == []


def test_per_rank_concrete_branching():
    """is_main_process is True exactly on rank 0: the simulator sends each
    synthetic rank down its real branch, so main-only *local* work is
    clean but main-only collectives are not."""
    clean = _analyze(
        """
        def f(accelerator, metrics):
            if accelerator.is_main_process:
                print(metrics)
        """
    )
    assert clean == []
    deadlock = _analyze(
        """
        def f(accelerator, metrics):
            if accelerator.is_main_process:
                accelerator.gather(metrics)
        """
    )
    assert _rules(deadlock) == ["TPU401"]
    assert deadlock[0].severity == ERROR
    assert "gather" in deadlock[0].message and "is_main_process" in deadlock[0].message


def test_numeric_roots_not_mistaken_for_accelerator():
    """jnp.log / functools.reduce must not resolve to Accelerator.log /
    .reduce effect summaries."""
    findings = _analyze(
        """
        import functools
        import jax.numpy as jnp


        def f(accelerator, xs):
            if accelerator.is_main_process:
                return functools.reduce(lambda a, b: a + b, xs) + jnp.log(xs[0])
            return None
        """
    )
    assert findings == []


def test_host_entropy_taints():
    """random/time/hostname reads are per-host state: a barrier under such
    a guard deadlocks."""
    findings = _analyze(
        """
        import random


        def f(accelerator):
            if random.random() > 0.5:
                accelerator.wait_for_everyone()
        """
    )
    assert _rules(findings) == ["TPU401"]


# --------------------------------------------------------------------- #
# per-rank trace diffing: the rule family
# --------------------------------------------------------------------- #


def test_tpu401_divergent_early_return():
    """a rank-divergent return before a barrier strands the other ranks."""
    findings = _analyze(
        """
        def f(accelerator, batch):
            if accelerator.process_index > 0:
                return None
            return accelerator.gather(batch)
        """
    )
    assert "TPU401" in _rules(findings)


def test_tpu401_collective_inside_main_process_first():
    """ranks are serialized inside main_process_first: a collective in the
    body can never line up."""
    findings = _analyze(
        """
        def f(accelerator, ds):
            with accelerator.main_process_first():
                ds = accelerator.broadcast(ds)
            return ds
        """
    )
    assert "TPU401" in _rules(findings)
    assert "main_process_first" in findings[0].message


def test_tpu401_barrier_inside_solo_decorated_function():
    """@on_main_process makes the body main-only — a barrier inside one is
    itself a deadlock, and the simulator models the decorator."""
    findings = _analyze(
        """
        from accelerate_tpu.state import on_main_process


        @on_main_process
        def publish(accelerator, path):
            accelerator.wait_for_everyone()
        """
    )
    assert "TPU401" in _rules(findings)


def test_tpu402_divergent_loop_trip_count():
    findings = _analyze(
        """
        import os


        def drain(accelerator):
            for shard in os.listdir("/data"):
                accelerator.reduce(shard)
        """
    )
    assert "TPU402" in _rules(findings)
    assert RULES["TPU402"].severity == ERROR
    assert "listdir" in findings[0].message


def test_tpu402_uniform_loop_is_clean():
    findings = _analyze(
        """
        def train(accelerator, batches):
            for batch in batches:
                accelerator.backward(batch)
                loss = accelerator.gather(batch)
            return loss
        """
    )
    assert findings == []


def test_tpu403_mismatched_order():
    findings = _analyze(
        """
        def step(accelerator, x):
            if accelerator.is_main_process:
                x = accelerator.gather(x)
                accelerator.wait_for_everyone()
            else:
                accelerator.wait_for_everyone()
                x = accelerator.gather(x)
            return x
        """
    )
    assert "TPU403" in _rules(findings)
    assert "order" in findings[0].message


def test_matched_syncs_across_branches_are_clean():
    """both arms emit the SAME collective program (different lines):
    runtime-equivalent, must not fire."""
    findings = _analyze(
        """
        def step(accelerator, x, y):
            if accelerator.is_main_process:
                out = accelerator.gather(x)
            else:
                out = accelerator.gather(y)
            accelerator.wait_for_everyone()
            return out
        """
    )
    assert findings == []


def test_tpu404_divergent_break_skips_barrier():
    findings = _analyze(
        """
        def loop(accelerator, batches):
            for batch in batches:
                if accelerator.process_index > 0:
                    break
                accelerator.backward(batch)
            accelerator.wait_for_everyone()
        """
    )
    assert "TPU404" in _rules(findings)
    assert RULES["TPU404"].severity == WARNING
    assert "wait_for_everyone" in findings[0].message


def test_tpu405_unguarded_write_and_guarded_clean():
    dirty = _analyze(
        """
        import os


        def finish(accelerator, payload):
            os.makedirs("out")
            with open("out/summary.json", "w") as fh:
                fh.write(payload)
            accelerator.wait_for_everyone()
        """
    )
    assert _rules(dirty) == ["TPU405", "TPU405"]
    guarded = _analyze(
        """
        import os


        def finish(accelerator, payload):
            if accelerator.is_main_process:
                os.makedirs("out")
                with open("out/summary.json", "w") as fh:
                    fh.write(payload)
            accelerator.wait_for_everyone()
        """
    )
    assert guarded == []


def test_tpu405_needs_rank_aware_scope():
    """a pure IO helper (no rank vocabulary) is the caller's problem —
    TPU405 stays quiet there."""
    findings = _analyze(
        """
        def dump(path, payload):
            with open(path, "w") as fh:
                fh.write(payload)
        """
    )
    assert findings == []


def test_tpu405_solo_decorator_guards_writes():
    findings = _analyze(
        """
        import os

        from accelerate_tpu.state import on_main_process


        @on_main_process
        def publish(run_dir, payload):
            os.makedirs(run_dir)
            with open(run_dir + "/out.json", "w") as fh:
                fh.write(payload)
        """
    )
    assert findings == []


def test_rank_namespaced_write_is_clean():
    """writes to a path derived from process_index can't collide."""
    findings = _analyze(
        """
        def dump(accelerator, payload):
            path = f"out/rank{accelerator.process_index}.json"
            with open(path, "w") as fh:
                fh.write(payload)
            accelerator.wait_for_everyone()
        """
    )
    assert findings == []


def test_interprocedural_one_level():
    """calls are followed one level deep within the file: a guarded call
    to a helper that syncs is the same deadlock."""
    findings = _analyze(
        """
        def sync_all(accelerator, x):
            return accelerator.gather(x)


        def f(accelerator, x):
            if accelerator.is_main_process:
                return sync_all(accelerator, x)
            return None
        """
    )
    assert "TPU401" in _rules(findings)


def test_save_state_commit_barriers_uniform():
    """the PR-4 atomic commit protocol (save_state = enter+commit
    barriers) is rank-uniform when called unconditionally, deadlock when
    main-only."""
    clean = _analyze(
        """
        def f(accelerator):
            accelerator.save_state("ckpt")
        """
    )
    assert clean == []
    dirty = _analyze(
        """
        def f(accelerator):
            if accelerator.is_main_process:
                accelerator.save_state("ckpt")
        """
    )
    assert "TPU401" in _rules(dirty)


def test_entry_restriction_and_paths(tmp_path):
    src = textwrap.dedent(
        """
        \"\"\"Fixture module.\"\"\"


        def good(accelerator, x):
            return accelerator.gather(x)


        def bad(accelerator, x):
            if accelerator.is_main_process:
                return accelerator.gather(x)
            return None
        """
    )
    mod = tmp_path / "train.py"
    mod.write_text(src)
    assert analyze_file(mod, entry="good") == []
    assert "TPU401" in _rules(analyze_file(mod, entry="bad"))
    # file.py::fn targets through analyze_paths
    assert analyze_paths([f"{mod}::good"]) == []
    assert "TPU401" in _rules(analyze_paths([f"{mod}::bad"]))
    assert "TPU401" in _rules(analyze_paths([str(tmp_path)]))


def test_inline_suppression():
    findings = _analyze(
        """
        def f(accelerator, metrics):
            if accelerator.is_main_process:
                return accelerator.gather(metrics)  # tpu-lint: disable=TPU401
            return None
        """
    )
    assert findings == []


def test_selfcheck_fixtures_fire_and_clean_is_clean():
    from accelerate_tpu.analysis.selfcheck import run_divergence_selfcheck

    ok, lines = run_divergence_selfcheck()
    assert ok, "\n".join(lines)
    assert sum("detected" in line for line in lines) == 5
    assert any("zero findings" in line for line in lines)


# --------------------------------------------------------------------- #
# effect-summary tables
# --------------------------------------------------------------------- #


def test_collectives_effect_table_covers_module_surface():
    """every public symbol in parallel.collectives must carry a divergence
    model — a new collective cannot silently bypass the analyzer."""
    import inspect

    from accelerate_tpu.parallel import collectives

    public = {
        name
        for name, obj in vars(collectives).items()
        if not name.startswith("_") and inspect.isfunction(obj) and obj.__module__ == collectives.__name__
    }
    assert public, "parallel.collectives exposes no functions?"
    missing = public - set(COLLECTIVE_EFFECTS)
    assert missing == set(), f"collectives without a divergence model: {sorted(missing)}"


def test_accelerator_effect_table_semantics():
    assert ACCELERATOR_EFFECTS["save_state"].events == ("barrier:save_state/enter", "barrier:save_state/commit")
    assert ACCELERATOR_EFFECTS["wait_for_everyone"].events == ("barrier:wait_for_everyone",)
    assert ACCELERATOR_EFFECTS["prepare"].events == ()  # purely local
    assert COLLECTIVE_EFFECTS["axis_index"].returns == DIVERGENT


def test_simulator_traces_shape():
    """k ranks, two worlds per entry, events carry line numbers."""
    sim = _sim(
        """
        def f(accelerator, x):
            accelerator.wait_for_everyone()
            return accelerator.gather(x)
        """,
        n_ranks=4,
    )
    results = [r for r in sim.run(entry="f")]
    assert len(results) == 2  # then + else worlds
    for res in results:
        assert len(res.traces) == 4
        for tr in res.traces:
            names = [(e.kind, e.name) for e in tr.events if e.sync]
            assert names == [("barrier", "wait_for_everyone"), ("collective", "gather")]
            assert all(e.line > 0 for e in tr.events)


# --------------------------------------------------------------------- #
# .tpulint.toml project configuration
# --------------------------------------------------------------------- #


def test_minimal_toml_parser_matches_schema():
    doc = _parse_minimal_toml(
        textwrap.dedent(
            """
            # comment
            [lint]
            format = "sarif"     # trailing comment
            disable = ["TPU103", "TPU405"]

            [divergence]
            ranks = 5

            [[suppress]]
            path = "examples/*"
            rules = ["TPU405"]

            [[suppress]]
            path = "vendor/"
            """
        )
    )
    assert doc["lint"]["format"] == "sarif"
    assert doc["lint"]["disable"] == ["TPU103", "TPU405"]
    assert doc["divergence"]["ranks"] == 5
    assert len(doc["suppress"]) == 2
    assert doc["suppress"][1] == {"path": "vendor/"}


def test_project_config_discovery_and_merge(tmp_path):
    (tmp_path / ".tpulint.toml").write_text(
        textwrap.dedent(
            """
            [lint]
            format = "json"
            disable = ["TPU404"]

            [divergence]
            ranks = 4

            [[suppress]]
            path = "vendored/*"
            """
        )
    )
    sub = tmp_path / "vendored"
    sub.mkdir()
    assert find_project_config(sub) == str(tmp_path / ".tpulint.toml")
    cfg = load_project_config(sub)
    assert cfg.resolve_format(None) == "json"
    assert cfg.resolve_format("text") == "text"  # CLI flag wins
    assert cfg.resolve_ranks(None) == 4
    assert cfg.merge_ignore(("tpu103",)) == frozenset({"TPU103", "TPU404"})

    from accelerate_tpu.analysis.rules import Finding

    kept = cfg.apply_suppressions(
        [
            Finding("TPU401", "x", path=str(sub / "a.py"), line=1),
            Finding("TPU401", "y", path=str(tmp_path / "train.py"), line=1),
        ]
    )
    assert [f.message for f in kept] == ["y"]


def test_project_config_absent_is_default(tmp_path):
    cfg = load_project_config(tmp_path)
    assert cfg == ProjectConfig()
    assert cfg.resolve_format(None) == "text"
    assert cfg.resolve_ranks(None) == 3


def test_repo_config_parses():
    """the checked-in .tpulint.toml must stay loadable."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = load_project_config(repo)
    assert cfg.path and cfg.path.endswith(".tpulint.toml")
    assert cfg.resolve_format(None) == "text"
    assert cfg.resolve_ranks(None) == 3
    assert cfg.disable == frozenset()


# --------------------------------------------------------------------- #
# CLI + SARIF + the repo's own tree
# --------------------------------------------------------------------- #


@pytest.fixture
def bad_script(tmp_path):
    p = tmp_path / "train.py"
    p.write_text(
        textwrap.dedent(
            """
            \"\"\"Seeded multi-host deadlock.\"\"\"


            def evaluate(accelerator, metrics):
                if accelerator.is_main_process:
                    return accelerator.gather(metrics)
                return None
            """
        )
    )
    return p


def test_cli_divergence_detects_and_exits_nonzero(bad_script):
    result = run_cli("divergence", str(bad_script))
    assert result.returncode == 1, result.stdout + result.stderr
    assert f"{bad_script}:7: TPU401" in result.stdout  # path:line: TPUxxx contract
    assert "1 error(s)" in result.stdout


def test_cli_divergence_json(bad_script):
    result = run_cli("divergence", str(bad_script), "--format", "json")
    payload = json.loads(result.stdout)
    assert [f["rule"] for f in payload] == ["TPU401"]
    assert payload[0]["severity"] == "error"
    assert payload[0]["path"] == str(bad_script)


def test_cli_divergence_sarif(bad_script):
    result = run_cli("divergence", str(bad_script), "--format", "sarif")
    doc = json.loads(result.stdout)
    assert doc["version"] == "2.1.0"
    results = doc["runs"][0]["results"]
    assert results[0]["ruleId"] == "TPU401" and results[0]["level"] == "error"
    assert results[0]["locations"][0]["physicalLocation"]["artifactLocation"]["uri"] == str(bad_script)
    rules = {r["id"]: r for r in doc["runs"][0]["tool"]["driver"]["rules"]}
    assert rules["TPU401"]["properties"]["tier"] == "divergence"


def test_cli_divergence_entry_target_and_ranks(bad_script):
    ok = run_cli("divergence", f"{bad_script}::missing_entry")
    assert ok.returncode == 0  # no such entry -> nothing analyzed, no findings
    bad = run_cli("divergence", f"{bad_script}::evaluate", "--ranks", "5")
    assert bad.returncode == 1
    assert "TPU401" in bad.stdout


def test_cli_divergence_selfcheck():
    result = run_cli("divergence", "--selfcheck")
    assert result.returncode == 0, result.stdout + result.stderr
    assert result.stdout.count("detected") == 5
    assert "zero findings" in result.stdout


def test_cli_divergence_config_defaults(bad_script, tmp_path):
    (tmp_path / ".tpulint.toml").write_text('[lint]\nformat = "json"\ndisable = ["TPU401"]\n')
    result = run_cli("divergence", str(bad_script), cwd=tmp_path)
    assert result.returncode == 0, result.stdout + result.stderr
    assert json.loads(result.stdout) == []  # json default + TPU401 disabled


def test_cli_flightcheck_sarif():
    """--format sarif wired through flight-check (shared reporter)."""
    result = run_cli(
        "flight-check",
        "examples/by_feature/flight_check.py::train_step",
        "--mesh", "data=8", "--donate", "0", "--format", "sarif",
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert result.returncode == 0, result.stdout + result.stderr
    doc = json.loads(result.stdout)
    assert doc["version"] == "2.1.0"
    assert doc["runs"][0]["tool"]["driver"]["name"] == "accelerate-tpu-lint"


def test_merge_sarif_script(tmp_path, bad_script):
    a = run_cli("divergence", str(bad_script), "--format", "sarif").stdout
    (tmp_path / "a.sarif").write_text(a)
    (tmp_path / "b.sarif").write_text(a)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    result = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "merge_sarif.py"),
         str(tmp_path / "a.sarif"), str(tmp_path / "b.sarif"),
         str(tmp_path / "missing.sarif"), "-o", str(tmp_path / "merged.sarif")],
        capture_output=True, text=True, env=CPU_ENV,
    )
    assert result.returncode == 0, result.stderr
    merged = json.loads((tmp_path / "merged.sarif").read_text())
    assert len(merged["runs"]) == 2  # missing input skipped, not fatal


def test_accelerator_lint_runs_divergence_on_calling_module(tmp_path):
    """Accelerator.lint analyzes the module that called it."""
    script = tmp_path / "lint_me.py"
    script.write_text(
        textwrap.dedent(
            """
            \"\"\"Fixture: calls Accelerator.lint from a module with a seeded deadlock.\"\"\"
            import jax
            import jax.numpy as jnp

            from accelerate_tpu import Accelerator


            def evaluate(accelerator, metrics):
                if accelerator.is_main_process:
                    return accelerator.gather(metrics)
                return None


            def step(x):
                return x * 2


            acc = Accelerator()
            findings = acc.lint(step, jax.ShapeDtypeStruct((8,), jnp.float32))
            print("RULES", sorted({f.rule for f in findings}))
            quiet = acc.lint(step, jax.ShapeDtypeStruct((8,), jnp.float32), divergence=False)
            print("QUIET", sorted({f.rule for f in quiet}))
            """
        )
    )
    result = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True, env=CPU_ENV, timeout=240,
    )
    assert result.returncode == 0, result.stderr
    assert "RULES ['TPU401']" in result.stdout
    assert "QUIET []" in result.stdout


def test_repo_tree_is_divergence_clean():
    """dogfood: the package's own tree (checkpointing, tracking, ft/,
    accelerator, commands) must carry zero TPU4xx errors — the make lint
    strict gate."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = analyze_paths([os.path.join(repo, "accelerate_tpu")])
    errors = [f for f in findings if f.is_error]
    assert errors == [], "\n".join(f"{f.path}:{f.line}: {f.rule} {f.message}" for f in errors)
    warnings = [f for f in findings if not f.is_error]
    assert warnings == [], "\n".join(f"{f.path}:{f.line}: {f.rule} {f.message}" for f in warnings)
