"""Run every by_feature example end-to-end on the CPU fake mesh
(reference analogue: tests/test_examples.py, 308 LoC).

The whole module is the ``slow`` tier: every test is a fresh subprocess
(own jax init + compiles). Run with ``pytest -m slow`` / ``make test-all``.
"""

import os
import pathlib
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

EXAMPLES_DIR = pathlib.Path(__file__).parent.parent / "examples" / "by_feature"
EXAMPLES = sorted(p.name for p in EXAMPLES_DIR.glob("*.py") if not p.name.startswith("_"))

REPO_ROOT = str(pathlib.Path(__file__).parent.parent)

ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8",
    # examples run from examples/by_feature; the package lives at the repo
    # root, which is not on sys.path for a subprocess
    "PYTHONPATH": os.pathsep.join(p for p in (REPO_ROOT, os.environ.get("PYTHONPATH", "")) if p),
}


@pytest.mark.parametrize("example", EXAMPLES)
def test_example_runs(example):
    result = subprocess.run(
        [sys.executable, example],
        cwd=EXAMPLES_DIR,
        env=ENV,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert result.returncode == 0, f"{example} failed:\n{result.stdout}\n{result.stderr}"


def test_all_examples_discovered():
    # guard against the glob silently matching nothing
    assert len(EXAMPLES) >= 8, EXAMPLES


@pytest.mark.parametrize("example", ["nlp_example.py", "cv_example.py"])
def test_root_example_runs_tiny(example):
    """The two canonical examples (reference: examples/nlp_example.py,
    examples/cv_example.py) in CI size."""
    result = subprocess.run(
        [sys.executable, example, "--tiny", "--num_epochs", "1"],
        cwd=EXAMPLES_DIR.parent,
        env=ENV,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert result.returncode == 0, f"{example} failed:\n{result.stdout}\n{result.stderr}"


@pytest.mark.parametrize("example", ["complete_nlp_example.py", "complete_cv_example.py"])
def test_complete_example_checkpoint_and_resume(example, tmp_path):
    """Kitchen-sink examples (reference: examples/complete_*_example.py):
    train with tracking + epoch checkpointing, then resume from the epoch-0
    checkpoint and finish."""
    out = tmp_path / "out"
    common = ["--tiny", "--num_epochs", "2", "--with_tracking", "--output_dir", str(out)]
    run = subprocess.run(
        [sys.executable, example, *common, "--checkpointing_steps", "epoch"],
        cwd=EXAMPLES_DIR.parent,
        env=ENV,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert run.returncode == 0, f"{example} failed:\n{run.stdout}\n{run.stderr}"
    assert (out / "epoch_0").is_dir() and (out / "final").is_dir()

    resume = subprocess.run(
        [sys.executable, example, *common, "--resume_from_checkpoint", str(out / "epoch_0")],
        cwd=EXAMPLES_DIR.parent,
        env=ENV,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert resume.returncode == 0, f"{example} resume failed:\n{resume.stdout}\n{resume.stderr}"
    assert "resumed from" in resume.stdout
