"""Unit tests for the small support modules (reference analogues:
tests/test_scheduler.py, test_optimizer.py, test_memory_utils.py,
test_logging.py, test_kwargs_handlers.py)."""

import logging
import re

import jax
import numpy as np
import optax
import pytest

from accelerate_tpu.scheduler import AcceleratedScheduler
from accelerate_tpu.utils.memory import (
    find_executable_batch_size,
    release_memory,
    should_reduce_batch_size,
)
from accelerate_tpu.utils.random import key_for_step, set_seed, synchronize_rng_states


# -------------------------- memory --------------------------------------


def test_find_executable_batch_size_halves_on_oom():
    attempts = []

    @find_executable_batch_size(starting_batch_size=64)
    def train(batch_size):
        attempts.append(batch_size)
        if batch_size > 16:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating")
        return batch_size

    assert train() == 16
    assert attempts == [64, 32, 16]


def test_find_executable_batch_size_reraises_non_oom():
    @find_executable_batch_size(starting_batch_size=8)
    def train(batch_size):
        raise ValueError("not an oom")

    with pytest.raises(ValueError):
        train()


def test_find_executable_batch_size_exhausted():
    @find_executable_batch_size(starting_batch_size=2)
    def train(batch_size):
        raise RuntimeError("RESOURCE_EXHAUSTED")

    with pytest.raises(RuntimeError, match="No executable batch size|RESOURCE_EXHAUSTED"):
        train()


def test_should_reduce_batch_size_patterns():
    assert should_reduce_batch_size(RuntimeError("RESOURCE_EXHAUSTED: HBM"))
    assert should_reduce_batch_size(MemoryError("Ran out of memory"))
    assert not should_reduce_batch_size(ValueError("shape mismatch"))


def test_release_memory_rebinds_to_none():
    a, b = np.ones(4), np.ones(4)
    a, b = release_memory(a, b)
    assert a is None and b is None


# -------------------------- scheduler -----------------------------------


def test_scheduler_scales_by_data_shards_and_roundtrips():
    sched = AcceleratedScheduler(optax.linear_schedule(1.0, 0.0, 100), optimizers=None)
    n = sched._data_shards()
    sched.step()
    assert sched.step_count == n
    lr = sched.get_last_lr()[0]
    assert lr == pytest.approx(1.0 - n / 100)
    state = sched.state_dict()
    sched2 = AcceleratedScheduler(optax.linear_schedule(1.0, 0.0, 100), optimizers=None)
    sched2.load_state_dict(state)
    assert sched2.step_count == sched.step_count


def test_scheduler_split_batches_no_scaling():
    sched = AcceleratedScheduler(
        optax.linear_schedule(1.0, 0.0, 100), optimizers=None, split_batches=True
    )
    sched.step()
    assert sched.step_count == 1


# -------------------------- rng -----------------------------------------


def test_set_seed_reproducible_key_chain():
    set_seed(123)
    k1 = key_for_step(5)
    set_seed(123)
    k2 = key_for_step(5)
    assert jax.random.uniform(k1) == jax.random.uniform(k2)
    k3 = key_for_step(6)
    assert jax.random.uniform(k2) != jax.random.uniform(k3)


def test_key_for_step_extra_folds_differ():
    set_seed(0)
    base = key_for_step(1)
    folded = key_for_step(1, 7)
    assert jax.random.uniform(base) != jax.random.uniform(folded)


def test_set_seed_seeds_python_and_numpy():
    import random as pyrandom

    set_seed(99)
    a = (pyrandom.random(), np.random.rand())
    set_seed(99)
    b = (pyrandom.random(), np.random.rand())
    assert a == b


def test_synchronize_rng_states_runs():
    synchronize_rng_states(["numpy", "python"])  # single process: no-op path


# -------------------------- logging -------------------------------------


def test_get_logger_main_process_only(caplog):
    from accelerate_tpu.logging import get_logger

    logger = get_logger("accelerate_tpu.test_unit")
    with caplog.at_level(logging.INFO, logger="accelerate_tpu.test_unit"):
        logger.info("visible", main_process_only=True)
    assert any("visible" in r.message for r in caplog.records)


def test_warning_once_dedups(caplog):
    from accelerate_tpu.logging import get_logger

    logger = get_logger("accelerate_tpu.test_unit2")
    with caplog.at_level(logging.WARNING, logger="accelerate_tpu.test_unit2"):
        logger.warning_once("only once please")
        logger.warning_once("only once please")
    assert sum("only once please" in r.message for r in caplog.records) == 1


# -------------------------- kwargs / dataclasses ------------------------


def test_mesh_config_from_env(monkeypatch):
    from accelerate_tpu.parallel.mesh import MeshConfig

    monkeypatch.setenv("ACCELERATE_MESH_DATA", "2")
    monkeypatch.setenv("ACCELERATE_MESH_TENSOR", "4")
    cfg = MeshConfig.from_env()
    assert cfg.data == 2 and cfg.tensor == 4


def test_precision_type_rejects_unknown():
    from accelerate_tpu.utils.dataclasses import PrecisionType

    with pytest.raises(ValueError):
        PrecisionType("fp64x")


def test_gradient_accumulation_plugin_validation():
    from accelerate_tpu.utils.dataclasses import GradientAccumulationPlugin

    plugin = GradientAccumulationPlugin(num_steps=4)
    assert plugin.num_steps == 4
    with pytest.raises((ValueError, TypeError)):
        GradientAccumulationPlugin(num_steps=0)


def test_get_free_port_is_bindable():
    """get_free_port returns a port another socket can immediately bind
    (reference: utils/other.py get_free_port)."""
    import socket

    from accelerate_tpu.utils.environment import get_free_port

    port = get_free_port()
    assert 1024 <= port <= 65535
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", port))  # must not raise


def test_launch_resolves_port_once_for_multiprocess(tmp_path):
    """A 2-process launch without --main_process_port picks one free port
    for the whole group (per-rank resolution would deadlock rendezvous)."""
    import os
    import subprocess
    import sys

    script = tmp_path / "s.py"
    script.write_text(
        "from accelerate_tpu import Accelerator\n"
        "acc = Accelerator()\n"
        "assert acc.num_processes == 2\n"
        "print('PORT_OK', acc.process_index)\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    result = subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.cli", "launch",
         "--num_processes", "2", "--cpu", "--fake_devices", "4", str(script)],
        capture_output=True, text=True, env=env, timeout=240,
    )
    assert result.returncode == 0, result.stderr + result.stdout
    assert result.stdout.count("PORT_OK") >= 1


def test_api_docs_generator_is_deterministic():
    """scripts/gen_api_docs.py must be reproducible (no memory-address
    reprs) and cover the core public surface."""
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "gen_api_docs", pathlib.Path(__file__).parent.parent / "scripts" / "gen_api_docs.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    page = mod.render_module("accelerate_tpu.accelerator")
    assert page == mod.render_module("accelerate_tpu.accelerator")  # deterministic
    assert not re.search(r"0x[0-9a-f]{6,}", page)  # an address, not the word "TPU10xx"
    assert "build_train_step" in page and "gather_for_metrics" in page
    ops_page = mod.render_module("accelerate_tpu.ops.qdense")
    assert "QuantDense" in ops_page and not re.search(r"0x[0-9a-f]{6,}", ops_page)
