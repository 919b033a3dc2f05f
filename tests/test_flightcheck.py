"""SPMD flight-check (``analysis.flightcheck`` + ``analysis.costmodel``):
peak-HBM liveness estimates, the collective cost model, the TPU3xx safety
rules with their negative (clean-code) paths, and the CLI/Accelerator
surfaces."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from accelerate_tpu.analysis import flight_check
from accelerate_tpu.analysis.costmodel import collect_traffic, price_collective
from accelerate_tpu.parallel.mesh import DCN, ICI, MeshConfig, axis_transport, dcn_axes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(report):
    return [f.rule for f in report.findings]


@pytest.fixture
def mesh1():
    return MeshConfig(data=1).build(jax.devices()[:1])


# --------------------------------------------------------------------- #
# cost model units
# --------------------------------------------------------------------- #


def test_price_collective_allreduce_ring_bytes(mesh8):
    rec = price_collective("psum", ("data",), 1024, mesh8)
    assert rec.group_size == 8
    assert rec.wire_bytes == int(1024 * 2 * 7 / 8)
    assert rec.transport == ICI
    assert rec.time_us("v5e") > 0


def test_price_collective_trivial_axis_and_unknown_prim(mesh8):
    assert price_collective("psum", ("tensor",), 1024, mesh8) is None  # size-1 axis
    assert price_collective("add", ("data",), 1024, mesh8) is None


def test_price_collective_dcn_classification(mesh8):
    rec = price_collective("all_gather", ("data",), 1024, mesh8, dcn=("data",))
    assert rec.transport == DCN
    assert rec.wire_bytes == 1024 * 7
    # DCN time dominates the same bytes over ICI
    assert rec.time_us("v5e") > price_collective("all_gather", ("data",), 1024, mesh8).time_us("v5e")


def test_axis_transport_env_protocol(mesh8, monkeypatch):
    assert axis_transport(mesh8, "data") == ICI
    monkeypatch.setenv("ACCELERATE_MESH_DCN_AXES", "data,pipe")
    assert dcn_axes() == ("data", "pipe")
    assert axis_transport(mesh8, "data") == DCN
    assert axis_transport(mesh8, "pipe") == ICI  # size-1 axis carries nothing


def test_collect_traffic_scan_multiplier(mesh8):
    from jax import shard_map

    def body(x):
        def step(c, _):
            return jax.lax.psum(c, "data"), None

        out, _ = jax.lax.scan(step, x, None, length=4)
        return out

    wrapped = shard_map(body, mesh=mesh8, in_specs=P(), out_specs=P(), check_vma=False)
    closed = jax.make_jaxpr(wrapped)(jax.ShapeDtypeStruct((16, 16), jnp.float32))
    report = collect_traffic(closed.jaxpr, mesh8)
    psums = [r for r in report.records if r.primitive == "psum"]
    assert psums and psums[0].count == 4
    assert report.total_wire_bytes == psums[0].wire_bytes
    assert report.bytes_by_transport()[ICI] == report.total_wire_bytes


# --------------------------------------------------------------------- #
# peak-HBM liveness estimate
# --------------------------------------------------------------------- #


def test_peak_hbm_within_2x_of_live_buffers_on_1_device(mesh1):
    """Acceptance bound: on a 1-device mesh the estimate must be within 2x
    of the sum of the obviously-live buffers (args + outputs)."""

    def step(params, batch):
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        new = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)
        return new, batch.sum()

    params = {"w": jax.ShapeDtypeStruct((256, 256), jnp.float32)}
    batch = jax.ShapeDtypeStruct((32, 256), jnp.float32)
    report = flight_check(step, params, batch, mesh=mesh1)
    live = 256 * 256 * 4 * 2 + 32 * 256 * 4  # params + new params + batch
    assert live <= report.peak_hbm_bytes <= 2 * live
    assert report.param_bytes == 256 * 256 * 4 + 32 * 256 * 4
    assert report.output_bytes >= 256 * 256 * 4


def test_peak_hbm_example_step_within_2x(mesh1):
    """The shipped example's step function, per the acceptance criterion."""
    sys.path.insert(0, os.path.join(REPO, "examples", "by_feature"))
    try:
        import flight_check as example
    finally:
        sys.path.pop(0)
    report = flight_check(example.train_step, *example.train_step_sample_args(), mesh=mesh1)
    args_bytes = sum(
        int(np.prod(l.shape or (1,))) * l.dtype.itemsize
        for a in example.train_step_sample_args()
        for l in jax.tree_util.tree_leaves(a)
    )
    live = args_bytes + report.output_bytes
    assert live <= report.peak_hbm_bytes <= 2 * live


def test_donation_lowers_peak(mesh1):
    """Donated read-and-replace params alias in place; the undonated step
    must account both copies."""

    def step(params, batch):
        new = jax.tree_util.tree_map(lambda p: p - 0.1, params)
        return new, batch.sum()

    params = {"w": jax.ShapeDtypeStruct((512, 512), jnp.float32)}
    batch = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    plain = flight_check(step, params, batch, mesh=mesh1)
    donated = flight_check(step, params, batch, mesh=mesh1, donate_argnums=(0,))
    assert donated.peak_hbm_bytes < plain.peak_hbm_bytes
    assert donated.donated_bytes == 512 * 512 * 4


def test_sharded_inputs_divide_per_device_bytes(mesh8):
    def step(x):
        return x * 2.0

    x = jax.device_put(np.zeros((64, 128), np.float32), NamedSharding(mesh8, P("data")))
    sharded = flight_check(step, x, mesh=mesh8)
    replicated = flight_check(step, jax.ShapeDtypeStruct((64, 128), jnp.float32), mesh=mesh8)
    assert sharded.peak_hbm_bytes * 8 == replicated.peak_hbm_bytes


def test_report_surfaces(mesh1):
    def step(x):
        return x.sum()

    report = flight_check(step, jax.ShapeDtypeStruct((8, 8), jnp.float32), mesh=mesh1)
    text = report.render_text()
    assert "peak HBM / device" in text and "findings: none" in text
    d = report.as_dict()
    assert d["peak_hbm_bytes_per_device"] == report.peak_hbm_bytes
    assert d["findings"] == []
    assert report.fits(16.0) and not report.fits(1e-9)
    assert report.ok


def test_flight_check_requires_mesh():
    with pytest.raises(ValueError, match="mesh"):
        flight_check(lambda x: x, jnp.ones(4))


# --------------------------------------------------------------------- #
# TPU301 — collective under value-dependent control flow
# --------------------------------------------------------------------- #


def test_tpu301_collective_under_cond(mesh8):
    def step(x):
        return jax.lax.cond(x.sum() > 0, lambda v: jax.lax.psum(v, "data"), lambda v: v, x)

    report = flight_check(step, jax.ShapeDtypeStruct((8, 16), jnp.float32), mesh=mesh8)
    assert "TPU301" in _rules(report)
    assert not report.ok  # error severity


def test_tpu301_collective_under_while(mesh8):
    def step(x):
        def cond(c):
            return c.sum() < 100.0

        def body(c):
            return jax.lax.psum(c, "data") + 1.0

        return jax.lax.while_loop(cond, body, x)

    report = flight_check(step, jax.ShapeDtypeStruct((8,), jnp.float32), mesh=mesh8)
    assert "TPU301" in _rules(report)


def test_tpu301_scan_and_straightline_are_clean(mesh8):
    def step(x):
        def body(c, _):
            return jax.lax.psum(c, "data"), None

        out, _ = jax.lax.scan(body, x, None, length=3)
        return out + jax.lax.psum(x, "data")

    report = flight_check(step, jax.ShapeDtypeStruct((8,), jnp.float32), mesh=mesh8)
    assert "TPU301" not in _rules(report)


# --------------------------------------------------------------------- #
# TPU302 — implicit reshard
# --------------------------------------------------------------------- #


def test_tpu302_conflicting_constraints(mesh8):
    def step(x):
        x = jax.lax.with_sharding_constraint(x, NamedSharding(mesh8, P("data", None)))
        x = x * 2.0
        x = jax.lax.with_sharding_constraint(x, NamedSharding(mesh8, P(None, "data")))
        return x.sum()

    report = flight_check(step, jax.ShapeDtypeStruct((64, 64), jnp.float32), mesh=mesh8)
    assert "TPU302" in _rules(report)


def test_tpu302_from_input_sharding(mesh8):
    def step(x):
        return jax.lax.with_sharding_constraint(x * 1.0, NamedSharding(mesh8, P(None, "data"))).sum()

    x = jax.device_put(np.zeros((64, 64), np.float32), NamedSharding(mesh8, P("data", None)))
    report = flight_check(step, x, mesh=mesh8)
    assert "TPU302" in _rules(report)


def test_tpu302_consistent_constraints_are_clean(mesh8):
    def step(x):
        x = jax.lax.with_sharding_constraint(x, NamedSharding(mesh8, P("data", None)))
        x = x * 2.0
        x = jax.lax.with_sharding_constraint(x, NamedSharding(mesh8, P("data", None)))
        return x.sum()

    report = flight_check(step, jax.ShapeDtypeStruct((64, 64), jnp.float32), mesh=mesh8)
    assert "TPU302" not in _rules(report)


# --------------------------------------------------------------------- #
# TPU303 — donation defeated by a late read
# --------------------------------------------------------------------- #


def test_tpu303_late_read_after_aliased_output(mesh8):
    def step(params, batch):
        new = jax.tree_util.tree_map(lambda p: p - 0.1, params)
        loss = (params["w"] * batch).sum()  # reads params after `new` exists
        return new, loss

    report = flight_check(
        step,
        {"w": jax.ShapeDtypeStruct((64, 64), jnp.float32)},
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        mesh=mesh8,
        donate_argnums=(0,),
    )
    assert "TPU303" in _rules(report)


def test_tpu303_clean_when_reads_precede_update(mesh8):
    def step(params, batch):
        loss = (params["w"] * batch).sum()
        new = jax.tree_util.tree_map(lambda p: p - 0.1, params)
        return new, loss

    report = flight_check(
        step,
        {"w": jax.ShapeDtypeStruct((64, 64), jnp.float32)},
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        mesh=mesh8,
        donate_argnums=(0,),
    )
    assert "TPU303" not in _rules(report)


def test_tpu303_clean_without_donation(mesh8):
    def step(params, batch):
        new = jax.tree_util.tree_map(lambda p: p - 0.1, params)
        return new, (params["w"] * batch).sum()

    report = flight_check(
        step,
        {"w": jax.ShapeDtypeStruct((64, 64), jnp.float32)},
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        mesh=mesh8,
    )
    assert "TPU303" not in _rules(report)


def test_select_ignore_filtering(mesh8):
    def step(x):
        return jax.lax.cond(x.sum() > 0, lambda v: jax.lax.psum(v, "data"), lambda v: v, x)

    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert _rules(flight_check(step, x, mesh=mesh8, ignore=("TPU301",))) == []
    assert "TPU301" in _rules(flight_check(step, x, mesh=mesh8, select=("TPU301",)))


# --------------------------------------------------------------------- #
# surfaces: Accelerator hook + CLI
# --------------------------------------------------------------------- #


def test_accelerator_flight_check_hook():
    from accelerate_tpu import Accelerator

    acc = Accelerator()

    def step(params, batch):
        new = jax.tree_util.tree_map(lambda p: p - 0.1, params)
        return new, batch.sum()

    report = acc.flight_check(
        step,
        {"w": jax.ShapeDtypeStruct((64, 64), jnp.float32)},
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
    )
    assert report.peak_hbm_bytes > 0
    assert report.ok


CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _run_cli(*args, timeout=240):
    return subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.cli", *args],
        capture_output=True, text=True, env=CPU_ENV, timeout=timeout, cwd=REPO,
    )


@pytest.mark.slow
def test_cli_flight_check_example_step():
    result = _run_cli(
        "flight-check", "examples/by_feature/flight_check.py::train_step",
        "--mesh", "data=8", "--donate", "0",
    )
    assert result.returncode == 0, result.stderr
    assert "peak HBM / device" in result.stdout
    assert "psum" in result.stdout  # the example's pmean is priced


@pytest.mark.slow
def test_cli_flight_check_selfcheck():
    result = _run_cli("flight-check", "--selfcheck")
    assert result.returncode == 0, result.stderr
    for rule in ("TPU301", "TPU302", "TPU303"):
        assert f"{rule}: detected" in result.stdout


@pytest.mark.slow
def test_cli_flight_check_arg_specs_and_json(tmp_path):
    import json
    import textwrap

    mod = tmp_path / "mystep.py"
    mod.write_text(
        textwrap.dedent(
            '''
            """Fixture step for the flight-check CLI."""
            import jax.numpy as jnp


            def step(w, x):
                return (x @ w).sum()
            '''
        )
    )
    result = _run_cli(
        "flight-check", f"{mod}::step",
        "--arg", "f32[128,64]", "--arg", "bf16[32,128]",
        "--format", "json",
    )
    assert result.returncode == 0, result.stderr
    payload = json.loads(result.stdout)
    assert payload["peak_hbm_bytes_per_device"] >= 128 * 64 * 4 + 32 * 128 * 2
