"""Kernel tier (``analysis.kernelmodel`` + ``analysis.kernel_rules`` +
``kernels.contracts``): site extraction from traced pallas calls, the
TPU1001–1006 rules with their clean twins, interpret-mode parity of the
shipped reference kernel against the stock lax path, the contract hooks
in perfmodel/flight-check/numerics, the warn-once blindness satellite,
and the CLI surfaces (paths gate, ``--changed``, ``--selfcheck``)."""

import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pl = pytest.importorskip("jax.experimental.pallas")

from accelerate_tpu.analysis import kernel_check, scan_paths
from accelerate_tpu.analysis.kernelmodel import counted_cost, vmem_occupancy_bytes
from accelerate_tpu.kernels import block_accumulate, block_matmul_softmax
from accelerate_tpu.kernels.contracts import (
    KernelCostSpec,
    UnknownOpWarning,
    register_kernel_cost,
    reset_unknown_op_warnings,
    unregister_kernel_cost,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}

# the reference decode-logits shape, hand-computed (see kernels/reference.py):
# 2·B·D·N MXU + 14·B·N VPU flops; per-step blocks (8·128 x + 128·128 w + 8·128
# out, f32) streamed over 2 grid steps for HBM and double-buffered for VMEM.
B, D, N = 16, 128, 128
REF_FLOPS = 2 * B * D * N + 14 * B * N  # 552_960
REF_HBM = (8 * D * 4 + D * N * 4 + 8 * N * 4) * 2  # 147_456
REF_VMEM_PEAK = 2 * (8 * D * 4 + D * N * 4 + 8 * N * 4) + 8 * N * 4  # 151_552


def _xw(dtype=jnp.float32):
    x = np.linspace(-1.0, 1.0, B * D, dtype=np.float32).reshape(B, D)
    w = np.linspace(-0.5, 0.5, D * N, dtype=np.float32).reshape(D, N)
    return jnp.asarray(x, dtype), jnp.asarray(w, dtype)


def _sds():
    return (
        jax.ShapeDtypeStruct((B, D), jnp.float32),
        jax.ShapeDtypeStruct((D, N), jnp.float32),
    )


def _softmax_step(x, w):
    return block_matmul_softmax(x, w)


def _rules(report):
    return [f.rule for f in report.findings]


# --------------------------------------------------------------------- #
# interpret-mode parity: the reference kernel IS the stock lax path
# --------------------------------------------------------------------- #


def test_reference_parity_f32_bit_exact():
    x, w = _xw()
    got = block_matmul_softmax(x, w, interpret=True)
    want = jax.nn.softmax(x @ w, axis=-1)
    assert jnp.array_equal(got, want), "f32 reference kernel must be bit-exact"


def test_reference_parity_bf16_within_declared_interval():
    x, w = _xw(jnp.bfloat16)
    got = np.asarray(block_matmul_softmax(x, w, interpret=True), np.float32)
    # the registered interval transfer declares row softmax ⊆ [0, 1]
    assert got.min() >= 0.0 and got.max() <= 1.0
    want = np.asarray(
        jax.nn.softmax(x.astype(jnp.float32) @ w.astype(jnp.float32), axis=-1)
    )
    np.testing.assert_allclose(got, want, atol=2e-2)


def test_block_accumulate_in_place_parity():
    acc, _ = _xw()
    delta = acc * 0.5
    got = block_accumulate(acc, delta, interpret=True)
    assert jnp.array_equal(got, acc + delta)


# --------------------------------------------------------------------- #
# extraction + the counted cost (hand-computed pins)
# --------------------------------------------------------------------- #


def test_extraction_and_counted_cost_exact(mesh8):
    report = kernel_check(
        _softmax_step, *_sds(), mesh=mesh8, generation="cpu", probe=False
    )
    assert report.findings == []
    assert len(report.sites) == 1
    site = report.sites[0]
    assert site.kernel_name == "block_matmul_softmax_kernel"
    assert site.spec is not None
    assert site.grid == (2,)
    assert [b.block_shape for b in site.in_blocks] == [(8, D), (D, N)]
    assert [b.block_shape for b in site.out_blocks] == [(8, N)]
    assert site.io_aliases == ()
    assert site.interpret
    assert counted_cost(site) == (REF_FLOPS, REF_HBM)
    assert vmem_occupancy_bytes(site) == REF_HBM  # same blocks, double-buffered
    # the declaration agrees exactly — the selfcheck reference in numbers
    assert float(site.spec.flops(*site.in_avals)) == REF_FLOPS
    assert float(site.spec.hbm_bytes(*site.in_avals)) == REF_HBM
    assert float(site.spec.vmem_peak_bytes(*site.in_avals)) == REF_VMEM_PEAK


def test_extraction_aliases_and_clean_alias_twin(mesh8):
    sds = jax.ShapeDtypeStruct((B, N), jnp.float32)
    report = kernel_check(
        block_accumulate, sds, sds, mesh=mesh8, generation="cpu", probe=False
    )
    assert report.findings == []
    assert report.sites[0].io_aliases == ((0, 0),)


def test_interpret_probe_runs(mesh8):
    report = kernel_check(_softmax_step, *_sds(), mesh=mesh8, generation="cpu")
    assert report.interpret_probe == "ran: outputs finite"


# --------------------------------------------------------------------- #
# the six rules on seeded defects (select= isolates each rule)
# --------------------------------------------------------------------- #


def _check(fn, *sds, mesh, rule):
    return kernel_check(
        fn, *sds, mesh=mesh, generation="cpu", select=(rule,), probe=False
    )


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def test_tpu1001_vmem_overflow(mesh8):
    def step(x):  # (512, 512) f32 blocks: 2 MB/step double-buffered ≫ 512 KB cpu
        return pl.pallas_call(
            _copy_kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((512, 512), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((512, 512), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((1024, 512), jnp.float32),
            interpret=True,
        )(x)

    report = _check(step, jax.ShapeDtypeStruct((1024, 512), jnp.float32), mesh=mesh8, rule="TPU1001")
    assert _rules(report) == ["TPU1001"]
    assert report.findings[0].is_error


def test_tpu1002_tile_misalignment(mesh8):
    def step(x):  # lane dim 100 is not a multiple of the 128 MXU lane
        return pl.pallas_call(
            _copy_kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((8, 100), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 100), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 100), jnp.float32),
            interpret=True,
        )(x)

    report = _check(step, jax.ShapeDtypeStruct((16, 100), jnp.float32), mesh=mesh8, rule="TPU1002")
    assert set(_rules(report)) == {"TPU1002"}
    assert "misaligned" in report.findings[0].message


def test_tpu1003_index_map_gap(mesh8):
    def step(x):  # out map pins every grid step to block (0, 0): (1, 0) is garbage
        return pl.pallas_call(
            _copy_kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
            interpret=True,
        )(x)

    report = _check(step, jax.ShapeDtypeStruct((16, 128), jnp.float32), mesh=mesh8, rule="TPU1003")
    assert _rules(report) == ["TPU1003"]
    assert report.findings[0].is_error and "unwritten" in report.findings[0].message


def test_tpu1004_alias_hazard(mesh8):
    def step(x):  # aliased operand read from block (0,0) while writing (i,0)
        return pl.pallas_call(
            _copy_kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
            input_output_aliases={0: 0},
            interpret=True,
        )(x)

    report = _check(step, jax.ShapeDtypeStruct((16, 128), jnp.float32), mesh=mesh8, rule="TPU1004")
    assert _rules(report) == ["TPU1004"]


def _anon_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def _anon_call(x):
    return pl.pallas_call(
        _anon_kernel,
        grid=(2,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
        interpret=True,
    )(x)


def test_tpu1005_unregistered_call(mesh8):
    report = _check(
        _anon_call, jax.ShapeDtypeStruct((16, 128), jnp.float32), mesh=mesh8, rule="TPU1005"
    )
    assert _rules(report) == ["TPU1005"]
    assert report.findings[0].is_error


def _drifty_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def test_tpu1006_declaration_drift(mesh8):
    # counted: 1 mul x 8·128 elements x 2 steps = 2048 flops; declare 3x that
    register_kernel_cost(
        KernelCostSpec(
            name="_drifty_kernel",
            flops=lambda x: float(3 * 2 * x.shape[0] * x.shape[1]),
            hbm_bytes=lambda x: float(2 * x.shape[0] * x.shape[1] * 4),  # exact
            vmem_peak_bytes=lambda x: float(4 * 8 * x.shape[1] * 4),
        )
    )
    try:

        def step(x):
            return pl.pallas_call(
                _drifty_kernel,
                grid=(2,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
                interpret=True,
            )(x)

        report = _check(
            step, jax.ShapeDtypeStruct((16, 128), jnp.float32), mesh=mesh8, rule="TPU1006"
        )
        assert _rules(report) == ["TPU1006"]
        assert "FLOPs" in report.findings[0].message  # only the FLOPs line drifts
    finally:
        unregister_kernel_cost("_drifty_kernel")


def test_kernel_selfcheck_green(mesh8):
    from accelerate_tpu.analysis import run_kernel_selfcheck

    ok, lines = run_kernel_selfcheck(mesh8)
    assert ok, "\n".join(lines)
    assert sum("detected" in l for l in lines) == 6
    assert sum("zero findings" in l for l in lines) == 6
    assert any("cost reference" in l and "exact" in l for l in lines)


# --------------------------------------------------------------------- #
# the contract feeds the other tiers
# --------------------------------------------------------------------- #


def test_perfmodel_prices_the_declared_cost(mesh8):
    from accelerate_tpu.analysis import perf_check

    report = perf_check(_softmax_step, *_sds(), mesh=mesh8, rules=False)
    ops = [o for o in report.ops if o.primitive == "pallas_call:block_matmul_softmax_kernel"]
    assert len(ops) == 1
    assert ops[0].flops == REF_FLOPS
    assert ops[0].hbm_bytes == REF_HBM
    assert report.unpriced == []


def test_perfmodel_unpriced_and_warn_once(mesh8):
    from accelerate_tpu.analysis import perf_check

    reset_unknown_op_warnings()
    sds = jax.ShapeDtypeStruct((16, 128), jnp.float32)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        first = perf_check(_anon_call, sds, mesh=mesh8, rules=False)
        second = perf_check(_anon_call, sds, mesh=mesh8, rules=False)
    assert first.unpriced == ["_anon_kernel"]
    assert second.unpriced == ["_anon_kernel"]
    blind = [w for w in caught if issubclass(w.category, UnknownOpWarning)]
    assert len(blind) == 1, "repeat walks must not repeat the blindness warning"
    assert "_anon_kernel" in str(blind[0].message)
    reset_unknown_op_warnings()


def test_flightcheck_charges_declared_vmem_peak():
    from accelerate_tpu.analysis.flightcheck import _sub_transient_bytes

    closed = jax.make_jaxpr(_softmax_step)(*_sds())
    eqn = next(e for e in closed.jaxpr.eqns if e.primitive.name == "pallas_call")
    assert _sub_transient_bytes(eqn) == REF_VMEM_PEAK

    reset_unknown_op_warnings()
    closed = jax.make_jaxpr(_anon_call)(jax.ShapeDtypeStruct((16, 128), jnp.float32))
    eqn = next(e for e in closed.jaxpr.eqns if e.primitive.name == "pallas_call")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert _sub_transient_bytes(eqn) == 0
    assert any(issubclass(w.category, UnknownOpWarning) for w in caught)
    reset_unknown_op_warnings()


def test_numerics_interval_through_registered_kernel(mesh8):
    from accelerate_tpu.analysis import numerics_check

    r = numerics_check(_softmax_step, *_sds(), mesh=mesh8, assume=(-3.0, 3.0))
    out = r.outputs[0]
    assert (out.lo, out.hi) == (0.0, 1.0)  # the declared softmax transfer


# --------------------------------------------------------------------- #
# the AST registration gate + CLI surfaces
# --------------------------------------------------------------------- #

_UNREGISTERED_SRC = """\
import jax, jax.numpy as jnp
from jax.experimental import pallas as pl

def mystery_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]

def step(x):
    return pl.pallas_call(
        mystery_kernel,
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
    )(x)
"""


def test_scan_paths_fires_and_respects_suppression(tmp_path):
    p = tmp_path / "unregistered.py"
    p.write_text(_UNREGISTERED_SRC)
    findings = scan_paths([str(p)])
    assert [f.rule for f in findings] == ["TPU1005"]
    assert "mystery_kernel" in findings[0].message

    p.write_text(
        _UNREGISTERED_SRC.replace(
            "    return pl.pallas_call(",
            "    return pl.pallas_call(  # tpu-lint: disable=TPU1005",
        )
    )
    assert scan_paths([str(p)]) == []

    registered = tmp_path / "registered.py"
    registered.write_text(
        _UNREGISTERED_SRC.replace("mystery_kernel", "block_matmul_softmax_kernel")
    )
    assert scan_paths([str(registered)]) == []


def _run_cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.cli", "kernel-check", *args],
        capture_output=True, text=True, env=CPU_ENV, cwd=cwd, timeout=240,
    )


def test_cli_paths_mode_unregistered_exits_nonzero(tmp_path):
    p = tmp_path / "unregistered.py"
    p.write_text(_UNREGISTERED_SRC)
    result = _run_cli(str(p))
    assert result.returncode == 1, result.stdout + result.stderr
    assert "TPU1005" in result.stdout


def test_cli_changed_without_git_falls_back(tmp_path):
    p = tmp_path / "unregistered.py"
    p.write_text(_UNREGISTERED_SRC)
    result = _run_cli("--changed", str(p), cwd=str(tmp_path))
    assert result.returncode == 1
    assert "needs a git work tree" in result.stderr
    assert "TPU1005" in result.stdout


def test_cli_traced_example_clean():
    result = _run_cli(
        "examples/by_feature/kernel_check.py::decode_step", "--mesh", "data=8"
    )
    assert result.returncode == 0, result.stdout + result.stderr
    assert "findings: none" in result.stdout
    assert "[registered]" in result.stdout


def test_cli_selfcheck():
    result = _run_cli("--selfcheck")
    assert result.returncode == 0, result.stdout + result.stderr
    assert result.stdout.count("detected") == 6
    assert result.stdout.count("clean twin") == 6
    assert "cost reference" in result.stdout and "exact" in result.stdout
