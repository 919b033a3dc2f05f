"""Numerics & precision analyzer (``analysis.numerics`` +
``analysis.numerics_rules``): the interval lattice against hand-computed
bounds (widening termination through scan/while, cond joins, cast
provenance round-trips, relational softmax refinements), the TPU601-606
rules with their clean twins, the compression numerics-model coverage
gate, the dogfood surfaces (build_train_step / ServingEngine /
examples), and the CLI (text/json/sarif/selfcheck/AST tier/strict
TPU602 gate)."""

import json
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.analysis.numerics import (
    DEFAULT_ASSUME,
    AbsVal,
    Interval,
    NumericsInterpreter,
    NumericsReport,
    _input_absvals,
    dtype_eps,
    dtype_max,
    numerics_check,
)
from accelerate_tpu.analysis.numerics_rules import (
    COMPRESSION_NUMERICS,
    check_key_reuse_source,
)
from accelerate_tpu.parallel.mesh import MeshConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

f32, f16, bf16 = jnp.float32, jnp.float16, jnp.bfloat16


def _rules(report: NumericsReport):
    return sorted({f.rule for f in report.findings})


def _out_iv(report: NumericsReport, i=0):
    o = report.outputs[i]
    return (o.lo, o.hi)


@pytest.fixture
def mesh1():
    return MeshConfig(data=1).build(jax.devices()[:1])


# --------------------------------------------------------------------- #
# the interval lattice (hand-computed references)
# --------------------------------------------------------------------- #


def test_interval_arithmetic_exact(mesh1):
    """log(x^2 + 1) / 2 on x in [-2, 3]: the pipeline's bounds are
    hand-computable and must match EXACTLY."""

    def step(x):
        return jnp.log(x**2 + 1.0) / 2.0

    r = numerics_check(step, jax.ShapeDtypeStruct((8,), f32), mesh=mesh1, assume=(-2.0, 3.0))
    lo, hi = _out_iv(r)
    assert lo == 0.0
    assert hi == pytest.approx(math.log(10.0) / 2.0, abs=1e-15)
    assert r.findings == []


def test_monotone_and_corner_transfers(mesh1):
    cases = [
        (lambda x: jnp.exp(x), (-1.0, 2.0), (math.exp(-1), math.exp(2))),
        (lambda x: jnp.tanh(x), (-50.0, 50.0), (-1.0, 1.0)),
        (lambda x: jnp.abs(x), (-3.0, 2.0), (0.0, 3.0)),
        (lambda x: -x, (-3.0, 2.0), (-2.0, 3.0)),
        (lambda x: x * 2.0 + 1.0, (-1.0, 1.0), (-1.0, 3.0)),
        (lambda x: jnp.maximum(x, 0.5), (-1.0, 1.0), (0.5, 1.0)),
        (lambda x: jnp.sqrt(jnp.maximum(x, 0.0)), (-4.0, 9.0), (0.0, 3.0)),
    ]
    for fn, assume, want in cases:
        r = numerics_check(fn, jax.ShapeDtypeStruct((4,), f32), mesh=mesh1, assume=assume)
        lo, hi = _out_iv(r)
        assert lo == pytest.approx(want[0], abs=1e-12), fn
        assert hi == pytest.approx(want[1], abs=1e-12), fn


def test_reduce_sum_scales_by_axis_length(mesh1):
    def step(x):
        return jnp.sum(x, axis=-1)

    r = numerics_check(step, jax.ShapeDtypeStruct((4, 100), f32), mesh=mesh1, assume=(-1.0, 2.0))
    assert _out_iv(r) == (-100.0, 200.0)


def test_psum_of_literal_is_group_size(mesh8):
    def step(x):
        return x * 0.0 + jax.lax.psum(1, "data")

    r = numerics_check(step, jax.ShapeDtypeStruct((4,), f32), mesh=mesh8)
    assert _out_iv(r) == (8.0, 8.0)


def test_scan_widening_terminates_and_is_sound(mesh1):
    """A growing carry widens to +inf (termination); a damped carry and a
    loop-invariant bound stay tight."""

    def growing(x):
        def body(c, _):
            return c + 1.0, c

        out, _ = jax.lax.scan(body, x, None, length=1000)
        return out

    r = numerics_check(growing, jax.ShapeDtypeStruct((), f32), mesh=mesh1)
    lo, hi = _out_iv(r)
    assert hi == math.inf and lo == DEFAULT_ASSUME[0] + 1.0  # lo moves once, then stable

    def damped(x):
        def body(c, _):
            return c * 0.5, c

        out, _ = jax.lax.scan(body, x, None, length=1000)
        return out

    r = numerics_check(damped, jax.ShapeDtypeStruct((), f32), mesh=mesh1)
    # the fixpoint carry is the init join [-16, 16]; the scan output is
    # the post-body carry 0.5*[-16, 16] — sound and tight, no widening
    assert _out_iv(r) == (-8.0, 8.0)


def test_while_widening_terminates(mesh1):
    def wloop(x):
        def cond(c):
            return c[1] < 10

        def body(c):
            return (c[0] + 1.0, c[1] + 1)

        return jax.lax.while_loop(cond, body, (x, 0))[0]

    r = numerics_check(wloop, jax.ShapeDtypeStruct((), f32), mesh=mesh1)
    lo, hi = _out_iv(r)
    assert hi == math.inf  # grows without a provable bound
    assert lo == DEFAULT_ASSUME[0]  # the zero-trip join keeps the init's lo


def test_cond_branches_join(mesh1):
    def step(x):
        return jax.lax.cond(x.sum() > 0, lambda v: v * 2.0, lambda v: v - 1.0, x)

    r = numerics_check(step, jax.ShapeDtypeStruct((4,), f32), mesh=mesh1, assume=(-1.0, 1.0))
    # branch 1: [-2, 2]; branch 2: [-2, 0]; join: [-2, 2]
    assert _out_iv(r) == (-2.0, 2.0)


def test_cast_provenance_round_trip(mesh1):
    """bf16 -> f32 -> bf16 keeps the 7-bit effective mantissa through the
    upcast (information does not come back)."""

    def step(x):
        return (x.astype(jnp.float32) * 2.0).astype(jnp.bfloat16)

    r = numerics_check(step, jax.ShapeDtypeStruct((4,), bf16), mesh=mesh1)
    assert r.outputs[0].mant == 7

    def stays_wide(x):
        return x * 2.0

    r = numerics_check(stays_wide, jax.ShapeDtypeStruct((4,), f32), mesh=mesh1)
    assert r.outputs[0].mant == 23


def test_interval_primitives():
    a = Interval(-2.0, 3.0)
    b = Interval(1.0, 4.0)
    assert a.join(b) == Interval(-2.0, 4.0)
    assert a.widen(Interval(-2.0, 5.0)) == Interval(-2.0, math.inf)
    assert a.widen(Interval(-3.0, 3.0)) == Interval(-math.inf, 3.0)
    assert a.contains_zero and not b.contains_zero
    assert Interval(-1.0, 2.0).magnitude() == 2.0
    assert dtype_max("float16") == 65504.0
    assert dtype_eps("bfloat16") == 2.0**-7


# --------------------------------------------------------------------- #
# TPU601-606: defect fires (priced), clean twin silent
# --------------------------------------------------------------------- #


def test_tpu601_low_precision_dot_and_clean_twin(mesh1):
    def low(x, w):
        return x @ w

    bad = numerics_check(
        low, jax.ShapeDtypeStruct((8, 512), bf16), jax.ShapeDtypeStruct((512, 16), bf16), mesh=mesh1
    )
    assert "TPU601" in _rules(bad)
    [f] = [f for f in bad.findings if f.rule == "TPU601"]
    assert "512" in f.message and "2" in f.message  # K and the priced K*eps/2 bound

    def fixed(x, w):
        return jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    clean = numerics_check(
        fixed, jax.ShapeDtypeStruct((8, 512), bf16), jax.ShapeDtypeStruct((512, 16), bf16), mesh=mesh1
    )
    assert clean.findings == []

    # a short contraction is below the pricing floor
    short = numerics_check(
        low, jax.ShapeDtypeStruct((8, 64), bf16), jax.ShapeDtypeStruct((64, 16), bf16), mesh=mesh1
    )
    assert "TPU601" not in _rules(short)


def test_tpu601_forced_low_precision_sum(mesh1):
    def forced(x):  # a genuinely bf16 accumulator (lax.reduce, bf16 add)
        return jax.lax.reduce(x, jnp.bfloat16(0), jax.lax.add, (1,))

    r = numerics_check(forced, jax.ShapeDtypeStruct((4, 1024), bf16), mesh=mesh1)
    assert "TPU601" in _rules(r)

    def default_sum(x):  # jnp upcasts the accumulator to f32 on its own
        return jnp.sum(x, axis=-1)

    assert "TPU601" not in _rules(
        numerics_check(default_sum, jax.ShapeDtypeStruct((4, 1024), bf16), mesh=mesh1)
    )
    # what jnp.sum(dtype=bf16) accumulates in is jax's choice (f32 and one narrowing up to 0.8,
    # a bf16 reduce_sum on 0.9): the rule follows the traced program either way
    def narrow(x):
        return jnp.sum(x, axis=-1, dtype=jnp.bfloat16)

    x = jax.ShapeDtypeStruct((4, 1024), bf16)
    traced_bf16 = any(
        e.primitive.name == "reduce_sum" and e.invars[0].aval.dtype == bf16
        for e in jax.make_jaxpr(narrow)(x).eqns
    )
    assert ("TPU601" in _rules(numerics_check(narrow, x, mesh=mesh1))) == traced_bf16


def test_tpu602_softmax_overflow_and_guarded_twin(mesh1):
    def bad(x):
        e = jnp.exp(x)
        return e / jnp.sum(e, axis=-1, keepdims=True)

    r = numerics_check(bad, jax.ShapeDtypeStruct((8, 64), f16), mesh=mesh1)
    assert "TPU602" in _rules(r)
    # two genuine overflow sites: the exp itself AND the f16 cast of the
    # (huge) sum — each a distinct fix point
    overflows = [f for f in r.findings if f.rule == "TPU602"]
    assert all(f.is_error for f in overflows)  # the strict-gate rule
    exp_f = next(f for f in overflows if f.message.startswith("exp"))
    assert "6.55e+04" in exp_f.message  # the dtype max is priced
    assert "running max" in exp_f.message  # the fix is named

    def good(x):
        m = jnp.max(x, axis=-1, keepdims=True)
        e = jnp.exp(x - m)  # relational: x - max(x) in [lo-hi, 0]
        return e / jnp.sum(e, axis=-1, keepdims=True)

    clean = numerics_check(good, jax.ShapeDtypeStruct((8, 64), f16), mesh=mesh1)
    assert clean.findings == []
    assert _out_iv(clean) == (0.0, 1.0)  # the x/sum(x) refinement

    # the same unguarded softmax in f32 cannot overflow at +-16
    assert "TPU602" not in _rules(numerics_check(bad, jax.ShapeDtypeStruct((8, 64), f32), mesh=mesh1))


def test_tpu602_fp16_variance_cancellation_with_assume(mesh1):
    """E[x^2] overflows fp16 once |x| can reach 1e3 — the squared term
    tops 65504 (the E[x^2]-E[x]^2 cancellation recipe); computing the
    moments in f32 is the fix."""

    def var_f16(x):
        return jnp.mean(x * x, axis=-1, dtype=jnp.float16) - jnp.mean(x, axis=-1, dtype=jnp.float16) ** 2

    r = numerics_check(var_f16, jax.ShapeDtypeStruct((4, 64), f16), mesh=mesh1, assume=(-1e3, 1e3))
    assert "TPU602" in _rules(r)

    def var_f32(x):
        x32 = x.astype(jnp.float32)
        return jnp.mean(x32 * x32, axis=-1) - jnp.mean(x32, axis=-1) ** 2

    assert "TPU602" not in _rules(
        numerics_check(var_f32, jax.ShapeDtypeStruct((4, 64), f16), mesh=mesh1, assume=(-1e3, 1e3))
    )


def test_tpu602_no_cascade_from_unguarded_div(mesh1):
    """One unguarded div must report TPU603 once — not a TPU602 wall from
    its infinite downstream intervals."""

    def step(x, n):
        y = x / n  # unbounded
        return (y * 2.0).astype(jnp.float16)

    r = numerics_check(
        step, jax.ShapeDtypeStruct((4,), f32), jax.ShapeDtypeStruct((4,), f32), mesh=mesh1
    )
    assert _rules(r) == ["TPU603"]


def test_tpu603_singularities_and_guards(mesh1):
    x = jax.ShapeDtypeStruct((8,), f32)

    def d(a, b):
        return a / b

    def lg(a):
        return jnp.log(a)

    def rs(a):
        return jax.lax.rsqrt(a)

    assert "TPU603" in _rules(numerics_check(d, x, x, mesh=mesh1))
    assert "TPU603" in _rules(numerics_check(lg, x, mesh=mesh1))
    assert "TPU603" in _rules(numerics_check(rs, x, mesh=mesh1))

    def d_ok(a, b):
        return a / jnp.maximum(b, 1e-6)

    def lg_ok(a):
        return jnp.log(jnp.exp(a))  # exp > 0

    def rs_ok(a):
        return jax.lax.rsqrt(a * a + 1e-6)

    assert "TPU603" not in _rules(numerics_check(d_ok, x, x, mesh=mesh1))
    assert "TPU603" not in _rules(numerics_check(lg_ok, x, mesh=mesh1))
    assert "TPU603" not in _rules(numerics_check(rs_ok, x, mesh=mesh1))


def test_tpu604_update_below_ulp_and_master_weights(mesh1):
    p16 = jax.ShapeDtypeStruct((64, 64), bf16)
    p32 = jax.ShapeDtypeStruct((64, 64), f32)

    def upd(p, g):
        return p - 1e-4 * g

    bad = numerics_check(upd, p16, p16, mesh=mesh1)
    assert "TPU604" in _rules(bad)
    [f] = [f for f in bad.findings if f.rule == "TPU604"]
    assert "master weights" in f.message and "eps" in f.message  # priced + the fix named

    # f32 master weights: clean
    assert "TPU604" not in _rules(numerics_check(upd, p32, p32, mesh=mesh1))

    # a big enough lr is representable: clean
    def big_upd(p, g):
        return p - 0.1 * g

    assert "TPU604" not in _rules(numerics_check(big_upd, p16, p16, mesh=mesh1))

    # epsilon-guard on an INTERMEDIATE (not a param leaf) must not fire
    def guard(x):
        t = jnp.exp(x.astype(jnp.float16))
        return t + jnp.float16(1e-5)

    assert "TPU604" not in _rules(
        numerics_check(guard, jax.ShapeDtypeStruct((8,), f16), mesh=mesh1, assume=(-4.0, 2.0))
    )


def test_tpu605_key_reuse_jaxpr_tier(mesh1):
    def reuse(seed):
        k = jax.random.key(seed)
        return jax.random.normal(k, (4,)) + jax.random.uniform(k, (4,))

    r = numerics_check(reuse, jax.ShapeDtypeStruct((), jnp.uint32), mesh=mesh1)
    assert "TPU605" in _rules(r)

    def split(seed):
        k = jax.random.key(seed)
        k1, k2 = jax.random.split(k)
        return jax.random.normal(k1, (4,)) + jax.random.uniform(k2, (4,))

    assert "TPU605" not in _rules(numerics_check(split, jax.ShapeDtypeStruct((), jnp.uint32), mesh=mesh1))


def test_tpu605_loop_invariant_key_in_scan(mesh1):
    """A key captured by a multi-iteration scan body and drawn from every
    iteration is reuse (same bits each trip); a per-iteration fold_in is
    the clean discipline."""

    def loop_reuse(seed, x):
        k = jax.random.key(seed)

        def body(c, _):
            return c + jax.random.normal(k, (4,)), None

        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    r = numerics_check(
        loop_reuse, jax.ShapeDtypeStruct((), jnp.uint32), jax.ShapeDtypeStruct((4,), f32), mesh=mesh1
    )
    assert "TPU605" in _rules(r)
    [f] = [f for f in r.findings if f.rule == "TPU605"]
    assert "loop iteration" in f.message

    def loop_folded(seed, x):
        k = jax.random.key(seed)

        def body(c, i):
            return c + jax.random.normal(jax.random.fold_in(k, i), (4,)), None

        out, _ = jax.lax.scan(body, x, jnp.arange(5), length=5)
        return out

    assert "TPU605" not in _rules(
        numerics_check(
            loop_folded, jax.ShapeDtypeStruct((), jnp.uint32), jax.ShapeDtypeStruct((4,), f32), mesh=mesh1
        )
    )


def test_tpu606_compressed_wire_and_twins(mesh8):
    from accelerate_tpu.parallel.compression import compressed_psum_mean

    def bf16_wire(g):
        return compressed_psum_mean({"w": g}, "data", "bf16")

    r = numerics_check(bf16_wire, jax.ShapeDtypeStruct((8, 16), f32), mesh=mesh8)
    assert "TPU606" in _rules(r)
    [f] = [f for f in r.findings if f.rule == "TPU606"]
    assert "amax" in f.message and "error feedback" in f.message  # the EQuARX-style bound

    def int8_wire(g):
        return compressed_psum_mean({"w": g}, "data", "int8")

    r = numerics_check(int8_wire, jax.ShapeDtypeStruct((8, 16), f32), mesh=mesh8)
    assert "TPU606" in _rules(r)
    assert any("254" in f.message for f in r.findings if f.rule == "TPU606")

    # exact f32 reduction: clean
    def exact(g):
        n = jax.lax.psum(1, "data")
        return jax.lax.psum(g, "data") / n

    assert "TPU606" not in _rules(numerics_check(exact, jax.ShapeDtypeStruct((8, 16), f32), mesh=mesh8))

    # an error-feedback scheme carries the residual: clean
    def with_feedback(g, e):
        n = jax.lax.psum(1, "data")
        c = (g + e).astype(jnp.bfloat16)
        red = jax.lax.psum(c, "data").astype(jnp.float32) / n
        new_e = (g + e) - c.astype(jnp.float32)
        return red, new_e

    assert "TPU606" not in _rules(
        numerics_check(
            with_feedback, jax.ShapeDtypeStruct((8, 16), f32), jax.ShapeDtypeStruct((8, 16), f32), mesh=mesh8
        )
    )


def test_powersgd_is_numerics_clean(mesh8):
    """PowerSGD reduces f32 factors (never a narrowed wire payload) and
    carries error feedback — the whole TPU6xx tier must stay silent."""
    from accelerate_tpu.parallel.compression import powersgd_psum_mean

    def psgd(g, e, q):
        return powersgd_psum_mean({"w": g}, "data", {"error": {"w": e}, "q": {"w": q}}, 2)

    r = numerics_check(
        psgd,
        jax.ShapeDtypeStruct((32, 16), f32),
        jax.ShapeDtypeStruct((32, 16), f32),
        jax.ShapeDtypeStruct((16, 2), f32),
        mesh=mesh8,
    )
    assert r.findings == []


# --------------------------------------------------------------------- #
# AST tier (TPU605 over source text)
# --------------------------------------------------------------------- #


def test_key_reuse_ast_tier_fires_and_split_is_clean():
    bad = textwrap.dedent(
        '''
        """Fixture."""
        import jax


        def sample(key, n):
            a = jax.random.normal(key, (n,))
            b = jax.random.uniform(key, (n,))
            return a + b
        '''
    )
    found = check_key_reuse_source(bad, path="<t>")
    assert [f.rule for f in found] == ["TPU605"]
    assert "bit-identical" in found[0].message

    good = bad.replace(
        "def sample(key, n):",
        "def sample(key, n):\n    key, sub = jax.random.split(key)",
    ).replace("jax.random.uniform(key", "jax.random.uniform(sub")
    assert check_key_reuse_source(good, path="<t>") == []

    # a rebind between draws (fold_in discipline) is clean too
    rebind = textwrap.dedent(
        '''
        """Fixture."""
        import jax


        def sample(key, n):
            a = jax.random.normal(key, (n,))
            key = jax.random.fold_in(key, 1)
            b = jax.random.uniform(key, (n,))
            return a + b
        '''
    )
    assert check_key_reuse_source(rebind, path="<t>") == []


# --------------------------------------------------------------------- #
# suppression / filtering / report surfaces
# --------------------------------------------------------------------- #


def test_findings_anchor_to_source_and_inline_suppression(tmp_path, mesh1):
    import importlib.util

    mod = tmp_path / "lowdot.py"
    mod.write_text(
        textwrap.dedent(
            '''
            """Fixture: low-precision accumulation, suppressed inline."""
            import jax.numpy as jnp


            def step(x, w):
                return x @ w  # tpu-lint: disable=TPU601
            '''
        )
    )
    spec = importlib.util.spec_from_file_location("lowdot", mod)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    r = numerics_check(
        m.step,
        jax.ShapeDtypeStruct((8, 512), bf16),
        jax.ShapeDtypeStruct((512, 16), bf16),
        mesh=mesh1,
    )
    assert "TPU601" not in _rules(r)


def test_select_ignore_filtering(mesh1):
    def step(x, w):
        return x @ w

    a = jax.ShapeDtypeStruct((8, 512), bf16)
    b = jax.ShapeDtypeStruct((512, 16), bf16)
    assert _rules(numerics_check(step, a, b, mesh=mesh1, ignore=("TPU601",))) == []
    assert _rules(numerics_check(step, a, b, mesh=mesh1, select=("TPU601",))) == ["TPU601"]


def test_report_dict_and_text_surfaces(mesh8):
    def step(x):
        m = jnp.max(x, axis=-1, keepdims=True)
        e = jnp.exp(x - m)
        return e / jnp.sum(e, axis=-1, keepdims=True)

    r = numerics_check(step, jax.ShapeDtypeStruct((8, 64), f16), mesh=mesh8, assume=(-8.0, 8.0))
    d = r.as_dict()
    assert d["assume"] == [-8.0, 8.0]
    assert d["eqns_interpreted"] == r.n_eqns > 0
    assert d["outputs"][0]["lo"] == 0.0 and d["outputs"][0]["hi"] == 1.0
    assert d["outputs"][0]["effective_mantissa_bits"] == 10
    assert d["findings"] == []
    text = r.render_text()
    assert "inputs assumed in [-8, 8]" in text
    assert "findings: none" in text
    assert "[0, 1]" in text


# --------------------------------------------------------------------- #
# selfcheck + registry drift (the executable spec)
# --------------------------------------------------------------------- #


def test_run_numerics_selfcheck_passes(mesh8):
    from accelerate_tpu.analysis.selfcheck import run_numerics_selfcheck

    ok, lines = run_numerics_selfcheck(mesh8)
    assert ok, "\n".join(lines)
    joined = "\n".join(lines)
    for rule in ("TPU601", "TPU602", "TPU603", "TPU604", "TPU605", "TPU606"):
        assert f"{rule} fixture: detected" in joined
        assert f"{rule} clean twin: zero findings" in joined
    assert any("interval reference" in line and "exact" in line for line in lines)


def test_selfcheck_fixture_count_matches_registry(mesh8):
    """Registry drift gate: every registered TPU6xx rule has a seeded
    defect AND a clean twin; TPU602 is the error-severity strict gate."""
    from accelerate_tpu.analysis.rules import ERROR, RULES
    from accelerate_tpu.analysis.selfcheck import _numerics_clean_fixtures, _numerics_fixtures

    registered = {rid for rid in RULES if rid.startswith("TPU6")}
    assert registered == {"TPU601", "TPU602", "TPU603", "TPU604", "TPU605", "TPU606"}
    assert set(_numerics_fixtures(mesh8)) == registered
    assert set(_numerics_clean_fixtures(mesh8)) == registered
    assert RULES["TPU602"].severity == ERROR
    assert all(RULES[r].severity == "warning" for r in registered - {"TPU602"})
    assert all(RULES[r].tier == "numerics" for r in registered)


# --------------------------------------------------------------------- #
# compression numerics-model coverage (the COLLECTIVE_EFFECTS pattern)
# --------------------------------------------------------------------- #


def test_every_compression_entry_point_has_numerics_model():
    """Every public compression method must carry a numerics model
    (wire dtype, error-feedback flag, per-leaf error bound) — a new
    compression mode cannot land outside the analysis stack."""
    from accelerate_tpu.parallel import compression

    for method in compression.METHODS:
        assert method in COMPRESSION_NUMERICS, f"no numerics model for {method!r}"
        model = COMPRESSION_NUMERICS[method]
        assert model.wire_dtype
        assert isinstance(model.error_feedback, bool)
        # the bound is a usable function of (amax, n)
        assert model.bound(1.0, 8) >= 0.0
        assert model.describe
    # schemes without error feedback must price a nonzero bound;
    # powersgd's residual carry is what licenses its zero steady-state bound
    assert COMPRESSION_NUMERICS["bf16"].bound(1.0, 8) > 0
    assert COMPRESSION_NUMERICS["int8"].bound(1.0, 8) > 0
    assert COMPRESSION_NUMERICS["powersgd"].error_feedback


# --------------------------------------------------------------------- #
# dogfood: build_train_step / ServingEngine / examples
# --------------------------------------------------------------------- #


def test_build_train_step_numerics_clean():
    """The fast-path train step program (the REAL jitted function, with
    the fp16 scale threaded) carries no TPU6xx findings — the loss-scale
    division is provably guarded by the scaler's >= 1 invariant."""
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.test_utils import RegressionDataset, RegressionModel, linear_loss_fn
    from accelerate_tpu.utils.random import key_for_step

    acc = Accelerator()
    model = acc.prepare_model(RegressionModel())
    optimizer = acc.prepare_optimizer(optax.sgd(0.1))
    acc.prepare_data_loader(RegressionDataset(length=64))
    step = acc.build_train_step(linear_loss_fn)
    inner = step._jitted.__wrapped__

    grad_buf = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x, dtype=jnp.float32), model.params)
    scale_state = {"scale": jnp.float32(1.0), "growth": jnp.int32(0)}
    batch = {"x": jnp.zeros((16, 1), jnp.float32), "y": jnp.zeros((16, 1), jnp.float32)}
    report = numerics_check(
        inner,
        model.params, optimizer.opt_state, grad_buf, None, batch, scale_state,
        jnp.bool_(True), key_for_step(0), jnp.float32(-1.0), {},
        mesh=acc.mesh,
    )
    assert report.n_eqns > 10
    assert report.findings == [], [f.message for f in report.findings]

    # build_eval_step's jitted program too
    eval_step = acc.build_eval_step(lambda p, b: linear_loss_fn(p, b))
    eval_report = numerics_check(
        lambda p, b: linear_loss_fn(acc._compute_cast(p), b),
        model.params, batch, mesh=acc.mesh,
    )
    assert eval_report.findings == [], [f.message for f in eval_report.findings]


def test_serving_engine_numerics_dogfood():
    from accelerate_tpu.models import LlamaConfig, create_llama_model
    from accelerate_tpu.serving import ServingEngine

    model = create_llama_model(LlamaConfig.tiny(), seq_len=16)
    eng = ServingEngine(model, num_slots=2, prompt_buckets=(8, 16))
    reports = eng.numerics_check()
    assert set(reports) == {"prefill", "decode_tick", "resume_recompute"}
    for name, rep in reports.items():
        assert rep.n_eqns > 50, name
        # the strict-gate rule and the whole tier must be clean on the
        # repo's own serving programs
        assert rep.findings == [], (name, [f.message for f in rep.findings])


def test_example_numerics_check_runs():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "numerics_example", os.path.join(REPO, "examples", "by_feature", "numerics_check.py")
    )
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    mesh = MeshConfig(data=1).build(jax.devices()[:1])
    seeded = numerics_check(m.train_step, *m.train_step_sample_args(), mesh=mesh)
    assert any(f.rule == "TPU601" for f in seeded.findings)
    fixed = numerics_check(m.fixed_step, *m.fixed_step_sample_args(), mesh=mesh)
    assert fixed.findings == []


def test_accelerator_numerics_check_surface():
    from accelerate_tpu import Accelerator

    acc = Accelerator()

    def step(x):
        return jnp.log(x)  # TPU603: operand can be <= 0

    report = acc.numerics_check(step, jax.ShapeDtypeStruct((8,), f32))
    assert "TPU603" in {f.rule for f in report.findings}
    assert report.ok  # warnings only

    clean = acc.numerics_check(step, jax.ShapeDtypeStruct((8,), f32), assume=(1.0, 10.0))
    assert clean.findings == []


# --------------------------------------------------------------------- #
# input assumption plumbing
# --------------------------------------------------------------------- #


def test_assume_per_leaf_overrides(mesh1):
    def step(x, n):
        return x / n

    # a per-leaf assume that keeps the denominator off zero: clean
    r = numerics_check(
        step,
        jax.ShapeDtypeStruct((8,), f32),
        jax.ShapeDtypeStruct((8,), f32),
        mesh=mesh1,
        assume=[(-16.0, 16.0), (1.0, 128.0)],
    )
    assert r.findings == []
    assert _out_iv(r) == (-16.0, 16.0)


def test_input_absvals_defaults(mesh1):
    from accelerate_tpu.analysis.jaxpr_lint import _trace

    def step(x, i):
        return x, i

    closed, _ = _trace(
        step, (jax.ShapeDtypeStruct((4,), f32), jax.ShapeDtypeStruct((4,), jnp.int32)), mesh1
    )
    vals = _input_absvals(closed, None, None)
    assert vals[0].iv == Interval(*DEFAULT_ASSUME) and vals[0].param_like
    assert not vals[1].iv.known  # ints carry no assumption


# --------------------------------------------------------------------- #
# CLI: selfcheck / text / json / sarif / AST tier / strict TPU602 gate
# --------------------------------------------------------------------- #

CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _run_cli(*args, timeout=240):
    return subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.cli", *args],
        capture_output=True, text=True, env=CPU_ENV, timeout=timeout, cwd=REPO,
    )


@pytest.mark.slow
def test_cli_numerics_check_selfcheck():
    result = _run_cli("numerics-check", "--selfcheck")
    assert result.returncode == 0, result.stderr
    for rule in ("TPU601", "TPU602", "TPU603", "TPU604", "TPU605", "TPU606"):
        assert f"{rule} fixture: detected" in result.stdout
        assert f"{rule} clean twin: zero findings" in result.stdout
    assert "interval reference" in result.stdout and "exact" in result.stdout


@pytest.mark.slow
def test_cli_numerics_check_example_text_json_sarif(tmp_path):
    target = (
        "numerics-check", "examples/by_feature/numerics_check.py::train_step", "--mesh", "data=8",
    )
    result = _run_cli(*target)
    assert result.returncode == 0, result.stderr  # TPU601 is a warning
    assert "TPU601" in result.stdout
    assert "output value intervals" in result.stdout

    js = _run_cli(*target, "--format", "json")
    assert js.returncode == 0, js.stderr
    payload = json.loads(js.stdout)
    assert payload["eqns_interpreted"] > 0
    assert any(f["rule"] == "TPU601" for f in payload["findings"])

    sarif = _run_cli(*target, "--format", "sarif")
    assert sarif.returncode == 0, sarif.stderr
    doc = json.loads(sarif.stdout)
    assert doc["version"] == "2.1.0"
    assert any(res["ruleId"] == "TPU601" for res in doc["runs"][0]["results"])


@pytest.mark.slow
def test_cli_numerics_check_strict_gate_on_tpu602(tmp_path):
    """The error-severity rule fails the CLI without --strict — the
    mechanism that promotes TPU602 into the make lint gate."""
    mod = tmp_path / "hot_softmax.py"
    mod.write_text(
        textwrap.dedent(
            '''
            """Fixture: fp16 softmax without max subtraction."""
            import jax
            import jax.numpy as jnp


            def step(x):
                e = jnp.exp(x)
                return e / jnp.sum(e, axis=-1, keepdims=True)


            def step_sample_args():
                return (jax.ShapeDtypeStruct((8, 64), jnp.float16),)
            '''
        )
    )
    result = _run_cli("numerics-check", f"{mod}::step", "--mesh", "data=1")
    assert result.returncode == 1
    assert "TPU602" in result.stdout

    # --assume narrow enough that exp cannot overflow: passes
    # (= form: argparse would read a leading -4 as an option otherwise)
    result = _run_cli("numerics-check", f"{mod}::step", "--mesh", "data=1", "--assume=-4,4")
    assert result.returncode == 0, result.stdout


@pytest.mark.slow
def test_cli_numerics_check_ast_tier(tmp_path):
    mod = tmp_path / "reuse.py"
    mod.write_text(
        textwrap.dedent(
            '''
            """Fixture: AST-tier key reuse."""
            import jax


            def draw(key):
                a = jax.random.normal(key, (4,))
                b = jax.random.uniform(key, (4,))
                return a + b
            '''
        )
    )
    result = _run_cli("numerics-check", str(mod))
    assert result.returncode == 0  # warning severity
    assert "TPU605" in result.stdout
