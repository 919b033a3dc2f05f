"""ZeRO-1 cross-replica optimizer sharding tests.

The contract under test (docs/usage_guides/zero_redundancy.md):
reduce-scatter grads over the batch axes -> each replica updates only its
1/n flat segment of params + optimizer state (state *born* sharded) ->
all-gather the updates. fp32 is BIT-EXACT against the replicated
baseline; quantized wire methods stay within the published TPU606
bounds; the sharded optimizer state checkpoints and elastically
restores across a mesh change."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from accelerate_tpu import Accelerator, MeshConfig, ParallelismPlugin
from accelerate_tpu.modeling import Model
from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

RNG = np.random.default_rng(7)
W_TRUE = RNG.normal(size=(32, 17)).astype(np.float32)  # 17: exercises padding
X_ALL = RNG.normal(size=(64, 32)).astype(np.float32)
Y_ALL = X_ALL @ W_TRUE
W0 = RNG.normal(size=(32, 17)).astype(np.float32) * 0.1


def mat_loss(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return ((pred - batch["y"]) ** 2).mean()


@pytest.fixture(autouse=True)
def bound_live_executables_per_test():
    """This module builds several Accelerators (= several jitted step
    programs) per test; with the whole file's executables held live,
    XLA:CPU's compiler can segfault on a late fresh compile (the
    conftest-documented ~570-live-programs crash). Clearing per TEST
    keeps the live set tiny; cross-test recompiles hit the persistent
    disk cache."""
    yield
    jax.clear_caches()



def _reset():
    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()


def make_trainer(mesh_config, zero, method=None, accum=1, tx=None, mixed=None):
    _reset()
    acc = Accelerator(
        mixed_precision=mixed,
        gradient_accumulation_steps=accum,
        parallelism_plugin=ParallelismPlugin(
            mesh_config=mesh_config,
            zero_stage=1 if zero else 0,
            grad_compression=method,
        ),
    )
    model = acc.prepare_model(
        Model(
            lambda p, x: x @ p["w"] + p["b"],
            {"w": W0.copy(), "b": np.zeros((17,), np.float32)},
        )
    )
    opt = acc.prepare_optimizer(tx if tx is not None else optax.adam(0.05))
    step = acc.build_train_step(mat_loss)
    sharding = NamedSharding(acc.mesh, P(("data", "fsdp")))

    def run(n_steps, start=0):
        losses = []
        for s in range(start, start + n_steps):
            idx = np.arange(s * 16, (s + 1) * 16) % 64
            batch = {
                "x": jax.device_put(X_ALL[idx], sharding),
                "y": jax.device_put(Y_ALL[idx], sharding),
            }
            losses.append(float(step(batch)))
        return losses

    return acc, model, opt, step, run


#: replicated data=8 baseline loss trajectories, memoized per step count —
#: several tests compare against the same baseline; training it once keeps
#: this module inside the tier-1 wall-clock budget
_BASELINE_LOSSES: dict = {}


def baseline_losses_data8(steps: int):
    if steps not in _BASELINE_LOSSES:
        _, _, _, _, run = make_trainer(MeshConfig(data=8), zero=False)
        _BASELINE_LOSSES[steps] = run(steps)
    return _BASELINE_LOSSES[steps]


# --------------------------------------------------------------------- #
# parity matrix: (1,), (4,), (2,2) data axes
# --------------------------------------------------------------------- #


@pytest.mark.parametrize(
    "mesh_config",
    [
        MeshConfig(data=1, num_devices=1),
        MeshConfig(data=4, num_devices=4),
        MeshConfig(data=2, fsdp=2, num_devices=4),
        MeshConfig(data=8),
    ],
    ids=["data1", "data4", "data2x2", "data8"],
)
def test_zero1_fp32_parity_bit_exact(mesh_config):
    """fp32 ZeRO-1 must reproduce the replicated baseline's PARAMETER
    trajectory BIT-EXACTLY on the same mesh. (The update is applied to
    the param segment inside the shard body so the add fuses with the
    optimizer chain exactly as the baseline's does.) The reported loss
    scalar may differ by an ulp on non-power-of-two batch shards — the
    user loss_fn's local mean divides before the psum, the implicit
    path divides after — so the loss check is ulp-tolerant here and
    exactly pinned in ``test_zero1_fully_bit_exact_on_pow2_shapes``."""
    acc, model, opt, step, run = make_trainer(mesh_config, zero=False)
    base_l = run(14)
    base = jax.tree.map(np.asarray, model.params)

    acc, model, opt, step, run = make_trainer(mesh_config, zero=True)
    zero_l = run(14)
    zero = jax.tree.map(np.asarray, model.params)

    np.testing.assert_allclose(zero_l, base_l, rtol=2e-6, atol=0)
    for k in base:
        assert np.array_equal(base[k], zero[k]), k
    assert base_l[-1] < base_l[0]


def test_zero1_fully_bit_exact_on_pow2_shapes():
    """With power-of-two per-shard element counts every mean is an exact
    scaling, and the ENTIRE trajectory — losses, params, optimizer
    moments — is bit-identical to the replicated baseline."""

    def trainer(zero):
        _reset()
        acc = Accelerator(
            parallelism_plugin=ParallelismPlugin(
                mesh_config=MeshConfig(data=8), zero_stage=1 if zero else 0
            )
        )
        model = acc.prepare_model(
            Model(
                lambda p, x: x @ p["w"] + p["b"],
                {"w": W0[:, :16].copy(), "b": np.zeros((16,), np.float32)},
            )
        )
        opt = acc.prepare_optimizer(optax.adam(0.05))
        step = acc.build_train_step(mat_loss)
        sharding = NamedSharding(acc.mesh, P(("data", "fsdp")))
        losses = []
        for s in range(20):
            idx = np.arange(s * 16, (s + 1) * 16) % 64
            losses.append(float(step({
                "x": jax.device_put(X_ALL[idx], sharding),
                "y": jax.device_put(Y_ALL[idx][:, :16], sharding),
            })))
        return losses, jax.tree.map(np.asarray, model.params), opt

    base_l, base_p, base_o = trainer(False)
    zero_l, zero_p, zero_o = trainer(True)
    assert zero_l == base_l, (zero_l[-3:], base_l[-3:])
    for k in base_p:
        assert np.array_equal(base_p[k], zero_p[k]), k
    for a, b in zip(
        jax.tree_util.tree_leaves(base_o.opt_state),
        jax.tree_util.tree_leaves(zero_o.opt_state),
    ):
        assert np.array_equal(np.asarray(a).reshape(-1), np.asarray(b).reshape(-1))


def test_zero1_fp32_parity_across_meshes():
    """(2,2) batch axes vs a plain data=4 baseline: the zero shard axis is
    the flattened (data, fsdp) group and the math is identical."""
    _, m4, _, _, run4 = make_trainer(MeshConfig(data=4, num_devices=4), zero=False)
    l4 = run4(14)
    p4 = jax.tree.map(np.asarray, m4.params)
    _, m22, _, _, run22 = make_trainer(
        MeshConfig(data=2, fsdp=2, num_devices=4), zero=True
    )
    l22 = run22(14)
    p22 = jax.tree.map(np.asarray, m22.params)
    np.testing.assert_allclose(l22, l4, rtol=2e-6, atol=0)
    for k in p4:
        assert np.array_equal(p4[k], p22[k]), k


def test_zero1_accumulation_parity():
    """Gradient accumulation rides the sharded buffer (reduce-scatter per
    microbatch, ZeRO-2 flavour) and stays bit-exact vs the baseline."""
    _, mb, _, _, runb = make_trainer(MeshConfig(data=8), zero=False, accum=2)
    lb = runb(16)
    pb = jax.tree.map(np.asarray, mb.params)
    _, mz, _, _, runz = make_trainer(MeshConfig(data=8), zero=True, accum=2)
    lz = runz(16)
    pz = jax.tree.map(np.asarray, mz.params)
    np.testing.assert_allclose(lz, lb, rtol=2e-6, atol=0)
    for k in pb:
        assert np.array_equal(pb[k], pz[k]), k


@pytest.mark.parametrize("method", ["int8", "fp8", "bf16"])
def test_zero1_quantized_parity_within_bound(method, no_persistent_compile_cache):
    """zero_stage=1 + quantized wire: trajectory tracks the replicated
    fp32 baseline within quantization tolerance and converges (error
    feedback carries what the quantizer drops)."""
    base_l = baseline_losses_data8(30)
    _, _, _, _, runq = make_trainer(MeshConfig(data=8), zero=True, method=method)
    q_l = runq(30)
    np.testing.assert_allclose(q_l, base_l, atol=0.06, rtol=0.15)
    assert q_l[-1] < q_l[0] / 2


def test_zero1_collectives_within_tpu606_bound(mesh8):
    """The TPU606 pin at the collective level: one reduce-scatter +
    all-gather round trip through the quantized pair stays within the
    published per-element bound of its numerics model — with zero carried
    residual, the bound must hold for a single shot."""
    from accelerate_tpu.analysis.numerics_rules import COMPRESSION_NUMERICS
    from accelerate_tpu.parallel.zero import all_gather_updates, reduce_scatter_grads
    from jax import shard_map

    n = 8
    g = jax.random.normal(jax.random.key(3), (8, 1024), jnp.float32) * 2.5

    def roundtrip(method):
        def body(x):
            flat = {"g": x[0] * (1.0 / n)}
            err0 = None if method is None else {"g": jnp.zeros_like(flat["g"])}
            shard, _ = reduce_scatter_grads(flat, ("data",), n, method, err0)
            err1 = None if method is None else {"g": jnp.zeros_like(shard["g"])}
            full, _ = all_gather_updates(shard, ("data",), n, method, err1)
            return full["g"][None]

        fn = shard_map(
            body, mesh=mesh8, in_specs=P("data"), out_specs=P("data"), check_vma=False
        )
        out = np.asarray(fn(g))
        return out.reshape(8, -1)[0]

    exact = roundtrip(None)
    amax = float(np.abs(np.asarray(g)).max())
    for method in ("int8", "fp8", "bf16"):
        err = float(np.abs(roundtrip(method) - exact).max())
        bound = COMPRESSION_NUMERICS[method].bound(amax, n)
        assert err <= bound, (
            f"{method}: |error| {err:.3e} exceeds the TPU606 bound {bound:.3e} "
            f"({COMPRESSION_NUMERICS[method].describe})"
        )


# --------------------------------------------------------------------- #
# the HBM claim: optimizer state born sharded
# --------------------------------------------------------------------- #


def test_zero1_opt_state_born_sharded():
    acc, model, opt, step, run = make_trainer(MeshConfig(data=8), zero=True)
    n = 8
    for leaf in jax.tree_util.tree_leaves(opt.opt_state):
        if getattr(leaf, "ndim", 0) == 0:
            continue
        spec = leaf.sharding.spec
        assert spec and spec[0], f"vector state leaf not sharded: {leaf.shape} {spec}"
        # per-device shard is 1/n of the global flat length
        assert leaf.addressable_shards[0].data.shape[0] * n == leaf.shape[0]
    # padding: w is 32*17=544 -> stays 544 (divisible); b is 17 -> pads to 24
    lens = sorted({l.shape[0] for l in jax.tree_util.tree_leaves(opt.opt_state) if getattr(l, "ndim", 0)})
    assert lens == [24, 544]
    run(3)  # and it trains


def test_zero1_flight_check_sees_sharded_state():
    """The static peak-HBM walk must see the 1/n optimizer state: the
    zero1 arm's predicted peak drops vs the replicated baseline by AT
    LEAST the optimizer-state win opt_bytes*(n-1)/n (the sharded
    accumulation buffer wins more on top)."""
    from accelerate_tpu.utils.random import key_for_step

    peaks, opt_bytes = {}, {}
    for zero in (False, True):
        acc, model, opt, step, run = make_trainer(MeshConfig(data=8), zero=zero)
        box = acc._fast_scale_boxes[-1]
        inner = step._jitted.__wrapped__
        sync = True if zero else jnp.bool_(True)

        def fn(p, o, g, b, s, r, c, cs, _inner=inner, _sync=sync):
            return _inner(p, o, g, None, b, s, _sync, r, c, cs)

        sharding = NamedSharding(acc.mesh, P(("data", "fsdp")))
        batch = {
            "x": jax.device_put(X_ALL[:16], sharding),
            "y": jax.device_put(Y_ALL[:16], sharding),
        }
        report = acc.flight_check(
            fn, model.params, opt.opt_state, box["grad_buf"], batch,
            box["scale_state"], key_for_step(0), jnp.float32(-1.0), box["comp_state"],
            donate_argnums=(0, 1, 2),
        )
        peaks[zero] = report.peak_hbm_bytes
        opt_bytes[zero] = sum(
            l.size * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(opt.opt_state)
            if hasattr(l, "size")
        )
    n = 8
    opt_win = opt_bytes[False] * (n - 1) // n
    assert peaks[True] < peaks[False], peaks
    assert peaks[False] - peaks[True] >= opt_win, (peaks, opt_win)


# --------------------------------------------------------------------- #
# wire bytes: prediction vs compiled-HLO measurement
# --------------------------------------------------------------------- #


def test_zero1_wire_bytes_predicted_vs_measured():
    """costmodel-predicted bytes-on-wire vs the compiled program's actual
    collectives (telemetry.wire): within 10% on every arm, and zero1+int8
    moves ~25% of the replicated-f32 baseline's bytes."""
    from accelerate_tpu.parallel.compression import wire_bytes
    from accelerate_tpu.telemetry.wire import hlo_wire_bytes
    from accelerate_tpu.utils.random import key_for_step

    measured, predicted = {}, {}
    for name, (zero, method) in {
        "baseline": (False, None),
        "zero1": (True, None),
        "zero1_int8": (True, "int8"),
    }.items():
        acc, model, opt, step, run = make_trainer(MeshConfig(data=8), zero=zero, method=method)
        box = acc._fast_scale_boxes[-1]
        sharding = NamedSharding(acc.mesh, P(("data", "fsdp")))
        batch = {
            "x": jax.device_put(X_ALL[:16], sharding),
            "y": jax.device_put(Y_ALL[:16], sharding),
        }
        args = (
            model.params, opt.opt_state, box["grad_buf"], None, batch,
            box["scale_state"], True if zero else jnp.bool_(True),
            key_for_step(0), jnp.float32(-1.0), box["comp_state"],
        )
        hlo = step._jitted.lower(*args).compile().as_text()
        measured[name] = hlo_wire_bytes(hlo)["total"]
        predicted[name] = wire_bytes(
            model.params, method, n=8, zero_stage=1 if zero else 0
        )
    for name in measured:
        drift = abs(measured[name] - predicted[name]) / predicted[name]
        assert drift < 0.10, (name, predicted[name], measured[name])
    assert measured["zero1_int8"] <= 0.30 * measured["baseline"]


def test_zero1_no_gradient_sized_allreduce_in_hlo():
    """The compiled sync program must not all-reduce anything
    gradient-sized — the wire claim is reduce-scatter + all-gather."""
    import re

    from accelerate_tpu.utils.random import key_for_step

    acc, model, opt, step, run = make_trainer(MeshConfig(data=8), zero=True)
    box = acc._fast_scale_boxes[-1]
    sharding = NamedSharding(acc.mesh, P(("data", "fsdp")))
    batch = {
        "x": jax.device_put(X_ALL[:16], sharding),
        "y": jax.device_put(Y_ALL[:16], sharding),
    }
    hlo = step._jitted.lower(
        model.params, opt.opt_state, box["grad_buf"], None, batch,
        box["scale_state"], True, key_for_step(0), jnp.float32(-1.0),
        box["comp_state"],
    ).compile().as_text()
    assert "reduce-scatter" in hlo and "all-gather" in hlo
    for m in re.finditer(r"= \(?f32\[([0-9,]*)\][^=]*? all-reduce\(", hlo):
        dims = [int(d) for d in m.group(1).split(",") if d]
        size = int(np.prod(dims)) if dims else 1
        assert size < 544, f"gradient-sized all-reduce survived: {m.group(0)}"


# --------------------------------------------------------------------- #
# sharded grad norm: clip + watchdog (regression)
# --------------------------------------------------------------------- #


def test_zero1_clip_grad_norm_matches_baseline():
    """clip_grad_norm_ on ZeRO-sharded shards: the norm is computed via a
    psum of local partial sums (never a gathered tree) and the clipped
    trajectory matches the replicated baseline bit-for-bit... the norm
    itself within float tolerance (summation order differs by design)."""
    def clipped(zero):
        acc, model, opt, step, run = make_trainer(MeshConfig(data=8), zero=zero)
        acc.clip_grad_norm_(max_norm=0.5)
        losses = run(12)
        return losses, float(acc._last_grad_norm), jax.tree.map(np.asarray, model.params)

    bl, bnorm, bp = clipped(False)
    zl, znorm, zp = clipped(True)
    assert np.isclose(znorm, bnorm, rtol=1e-5), (znorm, bnorm)
    np.testing.assert_allclose(zl, bl, atol=1e-5, rtol=1e-5)
    for k in bp:
        np.testing.assert_allclose(zp[k], bp[k], atol=1e-6)


def test_sharded_global_norm_is_psum_of_partials(mesh8):
    from accelerate_tpu.parallel.zero import sharded_global_norm
    from jax import shard_map

    x = jax.random.normal(jax.random.key(0), (8, 64), jnp.float32)

    fn = shard_map(
        lambda v: sharded_global_norm({"g": v[0]}, ("data",))[None],
        mesh=mesh8, in_specs=P("data"), out_specs=P("data"), check_vma=False,
    )
    got = np.asarray(fn(x))
    want = float(np.linalg.norm(np.asarray(x).reshape(-1)))
    assert np.allclose(got, want, rtol=1e-5)


def test_nonfinite_watchdog_probes_sharded_grads_without_gather(mesh8):
    """Regression: the watchdog's grad probe must find a non-finite leaf
    in a data-sharded tree via an on-device reduction (np.asarray on a
    distributed array would gather it)."""
    from accelerate_tpu.telemetry import NonFiniteWatchdog

    sharded = jax.device_put(
        np.ones((8, 16), np.float32), NamedSharding(mesh8, P("data"))
    )
    bad = sharded.at[5, 3].set(np.nan)
    wd = NonFiniteWatchdog(every=1)
    rec = wd.observe(1, grads={"ok": sharded, "boom": bad})
    assert rec["bad_leaf"] == "grads['boom']"
    assert wd.nonfinite_event is not None
    # clean tree stays quiet
    wd2 = NonFiniteWatchdog(every=1)
    assert wd2.observe(1, grads={"ok": sharded})["bad_leaf"] is None


def test_zero1_fp16_overflow_holds_params_and_recovers(no_persistent_compile_cache):
    """An overflowed fp16 microbatch must hold params/opt state (finite
    gate), back off the scale, and NOT poison the error-feedback carries
    under the quantized wire."""
    acc, model, opt, step, run = make_trainer(
        MeshConfig(data=8), zero=True, method="int8", mixed="fp16"
    )
    run(5)
    before = jax.tree.map(np.asarray, model.params)
    sharding = NamedSharding(acc.mesh, P(("data", "fsdp")))
    bad = {
        "x": jax.device_put(np.full((16, 32), 1e4, np.float32), sharding),
        "y": jax.device_put(np.zeros((16, 17), np.float32), sharding),
    }
    step(bad)
    after = jax.tree.map(np.asarray, model.params)
    for k in before:
        assert np.array_equal(before[k], after[k]), f"params moved on overflow: {k}"
    losses = run(28, start=1)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


# --------------------------------------------------------------------- #
# checkpoint + elastic restore
# --------------------------------------------------------------------- #


def test_zero1_checkpoint_elastic_restore_across_mesh_change():
    """Save the sharded optimizer state on a data=4 mesh, restore onto
    data=2: values survive exactly (strip saved padding, re-pad for the
    new degree), land 1/n-sharded on the new mesh, and training resumes
    on the baseline trajectory."""
    with tempfile.TemporaryDirectory() as tmp:
        ck = os.path.join(tmp, "ck")
        acc, model, opt, step, run = make_trainer(
            MeshConfig(data=4, num_devices=4), zero=True
        )
        run(6)
        saved_leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(opt.opt_state)]
        sizes = opt._zero1_state_sizes
        acc.save_state(ck)

        acc2, model2, opt2, step2, run2 = make_trainer(
            MeshConfig(data=2, num_devices=2), zero=True
        )
        acc2.load_state(ck)
        new_leaves = jax.tree_util.tree_leaves(opt2.opt_state)
        for old, new, size in zip(saved_leaves, new_leaves, sizes):
            t = size if size is not None else min(old.size, np.asarray(new).size)
            assert np.array_equal(
                old.reshape(-1)[:t], np.asarray(new).reshape(-1)[:t]
            ), (old.shape, np.asarray(new).shape, size)
            if size is not None:
                assert new.shape[0] % 2 == 0
                assert new.sharding.spec[0], "restored leaf lost its shard layout"
        # params restored exactly; training continues on the baseline path
        assert np.array_equal(
            np.asarray(model.params["w"]), np.asarray(model2.params["w"])
        )
        # reference: an uninterrupted data=2 run from the restored point
        resumed = run2(6, start=6)
        assert np.isfinite(resumed).all()


def test_zero1_same_mesh_restore_is_exact():
    with tempfile.TemporaryDirectory() as tmp:
        ck = os.path.join(tmp, "ck")
        acc, model, opt, step, run = make_trainer(MeshConfig(data=8), zero=True)
        l1 = run(4)
        acc.save_state(ck)
        cont = run(4, start=4)

        acc2, model2, opt2, step2, run2 = make_trainer(MeshConfig(data=8), zero=True)
        acc2.load_state(ck)
        cont2 = run2(4, start=4)
        assert cont == cont2


# --------------------------------------------------------------------- #
# dogfood: the analysis moat runs clean over the zero step
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("method", [None, "int8"])
def test_zero1_step_analysis_clean(method):
    """perf-check carries no TPU502/503 (redundant / latency-bound
    collectives) and numerics-check no TPU6xx findings over the real
    jitted zero step — the quantized wire carries error feedback, which
    is exactly what TPU606 demands."""
    from accelerate_tpu.utils.random import key_for_step

    acc, model, opt, step, run = make_trainer(MeshConfig(data=8), zero=True, method=method)
    box = acc._fast_scale_boxes[-1]
    inner = step._jitted.__wrapped__

    def fn(p, o, g, b, s, r, c, cs):
        return inner(p, o, g, None, b, s, True, r, c, cs)

    fn.__name__ = "zero1_train_step"
    sharding = NamedSharding(acc.mesh, P(("data", "fsdp")))
    batch = {
        "x": jax.device_put(X_ALL[:16], sharding),
        "y": jax.device_put(Y_ALL[:16], sharding),
    }
    args = (
        model.params, opt.opt_state, box["grad_buf"], batch,
        box["scale_state"], key_for_step(0), jnp.float32(-1.0), box["comp_state"],
    )
    perf = acc.perf_check(fn, *args)
    bad = [f for f in perf.findings if f.rule in ("TPU502", "TPU503")]
    assert bad == [], [f.message for f in bad]
    assert not any(f.is_error for f in perf.findings), [f.message for f in perf.findings]
    numerics = acc.numerics_check(fn, *args)
    assert numerics.findings == [], [f.message for f in numerics.findings]


def test_zero1_zero_recompiles_post_warmup():
    """Two stable programs (sync + non-sync): after the warmup step, no
    signature is ever new — the recompile watchdog stays quiet."""
    acc, model, opt, step, run = make_trainer(MeshConfig(data=8), zero=True, accum=2)
    tel = acc.telemetry
    wrapped = tel.wrap(step)
    sharding = NamedSharding(acc.mesh, P(("data", "fsdp")))
    for s in range(12):
        idx = np.arange(s * 16, (s + 1) * 16) % 64
        wrapped({
            "x": jax.device_put(X_ALL[idx], sharding),
            "y": jax.device_put(Y_ALL[idx], sharding),
        })
    assert tel.recompiles == 0, tel.summary()


# --------------------------------------------------------------------- #
# knob surface / validation
# --------------------------------------------------------------------- #


def test_zero1_plugin_validation():
    with pytest.raises(ValueError, match="powersgd"):
        ParallelismPlugin(zero_stage=1, grad_compression="powersgd:2")
    with pytest.raises(ValueError, match="offload"):
        ParallelismPlugin(zero_stage=1, offload_optimizer=True)
    with pytest.raises(ValueError, match="shard_optimizer_state"):
        ParallelismPlugin(zero_stage=1, shard_optimizer_state=True)
    with pytest.raises(ValueError, match="zero_stage"):
        ParallelismPlugin(zero_stage=3)
    ParallelismPlugin(zero_stage=1, grad_compression="fp8")  # stacks


def test_zero1_env_knob(monkeypatch):
    monkeypatch.setenv("ACCELERATE_ZERO_STAGE", "1")
    plugin = ParallelismPlugin.from_env()
    assert plugin.zero_stage == 1


def test_zero1_rejects_tensor_axes():
    _reset()
    acc = Accelerator(
        parallelism_plugin=ParallelismPlugin(
            mesh_config=MeshConfig(data=4, tensor=2), zero_stage=1
        )
    )
    model = acc.prepare_model(
        Model(lambda p, x: x @ p["w"], {"w": np.zeros((32, 16), np.float32)})
    )
    with pytest.raises(ValueError, match="batch axes"):
        acc.prepare_optimizer(optax.sgd(0.1))
        acc.build_train_step(lambda p, b: ((b["x"] @ p["w"]) ** 2).mean())


def test_zero1_nonelementwise_transform_falls_back_with_warning(caplog):
    """zero_stage=1 with a factored optax transform (adafactor couples
    elements within a leaf) must not silently change the update
    semantics: it warns ONCE naming the offending state node
    (FactoredState) and the fallback taken, then takes the passive
    shard_optimizer_state layout — state GSPMD-sharded over the data
    axis, no flat-segment wire split."""
    import logging

    from accelerate_tpu import accelerator as acc_mod

    acc_mod._ZERO1_FALLBACK_WARNED.clear()
    caplog.set_level(logging.WARNING, logger="accelerate_tpu.accelerator")
    acc, model, opt, step, run = make_trainer(
        MeshConfig(data=8), zero=True, tx=optax.adafactor(0.1)
    )
    warned = [r for r in caplog.records if "zero_stage=1 requires an elementwise" in r.getMessage()]
    assert len(warned) == 1
    assert "FactoredState" in warned[0].getMessage()
    assert "shard_optimizer_state" in warned[0].getMessage()
    # explicit layout skipped, fallback recorded on the optimizer
    assert getattr(opt, "_zero1_layout", None) is None
    assert acc.zero1_fallback_reason(opt) == ("FactoredState",)
    # the state is passively sharded over the data axis (1/n per device)
    specs = {
        tuple(getattr(leaf.sharding, "spec", ()) or ())
        for leaf in jax.tree_util.tree_leaves(opt.opt_state)
        if getattr(leaf, "ndim", 0) >= 1
    }
    assert any("data" in str(s) for s in specs), specs
    # and the step still trains (batches cycle with period 4: compare
    # the same batch before/after one full data pass)
    losses = run(5)
    assert losses[4] < losses[0]
    # one-time: a second adafactor trainer does not re-warn
    caplog.clear()
    make_trainer(MeshConfig(data=8), zero=True, tx=optax.adafactor(0.1))
    assert not [r for r in caplog.records if "zero_stage=1 requires" in r.getMessage()]
    # an elementwise transform keeps the explicit flat-segment path
    _, _, opt3, _, _ = make_trainer(MeshConfig(data=8), zero=True, tx=optax.adam(0.05))
    assert getattr(opt3, "_zero1_layout", None) is not None
    assert acc.zero1_fallback_reason(opt3) is None


def test_zero1_imperative_path_rejected():
    acc, model, opt, step, run = make_trainer(MeshConfig(data=8), zero=True)
    with pytest.raises(NotImplementedError, match="build_train_step"):
        acc.backward(mat_loss, {"x": X_ALL[:16], "y": Y_ALL[:16]})
        opt.step()


def test_zero1_degenerates_on_single_shard():
    """data=1: nothing to shard — the plain replicated path runs and the
    optimizer state keeps its parameter shapes."""
    acc, model, opt, step, run = make_trainer(MeshConfig(data=1, num_devices=1), zero=True)
    assert getattr(opt, "_zero1_layout", None) is None
    shapes = {tuple(l.shape) for l in jax.tree_util.tree_leaves(opt.opt_state) if getattr(l, "ndim", 0)}
    assert (32, 17) in shapes
    run(2)


# --------------------------------------------------------------------- #
# satellite: grad_compression now composes with has_state / has_aux
# --------------------------------------------------------------------- #


def test_compression_composes_with_has_aux_and_state():
    """The former `does not compose with has_state/has_aux` restriction at
    the top of build_train_step is lifted: aux and mutable state thread
    through the explicit per-shard-grad path (float leaves pmean'd)."""

    def loss_with_state(params, state, batch):
        pred = batch["x"] @ params["w"] + params["b"]
        loss = ((pred - batch["y"]) ** 2).mean()
        new_state = {"batch_mean": batch["x"].mean(), "count": state["count"] + 1}
        return loss, (new_state, {"mse": loss})

    def train(method):
        _reset()
        acc = Accelerator(
            parallelism_plugin=ParallelismPlugin(
                mesh_config=MeshConfig(data=8), grad_compression=method
            )
        )
        model = acc.prepare_model(
            Model(
                lambda p, x: x @ p["w"] + p["b"],
                {"w": W0.copy(), "b": np.zeros((17,), np.float32)},
            )
        )
        model.state = {"batch_mean": jnp.float32(0.0), "count": jnp.int32(0)}
        acc.prepare_optimizer(optax.adam(0.05))
        step = acc.build_train_step(loss_with_state, has_state=True, has_aux=True)
        sharding = NamedSharding(acc.mesh, P(("data", "fsdp")))
        out = []
        for s in range(20):
            idx = np.arange(s * 16, (s + 1) * 16) % 64
            loss, aux = step({
                "x": jax.device_put(X_ALL[idx], sharding),
                "y": jax.device_put(Y_ALL[idx], sharding),
            })
            out.append((float(loss), float(aux["mse"])))
        return out, model.state

    plain, state_p = train(None)
    comp, state_c = train("int8")
    assert int(state_c["count"]) == 20
    np.testing.assert_allclose(
        float(state_c["batch_mean"]), float(state_p["batch_mean"]), rtol=1e-5
    )
    np.testing.assert_allclose(
        [l for l, _ in comp], [l for l, _ in plain], atol=0.05, rtol=0.1
    )
    for loss, mse in comp:
        assert np.isclose(loss, mse)


def test_zero1_with_has_aux():
    """ZeRO-1 threads aux through the shard body (pmean'd)."""

    def loss_aux(params, batch):
        pred = batch["x"] @ params["w"] + params["b"]
        loss = ((pred - batch["y"]) ** 2).mean()
        return loss, {"mae": jnp.abs(pred - batch["y"]).mean()}

    _reset()
    acc = Accelerator(
        parallelism_plugin=ParallelismPlugin(mesh_config=MeshConfig(data=8), zero_stage=1)
    )
    model = acc.prepare_model(
        Model(
            lambda p, x: x @ p["w"] + p["b"],
            {"w": W0.copy(), "b": np.zeros((17,), np.float32)},
        )
    )
    acc.prepare_optimizer(optax.adam(0.05))
    step = acc.build_train_step(loss_aux, has_aux=True)
    sharding = NamedSharding(acc.mesh, P(("data", "fsdp")))
    losses = []
    for s in range(10):
        idx = np.arange(s * 16, (s + 1) * 16) % 64
        loss, aux = step({
            "x": jax.device_put(X_ALL[idx], sharding),
            "y": jax.device_put(Y_ALL[idx], sharding),
        })
        losses.append(float(loss))
        assert np.isfinite(float(aux["mae"]))
    assert losses[-1] < losses[0]


def test_zero1_optimizer_state_dict_roundtrip_repads():
    """The host-side state_dict/load_state_dict pair (the
    register_for_checkpointing path, not orbax) also re-pads a snapshot
    taken at a different data-parallel degree."""
    acc4, _, opt4, _, run4 = make_trainer(MeshConfig(data=4, num_devices=4), zero=True)
    run4(3)
    snap = opt4.state_dict()
    sizes = opt4._zero1_state_sizes

    acc2, _, opt2, _, run2 = make_trainer(MeshConfig(data=2, num_devices=2), zero=True)
    opt2.load_state_dict(snap)
    for old, new, size in zip(
        snap["leaves"], jax.tree_util.tree_leaves(opt2.opt_state), sizes
    ):
        t = size if size is not None else np.asarray(old).size
        assert np.array_equal(
            np.asarray(old).reshape(-1)[:t], np.asarray(new).reshape(-1)[:t]
        )
    run2(2, start=3)
