"""Gradient-compression tests (reference parity: DDP comm hooks —
fp16/bf16 compress + register_comm_hook, utils/dataclasses.py:130-226)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu import Accelerator, MeshConfig, ParallelismPlugin
from jax import shard_map
from accelerate_tpu.parallel.compression import compressed_psum_mean, wire_bytes
from accelerate_tpu.test_utils import RegressionDataset, RegressionModel, linear_loss_fn


def test_compressed_psum_mean_matches_plain(mesh8):
    from jax.sharding import PartitionSpec as P

    g = jax.random.normal(jax.random.key(0), (8, 16), jnp.float32)

    def reduce(method):
        def body(x):
            local = jax.tree.map(lambda l: l, {"g": x})
            if method is None:
                return jax.tree.map(lambda l: jax.lax.pmean(l, "data"), local)
            return compressed_psum_mean(local, "data", method)

        fn = shard_map(body, mesh=mesh8, in_specs=P("data"), out_specs=P(), check_vma=False)
        return np.asarray(fn(g)["g"])

    exact = reduce(None)
    np.testing.assert_allclose(reduce("bf16"), exact, atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(reduce("int8"), exact, atol=2e-2, rtol=5e-2)


def test_compressed_psum_mean_within_tpu606_bound(mesh8):
    """The parity pin behind numerics rule TPU606: the compressed mean
    must match the exact f32 mean within the per-leaf error bound the
    rule prices (``analysis.numerics_rules.COMPRESSION_NUMERICS``) —
    across five decades of gradient magnitude. If a compression change
    ever violates its published bound, this is the test that catches it."""
    from jax.sharding import PartitionSpec as P

    from accelerate_tpu.analysis.numerics_rules import COMPRESSION_NUMERICS

    n = 8
    for seed in (0, 2, 4):  # gradient scales 1e-2, 1, 1e2
        g = jax.random.normal(jax.random.key(seed), (8, 64), jnp.float32) * (10.0 ** (seed - 2))

        def reduce(method):
            def body(x):
                local = {"g": x}
                if method is None:
                    return jax.tree.map(lambda l: jax.lax.pmean(l, "data"), local)
                return compressed_psum_mean(local, "data", method)

            fn = shard_map(body, mesh=mesh8, in_specs=P("data"), out_specs=P(), check_vma=False)
            return np.asarray(fn(g)["g"])

        exact = reduce(None)
        amax = float(np.abs(np.asarray(g)).max())
        for method in ("bf16", "int8"):
            err = float(np.abs(reduce(method) - exact).max())
            bound = COMPRESSION_NUMERICS[method].bound(amax, n)
            assert err <= bound, (
                f"{method} @ seed {seed}: |error| {err:.3e} exceeds the "
                f"TPU606 bound {bound:.3e} ({COMPRESSION_NUMERICS[method].describe})"
            )


def test_wire_bytes_accounting():
    tree = {"a": jnp.zeros((100, 10)), "b": jnp.zeros((50,))}
    assert wire_bytes(tree, None) == 1050 * 8  # reduce-scatter + all-gather, f32
    assert wire_bytes(tree, "bf16") == 1050 * 4
    # int8: 1 B/elem per leg + two ring-priced pmax'd f32 amax scalars per
    # leaf (2 transfers x 4 B each in the limit)
    assert wire_bytes(tree, "int8") == 1050 * 2 + 2 * 2 * 2 * 4
    assert wire_bytes(tree, "fp8") == wire_bytes(tree, "int8")
    assert wire_bytes(tree, "int8") < wire_bytes(tree, None) // 3
    # exact ring terms with an explicit group size
    n = 8
    assert wire_bytes(tree, None, n=n) == round(1050 * 4 * 2 * (n - 1) / n)
    # zero_stage=1: reduce-scatter + all-gather legs over padded flats
    # (100*10 pads to 1000, 50 pads to 56 at n=8)
    assert wire_bytes(tree, None, n=n, zero_stage=1) == 2 * round(4 * 1000 * (n - 1) / n) + 2 * round(4 * 56 * (n - 1) / n)
    assert wire_bytes(tree, "int8", n=n, zero_stage=1) < wire_bytes(tree, None, n=n, zero_stage=1) // 3
    # quantized zero1 vs replicated f32 baseline: the headline claim
    assert wire_bytes(tree, "int8", n=n, zero_stage=1) <= 0.27 * wire_bytes(tree, None, n=n)


def test_wire_bytes_pins_costmodel_ring_formulas(mesh8):
    """Satellite pin: ``wire_bytes`` must agree with the cost model's ring
    formulas (``price_collective``) for every collective its plan fires —
    psum / reduce-scatter / all-gather / all-to-all, across methods and
    both zero stages. One set of formulas; units of truth cannot drift."""
    from accelerate_tpu.analysis.costmodel import price_collective, ring_wire_bytes
    from accelerate_tpu.parallel.compression import wire_plan

    tree = {"k": jnp.zeros((96, 16)), "b": jnp.zeros((50,))}
    n = 8
    for zero_stage in (0, 1):
        for method in (None, "bf16", "int8", "fp8"):
            total = 0
            for prim, payload in wire_plan(tree, method, zero_stage=zero_stage, n=n):
                # price_collective takes the jaxpr operand: the all_gather
                # operand is the per-shard input, everything else the full
                # payload
                operand = payload // n if prim == "all_gather" else payload
                rec = price_collective(prim, ("data",), operand, mesh8)
                assert rec is not None, prim
                assert rec.wire_bytes == ring_wire_bytes(prim, payload, n), (prim, payload)
                total += rec.wire_bytes
            assert total == wire_bytes(tree, method, n=n, zero_stage=zero_stage), (
                zero_stage, method,
            )


def test_int8_keeps_int8_on_the_wire(mesh8):
    """The compiled HLO must not contain an int32/f32 allreduce of the
    gradient payload — the compression claim is about wire bytes."""
    from jax.sharding import PartitionSpec as P

    g = jax.random.normal(jax.random.key(0), (8, 64), jnp.float32)
    fn = jax.jit(
        shard_map(
            lambda x: compressed_psum_mean({"g": x}, "data", "int8")["g"],
            mesh=mesh8, in_specs=P("data"), out_specs=P(), check_vma=False,
        )
    )
    hlo = fn.lower(g).compile().as_text()
    import re

    for op in ("all-to-all", "all-gather"):
        for m in re.finditer(rf"{op}[^=]*= \(?([a-z0-9]+)\[", hlo):
            assert m.group(1) in ("s8", "u8"), f"{op} moves {m.group(1)}, not int8:\n{m.group(0)}"


@pytest.mark.parametrize("method", ["bf16", "int8"])
def test_compressed_training_converges_like_plain(method):
    """Same model/data trained with and without compression: both converge,
    trajectories stay within compression tolerance (reference done-bar:
    identical convergence within tolerance)."""

    def train(compression):
        from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()
        acc = Accelerator(
            parallelism_plugin=ParallelismPlugin(
                mesh_config=MeshConfig(data=8), grad_compression=compression
            )
        )
        model = acc.prepare_model(RegressionModel())
        acc.prepare_optimizer(optax.sgd(0.1))
        step = acc.build_train_step(linear_loss_fn)
        ds = RegressionDataset(length=64)
        losses = []
        for s in range(48):
            idx = np.arange(s * 16, (s + 1) * 16) % 64
            batch = {"x": ds.x[idx], "y": ds.y[idx]}
            losses.append(float(step(batch)))
        return losses, jax.tree.map(np.asarray, model.params)

    plain_losses, plain_params = train(None)
    comp_losses, comp_params = train(method)
    assert comp_losses[-1] < 0.05, comp_losses[-5:]
    # per-step trajectory stays inside compression rounding of the exact run
    np.testing.assert_allclose(comp_losses, plain_losses, atol=0.02, rtol=0.1)
    for k in plain_params:
        np.testing.assert_allclose(comp_params[k], plain_params[k], atol=0.1, rtol=0.1)


def test_powersgd_rank_parsing():
    from accelerate_tpu.parallel.compression import powersgd_rank

    assert powersgd_rank("powersgd") == 1
    assert powersgd_rank("powersgd:4") == 4
    assert powersgd_rank("bf16") is None and powersgd_rank(None) is None
    with pytest.raises(ValueError):
        powersgd_rank("powersgd:0")
    with pytest.raises(ValueError):
        ParallelismPlugin(grad_compression="powersgd:x")
    # the plugin accepts the method strings
    ParallelismPlugin(grad_compression="powersgd:2")


def _psgd_reduce(mesh8, grads, state, rank):
    """Run one powersgd_psum_mean over the 8-way data axis; grads [8, n, m]
    (one matrix per shard), state error [8, n, m]."""
    from jax.sharding import PartitionSpec as P

    from accelerate_tpu.parallel.compression import powersgd_psum_mean

    def body(g, e, q):
        out, new = powersgd_psum_mean(
            {"w": g[0]}, "data", {"error": {"w": e[0]}, "q": {"w": q}}, rank
        )
        return out["w"], new["error"]["w"][None], new["q"]["w"]

    fn = shard_map(
        body, mesh=mesh8,
        in_specs=(P("data"), P("data"), P()),
        out_specs=(P(), P("data"), P()),
        check_vma=False,
    )
    return fn(grads, state["error"], state["q"])


def test_powersgd_exact_on_lowrank_and_feedback_identity(mesh8):
    """A gradient whose mean is rank-1 is reproduced exactly at r>=1, and
    the algebraic error-feedback identity g + e_prev == approx + e_new
    holds per shard (that identity is WHY the biased compressor converges:
    nothing is ever dropped, only delayed)."""
    from accelerate_tpu.parallel.compression import powersgd_init_state

    rng = np.random.default_rng(0)
    u = rng.normal(size=(24, 1)).astype(np.float32)
    v = rng.normal(size=(1, 16)).astype(np.float32)
    # identical rank-1 matrix on every shard -> mean is rank-1
    grads = jnp.broadcast_to(jnp.asarray(u @ v), (8, 24, 16))
    state = powersgd_init_state({"w": grads[0]}, 2, 8)
    state = {"error": state["error"]["w"], "q": state["q"]["w"]}
    approx, new_err, _ = _psgd_reduce(mesh8, grads, state, rank=2)
    np.testing.assert_allclose(np.asarray(approx), u @ v, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(new_err), 0.0, atol=1e-4)

    # feedback identity on a full-rank gradient with nonzero carried error
    grads2 = jnp.asarray(rng.normal(size=(8, 24, 16)).astype(np.float32))
    err0 = jnp.asarray(rng.normal(size=(8, 24, 16)).astype(np.float32))
    approx2, err2, _ = _psgd_reduce(mesh8, grads2, {"error": err0, "q": state["q"]}, rank=2)
    np.testing.assert_allclose(
        np.asarray(grads2 + err0),
        np.asarray(jnp.broadcast_to(approx2, (8, 24, 16)) + err2),
        atol=1e-4, rtol=1e-4,
    )


def test_powersgd_wire_bytes_and_hlo(mesh8):
    """Wire accounting: only the rank-r factors cross the wire; the HLO must
    not all-reduce anything gradient-sized."""
    from jax.sharding import PartitionSpec as P

    from accelerate_tpu.parallel.compression import (
        powersgd_init_state, powersgd_psum_mean, wire_bytes,
    )

    tree = {"k": jnp.zeros((256, 128)), "b": jnp.zeros((128,))}
    r = 2
    # k: P[256,2]+Q[128,2] f32 allreduced (2 transfers each); b: exact f32
    assert wire_bytes(tree, "powersgd:2") == 2 * 4 * r * (256 + 128) + 2 * 4 * 128
    assert wire_bytes(tree, "powersgd:2") < wire_bytes(tree, None) // 20

    g = jax.random.normal(jax.random.key(0), (256, 128), jnp.float32)
    state = powersgd_init_state({"w": g}, r, 8)

    def body(x, e, q):
        out, _ = powersgd_psum_mean({"w": x}, "data", {"error": {"w": e[0]}, "q": {"w": q}}, r)
        return out["w"]

    fn = jax.jit(shard_map(
        body, mesh=mesh8,
        in_specs=(P(), P("data"), P()), out_specs=P(), check_vma=False,
    ))
    hlo = fn.lower(g, state["error"]["w"], state["q"]["w"]).compile().as_text()
    import re as _re

    for m in _re.finditer(r"all-reduce[^=]*= \(?[a-z0-9]+\[([0-9,]*)\]", hlo):
        dims = [int(d) for d in m.group(1).split(",") if d]
        size = int(np.prod(dims)) if dims else 1
        assert size <= 256 * r, f"gradient-sized allreduce: {m.group(0)}"


def test_powersgd_training_converges():
    """End-to-end through the Accelerator: an eligible [32,16] kernel trains
    under powersgd:2 (error feedback carried in the step state) and reaches
    the same loss floor as the exact run."""
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    rng = np.random.default_rng(1)
    w_true = rng.normal(size=(32, 16)).astype(np.float32)
    x_all = rng.normal(size=(64, 32)).astype(np.float32)
    y_all = x_all @ w_true

    def mat_loss(params, batch):
        pred = batch["x"] @ params["w"]
        return ((pred - batch["y"]) ** 2).mean()

    def train(compression):
        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()
        acc = Accelerator(
            parallelism_plugin=ParallelismPlugin(
                mesh_config=MeshConfig(data=8), grad_compression=compression
            )
        )
        from accelerate_tpu.modeling import Model

        model = acc.prepare_model(Model(lambda p, x: x @ p["w"],
                                        {"w": np.zeros((32, 16), np.float32)}))
        acc.prepare_optimizer(optax.adam(0.1))
        step = acc.build_train_step(mat_loss)
        losses = []
        for s in range(150):
            idx = np.arange(s * 16, (s + 1) * 16) % 64
            losses.append(float(step({"x": x_all[idx], "y": y_all[idx]})))
        return losses

    plain = train(None)
    psgd = train("powersgd:2")
    assert plain[-1] < 1e-3
    # lossy start, but error feedback catches the trajectory up
    assert psgd[-1] < 5e-2, psgd[-5:]
    assert psgd[-1] < psgd[0] / 100



def test_powersgd_fp16_overflow_does_not_poison_state(no_persistent_compile_cache):
    """A loss-scale overflow step must leave the carried residual/Q finite
    (the step's finite gate already holds params): training recovers on the
    next good batches instead of dead-looping on a NaN carry. Also checks
    the residual is carried in UNSCALED units — after the backoff halves
    the scale, feedback still converges."""
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    acc = Accelerator(
        mixed_precision="fp16",
        parallelism_plugin=ParallelismPlugin(
            mesh_config=MeshConfig(data=8), grad_compression="powersgd:2"
        ),
    )
    from accelerate_tpu.modeling import Model

    rng = np.random.default_rng(3)
    w_true = rng.normal(size=(32, 16)).astype(np.float32)
    x_all = rng.normal(size=(64, 32)).astype(np.float32)
    y_all = x_all @ w_true

    def mat_loss(params, batch):
        return ((batch["x"] @ params["w"] - batch["y"]) ** 2).mean()

    model = acc.prepare_model(Model(lambda p, x: x @ p["w"],
                                    {"w": np.zeros((32, 16), np.float32)}))
    acc.prepare_optimizer(optax.adam(0.1))
    step = acc.build_train_step(mat_loss)
    good = {"x": x_all[:16], "y": y_all[:16]}
    for _ in range(5):
        step(good)
    # overflow batch: fp16 forward saturates -> non-finite grads
    bad = {"x": np.full((16, 32), 1e4, np.float32), "y": np.zeros((16, 16), np.float32)}
    step(bad)
    losses = [float(step({"x": x_all[s * 16:(s + 1) * 16], "y": y_all[s * 16:(s + 1) * 16]}))
              for s in [0, 1, 2, 3] * 20]
    assert np.isfinite(losses).all(), losses[:8]
    # recovery = still making progress after the overflow, not dead-looped
    assert losses[-1] < losses[0] / 3, (losses[0], losses[-1])


def test_compression_rejects_sharded_axes():
    with pytest.raises(ValueError):
        ParallelismPlugin(grad_compression="fp4")
    acc = Accelerator(
        parallelism_plugin=ParallelismPlugin(
            mesh_config=MeshConfig(data=4, tensor=2), grad_compression="bf16"
        )
    )
    model = acc.prepare_model(RegressionModel())
    acc.prepare_optimizer(optax.sgd(0.1))
    with pytest.raises(ValueError, match="data"):
        acc.build_train_step(linear_loss_fn)
