"""ZeRO-1 training-wire A/B/C: replicated-fp32 vs zero1 vs zero1+int8.

Three arms train the same model on the same data over an 8-way CPU fake
mesh (the SURVEY §4 multi-chip CI story), and every headline claim is
measured, not asserted:

* **bytes on wire** — the cost model predicts each arm's per-step
  collective traffic (``parallel.compression.wire_bytes``, which
  delegates to ``analysis.costmodel.ring_wire_bytes``), and the
  compiled program's ACTUAL collectives are counted from its
  post-GSPMD HLO (``telemetry.wire.hlo_wire_bytes`` — an independent
  measurement: GSPMD inserts the baseline's implicit grad all-reduce,
  which the jaxpr never shows). The pair lands as a ``wire_bytes``
  telemetry counter; the criterion is agreement within 10% and
  zero1+int8 <= ~25% of the replicated-fp32 baseline.
* **peak HBM** — flight-check's static liveness walk over each arm's
  real jitted step (sharding-aware: it sees the 1/n optimizer state);
  the criterion is the zero1 arm's peak lower than baseline by AT
  LEAST optimizer_state_bytes*(n-1)/n (the sharded accumulation
  buffer wins more on top). The live sampled peak rides along when
  the backend exposes memory stats (CPU jax usually does not — null
  then).
* **parity** — per-step loss deviation vs the replicated baseline:
  ~ulp for fp32 zero1, and for int8 a one-shot reduce-scatter +
  all-gather roundtrip is checked against the published TPU606 bound
  (``COMPRESSION_NUMERICS``).
* **compiles** — each arm's loop runs telemetry-wrapped; the criterion
  is ZERO post-warmup recompiles (the static do_sync pair is two
  stable programs).

Writes the JSON report to stdout:

    JAX_PLATFORMS=cpu python benchmarks/bench_zero1.py --smoke
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu.utils.environment import force_host_platform  # noqa: E402


def build_arm(name: str, zero: bool, method, hidden: int, n_data: int):
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu import Accelerator, MeshConfig, ParallelismPlugin
    from accelerate_tpu.modeling import Model
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils.dataclasses import TelemetryKwargs

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()

    acc = Accelerator(
        kwargs_handlers=[TelemetryKwargs(enabled=False, hbm_sample_every=4)],
        parallelism_plugin=ParallelismPlugin(
            mesh_config=MeshConfig(data=n_data),
            zero_stage=1 if zero else 0,
            grad_compression=method,
        ),
    )
    rng = np.random.default_rng(0)
    params = {
        "w1": (rng.normal(size=(hidden, hidden)) * 0.05).astype(np.float32),
        "b1": np.zeros((hidden,), np.float32),
        "w2": (rng.normal(size=(hidden, hidden // 4)) * 0.05).astype(np.float32),
        "b2": np.zeros((hidden // 4,), np.float32),
    }

    def apply_fn(p, x):
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        return h @ p["w2"] + p["b2"]

    model = acc.prepare_model(Model(apply_fn, params))
    opt = acc.prepare_optimizer(optax.adam(1e-2))

    def loss_fn(p, batch):
        return ((apply_fn(p, batch["x"]) - batch["y"]) ** 2).mean()

    step = acc.build_train_step(loss_fn)
    sharding = NamedSharding(acc.mesh, P(("data", "fsdp")))
    return acc, model, opt, step, sharding, loss_fn


def measure_arm(name, zero, method, args_ns):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.parallel.compression import wire_bytes
    from accelerate_tpu.telemetry.wire import hlo_wire_bytes
    from accelerate_tpu.utils.random import key_for_step

    n = args_ns.data
    acc, model, opt, step, sharding, loss_fn = build_arm(
        name, zero, method, args_ns.hidden, n
    )
    tel = acc.telemetry
    box = acc._fast_scale_boxes[-1]

    rng = np.random.default_rng(1)
    w_ref = rng.normal(size=(args_ns.hidden, args_ns.hidden // 4)).astype(np.float32) * 0.3
    x_all = rng.normal(size=(args_ns.batch * 4, args_ns.hidden)).astype(np.float32)
    y_all = np.tanh(x_all) @ w_ref

    batch0 = {
        "x": jax.device_put(x_all[: args_ns.batch], sharding),
        "y": jax.device_put(y_all[: args_ns.batch], sharding),
    }
    sample = (
        model.params, opt.opt_state, box["grad_buf"], None, batch0,
        box["scale_state"], True if zero else jnp.bool_(True),
        key_for_step(0), jnp.float32(-1.0), box["comp_state"],
    )

    # -- wire bytes: cost-model prediction vs compiled-HLO measurement --
    predicted = wire_bytes(model.params, method, n=n, zero_stage=1 if zero else 0)
    hlo = step._jitted.lower(*sample).compile().as_text()
    measured = hlo_wire_bytes(hlo)
    wire_rec = tel.record_wire_bytes(
        predicted, measured["total"], label=name, by_primitive=measured["by_primitive"],
        # one-time backend-upcast warning: a compressed arm whose dominant
        # collective got widened by the backend (XLA:CPU bf16->f32) is
        # named instead of silently losing its wire saving
        requested_wire_dtype=method, sites=measured["sites"],
        platform=jax.default_backend(),
    )

    # -- static peak HBM (flight-check sees the sharded opt state) ------
    inner = step._jitted.__wrapped__
    sync = True if zero else jnp.bool_(True)

    def fn(p, o, g, b, s, r, c, cs, _inner=inner, _sync=sync):
        return _inner(p, o, g, None, b, s, _sync, r, c, cs)

    fn.__name__ = f"{name}_train_step"
    report = acc.flight_check(
        fn, model.params, opt.opt_state, box["grad_buf"], batch0,
        box["scale_state"], key_for_step(0), jnp.float32(-1.0), box["comp_state"],
        donate_argnums=(0, 1, 2),
    )

    opt_bytes_global = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(opt.opt_state)
        if hasattr(leaf, "size")
    )

    # -- telemetry-wrapped training loop: parity + recompiles -----------
    wrapped = tel.wrap(step)
    losses = []
    for s in range(args_ns.steps):
        lo = (s * args_ns.batch) % (3 * args_ns.batch)
        batch = {
            "x": jax.device_put(x_all[lo : lo + args_ns.batch], sharding),
            "y": jax.device_put(y_all[lo : lo + args_ns.batch], sharding),
        }
        losses.append(float(wrapped(batch)))

    return {
        "zero_stage": 1 if zero else 0,
        "grad_compression": method,
        "predicted_wire_bytes_per_step": predicted,
        "measured_wire_bytes_per_step": measured["total"],
        "measured_by_primitive": measured["by_primitive"],
        "wire_prediction_drift": wire_rec["drift"],
        "static_peak_hbm_bytes": report.peak_hbm_bytes,
        "sampled_peak_hbm_bytes": tel.hbm.observed_peak_bytes or None,
        "optimizer_state_bytes_global": opt_bytes_global,
        "opt_state_bytes_per_device": sum(
            shard.data.nbytes
            for leaf in jax.tree_util.tree_leaves(opt.opt_state)
            if hasattr(leaf, "addressable_shards")
            for shard in leaf.addressable_shards[:1]
        ),
        "post_warmup_recompiles": tel.recompiles,
        "final_loss": losses[-1],
        "losses": [round(x, 6) for x in losses],
    }


def tpu606_roundtrip_check(n_data: int):
    """One-shot quantized reduce-scatter + all-gather roundtrip vs the
    exact path, checked against the published COMPRESSION_NUMERICS
    bounds (the collective-level TPU606 pin)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from accelerate_tpu.analysis.numerics_rules import COMPRESSION_NUMERICS
    from accelerate_tpu.parallel.mesh import MeshConfig
    from accelerate_tpu.parallel.zero import all_gather_updates, reduce_scatter_grads
    from jax import shard_map

    mesh = MeshConfig(data=n_data).build()
    g = jax.random.normal(jax.random.key(11), (n_data, 4096), jnp.float32) * 1.7

    def roundtrip(method):
        def body(x):
            flat = {"g": x[0] * (1.0 / n_data)}
            err0 = None if method is None else {"g": jnp.zeros_like(flat["g"])}
            shard, _ = reduce_scatter_grads(flat, ("data",), n_data, method, err0)
            err1 = None if method is None else {"g": jnp.zeros_like(shard["g"])}
            full, _ = all_gather_updates(shard, ("data",), n_data, method, err1)
            return full["g"][None]

        fn = shard_map(
            body, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False
        )
        return np.asarray(fn(g)).reshape(n_data, -1)[0]

    exact = roundtrip(None)
    amax = float(np.abs(np.asarray(g)).max())
    out = {}
    for method in ("int8", "fp8", "bf16"):
        err = float(np.abs(roundtrip(method) - exact).max())
        bound = COMPRESSION_NUMERICS[method].bound(amax, n_data)
        out[method] = {
            "max_abs_error": err,
            "tpu606_bound": bound,
            "within_bound": bool(err <= bound),
        }
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="small fast config (CI)")
    ap.add_argument("--data", type=int, default=8, help="data-parallel degree")
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--steps", type=int, default=40)
    args = ap.parse_args()
    if args.smoke:
        args.hidden, args.steps = min(args.hidden, 256), min(args.steps, 40)

    force_host_platform(args.data)

    arms = {}
    for name, (zero, method) in {
        "baseline": (False, None),
        "zero1": (True, None),
        "zero1_int8": (True, "int8"),
    }.items():
        arms[name] = measure_arm(name, zero, method, args)

    base, z1, zi = arms["baseline"], arms["zero1"], arms["zero1_int8"]
    n = args.data
    opt_win = base["optimizer_state_bytes_global"] * (n - 1) // n
    hbm_drop = base["static_peak_hbm_bytes"] - z1["static_peak_hbm_bytes"]
    dev_fp32 = max(
        abs(a - b) / max(abs(b), 1e-9)
        for a, b in zip(z1["losses"], base["losses"])
    )
    dev_int8 = max(
        abs(a - b) / max(abs(b), 1e-9)
        for a, b in zip(zi["losses"], base["losses"])
    )
    tpu606 = tpu606_roundtrip_check(n)

    report = {
        "bench": "zero1",
        "config": {
            "data_parallel": n,
            "hidden": args.hidden,
            "batch": args.batch,
            "steps": args.steps,
            "param_bytes": int(
                sum(v for v in [args.hidden * args.hidden, args.hidden,
                                args.hidden * (args.hidden // 4), args.hidden // 4]) * 4
            ),
        },
        "arms": arms,
        "criteria": {
            "wire_zero1_int8_over_baseline": round(
                zi["measured_wire_bytes_per_step"] / base["measured_wire_bytes_per_step"], 4
            ),
            "wire_zero1_int8_leq_25pct": bool(
                zi["measured_wire_bytes_per_step"]
                <= 0.27 * base["measured_wire_bytes_per_step"]
            ),
            "wire_prediction_within_10pct": bool(
                all(a["wire_prediction_drift"] <= 0.10 for a in arms.values())
            ),
            "static_hbm_drop_bytes": hbm_drop,
            "optimizer_state_win_bytes": opt_win,
            "hbm_drop_covers_opt_state_win": bool(hbm_drop >= opt_win),
            "fp32_parity_max_rel_dev": dev_fp32,
            "int8_parity_max_rel_dev": dev_int8,
            "tpu606_roundtrip": tpu606,
            "parity_within_tpu606": bool(
                dev_fp32 < 1e-5
                and dev_int8 < 0.05
                and all(v["within_bound"] for v in tpu606.values())
            ),
            "zero_post_warmup_recompiles": bool(
                all(a["post_warmup_recompiles"] == 0 for a in arms.values())
            ),
        },
    }
    report["ok"] = bool(
        report["criteria"]["wire_zero1_int8_leq_25pct"]
        and report["criteria"]["wire_prediction_within_10pct"]
        and report["criteria"]["hbm_drop_covers_opt_state_win"]
        and report["criteria"]["parity_within_tpu606"]
        and report["criteria"]["zero_post_warmup_recompiles"]
    )
    json.dump(report, sys.stdout, indent=2)
    sys.stdout.write("\n")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
