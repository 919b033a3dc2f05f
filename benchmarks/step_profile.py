"""Op-level breakdown of the headline BERT train step.

Two independent measurements:

1. **Ablation wall-clock**: forward-only, forward+backward, and the full
   step (fwd+bwd+adamw), each timed to a ``block_until_ready`` fence — the
   share of each phase falls out by subtraction.
2. **Compiled-program accounting**: ``compile().cost_analysis()`` FLOPs +
   bytes for each program, turned into a roofline lower bound
   (max(flops/peak_flops, bytes/peak_bw)) per phase.

Optionally (``--trace DIR``) also captures a ``jax.profiler`` trace for
TensorBoard's op profile.

Prints JSON lines; run on the real chip.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

PEAK_TFLOPS = 197.0  # v5e bf16
PEAK_HBM_GBS = 819.0  # v5e


def force(x) -> None:
    """Block until ``x`` has finished computing."""
    __import__("jax").block_until_ready(x)


def timed(fn, *args, n=10):
    # warm twice: a first call can hand its carried state back in another
    # layout than it was given, and call #2 then compiles again
    force(fn(*args))
    for _ in range(2):
        out = fn(*args)
    force(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    force(out)
    return (time.perf_counter() - t0) / n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default=None, help="also write a jax.profiler trace here")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument(
        "--phase",
        choices=["fwd", "fwdbwd", "step", "all"],
        default="all",
        help="measure one phase per process (separate processes avoid donation/"
        "allocator interference between the phase programs)",
    )
    ap.add_argument("--remat", action="store_true", help="activation-checkpoint each encoder layer")
    args = ap.parse_args()

    import jax
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import BertConfig, bert_classification_loss, create_bert_model
    from accelerate_tpu.parallel.mesh import batch_sharding

    acc = Accelerator(mixed_precision="bf16")
    model = acc.prepare_model(
        create_bert_model(BertConfig.base(remat=args.remat), seq_len=args.seq)
    )
    acc.prepare_optimizer(optax.adamw(2e-5, weight_decay=0.01))
    loss_fn = lambda p, b: bert_classification_loss(p, b, model.apply_fn)
    step = acc.build_train_step(loss_fn)

    rng = np.random.default_rng(0)
    batch = {
        "input_ids": rng.integers(5, 30000, size=(args.batch, args.seq)).astype(np.int32),
        "attention_mask": np.ones((args.batch, args.seq), np.bool_),
        "labels": rng.integers(0, 2, size=(args.batch,)).astype(np.int32),
    }
    batch = jax.device_put(batch, batch_sharding(acc.mesh))

    # phase programs (same dtype policy the train step uses internally)
    policy = acc.state.dtype_policy

    def cast(p):
        return jax.tree.map(lambda x: x.astype(policy.compute_dtype) if hasattr(x, "astype") else x, p)

    @jax.jit
    def fwd(params, batch):
        return loss_fn(cast(params), batch)

    @jax.jit
    def fwd_bwd(params, batch):
        loss, grads = jax.value_and_grad(lambda p, b: loss_fn(cast(p), b))(params, batch)
        # consume every grad leaf so no branch of the backward is DCE'd
        return loss + sum(g.astype(__import__("jax").numpy.float32).sum() for g in jax.tree_util.tree_leaves(grads)) * 0.0

    def cost(jitted, *a):
        c = jitted.lower(*a).compile().cost_analysis()
        c = c[0] if isinstance(c, (list, tuple)) else c
        fl = float(c.get("flops", 0.0))
        by = float(c.get("bytes accessed", 0.0))
        return fl, by, max(fl / (PEAK_TFLOPS * 1e12), by / (PEAK_HBM_GBS * 1e9))

    result = {"metric": f"bert_phase_{args.phase}", "batch": args.batch, "seq": args.seq}
    if args.phase in ("fwd", "all"):
        t = timed(fwd, model.params, batch, n=args.steps)
        fl, by, lb = cost(fwd, model.params, batch)
        result.update(fwd_ms=round(t * 1e3, 2), fwd_gflops=round(fl / 1e9, 1),
                      fwd_gbytes=round(by / 1e9, 3), fwd_roofline_ms=round(lb * 1e3, 2),
                      fwd_roofline_eff=round(lb / t, 3))
    if args.phase in ("fwdbwd", "all"):
        t = timed(fwd_bwd, model.params, batch, n=args.steps)
        fl, by, lb = cost(fwd_bwd, model.params, batch)
        result.update(fwdbwd_ms=round(t * 1e3, 2), fwdbwd_gflops=round(fl / 1e9, 1),
                      fwdbwd_gbytes=round(by / 1e9, 3), fwdbwd_roofline_ms=round(lb * 1e3, 2),
                      fwdbwd_roofline_eff=round(lb / t, 3))
    if args.phase in ("step", "all"):
        t = timed(step, batch, n=args.steps)
        result.update(step_ms=round(t * 1e3, 2))
    print(json.dumps(result))

    if args.trace:
        with jax.profiler.trace(args.trace):
            out = None
            for _ in range(5):
                out = step(batch)
            force(out)
        print(json.dumps({"trace_dir": args.trace}))


if __name__ == "__main__":
    main()
