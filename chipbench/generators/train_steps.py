"""``train_steps``: fenced training steps on synthetic batches from the seed.

Set-up builds one trainer, drives it through its first three steps by the window's own
feed and call, and hands the same object to the window. After the window the plain
reference follows those three steps from the same seeded weights and batches.
"""

from __future__ import annotations

import math
import time

from chipbench import stats
from chipbench.reference import train as reference

SPANS = ("feed", "step", "fence")
FAMILY_GIVES = ("spec", "loss_fn", "LAYER_NAMES", "train_flops")  # what this generator asks of a cell's family
CHECK_STEPS = 3
MIN_FENCED_SECONDS = 0.25  # a host-clock reading is off by some half a millisecond


def run(ctx) -> dict:
    import jax

    family = ctx.family()
    trainer = ctx.build()
    losses, at = [], 0
    for at in range(CHECK_STEPS):
        losses.append(float(trainer.step(trainer.feed(at))))
        if at == 0:
            first_gradient = trainer.first_gradient_norms(family.LAYER_NAMES)
            first_gradient_tree = trainer.first_gradient_on_host()
    shardings = trainer.param_shardings()
    start = ctx.fresh_weights(shardings=shardings)
    change = trainer.change_norms(start, family.LAYER_NAMES)
    del start

    # how many steps one fenced reading spans, from two steps timed together
    at = CHECK_STEPS
    t0 = time.perf_counter()
    for _ in range(2):
        loss = trainer.step(trainer.feed(at))
        at += 1
    jax.block_until_ready(loss)
    group = max(1, math.ceil(MIN_FENCED_SECONDS / ((time.perf_counter() - t0) / 2)))
    ctx.say("warm", steps=at, steps_per_fence=group, first_losses=losses, programs=ctx.compiles.requests)
    ctx.end_warm_up()

    seconds = ctx.seconds
    opens = time.perf_counter()
    ctx.window_opens(opens)
    trace_from = seconds - min(ctx.trace_seconds, seconds / 2) if ctx.trace else None
    groups, tracing, steps = [], False, 0
    while True:
        now = time.perf_counter() - opens
        if now >= seconds:
            break
        if ctx.trace and not tracing and now >= trace_from:
            ctx.start_trace()
            tracing = True
        t_start = time.perf_counter()
        for _ in range(group):
            with ctx.span("feed"):
                batch = trainer.feed(at)
            with ctx.span("step"):
                loss = trainer.step(batch)
            at += 1
        with ctx.span("fence"):
            jax.block_until_ready(loss)
        groups.append((t_start - opens, time.perf_counter() - opens))
        steps += group
    elapsed = time.perf_counter() - opens
    window_compiles = ctx.compiles_since_warm_up()
    if tracing:
        ctx.stop_trace()
    last_loss = float(loss)
    memory_peak = ctx.memory_peak()
    ctx.say("steps", steps=steps, fences=len(groups), elapsed_s=elapsed, last_loss=last_loss)

    tokens, seq = trainer.tokens_per_step, ctx.traffic["seq"]
    flops = family.train_flops(ctx.config, tokens // seq, seq)
    batches = [trainer.reference_batch(i) for i in range(CHECK_STEPS)]
    trainer.free()
    t0 = time.perf_counter()
    opt = ctx.config["bench"]["optimizer"]
    row_block = ctx.traffic["reference_row_block"]
    weights = ctx.fresh_weights("float32", shardings=shardings)
    ref = reference.follow(family, ctx.config, weights, batches, opt, row_block)
    ctx.say("reference", seconds=time.perf_counter() - t0, losses=ref["losses"])
    mine = {"losses": losses, "first_gradient": first_gradient, "change": change,
            "first_gradient_tree": first_gradient_tree}
    checks = [ctx.check("compiles_in_window", window_compiles.requests, 0),
              ctx.check("loss_finite", 0 if math.isfinite(last_loss) else 1, 0)]
    checks += _compare(ctx, mine, ref, say="compared")
    if ctx.control:
        precision = ctx.config["bench"].get("control", "int8")
        low = reference.follow(family, ctx.config, weights, batches, opt, row_block, dot_name=precision)
        numbers = {c["name"]: c["value"] for c in _compare(ctx, low, ref, say="control_compared")}
        ctx.say("control", precision=precision, **numbers,
                would_pass=all(ctx.check(k, v, ctx.limit(k))["ok"] for k, v in numbers.items()))
    step_ms = [(e - s) / group * 1e3 for s, e in groups]
    return {
        "end_to_end": {"train_tokens_per_s": steps * tokens / elapsed},
        "attempted": steps, "failed": 0, "checks": checks, "memory_peak_bytes": memory_peak,
        "observed": {"step_ms": [m for (s, _), m in zip(groups, step_ms) if trace_from is None or s < trace_from],
                     "flops_per_step": flops, "tokens_per_step": tokens, "spans": SPANS},
    }


def _compare(ctx, got: dict, ref: dict, say) -> list:
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"]))
    layer_names = ctx.family().LAYER_NAMES
    matrices = reference.matrix_leaves(ctx.spec(), layer_names)
    vectors = set(ref["change"]) - matrices
    noise = reference.all_but_zero_leaves(ref["first_gradient"])
    numbers, worst = {"loss_gap": loss_gap}, {}
    for which, skip in (("matrix", vectors), ("vector", matrices)):
        numbers[f"first_gradient_{which}_gap"], worst[f"gradient_{which}"] = reference.worst_leaf_gap(
            got["first_gradient"], ref["first_gradient"], skip)
        numbers[f"change_{which}_gap"], worst[f"change_{which}"] = reference.worst_leaf_gap(
            got["change"], ref["change"], skip | noise)
    # the first gradient itself, leaf by leaf: rounding that a norm hides shows in the difference
    difference = reference.leaf_difference_norms(got["first_gradient_tree"], ref["first_gradient_tree"], layer_names)
    numbers["first_gradient_difference"], worst["difference"] = reference.worst_leaf_difference(
        difference, ref["first_gradient"], vectors)
    if say:
        ctx.say(say, losses=got["losses"], worst_leaf=worst, change_not_compared=sorted(noise))
    return [ctx.check(name, value, ctx.limit(name)) for name, value in numbers.items()]
