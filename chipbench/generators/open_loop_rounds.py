"""``open_loop_rounds``: an open loop on the wall clock over stratified rounds.

Every round offers the same multiset of prompt lengths and new-token counts. The seed
makes the token ids, pairs prompts with counts, orders a round, and jitters each arrival
inside its own slot of an even grid, so every seed offers the same tokens per second.
``warm_rounds`` rounds run before the window opens (on the same grid, or with
``warm_burst`` all at once as the window opens, so that a cell above the knee starts on
a queue that is already deep); offering stops when the window closes; what was due inside
it is then drained, and whatever is still unfinished has failed.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from chipbench import stats

SPANS = ("submit", "step", "stamp", "wait")
FAMILY_GIVES = ("spec", "logits_at")  # what this generator asks of a cell's family


@dataclasses.dataclass
class Request:
    index: int
    due: float  # seconds from the window's opening; negative in the warm rounds
    prompt: np.ndarray
    new_tokens: int
    uid: Optional[int] = None
    submitted: Optional[float] = None
    first_token: Optional[float] = None
    finished: Optional[float] = None
    served: Optional[np.ndarray] = None


def schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> list:
    """Every request of the run, in due order."""
    rng = np.random.default_rng(seed)
    prompts, counts = list(traffic["prompt_tokens"]), list(traffic["new_tokens"])
    if len(prompts) != len(counts):
        raise ValueError("a round needs as many new-token counts as prompt lengths")
    per_round, rate, warm = len(prompts), float(traffic["rate_per_s"]), int(traffic["warm_rounds"])
    rounds = warm + int(np.ceil(seconds * rate / per_round))
    out = []
    for r in range(rounds):
        paired = list(zip(prompts, rng.permutation(counts)))
        for j, k in enumerate(rng.permutation(per_round)):
            slot = (r - warm) * per_round + j
            due = (slot + rng.random()) / rate
            if slot < 0 and traffic.get("warm_burst"):
                due = -1e-3 * (warm * per_round - len(out)) / (warm * per_round)  # in order, just before the window
            n_prompt, n_new = paired[k]
            ids = rng.integers(5, vocab - 1, size=n_prompt).astype(np.int32)
            if (slot + 1) / rate <= seconds:  # its whole slot inside the window: every seed offers as many
                out.append(Request(len(out), due, ids, int(n_new)))
    return out


def run(ctx) -> dict:
    """Drive ``ctx.build()``'s server through the schedule; see the module docstring."""
    traffic, seconds = ctx.traffic, ctx.seconds
    server = ctx.build()
    requests = schedule(traffic, ctx.seed, seconds, ctx.config["vocab_size"])

    # warm every program this traffic uses: one request per distinct prompt length, all at once
    t0 = time.perf_counter()
    rng = np.random.default_rng(ctx.seed + 1)
    vocab = ctx.config["vocab_size"]
    for n in sorted(set(traffic["prompt_tokens"])):
        server.submit(rng.integers(5, vocab - 1, size=n).astype(np.int32), 2 * server.tick_block + 1)
    while server.busy():
        server.step()
    ctx.say("warm", seconds=time.perf_counter() - t0, programs=ctx.compiles.requests)
    ctx.end_warm_up()
    server.reset_counters()

    warm_span = -requests[0].due if requests and requests[0].due < 0 else 0.0
    opens = time.perf_counter() + warm_span  # the window opens once the warm rounds have been offered
    ctx.window_opens(opens)
    clock = lambda: time.perf_counter() - opens
    trace_from = seconds - min(ctx.trace_seconds, seconds / 2) if ctx.trace else None
    in_flight, ticks, nxt, tracing = [], [], 0, False
    window_compiles = None
    while True:
        now = clock()
        if ctx.trace and not tracing and trace_from <= now < seconds:
            ctx.start_trace()
            tracing = True
        if now >= seconds and window_compiles is None:
            window_compiles = ctx.compiles_since_warm_up()
            if tracing:
                ctx.stop_trace()
                tracing = False
        with ctx.span("submit"):
            while nxt < len(requests) and requests[nxt].due <= min(now, seconds):
                r = requests[nxt]
                r.uid = server.submit(r.prompt, r.new_tokens)
                r.submitted = clock()
                in_flight.append(r)
                nxt += 1
        owed = [r for r in in_flight if r.due >= 0]
        if now >= seconds and (not owed or now >= seconds + traffic["drain_limit_s"]):
            break
        if not server.busy():
            with ctx.span("wait"):
                until = requests[nxt].due if nxt < len(requests) else seconds
                time.sleep(max(0.0, min(until, seconds) - clock()))
            continue
        before = server.counters()
        t_start = clock()
        with ctx.span("step"):
            server.step()
        t_end = clock()
        with ctx.span("stamp"):
            firsts, decoding, live = 0, 0, 0
            for r in in_flight:
                got = len(server.tokens_so_far(r.uid))
                if got and r.first_token is None:
                    r.first_token = t_end
                    firsts += len(r.prompt)
                elif got:
                    decoding += 1
                    live += len(r.prompt) + got
                if server.finished(r.uid):
                    r.finished = t_end
                    r.served = np.asarray(server.tokens_so_far(r.uid))
            in_flight = [r for r in in_flight if r.finished is None]
        after = server.counters()
        ticks.append({"start": t_start, "end": t_end, "prefills": after["prefills"] - before["prefills"],
                      "first_token_prompt_tokens": firsts, "decoding": decoding, "live_tokens": live,
                      "queue_len": after["queue_len"]})
    if tracing:
        ctx.stop_trace()
    closed = clock()
    counters = server.counters()
    memory_peak = ctx.memory_peak()

    due = [r for r in requests if r.due >= 0]
    done = [r for r in due if r.finished is not None]
    worst = closed  # no latency measured in this run is longer than the run
    ttft = stats.with_failed_as_worst(
        [(r.first_token - r.due) * 1e3 if r.finished is not None else None for r in due], worst * 1e3)
    tpot = stats.with_failed_as_worst(
        [(r.finished - r.first_token) / (len(r.served) - 1) * 1e3 if r.finished is not None and len(r.served) > 1
         else None for r in due], worst * 1e3)
    completed = [r for r in requests if r.finished is not None and 0 <= r.finished < seconds]
    tokens_done = sum(len(r.prompt) + len(r.served) for r in completed)
    ctx.say("requests", due_in_window=len(due), finished=len(done), completed_in_window=len(completed),
            ttft_samples=len(ttft), tpot_samples=len(tpot), ttft_p50_ms=stats.median(ttft),
            drained_s=closed - seconds, queue_len_at_close=next((t["queue_len"] for t in reversed(ticks) if t["end"] <= seconds), 0),
            ticks=len(ticks))
    end_to_end = {
        "ttft_p90_ms": stats.percentile(ttft, 90.0), "tpot_p90_ms": stats.percentile(tpot, 90.0),
        "serve_tokens_per_s": tokens_done / seconds,
    }

    server.free()
    checks = [
        ctx.check("compiles_in_window", window_compiles.requests if window_compiles else 0, 0),
        ctx.check("requests_unfinished", len(due) - len(done), 0),
        ctx.check("token_count_wrong", sum(len(r.served) != r.new_tokens for r in done), 0),
    ]
    checks += _compare_with_reference(ctx, done)
    before_trace = [r for r in due if trace_from is None or r.due < trace_from]
    return {
        "end_to_end": end_to_end, "attempted": len(due), "failed": len(due) - len(done), "checks": checks,
        "memory_peak_bytes": memory_peak,
        "observed": {"ticks": ticks, "tick_block": server.tick_block, "counters": counters,
                     "late_ms": [(r.submitted - r.due) * 1e3 for r in before_trace if r.submitted is not None],
                     "ttft_ms": ttft, "spans": SPANS,
                     "traced": None if trace_from is None else (trace_from, seconds)},
    }


def _compare_with_reference(ctx, done: list) -> list:
    """A seeded sample of the requests the window finished, the longest among them:
    the reference runs once over each prompt with its served tokens, and every served
    token's logit is held against the reference's best at that position."""
    import jax.numpy as jnp

    reference = ctx.family()
    if not done:
        return [ctx.check("logit_gap_max", float("inf"), ctx.limit("logit_gap_max"))]
    rng = np.random.default_rng(ctx.seed + 2)
    longest = max(done, key=lambda r: len(r.prompt) + len(r.served))
    others = [r for r in done if r is not longest]
    picks = [longest] + [others[i] for i in rng.permutation(len(others))[: ctx.traffic["check_requests"] - 1]]
    weights, cfg, pad = ctx.weights(), ctx.config, ctx.traffic["reference_pad"]
    gaps, control_gaps = [], []
    precision = ctx.config["bench"].get("control", "int8")
    t0 = time.perf_counter()
    for r in picks:
        n, p = len(r.served), len(r.prompt)
        if p + n > pad:
            raise ValueError(f"request of {p + n} tokens is longer than reference_pad {pad}")
        tokens = np.zeros((pad,), np.int32)
        tokens[: p + n] = np.concatenate([r.prompt, r.served])
        rows = jnp.arange(p - 1, p + n - 1)
        ref = reference.logits_at(weights, cfg, jnp.asarray(tokens), rows)
        best = ref.max(axis=-1)
        served = jnp.take_along_axis(ref, jnp.asarray(r.served)[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(best - served))
        if ctx.control:
            low = reference.logits_at(weights, cfg, jnp.asarray(tokens), rows, dot_name=precision)
            chosen = jnp.take_along_axis(ref, low.argmax(axis=-1)[:, None], axis=-1)[:, 0]
            control_gaps.append(np.asarray(best - chosen))
    gaps = np.concatenate(gaps)
    ctx.say("reference", requests=len(picks), tokens_compared=int(gaps.size), seconds=time.perf_counter() - t0,
            longest=len(longest.prompt) + len(longest.served))
    if ctx.control:
        c = np.concatenate(control_gaps)
        ctx.say("control", precision=precision, logit_gap_max=float(c.max()), logit_gap_mean=float(c.mean()),
                would_pass=all(ctx.check(k, v, ctx.limit(k))["ok"] for k, v in
                               (("logit_gap_max", float(c.max())), ("logit_gap_mean", float(c.mean())))))
    return [ctx.check("logit_gap_max", float(gaps.max()), ctx.limit("logit_gap_max")),
            ctx.check("logit_gap_mean", float(gaps.mean()), ctx.limit("logit_gap_mean"))]
