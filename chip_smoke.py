"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py                  # one chip: front_door, train, kernels, serve
    python chip_smoke.py --chips 4        # one four-chip host: the mesh phase only
    python chip_smoke.py --cpu-rehearsal  # tiny configs on the host CPU (control flow only)

Drives the main path once through the entry points a user calls, at the
published widths of models the repo supports (depth cut, weights random
from ``--seed``), and checks what comes out:

* ``front_door`` — ``accelerate-tpu serve --workers 1`` answers
  ``POST /v1/generate`` from a worker that reports a ``tpu`` device. Runs
  first, before this process has touched JAX: a chip belongs to one process.
* ``train`` — BERT-base, seq 128, batch 256, bf16, through
  ``Accelerator.prepare_model`` / ``prepare_optimizer`` / ``build_train_step``:
  loss finite and falling on a fixed batch, no recompile after warm-up.
* ``kernels`` — every ``pallas_call`` under ``ops/`` and ``kernels/``
  compiled (never interpreted) at the serve/train widths and compared with
  its plain XLA counterpart, forward and backward where there is one.
* ``serve`` — ``ServingEngine`` with the paged cache on Mistral-7B-v0.1 at
  its published widths, requests submitted while others decode; nothing
  compiles after warm-up, the decode program holds the Pallas kernel, and the
  log-probabilities agree with the dense layout on the same chip.
* ``mesh`` (``--chips 4`` only) — a few ``build_train_step`` steps of the
  llama core at the Mistral widths on ``MeshConfig(fsdp=2, tensor=2)``
  against the same steps on a one-device mesh.

Any phase that fails raises, so the exit code is non-zero and no result
line is printed. Without an accelerator the script exits at once. The last
line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``;
timings, compile counts, cache hits, losses and tolerances go on earlier
lines, one JSON object per phase. A time printed here is a reading from one
run, not a benchmark.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
WORK_DIR = os.path.join(HERE, ".cache", "chip_smoke")  # fixed: a cache that moves never hits

ONE_CHIP_PHASES = ("front_door", "train", "kernels", "serve")
FOUR_CHIP_PHASES = ("mesh",)

# Stated tolerances. Log-probabilities are f32 log-softmax values of about
# -6 nats (random weights, vocab 32000) computed from bf16 activations by
# two different attention programs.
LOGPROB_TOL_MAX = 0.15  # nats, any single token
LOGPROB_TOL_MEAN = 0.03  # nats, mean over all compared tokens
KERNEL_TOL = 2e-2  # max |kernel - xla| / max |xla|, bf16 operands
KERNEL_GRAD_TOL = 4e-2
MESH_LOSS_TOL = 1e-2  # relative, four-chip loss against one-device loss, per step

FULL = {
    "bert": {"tiny": False, "batch": 256, "seq": 128, "steps": 20},
    "serve": {
        "layers": 16, "context": 4096, "slots": 8, "buckets": (64, 256, 1024), "block": 16,
        "warm_prompts": (40, 200, 900, 1054, 1224, 2500, 20, 120), "warm_new": 9,
        "prompts": (24, 48, 100, 180, 300, 600, 950, 1100, 1500, 2000, 2600, 3000),
        "new_tokens": (32, 64),
    },
    "front_door": {
        "layers": 4, "context": 1024, "slots": 4, "buckets": (64, 256), "block": 16,
        "prompts": (12, 50, 200), "new_tokens": 16,
    },
    "mesh": {"layers": 2, "seq": 2048, "batch": 4, "steps": 4},
    "flash": (
        # (batch, seq, heads, kv_heads, head_dim, window)
        (2, 2048, 32, 8, 128, 4096),  # the mesh phase's attention, before sharding
        (1, 4096, 32, 8, 128, 1024),  # band narrower than the sequence: K blocks skipped
    ),
    "paged": {"slots": 8, "heads": 32, "kv_heads": 8, "dim": 128, "block": 16, "table": 256},
    "int4": ((4096, 14336, 128), (14336, 4096, 128), (4096, 4096, 64)),  # (in, out, group)
}

TINY = {
    "bert": {"tiny": True, "batch": 8, "seq": 32, "steps": 6},
    "serve": {
        "layers": 2, "context": 128, "slots": 3, "buckets": (8, 16, 32), "block": 8,
        "warm_prompts": (5, 12, 20, 36, 44, 90), "warm_new": 20,
        "prompts": (4, 7, 10, 14, 18, 22, 28, 34, 40, 50, 60, 80),
        "new_tokens": (6, 12),
    },
    "front_door": {
        "layers": 2, "context": 64, "slots": 2, "buckets": (8, 16), "block": 8,
        "prompts": (3, 6, 12), "new_tokens": 4,
    },
    "mesh": {"layers": 2, "seq": 64, "batch": 4, "steps": 3},
    "flash": ((2, 64, 4, 2, 16, 128), (1, 128, 4, 2, 16, 32)),
    "paged": {"slots": 3, "heads": 4, "kv_heads": 2, "dim": 16, "block": 8, "table": 16},
    "int4": ((128, 256, 64), (256, 128, 64)),
}


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, sort_keys=True), flush=True)


def check(cond: bool, message: str) -> None:
    if not cond:
        raise AssertionError(message)


class CompileCounter:
    """Counts what jax asks its backend to compile: every request, and
    those answered from the persistent cache. A request that is not a hit
    is a compilation."""

    def __init__(self):
        import jax

        self.requests = self.hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        return {
            "requests": self.requests, "persistent_cache_hits": self.hits,
            "compiled": self.requests - self.hits, "seconds": round(self.compile_s, 2),
        }

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: round(now[k] - before[k], 2) for k in now}


def mistral_width_model(num_hidden_layers: int, max_position_embeddings: int, seed: int = 0, tiny: bool = False):
    """Mistral-7B-v0.1 at its published widths (hidden 4096, ff 14336, 32/8
    heads x 128, vocab 32000, window 4096), cut in depth and in the context
    the cache is sized for; bf16 weights made on the device from ``seed``.
    Also the front-door worker's model factory (``chip_smoke:mistral_width_model``)."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import MistralConfig, create_mistral_model

    if not tiny and jax.devices()[0].platform != "tpu":
        raise RuntimeError(f"chip_smoke: full-width model asked for on {jax.devices()[0].platform!r}, not a tpu")
    make = MistralConfig.tiny if tiny else MistralConfig.mistral_7b_v1
    cfg = make(num_hidden_layers=num_hidden_layers, max_position_embeddings=max_position_embeddings)
    return create_mistral_model(cfg, seed=seed, seq_len=8, dtype=jnp.bfloat16)


# --------------------------------------------------------------------------- #
# front_door: the CLI's supervisor and its one worker are the only children
# --------------------------------------------------------------------------- #


def _http_json(url: str, body: dict | None = None, timeout: float = 120.0) -> dict:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:  # /healthz answers 503 with the same JSON body
        return json.loads(e.read())


def phase_front_door(sizes: dict, seed: int, rehearsal: bool) -> dict:
    import numpy as np

    fd = sizes["front_door"]
    run_dir = os.path.join(WORK_DIR, "front_door")
    os.makedirs(run_dir, exist_ok=True)
    ready = os.path.join(run_dir, "ready.json")
    if os.path.exists(ready):
        os.remove(ready)
    engine = {
        "num_slots": fd["slots"], "prompt_buckets": list(fd["buckets"]), "max_len": fd["context"],
        "paged_block_size": fd["block"],
    }
    model_kwargs = {
        "num_hidden_layers": fd["layers"], "max_position_embeddings": fd["context"],
        "seed": seed, "tiny": rehearsal,
    }
    cmd = [
        sys.executable, "-m", "accelerate_tpu.commands.cli", "serve", "--workers", "1",
        "--model-spec", "chip_smoke:mistral_width_model",
        "--model-kwargs", json.dumps(model_kwargs), "--engine-kwargs", json.dumps(engine),
        "--run-dir", run_dir, "--store-dir", os.path.join(WORK_DIR, "front_door_store"),
        "--http-port", "0", "--ready-file", ready,
    ]
    env = dict(
        os.environ, PYTHONPATH=HERE + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_DEBUG_LOG_MODULES="jax._src.compiler",  # the worker's log then names its persistent-cache hits
    )
    t0 = time.perf_counter()
    log_path = os.path.join(run_dir, "serve.log")
    worker_log = os.path.join(run_dir, "worker_w0.log")
    worker_log_start = os.path.getsize(worker_log) if os.path.exists(worker_log) else 0
    with open(log_path, "ab") as log:
        proc = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=log, stderr=subprocess.STDOUT)

    def tail(path: str) -> str:
        if not os.path.exists(path):
            return ""
        with open(path, errors="replace") as f:
            return f.read()[-2000:]

    try:
        while not os.path.exists(ready):
            check(proc.poll() is None, f"serve exited {proc.returncode} before it was ready:\n{tail(log_path)}")
            check(time.perf_counter() - t0 < 600, f"serve not ready after 600 s:\n{tail(worker_log)}")
            time.sleep(0.2)
        with open(ready) as f:
            port = json.load(f)["http_port"]
        base = f"http://127.0.0.1:{port}"
        health = _http_json(base + "/healthz")
        check(health["serving"], f"no live worker behind the front door:\n{tail(worker_log)}")
        worker = health["replicas"]["w0"]
        device = worker["device"]
        want = "cpu" if rehearsal else "tpu"
        check(device["platform"] == want, f"front door serves from {device}, not from a {want} device")
        ready_s = time.perf_counter() - t0

        rng = np.random.default_rng(seed)
        vocab = 256 if rehearsal else 32000
        t1 = time.perf_counter()
        answers = []
        for n in fd["prompts"]:
            prompt = rng.integers(1, vocab - 1, size=n).tolist()
            reply = _http_json(
                base + "/v1/generate",
                {"prompt": prompt, "max_new_tokens": fd["new_tokens"], "timeout_s": 300.0}, timeout=320.0,
            )
            check(reply["state"] == "done", f"front door request for prompt {n} ended {reply['state']}")
            tokens, lps = reply["tokens"], reply["lps"]
            check(len(tokens) == fd["new_tokens"], f"front door returned {len(tokens)} tokens for prompt {n}")
            check(all(0 <= t < vocab for t in tokens), "front door returned a token outside the vocabulary")
            check(bool(np.all(np.isfinite(lps)) and np.all(np.asarray(lps) <= 0.0)), "front door logprobs not finite")
            answers.append(len(tokens))
        requests_s = time.perf_counter() - t1
        after = _http_json(base + "/healthz")["replicas"]["w0"]
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)  # graceful drain: workers shut down, exit 0
            try:
                proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
    check(proc.returncode == 0, f"serve drained with exit code {proc.returncode}:\n{tail(log_path)}")
    with open(worker_log, errors="replace") as f:
        f.seek(worker_log_start)
        worker_cache_hits = f.read().count("Persistent compilation cache hit")
    return {
        "device": device, "layers": fd["layers"], "context": fd["context"],
        "requests": len(answers), "tokens": sum(answers),
        "ready_s": round(ready_s, 1), "requests_s": round(requests_s, 2),
        "worker_compiles": after.get("compiles"), "worker_deserialized": after.get("deserialized"),
        "worker_rejected": after.get("rejected"), "worker_persistent_cache_hits": worker_cache_hits,
    }


# --------------------------------------------------------------------------- #
# train: BERT-base
# --------------------------------------------------------------------------- #


def phase_train(sizes: dict, seed: int, counter: CompileCounter) -> dict:
    import jax
    import numpy as np
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import BertConfig, bert_classification_loss, create_bert_model
    from accelerate_tpu.parallel.mesh import batch_sharding
    from accelerate_tpu.telemetry import StepTelemetry
    from accelerate_tpu.utils import MixedPrecisionPolicy

    b = sizes["bert"]
    _reset_accelerator_state()
    accelerator = Accelerator(
        mixed_precision="bf16", kwargs_handlers=[MixedPrecisionPolicy(softmax_dtype="bfloat16")]
    )
    cfg = BertConfig.tiny() if b["tiny"] else BertConfig.base()
    model = accelerator.prepare_model(create_bert_model(cfg, seed=seed, seq_len=b["seq"]))
    accelerator.prepare_optimizer(optax.adamw(2e-5, weight_decay=0.01))
    step = accelerator.build_train_step(lambda p, batch: bert_classification_loss(p, batch, model.apply_fn))

    rng = np.random.default_rng(seed)
    global_batch = b["batch"] * accelerator.num_data_shards
    batch = {
        "input_ids": rng.integers(5, cfg.vocab_size - 1, size=(global_batch, b["seq"])).astype(np.int32),
        "attention_mask": np.ones((global_batch, b["seq"]), np.bool_),
        "labels": rng.integers(0, 2, size=(global_batch,)).astype(np.int32),
    }
    batch = jax.device_put(batch, batch_sharding(accelerator.mesh))

    telem = StepTelemetry(warmup_steps=2)
    step = telem.wrap(step)
    before = counter.snapshot()
    losses = [float(step(batch))]  # the compile; telemetry attributes it
    compile_s = telem.compile_ms / 1000.0
    times = []
    for _ in range(b["steps"] - 1):
        t0 = time.perf_counter()
        loss = step(batch)
        jax.block_until_ready(loss)
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
    check(bool(np.all(np.isfinite(losses))), f"train loss not finite: {losses}")
    check(losses[-1] < losses[0], f"train loss did not fall on a fixed batch: {losses[0]} -> {losses[-1]}")
    check(telem.recompiles == 0, f"{telem.recompiles} recompiles after warm-up")
    steady = sorted(times[2:])
    return {
        "model": "bert-tiny" if b["tiny"] else "bert-base", "batch": global_batch, "seq": b["seq"],
        "steps": len(losses), "loss_first": round(losses[0], 5), "loss_last": round(losses[-1], 5),
        "recompiles": telem.recompiles, "compile_s": round(compile_s, 1),
        "step_ms_median": round(steady[len(steady) // 2] * 1000.0, 2),
        "compile_cache": counter.since(before),
    }


def _reset_accelerator_state() -> None:
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()


# --------------------------------------------------------------------------- #
# kernels: each pallas_call compiled on the device against its XLA counterpart
# --------------------------------------------------------------------------- #


def _compiled_kernel(fn, *args):
    """Lower ``fn`` for the attached device, insist that the program holds a
    Mosaic custom call (an interpreted kernel lowers to plain HLO), compile
    it and run it."""
    import jax

    lowered = jax.jit(fn).lower(*args)
    check("tpu_custom_call" in lowered.as_text(), f"{getattr(fn, '__name__', fn)}: no tpu_custom_call in the program")
    return lowered.compile()(*args)


def _rel_err(got, want) -> float:
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def phase_kernels(sizes: dict, seed: int, rehearsal: bool) -> dict:
    """On the host (rehearsal) the kernels run interpreted and the custom-call
    assertion is skipped; on the chip they are compiled or the phase fails."""
    import functools

    import jax
    import jax.numpy as jnp

    from accelerate_tpu.kernels.reference import block_accumulate, block_matmul_softmax
    from accelerate_tpu.ops.attention import _xla_attention
    from accelerate_tpu.ops.paged_kv import paged_gather_attention
    from accelerate_tpu.ops.pallas_attention import pallas_flash_attention
    from accelerate_tpu.ops.pallas_paged_attention import paged_decode_attention
    from accelerate_tpu.ops.pallas_qmatmul import int4_matmul
    from accelerate_tpu.utils.quantization import grouped_dequantize

    run = (lambda fn, *a: jax.jit(fn)(*a)) if rehearsal else _compiled_kernel
    interpret = rehearsal
    key = jax.random.key(seed)
    report, seconds, t0 = {}, {}, time.perf_counter()

    def compare(name, got, want, tol=KERNEL_TOL):
        report[name] = _rel_err(got, want)
        check(report[name] <= tol, f"{name} off by {report[name]:.3g} (tolerance {tol})")

    # flash attention, forward and backward (three pallas_calls)
    for b, s, h, h_kv, d, window in sizes["flash"]:
        kq, kk, kv, kc, key = jax.random.split(key, 5)
        q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(kk, (b, s, h_kv, d), jnp.bfloat16)
        v = jax.random.normal(kv, (b, s, h_kv, d), jnp.bfloat16)
        cot = jax.random.normal(kc, (b, s, h, d), jnp.bfloat16)
        scale = d**-0.5
        band = (jnp.arange(s)[None, :] > jnp.arange(s)[:, None] - window)[None, None]

        def flash(q, k, v):
            return pallas_flash_attention(q, k, v, causal=True, window=window, interpret=interpret)

        def plain(q, k, v):
            return _xla_attention(q, k, v, band, True, scale, 0.0, None)

        def vjp_of(fn):
            return lambda q, k, v: jax.vjp(fn, q, k, v)[1](cot)

        name = f"flash_s{s}_w{window}"
        compare(name + "_fwd", run(flash, q, k, v), jax.jit(plain)(q, k, v))
        got, want = run(vjp_of(flash), q, k, v), jax.jit(vjp_of(plain))(q, k, v)
        for which, g, w in zip(("dq", "dk", "dv"), got, want):
            compare(f"{name}_{which}", g, w, KERNEL_GRAD_TOL)
        del q, k, v, cot, got, want

    seconds["flash"], t0 = round(time.perf_counter() - t0, 1), time.perf_counter()

    # paged decode attention against the gather path: the trivial band of the
    # serve phase, then a band narrower than the frontier with the expired
    # table entries pointed at the trash block, as window recycling leaves them
    p = sizes["paged"]
    slots, bs, mb = p["slots"], p["block"], p["table"]
    nb = slots * mb + 1
    kq, kk, kv, key = jax.random.split(key, 4)
    q = jax.random.normal(kq, (slots, p["heads"], p["dim"]), jnp.bfloat16)
    k_pool = jax.random.normal(kk, (nb, bs, p["kv_heads"], p["dim"]), jnp.bfloat16)
    v_pool = jax.random.normal(kv, (nb, bs, p["kv_heads"], p["dim"]), jnp.bfloat16)
    table = 1 + jnp.arange(slots * mb, dtype=jnp.int32).reshape(slots, mb)
    cur = jnp.linspace(3, mb * bs - 1, slots).astype(jnp.int32)  # frontiers from a few tokens to the last row
    scale = p["dim"] ** -0.5
    full_band = mb * bs
    narrow = full_band // 4
    recycled = jnp.where(jnp.arange(mb)[None, :] < (cur[:, None] - narrow + 1) // bs, 0, table)
    for name, window, tbl in (("paged_full", full_band, table), ("paged_band_recycled", narrow, recycled)):
        kernel = functools.partial(paged_decode_attention, sliding_window=window, scale=scale, interpret=interpret)
        plain = functools.partial(paged_gather_attention, scale=scale, sliding_window=window)
        compare(name, run(kernel, q, k_pool, v_pool, tbl, cur),
                jax.jit(plain)(q[:, None], k_pool, v_pool, table, cur)[:, 0])
    del k_pool, v_pool

    seconds["paged"], t0 = round(time.perf_counter() - t0, 1), time.perf_counter()

    # int4 dequantize+matmul against dequantize-then-matmul
    for n_in, n_out, group in sizes["int4"]:
        kx, kw, ks, key = jax.random.split(key, 4)
        x = jax.random.normal(kx, (8, n_in), jnp.bfloat16)
        packed = jax.random.randint(kw, (n_in // group, group // 2, n_out), 0, 256, jnp.int32).astype(jnp.uint8)
        qscale = jax.random.uniform(ks, (n_in // group, 1, n_out), jnp.float32, 0.005, 0.02)
        kernel = functools.partial(int4_matmul, group_size=group, interpret=interpret)

        def plain(x, packed, qscale):
            w = grouped_dequantize(packed, qscale, "int4").reshape(n_in, n_out)
            return x.astype(jnp.float32) @ w

        compare(f"int4_{n_in}x{n_out}_g{group}", run(kernel, x, packed, qscale), jax.jit(plain)(x, packed, qscale))

    seconds["int4"], t0 = round(time.perf_counter() - t0, 1), time.perf_counter()

    # the two registered reference kernels (kernels/reference.py), f32
    kx, kw, key = jax.random.split(key, 3)
    x = jax.random.normal(kx, (64, 128), jnp.float32)
    w = jax.random.normal(kw, (128, 256), jnp.float32) * 128**-0.5  # logits of unit scale
    compare("block_matmul_softmax", run(functools.partial(block_matmul_softmax, interpret=interpret), x, w),
            jax.nn.softmax(jnp.dot(x, w), axis=-1))
    compare("block_accumulate", run(functools.partial(block_accumulate, interpret=interpret), x, x * 0.5),
            x + x * 0.5, 1e-6)

    seconds["reference"] = round(time.perf_counter() - t0, 1)
    return {
        "compiled": not rehearsal, "kernels": len(report), "seconds": seconds,
        "tolerance": {"forward": KERNEL_TOL, "grad": KERNEL_GRAD_TOL},
        "rel_err": {k: float(f"{v:.3g}") for k, v in report.items()},
    }


# --------------------------------------------------------------------------- #
# serve: paged engine at Mistral widths, then the dense layout on the same chip
# --------------------------------------------------------------------------- #


def _drive(engine, prompts, new_tokens, stagger: int):
    """Submit ``prompts`` while others decode: a first wave, then one more
    request every ``stagger`` ticks. Returns the uids in submission order."""
    first = max(1, len(prompts) // 3)
    uids = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts[:first], new_tokens[:first])]
    pending = list(zip(prompts[first:], new_tokens[first:]))
    tick = 0
    while pending or engine.queue or engine.active_count:
        if pending and tick % stagger == 0:
            p, n = pending.pop(0)
            uids.append(engine.submit(p, max_new_tokens=n))
        engine.step()
        tick += 1
    return uids, tick


def _score_against_dense(dense, prompts, tokens, lps):
    """Per-token log-probabilities of the SAME tokens under the dense layout.

    The dense engine decodes greedily from the paged engine's context. While
    it picks the same token, the two log-probabilities of that token are
    compared. Where it picks another (the logits of random weights are
    nearly flat, so bf16 rounding flips near-ties), both engines saw the
    same context, so each reported the maximum of its own distribution and
    the two maxima differ by no more than the distributions do: that
    position is compared too, and the next round continues from the paged
    engine's token. Every generated token is compared exactly once."""
    import numpy as np

    done = [0] * len(prompts)
    diffs, flips, rounds = [], 0, 0
    while any(d < len(t) for d, t in zip(done, tokens)):
        rounds += 1
        check(rounds <= 24, f"dense layout still disagrees after {rounds - 1} rounds ({flips} flipped tokens)")
        uids = {}
        for i, (prompt, toks) in enumerate(zip(prompts, tokens)):
            if done[i] < len(toks):
                context = np.concatenate([prompt, toks[: done[i]]]).astype(np.int32)
                uids[i] = dense.submit(context, max_new_tokens=len(toks) - done[i])
        dense.run()
        for i, uid in uids.items():
            d_toks, d_lps = dense.partial(uid), dense.logprobs(uid)
            p_toks, p_lps = tokens[i][done[i]:], lps[i][done[i]:]
            same = int(np.argmax(np.append(d_toks != p_toks, True)))  # length of the common prefix
            upto = min(same + 1, len(p_toks))
            flips += int(upto > same)
            diffs.extend(np.abs(d_lps[:upto] - p_lps[:upto]).tolist())
            done[i] += upto
    return np.asarray(diffs), flips, rounds


def _decode_program_text(engine) -> str:
    """The decode tick as the engine traces it, lowered for the attached
    device: it must hold the Pallas kernel — neither ``_kernel_runner`` nor a
    dispatch threshold may route round it. (A function of its own: the
    program's argument builder closes over the engine, and a reference left
    behind would keep its whole cache pool on the device.)"""
    return engine._perf_programs["decode_tick"].lower().as_text()


def phase_serve(sizes: dict, seed: int, rehearsal: bool, counter: CompileCounter) -> dict:
    import jax
    import numpy as np

    from accelerate_tpu.aot import ExecutableStore, ProgramCache
    from accelerate_tpu.serving import ServingEngine

    from accelerate_tpu.ops import paged_kv

    # on the host the paged tick takes the XLA gather path; the rehearsal
    # routes it through the interpreted kernel, the composition the chip runs
    paged_kv.FORCE_KERNEL_INTERPRET = rehearsal
    s = sizes["serve"]
    t0 = time.perf_counter()
    model = mistral_width_model(s["layers"], s["context"], seed=seed, tiny=rehearsal)
    jax.block_until_ready(model.params)
    n_params = sum(x.size for x in jax.tree.leaves(model.params))
    init_s = time.perf_counter() - t0
    cfg = model.config
    rng = np.random.default_rng(seed)

    def make_prompts(lengths):
        return [rng.integers(1, cfg.vocab_size - 1, size=n).astype(np.int32) for n in lengths]

    def engine_for(layout: str):
        store = ExecutableStore(os.path.join(WORK_DIR, f"serve_store_{layout}"))
        return ServingEngine(
            model, num_slots=s["slots"], prompt_buckets=s["buckets"], max_len=s["context"],
            paged_block_size=s["block"] if layout == "paged" else None,
            program_cache=ProgramCache(store=store, name=f"smoke_{layout}"), seed=seed,
        )

    # -- the paged engine: warm every program, then the measured window
    paged = engine_for("paged")
    before = counter.snapshot()
    t0 = time.perf_counter()
    for p in make_prompts(s["warm_prompts"]):
        paged.submit(p, max_new_tokens=s["warm_new"])
    paged.run()
    warm_s = time.perf_counter() - t0
    warm = {**counter.since(before), "store_compiled": paged.program_cache.misses,
            "store_deserialized": paged.program_cache.deserialized,
            "store_rejected": paged.program_cache.rejected}

    if not rehearsal:
        check("tpu_custom_call" in _decode_program_text(paged), "no tpu_custom_call in the paged decode program")

    prompts = make_prompts(s["prompts"])
    lo, hi = s["new_tokens"]
    new_tokens = [int(n) for n in rng.integers(lo, hi + 1, size=len(prompts))]
    before = counter.snapshot()
    store_before = paged.program_cache.misses + paged.program_cache.deserialized
    t0 = time.perf_counter()
    uids, ticks = _drive(paged, prompts, new_tokens, stagger=2)
    serve_s = time.perf_counter() - t0
    window = counter.since(before)
    check(window["requests"] == 0, f"{window['requests']} programs were requested after warm-up: {window}")
    check(paged.program_cache.misses + paged.program_cache.deserialized == store_before,
          "the engine built a program after warm-up")
    tokens = [paged.partial(u) for u in uids]
    lps = [paged.logprobs(u) for u in uids]
    for u, p, n, t, lp in zip(uids, prompts, new_tokens, tokens, lps):
        check(paged.poll(u) is not None and len(paged.poll(u)) == len(p) + n, f"request {u} did not complete")
        check(len(t) == n and len(lp) == n, f"request {u}: {len(t)} tokens for {n} asked")
        check(bool(np.all((t >= 0) & (t < cfg.vocab_size))), f"request {u}: token outside the vocabulary")
        check(bool(np.all(np.isfinite(lp)) and np.all(lp <= 0.0)), f"request {u}: logprobs not finite")
    out_tokens = int(sum(new_tokens))
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")

    # -- the dense layout on the same chip, same weights
    del paged
    gc.collect()
    in_use_between = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    dense = engine_for("dense")
    t0 = time.perf_counter()
    diffs, flips, rounds = _score_against_dense(dense, prompts, tokens, lps)
    dense_s = time.perf_counter() - t0
    check(len(diffs) == out_tokens, f"compared {len(diffs)} of {out_tokens} tokens")
    check(float(diffs.max()) <= LOGPROB_TOL_MAX, f"paged vs dense logprob max diff {diffs.max():.4f} nats")
    check(float(diffs.mean()) <= LOGPROB_TOL_MEAN, f"paged vs dense logprob mean diff {diffs.mean():.4f} nats")
    return {
        "model": "mistral-tiny" if rehearsal else "mistral-7b-v0.1 widths",
        "layers": s["layers"], "layers_published": 32, "context": s["context"], "context_published": 32768,
        "params": int(n_params), "dtype": "bfloat16", "slots": s["slots"], "block": s["block"],
        "init_s": round(init_s, 1), "warm_s": round(warm_s, 1), "warm_compile": warm,
        "requests": len(uids), "prompt_tokens": [len(p) for p in prompts], "new_tokens": out_tokens,
        "ticks": ticks, "serve_s": round(serve_s, 2), "post_warmup_compile_requests": window["requests"],
        "peak_bytes_in_use": peak, "bytes_in_use_after_paged_engine": in_use_between,
        "dense": {
            "tokens_compared": len(diffs), "flipped": flips, "rounds": rounds, "wall_s": round(dense_s, 1),
            "logprob_diff_max": round(float(diffs.max()), 5), "logprob_diff_mean": round(float(diffs.mean()), 5),
            "tolerance": {"max": LOGPROB_TOL_MAX, "mean": LOGPROB_TOL_MEAN},
        },
    }


# --------------------------------------------------------------------------- #
# mesh: fsdp=2 x tensor=2 against one device (--chips 4)
# --------------------------------------------------------------------------- #


@contextlib.contextmanager
def _stderr_to(path: str):
    """The SPMD partitioner's warnings come from C++ on file descriptor 2."""
    sys.stderr.flush()
    saved = os.dup(2)
    with open(path, "wb") as f:
        os.dup2(f.fileno(), 2)
        try:
            yield
        finally:
            sys.stderr.flush()
            os.dup2(saved, 2)
            os.close(saved)
    with open(path, errors="replace") as f:
        sys.stderr.write(f.read())


def _record_program_args(step) -> dict:
    """Stand between ``step`` and its jitted program to keep the avals and
    shardings of a real call, so the same program can be lowered again
    for its HLO text."""
    import jax

    jitted = step._jitted
    cell = next(c for c in step.__closure__ if c.cell_contents is jitted)
    seen = {}

    def recorder(*args):
        # the newest call: from the second on, every array argument is an
        # output of the program itself and carries the sharding it runs with
        seen["args"] = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding if x.committed else None)
            if isinstance(x, jax.Array) else x,
            args,
        )
        return jitted(*args)

    cell.cell_contents = recorder
    return seen


def _llama_steps(sizes: dict, seed: int, rehearsal: bool, mesh_config, batch_np) -> dict:
    import jax
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import LlamaConfig, MistralConfig, causal_lm_loss, create_llama_model
    from accelerate_tpu.parallel.mesh import batch_sharding
    from accelerate_tpu.utils import ParallelismPlugin

    m = sizes["mesh"]
    _reset_accelerator_state()
    accelerator = Accelerator(
        mixed_precision="bf16", parallelism_plugin=ParallelismPlugin(mesh_config=mesh_config)
    )
    if rehearsal:
        cfg = LlamaConfig.tiny(num_hidden_layers=m["layers"], max_position_embeddings=m["seq"])
    else:
        cfg = MistralConfig.mistral_7b_v1(num_hidden_layers=m["layers"], max_position_embeddings=m["seq"])
    model = accelerator.prepare_model(create_llama_model(cfg, seed=seed, seq_len=8))
    accelerator.prepare_optimizer(optax.sgd(1e-2))
    step = accelerator.build_train_step(lambda p, b: causal_lm_loss(p, b, model.apply_fn))
    seen = _record_program_args(step)
    batch = jax.device_put({"input_ids": batch_np}, batch_sharding(accelerator.mesh))

    losses, times = [], []
    for _ in range(m["steps"]):
        t0 = time.perf_counter()
        loss = step(batch)
        jax.block_until_ready(loss)
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return {
        "model": model, "step": step, "args": seen["args"],
        "losses": losses, "first_step_s": times[0], "step_ms": sorted(times[1:])[len(times[1:]) // 2] * 1000.0,
    }


def phase_mesh(sizes: dict, seed: int, rehearsal: bool) -> dict:
    import jax
    import numpy as np

    from accelerate_tpu.parallel.mesh import MeshConfig

    m = sizes["mesh"]
    vocab = 256 if rehearsal else 32000
    batch_np = np.random.default_rng(seed).integers(5, vocab - 1, size=(m["batch"], m["seq"])).astype(np.int32)

    os.makedirs(WORK_DIR, exist_ok=True)
    with _stderr_to(os.path.join(WORK_DIR, "mesh_compile.stderr")):
        four = _llama_steps(sizes, seed, rehearsal, MeshConfig(fsdp=2, tensor=2), batch_np)
    with open(os.path.join(WORK_DIR, "mesh_compile.stderr"), errors="replace") as f:
        remat_warnings = f.read().count("nvoluntary full rematerialization")

    # every large parameter in four pieces on four devices
    devices = set(jax.devices())
    large = [(jax.tree_util.keystr(path), x) for path, x in jax.tree_util.tree_flatten_with_path(four["model"].params)[0]
             if x.size >= (1 << 12 if rehearsal else 1 << 20)]
    check(len(large) >= 7, f"only {len(large)} large parameters found")
    shares = {}
    for name, x in large:
        shards = x.addressable_shards
        on = {sh.device for sh in shards}
        check(on == devices, f"{name} lives on {len(on)} of {len(devices)} devices")
        share = max(sh.data.nbytes for sh in shards) / x.nbytes
        check(0.2 <= share <= 0.3, f"{name}: a device holds {share:.2f} of its bytes, not about a quarter")
        shares[name] = share

    # the program the four devices ran: collectives and the flash kernel
    text = four["step"]._jitted.lower(*four["args"]).compile().as_text()
    collectives = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                   for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")}
    check(sum(collectives.values()) > 0, "no collective in the four-chip step program")
    if not rehearsal:
        check("tpu_custom_call" in text, "no tpu_custom_call (flash attention) in the four-chip step program")
    four_losses, four_first, four_ms = four["losses"], four["first_step_s"], four["step_ms"]
    del four
    gc.collect()

    one = _llama_steps(sizes, seed, rehearsal, MeshConfig(num_devices=1), batch_np)
    check(bool(np.all(np.isfinite(four_losses + one["losses"]))), "mesh phase loss not finite")
    rel = [abs(a - b) / abs(b) for a, b in zip(four_losses, one["losses"])]
    check(max(rel) <= MESH_LOSS_TOL, f"four-chip losses {four_losses} against one-device {one['losses']}")
    check(four_losses[-1] < four_losses[0], f"four-chip loss did not fall: {four_losses}")
    return {
        "mesh": {"fsdp": 2, "tensor": 2}, "layers": m["layers"], "seq": m["seq"], "batch": m["batch"],
        "large_params": len(large), "max_device_share": round(max(shares.values()), 4),
        "collectives": collectives, "tpu_custom_call": "tpu_custom_call" in text,
        "involuntary_full_rematerialization_warnings": remat_warnings,
        "losses_four": [round(x, 5) for x in four_losses], "losses_one": [round(x, 5) for x in one["losses"]],
        "loss_rel_diff_max": float(f"{max(rel):.3g}"), "tolerance": MESH_LOSS_TOL,
        "first_step_s": {"four": round(four_first, 1), "one": round(one["first_step_s"], 1)},
        "step_ms": {"four": round(four_ms, 1), "one": round(one["step_ms"], 1)},
    }


# --------------------------------------------------------------------------- #


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("chip_smoke.py", description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the mesh phase on one four-chip host, and nothing else")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny configs on the host CPU: control flow only, never a device result")
    ap.add_argument("--phases", default=None, help="comma-separated subset (for finding faults)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    known = FOUR_CHIP_PHASES if args.chips == 4 else ONE_CHIP_PHASES
    phases = tuple(args.phases.split(",")) if args.phases else known
    unknown = [p for p in phases if p not in known]
    if unknown:
        ap.error(f"unknown phases for --chips {args.chips}: {unknown} (known: {known})")

    if args.cpu_rehearsal:
        global WORK_DIR
        WORK_DIR += "_rehearsal"  # host-built store entries stay apart from the chip's
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={args.chips}"
    elif "tpu" not in os.environ.get("JAX_PLATFORMS", "tpu"):
        # nothing to initialise: the environment has already ruled the chip out
        sys.exit(f"chip_smoke: no accelerator: JAX_PLATFORMS={os.environ['JAX_PLATFORMS']}")
    try:
        import accelerate_tpu  # noqa: F401
    except ImportError as e:
        sys.exit(f"chip_smoke: run it from the root of the repository ({e})")
    sizes = TINY if args.cpu_rehearsal else FULL
    t_start = time.perf_counter()
    walls = {}

    if "front_door" in phases:
        # before this process touches jax: the worker needs the chip
        t0 = time.perf_counter()
        report = phase_front_door(sizes, args.seed, args.cpu_rehearsal)
        walls["front_door"] = round(time.perf_counter() - t0, 1)
        say("front_door", ok=True, wall_s=walls["front_door"], **report)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if not args.cpu_rehearsal and device["platform"] != "tpu":
        sys.exit(f"chip_smoke: no accelerator: jax found {device}")
    if device["count"] != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but jax found {device}")

    if args.cpu_rehearsal:
        # XLA:CPU executables restored from the persistent cache serialize
        # into store entries that do not load (serving_proc.worker_main has
        # the story), and a rehearsal is about control flow, not caches
        jax.config.update("jax_enable_compilation_cache", False)
        cache_dir = None
    else:
        from accelerate_tpu.aot import configure_persistent_cache

        cache_dir = configure_persistent_cache()
    counter = CompileCounter()
    import jaxlib

    say("start", device=device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        compile_cache_dir=cache_dir,
        cache_entries_at_start=len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0,
        phases=list(phases), seed=args.seed)

    runners = {
        "train": lambda: phase_train(sizes, args.seed, counter),
        "kernels": lambda: phase_kernels(sizes, args.seed, args.cpu_rehearsal),
        "serve": lambda: phase_serve(sizes, args.seed, args.cpu_rehearsal, counter),
        "mesh": lambda: phase_mesh(sizes, args.seed, args.cpu_rehearsal),
    }
    for name in phases:
        if name == "front_door":
            continue
        t0 = time.perf_counter()
        report = runners[name]()
        walls[name] = round(time.perf_counter() - t0, 1)
        say(name, ok=True, wall_s=walls[name], **report)
        gc.collect()

    say("total", wall_s=round(time.perf_counter() - t_start, 1), phase_wall_s=walls, compile=counter.snapshot())
    result = {"ok": True, "device": device}
    if phases != known:
        result["phases"] = list(phases)  # a partial run says so
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
