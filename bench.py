"""Headline benchmark: BERT-base fine-tune throughput (samples/sec).

Matches BASELINE.json's metric ("BERT-base MRPC samples/sec + step time").
Runs on whatever accelerator is attached (the driver runs this on one real
TPU chip). Prints ONE JSON line:

  {"metric": ..., "value": N, "unit": "samples/sec", "vs_baseline": N, ...}

``vs_baseline`` is measured against a **per-chip A100 baseline of 350
samples/sec** — the commonly reported BERT-base GLUE fine-tune throughput
(seq 128, fp16, HF Trainer) on one A100; the reference's north-star target
(BASELINE.json) is v5e-8 within 10% of 8xA100, i.e. per-chip parity ~0.9+.

It needs an accelerator: where jax finds none it exits non-zero with one
line. ``--platform cpu`` is the explicit tiny-config smoke run on the host.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

A100_PER_CHIP_SAMPLES_PER_SEC = 350.0


def _peak_for_device(devices):
    """(peak bf16 TFLOP/s, device_kind string) for the attached chip, from
    the SAME per-generation table the static cost model prices with
    (``analysis.costmodel.PEAK_FLOPS_TABLE``) — runtime MFU and static
    rooflines must never disagree about "peak". A device the table does
    not know is an error, not some other chip's row."""
    from accelerate_tpu.analysis.costmodel import device_generation, peak_flops

    device_kind = getattr(devices[0], "device_kind", "unknown")
    generation = device_generation(devices[0])
    if generation is None:
        raise RuntimeError(f"no peak FLOP/s known for device_kind {device_kind!r}")
    return peak_flops(generation, "bf16") / 1e12, device_kind


def _devices(tiny: bool):
    """The devices to measure on: an accelerator, or (``tiny``) the host
    CPU asked for by name. No probe, no retry: find a chip or stop."""
    if tiny:
        from accelerate_tpu.utils.environment import force_host_platform

        force_host_platform(1)
    import jax

    devices = jax.devices()
    if not tiny and devices[0].platform == "cpu":
        sys.exit("bench: no accelerator: jax found only the host CPU (--platform cpu runs the tiny smoke config)")
    from accelerate_tpu.aot import configure_persistent_cache

    configure_persistent_cache()
    return devices


def _bert_step_flops(params, global_batch: int, seq_len: int) -> float:
    """Training-step FLOPs ≈ 6 * non-embedding-params * tokens (fwd 2x,
    bwd 4x). Embedding lookups are gathers, not matmuls — excluded, but the
    tied projection would count for an LM head; BERT classification head is
    tiny either way."""
    import jax
    import numpy as np

    def is_embedding(path):
        return any("embed" in getattr(k, "key", str(k)).lower() for k in path)

    n_params = sum(
        int(np.prod(x.shape))
        for path, x in jax.tree_util.tree_flatten_with_path(params)[0]
        if not is_embedding(path)
    )
    return 6.0 * n_params * global_batch * seq_len


def _llama_step_flops(params, global_batch: int, seq_len: int, cfg) -> float:
    """6 * non-embedding-params * tokens, plus the attention-score FLOPs
    (2*S^2*hidden per layer fwd, x3 with bwd, halved by causality) that the
    params-based formula misses — material at seq 2048."""
    import jax
    import numpy as np

    def is_embedding(path):
        return any("embed" in getattr(k, "key", str(k)).lower() for k in path)

    n_params = sum(
        int(np.prod(x.shape))
        for path, x in jax.tree_util.tree_flatten_with_path(params)[0]
        if not is_embedding(path)
    )
    tokens = global_batch * seq_len
    attn = 0.5 * 12.0 * cfg.num_hidden_layers * global_batch * seq_len**2 * cfg.hidden_size
    # the tied lm_head projection lives under an 'embed' param path (so the
    # filter above drops it) but its logits matmul is real compute
    lm_head = 6.0 * tokens * cfg.hidden_size * cfg.vocab_size if cfg.tie_word_embeddings else 0.0
    return 6.0 * n_params * tokens + attn + lm_head


def run_llama_bench():
    """Second headline: decoder-LM training at long sequence — llama-750M
    class, seq 2048, flash attention + remat + scan-over-layers, fsdp x data
    mesh degenerate to one chip (the regime the long-context kernels were
    built for; catches flash-bwd/remat regressions the BERT bench can't
    see). Prints ONE JSON line."""
    import jax
    import numpy as np
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import LlamaConfig, causal_lm_loss, create_llama_model
    from accelerate_tpu.parallel.mesh import MeshConfig, batch_sharding
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils import MixedPrecisionPolicy, ParallelismPlugin
    from accelerate_tpu.utils.memory import find_executable_batch_size

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()

    tiny = bool(os.environ.get("ACCELERATE_BENCH_FORCE_CPU"))
    devices = _devices(tiny)
    if tiny:
        cfg, seq_len, start_batch = LlamaConfig.tiny(), 128, 4
    else:
        # ~750M: the largest llama-class dense-Adam config that fits one
        # 16 GB v5e with headroom (16 bytes/param of train state = 12.1 GB
        # + seq-2048 boundary activations under remat)
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=1536,
            intermediate_size=6144,
            num_hidden_layers=20,
            num_attention_heads=12,
            num_key_value_heads=6,
            max_position_embeddings=2048,
            tie_word_embeddings=True,
        )
        seq_len, start_batch = 2048, 8

    accelerator = Accelerator(
        mixed_precision="bf16",
        parallelism_plugin=ParallelismPlugin(mesh_config=MeshConfig(data=-1, fsdp=1)),
        kwargs_handlers=[MixedPrecisionPolicy(softmax_dtype="bfloat16")],
    )
    n_dev = accelerator.state.num_devices

    model = accelerator.prepare_model(create_llama_model(cfg, seq_len=seq_len))
    accelerator.prepare_optimizer(optax.adamw(3e-4, weight_decay=0.01))
    step = accelerator.build_train_step(lambda p, b: causal_lm_loss(p, b, model.apply_fn))

    rng = np.random.default_rng(0)
    from accelerate_tpu.telemetry import StepTelemetry

    @find_executable_batch_size(starting_batch_size=start_batch)
    def measure(batch_size):
        global_batch = batch_size * accelerator.num_data_shards
        batch = {
            "input_ids": rng.integers(5, cfg.vocab_size - 1, size=(global_batch, seq_len)).astype(np.int32)
        }
        batch = jax.device_put(batch, batch_sharding(accelerator.mesh))
        # fresh telemetry per attempt: an OOM-halved retry changes the batch
        # shape, which must read as a new run, not a recompile storm
        telem = StepTelemetry(warmup_steps=2)
        tstep = telem.wrap(step)
        float(tstep(batch))  # compile (telemetry attributes it); surfaces OOM for the auto-halver
        for _ in range(2):
            loss = tstep(batch)
        float(loss)
        n_steps = 5 if tiny else 12
        t0 = time.perf_counter()
        for _ in range(n_steps):
            loss = tstep(batch)
        float(loss)
        dt = time.perf_counter() - t0
        return global_batch, dt / n_steps, telem

    global_batch, step_s, telem = measure()
    tokens_per_sec = global_batch * seq_len / step_s
    telem_summary = telem.summary()
    compile_s = telem.compile_ms / 1000.0

    peak, device_kind = _peak_for_device(devices)
    flops_per_step = _llama_step_flops(model.params, global_batch, seq_len, cfg)
    mfu = flops_per_step / step_s / (peak * 1e12 * n_dev)

    print(
        json.dumps(
            {
                "metric": "llama_750m_seq2048_flash_train_tokens_per_sec",
                "value": round(tokens_per_sec, 1),
                "unit": "tokens/sec",
                "vs_baseline": round(mfu / 0.45, 3),  # target: MFU >= 0.45 at seq 2048
                "step_time_ms": round(step_s * 1000, 2),
                "p95_step_ms": telem_summary.get("p95_step_ms"),
                "recompiles": telem.recompiles,
                "mfu": round(mfu, 4),
                "global_batch": global_batch,
                "seq_len": seq_len,
                "peak_bf16_tflops_assumed": peak,
                "device_kind": str(device_kind),
                "compile_s": round(compile_s, 1),
                "n_devices": n_dev,
                "baseline": "MFU 0.45 at seq 2048 with flash attention",
            }
        )
    )


def run_bench():
    import jax
    import numpy as np
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import BertConfig, bert_classification_loss, create_bert_model
    from accelerate_tpu.parallel.mesh import batch_sharding

    # --platform cpu: tiny config + small batch, so the smoke run finishes
    # in seconds, not the hour BERT-base at batch 256 would take on a CPU
    tiny = bool(os.environ.get("ACCELERATE_BENCH_FORCE_CPU"))
    devices = _devices(tiny)

    seq_len = 128
    batch_size = 8 if tiny else 256  # per-chip; best measured v5e throughput (128→1524, 256→1562, 512 regresses)

    from accelerate_tpu.utils import MixedPrecisionPolicy

    # softmax_dtype=bf16: the step is HBM-bound (benchmarks/README.md "step
    # breakdown"); skipping the f32 [B,H,S,S] logits materialisation is the
    # one measured lever (1.10x, loss trajectory within 1.5e-4 @ 20 steps)
    accelerator = Accelerator(
        mixed_precision="bf16",
        kwargs_handlers=[MixedPrecisionPolicy(softmax_dtype="bfloat16")],
    )
    n_dev = accelerator.state.num_devices
    global_batch = batch_size * accelerator.num_data_shards

    model = accelerator.prepare_model(
        create_bert_model(BertConfig.tiny() if tiny else BertConfig.base(), seq_len=seq_len)
    )
    optimizer = accelerator.prepare_optimizer(optax.adamw(2e-5, weight_decay=0.01))
    loss_fn = lambda p, b: bert_classification_loss(p, b, model.apply_fn)
    step = accelerator.build_train_step(loss_fn)

    rng = np.random.default_rng(0)
    batch = {
        "input_ids": rng.integers(5, 1000 if tiny else 30000, size=(global_batch, seq_len)).astype(np.int32),
        "attention_mask": np.ones((global_batch, seq_len), np.bool_),
        "labels": rng.integers(0, 2, size=(global_batch,)).astype(np.int32),
    }
    batch = jax.device_put(batch, batch_sharding(accelerator.mesh))

    # Step telemetry replaces the hand-rolled compile/execute split: the
    # first call's dispatch is attributed as compile, every later call
    # fences on its outputs, and the recompile watchdog proves the steady
    # loop really replays ONE program (a silent recompile here would
    # invalidate the whole samples/sec claim).
    from accelerate_tpu.telemetry import StepTelemetry

    peak, device_kind = _peak_for_device(devices)
    flops_per_step = _bert_step_flops(model.params, global_batch, seq_len)
    telem = StepTelemetry(
        warmup_steps=2,
        flops_per_step=flops_per_step,
        peak_flops_per_device=peak * 1e12,
        n_devices=n_dev,
    )
    step = telem.wrap(step)

    # compile + warmup; float(loss) both synchronises (scalar D2H fetch)
    # and surfaces NaNs immediately.
    float(step(batch))
    compile_s = telem.compile_ms / 1000.0
    for _ in range(3):
        loss = step(batch)
    float(loss)

    # steady state
    n_steps = 20
    t0 = time.perf_counter()
    for _ in range(n_steps):
        loss = step(batch)
    float(loss)
    dt = time.perf_counter() - t0

    step_time_ms = dt / n_steps * 1000
    samples_per_sec = global_batch * n_steps / dt
    per_chip = samples_per_sec / n_dev
    telem_summary = telem.summary()

    mfu = flops_per_step / (dt / n_steps) / (peak * 1e12 * n_dev)

    print(
        json.dumps(
            {
                "metric": "bert_base_seq128_train_samples_per_sec",
                "value": round(samples_per_sec, 1),
                "unit": "samples/sec",
                "vs_baseline": round(per_chip / A100_PER_CHIP_SAMPLES_PER_SEC, 3),
                "step_time_ms": round(step_time_ms, 2),
                "p95_step_ms": telem_summary.get("p95_step_ms"),
                "recompiles": telem.recompiles,
                "per_chip_samples_per_sec": round(per_chip, 1),
                "mfu": round(mfu, 4),
                "peak_bf16_tflops_assumed": peak,
                "device_kind": str(device_kind),
                "compile_s": round(compile_s, 1),
                "n_devices": n_dev,
                "global_batch": global_batch,
                "backend": accelerator.state.backend,
                "baseline": "350 samples/sec/A100 (BERT-base seq128 fp16 fine-tune)",
            }
        )
    )


def _parse_args(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        "bench.py", description="Headline benchmarks (one JSON line per metric)"
    )
    ap.add_argument(
        "--platform",
        choices=("auto", "cpu"),
        default=os.environ.get("ACCELERATE_BENCH_PLATFORM", "auto"),
        help="cpu = run the tiny smoke configuration on the host CPU "
        "(also ACCELERATE_BENCH_PLATFORM=cpu); auto needs an accelerator",
    )
    return ap.parse_args(argv)


def main(argv=None):
    args = _parse_args(argv)
    if args.platform == "cpu":
        os.environ["ACCELERATE_BENCH_FORCE_CPU"] = "1"
    rc = 0
    try:
        run_bench()
    except Exception as e:
        rc = 1
        print(
            json.dumps(
                {
                    "metric": "bert_base_seq128_train_samples_per_sec",
                    "value": 0.0,
                    "unit": "samples/sec",
                    "vs_baseline": 0.0,
                    "error": f"{type(e).__name__}: {str(e)[:400]}",
                    "traceback_tail": traceback.format_exc().splitlines()[-3:],
                }
            )
        )
    # second headline (decoder-LM long-seq training); its failure must not
    # mask a good BERT line and vice versa — each reports independently
    try:
        run_llama_bench()
    except Exception as e:
        rc = 1
        print(
            json.dumps(
                {
                    "metric": "llama_750m_seq2048_flash_train_tokens_per_sec",
                    "value": 0.0,
                    "unit": "tokens/sec",
                    "vs_baseline": 0.0,
                    "error": f"{type(e).__name__}: {str(e)[:400]}",
                    "traceback_tail": traceback.format_exc().splitlines()[-3:],
                }
            )
        )
    if rc:
        sys.exit(rc)


if __name__ == "__main__":
    main()
