"""Record ``data/cpu_program_phases.xplane.pb.gz``: a tiny paged ``ServingEngine`` and a tiny
train step driven on the host's CPU under the benchmark's own ``window`` / ``submit`` / ``step`` /
``fence`` spans, so the trace holds the program's ``engine.*`` / ``train.*`` phases inside them.
Three ticks (two requests admitted in the first, a 20-token prompt through chunk windows in the
second) and three train steps. Gzipped: most of an ``.xplane.pb`` is the programs' metadata.

    JAX_PLATFORMS=cpu python3 tests/chipbench/record_program_trace.py tests/chipbench/data/cpu_program_phases.xplane.pb.gz
"""

import gzip
import shutil
import sys
import tempfile

import numpy as np


def main(out: str) -> None:
    sys.path.insert(0, ".")
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import LlamaConfig, create_llama_model
    from accelerate_tpu.serving import ServingEngine
    from chipbench import trace

    tiny = LlamaConfig.tiny(num_hidden_layers=1)
    engine = ServingEngine(create_llama_model(tiny, seq_len=64), num_slots=2, prompt_buckets=(8, 16),
                           max_len=64, paged_block_size=8, tick_block=2)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(5, 200, size=n).astype(np.int32) for n in (5, 12, 20)]  # 20: chunk windows
    for p in prompts:
        engine.submit(p, 3)
    engine.run()  # every program compiles outside the recorded window

    acc = Accelerator()
    model = acc.prepare_model(create_llama_model(tiny, seq_len=16))
    acc.prepare_optimizer(optax.sgd(1e-2))
    batch = {"input_ids": jnp.asarray(rng.integers(5, 200, size=(2, 16)).astype(np.int32))}

    def loss_fn(params, b):
        logits = model.apply_fn(params, b["input_ids"])
        return jnp.mean(jax.nn.log_softmax(logits)[..., 0])

    train = acc.build_train_step(loss_fn)
    jax.block_until_ready(train(batch))

    first = engine._tick + 1
    where = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(where, profiler_options=options)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("submit"):
            for p in prompts[:2]:
                engine.submit(p, 4)
        while engine.queue or engine.active_count:
            with jax.profiler.TraceAnnotation("step"):
                engine.step()
            if engine._tick == first + 1:
                with jax.profiler.TraceAnnotation("submit"):
                    engine.submit(prompts[2], 3)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("step"):
                loss = train(batch)
            with jax.profiler.TraceAnnotation("fence"):
                jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    with open(trace.newest_xplane(where), "rb") as f, gzip.open(out, "wb", compresslevel=9) as g:
        shutil.copyfileobj(f, g)
    shutil.rmtree(where)


if __name__ == "__main__":
    main(sys.argv[1])
