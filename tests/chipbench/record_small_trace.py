"""Record ``data/v5e_small.xplane.pb``: run on the chip (``chiprun -- python3 tests/chipbench/record_small_trace.py <out>``)."""

import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    sys.path.insert(0, ".")
    from chipbench import trace

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(".cache/small_trace")
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(6):
            with jax.profiler.TraceAnnotation("feed"):
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    shutil.copy(trace.newest_xplane(".cache/small_trace"), out)


if __name__ == "__main__":
    main(sys.argv[1])
