"""Kill repeat compiles: persistent executable cache + AOT warm start
(docs/usage_guides/compilation.md; no reference analogue — the reference
delegates compilation to torch).

Phase 1 trains cold with a ``CompileKwargs`` handler: every step program
compiles once, then lands in the executable store as a serialized XLA
executable. Phase 2 simulates a restarted process (a new Accelerator
over the same cache dir — a preemption-resumed trainer or a new serving
replica): the SAME programs deserialize from the store with **zero** XLA
compiles, the loss trajectory is bit-exact, and the recompile watchdog
stays silent.
"""

import tempfile
import time

from accelerate_tpu import Accelerator, CompileKwargs
from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

from _common import make_task


def train(cache_dir: str, epochs: int = 3) -> tuple[list, object]:
    accelerator = Accelerator(kwargs_handlers=[CompileKwargs(cache_dir=cache_dir)])
    model, optimizer, dataloader, loss_fn = make_task(accelerator)
    step = accelerator.build_train_step(loss_fn)
    losses = []
    for epoch in range(epochs):
        dataloader.set_epoch(epoch)
        for batch in dataloader:
            losses.append(float(step(batch)))
    return losses, accelerator.program_cache


def main():
    with tempfile.TemporaryDirectory() as cache_dir:
        t0 = time.perf_counter()
        cold_losses, cold_pc = train(cache_dir)
        cold_s = time.perf_counter() - t0
        print(f"cold run : {cold_s:5.2f}s  {cold_pc.misses} XLA compile(s), "
              f"{len(cold_pc.store.keys())} executable(s) stored")

        # "restart": fresh singletons + fresh Accelerator over the same dir
        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()
        t0 = time.perf_counter()
        warm_losses, warm_pc = train(cache_dir)
        warm_s = time.perf_counter() - t0
        print(f"warm run : {warm_s:5.2f}s  {warm_pc.misses} XLA compile(s), "
              f"{warm_pc.deserialized} deserialized")
        assert warm_pc.misses == 0, "warm start must not compile"
        assert warm_losses == cold_losses, "warm trajectory must be bit-exact"
        print(f"speedup  : {cold_s / warm_s:.2f}x, trajectory bit-exact")

    print("compile_cache example: ALL OK")


if __name__ == "__main__":
    main()
