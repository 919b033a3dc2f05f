"""The chat cell's 32-slot decode tick compiled for a described v5e chip, at the cell's
real size: what the chip's compiler would refuse (a kernel it cannot lower, a program
that does not fit 16 GB) is refused here, at no chip time. A compile is not a chip run."""

import json
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HBM_BYTES = 15.75 * 2**30  # what the v5e's compiler budgets of the chip's 16 GB


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent cache but
    # cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_chat_cell_decode_tick_compiles_and_fits_one_v5e_chip(one_chip, monkeypatch):
    import contextlib

    from accelerate_tpu.models.llama import _wrap_llama
    from accelerate_tpu.serving import ServingEngine
    from chipbench.builders import llama_core_train

    with open(os.path.join(ROOT, "chipbench", "configs", "mistral-7b-v0.1-l16.json")) as f:
        config = json.load(f)
    cfg = llama_core_train.mistral_config(config)
    module, shapes = llama_core_train.abstract_params(cfg)
    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jax.numpy.bfloat16), shapes)
    s = config["bench"]["serving"]
    engine = ServingEngine(
        _wrap_llama(module, shapes, cfg), num_slots=s["num_slots"], prompt_buckets=tuple(s["prompt_buckets"]),
        max_len=s["max_len"], paged_block_size=s["paged_block_size"], pool_blocks=s["pool_blocks"],
    )
    raw_tick, tick_args, contexts = engine._perf_programs["decode_tick"]
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tick_args(None))
    # the program asks the default backend whether to lower the Pallas kernel or interpret it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with contextlib.ExitStack() as stack:
        for ctx in contexts:
            stack.enter_context(ctx())
        compiled = jax.jit(raw_tick).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "the paged decode kernel is in the tick"
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert total <= HBM_BYTES, f"the 32-slot tick needs {total / 2**30:.2f} GiB"
    assert m.argument_size_in_bytes > 8.9 * 2**30, "weights and the 2 GiB pool are arguments at their real size"
    print({k: round(getattr(m, k) / 2**30, 2) for k in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes", "alias_size_in_bytes")})
