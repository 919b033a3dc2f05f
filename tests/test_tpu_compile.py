"""Compile the ``ops/`` kernels for a described v5e chip, with no chip attached.

The TPU's compiler is installed beside jax and compiles for a topology that
is described, not attached (``jax.experimental.topologies``): it refuses here
what it would refuse on the chip — a slice off the tiling, too much VMEM, a
kernel that cannot be partitioned — which interpret mode never shows. Shapes
are the Mistral-7B widths ``chip_smoke.py`` serves and trains at. A compile
that passes is not a chip run: nothing executes, so results and times stay
``chip_smoke.py``'s business. Skipped where the topology cannot be described.

Also here: ``chip_smoke.py`` must refuse to run without an accelerator, and
the compile-cache helper must follow the one placement rule.
"""

import functools
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEADS, KV_HEADS, DIM = 32, 8, 128  # Mistral-7B-v0.1 attention widths
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot describe this topology
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: the next compile would warn
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _compile(fn, *avals):
    compiled = jax.jit(fn).lower(*avals).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the kernel was not lowered to a Mosaic custom call"
    return text


def _on(sharding, shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flash(window, grad):
    from accelerate_tpu.ops.pallas_attention import pallas_flash_attention

    fn = functools.partial(pallas_flash_attention, causal=True, window=window, interpret=False)
    if not grad:
        return fn
    return jax.grad(lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2))


@pytest.mark.parametrize(
    "seq,window,grad",
    [(2048, 4096, False), (2048, 4096, True), (4096, 1024, True)],
    ids=["fwd_s2048", "bwd_s2048", "bwd_s4096_band1024"],
)
def test_flash_attention_compiles_for_v5e(v5e, seq, window, grad):
    chip = SingleDeviceSharding(v5e.devices[0])
    q = _on(chip, (1, seq, HEADS, DIM))
    kv = _on(chip, (1, seq, KV_HEADS, DIM))
    text = _compile(_flash(window, grad), q, kv, kv)
    # forward alone is one kernel; the backward adds dq and dk/dv
    assert text.count("tpu_custom_call") >= (3 if grad else 1)


@pytest.mark.parametrize("window", [4096, None], ids=["window4096", "full"])
def test_paged_decode_attention_compiles_for_v5e(v5e, window):
    from accelerate_tpu.ops.pallas_paged_attention import paged_decode_attention

    chip = SingleDeviceSharding(v5e.devices[0])
    slots, block, table = 8, 16, 256  # 8 slots x 4096 tokens, as the serve phase
    pool = _on(chip, (slots * table + 1, block, KV_HEADS, DIM))
    fn = functools.partial(paged_decode_attention, sliding_window=window, interpret=False)
    _compile(
        fn, _on(chip, (slots, HEADS, DIM)), pool, pool,
        _on(chip, (slots, table), jnp.int32), _on(chip, (slots,), jnp.int32),
    )


@pytest.mark.parametrize(
    "n_in,n_out,group",
    [(4096, 14336, 128), (14336, 4096, 128), (4096, 4096, 64)],
    ids=["gate_up", "down", "attn_g64"],
)
def test_int4_matmul_compiles_for_v5e(v5e, n_in, n_out, group):
    from accelerate_tpu.ops.pallas_qmatmul import int4_matmul

    chip = SingleDeviceSharding(v5e.devices[0])
    fn = functools.partial(int4_matmul, group_size=group, interpret=False)
    _compile(
        fn, _on(chip, (8, n_in)), _on(chip, (n_in // group, group // 2, n_out), jnp.uint8),
        _on(chip, (n_in // group, 1, n_out), jnp.float32),
    )


def test_sharded_flash_attention_partitions_over_four_chips(v5e):
    """Under ``shard_map`` over fsdp=2 x tensor=2 each described chip runs the
    kernel on its own batch rows and heads: the program holds the kernel and
    gathers nothing."""
    from accelerate_tpu.ops.attention import sharded_pallas_attention
    from accelerate_tpu.parallel.mesh import MeshConfig

    mesh = MeshConfig(fsdp=2, tensor=2).build(devices=list(v5e.devices))
    spec = NamedSharding(mesh, P(("data", "fsdp"), None, "tensor", None))
    fn = functools.partial(sharded_pallas_attention, causal=True, mesh=mesh, interpret=False, window=4096)
    text = _compile(fn, _on(spec, (4, 2048, HEADS, DIM)), _on(spec, (4, 2048, KV_HEADS, DIM)),
                    _on(spec, (4, 2048, KV_HEADS, DIM)))
    assert "all-gather" not in text


def test_chip_smoke_refuses_to_run_without_an_accelerator():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode != 0
    assert time.monotonic() - t0 < 30
    assert '"platform": "tpu"' not in out.stdout and '"ok"' not in out.stdout
    assert "no accelerator" in out.stderr


def test_compile_cache_dir_follows_the_one_rule(monkeypatch, tmp_path):
    from accelerate_tpu.aot import configure_persistent_cache, default_compile_cache_dir

    placed = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert default_compile_cache_dir() == str(tmp_path)
    # placed from outside: used as it is, and nothing is set in code
    assert configure_persistent_cache("/somewhere/else") == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == placed

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert default_compile_cache_dir() == os.path.join(REPO, ".cache", "jax")
