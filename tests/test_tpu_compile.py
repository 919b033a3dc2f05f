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
import re
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
@pytest.mark.parametrize("slots,layers", [(8, 1), (32, 16)], ids=["serve_phase", "chat_cell"])
def test_paged_decode_attention_compiles_for_v5e(v5e, slots, layers, window):
    """The walk over live pages (async copies out of a pool left in HBM, a dynamic trip count, pages
    stacked into one product) is where Mosaic refuses a slice off the tiling or VMEM over the scoped
    limit. ``serve_phase``: 8 slots x 4096 tokens on a pool of its own, as ``chip_smoke.py`` serves.
    ``chat_cell``: ``mistral7b-serve-chat``'s call, 32 slots on one layer of the carried stack of 16 x
    2048 blocks, flattened and addressed as ``table + layer * NB``; the stack reaches the kernel as a
    bitcast, never a copy."""
    from accelerate_tpu.ops.pallas_paged_attention import paged_decode_attention

    chip = SingleDeviceSharding(v5e.devices[0])
    block, table = 16, 256
    blocks = slots * table + 1 if layers == 1 else 2048
    pool = _on(chip, (layers, blocks, block, KV_HEADS, DIM))

    def fn(q, key_stack, value_stack, tbl, cur, layer):
        flat = (layers * blocks, block, KV_HEADS, DIM)
        return paged_decode_attention(
            q, key_stack.reshape(flat), value_stack.reshape(flat), tbl + layer * blocks, cur,
            sliding_window=window, interpret=False,
        )

    text = _compile(
        fn, _on(chip, (slots, HEADS, DIM)), pool, pool,
        _on(chip, (slots, table), jnp.int32), _on(chip, (slots,), jnp.int32), _on(chip, (), jnp.int32),
    )
    assert "paged_decode_attention" in text, "chipbench's readers find the kernel by this name"
    pools = [line for line in text.splitlines() if f"[{layers * blocks}," in line and " copy(" in line]
    assert not pools, f"the pool is copied or re-laid on its way to the kernel: {pools[0][:200]}"


def _mosaic_programs(text, name):
    """The Mosaic programs of the custom calls named ``name`` in a compiled text, as MLIR without source
    locations (a call's ``body`` is the serialized module, line numbers of the kernel's file and all)."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    programs = []
    for line in text.splitlines():
        body = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line) if "tpu_custom_call" in line and name in line else None
        if body:
            ctx = mlir.make_ir_context()
            ctx.allow_unregistered_dialects = True  # the serialized dialect is ``stable_mosaic``
            with ctx:
                module = ir.Module.parse(base64.b64decode(body.group(1)))
                programs.append(module.operation.get_asm(enable_debug_info=False))
    return programs


@pytest.mark.parametrize(
    "slots,heads,kv_heads,blocks,table,window,digest",
    [
        (32, 32, 8, 16 * 2048, 256, 4096, "02ded84ae5a6f0cdb15bb556e2491af1e1ca6984d5f1956a7be39be1bf373450"),
        (128, 20, 1, 18433, 144, None, "2ac1ae1144392aedae1523ad0b613ce3837f577d52cbc87e25dceb3e69fa765f"),
    ],
    ids=["chat_cell", "longanswer_cell"],
)
def test_paged_decode_attention_is_the_program_its_cells_were_measured_with(
    v5e, slots, heads, kv_heads, blocks, table, window, digest
):
    """The walk over a slot's live pages is shared code (``ops/paged_walk.py``: the K/V kernel under two device
    names and the latent kernel), so a change to it for one cell's sake is a change to every serve cell's kernel.
    At the shapes of ``mistral7b-serve-chat`` and ``jamba2-3b-serve-longanswer`` the K/V kernel's Mosaic program,
    source locations apart, is pinned: PR 32 moved the walk out of the kernel's file and left the program PR 31's
    tree compiled; PR 50 changed it on purpose (a row with a frontier below zero takes no turn, and a row starts
    the first copies of the next row that has keys) and measured the cells: the digests are of that tree's. A
    change to the walk that moves this is a change to the K/V kernel: measure those cells, then take the new digest."""
    import hashlib

    from accelerate_tpu.ops.pallas_paged_attention import paged_decode_attention

    chip = SingleDeviceSharding(v5e.devices[0])
    pool = _on(chip, (blocks, 16, kv_heads, DIM))
    fn = functools.partial(paged_decode_attention, sliding_window=window, interpret=False)
    text = _compile(
        fn, _on(chip, (slots, heads, DIM)), pool, pool, _on(chip, (slots, table), jnp.int32), _on(chip, (slots,), jnp.int32)
    )
    (program,) = _mosaic_programs(text, "paged_decode_attention")
    assert len(program) > 40_000, "the program was not read whole"
    assert hashlib.sha256(program.encode()).hexdigest() == digest


def test_latent_paged_decode_compiles_for_v5e(v5e):
    """The latent (MLA) decode kernel at JoyAI-LLM-Flash widths, the ``joyai-flash-serve-longchat`` cell's
    call: 32 heads against one shared row of 512 + 64 values, 64 slots of 40 pages of 128 tokens. The walk
    (a page copied into its 128 lanes of a chunk buffer at a dynamic offset, a dynamic trip count) is where
    Mosaic refuses a slice off the tiling; the pool reaches the kernel as it is, never copied or re-laid."""
    from accelerate_tpu.ops.pallas_latent_attention import latent_paged_decode

    chip = SingleDeviceSharding(v5e.devices[0])
    slots, block, table, width, rank = 64, 128, 40, 576, 512
    blocks = slots * table + 1
    fn = functools.partial(latent_paged_decode, value_width=rank, scale=192**-0.5, interpret=False)
    text = _compile(
        fn, _on(chip, (slots, HEADS, width)), _on(chip, (blocks, width, block)),
        _on(chip, (slots, table), jnp.int32), _on(chip, (slots,), jnp.int32),
    )
    assert "latent_paged_decode" in text and "paged_decode_attention" not in text, "found by a name of its own"
    pools = [line for line in text.splitlines() if f"[{blocks}," in line and (" copy(" in line or " transpose(" in line)]
    assert not pools, f"the pool is copied or re-laid on its way to the kernel: {pools[0][:200]}"
    (program,) = _mosaic_programs(text, "latent_paged_decode")
    assert "iteration_bounds = array<i64: 64>" in program, "one grid step a slot, whatever its table holds"


def _expert_products(text):
    """The HLO instructions named for the routed experts' products, as chipbench's reader finds them."""
    import re

    return [line.strip() for line in text.splitlines() if re.match(r"\s*(ROOT )?%ragged-dot[\w.\-]* = ", line)]


@pytest.mark.parametrize(
    "pairs", [512, 2048, 8192, 32768], ids=["tick_64x8", "prefill_b256", "prefill_b1024", "prefill_b4096"]
)
def test_grouped_expert_products_compile_for_v5e(v5e, pairs):
    """The routed experts' grouped kernels at JoyAI-LLM-Flash widths (256 experts of ``[2048, 768]`` twice
    and ``[768, 2048]``), at the pair counts of the decode tick and the three prefill buckets: the row
    tile each gets (16 / 32 / 128 / 128), two Mosaic calls named ``ragged-dot-*``, and no product of XLA's."""
    from accelerate_tpu.ops.pallas_grouped_matmul import grouped_swiglu_ffn, row_tile

    chip = SingleDeviceSharding(v5e.devices[0])
    experts, d, ff = 256, 2048, 768
    assert row_tile(pairs, experts) == {512: 16, 2048: 32, 8192: 128, 32768: 128}[pairs]
    text = _compile(
        grouped_swiglu_ffn, _on(chip, (pairs, d)), _on(chip, (experts, d, ff)), _on(chip, (experts, d, ff)),
        _on(chip, (experts, ff, d)), _on(chip, (experts,), jnp.int32),
    )
    products = _expert_products(text)
    assert len(products) == 2 and all("tpu_custom_call" in p for p in products), products
    assert "ragged-dot-swiglu" in products[0] and "ragged-dot-down" in products[1]


def _cell_engine(config_name):
    """A serve cell's ``ServingEngine`` over abstract bf16 parameters at the configuration's widths, through
    the cell's own builder: ``(bench.serving, engine)``."""
    import json

    from accelerate_tpu.models.llama import _wrap_llama
    from accelerate_tpu.serving import ServingEngine
    from chipbench import run

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(REPO, "chipbench", "configs", config_name + ".json")) as f:
        config = json.load(f)
    builder = run.load(manifest, "builders", config["bench"]["builder"])
    cfg = getattr(builder, "core_config", None) or builder.mistral_config  # the chat cell's builder names its family
    cfg = cfg(config)
    module, shapes = builder.abstract_params(cfg)
    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, BF16), shapes)
    s = config["bench"]["serving"]
    return s, ServingEngine(
        _wrap_llama(module, shapes, cfg), num_slots=s["num_slots"], prompt_buckets=tuple(s["prompt_buckets"]),
        max_len=s["max_len"], paged_block_size=s["paged_block_size"], pool_blocks=s["pool_blocks"],
    )


@pytest.mark.parametrize("bucket,temp_mib", [(64, 1), (256, 1), (1024, 32)])
def test_chat_cell_prefill_bucket_attends_over_its_own_rows_on_v5e(v5e, monkeypatch, bucket, temp_mib):
    """A bucket of ``mistral7b-serve-chat`` at its real size, sixteen layers under the layer scan: the call that starts
    the row cache scores its own rows and not the 4,096 rows of a cache that was empty a moment ago (``f32[1,8,4,
    bucket,4096]``, 537 MB a layer at 1024: 267 MiB of temporaries), through the kernel ``prefers_flash`` names for a
    forward-only call of its shape (one Mosaic call in the scan's body, or none), and still hands back 4,096 rows."""
    from accelerate_tpu.ops.attention import prefers_flash

    s, engine = _cell_engine("mistral-7b-v0.1-l16")
    chip = SingleDeviceSharding(v5e.devices[0])
    prefill = engine._perf_programs["prefill"]
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), prefill.args(bucket))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the program asks whether to lower the kernel
    compiled = prefill.lower(*args).compile()
    text = compiled.as_text()
    # scores have a head axis or two ahead of ``[bucket, max_len]``; the hidden rows are ``[1, bucket, 4096]`` too
    assert not re.search(rf"f32\[(\d+,){{2,}}{bucket},{s['max_len']}\]", text), "scores against the whole cache"
    assert text.count('custom_call_target="tpu_custom_call"') == int(prefers_flash(bucket, bucket, HEADS, DIM, forward_only=True))
    assert compiled.memory_analysis().temp_size_in_bytes < temp_mib * 2**20
    cache = jax.eval_shape(prefill.fn, *args)[2]
    assert cache["layers"]["block"]["attn"]["key"].shape == (16, 1, s["max_len"], KV_HEADS, DIM)


def test_latent_moe_decode_tick_fits_one_v5e_chip_and_moves_no_pool(v5e, monkeypatch):
    """The 64-slot decode tick of the ``joyai-flash-serve-longchat`` cell at its real size: 5.56 B
    parameters and a 1.89 GB latent pool as arguments, the pool aliased to the output, and no
    operation that copies or re-lays a whole layer's pool (the compiler did both around a
    ``[.., 128, 576]`` pool: ops/paged_kv.py). A compile is not a chip run."""
    import re

    s, engine = _cell_engine("joyai-llm-flash-l5")
    chip = SingleDeviceSharding(v5e.devices[0])
    tick = engine._perf_programs["decode_tick"]
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tick.args(None))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the program asks whether to lower the kernel
    compiled = tick.lower(*args, donate_argnums=(1,)).compile()
    text = compiled.as_text()
    assert text.count("latent_paged_decode") >= 5 and "ragged-dot" in text
    products = _expert_products(text)  # four expert layers of two grouped kernels; XLA's own 512-row lowering of none
    assert len(products) >= 8 and all("tpu_custom_call" in p for p in products), [p[:160] for p in products]
    pool = f"{s['pool_blocks']},576,{s['paged_block_size']}"
    moved = [l.strip()[:140] for l in text.splitlines() if re.search(rf"= bf16\[{pool}\]\S* (copy|transpose)\(", l)]
    assert not moved, "the tick moves a whole pool:\n" + "\n".join(moved)
    m = compiled.memory_analysis()
    pool_bytes = 5 * s["pool_blocks"] * 576 * s["paged_block_size"] * 2
    assert m.alias_size_in_bytes >= pool_bytes and m.temp_size_in_bytes < 0.5 * 2**30
    assert m.argument_size_in_bytes > 11.9 * 2**30, "weights and the pool are arguments at their real size"
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert total <= 15.75 * 2**30, f"the 64-slot tick needs {total / 2**30:.2f} GiB"


def test_latent_moe_prefill_bucket_heads_one_row_on_one_v5e_chip(v5e, monkeypatch):
    """The 4096-token prefill of the same cell keeps one row of logits and computes one: no
    ``f32[4096,129280]`` (2.12 GB, which was 2.01 GiB of temporaries and the program's largest operation), the
    head a product of one normed row with the ``[2048, 129280]`` kernel. What is left, 0.32 GiB, is the layers'
    own: the routed experts' ``[4096 x 8, 2048]`` rows and their float32 sums."""
    import re

    s, engine = _cell_engine("joyai-llm-flash-l5")
    chip = SingleDeviceSharding(v5e.devices[0])
    prefill = engine._perf_programs["prefill"]
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), prefill.args(4096))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = prefill.lower(*args).compile()
    text = compiled.as_text()
    assert len(_expert_products(text)) == 8 and "lm_head" in text
    assert not re.search(r"f32\[(1,)?4096,129280\]", text), "the head runs on every position of the bucket"
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 0.4 * 2**30, f"the prefill's temporaries are {m.temp_size_in_bytes / 2**30:.2f} GiB"


def test_hybrid_ssm_decode_tick_steps_the_state_in_place_on_one_v5e_chip(v5e, monkeypatch):
    """The 128-slot decode tick of the ``jamba2-3b-serve-longanswer`` cell at its real size: 3.03 B
    parameters, 26 states of ``[128, 16, 5120]`` float32 and two K/V pools as arguments, all aliased to
    the output; 26 ``ssm_state_step`` kernels and two ``paged_decode_attention`` a step, and no operation
    that copies or re-lays a state leaf or a pool. A compile is not a chip run."""
    import re

    s, engine = _cell_engine("ai21-jamba2-3b")
    chip = SingleDeviceSharding(v5e.devices[0])
    tick = engine._perf_programs["decode_tick"]
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tick.args(None))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the program asks whether to lower the kernels
    compiled = tick.lower(*args, donate_argnums=(1,)).compile()
    text = compiled.as_text()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l and "custom-call(" in l]
    assert sum("ssm_state_step" in l for l in calls) == 26 and sum("paged_decode_attention" in l for l in calls) == 2
    slots = s["num_slots"]
    state = rf"(f32\[{slots},16,5120\]|bf16\[{slots},15360\]|bf16\[{s['pool_blocks']},{s['paged_block_size']},1,128\])"
    moved = [l.strip()[:160] for l in text.splitlines() if re.search(rf"= {state}\S* (copy|transpose)\(", l)]
    assert not moved, "the tick copies or re-lays a state leaf or a pool:\n" + "\n".join(moved)
    m = compiled.memory_analysis()
    state_bytes = 26 * slots * (16 * 5120 * 4 + 15360 * 2) + 2 * 2 * s["pool_blocks"] * s["paged_block_size"] * 128 * 2
    assert m.alias_size_in_bytes >= state_bytes and m.temp_size_in_bytes < 0.5 * 2**30
    assert m.argument_size_in_bytes > 6.05e9 + state_bytes, "weights, states and pools are arguments at their real size"
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert total <= 9.0 * 2**30, f"the 128-slot tick needs {total / 2**30:.2f} GiB"


def test_hybrid_ssm_prefill_bucket_compiles_for_v5e(v5e):
    """The 1024-token prefill of the same cell: 26 chunked scans whose temporaries stay far under one
    window's ``[1024, 16, 5120]`` float32 (335 MB a layer), and a row cache whose state leaves are one row."""
    _, engine = _cell_engine("ai21-jamba2-3b")
    chip = SingleDeviceSharding(v5e.devices[0])
    prefill = engine._perf_programs["prefill"]
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), prefill.args(1024))
    compiled = prefill.lower(*args).compile()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 1.5 * 2**30, f"the prefill's temporaries are {m.temp_size_in_bytes / 2**30:.2f} GiB"
    cache = jax.eval_shape(prefill.fn, *args)[2]
    assert cache["layer_0"]["mamba"]["ssm_state"].shape == (1, 16, 5120)
    assert cache["layer_7"]["attn"]["key"].shape == (1, 2304, 1, 128)


def test_lfm2_moe_decode_tick_fits_one_v5e_chip_and_copies_no_pool_or_state(v5e, monkeypatch):
    """The 128-slot decode tick of the ``lfm2-8b-a1b-serve-longanswer`` cell at its real size: 5.40 B
    parameters, four K/V pools of heads of 64 **folded two to a row of 128 lanes** (2.42 GB, not the 4.83
    a padded minor axis of 64 would take; unfolded, Mosaic refuses the page slice) and 12 convolution
    states of ``[128, 4096]`` as arguments, all aliased to the output; four ``paged_decode_attention`` and
    14 x 2 grouped expert kernels a step at ``[2048, 1792]`` with row tile 64, and no operation that
    copies or re-lays a pool or a state leaf whole. A compile is not a chip run."""
    import re

    from accelerate_tpu.ops.pallas_grouped_matmul import row_tile

    s, engine = _cell_engine("lfm2-8b-a1b-l16")
    chip = SingleDeviceSharding(v5e.devices[0])
    tick = engine._perf_programs["decode_tick"]
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tick.args(None))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the program asks whether to lower the kernels
    compiled = tick.lower(*args, donate_argnums=(1,)).compile()
    text = compiled.as_text()
    slots, blocks, bs = s["num_slots"], s["pool_blocks"], s["paged_block_size"]
    assert row_tile(slots * 4, 32) == 64
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l and "custom-call(" in l]
    assert sum("paged_decode_attention" in l for l in calls) == 4
    products = _expert_products(text)  # 14 expert layers of two grouped kernels; XLA's own 512-row lowering of none
    assert len(products) == 28 and all("tpu_custom_call" in p for p in products), [p[:160] for p in products]
    assert f"bf16[{blocks},{bs},4,128]" in text and f"bf16[{blocks},{bs},8,64]" not in text, "two heads of 64 to a row"
    leaf = rf"(bf16\[{slots},4096\]|bf16\[{blocks},\d+,\d+(,\d+)?\])"
    moved = [l.strip()[:160] for l in text.splitlines() if re.search(rf"= {leaf}\S* (copy|transpose)\(", l)]
    assert not moved, "the tick copies or re-lays a pool or a state leaf:\n" + "\n".join(moved)
    m = compiled.memory_analysis()
    pool_bytes = 4 * 2 * blocks * bs * 8 * 64 * 2
    state_bytes = 12 * slots * 4096 * 2
    assert 2.41e9 < pool_bytes < 2.42e9 and m.alias_size_in_bytes >= pool_bytes + state_bytes
    assert m.alias_size_in_bytes < pool_bytes + state_bytes + 2**20, "the pools are their logical bytes: no padded lanes"
    assert m.argument_size_in_bytes > 10.79e9 + pool_bytes and m.temp_size_in_bytes < 0.5 * 2**30
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert total <= 12.6 * 2**30, f"the 128-slot tick needs {total / 2**30:.2f} GiB"


def test_lfm2_moe_paste_row_scatters_into_the_pools_in_place(v5e):
    """``paste_row`` of the same cell, 8 pools of ``[18433, 16, 4, 128]`` donated: a head axis of 4 is under a
    sublane tile, and around a scatter of whole blocks the compiler re-laid every pool with the block's
    token axis innermost and back (16 whole-pool copies, 15 ms a paste on the chip: PR 34's first trace);
    through the flat view ``[NB, 64, 128]`` it scatters in place. Mistral's 8 heads and Jamba's one never did."""
    import re

    from accelerate_tpu.ops.paged_kv import paste_row

    s, engine = _cell_engine("lfm2-8b-a1b-l16")
    chip = SingleDeviceSharding(v5e.devices[0])
    on = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)  # noqa: E731
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)  # noqa: E731
    compiled = jax.jit(paste_row, donate_argnums=(0,)).lower(
        on(engine.slot_caches), on(engine._row_template), i32(engine._mb), i32(engine._mb), i32(), i32()).compile()
    text = compiled.as_text()
    moved = [l.strip()[:160] for l in text.splitlines() if re.search(rf"= bf16\[{s['pool_blocks']},[^\]]*\]\S* (copy|transpose)\(", l)]
    assert not moved, "paste_row copies or re-lays a pool:\n" + "\n".join(moved)
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 64 * 2**20 and m.alias_size_in_bytes > 2.41e9


def test_lfm2_moe_prefill_bucket_compiles_for_v5e(v5e, monkeypatch):
    """The 1024-token prefill of the same cell: 14 x 2 grouped kernels at row tile 128, temporaries far
    under a GiB, and a row cache whose state leaves are one row of two gated inputs a convolution layer."""
    _, engine = _cell_engine("lfm2-8b-a1b-l16")
    chip = SingleDeviceSharding(v5e.devices[0])
    prefill = engine._perf_programs["prefill"]
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), prefill.args(1024))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = prefill.lower(*args).compile()
    assert len(_expert_products(compiled.as_text())) == 28
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 0.75 * 2**30, f"the prefill's temporaries are {m.temp_size_in_bytes / 2**30:.2f} GiB"
    cache = jax.eval_shape(prefill.fn, *args)[2]
    assert cache["layer_0"]["conv"]["conv_state"].shape == (1, 4096)
    assert cache["layer_2"]["attn"]["key"].shape == (1, 2304, 8, 64)


def test_ssd_state_step_compiles_for_v5e_and_steps_the_state_in_place(v5e):
    """The Mamba-2 state-step kernel at granite-4.0-h-small's widths and the cell's 64 slots: ``h`` ``[64, 128,
    8192]`` float32 (4 MB a slot) left in HBM and aliased to the output, four VMEM buffers of one slot's state
    (16 MB: the kernel asks for its own limit), the ``[slots]`` mask scalar-prefetched. Mosaic accepts the
    layout (the state axis along the sublanes, the heads' channels along the lanes) and XLA copies no state."""
    import re

    from accelerate_tpu.ops.pallas_ssd_step import ssd_state_step

    chip = SingleDeviceSharding(v5e.devices[0])
    slots, n, heads, p = 64, 128, 128, 64
    f32 = jnp.float32
    fn = functools.partial(ssd_state_step, interpret=False)
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        _on(chip, (slots, n, heads * p), f32), _on(chip, (slots, heads, p)), _on(chip, (slots, heads), f32), _on(chip, (heads,), f32),
        _on(chip, (slots, n)), _on(chip, (slots, n)), _on(chip, (heads,), f32), _on(chip, (slots,), jnp.bool_)).compile()
    text = compiled.as_text()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l and "custom-call(" in l]
    assert len(calls) == 1 and "ssd_state_step" in calls[0]
    moved = [l.strip()[:160] for l in text.splitlines() if re.search(rf"= f32\[{slots},{n},{heads * p}\]\S* (copy|transpose)\(", l)]
    assert not moved, "the call copies or re-lays the state:\n" + "\n".join(moved)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= slots * n * heads * p * 4 and m.temp_size_in_bytes < 32 * 2**20


def test_granite_hybrid_decode_tick_fits_one_v5e_chip_and_copies_no_state(v5e, monkeypatch):
    """The 64-slot decode tick of the ``granite-4.0-h-small-serve-longanswer`` cell at its real size: 4.76 B
    parameters (36 of 72 experts a layer held), nine states of ``[64, 128, 8192]`` float32 (2.42 GB), nine
    convolution states and one K/V pool as arguments, all aliased to the output; nine ``ssd_state_step``
    kernels, one ``paged_decode_attention`` and 10 x 2 grouped expert kernels over ``[36, 4096, 768]`` a step,
    and no operation that copies or re-lays a state leaf or the pool whole. A compile is not a chip run."""
    import re

    s, engine = _cell_engine("granite-4.0-h-small-l10")
    assert engine.metrics.state_bytes_per_slot == 9 * (128 * 8192 * 4 + 3 * 8448 * 2) and engine._mask_idle_rows
    chip = SingleDeviceSharding(v5e.devices[0])
    tick = engine._perf_programs["decode_tick"]
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tick.args(None))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the program asks whether to lower the kernels
    compiled = tick.lower(*args, donate_argnums=(1,)).compile()
    text = compiled.as_text()
    slots, blocks, bs = s["num_slots"], s["pool_blocks"], s["paged_block_size"]
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l and "custom-call(" in l]
    assert sum("ssd_state_step" in l for l in calls) == 9 and sum("paged_decode_attention" in l for l in calls) == 1
    products = _expert_products(text)  # ten expert layers of two grouped kernels; XLA's own 512-row lowering of none
    assert len(products) == 20 and all("tpu_custom_call" in p for p in products), [p[:160] for p in products]
    assert "bf16[36,4096,768]" in text and "bf16[72,4096,768]" not in text, "the layer holds its share of the experts"
    leaf = rf"(f32\[{slots},128,8192\]|bf16\[{slots},25344\]|bf16\[{blocks},{bs},8,128\])"
    moved = [l.strip()[:160] for l in text.splitlines() if re.search(rf"= {leaf}\S* (copy|transpose)\(", l)]
    assert not moved, "the tick copies or re-lays a state leaf or the pool:\n" + "\n".join(moved)
    m = compiled.memory_analysis()
    state_bytes = 9 * slots * (128 * 8192 * 4 + 25344 * 2) + 2 * blocks * bs * 8 * 128 * 2
    assert m.alias_size_in_bytes >= state_bytes and m.temp_size_in_bytes < 0.25 * 2**30
    assert m.argument_size_in_bytes > 9.51e9 + state_bytes, "weights, states and the pool are arguments at their real size"
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert total <= 11.9 * 2**30, f"the 64-slot tick needs {total / 2**30:.2f} GiB"


def test_granite_hybrid_prefill_bucket_and_paste_compile_for_v5e(v5e, monkeypatch):
    """The 1024-token prefill of the same cell: nine chunked scans (four chunks of 256: a chunk's ``[256, 256,
    128]`` decays are 33 MB, the state is touched once a chunk), 10 x 2 grouped kernels, temporaries under
    half a GiB, and a row cache whose state leaves are one row; and ``paste_row`` of that row cache, 2.42 GB of
    state donated, writes a slot's 4 MB a layer in place."""
    import re

    from accelerate_tpu.ops.paged_kv import paste_row

    s, engine = _cell_engine("granite-4.0-h-small-l10")
    chip = SingleDeviceSharding(v5e.devices[0])
    on = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)  # noqa: E731
    prefill = engine._perf_programs["prefill"]
    args = on(prefill.args(1024))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = prefill.lower(*args).compile()
    assert len(_expert_products(compiled.as_text())) == 20
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 0.5 * 2**30, f"the prefill's temporaries are {m.temp_size_in_bytes / 2**30:.2f} GiB"
    cache = jax.eval_shape(prefill.fn, *args)[2]
    assert cache["layer_0"]["mamba"]["ssm_state"].shape == (1, 128, 8192) and cache["layer_0"]["mamba"]["ssm_state"].dtype == jnp.float32
    assert cache["layer_0"]["mamba"]["conv_state"].shape == (1, 3 * 8448) and cache["layer_5"]["attn"]["key"].shape == (1, 2304, 8, 128)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)  # noqa: E731
    pasted = jax.jit(paste_row, donate_argnums=(0,)).lower(
        on(engine.slot_caches), on(engine._row_template), i32(engine._mb), i32(engine._mb), i32(), i32()).compile()
    leaf = rf"(f32\[{s['num_slots']},128,8192\]|bf16\[{s['pool_blocks']},[^\]]*\])"
    moved = [l.strip()[:160] for l in pasted.as_text().splitlines() if re.search(rf"= {leaf}\S* (copy|transpose)\(", l)]
    assert not moved, "paste_row copies or re-lays a state leaf or the pool:\n" + "\n".join(moved)
    pm = pasted.memory_analysis()
    assert pm.temp_size_in_bytes < 64 * 2**20 and pm.alias_size_in_bytes > 3.0e9


def test_paged_decode_attention_compiles_at_pages_of_32_key_value_heads(v5e):
    """EvaByte's page, ``[16, 32, 128]`` (131,072 B for K): 512 rows of the flat view a page, eight pages (128 tokens) a
    chunk of 4 MiB of VMEM, every query head scoring all 512 rows of a page and keeping 16; the table is the 16 + 128
    entries a decode step gathers (``ops/eva_attention.py``). One Mosaic call."""
    from accelerate_tpu.ops.pallas_paged_attention import _pages_per_chunk, paged_decode_attention

    chip = SingleDeviceSharding(v5e.devices[0])
    assert _pages_per_chunk(16, 32, 128, BF16) == 8
    pool = _on(chip, (4737, 16, 32, 128))
    text = _compile(
        functools.partial(paged_decode_attention, interpret=False), _on(chip, (32, 32, 128)), pool, pool,
        _on(chip, (32, 144), jnp.int32), _on(chip, (32,), jnp.int32))
    assert len(_mosaic_programs(text, "paged_decode_attention")) == 1


def test_evabyte_decode_tick_fits_one_v5e_chip_and_copies_no_pool(v5e, monkeypatch):
    """The 32-slot decode tick of the ``evabyte-serve-longchat`` cell at its real size: 1.62 B parameters and eight
    pools of ``[4737, 16, 32, 128]`` for K and for V (9.93 GB) as arguments, all aliased to the output; eight
    ``paged_decode_attention`` kernels a step over the gathered table, the chunk's pooling (a page read back and
    a summary row written, in place), and no operation that copies or re-lays a pool whole. Inside 15.75 GiB with
    the 1 GB the issue asks to leave free. A compile is not a chip run."""
    import re

    s, engine = _cell_engine("evabyte-6.5b-l8")
    assert engine._aligned == (2048, 16) and engine._summary_entries == 20 and engine._mb == 320
    chip = SingleDeviceSharding(v5e.devices[0])
    tick = engine._perf_programs["decode_tick"]
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tick.args(None))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the program asks whether to lower the kernel
    compiled = tick.lower(*args, donate_argnums=(1,)).compile()
    text = compiled.as_text()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l and "custom-call(" in l]
    assert sum("paged_decode_attention" in l for l in calls) == 8 and "s32[32,144]" in text, "the gathered table: 16 + 128 entries a slot"
    pool = f"{s['pool_blocks']},{s['paged_block_size']},32,128"
    moved = [l.strip()[:160] for l in text.splitlines() if re.search(rf"= bf16\[{pool}\]\S* (copy|transpose)\(", l)]
    assert not moved, "the tick copies or re-lays a pool:\n" + "\n".join(moved)
    m = compiled.memory_analysis()
    pool_bytes = 8 * 2 * s["pool_blocks"] * 16 * 32 * 128 * 2
    assert pool_bytes == 4737 * 2_097_152 and m.alias_size_in_bytes >= pool_bytes and m.temp_size_in_bytes < 1.0 * 2**30
    assert m.argument_size_in_bytes > 3.24e9 + pool_bytes, "weights and the pools are arguments at their real size"
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert total <= 14.75 * 2**30, f"the 32-slot tick needs {total / 2**30:.2f} GiB"


def test_evabyte_prefill_bucket_and_paste_compile_for_v5e(v5e, monkeypatch):
    """The 4096-byte prefill of the same cell, two windows: the second attends ``[128 summaries | its 2048 rows]``
    through the flash kernel (a causal mask aligned bottom-right; 2,176 keys padded to 2,560), one Mosaic call a window a
    layer and no float32 score matrix, so the temporaries stay under 0.6 GiB beside a row cache of 0.66 GiB (5,120 rows
    of K and V a layer and their 320 summaries); and ``paste_row`` of that row cache, 9.93 GB of pools donated,
    scatters the open window's pages and the summaries' pages in place."""
    import re

    from accelerate_tpu.ops.paged_kv import paste_row

    s, engine = _cell_engine("evabyte-6.5b-l8")
    chip = SingleDeviceSharding(v5e.devices[0])
    on = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)  # noqa: E731
    prefill = engine._perf_programs["prefill"]
    args = on(prefill.args(4096))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = prefill.lower(*args).compile()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 0.6 * 2**30, f"the prefill's temporaries are {m.temp_size_in_bytes / 2**30:.2f} GiB"
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 16, "a flash call a window a layer"
    assert not re.search(r"f32\[(1,)?32,512,2176\]", text), "a window's float32 scores are formed"
    cache = jax.eval_shape(prefill.fn, *args)[2]
    assert cache["layer_0"]["attn"]["key"].shape == (1, 5120, 32, 128) and cache["layer_7"]["attn"]["summary_value"].shape == (1, 320, 32, 128)
    assert 0.6 * 2**30 < m.output_size_in_bytes < 0.7 * 2**30
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)  # noqa: E731
    pasted = jax.jit(paste_row, donate_argnums=(0,)).lower(
        on(engine.slot_caches), on(engine._row_template), i32(engine._mb), i32(engine._mb), i32(), i32(), i32(engine._summary_entries)).compile()
    leaf = rf"bf16\[{s['pool_blocks']},[^\]]*\]"
    moved = [l.strip()[:160] for l in pasted.as_text().splitlines() if re.search(rf"= {leaf}\S* (copy|transpose)\(", l)]
    assert not moved, "paste_row copies or re-lays a pool:\n" + "\n".join(moved)
    pm = pasted.memory_analysis()
    assert pm.temp_size_in_bytes < 64 * 2**20 and pm.alias_size_in_bytes > 9.9e9


@pytest.mark.parametrize("heads,window,table,blocks,name", [(48, None, 320, 10_241, "paged_decode_attention"),
                                                           (64, 512, 34, 2_177, "paged_decode_attention_w512")],
                         ids=["full_layers_groups_of_6", "window_layers_ring_of_34"])
def test_paged_decode_attention_compiles_at_laguna_widths_under_both_tables(v5e, heads, window, table, blocks, name):
    """The two shapes of the kernel in ``laguna-xs.2-serve-longchat``'s tick: 48 query heads on 8 key/value heads
    (groups of 6, new to the kernel) against the whole context's table, and 64 heads under a band of 512 keys against
    a ring of 34 entries a slot (``tbl_ref[row, page % 34]``), each under a device name of its own."""
    from accelerate_tpu.ops.pallas_paged_attention import paged_decode_attention

    chip = SingleDeviceSharding(v5e.devices[0])
    pool = _on(chip, (blocks, 16, KV_HEADS, DIM))
    fn = functools.partial(paged_decode_attention, sliding_window=window, interpret=False, ring=window is not None)
    text = _compile(fn, _on(chip, (64, heads, DIM)), pool, pool, _on(chip, (64, table), jnp.int32), _on(chip, (64,), jnp.int32))
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l and "custom-call(" in l]
    assert len(calls) == 1 and re.match(rf"\s*(ROOT )?%?{name}(\.\d+)* =", calls[0]), calls[0][:120]
    assert not [l for l in text.splitlines() if f"[{blocks}," in l and " copy(" in l], "the pool is copied on its way to the kernel"


def test_laguna_tick_prefill_and_paste_compile_for_v5e_and_move_no_pool(v5e, monkeypatch):
    """The three programs of ``laguna-xs.2-serve-longchat`` at their real size, one engine: the 64-slot decode tick (3.38 B
    parameters, four pools of ``[10241, 16, 8, 128]`` and nine of ``[2177, 16, 8, 128]`` for K and for V, 3.97 GB, all
    aliased to the output; 4 + 9 paged kernels a step under their two names and 12 + 12 grouped expert products), the
    4096-token prefill (13 flash calls, banded on the window layers, no float32 score matrix: under 0.6 GiB of
    temporaries beside a 273 MB row cache) and ``paste_row`` with the slot's ring (the window layers' last 34 pages
    cut out of the row cache and scattered in place). Inside 15.75 GiB with 1 GB to spare. A compile is not a chip run."""
    import json

    from accelerate_tpu.models.llama import _wrap_llama
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.serving_programs import paste_row_ring
    from chipbench import run

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(REPO, "chipbench", "configs", "laguna-xs.2-l13-ep4.json")) as f:
        config = json.load(f)
    builder = run.load(manifest, "builders", config["bench"]["builder"])
    cfg = builder.core_config(config)
    module, shapes = builder.abstract_params(cfg)
    s = config["bench"]["serving"]
    engine = ServingEngine(
        _wrap_llama(module, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, BF16), shapes), cfg), num_slots=s["num_slots"],
        prompt_buckets=tuple(s["prompt_buckets"]), max_len=s["max_len"], paged_block_size=s["paged_block_size"],
        pool_blocks=s["pool_blocks"], window_pool_blocks=s["window_pool_blocks"], tick_block=s["tick_block"])
    assert (engine._ring, engine._mb, engine._pcfg.window_blocks, engine._pcfg.num_blocks) == (34, 320, 2_177, 10_241)
    chip = SingleDeviceSharding(v5e.devices[0])
    on = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)  # noqa: E731
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the programs ask whether to lower the kernels
    tick = engine._perf_programs["decode_tick"]
    compiled = tick.lower(*on(tick.args(None)), donate_argnums=(1,)).compile()
    text = compiled.as_text()
    calls = [l.strip() for l in text.splitlines() if "tpu_custom_call" in l and "custom-call(" in l]
    named = lambda name: sum(bool(re.match(rf"(ROOT )?%?{name}(\.\d+)* =", l)) for l in calls)  # noqa: E731
    assert (named("paged_decode_attention"), named("paged_decode_attention_w512")) == (4, 9), "two shapes of the kernel, by name"
    assert (named("ragged-dot-swiglu"), named("ragged-dot-down")) == (12, 12)
    pools = r"bf16\[(10241|2177),16,8,128\]"
    moved = [l.strip()[:160] for l in text.splitlines() if re.search(rf"= {pools}\S* (copy|transpose)\(", l)]
    assert not moved, "the tick copies or re-lays a pool:\n" + "\n".join(moved)
    m = compiled.memory_analysis()
    pool_bytes = (4 * 10_241 + 9 * 2_177) * 65_536
    assert m.alias_size_in_bytes >= pool_bytes and m.argument_size_in_bytes > 6.76e9 + pool_bytes and m.temp_size_in_bytes < 0.7 * 2**30
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert total <= 14.75 * 2**30, f"the 64-slot tick needs {total / 2**30:.2f} GiB"
    prefill = engine._perf_programs["prefill"]
    compiled = prefill.lower(*on(prefill.args(4096))).compile()
    pm = compiled.memory_analysis()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 13 + 24, "a flash call a layer beside the grouped products"
    assert pm.temp_size_in_bytes < 0.6 * 2**30 and 0.25 * 2**30 < pm.output_size_in_bytes < 0.26 * 2**30
    assert not re.search(r"f32\[(1,)?(48|64),4096,5120\]", compiled.as_text()), "a bucket's float32 scores against the whole cache are formed"
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)  # noqa: E731
    keys, key = on(jax.eval_shape(lambda: jax.random.split(jax.random.key(0), 64))), on(jax.eval_shape(lambda: jax.random.key(0)))
    pasted = jax.jit(paste_row_ring, donate_argnums=(0,)).lower(
        on(engine.slot_caches), keys, on(engine._row_template), key, i32(320), i32(320), i32(), i32(), i32(34)).compile()
    moved = [l.strip()[:160] for l in pasted.as_text().splitlines() if re.search(rf"= {pools}\S* (copy|transpose)\(", l)]
    assert not moved, "paste_row copies or re-lays a pool:\n" + "\n".join(moved)
    assert pasted.memory_analysis().temp_size_in_bytes < 64 * 2**20 and pasted.memory_analysis().alias_size_in_bytes >= pool_bytes


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
