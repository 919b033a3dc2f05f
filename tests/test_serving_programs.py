"""The seam between ``ServingEngine`` and the programs it runs (``accelerate_tpu/serving_programs.py``): a program
is built, named and lowered from the module alone; one function decides which extra arguments a model's programs
take; the decode contract is held at construction."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import serving_programs as sp
from accelerate_tpu.aot import ProgramCache
from accelerate_tpu.generation import _make_sampler
from accelerate_tpu.modeling import Model
from accelerate_tpu.models.jamba import JambaConfig, create_jamba_model
from accelerate_tpu.models.joyai_llm_flash import JoyAIFlashConfig, create_joyai_flash_model
from accelerate_tpu.models.llama import LlamaConfig, create_llama_model
from accelerate_tpu.ops.paged_kv import PagedConfig, paged_mode
from accelerate_tpu.serving import ServingEngine

SLOTS, BUCKET, TICK_BLOCK, BLOCK, MAX_LEN = 2, 16, 2, 4, 32
I32 = jax.ShapeDtypeStruct((), jnp.int32)


@pytest.fixture(scope="module")
def llama():
    return create_llama_model(LlamaConfig.tiny(), seed=0, seq_len=8)


def _paged_config(model):
    """The engine's own default pool: every slot's ``max_len`` rows and the sink."""
    return PagedConfig(block_size=BLOCK, num_blocks=SLOTS * (MAX_LEN // BLOCK) + 1)


def _shapes(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _tick_shapes(model, config):
    """The paged tick's arguments as shapes, from the model alone."""
    one = jnp.zeros((SLOTS, 1), jnp.int32)
    with paged_mode(config):
        _, pool = jax.eval_shape(
            lambda p, i: model.apply_fn(p, i, positions=i, decode=True, cache=None), model.params, one
        )
    slots = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    return _shapes(model.params), pool, slots, slots, jax.eval_shape(lambda: jax.random.split(jax.random.key(0), SLOTS))


def _prefill_shapes(model):
    key = jax.eval_shape(lambda: jax.random.key(0))
    return _shapes(model.params), jax.ShapeDtypeStruct((1, BUCKET), jnp.int32), I32, key, I32


def _engine(model, paged=True, **options):
    paged_options = {"paged_block_size": BLOCK} if paged else {}
    return ServingEngine(
        model, num_slots=SLOTS, prompt_buckets=(BUCKET,), max_len=MAX_LEN, tick_block=TICK_BLOCK, **paged_options, **options
    )


@pytest.mark.parametrize("program", ["prefill", "decode_tick"])
def test_a_program_built_from_the_module_alone_is_the_engines_own(llama, program):
    """No engine: the builders take ``apply_fn``, the sampler and ``tick_block``, and what they return lowers to
    the text of the program the engine built for itself (``_perf_programs``)."""
    sampler = _make_sampler(0.0, None)
    extra = sp.extra_arguments(llama.config, sp.row_template(llama.apply_fn, llama.params), paged=True)
    config = _paged_config(llama)
    if program == "prefill":
        args = _prefill_shapes(llama)
        alone = jax.jit(sp.make_prefill(llama.apply_fn, sampler, extra)).lower(*args).as_text()
    else:
        args = _tick_shapes(llama, config)
        with paged_mode(config):
            alone = jax.jit(sp.make_tick(sp.make_paged_step(llama.apply_fn, sampler), TICK_BLOCK)).lower(*args).as_text()
    assert "stablehlo" in alone
    engine = _engine(llama)
    assert engine._pcfg == config
    assert engine._perf_programs[program].lower(*args).as_text() == alone
    # the engine's sample arguments are those shapes: the description lowers itself to the same text
    assert engine._perf_programs[program].lower(bucket=BUCKET).as_text() == alone


def _spied(model, seen):
    """``model`` with an ``apply_fn`` that notes the keywords each traced call passes beyond the contract's own."""

    def apply_fn(params, ids, positions=None, decode=False, cache=None, **extra):
        seen.append(frozenset(k for k, v in extra.items() if k != "logits_at"))
        return model.apply_fn(params, ids, positions=positions, decode=decode, cache=cache, **extra)

    spied = Model(apply_fn, model.params, name=model.name)
    spied.config = model.config
    return spied


FAMILIES = {
    # name: (model, paged, new_span, decoding, steps_idle_state off the chip)
    "dense_llama": (lambda: create_llama_model(LlamaConfig.tiny(), seed=0, seq_len=8), False, False, False, False),
    "paged_llama": (lambda: create_llama_model(LlamaConfig.tiny(), seed=0, seq_len=8), True, False, False, False),
    "paged_routed_experts": (lambda: create_joyai_flash_model(JoyAIFlashConfig.tiny(), seed=3, seq_len=16), True, False, True, False),
    "paged_hybrid_ssm_state": (lambda: create_jamba_model(JambaConfig.tiny(), seed=3, seq_len=16), True, True, True, True),
    "dense_hybrid_ssm_state": (lambda: create_jamba_model(JambaConfig.tiny(), seed=3, seq_len=16), False, True, False, True),
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_one_function_decides_the_extra_arguments_the_programs_are_called_with(family):
    """``extra_arguments`` from the configuration and the row template alone; then an engine over the same model
    calls its windows with ``new_span`` and its tick with the ``[slots]`` bool exactly where the function said."""
    create, paged, new_span, decoding, steps_idle = FAMILIES[family]
    model = create()
    template = sp.row_template(model.apply_fn, model.params)
    extra = sp.extra_arguments(model.config, template, paged)
    assert (extra.new_span, extra.decoding, extra.steps_idle_state) == (new_span, decoding, steps_idle)
    assert extra.span(2, 5) == ({"new_span": (2, 5)} if new_span else {})

    seen, ticks = [], []
    engine = _engine(_spied(model, seen), paged=paged)
    assert (engine._has_state, engine._mask_idle_rows, engine._steps_idle_state) == (new_span, decoding, steps_idle)
    tick = engine._decode_tick
    engine._decode_tick = lambda *args: ticks.append(len(args)) or tick(*args)
    del seen[:]  # the construction's abstract calls (the row template, the pool) pass nothing beyond the contract
    engine.submit(np.arange(1, 8, dtype=np.int32), max_new_tokens=3)
    engine.run()
    assert set(ticks) == {5 + decoding}
    window = frozenset({"new_span"} if new_span else ())
    mask = frozenset({"row_valid"} if decoding else ())
    assert seen[0] == window, "the bucket's prefill"
    assert set(seen[1:]) == {mask}, "the decode tick's steps"
    engine.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=2)  # over the bucket: chunk windows
    del seen[:]
    engine.step()
    assert seen[:2] == [window, window], "a cold and a warm window"


def test_an_apply_fn_outside_the_decode_contract_is_refused_at_construction():
    """An ``apply_fn`` that takes no ``logits_at`` cannot be served: the constructor says which contract it holds a
    model to, and chains the call's own error."""
    import accelerate_tpu.models as zoo

    inner = zoo.create_gpt2_model(zoo.GPT2Config.tiny(), seq_len=16)
    model = Model(
        lambda p, ids, positions=None, decode=False, cache=None: inner.apply_fn(p, ids, positions, decode, cache),
        inner.params, name="foreign",
    )
    model.config = inner.config
    with pytest.raises(TypeError, match=r"decode contract apply_fn\(params, ids, positions=.*logits_at=.*cannot be called so") as refused:
        ServingEngine(model, num_slots=2, prompt_buckets=(16,), max_len=48)
    assert "logits_at" in str(refused.value.__cause__)


@pytest.fixture(scope="module")
def alone(llama):
    """``EnginePrograms`` with no engine behind them: a cache of their own and the tick's shapes for arguments."""
    config = _paged_config(llama)
    built = []
    programs = sp.EnginePrograms(
        llama, temperature=0.0, top_k=None, tick_block=TICK_BLOCK, prompt_buckets=(BUCKET,), paged_config=config,
        program_cache=ProgramCache(), trace_ctx=sp.contextlib.nullcontext, tick_args=lambda: _tick_shapes(llama, config),
        on_bucket_build=lambda kind, bucket, ms: built.append((kind, bucket)),
    )
    return programs, built


@pytest.mark.parametrize("name", ["prefill_b16", "paged_decode_tick", "paste_row"])
def test_a_built_program_is_jitted_under_the_name_the_benchmark_reads(llama, alone, name):
    """``chipbench/layers/_decode_programs.py`` and the phase log find a program by its jit module's name."""
    programs, built = alone
    _, pool, _, _, keys = _tick_shapes(llama, _paged_config(llama))
    if name == "prefill_b16":
        assert len(programs.prefill) == 0, "nothing is compiled until a bucket is asked for"
        text = programs.prefill[BUCKET].as_text()
        assert built == [("prefill", BUCKET)] and programs.prefill.compiled_buckets() == (BUCKET,)
    elif name == "paged_decode_tick":
        with paged_mode(_paged_config(llama)):
            text = programs.decode_tick.__wrapped__.lower(*programs.described["decode_tick"].args(None)).as_text()
        assert "jax.buffer_donor" in text or "tf.aliasing_output" in text, "the pool is donated"
    else:
        table = jax.ShapeDtypeStruct((-(-llama.config.max_position_embeddings // BLOCK),), jnp.int32)  # a slot's row
        key = jax.eval_shape(lambda: jax.random.key(0))
        text = programs.paste_row.__wrapped__.lower(pool, keys, programs.row_template, key, table, table, I32, I32).as_text()
    assert f"jit_{name}" in text.split("\n", 1)[0], "the module's name, in the text's first line"
    assert programs.insert is None, "the dense layout's admission is not built for a paged engine"
