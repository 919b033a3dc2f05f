"""A program that keeps one row of logits asks the model for that row (``logits_at``): the final norm and the
output head run on the rows asked for, the layers and the cache on the whole call. Over the tiny configurations of
the six served families; ``gpt2`` and ``gptneox`` beside them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import EvaByteConfig, create_evabyte_model
from accelerate_tpu.models.granitemoehybrid import GraniteMoeHybridConfig, create_granitemoehybrid_model
from accelerate_tpu.models.jamba import JambaConfig, create_jamba_model
from accelerate_tpu.models.joyai_llm_flash import JoyAIFlashConfig, create_joyai_flash_model
from accelerate_tpu.models.lfm2_moe import Lfm2MoeConfig, create_lfm2_moe_model
from accelerate_tpu.models.llama import LlamaConfig, create_llama_model
from accelerate_tpu.ops.kv_cache import reset_cache_index
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.telemetry.trace import phase_log

FAMILIES = {
    "dense_llama": (create_llama_model, LlamaConfig.tiny, 4),
    "latent_attention_routed_experts": (create_joyai_flash_model, JoyAIFlashConfig.tiny, 4),
    "mamba_hybrid": (create_jamba_model, JambaConfig.tiny, 4),
    "short_convolution_experts": (create_lfm2_moe_model, Lfm2MoeConfig.tiny, 4),
    "mamba2_hybrid_tied_head_logits_scaling": (create_granitemoehybrid_model, GraniteMoeHybridConfig.tiny, 8),
    "eva": (create_evabyte_model, EvaByteConfig.tiny, 4),  # a chunk to a page
}
BUCKETS = (16, 32)
ATOL = 5e-6  # logits of a few units in float32
VOCAB = 251  # no other width of a tiny configuration: ``x251xf32`` in a lowered text is an array of logits


def _ids(n, start=1):
    return (np.arange(start, start + n, dtype=np.int32) * 7) % 250 + 1


@pytest.fixture(scope="module", params=list(FAMILIES))
def served(request):
    create, tiny, block = FAMILIES[request.param]
    model = create(tiny(vocab_size=VOCAB), seed=3, seq_len=16)
    return model, ServingEngine(model, num_slots=2, prompt_buckets=BUCKETS, max_len=64, tick_block=2, paged_block_size=block)


def _whole_prefill(model, engine, prompt, bucket):
    """The bucket program as it was: the head on every position of the bucket, one row kept."""
    span = {"new_span": (0, len(prompt))} if engine._has_state else {}
    ids = np.zeros((1, bucket), np.int32)
    ids[0, : len(prompt)] = prompt
    positions = jnp.broadcast_to(jnp.arange(bucket), (1, bucket))
    logits, cache = jax.jit(
        lambda p, i: model.apply_fn(p, i, positions=positions, decode=True, cache=None, **span)
    )(model.params, ids)
    row = logits[0, len(prompt) - 1]
    tok = jnp.argmax(row)
    return int(tok), float(jax.nn.log_softmax(row.astype(jnp.float32))[tok]), reset_cache_index(cache, len(prompt))


def _assert_same_cache(want, got, atol=0.0):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want), jax.tree.leaves(got), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=atol, err_msg=jax.tree_util.keystr(path))


def test_logits_at_is_the_rows_asked_for_and_the_cache_is_the_whole_calls(served):
    model, engine = served
    ids = jnp.asarray(_ids(16, start=5)[None])
    positions = jnp.broadcast_to(jnp.arange(16), (1, 16))
    span = {"new_span": (0, 11)} if engine._has_state else {}

    def call(**head):
        return jax.jit(lambda p, i: model.apply_fn(p, i, positions=positions, decode=True, cache=None, **span, **head))(
            model.params, ids
        )

    whole, cache = call()
    assert whole.shape == (1, 16, VOCAB) and whole.dtype == jnp.float32
    for at, rows in ((jnp.int32(10), [10]), (jnp.asarray([0, 15, 3], jnp.int32), [0, 15, 3])):
        got, got_cache = call(logits_at=at)
        assert got.shape == (1, len(rows), VOCAB) and got.dtype == jnp.float32
        # a product of n rows may order its sums otherwise than one of 16: float32's last digit, no more
        np.testing.assert_allclose(np.asarray(got)[0], np.asarray(whole)[0, rows], rtol=0, atol=ATOL)
        _assert_same_cache(cache, got_cache)
    # without a cache (training, evaluation): the same rows
    plain = jax.jit(model.apply_fn)(model.params, ids)
    at7 = jax.jit(lambda p, i: model.apply_fn(p, i, logits_at=jnp.int32(7)))(model.params, ids)
    np.testing.assert_allclose(np.asarray(at7)[0, 0], np.asarray(plain)[0, 7], rtol=0, atol=ATOL)


def test_without_rows_asked_for_the_program_is_the_one_it_was(served):
    """``logits_at=None`` is the call without the argument, to the letter of the lowered text: every position's
    logits out, and no gather of hidden rows ahead of the norm."""
    model, _ = served
    ids = jax.ShapeDtypeStruct((1, 16), jnp.int32)

    def text(**head):
        return jax.jit(lambda p, i: model.apply_fn(p, i, decode=True, cache=None, **head)).lower(model.params, ids).as_text()

    assert text(logits_at=None) == text()
    assert f"tensor<1x16x{VOCAB}xf32>" in text() and f"tensor<1x16x{VOCAB}xf32>" not in text(logits_at=jnp.int32(3))


def test_bucket_prefill_heads_one_row_and_serves_the_whole_programs_token(served):
    """A prompt in each bucket: first token, its logprob and the row cache are those of the program that ran the
    head on every position; ``head_rows`` is 1 a bucket prefill; the lowered program holds no ``[bucket, vocab]``."""
    model, engine = served
    rows_was = engine.metrics.head_rows
    for bucket, n in zip(BUCKETS, (11, 27)):
        prompt = _ids(n, start=2 * n)
        tok, lp, cache = _whole_prefill(model, engine, prompt, bucket)
        got_tok, got_lp, got_cache, _ = engine._prefill[bucket](
            model.params, np.pad(prompt, (0, bucket - n))[None], np.int32(n), engine._base_key, np.int32(0)
        )
        assert int(got_tok) == tok
        np.testing.assert_allclose(float(got_lp), lp, rtol=0, atol=ATOL)
        # two programs around the same layers (the reference has no sampler in it): where the compiler fused
        # a recurrent state's sums otherwise, the state and the rows of the layers above it differ in float32's
        # last digits
        _assert_same_cache(cache, got_cache, atol=1e-5)
        uid = engine.submit(prompt, max_new_tokens=3)
        engine.step()
        done = phase_log().roots("engine.tick")[-1].done
        assert done["admitted"] == 1 and done["head_rows"] == 1 and done["prefill_tokens"] == bucket
        engine.run()
        assert int(engine.partial(uid)[0]) == tok
        np.testing.assert_allclose(float(engine.logprobs(uid)[0]), lp, rtol=0, atol=ATOL)
        lowered = engine._perf_programs["prefill"].lower(bucket=bucket).as_text()
        assert f"x{VOCAB}xf32>" in lowered and f"x{bucket}x{VOCAB}xf32>" not in lowered
    assert engine.metrics.head_rows - rows_was == len(BUCKETS)


def test_a_prompt_over_the_largest_bucket_counts_its_windows_rows(served):
    """The chunk windows hand a window's logits to the host whole: ``head_rows`` counts every row of them."""
    model, engine = served
    rows_was = engine.metrics.head_rows
    if engine._aligned is not None:
        with pytest.raises(NotImplementedError, match="chunk windows"):
            engine.submit(_ids(40, start=9), max_new_tokens=2)  # EVA is served by bucketed prefill alone
        return
    engine.submit(_ids(40, start=9), max_new_tokens=2)
    engine.run()
    assert engine.metrics.head_rows - rows_was >= 40


def _created(name):
    import accelerate_tpu.models as zoo

    config = {"gpt2": zoo.GPT2Config, "gptneox": zoo.GPTNeoXConfig}[name].tiny()
    return {"gpt2": zoo.create_gpt2_model, "gptneox": zoo.create_gptneox_model}[name](config, seq_len=16)


@pytest.mark.parametrize("name", ["gpt2", "gptneox"])
def test_the_layer_norm_families_head_the_rows_asked_for_too(name):
    """``gpt2`` and ``gptneox`` end in a LayerNorm and a float32 head: the same rows picked ahead of the norm."""
    model = _created(name)
    ids = jnp.asarray(_ids(16, start=5)[None])
    whole, cache = jax.jit(lambda p, i: model.apply_fn(p, i, decode=True, cache=None))(model.params, ids)
    at = jnp.asarray([0, 15, 3], jnp.int32)
    got, got_cache = jax.jit(lambda p, i: model.apply_fn(p, i, decode=True, cache=None, logits_at=at))(model.params, ids)
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(whole)[0, [0, 15, 3]], rtol=0, atol=ATOL)
    _assert_same_cache(cache, got_cache)
    at7 = jax.jit(lambda p, i: model.apply_fn(p, i, logits_at=jnp.int32(7)))(model.params, ids)
    np.testing.assert_allclose(np.asarray(at7)[0, 0], np.asarray(whole)[0, 7], rtol=0, atol=ATOL)
    engine = ServingEngine(model, num_slots=2, prompt_buckets=(16,), max_len=48, tick_block=2)
    prompt = _ids(9)
    uid = engine.submit(prompt, max_new_tokens=3)
    engine.run()
    logits = np.asarray(model.apply_fn(model.params, jnp.asarray(prompt[None])))[0, -1]
    assert int(engine.partial(uid)[0]) == int(logits.argmax()) and engine.metrics.head_rows == 1


def test_rows_asked_for_leave_out_what_the_other_rows_hold():
    """The rows are selected, not weighted: an inf or a NaN in a row nobody asked for stays out of the sum, a
    position beyond the sequence reads its last row, and the row comes back to the bit."""
    from accelerate_tpu.models.llama import rows_at

    hidden = jax.random.normal(jax.random.key(0), (2, 8, 16), jnp.bfloat16)
    hidden = hidden.at[:, 2].set(jnp.inf).at[:, 5].set(jnp.nan)
    got = jax.jit(rows_at)(hidden, jnp.asarray([7, 0, 3, 99], jnp.int32))
    assert got.dtype == hidden.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(hidden[:, [7, 0, 3, 7]], np.float32))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(rows_at)(hidden, jnp.int32(4)), np.float32), np.asarray(hidden[:, 4:5], np.float32)
    )
