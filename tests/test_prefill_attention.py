"""Which attention a bucket's prefill runs (``models/llama.py`` ``LlamaAttention._cold_or_cached_attention``,
``ops/attention.py`` ``prefers_flash``): the call that starts a dense cache attends over its own rows in every
``LlamaAttention`` family, layers scanned or unrolled; no bucket program holds ``[.., bucket, max_len]`` float32
scores; and the automatic choice of kernel is a pure function of the call's shapes that knows a forward-only call."""

import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import serving_programs as sp
from accelerate_tpu.generation import _make_sampler
from accelerate_tpu.models.granitemoehybrid import GraniteMoeHybridConfig
from accelerate_tpu.models.jamba import JambaConfig
from accelerate_tpu.models.laguna import LagunaConfig
from accelerate_tpu.models.lfm2_moe import Lfm2MoeConfig
from accelerate_tpu.models.llama import create_llama_model
from accelerate_tpu.models.mistral import MistralConfig
from accelerate_tpu.ops import attention

TOLERANCE = 2e-5  # float32 sums in another order, on logits of size 4
BUCKET, MAX_LEN = 16, 96  # a context no toy width equals, so a shape that ends in it is a row of keys

# the serve cells' ``LlamaAttention`` families at toy widths: mistral's layers run under the layer scan (the ``cache``
# collection is carried by layer), the others unrolled beside mixers that keep a state
FAMILIES = {
    "mistral_band_scanned": lambda: MistralConfig.tiny(max_position_embeddings=MAX_LEN),
    "jamba_one_kv_head": lambda: JambaConfig.tiny(max_position_embeddings=MAX_LEN),
    "lfm2_head_64": lambda: Lfm2MoeConfig.tiny(hidden_size=256, max_position_embeddings=MAX_LEN),
    "granite_no_positions": lambda: GraniteMoeHybridConfig.tiny(max_position_embeddings=MAX_LEN),
    "laguna_two_kinds": lambda: LagunaConfig.tiny(max_position_embeddings=MAX_LEN),
}


@pytest.fixture(scope="module", params=list(FAMILIES))
def model(request):
    return create_llama_model(FAMILIES[request.param](), seed=1, seq_len=16)


def _forward(model, tokens):
    ids = np.zeros((1, MAX_LEN), np.int32)
    ids[0, : len(tokens)] = tokens
    return np.asarray(jax.jit(lambda i: model.apply_fn(model.params, i))(jnp.asarray(ids)))[0]


def test_a_cold_prefill_then_cached_decode_is_the_forward_pass(model):
    """The dense cache's two paths: the call that starts the cache attends over its own tokens
    (``_dispatch_attention``, banded under a window) and stores its rows; a step attends against the cache."""
    tokens = np.random.default_rng(0).integers(5, 250, size=40).astype(np.int32)
    full = _forward(model, tokens)[:40]
    logits, cache = model.apply_fn(model.params, jnp.asarray(tokens[None, :24]), positions=jnp.arange(24)[None], decode=True, cache=None)
    np.testing.assert_allclose(np.asarray(logits)[0], full[:24], atol=TOLERANCE)
    assert {"key", "value", "index"} <= {p[-1].key for p, _ in jax.tree_util.tree_flatten_with_path(cache)[0]}
    for t in range(24, 40):
        step, cache = model.apply_fn(model.params, jnp.asarray(tokens[None, t : t + 1]), positions=jnp.array([[t]]), decode=True, cache=cache)
        np.testing.assert_allclose(np.asarray(step)[0, 0], full[t], atol=TOLERANCE)
    assert np.abs(full).max() > 0.1


def _float32_shapes(jaxpr, seen=None):
    """The shape of every float32 value of ``jaxpr`` and of the jaxprs inside it (a scan's body, a ``pjit``)."""
    seen = set() if seen is None else seen
    for eqn in jaxpr.eqns:
        seen.update(tuple(v.aval.shape) for v in eqn.outvars if getattr(v.aval, "dtype", None) == jnp.float32)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _float32_shapes(inner, seen)
    return seen


def test_no_bucket_program_holds_scores_against_the_whole_cache(model):
    """The bucket's ``prefill`` program as the engine builds it, read as a jaxpr: no float32 value ends in
    ``[bucket, max_len]`` (the masked product against a cache that was empty a moment ago); the scores it does
    hold are ``[.., bucket, bucket]``, and the rows stored are ``[1, max_len, kv_heads, head_dim]``."""
    extra = sp.extra_arguments(model.config, sp.row_template(model.apply_fn, model.params), paged=True)
    prefill = sp.make_prefill(model.apply_fn, _make_sampler(0.0, None), extra)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    closed = jax.make_jaxpr(prefill)(
        model.params, jax.ShapeDtypeStruct((1, BUCKET), jnp.int32), i32, jax.eval_shape(lambda: jax.random.key(0)), i32)
    shapes = _float32_shapes(closed.jaxpr)
    # scores are ``[batch, heads, queries, keys]`` or, grouped, ``[batch, kv_heads, group, queries, keys]``
    assert not [s for s in shapes if len(s) >= 4 and s[-2:] == (BUCKET, MAX_LEN)]
    assert [s for s in shapes if len(s) >= 4 and s[-2:] == (BUCKET, BUCKET)], "the scores of the bucket's own rows"
    cache = jax.eval_shape(prefill, model.params, jnp.zeros((1, BUCKET), jnp.int32), 0, jax.random.key(0), 0)[2]
    keys = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0] if path[-1].key == "key"]
    assert keys and all(leaf.shape[-3] == MAX_LEN for leaf in keys)


# (Sq, Sk, heads, head_dim): the shapes the cells' buckets run
CELLS_1024 = [(1024, 1024, 32, 128), (1024, 1024, 48, 128), (1024, 1024, 64, 128), (1024, 1024, 32, 64), (1024, 1024, 20, 128),
              (1024, 1024, 32, 192)]


@pytest.mark.parametrize("shape,forward_only,flash", [
    # a call that may be differentiated answers as ``FLASH_MIN_SEQ`` did, at the shape it was measured on and at the cells'
    ((1024, 1024, 12, 64), False, False),
    ((2048, 2048, 12, 64), False, True),
    ((2047, 2047, 12, 64), False, False),
    ((4096, 4096, 64, 128), False, True),
    *[(shape, False, False) for shape in CELLS_1024],
    # a forward-only call answers as the chip read (PERF.md 6, PR 49): the kernel at 1024 and past it, XLA at 512 and under
    *[(shape, True, True) for shape in CELLS_1024],
    *[((4096, 4096, heads, dim), True, True) for _, _, heads, dim in CELLS_1024[:3]],
    *[((seq, seq, heads, dim), True, False) for seq in (64, 256, 512) for _, _, heads, dim in CELLS_1024],
])
def test_the_choice_of_kernel_is_a_pure_function_of_the_calls_shapes(shape, forward_only, flash):
    assert attention.prefers_flash(*shape, forward_only=forward_only) is flash


def test_the_automatic_choice_asks_the_rule_and_a_forward_only_call_says_so(monkeypatch):
    """``dot_product_attention`` hands the rule its own shapes and the caller's word, and takes the kernel where the
    rule says so; under a mask or a softcap the kernel is not chosen whatever the rule says."""
    asked, kernel_calls = [], []
    monkeypatch.setattr(attention, "prefers_flash", lambda *shape, forward_only: asked.append((shape, forward_only)) or forward_only)
    monkeypatch.setattr(attention, "sharded_pallas_attention", lambda q, k, v, **how: kernel_calls.append(how) or q)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 16, 4, 8))
    kv = jnp.zeros((1, 16, 2, 8))
    attention.dot_product_attention(q, kv, kv, causal=True)
    assert not kernel_calls
    attention.dot_product_attention(q, kv, kv, causal=True, window=8, forward_only=True)
    assert asked == [((16, 16, 4, 8), False), ((16, 16, 4, 8), True)]
    assert [how["window"] for how in kernel_calls] == [8]
    attention.dot_product_attention(q, kv, kv, causal=True, forward_only=True, logit_softcap=30.0)
    attention.dot_product_attention(q, kv, kv, mask=jnp.ones((1, 1, 16, 16), bool), forward_only=True)
    assert len(kernel_calls) == 1
    assert not re.search(r"environ|getenv", pathlib.Path(attention.__file__).read_text()), "no environment variable chooses the kernel"


def test_a_call_that_starts_a_cache_stays_off_the_ring_under_a_seq_mesh():
    """A cache's rows are not sharded over ``seq``: under a mesh with a ``seq`` axis the call that starts a cache
    attends as ``cached_attention`` always has, on any prompt length (seven tokens do not divide over two)."""
    from jax.sharding import Mesh

    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.parallel.sharding import mesh_context

    model = create_llama_model(LlamaConfig.tiny(), seed=0, seq_len=8)
    ids, positions = jnp.arange(1, 8)[None], jnp.arange(7)[None]
    plain, _ = model.apply_fn(model.params, ids, positions=positions, decode=True, cache=None)
    with mesh_context(Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "seq"))):
        under, cache = jax.jit(lambda p, i: model.apply_fn(p, i, positions=positions, decode=True, cache=None))(model.params, ids)
    np.testing.assert_allclose(np.asarray(under), np.asarray(plain), atol=TOLERANCE)
    index = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0] if path[-1].key == "index"]
    assert index and all(np.all(np.asarray(leaf) == 7) for leaf in index)
