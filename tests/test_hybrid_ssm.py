"""State-space (Mamba-1) layers beside attention on the llama core, at a toy size on the CPU: the scan,
its convolution and the step kernel against a loop a token; what a right pad, an overlapped window head,
a prefix snapshot and a resume do to a recurrent state (nothing); the model through ``ServingEngine``
(dense, paged with the XLA step, paged with the interpreted Pallas kernel) and ``generate()``; an idle
slot; the counts the tick carries; and what the engine cannot carry yet. The comparison with the
benchmark's plain reference is in tests/chipbench/test_chipbench_hybrid_ssm.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.jamba import JambaConfig, create_jamba_model
from accelerate_tpu.ops import paged_kv
from accelerate_tpu.ops.pallas_selective_scan import _block, ssm_state_step
from accelerate_tpu.ops.selective_scan import causal_conv1d, selective_scan, state_step
from accelerate_tpu.scheduling import SchedulerConfig
from accelerate_tpu.serving import ServingEngine

S, N, D, T, K = 5, 16, 256, 37, 4


@pytest.fixture(scope="module")
def scan_inputs():
    k = jax.random.split(jax.random.key(0), 10)
    return dict(
        h=jax.random.normal(k[0], (S, N, D)), u=jax.random.normal(k[1], (S, T, D)),
        delta=jax.nn.softplus(jax.random.normal(k[2], (S, T, D)) - 3), a=-jnp.exp(jax.random.normal(k[3], (N, D))),
        b=jax.random.normal(k[4], (S, T, N)), c=jax.random.normal(k[5], (S, T, N)), d=jax.random.normal(k[6], (D,)),
        w=jax.random.normal(k[7], (K, D)), bias=jax.random.normal(k[8], (D,)), x=jax.random.normal(k[9], (S, T, D)),
    )


def _token_loop(i, lo, hi):
    """The recurrence a token at a time over the tokens of ``[lo, hi)`` alone."""
    h, ys = i["h"], []
    for t in range(T):
        y = jnp.zeros((S, D))
        if lo <= t < hi:
            y, h = state_step(h, i["u"][:, t], i["delta"][:, t], i["b"][:, t], i["c"][:, t], i["a"], i["d"])
        ys.append(y)
    return jnp.stack(ys, 1), h


@pytest.mark.parametrize("chunk", [4, 16, 64], ids=lambda c: f"chunk{c}")
@pytest.mark.parametrize("span", [(0, T), (3, 30), (0, 0), (36, 37)], ids=["whole", "head_and_pad", "nothing_new", "last_alone"])
def test_chunked_scan_is_the_token_loop_over_the_new_tokens(scan_inputs, span, chunk):
    """Whatever the chunk (also one that does not divide the window, and one longer than it), and with
    the span traced: the same products and sums in the same order a token, so float32 rounding of the
    fused forms only: 1e-5 on values of size 1-10. Tokens outside the span leave ``h`` as it was."""
    i, (lo, hi) = scan_inputs, span
    want_y, want_h = _token_loop(i, lo, hi)
    y, h = jax.jit(selective_scan, static_argnames="chunk")(
        i["u"], i["delta"], i["a"], i["b"], i["c"], i["d"], i["h"], jnp.int32(lo), jnp.int32(hi), chunk=chunk)
    new = ((np.arange(T) >= lo) & (np.arange(T) < hi))[None, :, None]
    np.testing.assert_allclose(np.where(new, y, 0), np.where(new, want_y, 0), atol=1e-5)
    np.testing.assert_allclose(h, want_h, atol=1e-5)
    if hi == lo:
        np.testing.assert_array_equal(h, i["h"])


def _conv_reference(i):
    before = jnp.concatenate([jnp.zeros((S, K - 1, D)), i["x"]], 1)
    return sum(before[:, j : j + T] * i["w"][j] for j in range(K)) + i["bias"]


def test_convolution_over_two_windows_is_one_convolution(scan_inputs):
    """A bucket with a right pad (garbage in it), then an end-aligned window whose head overlaps seven
    tokens already counted (garbage there too): outputs of the new tokens and the carried inputs are
    those of one pass, exactly (the same products, no sum reordered)."""
    i = scan_inputs
    ref, zero = _conv_reference(i), jnp.zeros((S, (K - 1) * D))
    padded = jnp.pad(i["x"][:, :20], ((0, 0), (0, 4), (0, 0)), constant_values=7.0)
    out1, carried = causal_conv1d(padded, i["w"], i["bias"], zero, jnp.int32(0), jnp.int32(20))
    np.testing.assert_array_equal(out1[:, :20], ref[:, :20])
    np.testing.assert_array_equal(carried.reshape(S, K - 1, D), i["x"][:, 17:20])
    head = jnp.where(jnp.arange(24) < 7, 9.0, 1.0)[None, :, None]
    out2, carried2 = causal_conv1d(i["x"][:, 13:37] * head, i["w"], i["bias"], carried, jnp.int32(7), jnp.int32(24))
    np.testing.assert_array_equal(out2[:, 7:], ref[:, 20:])
    np.testing.assert_array_equal(carried2.reshape(S, K - 1, D), i["x"][:, 34:37])
    _, same = causal_conv1d(i["x"][:, :5], i["w"], i["bias"], carried, jnp.int32(2), jnp.int32(2))
    np.testing.assert_array_equal(same, carried)  # a window with nothing new shifts nothing


def test_convolution_step_is_the_windows_last_token(scan_inputs):
    i = scan_inputs
    ref = _conv_reference(i)
    _, carried = causal_conv1d(i["x"][:, :36], i["w"], i["bias"], jnp.zeros((S, (K - 1) * D)), 0, 36)
    out, after = causal_conv1d(i["x"][:, 36:], i["w"], i["bias"], carried, 0, 1)
    np.testing.assert_allclose(out[:, 0], ref[:, 36], atol=1e-6)
    np.testing.assert_array_equal(after.reshape(S, K - 1, D), i["x"][:, 34:37])


@pytest.mark.parametrize("blocks", [(8, 128), (8, 2560), (2, 256)], ids=["one_lane_tile", "default", "blocks_over_the_size"])
def test_step_kernel_is_the_plain_step(scan_inputs, blocks):
    """The kernel's contract, interpreted: ``(y, h')`` of :func:`state_step` in float32 (the same products,
    the sum over the state in another order: 1e-5), ``h`` aliased to ``h'``, under the device name the
    benchmark's reader finds it by, whatever blocks the sizes allow."""
    i = scan_inputs
    args = (i["h"], i["u"][:, 0].astype(jnp.bfloat16), i["delta"][:, 0], i["b"][:, 0], i["c"][:, 0], i["a"], i["d"])
    want_y, want_h = state_step(*args)
    y, h = ssm_state_step(*args, slot_block=blocks[0], lane_block=blocks[1], interpret=True)
    assert y.dtype == h.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, atol=1e-5)
    np.testing.assert_allclose(h, want_h, atol=1e-6)
    text = str(jax.make_jaxpr(lambda *a: ssm_state_step(*a, interpret=True))(*args))
    assert "name=ssm_state_step" in text and "input_output_aliases=((0, 1),)" in text


def _step_inputs(slots, n, d, seed=1):
    k = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(k[0], (slots, n, d)), jax.random.normal(k[1], (slots, d)),
            jax.nn.softplus(jax.random.normal(k[2], (slots, d)) - 3), jax.random.normal(k[3], (slots, n)),
            jax.random.normal(k[4], (slots, n)), -jnp.exp(jax.random.normal(k[5], (n, d))), jax.random.normal(k[6], (d,)))


def _mask(kind, slots):
    if kind == "random_70":
        return np.isin(np.arange(slots), np.random.default_rng(5).choice(slots, 70, replace=False))
    return {"all_live": np.ones(slots, bool), "none_live": np.zeros(slots, bool), "one_live": np.arange(slots) == 37 % slots,
            "every_block_mixed": (np.arange(slots) * 5 // 3) % 2 == 0}[kind]


@pytest.mark.parametrize(
    "kind,slots,d,lane_block",
    [("all_live", 128, 256, 128), ("none_live", 128, 256, 128), ("one_live", 128, 256, 128), ("random_70", 128, 256, 128),
     ("every_block_mixed", 128, 256, 128), ("every_block_mixed", 12, 256, 128), ("one_live", 5, 256, 2560),
     ("every_block_mixed", 16, 384, 2560), ("every_block_mixed", 16, 384, 256)],
    ids=["all_live", "none_live", "one_live", "random_70_of_128", "every_block_mixed", "slots_no_tile_divides",
         "five_slots", "d_inner_under_the_lane_block", "d_inner_no_multiple_of_the_lane_block"],
)
def test_step_kernel_visits_the_live_slots_alone(kind, slots, d, lane_block):
    """With ``row_valid`` the slots it names get the plain step's ``(y, h')`` to the unmasked kernel's
    tolerance; every other slot's ``h'`` is bitwise its ``h`` (never fetched, never written) and its ``y``
    is zeros, whichever blocks the live slots fall in and whatever blocks the sizes allow."""
    args = _step_inputs(slots, 16, d)
    live = _mask(kind, slots)
    if kind == "every_block_mixed" and slots % 8 == 0:
        assert all(0 < live[i : i + 8].sum() < 8 for i in range(0, slots, 8))
    want_y, want_h = map(np.asarray, state_step(*args))
    y, h = map(np.asarray, ssm_state_step(*args, jnp.asarray(live), lane_block=lane_block, interpret=True))
    np.testing.assert_allclose(y[live], want_y[live], atol=1e-5)
    np.testing.assert_allclose(h[live], want_h[live], atol=1e-6)
    np.testing.assert_array_equal(h[~live], np.asarray(args[0])[~live])
    assert not y[~live].any()


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def test_step_kernel_without_a_mask_is_the_program_it_was():
    """``row_valid=None`` and an all-true mask give the same numbers. The ``None`` call keeps its grid and
    its blocks at the benchmark's size (``h`` comes 8 slots x 2560 lanes a grid step through Pallas's own
    pipeline, aliased to ``h'``); the masked call walks the slot blocks with every lane in one step,
    ``h`` whole in HBM behind one scalar-prefetched mask, under the same device name."""
    args = _step_inputs(16, 16, 256)
    plain, masked = ssm_state_step(*args, interpret=True), ssm_state_step(*args, jnp.ones(16, bool), interpret=True)
    for a, b in zip(plain, masked):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    real = [jax.ShapeDtypeStruct(s, jnp.float32) for s in ((128, 16, 5120), (128, 5120), (128, 5120), (128, 16), (128, 16), (16, 5120), (5120,))]
    seen = {}
    for name, extra in (("plain", ()), ("masked", (jax.ShapeDtypeStruct((128,), jnp.bool_),))):
        (call,) = _pallas_calls(jax.make_jaxpr(ssm_state_step)(*real, *extra).jaxpr)
        gm = call.params["grid_mapping"]
        blocks = [tuple(getattr(b, "block_size", b) for b in bm.block_shape) for bm in gm.block_mappings]
        assert "name=ssm_state_step" in str(call)
        seen[name] = (gm.grid, blocks, call.params["input_output_aliases"], gm.num_index_operands)
    rows = [(8, 2560), (8, 2560), (8, 16, 1), (8, 16, 1), (16, 2560), (1, 2560), (8, 2560)]
    assert seen["plain"] == ((2, 16), [(8, 16, 2560), *rows, (8, 16, 2560)], ((0, 1),), 0)
    whole = [tuple(5120 if v == 2560 else v for v in r) for r in rows]  # a slot's state is one contiguous copy
    assert seen["masked"] == ((1, 16), [(128, 16, 5120), *whole, (128, 16, 5120)], ((1, 1),), 1)


def test_step_kernel_blocks_divide_the_sizes():
    assert (_block(128, 8, 8), _block(5120, 2560, 128), _block(5120, 3000, 128)) == (8, 2560, 2560)
    assert (_block(5, 8, 8), _block(12, 8, 8), _block(256, 2560, 128)) == (5, 12, 256)  # no tile divides: whole


# -- the model

@pytest.fixture(scope="module")
def model():
    return create_jamba_model(JambaConfig.tiny(), seed=3, seq_len=16)


def _ids(n, mul=7, start=1):
    return ((np.arange(start, start + n, dtype=np.int32)) * mul) % 250 + 1


def _state(cache):
    return {jax.tree_util.keystr(p): np.asarray(l) for p, l in jax.tree_util.tree_flatten_with_path(cache)[0]
            if str(p[-1].key) in paged_kv.STATE_LEAVES}


def test_layers_follow_the_attention_period(model):
    cfg = model.config
    assert [cfg.mixer_kind(i) for i in range(4)] == ["mamba", "attention", "mamba", "attention"]
    assert "mamba" in model.params["layer_0"] and "attn" in model.params["layer_1"] and "attn" not in model.params["layer_2"]
    assert model.params["layer_0"]["mamba"]["A_log"].shape == (8, 128), "d_inner along the lanes"
    assert "lm_head" not in model.params, "the head is the embedding"
    published = JambaConfig()
    assert [i for i in range(28) if published.mixer_kind(i) == "attention"] == [7, 21] and published.rope_theta is None


def test_attention_has_no_position_encoding(model):
    """The family publishes no rotary key and the core applies none: the attention layers see positions
    only through what the state-space layers put into the stream, so ``positions`` changes nothing."""
    ids = jnp.asarray(_ids(12)[None])
    a = model.apply_fn(model.params, ids, positions=jnp.arange(12)[None])
    b = model.apply_fn(model.params, ids, positions=jnp.arange(100, 112)[None])
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "make, names",
    [
        (lambda: create_jamba_model(JambaConfig.tiny(scan_layers=True), seed=3, seq_len=16), "scan_layers=False"),
        (lambda: JambaConfig.tiny(num_experts=16), "num_experts=16"),
        (lambda: create_jamba_model(JambaConfig.tiny(kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=16,
                                                     v_head_dim=16), seed=3, seq_len=16), "kv_lora_rank"),
    ],
    ids=["scanned", "experts_in_a_hybrid_layer", "latent_attention_beside_mamba"],
)
def test_what_no_configuration_runs_is_refused_by_name(make, names):
    with pytest.raises(NotImplementedError, match=names):
        make()


def test_prefill_then_steps_is_the_forward_without_a_cache(model):
    """``decode=False`` runs the scan from a zero state; a cold prefill and one-token steps over the dense
    cache give its logits: the same recurrence cut at another token, float32: 2e-5 on logits of size 1."""
    ids = _ids(24)[None]
    full = np.asarray(model.apply_fn(model.params, jnp.asarray(ids)))
    logits, cache = model.apply_fn(model.params, jnp.asarray(ids[:, :10]), positions=jnp.arange(10)[None], decode=True, cache=None)
    np.testing.assert_allclose(np.asarray(logits), full[:, :10], atol=2e-5)
    state = _state(cache)
    assert len(state) == 4 and {v.shape for v in state.values()} == {(1, 8, 128), (1, 3 * 128)}
    for t in range(10, 24):
        logits, cache = model.apply_fn(model.params, jnp.asarray(ids[:, t:t + 1]), positions=jnp.full((1, 1), t), decode=True, cache=cache)
        np.testing.assert_allclose(np.asarray(logits[:, 0]), full[:, t], atol=2e-5)


def test_right_padded_bucket_leaves_the_state_of_the_unpadded_prompt(model):
    """Ten tokens in a bucket of sixteen, the window told that ten are new: the recurrent state after it
    is the unpadded prompt's (the pad's ``delta`` is 0 and the convolution does not shift), the logits of
    the ten are the same, and without the span the state differs: that is what the argument is for."""
    ids = _ids(10)[None]
    padded = np.full((1, 16), 99, np.int32)
    padded[0, :10] = ids
    pos = jnp.arange(16)[None]
    want_logits, want = model.apply_fn(model.params, jnp.asarray(ids), positions=pos[:, :10], decode=True, cache=None)
    logits, got = model.apply_fn(model.params, jnp.asarray(padded), positions=pos, decode=True, cache=None,
                                 new_span=(jnp.int32(0), jnp.int32(10)))
    for (name, a), b in zip(_state(want).items(), _state(got).values()):
        np.testing.assert_allclose(b, a, atol=2e-6, err_msg=name)
    np.testing.assert_allclose(np.asarray(logits[:, :10]), np.asarray(want_logits), atol=2e-5)
    _, counted = model.apply_fn(model.params, jnp.asarray(padded), positions=pos, decode=True, cache=None)
    assert max(np.abs(a - b).max() for a, b in zip(_state(want).values(), _state(counted).values())) > 1e-3


def test_overlapped_window_head_counts_once_and_keeps_its_rows(model):
    """An end-aligned warm window ``[12, 28)`` over a cache that holds ``[0, 16)``: its first four tokens
    are an overlapped head. The state steps over the twelve new tokens alone, and the attention layers
    keep the head's K/V rows as the cache has them (the head's hidden states came through state-space
    layers that did not advance): cache and logits are those of one prefill of 28."""
    ids = _ids(28, mul=3)[None]
    want_logits, want = model.apply_fn(model.params, jnp.asarray(ids), positions=jnp.arange(28)[None], decode=True, cache=None)
    _, cache = model.apply_fn(model.params, jnp.asarray(ids[:, :16]), positions=jnp.arange(16)[None], decode=True, cache=None)
    from accelerate_tpu.ops.kv_cache import reset_cache_index

    logits, got = model.apply_fn(
        model.params, jnp.asarray(ids[:, 12:]), positions=jnp.arange(12, 28)[None], decode=True,
        cache=reset_cache_index(cache, 12), new_span=(jnp.int32(4), jnp.int32(16)))
    np.testing.assert_allclose(np.asarray(logits[:, 4:]), np.asarray(want_logits[:, 16:]), atol=2e-5)
    for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(want)[0], jax.tree_util.tree_flatten_with_path(got)[0]):
        a, b = np.asarray(a), np.asarray(b)
        if str(path[-1].key) in ("key", "value"):
            a, b = a[:, :28], b[:, :28]
        np.testing.assert_allclose(b, a, atol=2e-5, err_msg=jax.tree_util.keystr(path))


def _greedy_gap(model, prompt, out):
    """How far the served tokens' logits lie under the plain forward's best (0 where every one is the argmax)."""
    out = np.asarray(out)
    served = out[len(prompt):]
    ref = np.asarray(model.apply_fn(model.params, jnp.asarray(out[None])))[0, len(prompt) - 1:-1]
    return float((ref.max(-1) - ref[np.arange(len(served)), served]).max())


PROMPTS = [_ids(5), _ids(14, start=3), _ids(3, start=7), _ids(28, mul=3, start=2)]


@pytest.mark.parametrize("layout", ["dense", "paged_xla_step", "paged_kernel_interpreted"])
def test_engine_serves_the_hybrid_cache(model, layout, monkeypatch):
    """Bucketed prefill (right pads), a prompt over the largest bucket (a cold window and an end-aligned
    warm one), paste or insert, decode ticks with more slots than requests, retirement: every served
    token is the plain forward's greedy token, its logit within 1e-4 of the forward's best (float32; a
    state that counted a pad or a head would show there)."""
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", layout == "paged_kernel_interpreted")
    paged = {} if layout == "dense" else {"paged_block_size": 8}
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(8, 16), max_len=64, tick_block=4, **paged)
    if paged:
        names = {str(p[-1].key) for p, _ in jax.tree_util.tree_flatten_with_path(engine.slot_caches)[0]}
        assert names == {"key_pool", "value_pool", "block_table", "index", "ssm_state", "conv_state"}
        shapes = {l.shape for p, l in jax.tree_util.tree_flatten_with_path(engine.slot_caches)[0] if str(p[-1].key) == "ssm_state"}
        assert shapes == {(3, 8, 128)}, "one row a slot, no pages"
    for prompt, out in zip(PROMPTS, engine.generate_many(PROMPTS, max_new_tokens=9)):
        assert len(out) == len(prompt) + 9 and _greedy_gap(model, prompt, out) < 1e-4
    m = engine.metrics
    assert m.state_bytes_per_slot == 2 * (8 * 128 * 4 + 3 * 128 * 4)  # two state-space layers, float32 toy
    if layout == "paged_kernel_interpreted":
        assert m.state_slots_idle == 0, "the kernel is told which slots decode and steps no other"
    else:
        assert 0 < m.state_slots_idle <= engine._tick * engine.tick_block * 3


def test_generate_equals_the_engine(model):
    from accelerate_tpu.generation import generate

    prompt = _ids(8, mul=3)
    out = np.asarray(generate(model, jnp.asarray(prompt[None]), max_new_tokens=6))[0]
    engine = ServingEngine(model, num_slots=2, prompt_buckets=(8,), max_len=32, paged_block_size=8)
    np.testing.assert_array_equal(out, engine.generate_many([prompt], max_new_tokens=6)[0])
    assert _greedy_gap(model, prompt, out) < 1e-4


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_registered_prefix_snapshots_the_state(model, paged):
    """The stored row cache holds the recurrent state at the prefix's end; two requests copy it (paged:
    the prefix's full blocks are aliased, the state is each request's own) and continue token-exact."""
    prefix, suffixes = _ids(19, mul=5), [_ids(4, start=40), _ids(11, start=60)]
    kw = {"paged_block_size": 8} if paged else {}
    engine = ServingEngine(model, num_slots=2, prompt_buckets=(8, 16), max_len=64, tick_block=4, **kw)
    pid = engine.register_prefix(prefix)
    assert all(np.abs(v).max() > 0 for v in _state(engine._prefixes[pid]["cache"]).values())
    uids = [engine.submit(s, max_new_tokens=7, prefix_id=pid) for s in suffixes]
    engine.run()
    for uid, s in zip(uids, suffixes):
        assert _greedy_gap(model, np.concatenate([prefix, s]), engine.poll(uid)) < 1e-4


def test_preempted_request_resumes_token_exact(model):
    """Evicted mid-decode, requeued, resumed by chunk windows over prompt and generated tokens (a cold
    window, then warm ones with overlapped heads): the whole output is the unpreempted one."""
    victim_prompt, urgent_prompt = _ids(13), _ids(5, start=30)
    engine = ServingEngine(model, num_slots=1, prompt_buckets=(8,), max_len=64, tick_block=2, paged_block_size=8,
                           scheduler=SchedulerConfig(enable_preemption=True))
    victim = engine.submit(victim_prompt, max_new_tokens=12, priority=1)
    engine.step()
    engine.step()
    urgent = engine.submit(urgent_prompt, max_new_tokens=4, priority=0)
    engine.run()
    assert engine.metrics.decode_preemptions == 1 and engine.metrics.resumes == 1
    assert _greedy_gap(model, victim_prompt, engine.poll(victim)) < 1e-4
    assert _greedy_gap(model, urgent_prompt, engine.poll(urgent)) < 1e-4


@pytest.mark.parametrize("step", ["xla_step", "kernel_interpreted"])
def test_idle_slot_between_two_live_ones_is_finite_and_never_read(model, step, monkeypatch):
    """Slot 1 finishes early and idles between slots 0 and 2. The plain step goes on stepping its state
    (zeroed by ``clear_slot``, then token 0 from there), which stays finite; the kernel is told that nobody
    decodes there and leaves its ``ssm_state`` bit for bit what ``clear_slot`` made it, tick after tick,
    while the convolution's carried inputs (no kernel) move on. Either way the neighbours' outputs are exact,
    and a later request admitted to the slot starts from its pasted state, whatever lay there."""
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", step == "kernel_interpreted")
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(8, 16), max_len=64, tick_block=4, paged_block_size=8)
    prompts = [_ids(6), _ids(4, start=9), _ids(12, start=20)]
    uids = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, (24, 2, 24))]
    for _ in range(3):
        engine.step()
    assert engine.slot_req[1] is None and engine.slot_req[0] is not None and engine.slot_req[2] is not None
    state = _state(engine.slot_caches)
    assert all(np.isfinite(v).all() for v in state.values())
    engine.step()
    after = _state(engine.slot_caches)
    for name, v in after.items():
        assert np.abs(v[0] - state[name][0]).max() > 0, "a decoding slot's state moves"
        if step == "kernel_interpreted" and "ssm_state" in name:
            assert not v[1].any(), "the idle slot's state is what clear_slot left, never written"
        else:
            assert np.abs(v[1]).max() > 0, "the idle slot's state is stepped on"
    stale = jax.tree_util.tree_map_with_path(
        lambda p, l: l.at[1].set(7.0) if str(p[-1].key) == "ssm_state" else l, engine.slot_caches)
    engine.slot_caches = stale  # whatever an earlier tenant left: the next paste replaces it whole
    late = engine.submit(_ids(7, start=50), max_new_tokens=8)
    engine.step()
    assert engine.slot_req[1] is not None and engine.slot_req[1].uid == late
    engine.run()
    for uid, p in zip(uids + [late], prompts + [_ids(7, start=50)]):
        assert _greedy_gap(model, p, engine.poll(uid)) < 1e-4
    assert all(np.isfinite(v).all() for v in _state(engine.slot_caches).values())


def test_masked_tick_emits_the_unmasked_ticks_tokens_and_logprobs(model, monkeypatch):
    """The same requests on the same seed through the tick that takes the ``[slots]`` bool and through the
    parent's (five arguments, the kernel steps every slot): request for request the same tokens and the
    same logprobs, with slots that idle from the start, idle after a retirement and are admitted again."""
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", True)
    prompts = [_ids(6), _ids(4, start=9), _ids(12, start=20), _ids(9, start=33), _ids(5, start=41)]
    news = (17, 3, 11, 6, 9)

    def serve(masked):
        engine = ServingEngine(model, num_slots=4, prompt_buckets=(8, 16), max_len=64, tick_block=4, paged_block_size=8,
                               temperature=0.8, top_k=20, seed=11)
        assert engine._mask_idle_rows and not engine._steps_idle_state
        engine._mask_idle_rows = masked  # read when the tick's arguments are built: False is the parent's call
        uids = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts[:3], news)]
        for _ in range(3):
            engine.step()
        uids += [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts[3:], news[3:])]
        engine.run()
        assert len(engine._perf_programs["decode_tick"][1](None)) == (6 if masked else 5)
        return [(np.asarray(engine.poll(u)), engine.logprobs(u)) for u in uids]

    for (toks, lps), (want_toks, want_lps) in zip(serve(True), serve(False)):
        np.testing.assert_array_equal(toks, want_toks)
        np.testing.assert_allclose(lps, want_lps, atol=1e-6)


def test_clear_slot_zeroes_the_state_and_paste_blocks_passes_it(model):
    engine = ServingEngine(model, num_slots=2, prompt_buckets=(8,), max_len=32, paged_block_size=8)
    ones = jax.tree_util.tree_map_with_path(
        lambda p, l: jnp.ones_like(l) if str(p[-1].key) in paged_kv.STATE_LEAVES else l, engine.slot_caches)
    cleared = _state(paged_kv.clear_slot(ones, jnp.int32(1)))
    assert all((v[1] == 0).all() and (v[0] == 1).all() for v in cleared.values())
    _, row = model.apply_fn(model.params, jnp.asarray(_ids(8)[None]), positions=jnp.arange(8)[None], decode=True, cache=None)
    write_row = jnp.zeros((engine._mb,), jnp.int32).at[0].set(1)
    passed = _state(paged_kv.paste_blocks(ones, row, write_row))
    assert all((v == 1).all() for v in passed.values())
    pasted = _state(paged_kv.paste_row(ones, row, write_row, write_row, jnp.int32(1), jnp.int32(8)))
    for name, v in _state(row).items():
        np.testing.assert_array_equal(pasted[name][1], v[0])
        assert (pasted[name][0] == 1).all()


@pytest.mark.parametrize("kind", ["state_space_xla_step", "state_space_kernel", "convolution_kernel", "no_state"])
def test_tick_done_carries_the_idle_state_steps(model, kind, monkeypatch):
    """``state_slots_idle``: slot-steps of recurrent state the tick stepped for slots in which no request
    decodes. The plain step steps every slot's state: (slots - decoding) x steps. The kernel is told which
    slots decode: 0. A convolution's carried inputs have no kernel and move in every slot, mask or none: as
    before. 0 for a model without recurrent state (tests/test_serving.py's models never set it)."""
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", kind.endswith("kernel"))
    if kind == "convolution_kernel":
        from accelerate_tpu.models.lfm2_moe import Lfm2MoeConfig, create_lfm2_moe_model

        model = create_lfm2_moe_model(Lfm2MoeConfig.tiny(), seed=3, seq_len=16)
    elif kind == "no_state":
        from accelerate_tpu.models.llama import LlamaConfig, create_llama_model

        model = create_llama_model(LlamaConfig.tiny(), seed=0, seq_len=8)
    engine = ServingEngine(model, num_slots=4, prompt_buckets=(8,), max_len=32, paged_block_size=8, tick_block=2)
    engine.submit(_ids(5), max_new_tokens=6)
    seen = []
    while engine.queue or engine.active_count:
        engine.step()
        seen.append(engine._tick_state_idle)
    want = {"state_space_xla_step": 3 * 2, "state_space_kernel": 0, "convolution_kernel": 3 * 2, "no_state": 0}[kind]
    assert set(seen) == {want} and engine.metrics.state_slots_idle == sum(seen)
    assert engine._has_state == (kind != "no_state") and (engine.metrics.state_bytes_per_slot > 0) == engine._has_state
    assert engine._mask_idle_rows == (kind != "no_state") and len(engine._decoding_arg()) == int(kind != "no_state")


def test_hand_off_and_export_refuse_a_recurrent_state_by_name(model):
    from accelerate_tpu.serving_fleet import HandoffCodec

    engine = ServingEngine(model, num_slots=2, prompt_buckets=(8,), max_len=64)
    for refuse in (engine.kv_handoff_dims, lambda: engine.prefill_detached(_ids(5), 4), lambda: HandoffCodec.decode(b"", engine)):
        with pytest.raises(NotImplementedError, match="ssm_state"):
            refuse()
    engine.submit(_ids(5), max_new_tokens=20)
    engine.step()
    with pytest.raises(NotImplementedError, match=r"export_inflight\(include_kv=True\).*ssm_state"):
        engine.export_inflight(include_kv=True)
    snaps = engine.export_inflight(include_kv=False)  # a failover resumes by recompute, which is exact
    assert len(snaps) == 1 and "cache" not in snaps[0]


def test_speculative_decoding_refuses_a_recurrent_state_by_name(model):
    """A rejected draft is taken back by resetting the frontier, which a recurrent state cannot follow."""
    from accelerate_tpu.speculative import speculative_generate

    with pytest.raises(NotImplementedError, match="ssm_state"):
        speculative_generate(model, model, jnp.asarray(_ids(6)[None]), max_new_tokens=4, gamma=2)
