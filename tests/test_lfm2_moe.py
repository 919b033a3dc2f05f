"""Gated short convolutions beside attention, with routed experts in every layer past the leading dense
ones (LFM2-8B-A1B's ``lfm2_moe``), on the llama core at a toy size on the CPU: which mixer a layer builds,
the convolution against three shifted products, prefill then steps against the forward without a cache,
what a right pad, an overlapped window head, a prefix snapshot and a resume do to ``conv_state``
(nothing), the model through ``ServingEngine`` (the paged layout with the XLA step and with the
interpreted kernels, the K/V pool folded two heads to a row where a head is 64 wide) and ``generate()``
over the dense cache, an idle slot, the counts the tick carries, what the engine cannot carry yet, and
that routing at the published router width spreads its tokens. The comparison with the benchmark's
plain reference is in tests/chipbench/test_chipbench_lfm2_moe.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.lfm2_moe import LFM2_8B_A1B_LAYER_TYPES, Lfm2MoeConfig, create_lfm2_moe_model
from accelerate_tpu.models.llama import LlamaConfig, create_llama_model
from accelerate_tpu.ops import paged_kv
from accelerate_tpu.scheduling import SchedulerConfig
from accelerate_tpu.serving import ServingEngine


@pytest.fixture(scope="module")
def model():
    return create_lfm2_moe_model(Lfm2MoeConfig.tiny(), seed=3, seq_len=16)


@pytest.fixture(scope="module")
def head64():
    """Two key/value heads of 64: the pool's rows are folded, two heads to 128 lanes."""
    return create_lfm2_moe_model(Lfm2MoeConfig.tiny(hidden_size=256, num_attention_heads=4, num_key_value_heads=2), seed=5, seq_len=16)


def _ids(n, mul=7, start=1):
    return ((np.arange(start, start + n, dtype=np.int32)) * mul) % 250 + 1


def _state(cache):
    return {jax.tree_util.keystr(p): np.asarray(l) for p, l in jax.tree_util.tree_flatten_with_path(cache)[0]
            if str(p[-1].key) in paged_kv.STATE_LEAVES}


def _leaf_names(cache):
    return {str(p[-1].key) for p, _ in jax.tree_util.tree_flatten_with_path(cache)[0]}


def test_layers_follow_layer_types_and_the_leading_dense_count(model):
    cfg = model.config
    assert [cfg.mixer_kind(i) for i in range(6)] == ["conv", "conv", "attention", "conv", "attention", "conv"] and cfg.stateful
    for i in range(6):
        layer = model.params[f"layer_{i}"]
        assert ("conv" in layer) == (cfg.mixer_kind(i) == "conv") and ("attn" in layer) == (cfg.mixer_kind(i) == "attention")
        assert ("router/kernel" in layer["mlp"]) == (i >= 2), "two leading dense layers, experts in every later one"
    conv, attn = model.params["layer_0"]["conv"], model.params["layer_2"]["attn"]
    assert conv["in_proj"]["kernel"].shape == (64, 192) and conv["conv_kernel"].shape == (3, 64) and set(conv) == {"in_proj", "conv_kernel", "out_proj"}
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape == (16,), "an RMSNorm over each head of q and of k"
    assert model.params["layer_3"]["mlp"]["experts/gate_proj"].shape == (8, 64, 32) and "shared_experts" not in model.params["layer_3"]["mlp"]
    assert "lm_head" not in model.params, "the head is the embedding"


def test_published_config_is_the_catalogs():
    cfg = Lfm2MoeConfig()
    assert [i for i in range(24) if cfg.mixer_kind(i) == "attention"] == [2, 6, 10, 14, 18, 21] and len(LFM2_8B_A1B_LAYER_TYPES) == 24
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.intermediate_size) == (2048, 32, 8, 7168)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size, cfg.first_k_dense_replace) == (32, 4, 1792, 2)
    assert (cfg.n_shared_experts, cfg.rms_norm_eps, cfg.rope_theta, cfg.conv_L_cache, cfg.conv_bias) == (0, 1e-5, 1e6, 3, False)
    assert cfg.qk_norm and cfg.tie_word_embeddings and cfg.norm_topk_prob and cfg.routed_scaling_factor == 1.0 and cfg.vocab_size == 65536
    import dataclasses

    again = dataclasses.replace(cfg, sliding_window=None)  # the core copies a layer's config: the published names carry over again
    assert again.n_routed_experts == 32 and again.first_k_dense_replace == 2 and again.layer_types == cfg.layer_types


def test_conv_in_layer_types_is_no_attention_layer_for_any_family():
    """``layer_types`` also names sliding and full attention (Gemma2): ``"conv"`` is read by the mixer's
    one function and never builds an attention layer, under a plain ``LlamaConfig`` too."""
    cfg = LlamaConfig.tiny(num_hidden_layers=3, layer_types=("conv", "sliding_attention", "full_attention"), sliding_window=4,
                           scan_layers=False)
    assert [cfg.mixer_kind(i) for i in range(3)] == ["conv", "attention", "attention"] and cfg.stateful
    params = create_llama_model(cfg, seed=0, seq_len=8).params
    assert "conv" in params["layer_0"] and "attn" not in params["layer_0"] and "attn" in params["layer_1"]
    assert not LlamaConfig.tiny(layer_types=("sliding_attention", "full_attention"), scan_layers=False).stateful


def test_gated_short_convolution_is_three_shifted_products(model):
    """The mixer alone against the published equations written out: ``[B, C, x] = split3(in_proj(u))``,
    ``y = C * sum_j w_j (B * x)[t - 2 + j]``, ``out_proj(y)``; from zeros before the sequence."""
    from accelerate_tpu.models.llama import ShortConvMixer

    p = model.params["layer_1"]["conv"]
    u = jax.random.normal(jax.random.key(1), (2, 11, 64))
    got = ShortConvMixer(model.config).apply({"params": p}, u)
    bcx = u @ p["in_proj"]["kernel"]
    b, c, x = bcx[..., :64], bcx[..., 64:128], bcx[..., 128:]
    bx = jnp.pad(b * x, ((0, 0), (2, 0), (0, 0)))
    want = (c * sum(bx[:, j : j + 11] * p["conv_kernel"][j] for j in range(3))) @ p["out_proj"]["kernel"]
    assert float(jnp.abs(want).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize(
    "make, names",
    [
        (lambda: create_lfm2_moe_model(Lfm2MoeConfig.tiny(scan_layers=True), seed=3, seq_len=16), "scan_layers=False"),
        (lambda: create_lfm2_moe_model(Lfm2MoeConfig.tiny(kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=16,
                                                          v_head_dim=16), seed=3, seq_len=16), "kv_lora_rank"),
        (lambda: create_lfm2_moe_model(Lfm2MoeConfig.tiny(scoring_func="softmax"), seed=3, seq_len=16), "scoring_func"),
    ],
    ids=["scanned", "latent_attention_beside_a_convolution", "softmax_scores"],
)
def test_what_no_configuration_runs_is_refused_by_name(make, names):
    with pytest.raises(NotImplementedError, match=names):
        make()


def test_prefill_then_steps_is_the_forward_without_a_cache(model):
    """``decode=False`` runs the convolution from zeros; a cold prefill and one-token steps over the dense
    cache give its logits: the same three products cut at another token, float32: 2e-5 on logits of size 1.
    The cache's only state leaf is ``conv_state``, two rows of ``B * x`` a convolution layer, flat."""
    ids = _ids(24)[None]
    full = np.asarray(model.apply_fn(model.params, jnp.asarray(ids)))
    logits, cache = model.apply_fn(model.params, jnp.asarray(ids[:, :10]), positions=jnp.arange(10)[None], decode=True, cache=None)
    np.testing.assert_allclose(np.asarray(logits), full[:, :10], atol=2e-5)
    state = _state(cache)
    assert len(state) == 4 and {v.shape for v in state.values()} == {(1, 2 * 64)} and all("conv_state" in k for k in state)
    assert _leaf_names(cache) == {"conv_state", "key", "value", "index"}
    for t in range(10, 24):
        logits, cache = model.apply_fn(model.params, jnp.asarray(ids[:, t:t + 1]), positions=jnp.full((1, 1), t), decode=True, cache=cache)
        np.testing.assert_allclose(np.asarray(logits[:, 0]), full[:, t], atol=2e-5)


def test_conv_state_is_the_last_two_gated_inputs(model):
    """What the state holds: the rows of ``B * x`` of the last two tokens, oldest first."""
    ids = _ids(9)[None]
    _, cache = model.apply_fn(model.params, jnp.asarray(ids), positions=jnp.arange(9)[None], decode=True, cache=None)
    p = model.params["layer_0"]
    from accelerate_tpu.models.llama import RMSNorm

    h = model.params["embed_tokens"]["embedding"][ids[0]]
    normed = RMSNorm(model.config.rms_norm_eps).apply({"params": p["input_norm"]}, h)
    bcx = normed @ p["conv"]["in_proj"]["kernel"]
    want = (bcx[:, :64] * bcx[:, 128:])[-2:].reshape(-1)
    np.testing.assert_allclose(np.asarray(cache["layer_0"]["conv"]["conv_state"][0]), np.asarray(want), atol=1e-6)


def test_right_padded_bucket_leaves_the_state_of_the_unpadded_prompt(model):
    """Ten tokens in a bucket of sixteen, the window told that ten are new: ``conv_state`` after it is the
    unpadded prompt's, the logits of the ten are the same, and without the span the state differs."""
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
    """An end-aligned warm window ``[12, 28)`` over a cache that holds ``[0, 16)``: its first four tokens are
    an overlapped head. The convolution carries on from the state over the twelve new tokens alone, and the
    attention layers keep the head's K/V rows as the cache has them: cache and logits are those of one
    prefill of 28."""
    from accelerate_tpu.ops.kv_cache import reset_cache_index

    ids = _ids(28, mul=3)[None]
    want_logits, want = model.apply_fn(model.params, jnp.asarray(ids), positions=jnp.arange(28)[None], decode=True, cache=None)
    _, cache = model.apply_fn(model.params, jnp.asarray(ids[:, :16]), positions=jnp.arange(16)[None], decode=True, cache=None)
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


@pytest.mark.parametrize("widths", ["head16", "head64_folded_pool"])
@pytest.mark.parametrize("layout", ["paged_xla_step", "paged_kernels_interpreted"])
def test_engine_serves_the_convolutions_state_beside_paged_kv(model, head64, layout, widths, monkeypatch):
    """Bucketed prefill (right pads), a prompt over the largest bucket (a cold window and an end-aligned
    warm one), the paste of rows and state, decode ticks with more slots than requests, retirement: every
    served token is the plain forward's greedy token, its logit within 1e-4 of the forward's best. With
    heads of 64 the pool's rows hold two heads side by side (``pool_lane_fold``). Interpreted: the paged
    attention kernel and the experts' grouped kernel, as the chip composes them."""
    m = model if widths == "head16" else head64
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", layout == "paged_kernels_interpreted")
    engine = ServingEngine(m, num_slots=3, prompt_buckets=(8, 16), max_len=64, tick_block=4, paged_block_size=8)
    assert _leaf_names(engine.slot_caches) == {"key_pool", "value_pool", "block_table", "index", "conv_state"}
    shapes = {str(p[-1].key): l.shape for p, l in jax.tree_util.tree_flatten_with_path(engine.slot_caches)[0]}
    hidden = m.config.hidden_size
    assert shapes["conv_state"] == (3, 2 * hidden), "one row a slot, no pages"
    assert shapes["key_pool"][1:] == ((8, 2, 16) if widths == "head16" else (8, 1, 128))
    for prompt, out in zip(PROMPTS, engine.generate_many(PROMPTS, max_new_tokens=9)):
        assert len(out) == len(prompt) + 9 and _greedy_gap(m, prompt, out) < 1e-4
    metrics = engine.metrics
    assert metrics.state_bytes_per_slot == 4 * 2 * hidden * 4  # four convolution layers, two rows, float32 toy
    assert 0 < metrics.state_slots_idle <= engine._tick * engine.tick_block * 3
    assert metrics.experts_touched > 0 and metrics.expert_tile_visits >= metrics.experts_touched


def test_generate_runs_over_the_dense_cache(model):
    """The dense layout, a cache row a sequence: ``generate()`` is the paged engine's tokens. (The engine's
    own dense tick vmaps one slot's step, which the experts' grouped products have no batching rule for.)"""
    from accelerate_tpu.generation import generate

    prompt = _ids(8, mul=3)
    out = np.asarray(generate(model, jnp.asarray(prompt[None]), max_new_tokens=6))[0]
    engine = ServingEngine(model, num_slots=2, prompt_buckets=(8,), max_len=32, paged_block_size=8)
    np.testing.assert_array_equal(out, engine.generate_many([prompt], max_new_tokens=6)[0])
    assert _greedy_gap(model, prompt, out) < 1e-4


def test_registered_prefix_snapshots_the_state(model):
    """The stored row cache holds ``conv_state`` at the prefix's end; two requests copy it (the prefix's
    full blocks are aliased, the state is each request's own) and continue token-exact."""
    prefix, suffixes = _ids(19, mul=5), [_ids(4, start=40), _ids(11, start=60)]
    engine = ServingEngine(model, num_slots=2, prompt_buckets=(8, 16), max_len=64, tick_block=4, paged_block_size=8)
    pid = engine.register_prefix(prefix)
    assert all(np.abs(v).max() > 0 for v in _state(engine._prefixes[pid]["cache"]).values())
    uids = [engine.submit(s, max_new_tokens=7, prefix_id=pid) for s in suffixes]
    engine.run()
    for uid, s in zip(uids, suffixes):
        assert _greedy_gap(model, np.concatenate([prefix, s]), engine.poll(uid)) < 1e-4


def test_preempted_request_resumes_token_exact(model):
    """Evicted mid-decode, requeued, resumed by chunk windows over prompt and generated tokens (a cold
    window, then warm ones with overlapped heads): the state is recomputed, the whole output the unpreempted one."""
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


def test_clear_slot_zeroes_the_state_and_paste_row_writes_it(model):
    """On a cache whose only state leaf is ``conv_state``: ``clear_slot`` zeroes the slot's row,
    ``paste_blocks`` passes the state by, ``paste_row`` writes the prefill's over the slot's whole."""
    engine = ServingEngine(model, num_slots=2, prompt_buckets=(8,), max_len=32, paged_block_size=8)
    ones = jax.tree_util.tree_map_with_path(
        lambda p, l: jnp.ones_like(l) if str(p[-1].key) in paged_kv.STATE_LEAVES else l, engine.slot_caches)
    cleared = _state(paged_kv.clear_slot(ones, jnp.int32(1)))
    assert len(cleared) == 4 and all((v[1] == 0).all() and (v[0] == 1).all() for v in cleared.values())
    _, row = model.apply_fn(model.params, jnp.asarray(_ids(8)[None]), positions=jnp.arange(8)[None], decode=True, cache=None)
    write_row = jnp.zeros((engine._mb,), jnp.int32).at[0].set(1)
    passed = _state(paged_kv.paste_blocks(ones, row, write_row))
    assert all((v == 1).all() for v in passed.values())
    pasted = _state(paged_kv.paste_row(ones, row, write_row, write_row, jnp.int32(1), jnp.int32(8)))
    for name, v in _state(row).items():
        np.testing.assert_array_equal(pasted[name][1], v[0])
        assert (pasted[name][0] == 1).all()


def test_paste_row_folds_a_prefills_rows_into_the_pool(head64):
    """A dense prefill row ``[1, max_len, 2, 64]`` lands in the folded pool ``[NB, bs, 1, 128]`` byte for byte."""
    engine = ServingEngine(head64, num_slots=2, prompt_buckets=(8,), max_len=32, paged_block_size=8)
    _, row = head64.apply_fn(head64.params, jnp.asarray(_ids(8)[None]), positions=jnp.arange(8)[None], decode=True, cache=None)
    write_row = jnp.zeros((engine._mb,), jnp.int32).at[0].set(3)
    pasted = paged_kv.paste_row(engine.slot_caches, row, write_row, write_row, jnp.int32(0), jnp.int32(8))
    pool, dense = pasted["layer_2"]["attn"]["key_pool"], row["layer_2"]["attn"]["key"]
    assert pool.shape[1:] == (8, 1, 128) and dense.shape[2:] == (2, 64)
    np.testing.assert_array_equal(np.asarray(pool[3]).reshape(8, 2, 64), np.asarray(dense[0, :8]))


def test_idle_slot_between_two_live_ones_is_finite_and_never_read(model):
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(8, 16), max_len=64, tick_block=4, paged_block_size=8)
    prompts = [_ids(6), _ids(4, start=9), _ids(12, start=20)]
    uids = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, (24, 2, 24))]
    for _ in range(3):
        engine.step()
    assert engine.slot_req[1] is None and engine.slot_req[0] is not None and engine.slot_req[2] is not None
    assert all(np.isfinite(v).all() for v in _state(engine.slot_caches).values())
    late = engine.submit(_ids(7, start=50), max_new_tokens=8)
    engine.run()
    for uid, p in zip(uids + [late], prompts + [_ids(7, start=50)]):
        assert _greedy_gap(model, p, engine.poll(uid)) < 1e-4


def test_hand_off_and_export_refuse_the_convolutions_state_by_name(model):
    """``check_no_state_leaf`` goes by ``STATE_LEAVES``: a cache whose only state leaf is ``conv_state`` is
    refused as one with ``ssm_state`` is, and the message names the leaf it found (and not the other)."""
    from accelerate_tpu.serving import check_no_state_leaf
    from accelerate_tpu.serving_fleet import HandoffCodec

    _, row = model.apply_fn(model.params, jnp.asarray(_ids(5)[None]), positions=jnp.arange(5)[None], decode=True, cache=None)
    with pytest.raises(NotImplementedError, match="conv_state") as refused:
        check_no_state_leaf(row, "KV hand-off")
    assert "ssm_state" not in str(refused.value)
    engine = ServingEngine.__new__(ServingEngine)  # the layout checks read the row template alone
    engine._row_template, engine.paged = row, False
    for refuse in (engine.kv_handoff_dims, lambda: HandoffCodec.decode(b"", engine)):
        with pytest.raises(NotImplementedError, match="conv_state"):
            refuse()
    plain = create_llama_model(LlamaConfig.tiny(), seed=0, seq_len=8)
    _, plain_row = plain.apply_fn(plain.params, jnp.asarray(_ids(5)[None]), positions=jnp.arange(5)[None], decode=True, cache=None)
    check_no_state_leaf(plain_row, "KV hand-off")  # K/V rows alone pass


def test_speculative_decoding_refuses_a_recurrent_state_by_name(model):
    from accelerate_tpu.speculative import speculative_generate

    with pytest.raises(NotImplementedError, match="conv_state"):
        speculative_generate(model, model, jnp.asarray(_ids(6)[None]), max_new_tokens=4, gamma=2)


def test_routing_at_the_published_router_width_is_not_degenerate():
    """Hidden 2048, 32 experts, 4 a token, the router and bias drawn as the configuration's file says
    (columns of standard deviation hidden**-0.5 over a normed input, a bias a quarter of the scores'
    spread): seeded tokens do not all take the same four experts, every expert gets tokens, and the
    busiest gets no more than three times its share."""
    from accelerate_tpu.ops.moe import sigmoid_topk_routing

    k = jax.random.split(jax.random.key(11), 4)
    hidden, experts, tokens = 2048, 32, 512
    h = jax.random.normal(k[0], (tokens, hidden)) * (1 + 0.1 * jax.random.normal(k[1], (hidden,)))
    router = jax.random.normal(k[2], (hidden, experts)) * hidden ** -0.5
    bias = jax.random.normal(k[3], (experts,)) * 0.05
    logits = jnp.matmul(h, router, precision="highest")
    assert 0.8 < float(logits.std()) < 1.2, "logits of about unit spread"
    chosen, weights = sigmoid_topk_routing(logits, bias, 4, True, 1.0)
    load = np.bincount(np.asarray(chosen).reshape(-1), minlength=experts)
    assert len({tuple(sorted(row)) for row in np.asarray(chosen).tolist()}) > tokens // 2
    assert load.min() > 0 and load.max() < 3 * tokens * 4 / experts
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-5)
    unbiased, _ = sigmoid_topk_routing(logits, None, 4, True, 1.0)
    moved = (np.sort(np.asarray(unbiased), -1) != np.sort(np.asarray(chosen), -1)).any(-1).mean()
    assert 0.05 < moved < 0.9, "the bias changes a token's last expert often, and does not route alone"
