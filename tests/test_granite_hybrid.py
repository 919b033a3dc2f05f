"""Mamba-2 layers beside attention, routed experts by a softmax over the top k with a shared one, Granite's
four multipliers and one chip's share of the experts (granite-4.0-h-small's ``granitemoehybrid``), on the
llama core at a toy size on the CPU: which mixer a layer builds, the chunked scan against the token loop
(across a chunk edge, a span inside chunks, a carried state), the step kernel (interpreted) against the
plain step with and without a mask, the routing, what a held share computes and that the shares add up,
the multipliers, the model through ``ServingEngine`` (the paged layout with the XLA step and with the
interpreted kernels) and ``generate()``, an idle slot, the counts the tick carries, and what is refused by
name. The comparison with the benchmark's plain reference is in
tests/chipbench/test_chipbench_granite_hybrid.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.granitemoehybrid import (
    GRANITE_4_0_H_SMALL_LAYER_TYPES, GraniteMoeHybridConfig, create_granitemoehybrid_model)
from accelerate_tpu.models.llama import LlamaConfig, LlamaModel, RoutedFFN, _wrap_llama
from accelerate_tpu.ops import paged_kv
from accelerate_tpu.ops.kv_cache import reset_cache_index
from accelerate_tpu.ops.pallas_ssd_step import ssd_state_step
from accelerate_tpu.ops.ssd_scan import ssd_scan, ssd_state_step_plain
from accelerate_tpu.serving import ServingEngine

B, T, H, P, N = 2, 37, 4, 8, 16


def _ids(n, mul=7, start=1):
    return ((np.arange(start, start + n, dtype=np.int32)) * mul) % 250 + 1


def _state(cache):
    return {jax.tree_util.keystr(p): np.asarray(l) for p, l in jax.tree_util.tree_flatten_with_path(cache)[0]
            if str(p[-1].key) in paged_kv.STATE_LEAVES}


@pytest.fixture(scope="module")
def model():
    return create_granitemoehybrid_model(GraniteMoeHybridConfig.tiny(), seed=3, seq_len=16)


@pytest.fixture(scope="module")
def scan_inputs():
    k = jax.random.split(jax.random.key(0), 8)
    return dict(
        x=jax.random.normal(k[0], (B, T, H, P)), delta=jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 2.0),
        a=-jnp.exp(jax.random.normal(k[2], (H,))), b=jax.random.normal(k[3], (B, T, N)), c=jax.random.normal(k[4], (B, T, N)),
        d_skip=1.0 + 0.1 * jax.random.normal(k[5], (H,)), h0=jax.random.normal(k[6], (B, N, H * P)))


def _token_loop(s, lo, hi):
    h, ys = s["h0"], []
    for t in range(T):
        y = jnp.zeros((B, H, P))
        if lo <= t < hi:
            y, h = ssd_state_step_plain(h, s["x"][:, t], s["delta"][:, t], s["a"], s["b"][:, t], s["c"][:, t], s["d_skip"])
        ys.append(y)
    return jnp.stack(ys, axis=1), h


# -- ops: the chunked scan and the step kernel

@pytest.mark.parametrize("chunk", [8, 16, 256], ids=lambda c: f"chunk{c}")
@pytest.mark.parametrize("span", [(0, T), (5, 30), (0, 0), (36, 37), (9, 15)],
                         ids=["whole", "starts_and_ends_inside_chunks", "nothing_new", "last_alone", "inside_one_chunk"])
def test_chunked_scan_is_the_token_loop_over_the_new_tokens(scan_inputs, span, chunk):
    """From a carried state, over chunk edges (37 tokens in chunks of 8 or 16: five or three chunks, the last
    padded) and whole (256): the outputs of the new tokens and the state after the last are the plain
    step's, a token at a time. Float32 on both sides, ``highest`` in the scan's products: the same terms
    summed in another order, 2e-5 on outputs of size 10 (bfloat16 products would differ by 1e-1)."""
    lo, hi = span
    want_y, want_h = _token_loop(scan_inputs, lo, hi)
    s = scan_inputs
    y, h = ssd_scan(s["x"], s["delta"], s["a"], s["b"], s["c"], s["d_skip"], s["h0"], lo, hi, chunk=chunk)
    assert y.shape == (B, T, H, P) and h.shape == (B, N, H * P) and h.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y[:, lo:hi]), np.asarray(want_y[:, lo:hi]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h), atol=2e-5)
    if lo == hi:
        np.testing.assert_array_equal(np.asarray(h), np.asarray(s["h0"]))  # nothing new: the state passes through


def test_scan_over_two_windows_is_one_scan(scan_inputs):
    s = scan_inputs
    whole_y, whole_h = ssd_scan(s["x"], s["delta"], s["a"], s["b"], s["c"], s["d_skip"], s["h0"], 0, T, chunk=8)
    cut = 21  # inside the third chunk
    first = {k: (v[:, :cut] if k in ("x", "delta", "b", "c") else v) for k, v in s.items()}
    y1, h1 = ssd_scan(first["x"], first["delta"], s["a"], first["b"], first["c"], s["d_skip"], s["h0"], 0, cut, chunk=8)
    y2, h2 = ssd_scan(s["x"][:, cut:], s["delta"][:, cut:], s["a"], s["b"][:, cut:], s["c"][:, cut:], s["d_skip"], h1, 0, T - cut, chunk=8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)), np.asarray(whole_y), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(whole_h), atol=2e-5)


def test_a_wrong_recurrence_does_not_pass(scan_inputs):
    """What the tolerance above tells apart: a state rounded to bfloat16 a chunk, a decay of the wrong head."""
    s = scan_inputs
    want_y, want_h = _token_loop(s, 0, T)
    _, h = ssd_scan(s["x"], s["delta"], s["a"], s["b"], s["c"], s["d_skip"], s["h0"].astype(jnp.bfloat16), 0, T, chunk=8)
    assert float(jnp.abs(h - want_h).max()) > 1e-4
    y, _ = ssd_scan(s["x"], s["delta"], jnp.roll(s["a"], 1), s["b"], s["c"], s["d_skip"], s["h0"], 0, T, chunk=8)
    assert float(jnp.abs(y - want_y).max()) > 1e-2


def _step_inputs(slots, seed=1):
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (slots, N, H * P)), jax.random.normal(k[1], (slots, H, P)),
            jax.nn.softplus(jax.random.normal(k[2], (slots, H))), -jnp.exp(jax.random.normal(k[3], (H,))),
            jax.random.normal(k[4], (slots, N)), jax.random.normal(k[5], (slots, N)), jnp.linspace(0.5, 1.5, H))


@pytest.mark.parametrize("slots", [16, 8, 3, 1], ids=lambda s: f"slots{s}")
def test_step_kernel_is_the_plain_step(slots):
    """Interpreted, without a mask: every slot stepped, two grid steps of eight slots, one, or every slot in
    one step where eight does not divide them. The same float32 expression: 1e-5."""
    args = _step_inputs(slots)
    want_y, want_h = ssd_state_step_plain(*args)
    y, h = ssd_state_step(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h), atol=1e-5)


@pytest.mark.parametrize("kind,slots", [("random", 16), ("random", 24), ("none", 16), ("all", 8), ("first_alone", 16), ("last_alone", 16),
                                         ("a_block_idle", 16), ("random", 3)])
def test_step_kernel_visits_the_live_slots_alone(kind, slots):
    """With the ``[slots]`` bool: a live slot's ``y`` and ``h'`` are the plain step's, an idle slot's ``h`` is
    bit for bit what it was (a NaN laid there stays a NaN and reaches nothing) and its ``y`` is zeros."""
    live = {"random": np.random.default_rng(slots).random(slots) < 0.5, "none": np.zeros(slots, bool), "all": np.ones(slots, bool),
            "first_alone": np.arange(slots) == 0, "last_alone": np.arange(slots) == slots - 1, "a_block_idle": np.arange(slots) >= 8}[kind]
    h, *rest = _step_inputs(slots, seed=slots)
    h = jnp.where(jnp.asarray(live)[:, None, None], h, jnp.nan)
    want_y, want_h = ssd_state_step_plain(h, *rest)
    y, out = ssd_state_step(h, *rest, jnp.asarray(live), interpret=True)
    y, out, h = np.asarray(y), np.asarray(out), np.asarray(h)
    np.testing.assert_allclose(y[live], np.asarray(want_y)[live], atol=1e-5)
    np.testing.assert_allclose(out[live], np.asarray(want_h)[live], atol=1e-5)
    assert np.isnan(out[~live]).all() and not y[~live].any()
    assert np.isfinite(out[live]).all() and np.isfinite(y).all()


# -- routing and the held share

def test_softmax_over_the_top_k_is_not_a_softmax_cut_to_its_top():
    from accelerate_tpu.ops.moe import softmax_topk_routing

    logits = jax.random.normal(jax.random.key(2), (9, 12)) * 2.0
    experts, weights = softmax_topk_routing(logits, 4)
    top = np.sort(np.asarray(logits), axis=-1)[:, ::-1][:, :4]
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(logits), np.asarray(experts), -1), top)
    np.testing.assert_allclose(np.asarray(weights), np.exp(top) / np.exp(top).sum(-1, keepdims=True), atol=1e-6)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-6)
    cut = np.take_along_axis(np.asarray(jax.nn.softmax(logits, -1)), np.asarray(experts), -1)
    assert np.abs(cut - np.asarray(weights)).max() > 0.05, "a softmax over all twelve, cut to four, sums to less than one"


def _ffn(cfg, seed=4):
    ffn = RoutedFFN(cfg)
    hidden = jax.random.normal(jax.random.key(seed), (2, 9, cfg.hidden_size))
    return ffn, hidden


def _share_params(whole, first, held):
    cut = dict(whole)
    for name in ("experts/gate_proj", "experts/up_proj", "experts/down_proj"):
        cut[name] = whole[name][first : first + held]
    return cut


@pytest.mark.parametrize("kernels", [False, True], ids=["ragged_dot", "kernels_interpreted"])
def test_the_shares_add_up_to_the_uncut_layer(kernels, monkeypatch):
    """Share 0 and share 1 of a routed layer (each holds four of eight experts' matrices, both the whole
    router and the shared expert), the shared expert counted once, equal the uncut layer; and each share is
    the uncut layer's sum over its own experts alone. Float32, the same products grouped otherwise: 1e-5."""
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", kernels)
    cfg = GraniteMoeHybridConfig.tiny()
    ffn, hidden = _ffn(cfg)
    whole = ffn.init(jax.random.key(0), hidden)["params"]
    assert whole["router/kernel"].shape == (16, 8) and whole["experts/gate_proj"].shape == (8, 16, 8) and "router/e_score_correction_bias" not in whole
    uncut = ffn.apply({"params": whole}, hidden)
    shared_alone = ffn.apply({"params": {**whole, **{n: jnp.zeros_like(whole[n]) for n in whole if n.startswith("experts/down")}}}, hidden)
    parts = []
    for share in (0, 1):
        scfg = dataclasses.replace(cfg, expert_shares=2, expert_share=share)
        assert scfg.held_experts == (4 * share, 4)
        part = RoutedFFN(scfg)
        params = _share_params(whole, 4 * share, 4)
        assert jax.tree.map(jnp.shape, part.init(jax.random.key(0), hidden)["params"]) == jax.tree.map(jnp.shape, params)
        out, load = part.apply({"params": params}, hidden, mutable=["expert_load"])
        parts.append(out)
        counts = np.asarray(jax.tree_util.tree_leaves(load)[0])
        assert counts[0] <= 4 and 0 < counts[3] < 18 * 4, "the counts are of held experts and of the pairs that reached them"
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] - shared_alone), np.asarray(uncut), atol=1e-5)
    assert float(jnp.abs(parts[0] - uncut).max()) > 1e-3, "one share alone is a partial layer"
    # the pairs of the two shares are the uncut layer's: every token has k experts somewhere
    masked = RoutedFFN(dataclasses.replace(cfg, expert_shares=2, expert_share=0)).apply(
        {"params": _share_params(whole, 0, 4)}, hidden, jnp.zeros((2, 9), bool).at[0, :3].set(True))
    np.testing.assert_allclose(np.asarray(masked[0, :3]), np.asarray(parts[0][0, :3]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(masked[1]), np.asarray(shared_alone[1]), atol=1e-6)  # no routed expert for a row that does not count


def test_held_share_is_stated_or_refused():
    cfg = GraniteMoeHybridConfig.tiny()
    assert cfg.held_experts == (0, 8) and dataclasses.replace(cfg, expert_shares=4, expert_share=3).held_experts == (6, 2)
    for bad in (dict(expert_shares=3), dict(expert_shares=2, expert_share=2)):
        with pytest.raises(ValueError, match="equal shares"):
            dataclasses.replace(cfg, **bad).held_experts
    with pytest.raises(NotImplementedError, match="softmax over the top k"):
        ffn, hidden = _ffn(dataclasses.replace(cfg, scoring_func="softmax"))
        ffn.init(jax.random.key(0), hidden)
    with pytest.raises(NotImplementedError, match="one group"):
        create_granitemoehybrid_model(GraniteMoeHybridConfig.tiny(mamba_n_groups=2), seq_len=8)
    with pytest.raises(NotImplementedError, match="scan_layers=False"):
        create_granitemoehybrid_model(GraniteMoeHybridConfig.tiny(scan_layers=True), seq_len=8)


def test_sigmoid_models_keep_their_parameters_and_their_program():
    """The joyai / lfm2 expert layer is what it was: a selection bias, experts for every router column, a
    shared expert of ``moe_intermediate_size x n_shared_experts``, and a mask of every row changes no bit."""
    cfg = LlamaConfig.tiny(n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32, n_shared_experts=1, scan_layers=False)
    ffn, hidden = _ffn(cfg)
    params = ffn.init(jax.random.key(0), hidden)["params"]
    assert set(params) == {"router/kernel", "router/e_score_correction_bias", "experts/gate_proj", "experts/up_proj", "experts/down_proj",
                           "shared_experts"}
    assert params["experts/gate_proj"].shape == (8, 64, 32) and params["shared_experts"]["gate_proj"]["kernel"].shape == (64, 32)
    out = ffn.apply({"params": params}, hidden, jnp.ones((2, 9), bool))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ffn.apply({"params": params}, hidden)))


# -- the model

def test_layers_follow_layer_types(model):
    cfg = model.config
    assert [cfg.mixer_kind(i) for i in range(4)] == ["mamba2", "mamba2", "attention", "mamba2"] and cfg.stateful and cfg.rope_theta is None
    for i in range(4):
        layer = model.params[f"layer_{i}"]
        assert ("mamba" in layer) == (i != 2) and ("attn" in layer) == (i == 2)
        assert layer["mlp"]["router/kernel"].shape == (16, 8) and layer["mlp"]["shared_experts"]["gate_proj"]["kernel"].shape == (16, 24)
    mixer = model.params["layer_0"]["mamba"]
    assert set(mixer) == {"in_proj", "conv_kernel", "conv_bias", "dt_bias", "A_log", "D", "norm", "out_proj"}
    assert mixer["in_proj"]["kernel"].shape == (16, 2 * 32 + 2 * 16 + 4) and mixer["conv_kernel"].shape == (4, 32 + 2 * 16)
    assert mixer["A_log"].shape == mixer["dt_bias"].shape == mixer["D"].shape == (4,) and mixer["norm"]["scale"].shape == (32,)
    assert "lm_head" not in model.params, "the head is the embedding"
    jamba_named = LlamaConfig.tiny(num_hidden_layers=2, layer_types=("mamba", "attention"), scan_layers=False)
    assert [jamba_named.mixer_kind(i) for i in range(2)] == ["mamba", "attention"], "without mamba_n_heads a mamba layer is Mamba-1's"


def test_published_config_is_the_catalogs():
    cfg = GraniteMoeHybridConfig()
    assert [i for i in range(40) if cfg.mixer_kind(i) == "attention"] == [5, 15, 25, 35] and len(GRANITE_4_0_H_SMALL_LAYER_TYPES) == 40
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.vocab_size) == (4096, 32, 8, 100352)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size, cfg.shared_intermediate_size) == (72, 10, 768, 1536)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_expand, cfg.mamba_chunk_size) == (128, 64, 128, 4, 2, 256)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.attention_multiplier, cfg.logits_scaling) == (12.0, 0.22, 0.0078125, 16.0)
    assert cfg.rope_theta is None and cfg.tie_word_embeddings and cfg.scoring_func == "softmax_topk" and cfg.held_experts == (0, 72)
    again = dataclasses.replace(cfg, sliding_window=None)  # the core copies a layer's config: the published names carry over again
    assert again.n_routed_experts == 72 and again.moe_intermediate_size == 768 and again.rope_theta is None
    half = dataclasses.replace(cfg, expert_shares=2, expert_share=1)
    assert half.held_experts == (36, 36)


@pytest.mark.parametrize("name,moved", [("embedding_multiplier", 6.0), ("residual_multiplier", 0.5), ("attention_multiplier", 1.0),
                                        ("logits_scaling", 2.0)])
def test_each_multiplier_moves_the_logits_and_none_is_the_plain_model(model, name, moved):
    ids = jnp.asarray(_ids(12)[None])
    base = model.apply_fn(model.params, ids)

    def logits_with(value):  # the same weights under a config that differs in this one key
        cfg = dataclasses.replace(model.config, **{name: value})
        return _wrap_llama(LlamaModel(cfg), model.params, cfg).apply_fn(model.params, ids)

    got = logits_with(moved)
    assert float(jnp.abs(got - base).max()) > 1e-3
    if name == "logits_scaling":
        np.testing.assert_allclose(np.asarray(got), np.asarray(base) * model.config.logits_scaling / moved, rtol=1e-5)
    # None leaves the multiplication out: the program of a model that has none (heads of 4: 4 ** -0.5 is the scale)
    np.testing.assert_allclose(np.asarray(logits_with(None)), np.asarray(logits_with(0.5 if name == "attention_multiplier" else 1.0)), atol=1e-6)


def test_padded_prefill_then_steps_is_the_forward_without_a_cache(model):
    """A prompt of 13 in a window of 16 (``new_span`` (0, 13): the right pad neither decays nor feeds the state,
    nor shifts the convolution's carried inputs), then a token at a time through the plain step: every logit
    is the forward's without a cache, and the state leaves are one row a sequence."""
    ids = _ids(21)
    full = np.asarray(model.apply_fn(model.params, jnp.asarray(ids[None])))[0]
    padded = np.zeros((1, 16), np.int32)
    padded[0, :13] = ids[:13]
    logits, cache = model.apply_fn(model.params, jnp.asarray(padded), positions=jnp.arange(16)[None], decode=True, cache=None, new_span=(0, 13))
    np.testing.assert_allclose(np.asarray(logits)[0, :13], full[:13], atol=2e-5)
    state = _state(cache)
    assert {k.split("'")[-2] for k in state} == {"ssm_state", "conv_state"} and len(state) == 6
    assert next(v for k, v in state.items() if "ssm_state" in k).shape == (1, 16, 32)
    assert next(v for k, v in state.items() if "conv_state" in k).shape == (1, 3 * 64)
    # the K/V frontier of the attention layer stands at the window's end: set it back to the 13 tokens that are real
    cache = reset_cache_index(cache, 13)
    step = jax.jit(lambda c, tok, pos: model.apply_fn(model.params, tok, positions=pos, decode=True, cache=c))
    for t in range(13, 18):
        logits, cache = step(cache, jnp.asarray(ids[None, t : t + 1]), jnp.full((1, 1), t))
        np.testing.assert_allclose(np.asarray(logits)[0, 0], full[t], atol=2e-5)


def _greedy_gap(model, prompt, served):
    tokens = np.concatenate([prompt, served])
    logits = np.asarray(model.apply_fn(model.params, jnp.asarray(tokens[None])))[0][len(prompt) - 1 : len(tokens) - 1]
    return float((logits.max(-1) - logits[np.arange(len(served)), served]).max())


@pytest.mark.parametrize("layout", ["paged_xla_step", "paged_kernels_interpreted"])
def test_engine_serves_the_hybrid_cache(model, layout, monkeypatch):
    """Bucketed prefill (right pads), a prompt over the largest bucket (chunk windows with an overlapped
    head over a carried state), the paste of rows and state, and the tick: every served token is the
    greedy token of the forward without a cache, within float32's reordering. (The dense layout's tick is
    a ``vmap`` of one slot's step, which routed experts' ``ragged_dot`` has no rule for: the paged layout
    serves them, as it does the other families with experts.)"""
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", layout == "paged_kernels_interpreted")
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(8, 16), max_len=64, tick_block=4, paged_block_size=8)
    assert engine.metrics.state_bytes_per_slot == 3 * (16 * 32 * 4 + 3 * 64 * 4) and engine._mask_idle_rows
    prompts = [_ids(n, start=3 * n) for n in (5, 13, 21, 9)]
    uids = [engine.submit(p, max_new_tokens=11) for p in prompts]
    engine.run()
    for uid, p in zip(uids, prompts):
        assert len(engine.partial(uid)) == 11 and _greedy_gap(model, p, np.asarray(engine.partial(uid))) < 2e-5


def test_generate_equals_the_engine(model):
    from accelerate_tpu.generation import generate

    prompt = _ids(9)
    out = np.asarray(generate(model, jnp.asarray(prompt[None]), max_new_tokens=7))[0]
    engine = ServingEngine(model, num_slots=1, prompt_buckets=(16,), max_len=64, paged_block_size=8)
    np.testing.assert_array_equal(out, engine.generate_many([prompt], max_new_tokens=7)[0])


@pytest.mark.parametrize("step", ["xla_step", "kernel_interpreted"])
def test_idle_slot_keeps_its_state_under_the_kernel_and_counts_under_the_plain_step(model, step, monkeypatch):
    """``state_slots_idle`` and the four expert counts of ``engine.tick.done``: one request in four slots.
    The plain step steps every slot's state ((slots - decoding) x steps idle); the kernel is told which slots
    decode: 0, and the idle slots' ``ssm_state`` stays what ``clear_slot`` left. The expert counts are of the
    decoding slot's pairs alone (PR 36), here all held: ``expert_pairs`` = k x layers x steps."""
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", step == "kernel_interpreted")
    engine = ServingEngine(model, num_slots=4, prompt_buckets=(8,), max_len=32, paged_block_size=8, tick_block=2)
    engine.submit(_ids(5), max_new_tokens=6)
    seen, pairs = [], []
    while engine.queue or engine.active_count:
        engine.step()
        seen.append(engine._tick_state_idle)
        pairs.append(engine._tick_expert_load[3])
    assert set(seen) == {0 if step == "kernel_interpreted" else 3 * 2} and engine.metrics.state_slots_idle == sum(seen)
    assert set(pairs[1:]) == {1 * 4 * 4 * 2}, "one decoding slot x 4 experts x 4 layers x 2 steps"
    idle = [v[1:] for k, v in _state(engine.slot_caches).items() if "ssm_state" in k]
    assert all((not v.any()) == (step == "kernel_interpreted") for v in idle)


def test_held_share_halves_the_pairs_the_tick_multiplies(monkeypatch):
    """The same model told that it holds share 0 of 2: the tick's ``expert_pairs`` are the pairs that reached
    experts 0-3, about half, and ``experts_touched`` at most four a layer a step."""
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", True)
    model = create_granitemoehybrid_model(GraniteMoeHybridConfig.tiny(expert_shares=2), seed=3, seq_len=16)
    assert model.params["layer_0"]["mlp"]["experts/gate_proj"].shape == (4, 16, 8) and model.params["layer_0"]["mlp"]["router/kernel"].shape == (16, 8)
    engine = ServingEngine(model, num_slots=4, prompt_buckets=(8,), max_len=64, paged_block_size=8, tick_block=2)
    for n in (5, 7, 3):
        engine.submit(_ids(n, start=n), max_new_tokens=24)
    engine.step()
    engine.step()
    touched, _, _, pairs = engine._tick_expert_load
    routed = 3 * 4 * 4 * 2
    assert 0.25 * routed < pairs < 0.75 * routed and touched <= 4 * 4 * 2
    engine.run()


def test_hand_off_refuses_the_state_by_name(model):
    """The Mamba-2 state lives under the names ``STATE_LEAVES`` has: the hand-off refusal holds with no second list."""
    from accelerate_tpu.serving import check_no_state_leaf

    _, row = jax.eval_shape(
        lambda p, i: model.apply_fn(p, i, positions=jnp.zeros((1, 5), jnp.int32), decode=True, cache=None), model.params, jnp.zeros((1, 5), jnp.int32))
    assert paged_kv.state_bytes(row) == 3 * (16 * 32 * 4 + 3 * 64 * 4)
    with pytest.raises(NotImplementedError, match="ssm_state / conv_state"):
        check_no_state_leaf(row, "KV hand-off")


def test_routing_at_the_published_router_width_is_neither_flat_nor_one_hot():
    """Hidden 4096, 72 experts, 10 a token, the router drawn as the configuration's file says (columns of
    standard deviation hidden**-0.5 over a normed input): logits of unit spread, a softmax over the ten
    largest whose weights run from about 0.04 to 0.25, every expert with tokens, and about half of the
    pairs on experts 0-35."""
    from accelerate_tpu.ops.moe import softmax_topk_routing

    k = jax.random.split(jax.random.key(11), 3)
    hidden, experts, tokens = 4096, 72, 512
    h = jax.random.normal(k[0], (tokens, hidden)) * (1 + 0.1 * jax.random.normal(k[1], (hidden,)))
    logits = jnp.matmul(h, jax.random.normal(k[2], (hidden, experts)) * hidden ** -0.5, precision="highest")
    assert 0.8 < float(logits.std()) < 1.2
    chosen, weights = softmax_topk_routing(logits, 10)
    weights = np.asarray(weights)
    assert 0.15 < np.median(weights.max(-1)) < 0.4 and 0.02 < np.median(weights.min(-1)) < 0.08
    load = np.bincount(np.asarray(chosen).reshape(-1), minlength=experts)
    assert load.min() > 0 and load.max() < 3 * tokens * 10 / experts
    assert 0.45 < (np.asarray(chosen) < 36).mean() < 0.55
