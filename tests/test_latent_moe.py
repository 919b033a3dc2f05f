"""Latent attention (MLA) and dropless routed experts on the llama core, at a toy size on the CPU:
the routed FFN against a loop over experts, absorbed against non-absorbed attention, the model through
``ServingEngine`` with the paged latent cache (XLA gather path and the interpreted Pallas kernel), a
warm chunk window against a cold prefill, and what the engine cannot carry yet. The comparison with the
benchmark's plain reference is in tests/chipbench/test_chipbench_latent_moe.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.joyai_llm_flash import JoyAIFlashConfig, create_joyai_flash_model
from accelerate_tpu.ops import paged_kv
from accelerate_tpu.ops.moe import dropless_moe_ffn, sigmoid_topk_routing
from accelerate_tpu.serving import ServingEngine


def _experts(seed, e=8, d=16, ff=24):
    k = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(k[0], (e, d, ff)) * 0.3, jax.random.normal(k[1], (e, d, ff)) * 0.3,
            jax.random.normal(k[2], (e, ff, d)) * 0.3, k[3])


def _expert_loop(x, experts, weights, gate, up, down):
    """Every token through each of its experts, one pair at a time."""
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for e, w in zip(np.asarray(experts[t]), np.asarray(weights[t])):
            h = jax.nn.silu(x[t] @ gate[e]) * (x[t] @ up[e])
            out[t] += w * np.asarray(h @ down[e])
    return out


def _routing(case, e=8):
    """``[T, 2]`` experts of a case: the first three as they were, the rest what the grouped kernel's
    bookkeeping can get wrong at its row tile (16 rows for all of them but ``one_expert_many_tiles``: 64)."""
    if case == "single_token":
        return jnp.array([[0, 3]], jnp.int32)
    if case == "one_expert":  # every token on expert 5 (and 2): nothing is dropped at any skew
        return jnp.tile(jnp.array([[5, 2]], jnp.int32), (12, 1))
    if case == "balanced":
        return jnp.stack([jnp.arange(12) % e, (jnp.arange(12) + 3) % e], axis=1).astype(jnp.int32)
    if case == "empty_between":  # experts 1, 2, 4 and 6 get nothing, between experts that do
        return jnp.array([[0, 3], [0, 5], [3, 7], [5, 0], [7, 3]], jnp.int32)
    if case == "straddles_a_tile":  # 32 pairs: expert 1 holds rows 10..21, across the tile edge at 16
        return jnp.array([[0, 1]] * 10 + [[1, 2]] * 2 + [[2, 3]] * 4, jnp.int32)
    if case == "one_expert_many_tiles":  # expert 4 takes every one of 80 pairs: more rows than the 64 of a tile
        return jnp.tile(jnp.array([[4, 4]], jnp.int32), (40, 1))
    if case == "pairs_not_a_multiple_of_the_tile":  # 26 pairs on a 16-row tile: the last tile is cut
        return jnp.stack([jnp.arange(13) % 5, 5 + jnp.arange(13) % 3], axis=1).astype(jnp.int32)
    raise ValueError(case)


CASES = ["balanced", "one_expert", "single_token", "empty_between", "straddles_a_tile", "one_expert_many_tiles",
         "pairs_not_a_multiple_of_the_tile"]


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged_dot", "pallas_interpreted"])
@pytest.mark.parametrize("case", CASES)
def test_dropless_ffn_is_the_expert_loop(case, kernel, monkeypatch):
    """float32 on the CPU: the grouped products add the same terms in another order, so 1e-5, through
    ``jax.lax.ragged_dot`` and through the grouped Pallas kernel the chip runs, interpreted."""
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", kernel)
    gate, up, down, key = _experts(0)
    experts = _routing(case)
    t = experts.shape[0]
    x = jax.random.normal(key, (t, 16))
    weights = jax.random.uniform(jax.random.key(7), (t, 2)) + 0.1
    out, sizes = dropless_moe_ffn(x, experts, weights, gate, up, down)
    assert int(sizes.sum()) == 2 * t and (case != "one_expert" or int(sizes[5]) == t)
    np.testing.assert_allclose(np.asarray(out), _expert_loop(x, experts, weights, gate, up, down), atol=1e-5)


def _row_mask(masked, t):
    """``[t]`` bool: the tokens that count."""
    if masked == "none":
        return np.ones(t, bool)
    if masked == "all":
        return np.zeros(t, bool)
    return np.arange(t) % 5 == 1  # "some": one in five counts, as a dozen of a tick's 64 slots decode


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged_dot", "pallas_interpreted"])
@pytest.mark.parametrize("masked", ["none", "some", "all"])
def test_row_valid_keeps_the_rows_that_count_and_sends_the_others_to_no_expert(masked, kernel, monkeypatch):
    """A decode tick's shape: 40 tokens of which those that do not count are copies of one row (a free
    slot's token 0 at position 0) and route alike. The rows that count are the unmasked call's bit for bit,
    the others exactly zero, no group counts a pair of theirs, and the products visit no tile for them."""
    from accelerate_tpu.ops.moe import expert_load

    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", kernel)
    gate, up, down, key = _experts(2)
    t, k = 40, 2
    valid = _row_mask(masked, t)
    x = jnp.where(valid[:, None], jax.random.normal(key, (t, 16)), jnp.full((16,), 0.7))
    logits = x @ jax.random.normal(jax.random.key(5), (16, 8))
    experts, weights = sigmoid_topk_routing(logits, None, k)
    full, full_sizes = dropless_moe_ffn(x, experts, weights, gate, up, down)
    out, sizes = dropless_moe_ffn(x, experts, weights, gate, up, down, jnp.asarray(valid))
    out = np.asarray(out)
    assert np.array_equal(out[valid], np.asarray(full)[valid])
    assert np.isfinite(out).all() and not out[~valid].any()
    assert int(sizes.sum()) == k * int(valid.sum()) and int(full_sizes.sum()) == k * t
    live = np.asarray(experts)[valid].reshape(-1)
    assert np.array_equal(np.asarray(sizes), np.bincount(live, minlength=8))
    touched, _, visits, pairs = np.asarray(expert_load(sizes, t * k)).tolist()
    assert pairs == k * int(valid.sum()) and touched == len(set(live.tolist()))
    assert (visits == 0) == (masked == "all") and visits <= np.asarray(expert_load(full_sizes, t * k))[2]


@pytest.mark.parametrize("sizes", [[3, 0, 5, 0, 0, 2, 0, 0], [0, 0, 0, 20, 0, 0, 1, 0], [0] * 8, [0] * 7 + [64]],
                         ids=["ten_of_64", "21_of_64_straddling", "none", "every_row"])
def test_visit_plan_never_reaches_rows_past_the_last_group(sizes):
    """``group_sizes`` may sum to less than the rows (the pairs of tokens that do not count lie behind
    every group): the plan names the tiles that hold a grouped row and no other, a sum of zero makes no
    visit, and the kernels leave the grouped rows what ``ragged_dot`` makes them."""
    from accelerate_tpu.ops.moe import _ragged_swiglu_ffn
    from accelerate_tpu.ops.pallas_grouped_matmul import grouped_swiglu_ffn, visit_plan

    rows, tile, grouped = 64, 16, sum(sizes)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    offsets, group_of, tile_of, n = visit_plan(group_sizes, rows, tile)
    walked = list(zip(np.asarray(group_of)[:int(n)].tolist(), np.asarray(tile_of)[:int(n)].tolist()))
    owner = np.repeat(np.arange(8), sizes)  # the group of each grouped row
    assert walked == sorted({(int(g), i // tile) for i, g in enumerate(owner)})
    assert int(offsets[-1]) == grouped and all(til * tile < grouped for _, til in walked)
    gate, up, down, key = _experts(3)
    xs = jax.random.normal(key, (rows, 16))
    got = grouped_swiglu_ffn(xs, gate, up, down, group_sizes, interpret=True)
    want = _ragged_swiglu_ffn(xs, gate, up, down, group_sizes)
    np.testing.assert_allclose(np.asarray(got)[:grouped], np.asarray(want)[:grouped], atol=1e-5, rtol=1e-5)
    assert not np.asarray(want)[grouped:].any(), "off the chip a row in no group is zero"


def test_kernel_path_differentiates_as_ragged_dot_does(monkeypatch):
    """The kernel path's ``custom_vjp`` hands back the ``ragged_dot`` formulation's gradients, for the
    rows and for each of the three stacks."""
    gate, up, down, key = _experts(1)
    experts = _routing("straddles_a_tile")
    x = jax.random.normal(key, (experts.shape[0], 16))
    weights = jax.random.uniform(jax.random.key(7), experts.shape) + 0.1

    def loss(x, gate, up, down):
        return jnp.sum(dropless_moe_ffn(x, experts, weights, gate, up, down)[0] ** 2)

    grads = {}
    for kernel in (False, True):
        monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", kernel)
        grads[kernel] = jax.grad(loss, argnums=(0, 1, 2, 3))(x, gate, up, down)
    for plain, through_kernel in zip(grads[False], grads[True]):
        assert float(jnp.abs(plain).max()) > 1e-3
        np.testing.assert_allclose(np.asarray(through_kernel), np.asarray(plain), rtol=1e-5, atol=1e-5)


def test_under_a_mesh_of_several_devices_the_products_stay_ragged_dot(monkeypatch):
    """XLA's partitioner cannot split a ``pallas_call``: the kernel is for one device."""
    from jax.sharding import Mesh

    from accelerate_tpu.parallel.sharding import mesh_context

    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", True)
    gate, up, down, key = _experts(0)
    experts = _routing("balanced")
    x, weights = jax.random.normal(key, (12, 16)), jnp.ones((12, 2))

    def trace():  # a function of its own each time: the active mesh is no part of a trace cache's key
        return str(jax.make_jaxpr(lambda *a: dropless_moe_ffn(*a))(x, experts, weights, gate, up, down))

    assert "pallas_call" in trace() and "ragged_dot" not in trace()
    with mesh_context(Mesh(np.array(jax.devices()[:2]), ("data",))):
        assert "ragged_dot" in trace() and "pallas_call" not in trace()


@pytest.mark.parametrize("case", CASES)
def test_expert_tile_visits_is_the_kernels_grid(case):
    """The third count of ``expert_load`` against a count made in numpy: the (expert, row tile) pairs
    in which a sorted pair of that expert lies in that tile; and the plan walks exactly those."""
    from accelerate_tpu.ops.moe import expert_load
    from accelerate_tpu.ops.pallas_grouped_matmul import row_tile, visit_plan

    flat = np.sort(np.asarray(_routing(case)).reshape(-1))
    tile = row_tile(flat.size, 8)
    assert tile == (64 if case == "one_expert_many_tiles" else 16)
    want = sorted({(int(g), i // tile) for i, g in enumerate(flat)})
    sizes = jnp.asarray(np.bincount(flat, minlength=8), jnp.int32)
    touched, most, visits, pairs = np.asarray(expert_load(sizes, flat.size)).tolist()
    assert (touched, most, visits) == (len(set(flat.tolist())), int(np.bincount(flat).max()), len(want))
    assert pairs == flat.size
    rows = -(-flat.size // tile) * tile
    _, group_of, tile_of, n = visit_plan(sizes, rows, tile)
    walked = list(zip(np.asarray(group_of)[:n].tolist(), np.asarray(tile_of)[:n].tolist()))
    assert int(n) == len(want) and walked == want


@pytest.mark.parametrize("pairs,tile,sizes", [
    (512, 64, "even"), (512, 64, "skewed"), (2048, 128, "skewed"), (2048, 128, "one_empty_in_three"),
], ids=lambda v: str(v))
def test_grouped_kernel_at_32_groups_of_wide_matrices(pairs, tile, sizes):
    """LFM2-8B-A1B's shapes scaled down: 32 groups, matrices in the ratio of ``[2048, 1792]`` (``[64, 56]``),
    the tick's 128 slots x 4 (row tile 64: 16 rows a group, four groups a tile) and a prefill's 512 x 4
    (row tile 128, groups that straddle tiles, groups with nothing). Interpreted, against ``ragged_dot``:
    the same products, float32 sums in another order."""
    from accelerate_tpu.ops.moe import _ragged_swiglu_ffn
    from accelerate_tpu.ops.pallas_grouped_matmul import grouped_swiglu_ffn, row_tile, tile_visits

    groups, d, ff = 32, 64, 56
    assert row_tile(pairs, groups) == tile and row_tile(128 * 4, 32) == 64
    rng = np.random.default_rng(pairs)
    if sizes == "even":
        share = np.ones(groups)
    elif sizes == "skewed":
        share = rng.gamma(0.6, size=groups) + 0.01
    else:
        share = np.where(np.arange(groups) % 3 == 1, 0.0, rng.uniform(0.5, 1.5, groups))
    flat = np.sort(rng.choice(groups, size=pairs, p=share / share.sum()))
    group_sizes = jnp.asarray(np.bincount(flat, minlength=groups), jnp.int32)
    if sizes == "one_empty_in_three":
        assert int((group_sizes == 0).sum()) >= 10
    if sizes != "even":
        assert int(tile_visits(group_sizes, tile).sum()) > int((group_sizes > 0).sum()), "some group straddles a tile"
    k = jax.random.split(jax.random.key(pairs), 4)
    xs = jax.random.normal(k[0], (pairs, d))
    gate, up = jax.random.normal(k[1], (groups, d, ff)) * 0.2, jax.random.normal(k[2], (groups, d, ff)) * 0.2
    down = jax.random.normal(k[3], (groups, ff, d)) * 0.2
    got = grouped_swiglu_ffn(xs, gate, up, down, group_sizes, interpret=True)
    want = _ragged_swiglu_ffn(xs, gate, up, down, group_sizes)
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_selection_bias_changes_the_choice_and_not_the_weight():
    logits = jnp.array([[2.0, 1.0, 0.5, -1.0]])
    plain_e, plain_w = sigmoid_topk_routing(logits, None, 2, norm_topk=False, scaling_factor=1.0)
    assert sorted(np.asarray(plain_e[0])) == [0, 1]
    bias = jnp.array([0.0, -1.0, 0.0, 1.0])  # expert 3 now beats expert 1
    e, w = sigmoid_topk_routing(logits, bias, 2, norm_topk=False, scaling_factor=1.0)
    assert sorted(np.asarray(e[0])) == [0, 3]
    scores = np.asarray(jax.nn.sigmoid(logits[0]))
    np.testing.assert_allclose(np.asarray(w[0]), scores[np.asarray(e[0])], rtol=1e-6)  # the scores, without the bias
    _, normed = sigmoid_topk_routing(logits, bias, 2, norm_topk=True, scaling_factor=2.5)
    np.testing.assert_allclose(float(normed.sum()), 2.5, rtol=1e-6)


@pytest.fixture(scope="module")
def model():
    return create_joyai_flash_model(JoyAIFlashConfig.tiny(), seed=3, seq_len=16)


def test_leading_dense_layer_then_routed_layers(model):
    lead = model.params["layer_0"]
    assert "gate_proj" in lead["mlp"] and "kv_b_proj" in lead["attn"]
    for routed in (model.params["layer_1"], model.params["layer_2"]):
        assert "experts/gate_proj" in routed["mlp"] and "shared_experts" in routed["mlp"]


@pytest.mark.parametrize(
    "change, names",
    [
        ({"scan_layers": True}, "scan_layers=False"),  # no carried stack holds a latent pool, and layer 0 differs
        ({"scoring_func": "softmax"}, "sigmoid"),  # no configuration, and so no reference, scores otherwise
        ({"q_lora_rank": None}, "q_lora_rank"),
    ],
    ids=["scanned", "softmax_scores", "full_rank_queries"],
)
def test_what_no_configuration_runs_is_refused_by_name(change, names):
    with pytest.raises(NotImplementedError, match=names):
        create_joyai_flash_model(JoyAIFlashConfig.tiny(**change), seed=3, seq_len=16)


def test_absorbed_decode_is_the_non_absorbed_forward(model):
    """Cold prefill (K and V decompressed per head) then absorbed steps over the dense latent cache,
    against the plain forward, in float32: the same sums in another order, 2e-5 on logits of size 1."""
    ids = (np.arange(1, 25, dtype=np.int32)[None] * 7) % 250
    full = model.apply_fn(model.params, jnp.asarray(ids))
    logits, cache = model.apply_fn(model.params, jnp.asarray(ids[:, :10]), positions=jnp.arange(10)[None], decode=True, cache=None)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, :10]), atol=2e-5)
    latent = [l for p, l in jax.tree_util.tree_flatten_with_path(cache)[0] if str(p[-1].key) == "latent"]
    assert latent and all(l.shape[-2:] == (128, 32 + 16) for l in latent), "one row of rank + rope values a token"
    for t in range(10, 24):
        logits, cache = model.apply_fn(model.params, jnp.asarray(ids[:, t:t + 1]), positions=jnp.full((1, 1), t), decode=True, cache=cache)
        np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(full[:, t]), atol=2e-5)


def test_warm_chunk_window_is_the_cold_prefill(model):
    """A window over a cache that already holds rows attends through the absorbed path."""
    ids = (np.arange(3, 27, dtype=np.int32)[None] * 5) % 250
    cold, _ = model.apply_fn(model.params, jnp.asarray(ids), positions=jnp.arange(24)[None], decode=True, cache=None)
    _, cache = model.apply_fn(model.params, jnp.asarray(ids[:, :8]), positions=jnp.arange(8)[None], decode=True, cache=None)
    warm, _ = model.apply_fn(model.params, jnp.asarray(ids[:, 8:]), positions=jnp.arange(8, 24)[None], decode=True, cache=cache)
    np.testing.assert_allclose(np.asarray(warm), np.asarray(cold[:, 8:]), atol=2e-5)


def _greedy_gap(model, prompt, out, n):
    """How far the logit of each of the ``n`` served tokens lies under the plain forward's best."""
    out = np.asarray(out)
    ref = np.asarray(model.apply_fn(model.params, jnp.asarray(out[None])))[0, len(prompt) - 1:-1]
    served = out[len(prompt):]
    assert len(served) == n
    return (ref.max(-1) - ref[np.arange(n), served]).max()


@pytest.mark.parametrize("kernel", [False, True], ids=["xla_gather", "pallas_interpreted"])
def test_engine_serves_from_the_paged_latent_cache(model, kernel, monkeypatch):
    """Bucketed prefill, paste into the latent pool, decode ticks, a prompt over the largest bucket
    (chunk windows) and retirement: every served token is the plain forward's greedy token, and its
    logit is within 1e-4 of the forward's best (float32; a tie broken otherwise would show there)."""
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", kernel)
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(8, 16), max_len=64, paged_block_size=8, tick_block=4)
    pool = [l for p, l in jax.tree_util.tree_flatten_with_path(engine.slot_caches)[0] if str(p[-1].key) == "latent_pool"]
    assert pool and all(l.shape[-2:] == (48, 8) for l in pool), "pages of [rank + rope, block] values"
    prompts = [np.arange(1, 6, dtype=np.int32), np.arange(3, 17, dtype=np.int32), np.arange(7, 10, dtype=np.int32),
               np.arange(2, 30, dtype=np.int32)]
    for prompt, out in zip(prompts, engine.generate_many(prompts, max_new_tokens=9)):
        assert _greedy_gap(model, prompt, out, 9) < 1e-4
    m = engine.metrics
    layers = model.config.num_hidden_layers - 1
    assert 0 < m.expert_pairs_max <= 3 * 2 and m.experts_touched > 0
    assert m.experts_touched <= engine._tick * engine.tick_block * layers * 8
    assert m.experts_touched <= m.expert_tile_visits <= m.experts_touched + engine._tick * engine.tick_block * layers


def test_expert_load_counts_are_a_ticks(model):
    """The counts leave the tick beside its tokens (``[steps, expert layers, 4]``), and the cache holds
    nothing but rows, tables and frontiers: no cache program has to know of them."""
    engine = ServingEngine(model, num_slots=2, prompt_buckets=(8,), max_len=32, paged_block_size=8, tick_block=2)
    names = {str(p[-1].key) for p, _ in jax.tree_util.tree_flatten_with_path(engine.slot_caches)[0]}
    assert names == {"latent_pool", "block_table", "index"}
    engine.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=6)
    seen = []
    while engine.queue or engine.active_count:
        engine.step()
        seen.append(engine._tick_expert_load)
    layers = model.config.num_hidden_layers - 1
    # of two slots one decodes, and its token alone is routed: 2 experts in each of 2 steps of each expert layer
    assert all((touched, most, pairs) == (2 * layers * 2, 1, 2 * layers * 2) for touched, most, _, pairs in seen)
    # 2 pairs a call lie in one 16-row tile: an expert's pairs lie in one tile each, visits == experts touched
    assert all(visits == touched for touched, _, visits, _ in seen)
    assert engine.metrics.experts_touched == sum(touched for touched, _, _, _ in seen)
    assert engine.metrics.expert_tile_visits == sum(visits for _, _, visits, _ in seen)
    assert engine.metrics.expert_pairs == sum(pairs for _, _, _, pairs in seen)


def test_idle_slots_reach_no_expert_and_the_decoding_slots_tokens_are_the_forwards(model):
    """Five slots, three requests of unequal answers: slots fall idle one by one between ticks while
    others decode on. Every served token is the plain forward's greedy token, a tick with one decoding
    slot touches at most ``num_experts_per_tok`` experts a layer a step, and ``expert_pairs`` is the
    decoding slots' pairs alone: what the mask kept from the experts is the rest of slots x k."""
    engine = ServingEngine(model, num_slots=5, prompt_buckets=(8, 16), max_len=64, paged_block_size=8, tick_block=2)
    cfg = model.config
    k, layers = cfg.num_experts_per_tok, cfg.num_hidden_layers - cfg.first_k_dense_replace
    prompts = [np.arange(1, 6, dtype=np.int32), np.arange(3, 17, dtype=np.int32), np.arange(7, 10, dtype=np.int32)]
    uids = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, (4, 9, 15))]
    seen = []
    while engine.queue or engine.active_count:
        decoding = sum(ph == "decode" for ph in engine.slot_phase)  # before the tick: admissions join inside it
        engine.step()
        seen.append((decoding, *engine._tick_expert_load))
    for uid, prompt, n in zip(uids, prompts, (4, 9, 15)):
        assert _greedy_gap(model, prompt, engine.done[uid], n) < 1e-4
    steps = engine.tick_block
    ticks = [row for row in seen[1:] if row[0]]  # the first tick admits all three: its decoding slots were none before it
    assert {d for d, *_ in ticks} >= {1, 2, 3}
    for decoding, touched, most, visits, pairs in ticks:
        assert pairs == decoding * k * layers * steps < engine.num_slots * k * layers * steps
        assert touched <= pairs and most <= decoding and visits == touched  # 16-row tile: no group straddles
    assert [row for row in ticks if row[0] == 1 and row[1] <= k * layers * steps]
    assert engine.metrics.expert_pairs == sum(row[4] for row in seen)


def test_expert_pairs_is_a_count_of_engine_tick_done(model, tmp_path):
    """Under a profiler session the zero-length ``engine.tick.done`` carries ``expert_pairs`` beside the
    other counts of the routed experts' load, and they sum to ``ServingMetrics``'."""
    from chipbench import program_trace, trace
    from chipbench.generators import open_loop_rounds

    engine = ServingEngine(model, num_slots=3, prompt_buckets=(8,), max_len=32, paged_block_size=8, tick_block=2)
    engine.generate_many([np.arange(1, 6, dtype=np.int32)], max_new_tokens=3)  # compiled before the session opens
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        was = engine.metrics.expert_pairs
        engine.generate_many([np.arange(2, 8, dtype=np.int32), np.arange(4, 7, dtype=np.int32)], max_new_tokens=5)
    finally:
        jax.profiler.stop_trace()
    phases = program_trace.reduce(program_trace.load(trace.newest_xplane(str(tmp_path)), ("window",) + open_loop_rounds.SPANS))
    done = [phases["spans"][i]["stats"] for i in program_trace.named(phases, "engine.tick.done")]
    assert done and all({"experts_touched", "expert_pairs_max", "expert_tile_visits", "expert_pairs"} <= set(d) for d in done)
    assert sum(d["expert_pairs"] for d in done) == engine.metrics.expert_pairs - was > 0
    assert all(d["experts_touched"] <= d["expert_pairs"] for d in done)


def test_expert_load_is_collected_only_inside_its_context(model):
    """Through ``nn.remat`` too (the toy model rematerialises its layers): the counts are sown, not leaked."""
    from accelerate_tpu.ops.moe import expert_load, expert_load_counts

    assert np.asarray(expert_load(jnp.array([4, 0, 1, 2, 0]), 7)).tolist() == [3, 4, 3, 7]
    ids, pos = jnp.asarray([[5, 9, 5]]), jnp.arange(3)[None]
    with expert_load_counts() as loads:
        jax.jit(lambda p: model.apply_fn(p, ids, positions=pos, decode=True, cache=None)[0])(model.params)
        assert len(loads) == model.config.num_hidden_layers - 1 and all(l.shape == (4,) for l in loads)
    _, cache = model.apply_fn(model.params, ids, positions=pos, decode=True, cache=None)
    assert len(loads) == 2 and "expert_load" not in cache


def test_kv_handoff_says_it_cannot_carry_a_latent_cache(model):
    from accelerate_tpu.serving_fleet import HandoffCodec

    engine = ServingEngine(model, num_slots=2, prompt_buckets=(8,), max_len=32)
    with pytest.raises(NotImplementedError, match="latent"):
        engine.kv_handoff_dims()
    with pytest.raises(NotImplementedError, match="latent"):
        engine.prefill_detached(np.arange(1, 6, dtype=np.int32), 4)
    with pytest.raises(NotImplementedError, match="latent"):
        HandoffCodec.decode(b"", engine)


def test_generate_works_through_the_dense_latent_cache():
    from accelerate_tpu.generation import generate

    model = create_joyai_flash_model(JoyAIFlashConfig.tiny(), seed=3, seq_len=16)
    prompt = jnp.asarray((np.arange(1, 9, dtype=np.int32)[None] * 3) % 250)
    out = np.asarray(generate(model, prompt, max_new_tokens=6))
    ref = np.asarray(model.apply_fn(model.params, jnp.asarray(out)))[0, 7:-1]
    assert (ref.max(-1) - ref[np.arange(6), out[0, 8:]]).max() < 1e-4
