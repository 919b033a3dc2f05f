"""Laguna on the core (``models/laguna.py``): layers of two kinds of attention that differ in more than the band
(query heads, a rotary rule and a gate by layer), routed experts by ``mlp_layer_types``; and under the serving
engine's paged layout **a pool and a table a kind of layer**: the window layers' ring (``ops/paged_kv.py``
``window_table``, ``ops/paged_walk.py`` ``ring``), the allocator a kind, the counts, and every refusal by its message."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.laguna import LAGUNA_XS2_LAYER_TYPES, LagunaConfig, create_laguna_model
from accelerate_tpu.models.llama import LlamaConfig, create_llama_model
from accelerate_tpu.ops import paged_kv
from accelerate_tpu.ops.paged_kv import PagedConfig, ring_entries
from accelerate_tpu.scheduling import SchedulerConfig
from accelerate_tpu.serving import ServingEngine

TOLERANCE = 2e-5  # float32 sums in another order, on logits of size 4
WINDOW, BLOCK = 8, 4  # the toy's band and page: a band spans three pages at most, a ring is four entries


@pytest.fixture(scope="module")
def model():
    return create_laguna_model(LagunaConfig.tiny(), seed=1, seq_len=16)


def _forward(model, tokens):
    ids = np.zeros((1, 128), np.int32)
    ids[0, : len(tokens)] = tokens
    return np.asarray(jax.jit(lambda i: model.apply_fn(model.params, i))(jnp.asarray(ids)))[0]


def _served_against_forward(model, engine, uid, prompt):
    """The served tokens' log-probabilities against one full forward pass of the model over prompt and served tokens."""
    served, lps = np.asarray(engine.partial(uid)), np.asarray(engine.logprobs(uid))
    logits = _forward(model, np.concatenate([prompt, served]))[len(prompt) - 1 : len(prompt) + len(served) - 1]
    want = np.asarray(jax.nn.log_softmax(logits, axis=-1))[np.arange(len(served)), served]
    return np.abs(lps - want).max()


def test_published_config_is_the_catalogs():
    cfg = LagunaConfig()
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers, cfg.vocab_size, cfg.head_dim) == (2048, 8192, 40, 100352, 128)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.sliding_window, cfg.rms_norm_eps) == (48, 8, 512, 1e-6)
    assert cfg.layer_types == LAGUNA_XS2_LAYER_TYPES and cfg.layer_types.count("full_attention") == 10
    assert cfg.num_attention_heads_per_layer == tuple(48 if i % 4 == 0 else 64 for i in range(40))
    assert cfg.mlp_layer_types == ("dense",) + ("sparse",) * 39 and not cfg.tie_word_embeddings
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size, cfg.shared_intermediate_size) == (256, 8, 512, 512)
    assert (cfg.routed_scaling_factor, cfg.scoring_func, cfg.norm_topk_prob, cfg.attn_gate, cfg.qk_norm) == (2.5, "sigmoid", True, True, True)
    full, window = cfg.rope_parameters["full_attention"], cfg.rope_parameters["sliding_attention"]
    assert (full["rope_theta"], full["rope_type"], full["factor"], full["beta_fast"], full["partial_rotary_factor"]) == (500000, "yarn", 64, 64, 0.5)
    assert full["attention_factor"] == 1.4158883083359672 and (window["rope_theta"], window["rope_type"]) == (10000, "default")
    again = dataclasses.replace(cfg, sliding_window=None)  # the core copies a layer's config: the published names carry over again
    assert again.n_routed_experts == 256 and again.attn_gate and again.layer_types == cfg.layer_types


def test_a_cut_of_the_leading_layers_reads_the_first_entries_of_the_published_lists():
    cfg = LagunaConfig(num_hidden_layers=13)
    assert cfg.layer_types == LAGUNA_XS2_LAYER_TYPES[:13] and cfg.layer_types.count("sliding_attention") == 9
    assert cfg.num_attention_heads_per_layer == (48, 64, 64, 64) * 3 + (48,) and cfg.mlp_layer_types == ("dense",) + ("sparse",) * 12
    with pytest.raises(ValueError, match="need 13 entries"):
        LagunaConfig(num_hidden_layers=13, layer_types=LAGUNA_XS2_LAYER_TYPES[:5])
    with pytest.raises(NotImplementedError, match="moe_apply_router_weight_on_input"):
        LagunaConfig(moe_apply_router_weight_on_input=True)


def test_a_layers_place_decides_its_heads_its_rotary_rule_and_its_feed_forward(model):
    cfg = model.config
    assert cfg.layer_overrides(0) == {"num_attention_heads": 6, "rope_theta": 500000.0, "partial_rotary_factor": 0.5,
                                      "rope_scaling": cfg.rope_parameters["full_attention"]}
    assert cfg.layer_overrides(1) == {"num_attention_heads": 8, "rope_theta": 10000.0, "partial_rotary_factor": 1.0, "rope_scaling": None}
    shapes = jax.tree.map(lambda x: x.shape, model.params)
    for i, heads in enumerate((6, 8, 8, 8, 6)):
        attn = shapes[f"layer_{i}"]["attn"]
        assert attn["q_proj"]["kernel"] == (64, heads * 16) and attn["o_proj"]["kernel"] == (heads * 16, 64)
        assert attn["g_proj"]["kernel"] == (64, heads) and attn["k_proj"]["kernel"] == (64, 32) and attn["q_norm"]["scale"] == (16,)
    assert set(shapes["layer_0"]["mlp"]) == {"gate_proj", "up_proj", "down_proj"}
    assert shapes["layer_1"]["mlp"]["experts/gate_proj"] == (8, 64, 32) and "shared_experts" in shapes["layer_4"]["mlp"]
    # a model without the keys has none of it
    plain = create_llama_model(LlamaConfig.tiny(scan_layers=False), seed=0, seq_len=8)
    assert "g_proj" not in plain.params["layer_0"]["attn"] and plain.config.layer_overrides is not None


# -- the ring: the kernel, the paste, the engine

@pytest.mark.parametrize("heads,kv_heads", [(6, 1), (8, 1), (6, 2), (16, 2)], ids=["groups_of_6", "groups_of_8", "groups_of_3", "groups_of_8_on_2"])
def test_ring_kernel_interpreted_is_the_gather(heads, kv_heads):
    """``paged_decode_attention(ring=True)`` against ``paged_gather_attention(ring=True)`` on a hand-filled ring: five
    slots at frontiers before the first turn, on a page's edge, and past two turns of a ring of four entries."""
    from accelerate_tpu.ops.pallas_paged_attention import paged_decode_attention

    rng = np.random.default_rng(3)
    slots, dim, ring, blocks = 5, 16, 4, 1 + 5 * 4
    key_pool = jnp.asarray(rng.normal(size=(blocks, BLOCK, kv_heads, dim)), jnp.float32)
    value_pool = jnp.asarray(rng.normal(size=(blocks, BLOCK, kv_heads, dim)), jnp.float32)
    table = jnp.asarray(1 + np.arange(slots * ring).reshape(slots, ring), jnp.int32)
    cur = jnp.asarray([2, 7, 15, 16, 41], jnp.int32)
    q = jnp.asarray(rng.normal(size=(slots, heads, dim)), jnp.float32)
    want = paged_kv.paged_gather_attention(q[:, None], key_pool, value_pool, table, cur, scale=0.25, sliding_window=WINDOW, ring=True)[:, 0]
    got = paged_decode_attention(q, key_pool, value_pool, table, cur, sliding_window=WINDOW, scale=0.25, interpret=True, ring=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # the band by hand for the slot past two turns: positions 34..41, page p at entry p % 4
    pos = np.arange(34, 42)
    keys = np.asarray(key_pool)[np.asarray(table)[4, (pos // BLOCK) % ring], pos % BLOCK]  # [8, kv, dim]
    values = np.asarray(value_pool)[np.asarray(table)[4, (pos // BLOCK) % ring], pos % BLOCK]
    group = np.arange(heads) // (heads // kv_heads)
    scores = np.einsum("hd,khd->hk", np.asarray(q)[4], keys[:, group]) * 0.25
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    by_hand = np.einsum("hk,khd->hd", probs / probs.sum(-1, keepdims=True), values[:, group])
    np.testing.assert_allclose(np.asarray(got)[4], by_hand, atol=2e-6)
    with pytest.raises(ValueError, match="ring=True needs sliding_window"):
        paged_decode_attention(q, key_pool, value_pool, table, cur, interpret=True, ring=True)


@pytest.mark.parametrize("curs", [[-1, 41, 7], [41, 7, -1], [41, -1, -1, 16, 2], [-1, -1, -1], [-1, 0, -1, 41]],
                         ids=["first", "last", "two_in_a_row_between", "every_row", "beside_a_row_of_one_key"])
def test_ring_kernel_walks_no_row_that_has_no_keys(curs):
    """Under a band through the ring, where nothing clamps a frontier: a row handed -1 (a slot that stores into the
    sink, ``paged_kv.NO_KEYS``) starts no copy and returns zeros, with the sink and every block of its own NaN; the rows
    beside it are to the bit what they are with those rows at frontier 0 and what they are alone, and are the gather's."""
    from accelerate_tpu.ops.pallas_paged_attention import paged_decode_attention

    rng = np.random.default_rng(4)
    slots, heads, kv_heads, dim, ring = len(curs), 6, 2, 16, 4
    blocks = 1 + slots * ring
    table = 1 + np.arange(slots * ring).reshape(slots, ring)
    skipped = [i for i, c in enumerate(curs) if c < 0]
    live = [i for i, c in enumerate(curs) if c >= 0]
    table[skipped] = 0
    pools = [rng.normal(size=(blocks, BLOCK, kv_heads, dim)).astype(np.float32) for _ in range(2)]
    clean = [jnp.asarray(pool) for pool in pools]
    for pool in pools:
        pool[0] = np.nan
        pool[[1 + i * ring + e for i in skipped for e in range(ring)]] = np.nan
    key_pool, value_pool = (jnp.asarray(pool) for pool in pools)
    table, cur = jnp.asarray(table, jnp.int32), jnp.asarray(curs, jnp.int32)
    q = jnp.asarray(rng.normal(size=(slots, heads, dim)), jnp.float32)
    run = functools.partial(paged_decode_attention, sliding_window=WINDOW, scale=0.25, interpret=True, ring=True)
    got = np.asarray(run(q, key_pool, value_pool, table, cur))
    assert not got[skipped].any() and np.isfinite(got).all()
    if live:
        at = jnp.asarray(live)
        np.testing.assert_array_equal(got[live], np.asarray(run(q, *clean, table, jnp.maximum(cur, 0)))[live])
        np.testing.assert_array_equal(got[live], np.asarray(run(q[at], key_pool, value_pool, table[at], cur[at])))
        want = paged_kv.paged_gather_attention(q[at][:, None], *clean, table[at], cur[at], scale=0.25, sliding_window=WINDOW, ring=True)
        np.testing.assert_allclose(got[live], np.asarray(want)[:, 0], atol=2e-6)


@pytest.mark.parametrize("grown", [5, 41, 300], ids=["first_turn", "past_two_turns", "past_the_context"])
def test_a_slot_at_the_sinks_ring_is_handed_no_keys(monkeypatch, grown):
    """``_ring_cached_attention``: an idle slot's index grows a token a step and its ring is the sink's, so its row is
    stored in the sink and the kernel is handed ``NO_KEYS`` for it, whatever the index has grown to: the sink is NaN but
    for the row just stored, the slot's output zeros, the slot beside it the gather's."""
    import flax.linen as nn

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, q, k, v):
            cfg = PagedConfig(BLOCK, 9, window_ring=4, window_blocks=9)
            return paged_kv.paged_cached_attention(self, q, k, v, 128, scale=0.25, sliding_window=WINDOW, cfg=cfg)

    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", True)
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 1, h, 16)), jnp.float32) for h in (6, 2, 2))
    pools = [jnp.asarray(rng.normal(size=(9, BLOCK, 2, 16)), jnp.float32).at[0].set(jnp.nan) for _ in range(2)]
    cache = {"key_pool": pools[0], "value_pool": pools[1], "window_table": jnp.asarray([[3, 1, 4, 2], [0, 0, 0, 0]], jnp.int32),
             "index": jnp.asarray([21, grown], jnp.int32)}
    out, new = Layer().apply({"cache": cache}, q, k, v, mutable=["cache"])
    new = new["cache"]
    np.testing.assert_array_equal(np.asarray(new["key_pool"][0, grown % BLOCK]), np.asarray(k[1, 0]))  # stored in the sink
    want = paged_kv.paged_gather_attention(q[:1], new["key_pool"].at[0].set(0), new["value_pool"].at[0].set(0), cache["window_table"][:1],
                                           jnp.asarray([21]), scale=0.25, sliding_window=WINDOW, ring=True)
    np.testing.assert_allclose(np.asarray(out[:1]), np.asarray(want), atol=2e-6)
    assert not np.asarray(out[1]).any(), "a slot at the sink reads nothing and returns zeros"


def test_ring_entries_are_the_pages_a_band_spans_and_one():
    assert ring_entries(512, 16, 5120) == 34 and ring_entries(512, 16, 262144) == 34  # 33 pages at most, one spare
    assert ring_entries(WINDOW, BLOCK, 128) == 4 and ring_entries(4096, 16, 4096) == 256  # never more than the context's
    # 33 is what a band of 512 keys can span: at every offset of the frontier in its page but the last
    spans = {(cur // 16) - (max(cur - 511, 0) // 16) + 1 for cur in range(600, 700)}
    assert spans == {32, 33}


@pytest.mark.parametrize("total", [3, 13, 16, 17, 40], ids=lambda t: f"prompt_{t}")
def test_paste_writes_the_last_ring_of_pages_at_their_entries(total):
    """``paste_row(window_row=)``: page ``p`` of the prompt goes to ``window_row[p % ring]`` for the last ``ring`` pages, the
    full layer's pool takes the whole prompt at ``write_row``, both tables and the frontier are the slot's."""
    ring, mb, kv, dim = 4, 12, 1, 8
    zeros = lambda *shape: jnp.zeros(shape, jnp.float32)  # noqa: E731
    cache = {"full": {"key_pool": zeros(20, BLOCK, kv, dim), "value_pool": zeros(20, BLOCK, kv, dim),
                      "block_table": jnp.zeros((2, mb), jnp.int32), "index": jnp.zeros((2,), jnp.int32)},
             "win": {"key_pool": zeros(9, BLOCK, kv, dim), "value_pool": zeros(9, BLOCK, kv, dim),
                     "window_table": jnp.zeros((2, ring), jnp.int32), "index": jnp.zeros((2,), jnp.int32)}}
    rows = jnp.arange(1, mb * BLOCK + 1, dtype=jnp.float32)[None, :, None, None] * jnp.ones((1, mb * BLOCK, kv, dim))
    row_cache = {name: {"key": rows, "value": -rows, "index": jnp.int32(total)} for name in ("full", "win")}
    write = np.zeros((mb,), np.int32)
    write[: -(-total // BLOCK)] = 1 + np.arange(-(-total // BLOCK))
    window_row = np.asarray([5, 6, 7, 8], np.int32)
    out = paged_kv.paste_row(cache, row_cache, jnp.asarray(write), jnp.asarray(write), jnp.int32(1), jnp.int32(total),
                             window_row=jnp.asarray(window_row))
    assert np.asarray(out["win"]["window_table"]).tolist() == [[0] * 4, window_row.tolist()]
    assert np.asarray(out["full"]["block_table"])[1].tolist() == write.tolist() and np.asarray(out["win"]["index"]).tolist() == [0, total]
    pool = np.asarray(out["win"]["key_pool"])[:, :, 0, 0]
    last = (total - 1) // BLOCK
    for page in range(max(last - ring + 1, 0), last + 1):  # every page the ring can hold, each at its entry
        assert pool[window_row[page % ring]].tolist() == [page * BLOCK + o + 1 for o in range(BLOCK)]
    assert not pool[1:5].any(), "a block that is nobody's stays as it was"
    full = np.asarray(out["full"]["key_pool"])[:, :, 0, 0]
    assert full[1].tolist() == [1, 2, 3, 4] and full[-(-total // BLOCK)].tolist()[0] == (-(-total // BLOCK) - 1) * BLOCK + 1


PROMPTS = (5, 13, 30, 50, 9, 64, 16)  # inside the first window, past it, past a whole turn of the ring, on a page's edge
NEW_TOKENS = (40, 30, 25, 20, 60, 11, 33)  # past two turns of the ring (16 positions a turn), finishing mid-tick


@pytest.mark.parametrize("layout,ring", [("paged_xla_gather", None), ("paged_kernel_interpreted", None), ("paged_kernel_interpreted", 3),
                                         ("paged_xla_gather", 3)], ids=["gather", "kernel", "kernel_ring_of_3", "gather_ring_of_3"])
def test_prefill_then_decode_through_both_pools_is_the_forward_pass(model, layout, ring, monkeypatch):
    """Through ``ServingEngine``: a bucket's prefill (cold, banded on the window layers), the paste of the full layers'
    rows whole and of the window layers' last pages into their ring, and the decode tick through both tables (XLA's
    gather, or the kernel interpreted: two shapes of it, groups of 3 and of 4), three slots at once, ticks of eight
    steps; against one forward pass over prompt and served tokens. A ring of three entries, the pages a band of 8 keys
    spans with none to spare, serves as well: the spare entry of ``ring_entries`` is margin, not need."""
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", layout == "paged_kernel_interpreted")
    if ring is not None:
        monkeypatch.setattr(paged_kv, "ring_entries", lambda window, block, max_len: ring)
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(16, 64), max_len=128, tick_block=8, paged_block_size=BLOCK)
    assert engine._ring == (ring or 4) and engine._pcfg == PagedConfig(BLOCK, 97, window_ring=ring or 4, window_blocks=3 * (ring or 4) + 1)
    names = {"/".join(str(k.key) for k in p) for p, _ in jax.tree_util.tree_flatten_with_path(engine.slot_caches)[0]}
    assert "layer_0/attn/block_table" in names and "layer_1/attn/window_table" in names and "layer_1/attn/block_table" not in names
    assert engine.slot_caches["layer_1"]["attn"]["key_pool"].shape == (3 * (ring or 4) + 1, BLOCK, 2, 16)
    assert engine.slot_caches["layer_4"]["attn"]["key_pool"].shape == (97, BLOCK, 2, 16)
    free = engine.pool_free_blocks
    rng = np.random.default_rng(1)
    prompts = [rng.integers(5, 250, size=n).astype(np.int32) for n in PROMPTS]
    uids = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, NEW_TOKENS)]
    engine.run()
    for uid, prompt in zip(uids, prompts):
        assert _served_against_forward(model, engine, uid, prompt) < TOLERANCE
    assert engine.pool_free_blocks == free and engine._alloc_w.free_count == 3 * (ring or 4)
    assert len({tuple(np.asarray(engine.partial(u))[:9]) for u in uids}) == len(uids), "the sequences differ"


def test_a_ring_one_block_shorter_than_the_band_can_span_fails_by_the_comparison(model, monkeypatch):
    """Two entries where a band of 8 keys spans three pages: the frontier's page overwrites the band's oldest, and
    every request that leaves its first window misses the forward pass by thousands of tolerances (not by luck: each
    of the five does, and the one that stays inside two pages does not)."""
    monkeypatch.setattr(paged_kv, "ring_entries", lambda window, block, max_len: 2)
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(16, 64), max_len=128, tick_block=8, paged_block_size=BLOCK)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(5, 250, size=n).astype(np.int32) for n in (5, 13, 30, 50, 9, 3)]
    uids = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, (40, 30, 25, 20, 60, 4))]
    engine.run()
    missed = [_served_against_forward(model, engine, uid, prompt) for uid, prompt in zip(uids, prompts)]
    assert all(m > 1000 * TOLERANCE for m in missed[:5]), missed
    assert missed[5] < TOLERANCE, "seven positions lie in two pages: nothing is overwritten"


def test_a_slot_that_overshoots_stores_into_its_own_ring_or_the_sink(model):
    """Requests of 3 and 5 new tokens in ticks of eight steps: each finishes mid-tick and the tick goes on storing for
    its slot, five and three steps past its last kept token, round its own ring; the long request beside them stays
    exact, and of the window pool's blocks those that were never handed out are untouched."""
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(16, 64), max_len=128, tick_block=8, paged_block_size=BLOCK,
                           window_pool_blocks=40)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(5, 250, size=n).astype(np.int32) for n in (14, 15, 11, 16, 13)]
    uids = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, (45, 3, 5, 4, 2))]
    engine.run()
    assert _served_against_forward(model, engine, uids[0], prompts[0]) < TOLERANCE
    handed_out = 3 * 4  # at most three slots' rings at once, taken from the low ids up
    for layer in (1, 2, 3):
        pool = np.asarray(engine.slot_caches[f"layer_{layer}"]["attn"]["key_pool"])
        assert not pool[1 + 2 * handed_out :].any(), "a block nobody owned was written"
        assert pool[0].any(), "the sink took the idle slots' rows"


# -- the allocator a kind, the counts

def test_blocks_by_kind_at_admission_growth_and_retirement(model):
    engine = ServingEngine(model, num_slots=2, prompt_buckets=(16, 64), max_len=128, tick_block=8, paged_block_size=BLOCK)
    full_free, window_free = engine._alloc.free_count, engine._alloc_w.free_count
    assert (full_free, window_free) == (64, 8) and engine.pool_free_blocks == 8, "free_blocks is the scarcer pool's"
    short = engine.submit(np.arange(5, 7, dtype=np.int32), max_new_tokens=11)  # 2 + 11 - 1 = 12 rows: three pages of each kind
    long = engine.submit(np.arange(5, 35, dtype=np.int32), max_new_tokens=60)  # 89 rows: 23 blocks and a whole ring
    engine.step()
    assert (full_free - engine._alloc.free_count, window_free - engine._alloc_w.free_count) == (3 + 23, 3 + 4)
    assert [len(r) for r in engine._slot_ring] == [3, 4] and [len(b) for b in engine._slot_blocks] == [3, 23]
    assert (engine.metrics.full_pages_held, engine.metrics.window_pages_held) == (26, 7)
    while engine.poll(short) is None:
        engine.step()
    assert engine._slot_ring[0] == [] and window_free - engine._alloc_w.free_count == 4, "a retirement frees its ring"
    for _ in range(3):  # growth: the long request goes round its ring and reserves nothing more
        engine.step()
        assert (full_free - engine._alloc.free_count, window_free - engine._alloc_w.free_count) == (23, 4)
    engine.run()
    assert (engine._alloc.free_count, engine._alloc_w.free_count) == (full_free, window_free) and engine.poll(long) is not None
    assert (engine.metrics.full_pages_held, engine.metrics.window_pages_held) == (0, 0)


def test_a_slot_at_5120_tokens_holds_320_and_34_blocks():
    cfg = LagunaConfig.tiny(max_position_embeddings=5120, sliding_window=512)
    engine = ServingEngine(create_laguna_model(cfg, seed=0, seq_len=8), num_slots=2, prompt_buckets=(16,), max_len=5120, paged_block_size=16)
    assert engine._ring == 34 and engine._pcfg.window_blocks == 2 * 34 + 1 and engine._pcfg.num_blocks == 2 * 320 + 1
    assert engine._new_blocks_for(0, 4096, 1024) == 320 and engine._ring_blocks_for(4096, 1024) == 34
    assert engine._ring_blocks_for(128, 64) == 12 and engine._new_blocks_for(0, 128, 64) == 12, "a short request holds its pages alone"
    assert engine._ring_blocks_for(400, 200) == 34 and engine._new_blocks_for(0, 400, 200) == 38
    assert engine.slot_caches["layer_1"]["attn"]["window_table"].shape == (2, 34)
    assert engine.slot_caches["layer_0"]["attn"]["block_table"].shape == (2, 320)


def test_admission_waits_for_the_scarcer_pool(model):
    """A window pool of one ring and a half: the second long request waits (``pool_blocked``) though the full
    layers' pool has room, holds nothing meanwhile, and is admitted when the first retires; both stay exact."""
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(16, 64), max_len=128, tick_block=8, paged_block_size=BLOCK,
                           window_pool_blocks=7)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(5, 250, size=n).astype(np.int32) for n in (20, 22)]
    uids = [engine.submit(p, max_new_tokens=20) for p in prompts]
    engine.step()
    assert engine._pool_blocked and engine.metrics.preemptions >= 1 and len(engine.queue) == 1 and engine._alloc_w.free_count == 2
    assert engine._alloc.free_count == 96 - 10, "the blocked request holds no block of the other pool"
    from accelerate_tpu.telemetry.trace import phase_log

    assert phase_log().roots("engine.tick")[-1].done["pool_blocked"] == 1 and phase_log().roots("engine.tick")[-1].done["free_blocks"] == 2
    engine.run()
    for uid, prompt in zip(uids, prompts):
        assert _served_against_forward(model, engine, uid, prompt) < TOLERANCE
    with pytest.raises(ValueError, match="blocks of the window layers' pool but it has 2"):
        ServingEngine(model, num_slots=1, prompt_buckets=(16,), max_len=128, paged_block_size=BLOCK, window_pool_blocks=3).submit(prompts[0][:10], 20)
    with pytest.raises(ValueError, match="window_pool_blocks is the window layers' pool"):
        ServingEngine(create_llama_model(LlamaConfig.tiny(), seed=0, seq_len=8), num_slots=1, prompt_buckets=(16,), paged_block_size=BLOCK,
                      window_pool_blocks=9)


def test_counts_by_kind_agree_with_the_hosts_arithmetic_to_the_row(model, monkeypatch):
    """``window_rows_read`` (``min(t + 1, window)`` over decoding slots and kept steps) beside ``context_rows``, the pages by
    kind at a tick's end, ``engine.admit``'s blocks by kind; 0 and absent for a model of one kind."""
    from accelerate_tpu import serving
    from accelerate_tpu.telemetry.trace import phase, phase_log

    admit = []  # the counts ``engine.admit`` is entered with: a root's direct child keeps its seconds alone in the log

    def spy(name, **counts):
        if name == "engine.admit":
            admit.append(counts)
        return phase(name, **counts)

    monkeypatch.setattr(serving, "phase", spy)
    engine = ServingEngine(model, num_slots=2, prompt_buckets=(16, 64), max_len=128, tick_block=8, paged_block_size=BLOCK)
    uid = engine.submit(np.arange(5, 10, dtype=np.int32), max_new_tokens=21)
    engine.step()  # the prefill's token, then eight steps at positions 5..12
    assert (engine.metrics.window_rows_read, engine.metrics.context_rows) == (6 + 7 + 6 * 8, sum(range(6, 14)))
    done = phase_log().roots("engine.tick")[-1].done
    assert (done["window_rows_read"], done["context_rows"], done["full_pages"], done["window_pages"]) == (61, 76, 7, 4)
    assert [(a["full_blocks"], a["window_blocks"]) for a in admit] == [(7, 4)]
    engine.run()  # 20 decode steps in all, positions 5..24
    assert engine.metrics.context_rows == sum(t + 1 for t in range(5, 25))
    assert engine.metrics.window_rows_read == sum(min(t + 1, WINDOW) for t in range(5, 25)) and engine.poll(uid) is not None
    one_kind = ServingEngine(create_llama_model(LlamaConfig.tiny(sliding_window=8), seed=0, seq_len=8), num_slots=2, prompt_buckets=(16,),
                             max_len=64, paged_block_size=BLOCK)
    one_kind.generate_many([np.arange(5, 12, dtype=np.int32)], max_new_tokens=12)
    done = phase_log().roots("engine.tick")[-1].done
    assert one_kind._ring is None and (done["window_rows_read"], done["full_pages"], done["window_pages"]) == (0, 0, 0)
    assert len(admit) == 2 and "full_blocks" not in admit[1]
    assert (one_kind.metrics.window_rows_read, one_kind.metrics.full_pages_held, one_kind.metrics.window_pages_held) == (0, 0, 0)


@pytest.mark.parametrize("what", ["chunk windows", "prefix reuse", "preemption", "import_inflight", "hand-off", "export"])
def test_every_refusal_over_the_ring_is_by_name(model, what):
    options = dict(num_slots=2, prompt_buckets=(16, 32), max_len=128, paged_block_size=BLOCK)
    if what == "preemption":
        with pytest.raises(NotImplementedError, match=r"preemption with resume \(SchedulerConfig.enable_preemption\) is not built over a ring table"):
            ServingEngine(model, scheduler=SchedulerConfig(enable_preemption=True), **options)
        return
    engine = ServingEngine(model, **options)
    prompt = np.arange(5, 45, dtype=np.int32)
    if what == "chunk windows":
        with pytest.raises(NotImplementedError, match=r"chunk windows \(a prompt of 40 tokens, past the largest prefill bucket 32\) is not built over a ring"):
            engine.submit(prompt, 4)
    elif what == "prefix reuse":
        with pytest.raises(NotImplementedError, match=r"prefix reuse \(register_prefix\) is not built over a ring table"):
            engine.register_prefix(prompt[:8])
    elif what == "import_inflight":
        snap = {"prompt": prompt[:8], "max_new_tokens": 6, "out_tokens": [7, 9], "out_lps": [-1.0, -1.0],
                "key_data": np.asarray(jax.random.key_data(jax.random.key(0)))}
        with pytest.raises(NotImplementedError, match="import_inflight of a request that has decoded .* is not built over a ring table"):
            engine.import_inflight(snap)
        assert engine.import_inflight(dict(snap, out_tokens=[], out_lps=[])) == 0, "a request that has not decoded is a fresh one"
    elif what == "hand-off":
        for call in (engine.kv_handoff_dims, lambda: engine.prefill_detached(prompt[:8], 4),
                     lambda: engine.submit_prefilled({"prompt": prompt[:8], "total": 8, "max_new_tokens": 4})):
            with pytest.raises(NotImplementedError, match=r"KV hand-off \(kv_handoff_dims, prefill_detached, submit_prefilled\) is not built over a ring"):
                call()
    else:
        with pytest.raises(NotImplementedError, match="export_inflight .* is not built over a ring table"):
            engine.export_inflight()
