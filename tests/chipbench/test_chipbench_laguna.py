"""The family ``laguna`` (full and window attention layers with 48 / 64 query heads, a rotary rule a kind and a gate a
head; routed experts of which this chip holds a share): its seeded weights, the program against its plain reference
at a toy size on the CPU (logits, not tokens) through the forward pass and through prefill then decode over both
pools, the wrong rules that must miss by far more than the tolerance the program meets, the shares that add up to the
uncut layer, its configuration file and counts, the five readers this family's cell brought, and the rehearsal of a
toy cell made only of files of its own (``rehearsal-laguna.json``)."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run, weights
from chipbench.generators import open_loop_rounds

from test_chipbench_run import rehearse, result  # noqa: F401  the fixture that runs one cell in this process

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LAGUNA = os.path.join(HERE, "rehearsal-laguna.json")
CELL, CONFIG = "laguna-xs.2-serve-longchat", "laguna-xs.2-l13-ep4"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    M = json.load(f)
FAMILY = run.load(M, "reference", "laguna")
NEW = ("mixed_rows_read_share", "window_pages_share", "full_decode_attention_roofline", "window_decode_attention_roofline",
       "mixed_decode_roofline_share")
CUT = {"num_hidden_layers", "num_experts", "max_position_embeddings"}
# What the program meets against the reference in float32 on the CPU: the same terms summed in another order, on
# logits of size 4. Each wrong rule below moves them hundreds of times further.
TOLERANCE = 3e-5

# sha256 over every tensor (name, type, shape, bytes; names sorted) of ``weights.make`` on the host's CPU, when
# the family was written (PR 48): a later change to ``spec`` that moves a seeded weight shows here
DIGESTS = {5: "2e632ed75ee1a07fd174dfc2dc7e8806586ec9bf7ef7d988083b80e9a6c07a54",
           2**31 + 99: "62d64f8ba7c982ce1d6ed8f71ae64df42a2378d19cbbc36e035482e58cd378b2"}


def config(name="laguna-tiny", where=os.path.join(HERE, "configs")):
    with open(os.path.join(where, name + ".json")) as f:
        return json.load(f)


def published():
    return config(CONFIG, os.path.join(ROOT, "chipbench", "configs"))


def catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return None
    with open(catalog) as f:
        return next(e for e in map(json.loads, f) if e["name"] == "Laguna-XS.2")


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_seeded_weights_are_pinned(seed):
    made = weights.make(FAMILY.spec(config()), seed, "bfloat16")
    digest = hashlib.sha256()
    for tensor in sorted(made):
        x = np.asarray(made[tensor])
        digest.update(f"{tensor}:{x.dtype}:{x.shape}:".encode())
        digest.update(x.tobytes())
    assert digest.hexdigest() == DIGESTS[seed]


def test_family_gives_what_its_cells_generator_and_readers_ask():
    assert all(hasattr(FAMILY, name) for name in open_loop_rounds.FAMILY_GIVES)
    for name in ("weight_bytes_per_decode_step", "cache_bytes_per_decode_step", "attention_bytes", "attention_shape", "page_bytes",
                 "layers_of", "expert_layers", "expert_products_bytes", "held_experts"):
        assert callable(getattr(FAMILY, name)), name


def test_configuration_file_holds_every_published_number():
    """Against the catalog's entry where the catalog is beside the guides (the driver checks the same before any
    run): every key as published but the depth, the experts held and the context, the three lists a layer whole;
    the file states the deployment, the published counts and every assumed point."""
    cfg = published()
    assert set(cfg["reduced"]) == CUT and "v5e-8" in cfg["deployment"] and "expert parallelism" in cfg["deployment"]
    assert {"gate", "q/k norms", "router", "gate_std", "router_std", "expert_bias_std", "norm scales", "rotary pairs"} <= set(cfg["assumed"])
    assert (cfg["num_hidden_layers"], cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"], cfg["head_dim"]) == (13, 2048, 8192, 100352, 128)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["sliding_window"], cfg["moe_intermediate_size"]) == (48, 8, 512, 512)
    assert (cfg["num_experts"], cfg["router_experts"], cfg["expert_shares"], cfg["expert_share"], cfg["num_experts_per_tok"]) == (64, 256, 4, 0, 8)
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == len(cfg["num_attention_heads_per_layer"]) == 40, "the lists stand as published"
    assert [FAMILY.layer_type(cfg, i)[0] for i in range(13)] == list("fsssfsssfsssf") and FAMILY.expert_layers(cfg) == 12
    assert (FAMILY.layers_of(cfg, FAMILY.FULL), FAMILY.layers_of(cfg, FAMILY.WINDOW)) == (4, 9)
    assert cfg["published"]["num_hidden_layers"] == 40 and cfg["published"]["num_experts"] == 256
    entry = catalog_entry()
    if entry is not None:
        assert entry["source_url"] == cfg["source"]
        assert {k for k, v in entry["config"].items() if cfg.get(k, "absent") != v} == CUT
    listed = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert set(listed["reduced"]) == CUT and listed["source"] == cfg["source"] and listed["file"] == f"chipbench/configs/{CONFIG}.json"
    s = cfg["bench"]["serving"]
    assert (s["num_slots"], s["paged_block_size"], s["pool_blocks"], s["window_pool_blocks"], s["max_len"], s["prompt_buckets"], s["tick_block"]) == (
        64, 16, 32 * 320 + 1, 64 * 34 + 1, 5120, [256, 1024, 4096], 8)  # the full pool at half its dense equivalent


def test_counts_at_the_published_widths_are_the_issues_arithmetic():
    cfg = published()
    assert (FAMILY.attention_params(cfg, 0), FAMILY.attention_params(cfg, 1), FAMILY.expert_params(cfg)) == (29_458_688, 37_880_064, 3_145_728)
    count = lambda i: sum(int(np.prod(shape)) for name, (shape, _) in FAMILY.spec(cfg).items() if name.startswith(FAMILY.name(i, "")))  # noqa: E731
    assert (count(0), count(1), count(4)) == (79_794_432, 242_881_024, 234_459_648)
    assert weights.count(FAMILY.spec(cfg)) == FAMILY.params(cfg) == 3_380_146_432  # 6.76 GB in bf16
    whole = dict(cfg, num_hidden_layers=40, num_experts=256, expert_shares=1)
    assert 33.3e9 < weights.count(FAMILY.spec(whole)) < 33.5e9, "33.4B"
    # a page of 16 rows is 65,536 B a layer: the two pools, and one table for all thirteen layers
    assert (FAMILY.page_bytes(cfg, FAMILY.FULL, 16), FAMILY.page_bytes(cfg, FAMILY.WINDOW, 16)) == (4 * 65_536, 9 * 65_536)
    full, window = 20_481 * FAMILY.page_bytes(cfg, FAMILY.FULL, 16), 2_177 * FAMILY.page_bytes(cfg, FAMILY.WINDOW, 16)
    assert 5.36e9 < full < 5.38e9 and 1.28e9 < window < 1.29e9 and 17.4e9 < 20_481 * 13 * 65_536 < 17.5e9  # dense-equivalent; the cell runs the full pool at 10,241
    # a slot at 5,120 tokens: 273 MB under one table, 104 MB under two
    assert 13 * 5120 * 4096 == 272_629_760 and (4 * 5120 + 9 * 544) * 4096 == 103_940_096
    # rows by kind: 32 slots at 1,650 tokens read 1.5 GB of rows a step where one table would read 2.8
    rows = FAMILY.cache_bytes_per_decode_step(cfg, 32 * 1650, 32 * 512, 32)
    assert 1.47e9 < rows < 1.5e9 and 2.8e9 < FAMILY.cache_bytes_per_decode_step(cfg, 32 * 1650, 32 * 1650, 32) < 2.83e9
    per_row = FAMILY.attention_bytes(cfg, FAMILY.WINDOW, 1001, 1) - FAMILY.attention_bytes(cfg, FAMILY.WINDOW, 1000, 1)
    assert per_row == 9 * 4096 and FAMILY.attention_bytes(cfg, FAMILY.FULL, 0, 1) == 4 * 2 * 2 * 48 * 128
    outside = 2 * FAMILY.params_outside_experts(cfg)
    assert 1.51e9 < outside < 1.53e9 and FAMILY.weight_bytes_per_decode_step(cfg, 0, experts_touched=0) == outside
    assert FAMILY.weight_bytes_per_decode_step(cfg, 0) == outside + 2 * 12 * 64 * 3_145_728


def _program(cfg, flat):
    from accelerate_tpu.models.llama import _wrap_llama
    from chipbench.builders._tree import check_same_shapes, to_tree

    builder = run.load(M, "builders", cfg["bench"]["builder"])
    core = builder.core_config(cfg)
    module, shapes = builder.abstract_params(core)
    tree = to_tree(flat, builder.table(cfg), core.num_hidden_layers)
    check_same_shapes(tree, shapes)
    return _wrap_llama(module, tree, core)


@pytest.fixture(scope="module")
def toy():
    """The toy configuration's program (float32 weights from the seed, through the cell's own builder table) beside
    the same weights for the reference: five layers ``f s s s f``, 6 / 8 query heads on 2 key/value heads of 16, a
    window of 8, two of the router's eight experts held."""
    cfg = config()
    flat = weights.make(FAMILY.spec(cfg), 7, "float32")
    return cfg, flat, _program(cfg, flat)


TOKENS = np.random.default_rng(0).integers(5, 250, size=100).astype(np.int32)


def _reference(flat, cfg, tokens=TOKENS, **how):
    return np.asarray(FAMILY.logits_at(flat, cfg, jnp.asarray(tokens), jnp.arange(len(tokens)), **how))


def test_program_forward_is_the_reference(toy):
    """Float32 on both sides, no cache: the program's grouped products over all heads at once, its rotary and gate,
    its dropless expert products over a held share, against the reference's head at a time and expert at a time."""
    cfg, flat, model = toy
    got = np.asarray(model.apply_fn(model.params, jnp.asarray(TOKENS[None])))[0]
    want = _reference(flat, cfg)
    assert np.abs(want).max() > 2.0 and got.shape == (100, 256)
    np.testing.assert_allclose(got, want, atol=TOLERANCE)


def _wrong(rule_change):
    def rule_of(cfg, i):
        return rule_change(FAMILY.rule_of(cfg, i), FAMILY.layer_type(cfg, i))

    return rule_of


def _rotary(rule, **changed):
    return rule._replace(rotary=tuple(sorted({**dict(rule.rotary), **changed}.items())))


def _gate_of_the_full_layers_heads(g):  # 6 heads' worth of gate on an 8-head layer: the last two heads go ungated
    return jnp.where(jnp.arange(g.shape[-1]) < 6, jax.nn.softplus(g), 1.0)


WRONG_RULES = {
    "the band on every layer": lambda rule, kind: rule._replace(window=8),
    "the band on none": lambda rule, kind: rule._replace(window=None),
    "the window layers' rotary on full layers": lambda rule, kind: rule._replace(
        rotary=tuple(sorted({"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}.items()))) if kind == FAMILY.FULL else rule,
    "all values turned on a full layer": lambda rule, kind: _rotary(rule, partial_rotary_factor=1.0) if kind == FAMILY.FULL else rule,
    "no attention_factor": lambda rule, kind: _rotary(rule, attention_factor=1.0) if kind == FAMILY.FULL else rule,
    "no gate": lambda rule, kind: rule._replace(gate=jnp.ones_like),
    "a sigmoid gate": lambda rule, kind: rule._replace(gate=jax.nn.sigmoid),
    "the full layers' heads of gate on a window layer": lambda rule, kind: rule._replace(gate=_gate_of_the_full_layers_heads) if kind == FAMILY.WINDOW else rule,
}


@pytest.mark.parametrize("wrong", sorted(WRONG_RULES) + ["no norm on the queries", "the shared expert left out", "every expert held"])
def test_wrong_rules_miss_by_far_more_than_the_tolerance_the_program_meets(toy, wrong):
    """Each wrong model that the issue names, computed by the reference's own code with one rule changed, lies
    further from the reference than ``TOLERANCE`` by a factor of a hundred and more: none of them could pass for the
    program. Inside the first window the band's two wrong rules agree with the right one."""
    cfg, flat, _ = toy
    want = _reference(flat, cfg)
    changed, other = dict(flat), cfg
    if wrong in WRONG_RULES:
        got = _reference(flat, cfg, rule_of=_wrong(WRONG_RULES[wrong]))
    else:
        for i in range(cfg["num_hidden_layers"]):
            if wrong == "no norm on the queries":
                changed[FAMILY.name(i, "norm_q")] = jnp.full_like(flat[FAMILY.name(i, "norm_q")], 1.0)
            elif wrong == "the shared expert left out" and FAMILY.is_sparse(cfg, i):
                changed[FAMILY.name(i, "shared_down")] = jnp.zeros_like(flat[FAMILY.name(i, "shared_down")])
        if wrong == "every expert held":  # the uncut layer where the share is asked for
            other = dict(cfg, num_experts=8, expert_shares=1)
            changed = weights.make(FAMILY.spec(other), 7, "float32")
            for name in flat:  # the same weights outside the experts, the share's experts first among the eight
                if "experts_" in name:
                    changed[name] = changed[name].at[:2].set(flat[name])
                else:
                    changed[name] = flat[name]
        got = _reference(changed, other)
    miss = np.abs(got - want)[8:].max()
    assert miss > 100 * TOLERANCE, f"{wrong}: {miss}"
    if wrong == "the band on none":
        assert np.abs(got - want)[:8].max() <= TOLERANCE, "inside the first window the rules agree"


def test_the_four_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer(toy):
    """One sparse layer's feed-forward: every share computes its held experts' part of the routed sum and the shared
    expert; the four routed parts and ONE shared expert are the layer with all eight experts held."""
    cfg, flat, _ = toy
    whole = dict(cfg, num_experts=8, expert_shares=1)
    w = {k: v for k, v in FAMILY.layer_weights(weights.make(FAMILY.spec(whole), 11, "float32"), whole, 1).items()}
    z = jax.random.normal(jax.random.key(3), (40, 64))
    dot = FAMILY.DOTS["exact"]
    uncut = FAMILY.routed_ffn(z, w, whole, dot)
    parts = []
    for share in range(4):
        held = {k: (v[2 * share : 2 * share + 2] if k.startswith("experts_") else v) for k, v in w.items()}
        parts.append(FAMILY.routed_ffn(z, held, dict(cfg, expert_share=share), dot))
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(uncut), atol=2e-6)
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts), "every share holds somebody's expert"
    weights_of = np.asarray(FAMILY.routing(z, w, whole))
    assert ((weights_of > 0).sum(-1) == 2).all() and np.allclose(weights_of.sum(-1), 2.5, atol=1e-5), "two a token, normalised, times 2.5"


PROMPTS = (5, 12, 32, 45, 64, 30, 3)
NEW_TOKENS = (40, 30, 20, 60, 11, 9, 70)  # past two turns of a ring of four pages of four, finishing mid-tick


@pytest.mark.parametrize("layout", ["paged_xla_gather", "paged_kernel_interpreted"])
def test_prefill_then_decode_is_the_references_full_forward(toy, layout, monkeypatch):
    """Through ``ServingEngine``: a bucket's prefill, the paste into both pools (the window layers' last pages into
    their ring), and the decode tick through both tables (XLA's gather or the interpreted kernel, two shapes of it),
    three slots at once, ticks of eight steps; against one full forward of the reference over prompt and served
    tokens. Logits, not tokens: the served token's log-probability (the engine's float32 log-softmax) is the
    reference's within ``TOLERANCE``, and the reference's best logit is no more than that above the served token's."""
    from accelerate_tpu.ops import paged_kv
    from accelerate_tpu.serving import ServingEngine

    cfg, flat, model = toy
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", layout == "paged_kernel_interpreted")
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(8, 32, 64), max_len=128, tick_block=8, paged_block_size=4)
    assert engine._ring == 4
    rng = np.random.default_rng(1)
    prompts = [rng.integers(5, 250, size=n).astype(np.int32) for n in PROMPTS]
    uids = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, NEW_TOKENS)]
    engine.run()
    for uid, prompt in zip(uids, prompts):
        served, lps = np.asarray(engine.partial(uid)), np.asarray(engine.logprobs(uid))
        tokens = np.concatenate([prompt, served])
        ref = FAMILY.logits_at(flat, cfg, jnp.asarray(tokens), jnp.arange(len(prompt) - 1, len(tokens) - 1))
        want = np.asarray(jax.nn.log_softmax(ref, axis=-1))[np.arange(len(served)), served]
        np.testing.assert_allclose(lps, want, atol=TOLERANCE)
        assert float((ref.max(axis=-1) - ref[jnp.arange(len(served)), served]).max()) < TOLERANCE
    assert engine.metrics.window_rows_read < engine.metrics.context_rows
    assert len({tuple(np.asarray(engine.partial(u))[:9]) for u in uids}) == len(uids), "the sequences differ"


# -- the readers, on hand-built ticks

def _ticks():
    full = "%paged_decode_attention.3 = bf16[64,48,128]{2,1,0} custom-call(s32[64,320]{1,0} %table), custom_call_target=\"tpu_custom_call\""
    window = "%paged_decode_attention_w512.5 = bf16[64,64,128]{2,1,0} custom-call(s32[64,34]{1,0} %ring), custom_call_target=\"tpu_custom_call\""
    fusion = "%fusion.9 = bf16[64,64,128]{2,1,0} fusion(bf16[64,64,128]{2,1,0} %paged_decode_attention_w512.5, bf16[2048]{0} %p), kind=kLoop"
    ops = [(full, 0.00030), (window, 0.00020), (fusion, 0.0001), ("%fusion.2 = bf16[64,8192]{1,0} fusion()", 0.0014)]
    return [{"stats": {"admitted": 0, "context_rows": 8 * 30 * 1600, "window_rows_read": 8 * 30 * 500, "full_pages": 3000, "window_pages": 900,
                       "experts_touched": 8 * 12 * 40}, "ops": ops * 32, "dispatch": {"decoding": 30, "live_tokens": 48000, "tick_block": 8}},
            {"stats": {"admitted": 1, "context_rows": 8 * 28 * 1500, "window_rows_read": 8 * 28 * 480, "full_pages": 2800, "window_pages": 880,
                       "experts_touched": 8 * 12 * 38}, "ops": ops * 32, "dispatch": {"decoding": 28, "live_tokens": 42000, "tick_block": 8}},
            {"stats": {"admitted": 0, "context_rows": 8 * 28 * 1500, "window_rows_read": 8 * 28 * 480, "full_pages": 2800, "window_pages": 880,
                       "experts_touched": 8 * 12 * 38}, "ops": None, "dispatch": {"decoding": 28, "live_tokens": 42000, "tick_block": 8}}]


def _observed():
    return {"config": published(), "family": FAMILY, "device": {"kind": "TPU v5 lite"}}


def _kind_bytes(layers, heads, rows, steps):  # a kind's keys and values of the rows, queries and outputs of the slot-steps
    return layers * 2 * (2 * rows * 1024 + 2 * steps * heads * 128)


_OUTSIDE = 2 * (4 * 29_458_688 + 9 * 37_880_064 + 13 * 4096 + 3 * 2048 * 8192 + 12 * (2048 * 256 + 256 + 3_145_728) + 2048 + 2048 * 100_352)


@pytest.mark.parametrize("reader,want", [
    ("mixed_rows_read_share", 100.0 * (4 * (30 * 1600 + 2 * 28 * 1500) + 9 * (30 * 500 + 2 * 28 * 480)) / (13 * (30 * 1600 + 2 * 28 * 1500))),
    ("window_pages_share", 100.0 * 9 * (900 + 2 * 880) / (9 * (900 + 2 * 880) + 4 * (3000 + 2 * 2800))),
    # the two ticks with operations: 32 calls of each kernel, by its own name; the window kernel's name begins with the full kernel's
    ("full_decode_attention_roofline", 100.0 * (_kind_bytes(4, 48, 8 * 30 * 1600, 240) + _kind_bytes(4, 48, 8 * 28 * 1500, 224)) / 819e9 / (2 * 32 * 0.00030)),
    ("window_decode_attention_roofline", 100.0 * (_kind_bytes(9, 64, 8 * 30 * 500, 240) + _kind_bytes(9, 64, 8 * 28 * 480, 224)) / 819e9 / (2 * 32 * 0.00020)),
    # the tick that admitted nothing and has operations: the weights outside the experts eight times, the experts it touched, its rows by kind
    ("mixed_decode_roofline_share", 100.0 * (8 * (_OUTSIDE + 2 * 30 * 2048) + 2 * 8 * 12 * 40 * 3_145_728 + _kind_bytes(4, 48, 8 * 30 * 1600, 240)
                                             + _kind_bytes(9, 64, 8 * 30 * 500, 240)) / 819e9 / (32 * 0.0020)),
])
def test_new_readers_on_hand_built_ticks(reader, want, monkeypatch):
    module = run.load(M, "layers", reader)
    monkeypatch.setattr(module._mixed_ticks._decode_programs, "decode_ticks", lambda observed: _ticks())
    got = module.read(_observed())
    assert got == pytest.approx(want, rel=1e-12) and 0 < got <= 100


@pytest.mark.parametrize("reader", NEW)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(reader, monkeypatch):
    """As on a program without the counts (the parent), in a cell whose model has one kind of layer (the counts are
    0), and on a trace that names no operation (a CPU's): ``None``, and nothing raised."""
    module = run.load(M, "layers", reader)
    for stats in ({"admitted": 0}, {"admitted": 0, "context_rows": 0, "window_rows_read": 0, "full_pages": 0, "window_pages": 0}):
        none = [{"stats": stats, "ops": [("fusion.1", 0.01)], "dispatch": {"decoding": 3, "live_tokens": 9, "tick_block": 8}}]
        monkeypatch.setattr(module._mixed_ticks._decode_programs, "decode_ticks", lambda observed, none=none: none)
        assert module.read(_observed()) is None
    monkeypatch.setattr(module._mixed_ticks._decode_programs, "decode_ticks", lambda observed: [])
    assert module.read(_observed()) is None
    if reader.endswith(("_roofline", "_roofline_share")):
        monkeypatch.setattr(module._mixed_ticks._decode_programs, "decode_ticks", lambda observed: [dict(t, ops=None) for t in _ticks()])
        assert module.read(_observed()) is None


# -- the toy cell, end to end on the CPU

def test_rehearsal_runs_the_laguna_cell(rehearse):  # noqa: F811
    lines = rehearse("tiny-serve-longchat-ep4", "--trace", "0", "--control", "1", manifest=LAGUNA, seconds="3")
    last = result(lines)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"]["ttft_p90_ms"]["value"] > 0 and last["metrics"]["tpot_p90_ms"]["value"] > 0
    checks = {l["check"]: l for l in lines if "check" in l}
    assert checks["compiles_in_window"]["value"] == 0 and checks["token_count_wrong"]["value"] == 0
    assert next(l for l in lines if l.get("note") == "control")["would_pass"] is False


def test_traced_rehearsal_reads_the_counts_by_kind(rehearse):  # noqa: F811
    last = result(rehearse("tiny-serve-longchat-ep4", "--trace", "1", manifest=LAGUNA, seconds="3"))
    assert last["correct"] is True
    assert 40 <= last["metrics"]["mixed_rows_read_share"]["value"] < 100, "every toy request leaves its first window of 8"
    assert 10 <= last["metrics"]["window_pages_share"]["value"] <= 60
    assert 15 <= last["metrics"]["held_expert_pairs_share"]["value"] <= 40, "two of the router's eight experts are held"
    assert "engine_decode_step_ms" in last["metrics"] and "warm_programs" in last["metrics"] and "tick_longest_ms" in last["metrics"]
    assert not any("roofline" in name for name in last["metrics"]), "no share of a peak from a CPU"


def test_toy_manifest_names_only_files_of_its_own():
    with open(LAGUNA) as f:
        stated = json.load(f)
    assert [c["file"] for c in stated["configs"]] == ["tests/chipbench/configs/laguna-tiny.json"]
    assert [w["traffic"] for w in stated["workloads"]] == ["longchat-ep4-tiny"]
    real = {m["name"]: m for m in M["per_layer"]}
    for m in stated["per_layer"]:
        assert {k: v for k, v in m.items() if k != "workloads"} == {k: v for k, v in real[m["name"]].items() if k != "workloads"}
    assert {m["name"] for m in stated["per_layer"]} == {m["name"] for m in M["per_layer"] if CELL in m.get("workloads", ())}


def test_traffic_file_is_the_accepted_round_with_a_rate_of_its_own():
    with open(os.path.join(ROOT, "chipbench", "traffic", "longchat-ep4.json")) as f:
        mine = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "traffic", "longchat.json")) as f:
        accepted = json.load(f)
    assert mine["prompt_tokens"] == accepted["prompt_tokens"] and mine["new_tokens"] == accepted["new_tokens"], "longchat.json's lists to the number"
    assert (sum(mine["prompt_tokens"]) / 16, sum(mine["new_tokens"]) / 16) == (1228.0, 418.0)
    assert sum(p > 512 for p in mine["prompt_tokens"]) == 10 and mine["generator"] == "open_loop_rounds" and mine["reference_pad"] == 5120
    rounds = mine["rate_per_s"] * 51 / 16
    assert abs(rounds - round(rounds)) < 1e-3 and rounds >= 10, "a whole number of rounds a window, ten at the least"
    limits = mine["limits"][CONFIG]
    assert 0 < limits["logit_gap_mean"] < limits["logit_gap_max"] and "rate_note" in mine and "limits_note" in mine


def test_manifest_gained_the_cell_and_nothing_that_was_there_moved():
    """Entries were appended: the seven cells and configurations of PR 46's manifest stand first and as they were,
    every metric of it stands in its place with its fields, and a list of cells that gained this one gained it behind
    the cells it had. Later cells may follow: nothing here counts the entries."""
    cells = [w["name"] for w in M["workloads"]]
    before = ["mistral7b-serve-chat", "bert-base-train-seq128", "joyai-flash-serve-longchat", "jamba2-3b-serve-longanswer",
              "lfm2-8b-a1b-serve-longanswer", "granite-4.0-h-small-serve-longanswer", "evabyte-serve-longchat"]
    assert cells[:7] == before and CELL in cells[7:] and all(w["chips"] == 1 for w in M["workloads"][:8])
    configs = [c["name"] for c in M["configs"]]
    assert configs[:7] == ["bert-base-uncased", "mistral-7b-v0.1-l16", "joyai-llm-flash-l5", "ai21-jamba2-3b", "lfm2-8b-a1b-l16",
                           "granite-4.0-h-small-l10", "evabyte-6.5b-l8"] and CONFIG in configs[7:]
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "longchat-ep4", "chips": 1, "why": cell["why"]} and len(cell["why"]) <= 200
    assert [m["name"] for m in M["end_to_end"]] == ["train_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"]
    assert [(m["bound"], m["better"]) for m in M["end_to_end"]] == [(0.01, "higher"), (0.1, "lower"), (0.06, "lower"), (0.1, "lower")]
    assert M["run_seconds"] == 51 and M["command"] == ["python3", "-m", "chipbench"] and M["paths"] == ["chipbench", "tests/chipbench"]
    names = [m["name"] for m in M["per_layer"]]
    assert names[29:33] == ["eva_rows_read_share", "eva_summary_pages_share", "eva_decode_attention_roofline", "eva_decode_roofline_share"]
    assert set(names[33:]) >= set(NEW)
    reports = {m["name"] for m in M["per_layer"] if CELL in m.get("workloads", ())}
    assert reports >= {"chat_idle_share", "generator_late_p90_ms", "queue_wait_p90_ms", "warm_programs", "first_token_hold_p50_ms",
                       "engine_prefill_ms_per_ktok", "engine_decode_step_ms", "tick_host_ms", "chat_prefill_device_share",
                       "tick_longest_ms", "tick_longest_sync_ms", "tick_longest_cpu_ms", "routed_experts_roofline",
                       "held_expert_pairs_share", *NEW}
    # the two accepted readers that price the contexts' sum on every layer, 1.9 times what this cell reads: not this cell's
    assert not reports & {"paged_decode_attention_roofline", "decode_roofline_share", "eva_rows_read_share"}
    for m in M["end_to_end"] + M["per_layer"]:
        listed = m.get("workloads", [])
        if CELL in listed:
            rest = [c for c in listed if c in before]
            assert listed[: len(rest)] == rest and listed.index(CELL) >= len(rest), f"{m['name']}: the cell was appended"
    for name, better, source, layer in (("mixed_rows_read_share", "lower", "program_counter", "ops/paged_kv cache"),
                                        ("window_pages_share", "lower", "program_counter", "scheduler"),
                                        ("full_decode_attention_roofline", "higher", "device_trace", "kernels"),
                                        ("window_decode_attention_roofline", "higher", "device_trace", "kernels"),
                                        ("mixed_decode_roofline_share", "higher", "device_trace", "jitted programs")):
        new = next(m for m in M["per_layer"] if m["name"] == name)
        assert new == {"name": name, "unit": "%", "better": better, "source": source, "layer": layer, "moves": "tpot_p90_ms",
                       "workloads": new["workloads"]} and new["workloads"][0] == CELL
