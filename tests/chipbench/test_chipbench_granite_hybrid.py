"""The family ``granite_hybrid`` (Mamba-2 layers beside attention without positions, routed experts by a
softmax over the top k with a shared one, Granite's four multipliers, a share of the experts and of the
vocabulary held): its seeded weights, the program against its plain reference at a toy size on the CPU
(logits, not tokens), what a share leaves out and that the shares add up to the uncut reference, each
multiplier, its configuration file and cost counts, the two readers this family's cell brought, and the
rehearsal of a toy cell made only of files of its own (``rehearsal-granite.json``)."""

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
GRANITE = os.path.join(HERE, "rehearsal-granite.json")
CELL, CONFIG = "granite-4.0-h-small-serve-longanswer", "granite-4.0-h-small-l10"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    M = json.load(f)
FAMILY = run.load(M, "reference", "granite_hybrid")

# sha256 over every tensor (name, type, shape, bytes; names sorted) of ``weights.make`` on the host's CPU, when
# the family was written (PR 40): a later change to ``spec`` that moves a seeded weight shows here
DIGESTS = {5: "20001f4e32e4a1f38c4f8543eafc14fa12c9ea657fd4d1756c11a092dc741c48",
           2**31 + 99: "928cbd6a7fd141a4f29c2599e37d928424fd162f3cef3ca45c1c10da7378aa5f"}
CUT = {"num_hidden_layers", "layer_types", "num_local_experts", "vocab_size", "max_position_embeddings"}


def config(name="granite-tiny", where=os.path.join(HERE, "configs")):
    with open(os.path.join(where, name + ".json")) as f:
        return json.load(f)


def published():
    return config(CONFIG, os.path.join(ROOT, "chipbench", "configs"))


def catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return None
    with open(catalog) as f:
        return next(e for e in map(json.loads, f) if e["name"] == "granite-4.0-h-small")


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
    for name in ("weight_bytes_per_decode_step", "cache_bytes_per_decode_step", "attention_shape", "expert_layers", "expert_params",
                 "expert_products_bytes", "mamba_layers", "ssd_state_step_bytes"):
        assert callable(getattr(FAMILY, name)), name


def test_configuration_file_holds_every_published_number():
    """Against the catalog's entry where the catalog is beside the guides (the driver checks the same before
    any run): every key as published but the depth, the list of layer kinds cut with it, the experts held,
    the vocabulary's slice and the context; the file states the deployment, the published counts and what
    was assumed."""
    cfg = published()
    assert set(cfg["reduced"]) == CUT and cfg["assumed"] and "expert parallelism" in cfg["deployment"] and "two" in cfg["deployment"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"], cfg["hidden_size"], cfg["intermediate_size"]) == (10, 50176, 4096, 768)
    assert (cfg["num_local_experts"], cfg["router_experts"], cfg["expert_shares"], cfg["expert_share"], cfg["num_experts_per_tok"]) == (36, 72, 2, 0, 10)
    assert (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["shared_intermediate_size"]) == (128, 64, 128, 4, 1536)
    assert (cfg["embedding_multiplier"], cfg["residual_multiplier"], cfg["attention_multiplier"], cfg["logits_scaling"]) == (12, 0.22, 0.0078125, 16)
    assert "".join(t[0] for t in cfg["layer_types"]) == "mmmmmammmm"
    assert cfg["published"]["num_local_experts"] == 72 and cfg["published"]["vocab_size"] == 100352 and cfg["published"]["num_hidden_layers"] == 40
    entry = catalog_entry()
    if entry is not None:
        assert entry["source_url"] == cfg["source"]
        differs = {k for k, v in entry["config"].items() if cfg.get(k, "absent") != v}
        assert differs == CUT
        assert cfg["layer_types"] == entry["config"]["layer_types"][:10], "the first period as published"
        assert cfg["router_experts"] == entry["config"]["num_local_experts"] and 2 * cfg["vocab_size"] == entry["config"]["vocab_size"]
    listed = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert set(listed["reduced"]) == CUT and listed["source"] == cfg["source"] and listed["file"] == f"chipbench/configs/{CONFIG}.json"
    s = cfg["bench"]["serving"]
    assert (s["num_slots"], s["paged_block_size"], s["pool_blocks"], s["max_len"], s["prompt_buckets"]) == (64, 16, 64 * 144 + 1, 2304, [64, 256, 1024])


def test_counts_at_the_published_widths_are_the_issues_arithmetic():
    cfg = published()
    assert weights.count(FAMILY.spec(cfg)) == 4_757_211_776  # 9.51 GB in bf16
    assert FAMILY._mixer_params(cfg, False) == 102_286_976 and FAMILY._mixer_params(cfg, True) == 41_943_040
    assert FAMILY.expert_params(cfg) == 9_437_184 and FAMILY.expert_layers(cfg) == 10
    assert (FAMILY.attention_layers(cfg), FAMILY.mamba_layers(cfg), FAMILY.attention_shape(cfg)) == (1, 9, (32, 8, 128))
    assert FAMILY.held_experts(cfg) == (0, 36, 72)
    # a Mamba-2 layer 461.2 M, the attention layer 400.9 M, at 36 experts; whole (72 experts) a Mamba-2 layer is 800.9 M
    ffn = 2 * 4096 + 3 * 4096 * 1536 + 4096 * 72 + 36 * 9_437_184
    assert (102_286_976 + ffn, 41_943_040 + ffn, 102_286_976 + ffn + 36 * 9_437_184) == (461_203_072, 400_859_136, 800_941_696)
    whole = dict(cfg, num_local_experts=72, expert_shares=1, vocab_size=100352)
    assert weights.count(FAMILY.spec(whole)) == 4_757_211_776 + 10 * 36 * 9_437_184 + 50176 * 4096
    # the state: 128 x 8192 float32 a sequence a layer, and the convolution's three rows of 8,448 in bf16
    assert 128 * 8192 * 4 == 4_194_304 and FAMILY.state_bytes_per_slot(cfg) == 9 * (4_194_304 + 3 * 8448 * 2) == 38_204_928
    assert FAMILY.ssd_state_step_bytes(cfg, 1) - FAMILY.ssd_state_step_bytes(cfg, 0) == 8_388_608 + 8192 * 4 + 128 * 4 + 2 * 128 * 2
    assert 64 * 9 * 4_194_304 == 2_415_919_104  # 2.42 GB of state at 64 slots
    # a decode step at 40 decoding slots: every held expert is reached (0.3 % stay empty), 9.50 of 9.51 GB of weights
    step = FAMILY.weight_bytes_per_decode_step(cfg, 40)
    assert 9.49e9 < step < 9.515e9 and 0.995 < FAMILY.expected_experts_touched(cfg, 40) / 36 < 0.998
    assert 0.70 < 10 * 36 * 9_437_184 * 2 / step < 0.72, "the held experts are 71 % of a decode step's weights"
    cache = FAMILY.cache_bytes_per_decode_step(cfg, 40 * 600, 40)
    assert cache == 2 * (2 * 24000 * 1024 + 2 * 40 * 4096) + 9 * (FAMILY.ssd_state_step_bytes(cfg, 40) + 2 * 40 * 3 * 8448 * 2)
    assert 3.0e9 < 9 * FAMILY.ssd_state_step_bytes(cfg, 40) < 3.05e9, "the state steps move 3.0 GB a step at 40 decoding slots"
    pool = cfg["bench"]["serving"]["pool_blocks"] * 16 * 8 * 128 * 2 * 2
    assert 0.60e9 < pool < 0.61e9
    # the tick's grouped products: of 64 slots x 10 experts x 10 layers x 8 steps routed pairs, the held half's activations
    pairs = 64 * 10 * 10 * 8
    assert FAMILY.expert_products_bytes(cfg, 10 * 36 * 8, pairs) == 2 * (2880 * 9_437_184 + pairs / 2 * (2 * 4096 + 4 * 768))
    assert FAMILY.expert_products_flops(cfg, pairs) == 2.0 * pairs / 2 * 9_437_184


def _program(cfg, flat):
    from accelerate_tpu.models.llama import _wrap_llama
    from chipbench.builders._tree import check_same_shapes, to_tree

    builder = run.load(M, "builders", cfg["bench"]["builder"])
    core = builder.core_config(cfg)
    module, shapes = builder.abstract_params(core)
    tree = to_tree(builder.with_constants(flat, cfg), builder.table(cfg), core.num_hidden_layers)
    check_same_shapes(tree, shapes)
    return _wrap_llama(module, tree, core)


@pytest.fixture(scope="module")
def toy():
    """The toy configuration's program (float32 weights from the seed, through the cell's own builder table
    and the family's map of the recurrence's constants) beside the same weights for the reference."""
    cfg = config()
    flat = weights.make(FAMILY.spec(cfg), 7, "float32")
    return cfg, flat, _program(cfg, flat)


TOKENS = np.random.default_rng(0).integers(5, 250, size=40).astype(np.int32)


def test_program_forward_is_the_reference(toy):
    """Float32 on both sides, no cache, share 0 of 2 on both: the program's chunked scan (chunks of 8 over 40
    tokens), carried convolution, fused attention and sorted grouped products over the held experts against
    the reference's token loop, shifted products, per-head attention and loop over every held expert: the
    same terms in another order: 2e-5 on logits of size 0.3 and more (the toy divides them by 0.5). The state in bfloat16 or a multiplier
    left out moves them by 1e-3 and more (below)."""
    cfg, flat, model = toy
    got = np.asarray(model.apply_fn(model.params, jnp.asarray(TOKENS[None])))[0]
    want = np.asarray(FAMILY.logits_at(flat, cfg, jnp.asarray(TOKENS), jnp.arange(40)))
    assert np.abs(want).max() > 0.3 and got.shape == (40, 256)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("key,moved", [("embedding_multiplier", 6), ("residual_multiplier", 0.5), ("attention_multiplier", 0.25),
                                       ("logits_scaling", 2), ("expert_share", 1)])
def test_each_multiplier_and_the_share_move_the_reference_as_they_move_the_program(toy, key, moved):
    """One key moved alone: the reference's logits move (a multiplier left out of either side could not
    pass the comparison above), and the program built from the moved configuration follows them."""
    cfg, flat, model = toy
    base = np.asarray(FAMILY.logits_at(flat, cfg, jnp.asarray(TOKENS), jnp.arange(40)))
    other = dict(cfg, **{key: moved})
    want = np.asarray(FAMILY.logits_at(flat, other, jnp.asarray(TOKENS), jnp.arange(40)))
    assert np.abs(want - base).max() > 1e-3
    got = np.asarray(_program(other, flat).apply_fn(model.params, jnp.asarray(TOKENS[None])))[0]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_shares_of_the_reference_add_up_to_its_uncut_layer(toy):
    """Guide section 4: the feed-forward of one layer under share 0 and under share 1 (each the whole router,
    its four experts' matrices, the shared expert), the shared expert counted once, is the uncut layer's
    (eight experts, one share); and the program's two shares are the reference's two."""
    import dataclasses

    from accelerate_tpu.models.llama import RoutedFFN

    cfg, _, _ = toy
    uncut_cfg = dict(cfg, num_local_experts=8, expert_shares=1, expert_share=0)
    flat = weights.make(FAMILY.spec(uncut_cfg), 11, "float32")
    w = {n: flat[FAMILY.name(1, n)] for n in FAMILY.COMMON}
    assert w["experts_gate"].shape == (8, 64, 32) and w["router"].shape == (64, 8)
    h = jax.random.normal(jax.random.key(3), (9, cfg["hidden_size"]))
    dot = FAMILY.DOTS["exact"]
    uncut = FAMILY.feed_forward(h, w, uncut_cfg, dot)
    shared = FAMILY._swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"], dot)
    builder = run.load(M, "builders", cfg["bench"]["builder"])
    parts = []
    for share in (0, 1):
        scfg = dict(cfg, expert_share=share)
        sw = dict(w, **{n: w[n][4 * share : 4 * share + 4] for n in ("experts_gate", "experts_up", "experts_down")})
        parts.append(FAMILY.feed_forward(h, sw, scfg, dot))
        core = dataclasses.replace(builder.core_config(scfg), num_hidden_layers=1)
        params = {"router/kernel": sw["router"], "experts/gate_proj": sw["experts_gate"], "experts/up_proj": sw["experts_up"],
                  "experts/down_proj": sw["experts_down"],
                  "shared_experts": {k: {"kernel": sw[f"shared_{k[:-5]}"]} for k in ("gate_proj", "up_proj", "down_proj")}}
        program = RoutedFFN(core).apply({"params": params}, h[None])[0]
        np.testing.assert_allclose(np.asarray(program), np.asarray(parts[-1]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] - shared), np.asarray(uncut), atol=1e-5)
    assert float(jnp.abs(parts[0] - uncut).max()) > 1e-2, "one share alone is a partial layer"


def test_reference_recurrence_remembers_and_its_routing_is_a_softmax_over_the_top(toy):
    """A wrong recurrence must not pass: through one Mamba-2 layer alone, the output at the last token moves
    with the input thirty tokens back (a state that forgot in a few tokens would not show it) and with the
    input three tokens back through the convolution, and the constants lie where Mamba-2's initialiser puts
    them; the routing gives every token exactly k experts whose weights sum to 1."""
    cfg, flat, _ = toy
    w = {n: flat[FAMILY.name(0, n)] for n in FAMILY.MAMBA}
    x = jax.random.normal(jax.random.key(0), (40, cfg["hidden_size"]))
    base = FAMILY.mamba2(x, w, cfg, FAMILY.DOTS["exact"])
    for back in (3, 30):
        other = FAMILY.mamba2(x.at[39 - back].add(1.0), w, cfg, FAMILY.DOTS["exact"])
        assert float(jnp.abs(other[39] - base[39]).max()) > 1e-4, back
        assert float(jnp.abs(other[: 39 - back] - base[: 39 - back]).max()) == 0.0, "causal"
    dt_bias, a_log = FAMILY.ssd_constants(jax.random.normal(jax.random.key(1), (4096,)), jax.random.normal(jax.random.key(2), (4096,)))
    step, a = np.asarray(jax.nn.softplus(dt_bias)), np.exp(np.asarray(a_log))
    assert 0.001 <= step.min() < 0.0012 and 0.09 < step.max() <= 0.1 and 1.0 <= a.min() < 1.1 and 15.9 < a.max() <= 16.0
    assert abs(np.median(a) - 8.5) < 0.5 and abs(np.median(np.log(step)) - np.log(0.01)) < 0.2
    picked = np.asarray(FAMILY.routing(jax.random.normal(jax.random.key(1), (9, 64)), {"router": flat["L00.router"]}, cfg))
    assert picked.shape == (9, 8) and ((picked > 0).sum(axis=1) == 4).all()
    np.testing.assert_allclose(picked.sum(axis=1), 1.0, atol=1e-6)


@pytest.mark.parametrize("layout", ["paged_xla_step", "paged_kernels_interpreted"])
def test_prefill_then_decode_is_the_references_full_forward(toy, layout, monkeypatch):
    """Through ``ServingEngine``: bucketed prefill (right pads; the chunked scan over chunks of 8), a prompt over
    the largest bucket (chunk windows with an overlapped head over the carried state), the paste of rows and
    state, and the decode tick (XLA's gather, ``ragged_dot`` and the plain step, or the interpreted kernels with
    the row mask), against one full forward of the reference over prompt and served tokens. Logits, not
    tokens: the served token's log-probability (the engine's float32 log-softmax) is the reference's within
    5e-5, and the reference's best logit is no more than 2e-5 above the served token's: float32 sums in
    another order, nothing else."""
    from accelerate_tpu.ops import paged_kv
    from accelerate_tpu.serving import ServingEngine

    cfg, flat, model = toy
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", layout == "paged_kernels_interpreted")
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(8, 16), max_len=128, tick_block=4, paged_block_size=8)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(5, 250, size=n).astype(np.int32) for n in (5, 13, 30, 9)]
    uids = [engine.submit(p, max_new_tokens=11) for p in prompts]
    engine.run()
    for uid, prompt in zip(uids, prompts):
        served, lps = np.asarray(engine.partial(uid)), np.asarray(engine.logprobs(uid))
        tokens = np.concatenate([prompt, served])
        ref = FAMILY.logits_at(flat, cfg, jnp.asarray(tokens), jnp.arange(len(prompt) - 1, len(tokens) - 1))
        want = np.asarray(jax.nn.log_softmax(ref, axis=-1))[np.arange(len(served)), served]
        np.testing.assert_allclose(lps, want, atol=5e-5)
        assert float((ref.max(axis=-1) - ref[jnp.arange(len(served)), served]).max()) < 2e-5
    assert len({tuple(np.asarray(engine.partial(u))) for u in uids}) == len(uids), "the sequences differ: no token repeats for ever"


# -- the readers, on hand-built ticks

def _ticks():
    fusion = "%fusion.9 = f32[64,128,64]{2,1,0} fusion(f32[64,8192]{1,0} %ssd_state_step.3, f32[128]{0} %p), kind=kLoop"
    kernel = "%ssd_state_step.3 = (f32[64,8192]{1,0}, f32[64,128,8192]{2,1,0}) custom-call(s32[64]{0} %live), custom_call_target=\"tpu_custom_call\""
    ops = [(kernel, 0.00050), (fusion, 0.00003), ("ragged-dot-swiglu.3", 0.0006), ("paged_decode_attention.2", 0.0003)]
    return [{"stats": {"experts_touched": 8 * 10 * 36, "expert_pairs": 8 * 10 * 205, "state_slots_idle": 0}, "ops": ops * 72,
             "dispatch": {"decoding": 40, "live_tokens": 24000, "tick_block": 8}},
            {"stats": {"experts_touched": 8 * 10 * 35, "expert_pairs": 8 * 10 * 190, "state_slots_idle": 0}, "ops": None,
             "dispatch": {"decoding": 38, "live_tokens": 22000, "tick_block": 8}}]


def _observed():
    return {"config": published(), "family": FAMILY, "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("reader,want", [
    # the first tick alone has operations: 72 calls of the kernel itself, 0.5 ms each; the fusion that names it among its operands is not it
    ("ssd_state_step_roofline", 100.0 * 8 * 9 * (40 * 8_422_400 + 1024) / 819e9 / (72 * 0.00050)),
    ("held_expert_pairs_share", 100.0 * (205 / 400 + 190 / 380) / 2),
])
def test_new_readers_on_hand_built_ticks(reader, want, monkeypatch):
    module = run.load(M, "layers", reader)
    monkeypatch.setattr(module._decode_programs, "decode_ticks", lambda observed: _ticks())
    got = module.read(_observed())
    assert got == pytest.approx(want, rel=1e-12) and 0 < got <= 100


@pytest.mark.parametrize("reader", ["ssd_state_step_roofline", "held_expert_pairs_share"])
def test_new_readers_return_nothing_where_there_is_nothing_to_read(reader, monkeypatch):
    """As on a program without the kernel or the count (the parent), in a cell without experts (0), and for a
    family that states no such bytes: ``None``, and nothing raised."""
    module = run.load(M, "layers", reader)
    for stats in ({"admitted": 0}, {"expert_pairs": 0, "experts_touched": 0}):
        none = [{"stats": stats, "ops": [("fusion.1", 0.01)], "dispatch": {"decoding": 3, "live_tokens": 9, "tick_block": 8}}]
        monkeypatch.setattr(module._decode_programs, "decode_ticks", lambda observed, none=none: none)
        assert module.read(_observed()) is None
    monkeypatch.setattr(module._decode_programs, "decode_ticks", lambda observed: [])
    assert module.read(_observed()) is None
    if reader == "ssd_state_step_roofline":
        monkeypatch.setattr(module._decode_programs, "decode_ticks", lambda observed: _ticks())
        assert module.read(dict(_observed(), family=run.load(M, "reference", "hybrid_ssm"))) is None


# -- the toy cell, end to end on the CPU

def test_rehearsal_runs_the_granite_cell(rehearse):  # noqa: F811
    lines = rehearse("tiny-serve-longanswer-ep2", "--trace", "0", "--control", "1", manifest=GRANITE, seconds="3")
    last = result(lines)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"]["ttft_p90_ms"]["value"] > 0 and last["metrics"]["tpot_p90_ms"]["value"] > 0
    checks = {l["check"]: l for l in lines if "check" in l}
    assert checks["compiles_in_window"]["value"] == 0
    assert next(l for l in lines if l.get("note") == "control")["would_pass"] is False


def test_traced_rehearsal_reads_the_tick_counts(rehearse):  # noqa: F811
    last = result(rehearse("tiny-serve-longanswer-ep2", "--trace", "1", manifest=GRANITE, seconds="3"))
    assert last["correct"] is True
    assert 25 <= last["metrics"]["held_expert_pairs_share"]["value"] <= 75  # four of eight experts held
    assert last["metrics"]["state_slots_idle_share"]["value"] == 0, "the interpreted kernel is told which slots decode"
    assert "engine_decode_step_ms" in last["metrics"] and "warm_programs" in last["metrics"] and "tick_longest_ms" in last["metrics"]
    assert not any(name.endswith("_roofline") for name in last["metrics"]), "no share of a peak from a CPU"


def test_toy_manifest_names_only_files_of_its_own():
    with open(GRANITE) as f:
        stated = json.load(f)
    assert [c["file"] for c in stated["configs"]] == ["tests/chipbench/configs/granite-tiny.json"]
    assert [w["traffic"] for w in stated["workloads"]] == ["longanswer-ep2-tiny"]
    real = {m["name"]: m for m in M["per_layer"]}
    for m in stated["per_layer"]:
        assert {k: v for k, v in m.items() if k != "workloads"} == {k: v for k, v in real[m["name"]].items() if k != "workloads"}
    assert {m["name"] for m in stated["per_layer"]} == {m["name"] for m in M["per_layer"] if CELL in m.get("workloads", ())}


def test_manifest_gained_the_cell_and_nothing_that_was_there_moved():
    """Entries were appended: the five cells and five configurations of PR 39's manifest stand first and as
    they were, every metric of it stands in its place with its fields, and a list of cells that gained this
    one gained it behind the cells it had. Later cells may follow: nothing here counts the entries, and the
    set of metrics this cell reports may grow."""
    cells = [w["name"] for w in M["workloads"]]
    before = ["mistral7b-serve-chat", "bert-base-train-seq128", "joyai-flash-serve-longchat", "jamba2-3b-serve-longanswer",
              "lfm2-8b-a1b-serve-longanswer"]
    assert cells[:5] == before and CELL in cells[5:]
    configs = [c["name"] for c in M["configs"]]
    assert configs[:5] == ["bert-base-uncased", "mistral-7b-v0.1-l16", "joyai-llm-flash-l5", "ai21-jamba2-3b", "lfm2-8b-a1b-l16"]
    assert CONFIG in configs[5:]
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "longanswer-ep2", "chips": 1, "why": cell["why"]} and len(cell["why"]) <= 200
    assert [m["name"] for m in M["end_to_end"]] == ["train_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"]
    assert [(m["bound"], m["better"]) for m in M["end_to_end"]] == [(0.01, "higher"), (0.1, "lower"), (0.06, "lower"), (0.1, "lower")]
    assert M["run_seconds"] == 51 and M["command"] == ["python3", "-m", "chipbench"] and M["paths"] == ["chipbench", "tests/chipbench"]
    names = [m["name"] for m in M["per_layer"]]
    assert names[:27] == [
        "train_idle_share", "chat_idle_share", "paged_decode_attention_roofline", "decode_roofline_share", "train_step_ms", "train_mfu",
        "generator_late_p90_ms", "queue_wait_p90_ms", "warm_programs", "first_token_hold_p50_ms", "engine_prefill_ms_per_ktok",
        "engine_decode_step_ms", "tick_host_ms", "chat_prefill_device_share", "train_dispatch_ms", "train_idle_in_dispatch_share",
        "latent_decode_attention_roofline", "routed_experts_roofline", "experts_touched_share", "ssm_state_step_roofline",
        "state_slots_idle_share", "expert_rows_per_visit", "tick_longest_ms", "tick_longest_sync_ms", "tick_longest_cpu_ms",
        "setup_lower_s", "setup_load_s"]
    assert set(names[27:]) >= {"ssd_state_step_roofline", "held_expert_pairs_share"}
    reports = {m["name"] for m in M["per_layer"] if CELL in m.get("workloads", ())}
    assert reports >= {"chat_idle_share", "paged_decode_attention_roofline", "decode_roofline_share", "generator_late_p90_ms",
                       "queue_wait_p90_ms", "warm_programs", "first_token_hold_p50_ms", "engine_prefill_ms_per_ktok",
                       "engine_decode_step_ms", "tick_host_ms", "chat_prefill_device_share", "routed_experts_roofline",
                       "state_slots_idle_share", "tick_longest_ms", "tick_longest_sync_ms", "tick_longest_cpu_ms",
                       "ssd_state_step_roofline", "held_expert_pairs_share"}
    for m in M["end_to_end"] + M["per_layer"]:
        listed = m.get("workloads", [])
        if CELL in listed:
            rest = [c for c in listed if c in before]
            assert listed[: len(rest)] == rest and listed.index(CELL) >= len(rest), f"{m['name']}: the cell was appended"
    for name, unit, source, layer in (("ssd_state_step_roofline", "%", "device_trace", "kernels"),
                                      ("held_expert_pairs_share", "%", "program_counter", "ops/moe routing")):
        new = next(m for m in M["per_layer"] if m["name"] == name)
        assert new == {"name": name, "unit": unit, "better": "higher", "source": source, "layer": layer, "moves": "tpot_p90_ms",
                       "workloads": new["workloads"]} and new["workloads"][0] == CELL
    for name in ("ssm_state_step_roofline", "experts_touched_share", "expert_rows_per_visit", "latent_decode_attention_roofline", "train_mfu"):
        assert CELL not in next(m for m in M["per_layer"] if m["name"] == name)["workloads"]
