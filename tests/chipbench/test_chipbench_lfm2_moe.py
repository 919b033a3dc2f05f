"""The family ``lfm2_moe`` (gated short convolutions beside attention with q/k norms and rotary, routed
experts past two leading dense layers, a tied head): its seeded weights, the program against its plain
reference at a toy size on the CPU (logits, not tokens), its configuration file and cost counts, the
reader this family's cell brought, and the rehearsal of a toy cell made only of files of its own
(``rehearsal-lfm2.json``)."""

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
LFM2 = os.path.join(HERE, "rehearsal-lfm2.json")
CELL, CONFIG = "lfm2-8b-a1b-serve-longanswer", "lfm2-8b-a1b-l16"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    M = json.load(f)
FAMILY = run.load(M, "reference", "lfm2_moe")

# sha256 over every tensor (name, type, shape, bytes; names sorted) of ``weights.make`` on the host's CPU, when
# the family was written (PR 34): a later change to ``spec`` that moves a seeded weight shows here
DIGESTS = {5: "94fb4cbbc8fd8208aa48816ac2efdf3200eba3e5f4499d168242b316c51631cc", 2**31 + 99: "e5737ed8db1f0068dc9d570ed16fe3e4fd82f643d6cf7782d05eeeb5b061c645"}


def config(name="lfm2-tiny", where=os.path.join(HERE, "configs")):
    with open(os.path.join(where, name + ".json")) as f:
        return json.load(f)


def published():
    return config(CONFIG, os.path.join(ROOT, "chipbench", "configs"))


def catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return None
    with open(catalog) as f:
        return next(e for e in map(json.loads, f) if e["name"] == "LFM2-8B-A1B")


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
                 "expert_products_bytes", "expert_products_flops", "conv_state_bytes"):
        assert callable(getattr(FAMILY, name)), name
    cfg = published()
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 32, "the readers of longchat's cell ask cfg['n_routed_experts']"


def test_configuration_file_holds_every_published_number():
    """Against the catalog's entry where the catalog is beside the guides (the driver checks the same before
    any run): every key as published but the depth, the list of layer kinds cut with it, and the context."""
    cfg = published()
    cut = {"num_hidden_layers", "layer_types", "max_position_embeddings"}
    assert set(cfg["reduced"]) == cut and cfg["assumed"] and "pipeline" in cfg["deployment"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"], cfg["hidden_size"], cfg["intermediate_size"]) == (16, 65536, 2048, 7168)
    assert (cfg["num_experts"], cfg["num_experts_per_tok"], cfg["moe_intermediate_size"], cfg["num_dense_layers"]) == (32, 4, 1792, 2)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["conv_L_cache"], cfg["rope_theta"]) == (32, 8, 3, 1000000)
    assert "".join(t[0] for t in cfg["layer_types"]) == "ccfcccfcccfcccfc" and len(cfg["layer_types"]) == 16
    entry = catalog_entry()
    if entry is not None:
        assert entry["source_url"] == cfg["source"]
        differs = {k for k, v in entry["config"].items() if cfg.get(k, "absent") != v}
        assert differs == cut
        assert cfg["layer_types"] == entry["config"]["layer_types"][:16], "the first 16 layers as published"
    listed = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert set(listed["reduced"]) == cut and listed["source"] == cfg["source"] and listed["file"] == f"chipbench/configs/{CONFIG}.json"


def test_counts_at_the_published_widths_are_the_issues_arithmetic():
    cfg = published()
    assert weights.count(FAMILY.spec(cfg)) == 5_399_129_024  # 10.80 GB in bf16
    whole = dict(cfg, num_hidden_layers=24)
    entry = catalog_entry()
    whole["layer_types"] = entry["config"]["layer_types"] if entry else cfg["layer_types"] + ["conv", "conv", "full_attention", "conv", "conv",
                                                                                               "full_attention", "conv", "conv"]
    assert weights.count(FAMILY.spec(whole)) == 8_339_930_560  # 16.68 GB: not one chip's
    assert FAMILY.expert_params(cfg) == 11_010_048 and FAMILY.expert_layers(cfg) == 14
    assert (FAMILY.attention_layers(cfg), FAMILY.conv_layers(cfg), FAMILY.attention_shape(cfg)) == (4, 12, (32, 8, 64))
    assert FAMILY._operator_params(cfg, False) == 16_783_360 and FAMILY._operator_params(cfg, True) == 10_485_888
    # a routed convolution layer 369.2 M, a routed attention layer 362.9 M, a dense layer 60.8 M
    routed = 32 * 11_010_048 + 2048 * 32 + 32 + 2 * 2048
    assert (16_783_360 + routed, 10_485_888 + routed, 16_783_360 + 3 * 2048 * 7168 + 4096) == (369_174_560, 362_877_088, 60_827_648)
    step = FAMILY.weight_bytes_per_decode_step(cfg, 128)
    assert 10.79e9 < step < 10.80e9, "128 tokens of 4 reach every one of 32 experts: every weight once"
    assert 0.91 < 14 * 32 * 11_010_048 * 2 / step < 0.92, "the routed experts are 91 % of a decode step's bytes"
    # 128 slots of 600 live tokens: K/V of 4 layers, 2,048 B a token a layer, and 12 states of 8 KB a slot read and written
    cache = FAMILY.cache_bytes_per_decode_step(cfg, 128 * 600, 128)
    assert cache == 4 * 2 * (2 * 76800 * 512 + 2 * 128 * 2048) + 12 * 2 * 128 * 2 * 2048 * 2
    assert 12 * 2 * 2048 * 2 == 98_304  # a sequence's state whatever its length
    pool = cfg["bench"]["serving"]["pool_blocks"] * 16 * 8 * 64 * 2 * 2 * 4
    assert cfg["bench"]["serving"]["pool_blocks"] == 128 * 144 + 1 and 2.41e9 < pool < 2.42e9
    # the tick's grouped products: 128 slots x 4 experts x 14 layers x 8 steps
    pairs = 128 * 4 * 14 * 8
    assert FAMILY.expert_products_bytes(cfg, 14 * 32 * 8, pairs) == 2 * (3584 * 11_010_048 + pairs * (2 * 2048 + 4 * 1792))
    assert FAMILY.expert_products_flops(cfg, pairs) == 2.0 * pairs * 11_010_048


@pytest.fixture(scope="module")
def toy():
    """The toy configuration's program (float32 weights from the seed, through the cell's own builder
    table and its re-pairing of the rotary columns) beside the same weights for the reference."""
    from accelerate_tpu.models.llama import _wrap_llama
    from chipbench.builders._tree import check_same_shapes, to_tree

    cfg = config()
    builder = run.load(M, "builders", cfg["bench"]["builder"])
    flat = weights.make(FAMILY.spec(cfg), 7, "float32")
    core = builder.core_config(cfg)
    module, shapes = builder.abstract_params(core)
    tree = to_tree(builder.re_paired(flat, cfg), builder.table(cfg), core.num_hidden_layers)
    check_same_shapes(tree, shapes)
    return cfg, flat, _wrap_llama(module, tree, core), (builder, module, core)


def test_program_forward_is_the_reference(toy):
    """Float32 on both sides, no cache: the program's carried convolution, fused attention on adjacent
    rotary pairs and sorted grouped products against the reference's shifted products, per-head attention
    on rotary halves and loop over every expert: the same terms in another order: 2e-5 on logits of size 1
    and more. Without the builder's re-pairing the two differ by 0.1 and more: the conventions are not one."""
    from accelerate_tpu.models.llama import _wrap_llama
    from chipbench.builders._tree import to_tree

    cfg, flat, model, (builder, module, core) = toy
    tokens = np.random.default_rng(0).integers(5, 250, size=40).astype(np.int32)
    got = np.asarray(model.apply_fn(model.params, jnp.asarray(tokens[None])))[0]
    want = np.asarray(FAMILY.logits_at(flat, cfg, jnp.asarray(tokens), jnp.arange(40)))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=2e-5)
    as_drawn = _wrap_llama(module, to_tree(flat, builder.table(cfg), core.num_hidden_layers), core)
    assert np.abs(np.asarray(as_drawn.apply_fn(as_drawn.params, jnp.asarray(tokens[None])))[0] - want).max() > 0.1


def test_reference_carries_the_convolution_and_the_bias_chooses(toy):
    """A wrong convolution must not pass: the reference's output at a token moves with the gated input two
    tokens back and not with the one three back (through a convolution layer alone); and its routing gives
    every token exactly k experts whose weights sum to 1 (less the published 1e-6), also when the bias
    sends every token to the same four."""
    cfg, flat, _, _ = toy
    w = {n: flat[FAMILY.name(0, n)] for n in FAMILY.CONV}
    x = jax.random.normal(jax.random.key(0), (9, cfg["hidden_size"]))
    base = FAMILY.short_conv(x, w, cfg, FAMILY.DOTS["exact"])
    for back, moves in ((2, True), (3, False)):
        other = FAMILY.short_conv(x.at[8 - back].add(1.0), w, cfg, FAMILY.DOTS["exact"])
        assert (float(jnp.abs(other[8] - base[8]).max()) > 1e-3) is moves
    h = jax.random.normal(jax.random.key(1), (9, cfg["hidden_size"]))
    rw = {"router": flat["L02.router"], "router_bias": jnp.zeros((8,)).at[jnp.array([0, 2, 5, 6])].set(10.0)}
    picked = np.asarray(FAMILY.routing(h, rw, cfg))
    assert ((picked > 0).sum(axis=1) == 4).all() and (picked[:, [0, 2, 5, 6]] > 0).all()
    np.testing.assert_allclose(picked.sum(axis=1), 1.0, atol=1e-5)
    assert (picked.sum(axis=1) < 1.0).all(), "the published normaliser: sum + 1e-6"


@pytest.mark.parametrize("layout", ["paged_xla_step", "paged_kernels_interpreted"])
def test_prefill_then_decode_is_the_references_full_forward(toy, layout, monkeypatch):
    """Through ``ServingEngine``: bucketed prefill (right pads), a prompt over the largest bucket (chunk
    windows with an overlapped head), the paste of rows and state, and the decode tick (XLA's gather and
    ``ragged_dot``, or the interpreted kernels), against one full forward of the reference over prompt and
    served tokens. Logits, not tokens: the served token's log-probability (the engine's float32
    log-softmax) is the reference's within 5e-5, and the reference's best logit is no more than 2e-5
    above the served token's: float32 sums in another order, nothing else."""
    from accelerate_tpu.ops import paged_kv
    from accelerate_tpu.serving import ServingEngine

    cfg, flat, model, _ = toy
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", layout == "paged_kernels_interpreted")
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(8, 16, 32), max_len=128, tick_block=4, paged_block_size=8)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(5, 250, size=n).astype(np.int32) for n in (5, 13, 30, 21, 45)]
    uids = [engine.submit(p, max_new_tokens=11) for p in prompts]
    engine.run()
    for uid, prompt in zip(uids, prompts):
        served, lps = np.asarray(engine.partial(uid)), np.asarray(engine.logprobs(uid))
        tokens = np.concatenate([prompt, served])
        ref = FAMILY.logits_at(flat, cfg, jnp.asarray(tokens), jnp.arange(len(prompt) - 1, len(tokens) - 1))
        want = np.asarray(jax.nn.log_softmax(ref, axis=-1))[np.arange(len(served)), served]
        np.testing.assert_allclose(lps, want, atol=5e-5)
        assert float((ref.max(axis=-1) - ref[jnp.arange(len(served)), served]).max()) < 2e-5


def test_preempted_request_is_the_references_full_forward(toy):
    """Evicted mid-decode and resumed by chunk windows that recompute ``conv_state``: against the reference."""
    from accelerate_tpu.scheduling import SchedulerConfig
    from accelerate_tpu.serving import ServingEngine

    cfg, flat, model, _ = toy
    engine = ServingEngine(model, num_slots=1, prompt_buckets=(8,), max_len=64, tick_block=2, paged_block_size=8,
                           scheduler=SchedulerConfig(enable_preemption=True))
    rng = np.random.default_rng(2)
    victim_prompt, urgent_prompt = rng.integers(5, 250, size=13).astype(np.int32), rng.integers(5, 250, size=5).astype(np.int32)
    victim = engine.submit(victim_prompt, max_new_tokens=12, priority=1)
    engine.step()
    engine.step()
    urgent = engine.submit(urgent_prompt, max_new_tokens=4, priority=0)
    engine.run()
    assert engine.metrics.decode_preemptions == 1 and engine.metrics.resumes == 1
    for uid, prompt in ((victim, victim_prompt), (urgent, urgent_prompt)):
        served = np.asarray(engine.partial(uid))
        tokens = np.concatenate([prompt, served])
        ref = FAMILY.logits_at(flat, cfg, jnp.asarray(tokens), jnp.arange(len(prompt) - 1, len(tokens) - 1))
        assert float((ref.max(axis=-1) - ref[jnp.arange(len(served)), served]).max()) < 2e-5


# -- the reader, on hand-built ticks

def _ticks():
    ops = [("ragged-dot-swiglu.3", 0.009), ("ragged-dot-down.1", 0.0045), ("paged_decode_attention.2", 0.0004), ("fusion.9", 0.002)]
    return [{"stats": {"experts_touched": 8 * 14 * 32, "expert_pairs_max": 31, "expert_tile_visits": 8 * 14 * 40}, "ops": ops * 8,
             "dispatch": {"decoding": 90, "live_tokens": 50000, "tick_block": 8}},
            {"stats": {"experts_touched": 8 * 14 * 31, "expert_pairs_max": 40, "expert_tile_visits": 8 * 14 * 32}, "ops": None,
             "dispatch": {"decoding": 80, "live_tokens": 45000, "tick_block": 8}}]


def _observed():
    return {"config": published(), "family": FAMILY, "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("reader,want", [
    ("expert_rows_per_visit", (512 / 40 + 512 / 32) / 2),  # 128 slots x 4 pairs a layer a step over 40 and 32 visits
    ("experts_touched_share", 100.0 * (32 + 31) / 2 / 32),
    # the first tick alone has operations: 3584 experts of 22,020,096 bytes and 57,344 pairs of 22,528, over 8 x 13.5 ms
    ("routed_experts_roofline", 100.0 * (3584 * 22_020_096 + 57_344 * 22_528) / 819e9 / (8 * 0.0135)),
])
def test_readers_of_the_expert_counts_on_hand_built_ticks(reader, want, monkeypatch):
    module = run.load(M, "layers", reader)
    monkeypatch.setattr(module._decode_programs, "decode_ticks", lambda observed: _ticks())
    got = module.read(_observed())
    assert got == pytest.approx(want, rel=1e-12) and 0 < got <= 100


def test_new_reader_returns_nothing_where_the_program_has_no_such_count(monkeypatch):
    """As on a program from before PR 30, whose ticks carry no ``expert_tile_visits``, and in a cell without experts (0)."""
    module = run.load(M, "layers", "expert_rows_per_visit")
    for stats in ({"admitted": 0}, {"experts_touched": 40, "expert_tile_visits": 0}):
        none = [{"stats": stats, "ops": [("fusion.1", 0.01)], "dispatch": {"decoding": 3, "live_tokens": 9, "tick_block": 8}}]
        monkeypatch.setattr(module._decode_programs, "decode_ticks", lambda observed, none=none: none)
        assert module.read(_observed()) is None
    monkeypatch.setattr(module._decode_programs, "decode_ticks", lambda observed: [])
    assert module.read(_observed()) is None


# -- the toy cell, end to end on the CPU

def test_rehearsal_runs_the_lfm2_cell(rehearse):  # noqa: F811
    lines = rehearse("tiny-serve-longanswer-moe", "--trace", "0", "--control", "1", manifest=LFM2, seconds="3")
    last = result(lines)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"]["ttft_p90_ms"]["value"] > 0 and last["metrics"]["tpot_p90_ms"]["value"] > 0
    checks = {l["check"]: l for l in lines if "check" in l}
    assert checks["compiles_in_window"]["value"] == 0
    assert next(l for l in lines if l.get("note") == "control")["would_pass"] is False


def test_traced_rehearsal_reads_the_tick_counts(rehearse):  # noqa: F811
    last = result(rehearse("tiny-serve-longanswer-moe", "--trace", "1", manifest=LFM2, seconds="3"))
    assert 4 / 8 * 100 <= last["metrics"]["experts_touched_share"]["value"] <= 100  # 4 slots of 4 experts among 8
    assert 16 / 11 <= last["metrics"]["expert_rows_per_visit"]["value"] <= 4  # 16 pairs a layer a step on one row tile: 4 to 11 visits
    assert 0 <= last["metrics"]["state_slots_idle_share"]["value"] < 100
    assert "engine_decode_step_ms" in last["metrics"] and "warm_programs" in last["metrics"]
    assert not any(name.endswith("_roofline") for name in last["metrics"]), "no share of a peak from a CPU"


def test_toy_manifest_names_only_files_of_its_own():
    with open(LFM2) as f:
        stated = json.load(f)
    assert [c["file"] for c in stated["configs"]] == ["tests/chipbench/configs/lfm2-tiny.json"]
    assert [w["traffic"] for w in stated["workloads"]] == ["longanswer-moe-tiny"]
    real = {m["name"]: m for m in M["per_layer"]}
    for m in stated["per_layer"]:
        assert {k: v for k, v in m.items() if k != "workloads"} == {k: v for k, v in real[m["name"]].items() if k != "workloads"}
    assert {m["name"] for m in stated["per_layer"]} == {m["name"] for m in M["per_layer"] if CELL in m.get("workloads", ())}


def test_manifest_gained_the_cell_and_nothing_that_was_there_moved():
    """Entries were appended: the four cells and four configurations of PR 32's manifest stand first and
    as they were, every metric of it stands in its place with its fields, and a list of cells that gained
    this one gained it at its end. Later cells may follow: nothing here counts the entries."""
    cells = [w["name"] for w in M["workloads"]]
    before = ["mistral7b-serve-chat", "bert-base-train-seq128", "joyai-flash-serve-longchat", "jamba2-3b-serve-longanswer"]
    assert cells[:5] == before + [CELL]
    assert [c["name"] for c in M["configs"]][:5] == ["bert-base-uncased", "mistral-7b-v0.1-l16", "joyai-llm-flash-l5", "ai21-jamba2-3b", CONFIG]
    cell = M["workloads"][4]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "longanswer-moe", "chips": 1, "why": cell["why"]} and len(cell["why"]) <= 200
    assert [m["name"] for m in M["end_to_end"]] == ["train_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"]
    assert [(m["bound"], m["better"]) for m in M["end_to_end"]] == [(0.01, "higher"), (0.1, "lower"), (0.06, "lower"), (0.1, "lower")]
    assert M["run_seconds"] == 51 and M["command"] == ["python3", "-m", "chipbench"] and M["paths"] == ["chipbench", "tests/chipbench"]
    names = [m["name"] for m in M["per_layer"]]
    assert names[:22] == [
        "train_idle_share", "chat_idle_share", "paged_decode_attention_roofline", "decode_roofline_share", "train_step_ms", "train_mfu",
        "generator_late_p90_ms", "queue_wait_p90_ms", "warm_programs", "first_token_hold_p50_ms", "engine_prefill_ms_per_ktok",
        "engine_decode_step_ms", "tick_host_ms", "chat_prefill_device_share", "train_dispatch_ms", "train_idle_in_dispatch_share",
        "latent_decode_attention_roofline", "routed_experts_roofline", "experts_touched_share", "ssm_state_step_roofline",
        "state_slots_idle_share", "expert_rows_per_visit"]
    reports = {m["name"] for m in M["per_layer"] if CELL in m.get("workloads", ())}
    assert reports == {"chat_idle_share", "paged_decode_attention_roofline", "decode_roofline_share", "generator_late_p90_ms",
                       "queue_wait_p90_ms", "warm_programs", "first_token_hold_p50_ms", "engine_prefill_ms_per_ktok",
                       "engine_decode_step_ms", "tick_host_ms", "chat_prefill_device_share", "routed_experts_roofline",
                       "experts_touched_share", "state_slots_idle_share", "expert_rows_per_visit"}
    for m in M["end_to_end"] + M["per_layer"]:
        listed = m.get("workloads", [])
        if CELL in listed:
            rest = [c for c in listed if c in before]
            assert listed[: len(rest)] == rest and listed[len(rest)] == CELL, f"{m['name']}: the cell was appended"
    new = M["per_layer"][21]
    assert new == {"name": "expert_rows_per_visit", "unit": "rows", "better": "higher", "source": "program_counter", "layer": "kernels",
                   "moves": "tpot_p90_ms", "workloads": new["workloads"]} and new["workloads"][0] == CELL
    for name in ("ssm_state_step_roofline", "latent_decode_attention_roofline", "train_mfu"):
        assert CELL not in next(m for m in M["per_layer"] if m["name"] == name)["workloads"]
