"""The family ``hybrid_ssm`` (state-space layers beside attention without rotary, a tied head): its seeded
weights and the one map its recurrence's constants go through, the program against its plain reference at
a toy size on the CPU (logits, not tokens), its cost counts, the two readers this family's cell brought,
and the rehearsal of a toy cell made only of files of its own (``rehearsal-hybrid.json``)."""

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
HYBRID = os.path.join(HERE, "rehearsal-hybrid.json")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    M = json.load(f)
FAMILY = run.load(M, "reference", "hybrid_ssm")

# sha256 over every tensor (name, type, shape, bytes; names sorted) of ``weights.make`` on the host's CPU, when
# the family was written (PR 31): a later change to ``spec`` that moves a seeded weight shows here
DIGESTS = {5: "d109fd228b9ffaec2a872dee6018566420533b175d75f098eedd4e8cf52ac0b7", 2**31 + 99: "8d0762a9f3b558e29bcd364427d3e6304a22d1a01b0e78eb8bd98542baf69206"}


def config(name="jamba-tiny", where=os.path.join(HERE, "configs")):
    with open(os.path.join(where, name + ".json")) as f:
        return json.load(f)


def published():
    return config("ai21-jamba2-3b", os.path.join(ROOT, "chipbench", "configs"))


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
    for name in ("weight_bytes_per_decode_step", "cache_bytes_per_decode_step", "attention_shape", "state_step_bytes",
                 "mamba_layers", "ssm_constants"):
        assert callable(getattr(FAMILY, name)), name


def test_configuration_file_holds_every_published_number():
    """Against the catalog's entry where the catalog is beside the guides (the driver checks the same before
    any run): every key as published but the context, which alone is in ``reduced``."""
    cfg = published()
    assert list(cfg["reduced"]) == ["max_position_embeddings"] and cfg["assumed"] and cfg["deployment"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"], cfg["hidden_size"], cfg["intermediate_size"]) == (28, 65536, 2560, 8192)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            entry = next(e for e in map(json.loads, f) if e["name"] == "AI21-Jamba2-3B")
        assert entry["source_url"] == cfg["source"]
        differs = {k for k, v in entry["config"].items() if cfg.get(k, "absent") != v}
        assert differs == {"max_position_embeddings"}
    entry = next(c for c in M["configs"] if c["name"] == "ai21-jamba2-3b")
    assert entry["reduced"] == ["max_position_embeddings"] and entry["source"] == cfg["source"]


def test_counts_at_the_published_widths_are_the_issues_arithmetic():
    cfg = published()
    assert weights.count(FAMILY.spec(cfg)) == 3_029_337_472  # 6.06 GB in bf16
    assert FAMILY._mixer_params(cfg, False) == 41_241_792 and FAMILY._mixer_params(cfg, True) == 13_762_560
    assert (FAMILY.attention_layers(cfg), FAMILY.mamba_layers(cfg)) == (2, 26)
    assert FAMILY.attention_shape(cfg) == (20, 1, 128) and FAMILY.d_inner(cfg) == 5120
    assert [i for i in range(28) if FAMILY.is_attention(cfg, i)] == [7, 21]
    # the kernel's call at 128 slots: 84 MB of state read and written, and 5.6 MB of everything else
    assert 83.8e6 < FAMILY.state_step_bytes(cfg, 128) - 128 * 5120 * 8 - 4 * 17 * 5120 - 128 * 64 < 83.9e6
    assert 89e6 < FAMILY.state_step_bytes(cfg, 128) < 90e6
    step = FAMILY.weight_bytes_per_decode_step(cfg, 128)
    assert 6.05e9 < step < 6.07e9, "every weight once: the head is the embedding"
    # 128 slots of 600 live tokens: 26 x (89.5 + 7.9) MB of state against 0.16 MB of K/V a layer
    cache = FAMILY.cache_bytes_per_decode_step(cfg, 128 * 600, 128)
    state = 26 * (FAMILY.state_step_bytes(cfg, 128) + FAMILY.conv_state_bytes(cfg, 128))
    assert abs(cache - state - 2 * 2 * (2 * 76800 * 128 + 2 * 128 * 2560)) < 1 and 2.5e9 < state < 2.6e9
    # a sequence's state whatever its length: 26 x (16 x 5120 x 4 + 3 x 5120 x 2) bytes
    assert 26 * (16 * 5120 * 4 + 3 * 5120 * 2) == 9_318_400


def test_constants_lie_where_mambas_initialiser_puts_them():
    """The one map: ``softplus(dt_bias)`` log-uniform in [0.001, 0.1], ``A`` in about -16 .. -0.2: a state
    that remembers tens to thousands of tokens, so that a wrong recurrence cannot pass."""
    flat = weights.make(FAMILY.spec(config()), 11, "bfloat16")
    dt_bias, a_log = FAMILY.ssm_constants(flat["L00.dt_bias_raw"], flat["L00.a_raw"])
    step, a = np.asarray(jax.nn.softplus(dt_bias)), -np.exp(np.asarray(a_log))
    assert dt_bias.dtype == a_log.dtype == jnp.float32 and a_log.shape == flat["L00.a_raw"].shape
    assert 0.001 <= step.min() and step.max() <= 0.1 and 0.005 < np.median(step) < 0.02
    assert -8.01 <= a.min() and a.max() < 0 and (a[0] >= -1).all() and (a[-1] <= -8 * np.exp(-0.5 * 5)).all()
    decay = np.exp(np.median(step) * a)  # of one token: between 0.85 and 0.9999
    assert 0.85 < decay.min() and decay.max() < 0.99999


@pytest.fixture(scope="module")
def toy():
    """The toy configuration's program (float32 weights from the seed, through the cell's own builder
    table and the family's map) beside the same weights for the reference."""
    from accelerate_tpu.models.llama import _wrap_llama
    from chipbench.builders._tree import check_same_shapes, to_tree

    cfg = config()
    builder = run.load(M, "builders", cfg["bench"]["builder"])
    flat = weights.make(FAMILY.spec(cfg), 7, "float32")
    core = builder.core_config(cfg)
    module, shapes = builder.abstract_params(core)
    tree = to_tree(builder.with_constants(flat, cfg), builder.table(cfg), core.num_hidden_layers)
    check_same_shapes(tree, shapes)
    return cfg, flat, _wrap_llama(module, tree, core)


def test_program_forward_is_the_reference(toy):
    """Float32 on both sides, no cache: the program's chunked scan over ``[d_state, d_inner]`` and fused
    attention against the reference's scan a token over ``[d_inner, d_state]`` and per-head attention:
    the same terms in another order: 2e-5 on logits of size 0.5 and more."""
    cfg, flat, model = toy
    tokens = np.random.default_rng(0).integers(5, 250, size=40).astype(np.int32)
    got = np.asarray(model.apply_fn(model.params, jnp.asarray(tokens[None])))[0]
    want = np.asarray(FAMILY.logits_at(flat, cfg, jnp.asarray(tokens), jnp.arange(40)))
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_reference_remembers_across_its_whole_sequence(toy):
    """A wrong recurrence must not pass: changing the first token moves the reference's logits forty
    tokens later (through the state alone where attention is taken out of the comparison by its
    softmax weights being small), far above the 2e-5 the program is held to."""
    cfg, flat, _ = toy
    tokens = np.random.default_rng(3).integers(5, 250, size=41).astype(np.int32)
    other = tokens.copy()
    other[0] = (other[0] + 17) % 250 + 5
    a, b = (np.asarray(FAMILY.logits_at(flat, cfg, jnp.asarray(t), jnp.arange(40, 41))) for t in (tokens, other))
    assert np.abs(a - b).max() > 1e-3


@pytest.mark.parametrize("layout", ["dense", "paged_xla_step", "paged_kernel_interpreted"])
def test_prefill_then_decode_is_the_references_full_forward(toy, layout, monkeypatch):
    """Through ``ServingEngine``: bucketed prefill (right pads), a prompt over the largest bucket (chunk
    windows with an overlapped head), the paste of rows and state, and the decode tick (plain step or the
    interpreted kernel), against one full forward of the reference over prompt and served tokens. Logits,
    not tokens: the served token's log-probability (the engine's float32 log-softmax) is the reference's
    within 5e-5, and the reference's best logit is no more than 2e-5 above the served token's: float32
    sums in another order, nothing else; a state that counted one pad token reads 1e-3 and more."""
    from accelerate_tpu.ops import paged_kv
    from accelerate_tpu.serving import ServingEngine

    cfg, flat, model = toy
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", layout == "paged_kernel_interpreted")
    paged = {} if layout == "dense" else {"paged_block_size": 8}
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(8, 16, 32), max_len=128, tick_block=4, **paged)
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


# -- the readers, on hand-built ticks

def _ticks():
    ops = [("ssm_state_step.3", 0.00013)] * 26 + [("paged_decode_attention.1", 0.00004)] * 2 + [("fusion.9", 0.002)]
    return [{"stats": {"state_slots_idle": 8 * 88}, "ops": ops * 8, "dispatch": {"decoding": 40, "live_tokens": 24000, "tick_block": 8}},
            {"stats": {"state_slots_idle": 8 * 98}, "ops": None, "dispatch": {"decoding": 30, "live_tokens": 20000, "tick_block": 8}}]


def _observed():
    return {"config": published(), "family": FAMILY, "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("reader,want", [
    ("state_slots_idle_share", 100.0 * (88 + 98) / 2 / 128),
    # the first tick alone has operations: 8 steps of 26 calls of 0.13 ms, the bytes of its 40 decoding slots
    ("ssm_state_step_roofline", None),
])
def test_new_readers_on_hand_built_ticks(reader, want, monkeypatch):
    module = run.load(M, "layers", reader)
    monkeypatch.setattr(module._decode_programs, "decode_ticks", lambda observed: _ticks())
    got = module.read(_observed())
    if want is None:
        want = 100.0 * 8 * 26 * FAMILY.state_step_bytes(published(), 40) / 819e9 / (8 * 26 * 0.00013)
    assert got == pytest.approx(want, rel=1e-12) and 0 < got < 100


@pytest.mark.parametrize("reader", ["state_slots_idle_share", "ssm_state_step_roofline"])
def test_new_readers_return_nothing_where_the_program_has_no_such_count(reader, monkeypatch):
    """As on the parent commit, whose ticks carry no ``state_slots_idle`` and run no ``ssm_state_step``."""
    module = run.load(M, "layers", reader)
    none = [{"stats": {"admitted": 0}, "ops": [("fusion.1", 0.01)], "dispatch": {"decoding": 3, "live_tokens": 9, "tick_block": 8}}]
    monkeypatch.setattr(module._decode_programs, "decode_ticks", lambda observed: none)
    assert module.read(_observed()) is None
    monkeypatch.setattr(module._decode_programs, "decode_ticks", lambda observed: [])
    assert module.read(_observed()) is None


# -- the toy cell, end to end on the CPU

def test_rehearsal_runs_the_hybrid_cell(rehearse):  # noqa: F811
    lines = rehearse("tiny-serve-longanswer", "--trace", "0", "--control", "1", manifest=HYBRID, seconds="3")
    last = result(lines)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"]["ttft_p90_ms"]["value"] > 0 and last["metrics"]["tpot_p90_ms"]["value"] > 0
    checks = {l["check"]: l for l in lines if "check" in l}
    assert checks["compiles_in_window"]["value"] == 0
    assert next(l for l in lines if l.get("note") == "control")["would_pass"] is False


def test_traced_rehearsal_reads_the_idle_state_steps(rehearse):  # noqa: F811
    last = result(rehearse("tiny-serve-longanswer", "--trace", "1", manifest=HYBRID, seconds="3"))
    assert 0 <= last["metrics"]["state_slots_idle_share"]["value"] < 100  # of 4 slots, those that decode nothing
    assert "engine_decode_step_ms" in last["metrics"] and "warm_programs" in last["metrics"]
    assert not any(name.endswith("_roofline") for name in last["metrics"]), "no share of a peak from a CPU"


def test_toy_manifest_names_only_files_of_its_own():
    with open(HYBRID) as f:
        stated = json.load(f)
    assert [c["file"] for c in stated["configs"]] == ["tests/chipbench/configs/jamba-tiny.json"]
    assert [w["traffic"] for w in stated["workloads"]] == ["longanswer-tiny"]
    real = {m["name"]: m for m in M["per_layer"]}
    for m in stated["per_layer"]:
        assert {k: v for k, v in m.items() if k != "workloads"} == {k: v for k, v in real[m["name"]].items() if k != "workloads"}


def test_cell_is_appended_and_nothing_else_of_the_manifest_moved():
    cell = "jamba2-3b-serve-longanswer"
    assert [w["name"] for w in M["workloads"]][-1] == cell and len(M["workloads"]) == 4
    assert all(w["chips"] == 1 for w in M["workloads"])
    reports = {m["name"] for m in M["per_layer"] if cell in m.get("workloads", ())}
    assert reports == {"chat_idle_share", "paged_decode_attention_roofline", "decode_roofline_share", "generator_late_p90_ms",
                       "queue_wait_p90_ms", "warm_programs", "first_token_hold_p50_ms", "engine_prefill_ms_per_ktok",
                       "engine_decode_step_ms", "tick_host_ms", "chat_prefill_device_share", "ssm_state_step_roofline",
                       "state_slots_idle_share"}
    assert [m["name"] for m in M["per_layer"]][-2:] == ["ssm_state_step_roofline", "state_slots_idle_share"]
