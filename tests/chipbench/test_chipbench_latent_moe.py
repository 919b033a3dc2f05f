"""The family ``latent_moe`` (latent attention, routed experts with a shared one, a leading dense
layer): its seeded weights, the program against its plain reference at a toy size on the CPU (logits,
not tokens), its cost counts, the three readers this family's cell brought, and the rehearsal of a
toy cell made only of files of its own (``rehearsal-latent.json``; ``rehearsal.json`` is as it was)."""

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
LATENT = os.path.join(HERE, "rehearsal-latent.json")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    M = json.load(f)
FAMILY = run.load(M, "reference", "latent_moe")

# sha256 over every tensor (name, type, shape, bytes; names sorted) of ``weights.make`` on the host's CPU, when
# the family was written (PR 27): a later change to ``spec`` that moves a seeded weight shows here
DIGESTS = {5: "caba4b87c2e17cfe8e4e46879224a6560c6ac8ff0e5f5aef55b74bce84692de7", 2**31 + 99: "2d59869c4a214579bf0f1fb40ce8dfcbee3ef508411a88985e48bae36d7bd6a8"}


def config(name="joyai-tiny", where=os.path.join(HERE, "configs")):
    with open(os.path.join(where, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_seeded_weights_are_pinned(seed):
    cfg = config()
    made = weights.make(FAMILY.spec(cfg), seed, "bfloat16")
    digest = hashlib.sha256()
    for tensor in sorted(made):
        x = np.asarray(made[tensor])
        digest.update(f"{tensor}:{x.dtype}:{x.shape}:".encode())
        digest.update(x.tobytes())
    assert digest.hexdigest() == DIGESTS[seed]


def test_family_gives_what_its_cells_generator_and_readers_ask():
    assert all(hasattr(FAMILY, name) for name in open_loop_rounds.FAMILY_GIVES)
    for name in ("weight_bytes_per_decode_step", "cache_bytes_per_decode_step", "attention_shape", "latent_decode_bytes",
                 "expert_products_bytes", "expert_products_flops", "expert_layers"):
        assert callable(getattr(FAMILY, name)), name


def test_counts_at_the_published_widths_are_the_issues_arithmetic():
    cfg = config("joyai-llm-flash-l5", os.path.join(ROOT, "chipbench", "configs"))
    assert weights.count(FAMILY.spec(cfg)) == 5_558_141_952  # 5.56 B: 11.12 GB in bf16
    assert FAMILY.expert_params(cfg) == 4_718_592 and FAMILY.expert_layers(cfg) == 4
    assert FAMILY.latent_width(cfg) * 2 == 1152  # bytes a token a layer
    assert abs(FAMILY.expected_experts_touched(cfg, 64) - 222.5) < 0.5
    # 64 busy slots: about 8.4 GB of routed experts and 0.92 GB of everything else a step
    step = FAMILY.weight_bytes_per_decode_step(cfg, 64)
    assert 9.2e9 < step < 9.5e9
    # 100k live tokens: 115 MB of latent rows a layer, once
    assert abs(FAMILY.latent_decode_bytes(cfg, 100_000, 0) - 115.2e6) < 1e3
    assert FAMILY.cache_bytes_per_decode_step(cfg, 100_000, 64) == 5 * FAMILY.latent_decode_bytes(cfg, 100_000, 64)
    assert FAMILY.expert_products_flops(cfg, 512) == 2.0 * 512 * 4_718_592


@pytest.fixture(scope="module")
def toy():
    """The toy configuration's program (float32 weights from the seed, through the cell's own builder
    table) beside the same weights for the reference."""
    from accelerate_tpu.models.llama import _wrap_llama
    from chipbench.builders._tree import check_same_shapes, to_tree

    cfg = config()
    builder = run.load(M, "builders", cfg["bench"]["builder"])
    flat = weights.make(FAMILY.spec(cfg), 7, "float32")
    core = builder.core_config(cfg)
    module, shapes = builder.abstract_params(core)
    tree = to_tree(flat, builder.table(cfg), core.num_hidden_layers)
    check_same_shapes(tree, shapes)
    return cfg, flat, _wrap_llama(module, tree, core)


def test_program_forward_is_the_reference(toy):
    """Float32 on both sides; the program's grouped products and fused attention add the same terms in
    another order than the reference's expert loop and per-head attention: 2e-5 on logits of size 0.3."""
    cfg, flat, model = toy
    tokens = np.random.default_rng(0).integers(5, 250, size=40).astype(np.int32)
    got = np.asarray(model.apply_fn(model.params, jnp.asarray(tokens[None])))[0]
    want = np.asarray(FAMILY.logits_at(flat, cfg, jnp.asarray(tokens), jnp.arange(40)))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla_gather", "pallas_interpreted"])
def test_prefill_then_paged_decode_is_the_references_full_forward(toy, kernel, monkeypatch):
    """Through ``ServingEngine``: bucketed prefill, the paste into the latent pool and the absorbed paged
    decode, against one full forward of the reference over prompt and served tokens. Logits, not tokens:
    the served token's log-probability (the engine's float32 log-softmax) is the reference's within 5e-5,
    and the reference's best logit is no more than 2e-5 above the served token's."""
    from accelerate_tpu.ops import paged_kv
    from accelerate_tpu.serving import ServingEngine

    cfg, flat, model = toy
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", kernel)
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(8, 16, 32), max_len=64, paged_block_size=8, tick_block=4)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(5, 250, size=n).astype(np.int32) for n in (5, 13, 30, 21)]
    uids = [engine.submit(p, max_new_tokens=11) for p in prompts]
    engine.run()
    for uid, prompt in zip(uids, prompts):
        served, lps = np.asarray(engine.partial(uid)), np.asarray(engine.logprobs(uid))
        tokens = np.concatenate([prompt, served])
        ref = FAMILY.logits_at(flat, cfg, jnp.asarray(tokens), jnp.arange(len(prompt) - 1, len(tokens) - 1))
        want = np.asarray(jax.nn.log_softmax(ref, axis=-1))[np.arange(len(served)), served]
        np.testing.assert_allclose(lps, want, atol=5e-5)
        assert float((ref.max(axis=-1) - ref[jnp.arange(len(served)), served]).max()) < 2e-5


def test_routed_ffn_of_the_reference_drops_no_token_under_any_bias(toy):
    """The reference's routing: every token has exactly k experts with weights that sum to the scaling
    factor, also when the bias sends every token to the same two."""
    cfg, flat, _ = toy
    h = jax.random.normal(jax.random.key(0), (9, cfg["hidden_size"]))
    w = {"router": flat["L01.router"], "router_bias": jnp.zeros((8,)).at[jnp.array([2, 6])].set(10.0)}
    picked = np.asarray(FAMILY.routing(h, w, cfg, FAMILY.DOTS["exact"]))
    assert ((picked > 0).sum(axis=1) == 2).all() and (picked[:, [2, 6]] > 0).all()
    np.testing.assert_allclose(picked.sum(axis=1), cfg["routed_scaling_factor"], rtol=1e-5)


# -- the readers, on hand-built ticks

def _ticks():
    ops = [("ragged-dot-none.3", 0.012), ("ragged-dot-metadata.1", 0.0005), ("latent_paged_decode.2", 0.001), ("fusion.9", 0.002)]
    return [{"stats": {"experts_touched": 8 * 4 * 200, "expert_pairs_max": 9}, "ops": ops * 8,
             "dispatch": {"decoding": 40, "live_tokens": 60000, "tick_block": 8}},
            {"stats": {"experts_touched": 8 * 4 * 180, "expert_pairs_max": 7}, "ops": None,
             "dispatch": {"decoding": 30, "live_tokens": 50000, "tick_block": 8}}]


def _observed():
    cfg = config("joyai-llm-flash-l5", os.path.join(ROOT, "chipbench", "configs"))
    return {"config": cfg, "family": FAMILY, "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("reader,want", [
    ("experts_touched_share", 100.0 * (200 + 180) / 2 / 256),
    # 6400 experts of 9,437,184 bytes and 64 x 8 x 4 x 8 pairs of 14,336 bytes, over 819 GB/s, over 8 x 12.5 ms
    ("routed_experts_roofline", 100.0 * (6400 * 9437184 + 16384 * 14336) / 819e9 / (8 * 0.0125)),
    ("latent_decode_attention_roofline", None),
])
def test_new_readers_on_hand_built_ticks(reader, want, monkeypatch):
    module = run.load(M, "layers", reader)
    monkeypatch.setattr(module._decode_programs, "decode_ticks", lambda observed: _ticks())
    got = module.read(_observed())
    if want is None:  # the first tick alone has operations: 8 steps of 5 layers, 40 slots growing a token a step
        cfg = _observed()["config"]
        need = sum(5 * FAMILY.latent_decode_bytes(cfg, 60000 + 40 * k, 40) for k in range(8))
        want = 100.0 * need / 819e9 / (8 * 0.001)
    assert got == pytest.approx(want, rel=1e-12) and 0 < got < 100


@pytest.mark.parametrize("reader", ["experts_touched_share", "routed_experts_roofline", "latent_decode_attention_roofline"])
def test_new_readers_return_nothing_where_the_program_has_no_such_count(reader, monkeypatch):
    module = run.load(M, "layers", reader)
    none = [{"stats": {"admitted": 0}, "ops": [("fusion.1", 0.01)], "dispatch": {"decoding": 3, "live_tokens": 9, "tick_block": 8}}]
    monkeypatch.setattr(module._decode_programs, "decode_ticks", lambda observed: none)
    assert module.read(_observed()) is None
    monkeypatch.setattr(module._decode_programs, "decode_ticks", lambda observed: [])
    assert module.read(_observed()) is None


# -- the toy cell, end to end on the CPU

def test_rehearsal_runs_the_latent_cell(rehearse):  # noqa: F811
    lines = rehearse("tiny-serve-longchat", "--trace", "0", "--control", "1", manifest=LATENT, seconds="3")
    last = result(lines)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"]["ttft_p90_ms"]["value"] > 0 and last["metrics"]["tpot_p90_ms"]["value"] > 0
    checks = {l["check"]: l for l in lines if "check" in l}
    assert checks["compiles_in_window"]["value"] == 0
    assert next(l for l in lines if l.get("note") == "control")["would_pass"] is False


def test_traced_rehearsal_reads_the_expert_counts(rehearse):  # noqa: F811
    last = result(rehearse("tiny-serve-longchat", "--trace", "1", manifest=LATENT, seconds="3"))
    assert 2 / 8 * 100 <= last["metrics"]["experts_touched_share"]["value"] <= 100  # 4 slots of 2 experts among 8
    assert "engine_decode_step_ms" in last["metrics"] and "warm_programs" in last["metrics"]
    assert not any(name.endswith("_roofline") for name in last["metrics"]), "no share of a peak from a CPU"


def test_toy_manifest_names_only_files_of_its_own():
    with open(LATENT) as f:
        stated = json.load(f)
    assert [c["file"] for c in stated["configs"]] == ["tests/chipbench/configs/joyai-tiny.json"]
    assert [w["traffic"] for w in stated["workloads"]] == ["longchat-tiny"]
    real = {m["name"]: m for m in M["per_layer"]}
    for m in stated["per_layer"]:
        assert {k: v for k, v in m.items() if k != "workloads"} == {k: v for k, v in real[m["name"]].items() if k != "workloads"}
