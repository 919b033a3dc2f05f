"""The family ``evabyte`` (EVA, chunked linearised attention: an aligned window of exact rows beside one pooled key
and value for every chunk before it): its seeded weights, the program against its plain reference at a toy size on
the CPU (logits, not tokens) through the forward pass and through prefill then decode over the paged cache, the
five controls that must fail the tolerance the program meets, its configuration file and counts, the four readers
this family's cell brought, and the rehearsal of a toy cell made only of files of its own
(``rehearsal-evabyte.json``)."""

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
EVABYTE = os.path.join(HERE, "rehearsal-evabyte.json")
CELL, CONFIG = "evabyte-serve-longchat", "evabyte-6.5b-l8"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    M = json.load(f)
FAMILY = run.load(M, "reference", "evabyte")
NEW = ("eva_rows_read_share", "eva_summary_pages_share", "eva_decode_attention_roofline", "eva_decode_roofline_share")
CUT = {"num_hidden_layers", "max_position_embeddings"}
# What the program meets against the reference in float32 on the CPU: the same terms summed in another order, on
# logits of size 3. Each control below moves them a hundred times further.
TOLERANCE = 2e-5

# sha256 over every tensor (name, type, shape, bytes; names sorted) of ``weights.make`` on the host's CPU, when
# the family was written (PR 42): a later change to ``spec`` that moves a seeded weight shows here
DIGESTS = {5: "05ec60ac560397d2a9d003adb0d651887b45aa9fc890137fae64935b449c4d02",
           2**31 + 99: "b7df7a3f46b8105ce116a6dd1d24a813836ffb24ed328e2bae9643cda75371e5"}


def config(name="evabyte-tiny", where=os.path.join(HERE, "configs")):
    with open(os.path.join(where, name + ".json")) as f:
        return json.load(f)


def published():
    return config(CONFIG, os.path.join(ROOT, "chipbench", "configs"))


def catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return None
    with open(catalog) as f:
        return next(e for e in map(json.loads, f) if e["name"] == "EvaByte")


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
    for name in ("weight_bytes_per_decode_step", "cache_bytes_per_decode_step", "attention_shape", "rows_read", "page_bytes", "pool_blocks"):
        assert callable(getattr(FAMILY, name)), name


def test_configuration_file_holds_every_published_number():
    """Against the catalog's entry where the catalog is beside the guides (the driver checks the same before any
    run): every key as published but the depth and the context; the file states the four-stage deployment, the
    published counts, what is not served and every assumed point."""
    cfg = published()
    assert set(cfg["reduced"]) == CUT and "four stages" in cfg["deployment"] and "first stage" in cfg["deployment"]
    assert set(cfg["not_served"]) == {"num_pred_heads"} and cfg["num_pred_heads"] == 8
    assert {"pooling", "windows", "summary column", "pred heads", "pooling_vector_std", "byte ids"} <= set(cfg["assumed"])
    assert (cfg["num_hidden_layers"], cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]) == (8, 4096, 11008, 320)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["window_size"], cfg["chunk_size"]) == (32, 32, 2048, 16)
    assert (cfg["attention_class"], cfg["rope_theta"], cfg["norm_add_unit_offset"], cfg["fp32_skip_add"], cfg["fp32_logits"]) == ("eva", 100000, True, True, True)
    assert cfg["published"]["num_hidden_layers"] == 32 and cfg["published"]["max_position_embeddings"] == 32768
    entry = catalog_entry()
    if entry is not None:
        assert entry["source_url"] == cfg["source"]
        assert {k for k, v in entry["config"].items() if cfg.get(k, "absent") != v} == CUT
    listed = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert set(listed["reduced"]) == CUT and listed["source"] == cfg["source"] and listed["file"] == f"chipbench/configs/{CONFIG}.json"
    s = cfg["bench"]["serving"]
    assert (s["num_slots"], s["paged_block_size"], s["pool_blocks"], s["max_len"], s["prompt_buckets"]) == (32, 16, 4737, 5120, [256, 1024, 4096])


def test_counts_at_the_published_widths_are_the_issues_arithmetic():
    cfg = published()
    assert FAMILY._layer_params(cfg) == 202_391_552 and weights.count(FAMILY.spec(cfg)) == 1_621_757_952  # 3.24 GB in bf16
    assert weights.count(FAMILY.spec(dict(cfg, num_hidden_layers=32))) == 32 * 202_391_552 + 2 * 320 * 4096 + 4096  # 6.48 B
    assert FAMILY.page_bytes(cfg, 16) == 2_097_152 and FAMILY.attention_shape(cfg) == (32, 32, 128)
    assert FAMILY.pages_per_slot(cfg, 5120, 16) == 148 and FAMILY.pool_blocks(cfg, 32, 5120, 16) == 4737 == cfg["bench"]["serving"]["pool_blocks"]
    assert 9.93e9 < 4737 * 2_097_152 < 9.94e9
    # a row of keys and values a layer is 16,384 B (Mistral's: 4,096): a step at 1,400 bytes of context in its first
    # window reads them all, one past two closes reads 256 summaries and its own window's rows
    assert FAMILY.rows_read(cfg, 1399) == 1400 and FAMILY.rows_read(cfg, 2048) == 129 and FAMILY.rows_read(cfg, 5119) == 256 + 1024
    per_row = FAMILY.cache_bytes_per_decode_step(cfg, 1001, 1) - FAMILY.cache_bytes_per_decode_step(cfg, 1000, 1)
    assert per_row == 8 * 16_384
    # 25 slots reading 800 rows each: 2.6 GB of cache beside 3.24 GB of weights
    assert 2.6e9 < FAMILY.cache_bytes_per_decode_step(cfg, 25 * 800, 25) < 2.65e9
    assert 3.24e9 < FAMILY.weight_bytes_per_decode_step(cfg, 25) < 3.25e9


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
    the same weights for the reference. ``mu`` and ``phi`` are drawn at 2.0: the pooling weights of a chunk of four
    range over a factor of ten and more."""
    cfg = config()
    flat = weights.make(FAMILY.spec(cfg), 7, "float32")
    return cfg, flat, _program(cfg, flat)


TOKENS = np.random.default_rng(0).integers(5, 318, size=100).astype(np.int32)


def _reference(flat, cfg, tokens=TOKENS):
    return np.asarray(FAMILY.logits_at(flat, cfg, jnp.asarray(tokens), jnp.arange(len(tokens))))


def test_program_forward_is_the_reference(toy):
    """Float32 on both sides, no cache: the program's windows (summaries and rows concatenated, queries in blocks,
    all heads in one product) against the reference's one masked softmax a head over every summary and every row of
    the sequence: the same terms in another order."""
    cfg, flat, model = toy
    got = np.asarray(model.apply_fn(model.params, jnp.asarray(TOKENS[None])))[0]
    want = _reference(flat, cfg)
    assert np.abs(want).max() > 2.0 and got.shape == (100, 320)
    np.testing.assert_allclose(got, want, atol=TOLERANCE)


def _control_visible(kind):
    def visible(t, window, chunk):
        pos = jnp.arange(t)
        if kind == "sliding band":  # the last ``window`` rows, and the chunks that lie wholly before them
            rows = (pos[None, :] > pos[:, None] - window) & (pos[None, :] <= pos[:, None])
            chunks = (jnp.arange(t // chunk)[None, :] + 1) * chunk <= (pos[:, None] - window + 1)
        else:  # summaries dropped: the aligned window's rows alone
            first = (pos // window) * window
            rows = (pos[None, :] >= first[:, None]) & (pos[None, :] <= pos[:, None])
            chunks = jnp.zeros((t, t // chunk), bool)
        return jnp.concatenate([chunks, rows], axis=1)

    return visible


@pytest.mark.parametrize("control", ["summaries dropped", "sliding band", "mu and phi swapped", "plain mean of the keys",
                                     "plain mean of the values", "bf16 logits"])
def test_controls_fail_the_tolerance_the_program_meets(toy, control, monkeypatch):
    """Each wrong model that the issue names, computed by the reference's own code with one thing changed, lies
    further from the reference than ``TOLERANCE`` by a factor of fifty and more: none of them could pass for the
    program. (A tolerance of 2e-5 is what float32 sums in another order need on logits of size 3; the controls read
    3e-3 and more.)"""
    cfg, flat, _ = toy
    want = _reference(flat, cfg)
    changed = dict(flat)
    if control in ("summaries dropped", "sliding band"):
        monkeypatch.setattr(FAMILY, "visible", _control_visible(control))
        jax.clear_caches()  # the reference's layer is jitted: traced again under the changed rule
    for i in range(cfg["num_hidden_layers"]):
        mu, phi = FAMILY.name(i, "mu"), FAMILY.name(i, "phi")
        if control == "mu and phi swapped":
            changed[mu], changed[phi] = flat[phi], flat[mu]
        elif control == "plain mean of the keys":
            changed[mu] = jnp.zeros_like(flat[mu])  # a flat softmax is a mean
        elif control == "plain mean of the values":
            changed[phi] = jnp.zeros_like(flat[phi])
    if control == "bf16 logits":
        hidden = flat["embed"][TOKENS].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            hidden = FAMILY._layer_at(hidden, FAMILY.layer_weights(flat, i), FAMILY._cfg_key(cfg), "exact")
        normed = FAMILY._rms_norm(hidden, flat["norm_final"], cfg["rms_norm_eps"])
        got = np.asarray((normed.astype(jnp.bfloat16) @ flat["lm_head"].astype(jnp.bfloat16)).astype(jnp.float32))
    else:
        got = _reference(changed, cfg)
    jax.clear_caches()
    beyond = np.abs(got - want)[32:].max()  # past the first close: before it no summary is read
    assert beyond > 50 * TOLERANCE, f"{control}: {beyond}"
    if control != "bf16 logits":
        first = np.abs(got - want)[:32].max()
        assert first <= (TOLERANCE if control != "sliding band" else 0.0), "inside the first window the rules agree"


PROMPTS = (5, 12, 32, 45, 64, 30, 3)  # inside a chunk, on a chunk's edge, on a window's edge, past one, on the second edge
NEW_TOKENS = (40, 30, 20, 60, 11, 9, 70)  # across chunks' completions, and across a close in the middle of a tick


@pytest.mark.parametrize("layout", ["paged_xla_gather", "paged_kernel_interpreted"])
def test_prefill_then_decode_is_the_references_full_forward(toy, layout, monkeypatch):
    """Through ``ServingEngine``: bucketed prefill by windows (right pads; a bucket of 64 is two windows; a whole window
    through the flash kernel where the kernels run, as the chip's windows of 2048 do), the paste of
    the open window's rows and of every complete chunk's summary, and the decode tick (the chunk pooled when its
    last row is written, the gathered table through XLA's gather or through the interpreted kernel), three slots at
    once, ticks of eight steps, so that windows close in the middle of a tick; against one full forward of the
    reference over prompt and served tokens. Logits, not tokens: the served token's log-probability (the engine's
    float32 log-softmax) is the reference's within ``TOLERANCE``, and the reference's best logit is no more than that
    above the served token's."""
    from accelerate_tpu.ops import eva_attention, paged_kv
    from accelerate_tpu.serving import ServingEngine

    cfg, flat, model = toy
    monkeypatch.setattr(paged_kv, "FORCE_KERNEL_INTERPRET", layout == "paged_kernel_interpreted")
    monkeypatch.setattr(eva_attention, "FLASH_MIN_ROWS", cfg["window_size"])
    engine = ServingEngine(model, num_slots=3, prompt_buckets=(8, 32, 64), max_len=128, tick_block=8, paged_block_size=4)
    free = engine.pool_free_blocks
    rng = np.random.default_rng(1)
    prompts = [rng.integers(5, 318, size=n).astype(np.int32) for n in PROMPTS]
    uids = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, NEW_TOKENS)]
    engine.run()
    for uid, prompt in zip(uids, prompts):
        served, lps = np.asarray(engine.partial(uid)), np.asarray(engine.logprobs(uid))
        tokens = np.concatenate([prompt, served])
        ref = FAMILY.logits_at(flat, cfg, jnp.asarray(tokens), jnp.arange(len(prompt) - 1, len(tokens) - 1))
        want = np.asarray(jax.nn.log_softmax(ref, axis=-1))[np.arange(len(served)), served]
        np.testing.assert_allclose(lps, want, atol=TOLERANCE)
        assert float((ref.max(axis=-1) - ref[jnp.arange(len(served)), served]).max()) < TOLERANCE
    assert engine.pool_free_blocks == free and engine.metrics.windows_closed == 7
    assert len({tuple(np.asarray(engine.partial(u))[:9]) for u in uids}) == len(uids), "the sequences differ"


# -- the readers, on hand-built ticks

def _ticks():
    fusion = "%fusion.9 = bf16[32,32,128]{2,1,0} fusion(bf16[32,32,128]{2,1,0} %paged_decode_attention.3, bf16[4096]{0} %p), kind=kLoop"
    kernel = "%paged_decode_attention.3 = bf16[32,32,128]{2,1,0} custom-call(s32[32,144]{1,0} %table), custom_call_target=\"tpu_custom_call\""
    ops = [(kernel, 0.0005), (fusion, 0.0001), ("%fusion.2 = bf16[32,11008]{1,0} fusion()", 0.0006)]
    return [{"stats": {"admitted": 0, "attn_rows_read": 8 * 25 * 800, "context_rows": 8 * 25 * 1400, "exact_pages": 2000, "summary_pages": 120}, "ops": ops * 64,
             "dispatch": {"decoding": 25, "live_tokens": 35000, "tick_block": 8}},
            {"stats": {"admitted": 1, "attn_rows_read": 8 * 24 * 700, "context_rows": 8 * 24 * 1400, "exact_pages": 1900, "summary_pages": 136}, "ops": ops * 64,
             "dispatch": {"decoding": 24, "live_tokens": 33600, "tick_block": 8}},
            {"stats": {"admitted": 0, "attn_rows_read": 8 * 24 * 700, "context_rows": 8 * 24 * 1400, "exact_pages": 1900, "summary_pages": 136}, "ops": None,
             "dispatch": {"decoding": 24, "live_tokens": 33600, "tick_block": 8}}]


def _observed():
    return {"config": published(), "family": FAMILY, "device": {"kind": "TPU v5 lite"}}


def _tick_bytes(rows, slots):  # every layer's keys and values of the rows, queries and outputs of the slot-steps
    return 8 * 2 * (2 * rows * 4096 + 2 * slots * 4096)


@pytest.mark.parametrize("reader,want", [
    ("eva_rows_read_share", 100.0 * (25 * 800 + 2 * 24 * 700) / ((25 + 2 * 24) * 1400)),
    ("eva_summary_pages_share", 100.0 * (120 + 2 * 136) / (2120 + 2 * 2036)),
    # the two ticks with operations: 64 calls of the kernel itself each, 0.5 ms a call; the fusion that names it among its operands is not it
    ("eva_decode_attention_roofline", 100.0 * (_tick_bytes(8 * 25 * 800, 200) + _tick_bytes(8 * 24 * 700, 192)) / 819e9 / (2 * 64 * 0.0005)),
    # the tick that admitted nothing and has operations: the weights eight times and its rows, over all its device seconds
    ("eva_decode_roofline_share", 100.0 * (8 * 2 * (1_621_757_952 - 320 * 4096 + 25 * 4096) + _tick_bytes(8 * 25 * 800, 200)) / 819e9 / (64 * 0.0012)),
])
def test_new_readers_on_hand_built_ticks(reader, want, monkeypatch):
    module = run.load(M, "layers", reader)
    monkeypatch.setattr(module._eva_ticks._decode_programs, "decode_ticks", lambda observed: _ticks())
    got = module.read(_observed())
    assert got == pytest.approx(want, rel=1e-12) and 0 < got <= 100


@pytest.mark.parametrize("reader", NEW)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(reader, monkeypatch):
    """As on a program without the counts (the parent), in a cell whose model has no such window (the counts are 0),
    and on a trace that names no operation (a CPU's): ``None``, and nothing raised."""
    module = run.load(M, "layers", reader)
    for stats in ({"admitted": 0}, {"admitted": 0, "attn_rows_read": 0, "context_rows": 0, "exact_pages": 0, "summary_pages": 0}):
        none = [{"stats": stats, "ops": [("fusion.1", 0.01)], "dispatch": {"decoding": 3, "live_tokens": 9, "tick_block": 8}}]
        monkeypatch.setattr(module._eva_ticks._decode_programs, "decode_ticks", lambda observed, none=none: none)
        assert module.read(_observed()) is None
    monkeypatch.setattr(module._eva_ticks._decode_programs, "decode_ticks", lambda observed: [])
    assert module.read(_observed()) is None
    if reader.endswith(("_roofline", "_roofline_share")):
        monkeypatch.setattr(module._eva_ticks._decode_programs, "decode_ticks", lambda observed: [dict(t, ops=None) for t in _ticks()])
        assert module.read(_observed()) is None


# -- the toy cell, end to end on the CPU

def test_rehearsal_runs_the_evabyte_cell(rehearse):  # noqa: F811
    lines = rehearse("tiny-serve-longchat-bytes", "--trace", "0", "--control", "1", manifest=EVABYTE, seconds="3")
    last = result(lines)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"]["ttft_p90_ms"]["value"] > 0 and last["metrics"]["tpot_p90_ms"]["value"] > 0
    checks = {l["check"]: l for l in lines if "check" in l}
    assert checks["compiles_in_window"]["value"] == 0
    assert next(l for l in lines if l.get("note") == "control")["would_pass"] is False


def test_traced_rehearsal_reads_the_windows_counts(rehearse):  # noqa: F811
    last = result(rehearse("tiny-serve-longchat-bytes", "--trace", "1", manifest=EVABYTE, seconds="3"))
    assert last["correct"] is True
    assert 20 <= last["metrics"]["eva_rows_read_share"]["value"] < 100, "half the toy's requests pass a close"
    assert 5 <= last["metrics"]["eva_summary_pages_share"]["value"] <= 60
    assert "engine_decode_step_ms" in last["metrics"] and "warm_programs" in last["metrics"] and "tick_longest_ms" in last["metrics"]
    assert not any("roofline" in name for name in last["metrics"]), "no share of a peak from a CPU"


def test_toy_manifest_names_only_files_of_its_own():
    with open(EVABYTE) as f:
        stated = json.load(f)
    assert [c["file"] for c in stated["configs"]] == ["tests/chipbench/configs/evabyte-tiny.json"]
    assert [w["traffic"] for w in stated["workloads"]] == ["longchat-bytes-tiny"]
    real = {m["name"]: m for m in M["per_layer"]}
    for m in stated["per_layer"]:
        assert {k: v for k, v in m.items() if k != "workloads"} == {k: v for k, v in real[m["name"]].items() if k != "workloads"}
    assert {m["name"] for m in stated["per_layer"]} == {m["name"] for m in M["per_layer"] if CELL in m.get("workloads", ())}


def test_manifest_gained_the_cell_and_nothing_that_was_there_moved():
    """Entries were appended: the six cells and six configurations of PR 41's manifest stand first and as they
    were, every metric of it stands in its place with its fields, and a list of cells that gained this one gained it
    behind the cells it had. Later cells may follow: nothing here counts the entries, and the set of metrics this
    cell reports may grow."""
    cells = [w["name"] for w in M["workloads"]]
    before = ["mistral7b-serve-chat", "bert-base-train-seq128", "joyai-flash-serve-longchat", "jamba2-3b-serve-longanswer",
              "lfm2-8b-a1b-serve-longanswer", "granite-4.0-h-small-serve-longanswer"]
    assert cells[:6] == before and CELL in cells[6:]
    configs = [c["name"] for c in M["configs"]]
    assert configs[:6] == ["bert-base-uncased", "mistral-7b-v0.1-l16", "joyai-llm-flash-l5", "ai21-jamba2-3b", "lfm2-8b-a1b-l16",
                           "granite-4.0-h-small-l10"] and CONFIG in configs[6:]
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "longchat-bytes", "chips": 1, "why": cell["why"]} and len(cell["why"]) <= 200
    assert [m["name"] for m in M["end_to_end"]] == ["train_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"]
    assert [(m["bound"], m["better"]) for m in M["end_to_end"]] == [(0.01, "higher"), (0.1, "lower"), (0.06, "lower"), (0.1, "lower")]
    assert M["run_seconds"] == 51 and M["command"] == ["python3", "-m", "chipbench"] and M["paths"] == ["chipbench", "tests/chipbench"]
    names = [m["name"] for m in M["per_layer"]]
    assert names[:29] == [
        "train_idle_share", "chat_idle_share", "paged_decode_attention_roofline", "decode_roofline_share", "train_step_ms", "train_mfu",
        "generator_late_p90_ms", "queue_wait_p90_ms", "warm_programs", "first_token_hold_p50_ms", "engine_prefill_ms_per_ktok",
        "engine_decode_step_ms", "tick_host_ms", "chat_prefill_device_share", "train_dispatch_ms", "train_idle_in_dispatch_share",
        "latent_decode_attention_roofline", "routed_experts_roofline", "experts_touched_share", "ssm_state_step_roofline",
        "state_slots_idle_share", "expert_rows_per_visit", "tick_longest_ms", "tick_longest_sync_ms", "tick_longest_cpu_ms",
        "setup_lower_s", "setup_load_s", "ssd_state_step_roofline", "held_expert_pairs_share"]
    assert set(names[29:]) >= set(NEW)
    reports = {m["name"] for m in M["per_layer"] if CELL in m.get("workloads", ())}
    assert reports >= {"chat_idle_share", "generator_late_p90_ms", "queue_wait_p90_ms", "warm_programs", "first_token_hold_p50_ms",
                       "engine_prefill_ms_per_ktok", "engine_decode_step_ms", "tick_host_ms", "chat_prefill_device_share",
                       "tick_longest_ms", "tick_longest_sync_ms", "tick_longest_cpu_ms", *NEW}
    # the two accepted readers that are handed the contexts' sum, of which a closed window leaves a sixteenth: not this cell's
    assert not reports & {"paged_decode_attention_roofline", "decode_roofline_share"}
    for m in M["end_to_end"] + M["per_layer"]:
        listed = m.get("workloads", [])
        if CELL in listed:
            rest = [c for c in listed if c in before]
            assert listed[: len(rest)] == rest and listed.index(CELL) >= len(rest), f"{m['name']}: the cell was appended"
    for name, better, source, layer in (("eva_rows_read_share", "lower", "program_counter", "ops/paged_kv cache"),
                                        ("eva_summary_pages_share", "lower", "program_counter", "scheduler"),
                                        ("eva_decode_attention_roofline", "higher", "device_trace", "kernels"),
                                        ("eva_decode_roofline_share", "higher", "device_trace", "jitted programs")):
        new = next(m for m in M["per_layer"] if m["name"] == name)
        assert new == {"name": name, "unit": "%", "better": better, "source": source, "layer": layer, "moves": "tpot_p90_ms",
                       "workloads": new["workloads"]} and new["workloads"][0] == CELL
    for name in ("ssm_state_step_roofline", "experts_touched_share", "routed_experts_roofline", "latent_decode_attention_roofline", "train_mfu"):
        assert CELL not in next(m for m in M["per_layer"] if m["name"] == name)["workloads"]
