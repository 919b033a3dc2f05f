"""What belongs to a model family is in the family's own file and nowhere else: the seeded weights
are the ones the benchmark has always made, the generic readers read what they read with the
family's cost counts, and no general file of the harness names a family."""

import hashlib
import io
import json
import os
import tokenize

import numpy as np
import pytest

from chipbench import layer_tools, run, weights

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    M = json.load(f)

# sha256 over every tensor (name, type, shape, bytes; names sorted) of ``weights.make`` at the parent
# commit of PR 26 (cbb1c68: ``weights.mistral_spec`` / ``weights.bert_spec``), on the host's CPU
PARENT_DIGESTS = {
    ("mistral", "mistral-tiny", 5): "637ef434df1b85151bb339cd5cf27c7f6e720cd4ba50ef037d8942c3b8012fdb",
    ("mistral", "mistral-tiny", 2**31 + 99): "0466a01d3bfb3a4f85c94c3cdc13def7f8872d04a3eaa7a841548cba23e8493d",
    ("bert", "bert-tiny", 5): "815b90bd6f1c679e5b3a67454e109fc9913a550e5bc0d0e9ee8744de91bdb116",
    ("bert", "bert-tiny", 2**31 + 99): "9f1561d2e566f2f3d45efc8dc91199ceba268f5431e8b0ff6fe00f485cb8c84b",
}


def config(name, where=os.path.join(HERE, "configs")):
    with open(os.path.join(where, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("family,name,seed", sorted(PARENT_DIGESTS))
def test_seeded_weights_are_the_parents_bit_for_bit(family, name, seed):
    cfg = config(name)
    made = weights.make(run.load(M, "reference", family).spec(cfg), seed, cfg["bench"]["param_dtype"])
    digest = hashlib.sha256()
    for tensor in sorted(made):
        x = np.asarray(made[tensor])
        digest.update(f"{tensor}:{x.dtype}:{x.shape}:".encode())
        digest.update(x.tobytes())
    assert digest.hexdigest() == PARENT_DIGESTS[family, name, seed]


def test_no_general_file_of_the_harness_names_a_family():
    """Outside ``reference/`` (a family's own files) and ``builders/`` (which import the program), no
    Python file under ``chipbench/`` holds a family's name, comments apart."""
    general = {"__init__", "train", "lowprec"}  # the files under reference/ that are no family's
    families = {f[:-3] for f in os.listdir(os.path.join(ROOT, "chipbench", "reference")) if f.endswith(".py")} - general
    assert {"mistral", "bert"} <= families
    named = []
    for where, _, files in os.walk(os.path.join(ROOT, "chipbench")):
        if os.path.basename(where) in ("reference", "builders", "__pycache__"):
            continue
        for name in (f for f in files if f.endswith(".py")):
            with open(os.path.join(where, name)) as f:
                code = " ".join(t.string for t in tokenize.generate_tokens(io.StringIO(f.read()).readline)
                                if t.type != tokenize.COMMENT).lower()
            named += [(os.path.join(where, name), family) for family in families if family in code]
    assert not named
    for name in general:
        with open(os.path.join(ROOT, "chipbench", "reference", name + ".py")) as f:
            assert not any(family in f.read().lower() for family in families), name


def observed_of_a_chat_run():
    ticks = [{"start": 0.1 * i, "end": 0.1 * i + 0.26 + 0.001 * i, "prefills": int(i % 3 == 0), "decoding": 20 + i,
              "live_tokens": 9000 + 517 * i, "first_token_prompt_tokens": 0, "queue_len": 0} for i in range(7)]
    cfg = config("mistral-7b-v0.1-l16", os.path.join(ROOT, "chipbench", "configs"))
    return {"ticks": ticks, "tick_block": 8, "config": cfg, "family": run.load(M, "reference", cfg["bench"]["reference"]),
            "device": {"kind": "TPU v5 lite"}, "traced": (0.25, 0.65),
            "trace": {"op_seconds": {"paged_decode_attention": 1.7, "fusion": 1.0},
                      "op_calls": {"paged_decode_attention": 1280.0, "fusion": 5.0}, "busy_s": 3.0, "window_s": 4.0}}


@pytest.mark.parametrize("reader,parents", [("decode_roofline_share", 29.465069729373166),
                                            ("paged_decode_attention_roofline", 3.9731111743158807)])
def test_generic_readers_read_what_the_parents_read(reader, parents):
    """The value is the parent commit's reader's (``costs.mistral_*``) on the same ``observed``, to every digit."""
    assert getattr(layer_tools, reader)(observed_of_a_chat_run()) == parents


@pytest.mark.parametrize("family,name,shape,parents", [
    ("mistral", "mistral-7b-v0.1-l16", (4, 2048), 184563334643712.0), ("bert", "bert-base-uncased", (256, 128), 17162689314816.0)])
def test_train_flops_are_the_parents(family, name, shape, parents):
    cfg = config(name, os.path.join(ROOT, "chipbench", "configs"))
    assert run.load(M, "reference", family).train_flops(cfg, *shape) == parents


@pytest.mark.parametrize("kind", ["layers", "reference", "builders", "generators"])
def test_a_file_that_is_not_there_is_one_message(kind):
    with pytest.raises(SystemExit) as refused:
        run.load(M, kind, "nowhere")
    assert str(refused.value) == f"chipbench: no {kind} file named 'nowhere' under {M['paths']}"
