"""The plain references against the program's models at a tiny size, in float32; and the
int8 control, which has to move what the references compute."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.builders import _tree, bert as bert_builder, llama_core_train
from chipbench.reference import bert, lowprec, mistral, train

HERE = os.path.dirname(os.path.abspath(__file__))


def config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_reference_matches_the_llama_core():
    cfg = config("mistral-tiny")
    flat = weights.make(mistral.spec(cfg), seed=7, dtype="float32")
    module, shapes = llama_core_train.abstract_params(llama_core_train.mistral_config(cfg))
    tree = _tree.to_tree(flat, llama_core_train.TABLE, cfg["num_hidden_layers"])
    _tree.check_same_shapes(tree, shapes)
    tokens = np.random.default_rng(0).integers(5, 250, size=(96,)).astype(np.int32)  # longer than the 64-key band
    with jax.default_matmul_precision("highest"):
        want = module.apply({"params": tree}, jnp.asarray(tokens)[None])[0]
    got = mistral.logits_at(flat, cfg, jnp.asarray(tokens), jnp.arange(96))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4
    loss = mistral.loss_fn(flat, cfg, jnp.asarray(tokens)[None, :64])
    logp = jax.nn.log_softmax(want[:63], axis=-1)
    assert float(loss) == pytest.approx(float(-jnp.take_along_axis(logp, jnp.asarray(tokens[1:64])[:, None], -1).mean()), rel=1e-3)


def test_bert_reference_matches_the_bert_model():
    from accelerate_tpu.models import BertConfig, bert_classification_loss, create_bert_model

    cfg = config("bert-tiny")
    flat = weights.make(bert.spec(cfg), seed=11, dtype="float32")
    fields = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads", "intermediate_size",
              "max_position_embeddings", "type_vocab_size", "layer_norm_eps")
    model = create_bert_model(BertConfig(num_labels=2, **{k: cfg[k] for k in fields}), seed=0, seq_len=16)
    tree = _tree.to_tree(flat, bert_builder.TABLE, cfg["num_hidden_layers"])
    _tree.check_same_shapes(tree, model.params)
    rng = np.random.default_rng(1)
    batch = {"input_ids": rng.integers(5, 1000, size=(8, 16)).astype(np.int32),
             "attention_mask": np.ones((8, 16), bool), "labels": rng.integers(0, 2, size=(8,)).astype(np.int32)}
    with jax.default_matmul_precision("highest"):
        want, want_grad = jax.value_and_grad(lambda p: bert_classification_loss(p, batch, model.apply_fn))(tree)
    got, got_grad = jax.value_and_grad(lambda w: bert.loss_fn(w, cfg, {"input_ids": batch["input_ids"], "labels": batch["labels"]}))(flat)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    mine = _tree.to_flat(want_grad, bert_builder.TABLE, cfg["num_hidden_layers"])
    for name in ("word_emb", "q_w", "ff2_w", "ffn_ln_g", "cls_w", "o_b"):
        assert float(jnp.max(jnp.abs(mine[name] - got_grad[name]))) <= 1e-4 * float(jnp.max(jnp.abs(mine[name])) + 1e-9) + 1e-7


def test_int8_dot_rounds_and_differentiates():
    x = jax.random.normal(jax.random.key(0), (32, 64))
    w = jax.random.normal(jax.random.key(1), (64, 48))
    exact, low = lowprec.exact_dot(x, w), lowprec.int8_dot(x, w)
    rel = float(jnp.linalg.norm(low - exact) / jnp.linalg.norm(exact))
    assert 1e-3 < rel < 5e-2  # eight bits: about one percent, never exact
    gx, gw = jax.grad(lambda x, w: lowprec.int8_dot(x, w).sum(), argnums=(0, 1))(x, w)
    ex, ew = jax.grad(lambda x, w: lowprec.exact_dot(x, w).sum(), argnums=(0, 1))(x, w)
    assert 0 < float(jnp.linalg.norm(gx - ex) / jnp.linalg.norm(ex)) < 5e-2
    assert 0 < float(jnp.linalg.norm(gw - ew) / jnp.linalg.norm(ew)) < 5e-2


def test_adamw_follow_and_worst_leaf_gap():
    cfg = config("bert-tiny")
    flat = weights.make(bert.spec(cfg), seed=3, dtype="float32")
    rng = np.random.default_rng(2)
    batches = [{"input_ids": rng.integers(5, 1000, size=(8, 16)).astype(np.int32),
                "labels": rng.integers(0, 2, size=(8,)).astype(np.int32)} for _ in range(3)]
    opt = cfg["bench"]["optimizer"]
    whole = train.follow(bert, cfg, flat, batches, opt, row_block=8)
    blocks = train.follow(bert, cfg, flat, batches, opt, row_block=2)  # blocks of rows give the same gradient
    assert whole["losses"] == pytest.approx(blocks["losses"], rel=1e-5)
    gap, _ = train.worst_leaf_gap(blocks["first_gradient"], whole["first_gradient"])
    assert gap < 1e-3
    skip = train.all_but_zero_leaves(whole["first_gradient"])
    assert skip == {f"k_b[{i}]" for i in range(cfg["num_hidden_layers"])}  # a key bias moves no softmax
    # a step that returns its state unchanged has changed nothing: every leaf is off by its whole norm
    still = {k: 0.0 for k in whole["change"]}
    assert train.worst_leaf_gap(still, whole["change"], skip)[0] == pytest.approx(1.0)
    # first step of Adam: every element moves by about lr
    n = flat["cls_w"].size
    assert whole["change"]["cls_w"] == pytest.approx(3 * opt["lr"] * n**0.5, rel=0.5)


def test_seeds_beyond_32_bits_make_weights():
    spec = {"a": ((4, 4), ("normal", 0.02)), "g": ((4,), ("one_plus", 0.1))}
    big, other = weights.make(spec, 2**31 + 99, "float32"), weights.make(spec, 99, "float32")
    again = weights.make(spec, 2**31 + 99, "bfloat16")
    assert not np.allclose(big["a"], other["a"]) and big["a"].dtype == jnp.float32
    assert np.allclose(np.asarray(again["a"], np.float32), big["a"], atol=2e-4) and abs(float(big["g"].mean()) - 1) < 0.3


def test_matrix_leaves_split_vectors_from_matrices():
    cfg = config("bert-tiny")
    spec = bert.spec(cfg)
    matrices = train.matrix_leaves(spec, bert.LAYER_NAMES)
    assert "word_emb" in matrices and "q_w[0]" in matrices and "ff2_w[3]" in matrices and "cls_w" in matrices
    assert not {"cls_b", "q_b[0]", "attn_ln_g[2]", "emb_ln_g"} & matrices
    layers = cfg["num_hidden_layers"]
    assert len(matrices) == 5 + 6 * layers  # three embeddings, pooler, classifier; q k v o ff1 ff2 a layer
