"""``bert``: BERT for sequence classification through ``Accelerator.prepare_model`` /
``prepare_optimizer`` / ``build_train_step``, as ``bench.py`` and ``chip_smoke.py`` build it."""

from __future__ import annotations

from chipbench.builders._trainer import Trainer
from chipbench.builders._tree import check_same_shapes, reset_accelerator_state, to_tree

_LAYER = "encoder|layer_{i}|"
TABLE = [
    ("word_emb", "encoder|embeddings/word_embeddings|embedding", False),
    ("pos_emb", "encoder|embeddings/position_embeddings|embedding", False),
    ("type_emb", "encoder|embeddings/token_type_embeddings|embedding", False),
    ("emb_ln_g", "encoder|embeddings/norm|scale", False), ("emb_ln_b", "encoder|embeddings/norm|bias", False),
    ("q_w", _LAYER + "attention|query|kernel", True), ("q_b", _LAYER + "attention|query|bias", True),
    ("k_w", _LAYER + "attention|key|kernel", True), ("k_b", _LAYER + "attention|key|bias", True),
    ("v_w", _LAYER + "attention|value|kernel", True), ("v_b", _LAYER + "attention|value|bias", True),
    ("o_w", _LAYER + "attention|out|kernel", True), ("o_b", _LAYER + "attention|out|bias", True),
    ("attn_ln_g", _LAYER + "attention_norm|scale", True), ("attn_ln_b", _LAYER + "attention_norm|bias", True),
    ("ff1_w", _LAYER + "ffn/intermediate|kernel", True), ("ff1_b", _LAYER + "ffn/intermediate|bias", True),
    ("ff2_w", _LAYER + "ffn/output|kernel", True), ("ff2_b", _LAYER + "ffn/output|bias", True),
    ("ffn_ln_g", _LAYER + "ffn_norm|scale", True), ("ffn_ln_b", _LAYER + "ffn_norm|bias", True),
    ("pooler_w", "pooler|kernel", False), ("pooler_b", "pooler|bias", False),
    ("cls_w", "classifier|kernel", False), ("cls_b", "classifier|bias", False),
]


def build(config: dict, traffic: dict, seed: int, make_weights) -> Trainer:
    import jax
    import numpy as np
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import BertConfig, bert_classification_loss, create_bert_model
    from accelerate_tpu.parallel.mesh import batch_sharding
    from accelerate_tpu.utils import MixedPrecisionPolicy

    bench = config["bench"]
    reset_accelerator_state()
    accelerator = Accelerator(
        mixed_precision="bf16", kwargs_handlers=[MixedPrecisionPolicy(softmax_dtype=bench["softmax_dtype"])]
    )
    fields = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads", "intermediate_size",
              "max_position_embeddings", "type_vocab_size", "hidden_dropout_prob", "attention_probs_dropout_prob",
              "layer_norm_eps")
    cfg = BertConfig(num_labels=bench["num_labels"], **{k: config[k] for k in fields})
    batch, seq = traffic["batch"], traffic["seq"]
    model = create_bert_model(cfg, seed=0, seq_len=seq)
    layers = cfg.num_hidden_layers
    tree = to_tree(make_weights(), TABLE, layers)
    check_same_shapes(tree, model.params)
    model.params = tree
    model = accelerator.prepare_model(model)
    opt = bench["optimizer"]
    accelerator.prepare_optimizer(
        optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"], weight_decay=opt["weight_decay"])
    )
    step = accelerator.build_train_step(lambda p, b: bert_classification_loss(p, b, model.apply_fn))

    global_batch = batch * accelerator.num_data_shards
    rng = np.random.default_rng(seed)
    # One row in sixteen is labelled 0 and the rest 1; the seed says which. A freshly seeded model predicts
    # nearly the same for every row, so the first gradient is that prediction less the batch's share of ones,
    # times one direction: with labels drawn evenly the two met within 0.005 on a seed in thirty, the whole
    # gradient cancelled down to its rounding and the run read ``correct: false`` (PERF.md 6, PR 26).
    ones = np.arange(global_batch) % 16 != 0
    batches = [
        {"input_ids": rng.integers(5, cfg.vocab_size - 1, size=(global_batch, seq)).astype(np.int32),
         "attention_mask": np.ones((global_batch, seq), np.bool_),
         "labels": rng.permutation(ones).astype(np.int32)}
        for _ in range(traffic["distinct_batches"])
    ]
    sharding = batch_sharding(accelerator.mesh)
    return Trainer(
        accelerator=accelerator, model=model, step=step, table=TABLE, layers=layers, b1=opt["b1"], batches=batches,
        device_batch=lambda b: jax.device_put(b, sharding), tokens_per_step=global_batch * seq,
        ref_batch=lambda b: {"input_ids": b["input_ids"], "labels": b["labels"]},
    )
