"""BERT-base as published (Devlin et al. 2018, arXiv:1810.04805; config.json): summed
word, position and type embeddings under a LayerNorm; post-norm encoder layers of
multi-head attention and a GELU (erf) feed-forward; a tanh pooler over the first
token and a linear classifier; mean cross-entropy. Float32 throughout.

Departures: dropout is off, as in the timed step. The classifier head is the
fine-tuning head (``num_labels``), not the masked-LM head of the checkpoint.

This file is the family: its seeded weights (``spec``), its plain reference (``loss_fn`` and
``LAYER_NAMES``: a train cell; no serve path) and a train step's operations (``train_flops``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.lowprec import DOTS

LAYER_NAMES = ("q_w", "q_b", "k_w", "k_b", "v_w", "v_b", "o_w", "o_b", "attn_ln_g", "attn_ln_b",
               "ff1_w", "ff1_b", "ff2_w", "ff2_b", "ffn_ln_g", "ffn_ln_b")


def spec(cfg: dict) -> dict:
    layers, hidden, ff = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["intermediate_size"]
    std = cfg.get("initializer_range", 0.02)
    normal, scale = ("normal", std), ("one_plus", 0.1)
    spec = {
        "word_emb": ((cfg["vocab_size"], hidden), normal),
        "pos_emb": ((cfg["max_position_embeddings"], hidden), normal),
        "type_emb": ((cfg["type_vocab_size"], hidden), normal),
        "emb_ln_g": ((hidden,), scale), "emb_ln_b": ((hidden,), normal),
        "ff1_w": ((layers, hidden, ff), normal), "ff1_b": ((layers, ff), normal),
        "ff2_w": ((layers, ff, hidden), normal), "ff2_b": ((layers, hidden), normal),
        "pooler_w": ((hidden, hidden), normal), "pooler_b": ((hidden,), normal),
        "cls_w": ((hidden, cfg["bench"]["num_labels"]), normal), "cls_b": ((cfg["bench"]["num_labels"],), normal),
    }
    for name in ("q", "k", "v", "o"):
        spec[f"{name}_w"] = ((layers, hidden, hidden), normal)
        spec[f"{name}_b"] = ((layers, hidden), normal)
    for name in ("attn_ln", "ffn_ln"):
        spec[f"{name}_g"] = ((layers, hidden), scale)
        spec[f"{name}_b"] = ((layers, hidden), normal)
    return spec


def train_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward and backward of one step: 6 per matmul parameter and token, plus full attention."""
    hidden, ff, layers = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    tokens = batch * seq
    matmul = 2.0 * tokens * layers * (4 * hidden * hidden + 2 * hidden * ff)
    attention = layers * 4.0 * batch * seq * seq * hidden
    return 3.0 * (matmul + attention)


def _layer_norm(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def loss_fn(weights: dict, cfg: dict, batch: dict, dot_name: str = "exact"):
    """Mean classification loss over the rows of ``batch`` (input_ids [B, T], labels [B]; no padding)."""
    dot = DOTS[dot_name]
    ids = batch["input_ids"]
    b, t = ids.shape
    heads = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // heads
    eps = cfg["layer_norm_eps"]
    x = weights["word_emb"][ids] + weights["pos_emb"][:t][None] + weights["type_emb"][0][None, None]
    x = _layer_norm(x, weights["emb_ln_g"], weights["emb_ln_b"], eps)

    def body(x, w):
        split = lambda y: y.reshape(b, t, heads, d)
        q, k, v = (split(dot(x, w[f"{n}_w"]) + w[f"{n}_b"]) for n in ("q", "k", "v"))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * d**-0.5
        ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v, precision="highest")
        x = _layer_norm(x + dot(ctx.reshape(b, t, heads * d), w["o_w"]) + w["o_b"], w["attn_ln_g"], w["attn_ln_b"], eps)
        ff = dot(jax.nn.gelu(dot(x, w["ff1_w"]) + w["ff1_b"], approximate=False), w["ff2_w"]) + w["ff2_b"]
        return _layer_norm(x + ff, w["ffn_ln_g"], w["ffn_ln_b"], eps), None

    x, _ = jax.lax.scan(body, x, {n: weights[n] for n in LAYER_NAMES})
    pooled = jnp.tanh(dot(x[:, 0], weights["pooler_w"]) + weights["pooler_b"])
    logits = dot(pooled, weights["cls_w"]) + weights["cls_b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)[:, 0].mean()
