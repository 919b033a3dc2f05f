"""Seeded weights, made by the benchmark on the device in one jitted call.

A family's ``*_spec`` lists every tensor by the benchmark's own name with its
shape and how it is drawn. The plain references read these names; a builder
maps them onto the program's parameter tree. Nothing here imports the program.
"""

from __future__ import annotations


def seed_key(seed: int):
    """A key from any whole-number seed, also one beyond 32 signed bits."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def mistral_spec(cfg: dict) -> dict:
    layers, hidden, ff, vocab = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    dim = hidden // cfg["num_attention_heads"]
    q_out, kv_out = cfg["num_attention_heads"] * dim, cfg["num_key_value_heads"] * dim
    std = cfg.get("initializer_range", 0.02)
    normal, scale = ("normal", std), ("one_plus", 0.1)
    return {
        "embed": ((vocab, hidden), normal),
        "wq": ((layers, hidden, q_out), normal), "wk": ((layers, hidden, kv_out), normal),
        "wv": ((layers, hidden, kv_out), normal), "wo": ((layers, q_out, hidden), normal),
        "w_gate": ((layers, hidden, ff), normal), "w_up": ((layers, hidden, ff), normal),
        "w_down": ((layers, ff, hidden), normal),
        "norm_attn": ((layers, hidden), scale), "norm_mlp": ((layers, hidden), scale),
        "norm_final": ((hidden,), scale), "lm_head": ((hidden, vocab), normal),
    }


def bert_spec(cfg: dict) -> dict:
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


SPECS = {"mistral": mistral_spec, "bert": bert_spec}


def make(spec: dict, seed: int, dtype: str, shardings: dict | None = None) -> dict:
    """Every tensor of ``spec`` from ``seed``, in ``dtype``, in one jitted call.
    The same seed gives the same values whatever ``shardings`` lays them out as."""
    import jax
    import jax.numpy as jnp

    names = sorted(spec)
    dt = jnp.dtype(dtype)

    def draw(key):
        out = {}
        for i, name in enumerate(names):
            shape, (kind, amount) = spec[name]
            noise = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * amount
            out[name] = ((1.0 + noise) if kind == "one_plus" else noise).astype(dt)
        return out

    out_shardings = None if shardings is None else {n: shardings[n] for n in names}
    return jax.jit(draw, out_shardings=out_shardings)(seed_key(seed))


def count(spec: dict) -> int:
    total = 0
    for shape, _ in spec.values():
        n = 1
        for s in shape:
            n *= s
        total += n
    return total
