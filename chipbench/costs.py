"""Operations and bytes that the work requires, from shapes alone. Recomputation and
padding are not counted: these are what the algorithm needs, not what the program does."""

from __future__ import annotations


def _mistral_dims(cfg: dict) -> dict:
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    d = hidden // heads
    return {"hidden": hidden, "d": d, "q_out": heads * d, "kv_out": cfg["num_key_value_heads"] * d,
            "ff": cfg["intermediate_size"], "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"]}


def mistral_layer_matmul_params(cfg: dict) -> int:
    m = _mistral_dims(cfg)
    return m["hidden"] * (m["q_out"] + 2 * m["kv_out"]) + m["q_out"] * m["hidden"] + 3 * m["hidden"] * m["ff"]


def mistral_train_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward and backward of one step: 6 per matmul parameter and token, plus causal attention."""
    m = _mistral_dims(cfg)
    tokens = batch * seq
    matmul = 2.0 * tokens * (m["layers"] * mistral_layer_matmul_params(cfg) + m["hidden"] * m["vocab"])
    attention = m["layers"] * flash_attention_flops(batch, seq, cfg["num_attention_heads"], m["d"], "fwd")
    return 3.0 * (matmul + attention)


def bert_train_flops(cfg: dict, batch: int, seq: int) -> float:
    hidden, ff, layers = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    tokens = batch * seq
    matmul = 2.0 * tokens * layers * (4 * hidden * hidden + 2 * hidden * ff)
    attention = layers * 4.0 * batch * seq * seq * hidden
    return 3.0 * (matmul + attention)


def flash_attention_flops(batch: int, seq: int, heads: int, d: int, which: str) -> float:
    """Causal attention over ``seq`` keys: QK^T and PV in the forward (4 b h s^2 d, halved
    by the causal mask); the backward recomputes the scores once and makes four more
    products (dq: 3 products; dk and dv: 4 products)."""
    fwd = 4.0 * batch * heads * seq * seq * d / 2.0
    return {"fwd": fwd, "dq": 1.5 * fwd, "dkv": 2.0 * fwd}[which]


def mistral_weight_bytes_per_decode_step(cfg: dict, slots: int, itemsize: int = 2) -> float:
    """Every layer's weights, the final norm and the output head, read once; one embedding row a slot."""
    m = _mistral_dims(cfg)
    params = m["layers"] * (mistral_layer_matmul_params(cfg) + 2 * m["hidden"]) + m["hidden"] + m["hidden"] * m["vocab"]
    return float(itemsize) * (params + slots * m["hidden"])


def paged_decode_attention_bytes(cfg: dict, live_tokens: float, slots: int, itemsize: int = 2) -> float:
    """One call (one layer, one token a slot): the live keys and values, the queries and the output."""
    m = _mistral_dims(cfg)
    return float(itemsize) * (2.0 * live_tokens * m["kv_out"] + 2.0 * slots * m["q_out"])


def mistral_cache_bytes_per_decode_step(cfg: dict, live_tokens: float, slots: int, itemsize: int = 2) -> float:
    return cfg["num_hidden_layers"] * paged_decode_attention_bytes(cfg, live_tokens, slots, itemsize)
