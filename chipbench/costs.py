"""Operations and bytes that a kernel's work requires, from shapes alone. Recomputation and
padding are not counted: these are what the algorithm needs, not what the program does.
What a whole model needs (a train step's operations, a decode step's bytes) is its family's
to say: ``reference/<family>.py``."""

from __future__ import annotations


def flash_attention_flops(batch: int, seq: int, heads: int, d: int, which: str) -> float:
    """Causal attention over ``seq`` keys: QK^T and PV in the forward (4 b h s^2 d, halved
    by the causal mask); the backward recomputes the scores once and makes four more
    products (dq: 3 products; dk and dv: 4 products)."""
    fwd = 4.0 * batch * heads * seq * seq * d / 2.0
    return {"fwd": fwd, "dq": 1.5 * fwd, "dkv": 2.0 * fwd}[which]


def paged_decode_attention_bytes(q_out: int, kv_out: int, live_tokens: float, slots: int, itemsize: int = 2) -> float:
    """One call (one layer, one token a slot): the live keys and values (``kv_out`` wide each),
    the queries and the output (``q_out`` wide each)."""
    return float(itemsize) * (2.0 * live_tokens * kv_out + 2.0 * slots * q_out)
