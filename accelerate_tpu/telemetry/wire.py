"""Bytes-on-wire counters: measure collective traffic from the COMPILED
program and cross-check it against the cost model's prediction.

The static tier (``analysis.costmodel.collect_traffic``) prices the
collectives *the author wrote* in the jaxpr; this module counts the
collectives that actually survived compilation — GSPMD both inserts
reductions the jaxpr never shows (the implicit data-parallel grad
all-reduce) and elides ones it can prove redundant. Parsing the
post-partitioning HLO is therefore a genuinely independent measurement:
``measured ~= predicted`` is the cross-check that keeps the wire-byte
model honest (the ``perf_model_drift`` discipline applied to bytes), and
both sides price through the SAME ring formulas
(``analysis.costmodel.ring_wire_bytes``) so a disagreement means missing
or phantom traffic, never unit drift.

Usage::

    compiled = step._jitted.lower(*sample_args).compile()
    measured = hlo_wire_bytes(compiled.as_text())
    telemetry.record_wire_bytes(predicted, measured["total"], label="train_step")

Pure text parsing — no jax import, no backend touch.
"""

from __future__ import annotations

import re
from typing import Optional

#: HLO collective opcode -> costmodel primitive (ring-formula key)
_HLO_TO_PRIM = {
    "all-reduce": "psum",
    "all-gather": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "all-to-all": "all_to_all",
    "collective-permute": "ppermute",
}

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "pred": 1,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+\]")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")


def _result_bytes(result: str) -> tuple:
    """(total payload bytes, {dtype: bytes}) over every shape in the
    result portion (tuples sum their members)."""
    total = 0
    by_dtype: dict[str, int] = {}
    for dtype, dims in _SHAPE_RE.findall(result):
        if dtype not in _DTYPE_BYTES:
            continue
        elems = 1
        for d in dims.split(","):
            if d:
                elems *= int(d)
        nbytes = elems * _DTYPE_BYTES[dtype]
        total += nbytes
        by_dtype[dtype] = by_dtype.get(dtype, 0) + nbytes
    return total, by_dtype


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # [num_groups, group_size] <= [total]
        return int(m.group(2))
    m = _PAIRS_RE.search(line)
    if m:
        # collective-permute carries source_target_pairs, not
        # replica_groups: the "group" is the permutation cycle (a ring
        # handoff over an n-axis is n pairs per ring; follow one cycle)
        nxt = {}
        for pair in m.group(1).split("},{"):
            src, dst = pair.strip("{}").split(",")
            nxt[int(src)] = int(dst)
        start = min(nxt)
        cur, hops = nxt[start], 1
        while cur != start and cur in nxt and hops <= len(nxt):
            cur, hops = nxt[cur], hops + 1
        return hops
    return default


def hlo_collective_sites(hlo_text: str, *, default_group: int = 1) -> list[dict]:
    """Every collective instruction in a compiled HLO module:
    ``{op, prim, result_bytes, group_size}``.

    Plain string splitting, not one grand regex: the result portion may
    be a tuple interleaved with ``/*index=N*/`` comments (XLA's tuple
    all-to-all form — one buffer per split chunk; summing every shape in
    the tuple recovers the full payload). ``-done`` halves of async pairs
    are skipped (the ``-start`` carries the payload)."""
    sites = []
    for line in hlo_text.splitlines():
        if "-done(" in line or "=" not in line:
            continue
        for op in _HLO_TO_PRIM:
            hit = None
            for suffix in ("(", "-start("):
                idx = line.find(f" {op}{suffix}")
                if idx >= 0:
                    hit = idx
                    break
            if hit is None:
                continue
            eq = line.find("= ")
            if eq < 0 or eq > hit:
                continue
            result = line[eq + 2 : hit]
            nbytes, by_dtype = _result_bytes(result)
            sites.append(
                {
                    "op": op,
                    "prim": _HLO_TO_PRIM[op],
                    "result_bytes": nbytes,
                    "dtypes": by_dtype,
                    "group_size": _group_size(line, default_group),
                }
            )
            break
    return sites


def hlo_wire_bytes(hlo_text: str, *, default_group: Optional[int] = None) -> dict:
    """Per-device ring wire bytes the compiled program moves per
    execution, measured from its HLO text and priced through
    ``analysis.costmodel.ring_wire_bytes`` (the shared formulas).

    Operand conventions per op: an all-reduce's result IS the full
    payload; an all-gather's result is the full gathered payload (its
    per-shard input is ``result/n``); a reduce-scatter's result is the
    shard (full payload ``result*n``); all-to-all and permute move their
    own size. Returns ``{"total": int, "by_primitive": {...},
    "sites": [...]}``."""
    from ..analysis.costmodel import ring_wire_bytes

    sites = hlo_collective_sites(hlo_text, default_group=default_group or 1)
    by_prim: dict[str, int] = {}
    total = 0
    for s in sites:
        n = s["group_size"] if default_group is None else max(s["group_size"], default_group)
        if n <= 1:
            continue
        payload = s["result_bytes"]
        if s["prim"] == "reduce_scatter":
            payload *= n
        wire = ring_wire_bytes(s["prim"], payload, n)
        s["wire_bytes"] = wire
        by_prim[s["prim"]] = by_prim.get(s["prim"], 0) + wire
        total += wire
    return {"total": int(total), "by_primitive": by_prim, "sites": sites}


#: requested compression-scheme name -> expected wire payload width
_WIRE_DTYPE_WIDTH = {"bf16": 2, "f16": 2, "fp8": 1, "f8": 1, "int8": 1, "s8": 1}


def wire_dtype_upcast(sites, requested_dtype: str) -> Optional[dict]:
    """Did the compiled program's dominant collective move a WIDER dtype
    than the compression scheme requested? Some backends upcast narrow
    collectives during lowering (XLA:CPU runs bf16 all-reduces in f32),
    which silently erases the wire saving the scheme was chosen for —
    TPU backends keep the narrow dtype on the wire.

    ``sites`` is :func:`hlo_collective_sites` output (or the ``sites``
    list of :func:`hlo_wire_bytes`). Only the payload-dominant site is
    judged: tiny control collectives (an f32 loss pmean, a grad-norm
    psum) legitimately stay wide next to a quantized gradient leg.
    Returns ``{"requested", "requested_bytes", "measured_dtype",
    "measured_bytes", "site_bytes"}`` when an upcast is detected, else
    None."""
    want = _WIRE_DTYPE_WIDTH.get(str(requested_dtype).lower())
    if want is None or not sites:
        return None
    dominant = max(sites, key=lambda s: s.get("result_bytes", 0))
    dtypes = dominant.get("dtypes") or {}
    if not dtypes:
        return None
    # the dominant site's dominant dtype (a fused tuple may mix)
    dtype = max(dtypes, key=dtypes.get)
    width = _DTYPE_BYTES.get(dtype, 0)
    if width <= want:
        return None
    return {
        "requested": str(requested_dtype),
        "requested_bytes": want,
        "measured_dtype": dtype,
        "measured_bytes": width,
        "site_bytes": int(dominant.get("result_bytes", 0)),
    }
