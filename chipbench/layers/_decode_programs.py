"""What the readers of the routed experts and the latent kernel share: the decode ticks of the traced
window with the counts their ``engine.tick.done`` span carries, and the device operations that ran
inside each tick's decode program. The trace is opened again here because ``program_trace.phases_of``
keeps no single device event; it is read once a process."""

from __future__ import annotations

import os

from chipbench import program_trace

DECODE_PROGRAM = "paged_decode_tick"
_ticks: dict = {}  # (trace path, mtime) -> decode_ticks: one reduction a process, shared by the readers


def decode_ticks(observed: dict) -> list:
    path = program_trace.newest_trace()
    key = path and (path, os.path.getmtime(path))
    if key not in _ticks:
        _ticks.clear()
        _ticks[key] = _reduce(observed, path) if path else []
    return _ticks[key]


def _reduce(observed: dict, path) -> list:
    """One entry a traced tick that decoded: the tick's ``engine.tick.done`` counts (``stats``), its
    ``engine.decode.dispatch`` counts (``decoding``, ``live_tokens``, ``tick_block``) and ``ops``, the
    device operations ``[(name, seconds)]`` inside its decode program, or ``None`` where that program
    did not run whole inside the window (or the trace names no program, as a CPU's). Empty where the
    program has no such spans."""
    phases = program_trace.phases_of(observed)
    if not phases:
        return []
    raw = program_trace.load(path, observed.get("spans", ()))
    lo, hi = phases["window"]
    spans = phases["spans"]
    programs, ops = [], []
    if raw["modules"]:
        device = min(raw["modules"])
        programs = sorted((s, s + d) for n, s, d in raw["modules"][device]
                          if program_trace.program_name(n) == DECODE_PROGRAM and s >= lo and s + d <= hi)
        ops = sorted((s, n, d) for n, s, d in program_trace.trace.self_times(raw["devices"].get(device, ())))
    out = []
    for i in program_trace.named(phases, "engine.tick"):
        tick = spans[i]
        done = [spans[j] for j in tick["children"] if spans[j]["name"] == "engine.tick.done"]
        dispatch = [spans[j] for j in tick["children"] if spans[j]["name"] == "engine.decode.dispatch"]
        if not done or not dispatch:
            continue
        inside = [p for p in programs if tick["start"] <= p[0] < tick["end"]]
        out.append({"stats": done[0]["stats"], "dispatch": dispatch[0]["stats"],
                    "ops": [(n, d) for s, n, d in ops if inside[0][0] <= s < inside[0][1]] if len(inside) == 1 else None})
    return out


def seconds_of(tick: dict, needle: str) -> float:
    return sum(d for n, d in tick["ops"] or () if needle in n)
