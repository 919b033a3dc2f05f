"""From the profiler's ``.xplane.pb`` to busy and idle time, time per operation,
exposed collective time, and idle gaps by what the host was doing.

Read with ``jax.profiler.ProfileData`` alone. Device planes are ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per executed HLO operation, with control-flow
operations (``while``, ``conditional``) enclosing their bodies, so time per operation
is self time: an event's duration less its children's. Host spans are the
benchmark's own ``TraceAnnotation`` names on the ``/host:CPU`` plane, same clock.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute",
               "collective-broadcast", "send", "recv")
CPU_OPS_LINES = ("tf_XLAPjRtCpuClient", "tf_XLAEigen")
WINDOW_SPAN = "window"
NO_SPAN = "_no_span_"


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_family(name: str) -> str:
    """``%fusion.123`` -> ``fusion``; ``paged_decode_attention.2`` -> ``paged_decode_attention``."""
    name = name.lstrip("%").split(" ")[0]
    return re.sub(r"(\.\d+)+$", "", name)


def is_collective(family: str) -> bool:
    return family.startswith(COLLECTIVES)


def load(path: str, span_names) -> dict:
    """``{"devices": {index: [(name, start_s, dur_s)]}, "spans": [(name, start_s, dur_s)]}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    wanted = set(span_names) | {WINDOW_SPAN}
    devices, spans, host_ops = {}, [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            events = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    events.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events if e.name in wanted)
                if line.name.startswith(CPU_OPS_LINES):
                    host_ops.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events if e.duration_ns)
    if not devices and host_ops:
        devices[0] = host_ops  # a CPU rehearsal: XLA:CPU runs its operations on host threads
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def self_times(events) -> list:
    """``[(name, start, self_seconds)]``: each event's duration less its direct children's."""
    out, stack = [], []  # stack of [end, index into out]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start + 1e-12:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            out[stack[-1][1]] = (parent[0], parent[1], parent[2] - dur)
        out.append((name, start, dur))
        stack.append([start + dur, len(out) - 1])
    return [(n, s, max(d, 0.0)) for n, s, d in out]


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[start, end]`` pieces of ``intervals`` clipped to ``[lo, hi]``."""
    merged = []
    for start, end in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _gaps(busy, lo: float, hi: float) -> list:
    gaps, at = [], lo
    for start, end in busy:
        if start > at:
            gaps.append((at, start))
        at = max(at, end)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def reduce(raw: dict) -> dict:
    """The numbers every reader takes from one trace. Times in seconds; ``busy_s``,
    ``op_seconds`` and ``collective_s`` are averages over the devices traced."""
    devices = {i: ev for i, ev in raw["devices"].items() if ev}
    if not devices:
        raise ValueError("the trace holds no device operation")
    window = [(s, s + d) for n, s, d in raw["spans"] if n == WINDOW_SPAN]
    if window:
        lo, hi = min(s for s, _ in window), max(e for _, e in window)
    else:
        lo = min(s for ev in devices.values() for _, s, _ in ev)
        hi = max(s + d for ev in devices.values() for _, s, d in ev)
    n = len(devices)
    op_seconds, op_calls = collections.Counter(), collections.Counter()
    busy_s = collective_s = 0.0
    first_busy = None
    for index in sorted(devices):
        inside = [(name, s, d) for name, s, d in devices[index] if s + d > lo and s < hi]
        busy = union([(s, s + d) for _, s, d in inside], lo, hi)
        if first_busy is None:
            first_busy = busy
        busy_s += sum(e - s for s, e in busy) / n
        for name, s, d in self_times(inside):
            family = op_family(name)
            op_seconds[family] += d / n
            op_calls[family] += 1.0 / n
            if is_collective(family):
                collective_s += d / n
    spans = [(name, s, s + d) for name, s, d in raw["spans"] if name != WINDOW_SPAN]
    starts = [s for _, s, _ in spans]
    idle_by_span = collections.Counter()
    for start, end in _gaps(first_busy, lo, hi):
        mid = (start + end) / 2.0
        owner = NO_SPAN
        at = bisect.bisect_right(starts, mid)
        for k in range(at - 1, max(-1, at - 9), -1):  # the benchmark's spans do not nest: a few back is enough
            if spans[k][1] <= mid < spans[k][2]:
                owner = spans[k][0]
                break
        idle_by_span[owner] += end - start
    span_seconds = collections.Counter()
    for name, s, e in spans:
        span_seconds[name] += max(0.0, min(e, hi) - max(s, lo))
    return {
        "window_s": hi - lo, "busy_s": busy_s, "devices": n,
        "op_seconds": dict(op_seconds), "op_calls": dict(op_calls), "collective_s": collective_s,
        "idle_by_span": dict(idle_by_span), "span_seconds": dict(span_seconds),
    }


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced["idle_by_span"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": [[k, v] for k, v in gaps]}
