"""The program's own phases, read from the run's ``.xplane.pb``.

The program marks its phases with ``jax.profiler.TraceAnnotation`` (names that start with
``engine.`` or ``train.``, counts as event stats), so they lie in the profiler's trace on the
device lines' clock, inside the benchmark's own ``step`` spans. ``trace.load`` keeps only
the generator's span names and ``observed`` carries no path, so this module opens the trace
itself: the newest ``.xplane.pb`` under ``<chipbench.run.ROOT>/.cache/chipbench_trace/``,
which ``Context.stop_trace`` has just written. It is reduced once per process; every reader
under ``layers/`` shares the result, and the first one prints a ``program_phases`` note.

What a reader gets (``phases_of(observed)``, or ``None`` when the trace holds no such span,
as with a program that has none):

- ``spans``: every program span and every benchmark span of the host thread that ran the
  program, in start order: ``name``, ``start``/``end`` (seconds on the trace's clock),
  ``stats``, ``parent`` and ``children`` (indices), ``self_s`` (duration less its children);
- ``idle_by_phase`` (by name) and ``idle_by_span`` (by index): device idle seconds inside the
  window by the innermost span over each gap's midpoint (a program span where there is one,
  else the benchmark's, else ``_no_span_`` / ``None``);
- ``program_seconds``: device seconds per executed program, from the ``XLA Modules`` line,
  named as ``ProgramCache`` logs it (``jit_`` and the fingerprint stripped);
- ``window``: ``(lo, hi)``; ``profile_start_ns``: the trace's ``profile_start_time`` (unix
  ns; an event's ``start`` counts from it).
"""

from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re

from . import stats, trace

PROGRAM_PREFIXES = ("engine.", "train.")
MODULES_LINE = "XLA Modules"
ROOT_PHASES = ("engine.tick", "train.step")  # spans whose children tile them: not leaves
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
_reduced: dict = {}  # (path, mtime) -> phases: one reduction a process


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES)


def check_names(benchmark_spans) -> None:
    """The benchmark selects its host events by exact name and this module selects the
    program's by prefix: a benchmark span under a program prefix would be read as both."""
    clash = sorted(n for n in benchmark_spans if is_program_span(n))
    if clash:
        raise ValueError(f"benchmark spans {clash} bear the program's prefixes {PROGRAM_PREFIXES}")


def program_name(module: str) -> str:
    """``jit_prefill_b256(6074760096634504725)`` -> ``prefill_b256``."""
    return _MODULE.match(module).group(1)


def newest_trace():
    from . import run

    found = glob.glob(os.path.join(run.ROOT, ".cache", "chipbench_trace", "*", "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path: str, benchmark_spans) -> dict:
    """Host spans of the program's thread with their stats, device operations and modules."""
    from jax.profiler import ProfileData

    check_names(benchmark_spans)
    wanted = set(benchmark_spans) | {trace.WINDOW_SPAN}
    data = ProfileData.from_file(path)
    lines, devices, modules, host_ops, start_ns = [], {}, {}, [], 0
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                events = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events]
                if line.name == trace.OPS_LINE:
                    devices.setdefault(int(m.group(1)), []).extend(events)
                elif line.name == MODULES_LINE:
                    modules.setdefault(int(m.group(1)), []).extend(events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [{"name": e.name, "start": e.start_ns * 1e-9, "end": (e.start_ns + e.duration_ns) * 1e-9,
                          "stats": dict(e.stats) if is_program_span(e.name) else {}}
                         for e in line.events if e.name in wanted or is_program_span(e.name)]
                lines.append(spans)
                if line.name.startswith(trace.CPU_OPS_LINES):
                    host_ops.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events if e.duration_ns)
        elif plane.name == "Task Environment":
            start_ns = int(dict(plane.stats).get("profile_start_time", 0))
    if not devices and host_ops:
        devices[0] = host_ops  # a CPU rehearsal: XLA:CPU runs its operations on host threads
    # the thread that ran the program: the one with most of its spans, else of the benchmark's
    spans = max(lines, key=lambda ls: (sum(is_program_span(s["name"]) for s in ls), len(ls)), default=[])
    return {"spans": spans, "devices": devices, "modules": modules, "profile_start_ns": start_ns}


def nest(spans: list) -> list:
    """Spans of one thread in start order, each with ``parent``, ``children`` and ``self_s``."""
    out = sorted((dict(s) for s in spans), key=lambda s: (s["start"], -(s["end"] - s["start"])))
    stack = []
    for i, s in enumerate(out):
        while stack and out[stack[-1]]["end"] <= s["start"] + 1e-12:
            stack.pop()
        s["parent"], s["children"], s["self_s"] = (stack[-1] if stack else None), [], s["end"] - s["start"]
        if stack:
            parent = out[stack[-1]]
            parent["children"].append(i)
            parent["self_s"] -= s["end"] - s["start"]
        stack.append(i)
    for s in out:
        s["self_s"] = max(s["self_s"], 0.0)
    return out


def innermost(spans: list, starts: list, at: float):
    """Index of the deepest span of ``nest``'s output that holds instant ``at``, or ``None``."""
    i = bisect.bisect_right(starts, at) - 1
    while i is not None and i >= 0:
        if spans[i]["start"] <= at < spans[i]["end"]:
            return i
        i = spans[i]["parent"]
    return None


def reduce(raw: dict) -> dict:
    window = [s for s in raw["spans"] if s["name"] == trace.WINDOW_SPAN]
    spans = nest([s for s in raw["spans"] if s["name"] != trace.WINDOW_SPAN])
    if window:
        lo, hi = min(s["start"] for s in window), max(s["end"] for s in window)
    else:
        lo, hi = min((s["start"] for s in spans), default=0.0), max((s["end"] for s in spans), default=0.0)
    starts = [s["start"] for s in spans]
    idle_by_span, idle_by_phase = collections.Counter(), collections.Counter()
    devices = {i: ev for i, ev in raw["devices"].items() if ev}
    if devices:
        first = devices[min(devices)]
        busy = trace.union([(s, s + d) for _, s, d in first if s + d > lo and s < hi], lo, hi)
        for start, end in trace._gaps(busy, lo, hi):
            owner = innermost(spans, starts, (start + end) / 2.0)
            idle_by_span[owner] += end - start
            idle_by_phase[trace.NO_SPAN if owner is None else spans[owner]["name"]] += end - start
    program_seconds = collections.Counter()
    for events in raw["modules"].values():
        for name, s, d in events:
            program_seconds[program_name(name)] += max(0.0, min(s + d, hi) - max(s, lo)) / len(raw["modules"])
    return {"spans": spans, "idle_by_span": dict(idle_by_span), "idle_by_phase": dict(idle_by_phase),
            "program_seconds": dict(program_seconds), "window": (lo, hi), "profile_start_ns": raw["profile_start_ns"]}


def ancestor(spans: list, i: int, name: str):
    """Index of the nearest enclosing span called ``name``, or ``None``."""
    i = spans[i]["parent"]
    while i is not None and spans[i]["name"] != name:
        i = spans[i]["parent"]
    return i


def summary(phases: dict) -> dict:
    """The ``program_phases`` note: seconds and device-idle seconds per phase, device seconds per
    program, how much of a tick or a step its children cover, and how much of the device idle
    inside the benchmark's ``step`` spans lies in a leaf phase of the program."""
    spans = phases["spans"]
    seconds, self_seconds, calls = collections.Counter(), collections.Counter(), collections.Counter()
    for s in spans:
        if is_program_span(s["name"]):
            seconds[s["name"]] += s["end"] - s["start"]
            self_seconds[s["name"]] += s["self_s"]
            calls[s["name"]] += 1
    cover = {}
    for root in ROOT_PHASES:
        shares = [1.0 - s["self_s"] / (s["end"] - s["start"]) for s in spans if s["name"] == root and s["end"] > s["start"]]
        if shares:
            cover[root] = {"min": min(shares), "median": stats.median(shares)}
    in_step = in_leaf = 0.0
    for owner, idle_s in phases["idle_by_span"].items():
        if owner is None:
            continue
        inside_step = spans[owner]["name"] == "step" or ancestor(spans, owner, "step") is not None
        if inside_step:
            in_step += idle_s
            if is_program_span(spans[owner]["name"]) and spans[owner]["name"] not in ROOT_PHASES:
                in_leaf += idle_s
    return {"phase_seconds": dict(seconds), "phase_self_seconds": dict(self_seconds), "phase_calls": dict(calls),
            "idle_seconds": phases["idle_by_phase"], "program_seconds": phases["program_seconds"],
            "children_cover": cover, "idle_in_step_s": in_step,
            "idle_in_leaf_share": in_leaf / in_step if in_step else None,
            "window_s": phases["window"][1] - phases["window"][0]}


def phases_of(observed: dict):
    """The reduced phases of this run's trace, or ``None`` where it holds no program span."""
    path = newest_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _reduced:
        phases = reduce(load(path, observed.get("spans", ())))
        if phases["program_seconds"] or any(is_program_span(s["name"]) for s in phases["spans"]):
            print(json.dumps({"note": "program_phases", **summary(phases)}), flush=True)
        _reduced.clear()
        _reduced[key] = phases if any(is_program_span(s["name"]) for s in phases["spans"]) else None
    return _reduced[key]


# -- what the readers under layers/ compute

def named(phases: dict, name: str) -> list:
    return [i for i, s in enumerate(phases["spans"]) if s["name"] == name]


def median_ms(seconds: list):
    return stats.median([s * 1e3 for s in seconds]) if seconds else None


def first_token_hold_ms(phases: dict):
    """End of the enclosing ``engine.tick`` less the end of ``engine.prefill.sync``: how long a
    first token that is on the host waits for ``step()`` to return."""
    spans, holds = phases["spans"], []
    for i in named(phases, "engine.prefill.sync"):
        tick = ancestor(spans, i, "engine.tick")
        if tick is not None:
            holds.append(spans[tick]["end"] - spans[i]["end"])
    return median_ms(holds)


def prefill_ms_per_ktok(phases: dict):
    """A request's first ``engine.prefill.dispatch`` to the end of its ``engine.prefill.sync``,
    per 1000 prompt tokens."""
    spans, began, rates = phases["spans"], {}, []
    for i in named(phases, "engine.prefill.dispatch"):
        began.setdefault(spans[i]["stats"].get("uid"), spans[i])
    for i in named(phases, "engine.prefill.sync"):
        first = began.get(spans[i]["stats"].get("uid"))
        if first is not None and first["stats"].get("prompt_tokens"):
            rates.append((spans[i]["end"] - first["start"]) / (first["stats"]["prompt_tokens"] / 1e3))
    return median_ms(rates)


def decode_step_ms(phases: dict):
    """``engine.decode.dispatch`` to the end of the same tick's ``engine.decode.sync``, over ``tick_block``."""
    steps, dispatch = [], None
    for s in phases["spans"]:
        if s["name"] == "engine.decode.dispatch":
            dispatch = s
        elif s["name"] == "engine.decode.sync" and dispatch is not None and dispatch["stats"].get("tick_block"):
            steps.append((s["end"] - dispatch["start"]) / dispatch["stats"]["tick_block"])
            dispatch = None
    return median_ms(steps)


def tick_host_ms(phases: dict):
    """``engine.tick`` less the phases in which the host waits for the device (``*.sync``)."""
    spans, host = phases["spans"], []
    for i in named(phases, "engine.tick"):
        waits = sum(spans[j]["end"] - spans[j]["start"] for j in spans[i]["children"] if spans[j]["name"].endswith(".sync"))
        host.append(spans[i]["end"] - spans[i]["start"] - waits)
    return median_ms(host)


def program_share(phases: dict, busy_s: float, prefixes: tuple):
    """Device seconds of the programs whose names start with ``prefixes``, over ``busy_s``, in percent."""
    seconds = sum(v for k, v in phases["program_seconds"].items() if k.startswith(prefixes))
    return 100.0 * seconds / busy_s if busy_s else None


def span_ms(phases: dict, name: str):
    spans = phases["spans"]
    return median_ms([spans[i]["end"] - spans[i]["start"] for i in named(phases, name)])


def idle_share_within(phases: dict, prefix: str):
    """Device idle seconds owned by spans whose names start with ``prefix``, over the window, in percent."""
    lo, hi = phases["window"]
    idle = sum(v for k, v in phases["idle_by_phase"].items() if k.startswith(prefix))
    return 100.0 * idle / (hi - lo) if hi > lo else None
