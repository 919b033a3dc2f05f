"""What the readers of the program's phase log share. The program stamps every phase on the host's
monotonic clock whether or not a profiler runs (``accelerate_tpu.telemetry.trace.phase_log``): one
record a tick or a train step (wall, the thread's CPU time, the gap since the root before, counts,
time per child) and one span a program traced, lowered, loaded or compiled. The readers run in the
run's own process after the window and ask for that log: it covers the **whole** window and set-up,
where the profiler's trace covers the last four seconds. A program without the log (the parent of
the PR that brought it) gives ``None`` everywhere and no note."""

from __future__ import annotations

import json
import time

from chipbench import stats

AGREE_MS = 1.0  # a generator's step() holds its tick's record and little else: the two durations agree within this ...
APART = 10  # ... in all but one pair in this many (the log's own reads of the thread's CPU clock lie between them)
LONGEST = 3
_memo: dict = {}  # the run's ``observed`` and what was read of it: one reduction a run, shared by the readers


def the_log():
    try:
        from accelerate_tpu.telemetry.trace import phase_log
    except ImportError:
        return None
    return phase_log()


def _once(what: str, observed: dict, read):
    if _memo.get("of") is not observed:  # the harness hands every reader of a run the same dict
        _memo.clear()
        _memo["of"] = observed
    if what not in _memo:
        _memo[what] = read(observed)
    return _memo[what]


def _is_load(span: dict) -> bool:
    return span["name"] == "program.load" or span.get("stage") == "compile"


def longest_tick(observed: dict):
    """The ``engine.tick`` record with the longest wall among the ticks begun inside the measured
    window, or ``None``. ``observed["ticks"]`` lists every ``step()`` the generator made after warm-up,
    in order, so the log's last ``len(ticks)`` ``engine.tick`` roots are those: the pairs' durations
    have to agree within ``AGREE_MS`` (all but one in ``APART``: between the generator's stamps and the
    tick's lie the log's two reads of the thread's CPU clock, system calls that a crowded host now and
    then holds for a millisecond; another engine's ticks would disagree nearly everywhere), else nothing
    is read. Prints the ``slow_ticks`` note, which counts the pairs apart."""
    return _once("tick", observed, _longest_tick)


def _longest_tick(observed: dict):
    log, ticks = the_log(), observed.get("ticks")
    if log is None or not ticks:
        return None
    roots = log.roots("engine.tick", n=len(ticks))
    apart = sum(abs(r.wall_ns / 1e6 - (t["end"] - t["start"]) * 1e3) > AGREE_MS for r, t in zip(roots, ticks))
    if len(roots) != len(ticks) or apart > max(1, len(ticks) // APART):
        return None
    closes = observed["traced"][1] if observed.get("traced") else float("inf")
    inside = [r for r, t in zip(roots, ticks) if 0 <= t["start"] < closes]
    if not inside:
        return None
    by_wall = sorted(inside, key=lambda r: r.wall_ns, reverse=True)
    print(json.dumps({"note": "slow_ticks", "ticks_in_window": len(inside), "ticks_after_warm_up": len(roots), "pairs_apart": apart,
                      "tick_ms_median": stats.median([r.wall_ns / 1e6 for r in inside]),
                      "flagged_slow": sum(r.slow for r in roots), "held_programs": sum(bool(r.programs) for r in roots),
                      "longest": [r.fields() for r in by_wall[:LONGEST]]}), flush=True)
    return by_wall[0]


def setup_seconds(observed: dict):
    """``{"lower_s", "load_s"}``: seconds the process spent tracing and lowering programs, and loading
    or compiling them, before the window opened (``program.lower`` / ``program.load`` spans through the
    compile caches, and jax's own duration events outside them, ``program.jax``), or ``None``. Prints
    the ``setup_programs`` note."""
    return _once("setup", observed, _setup_seconds)


def _setup_seconds(observed: dict):
    from chipbench import run

    log = the_log()
    if log is None:
        return None
    # the window opened setup_s after the harness started, on perf_counter: carried to the log's clock
    opened_ns = int((run._T_START + observed["end_to_end"]["setup_s"] + time.monotonic() - time.perf_counter()) * 1e9)
    spans = [s for s in log.spans() if s["t0_ns"] < opened_ns]
    programs = [s for s in spans if s["name"].startswith("program.")]
    if not programs:
        return None
    lower, load = [s for s in programs if not _is_load(s)], [s for s in programs if _is_load(s)]
    seconds = lambda some: sum(s["wall_ns"] for s in some) / 1e9
    read = {"lower_s": seconds(lower), "load_s": seconds(load)}
    by_source, by_program = {}, {}
    for s in load:
        by_source[s.get("source")] = by_source.get(s.get("source"), 0.0) + s["wall_ns"] / 1e9
    for s in programs:
        entry = by_program.setdefault(s.get("program"), {"lower_s": 0.0, "load_s": 0.0})
        entry["load_s" if _is_load(s) else "lower_s"] += s["wall_ns"] / 1e9
        if _is_load(s) and s.get("source") != "memory":
            entry["source"] = s.get("source")
    inits = [s for s in spans if s["name"] == "engine.init"]
    roots = [r for r in log.roots() if r.t0_ns < opened_ns]
    within = lambda s, t0, wall: t0 <= s["t0_ns"] < t0 + wall
    heaviest = sorted(by_program.items(), key=lambda kv: -(kv[1]["lower_s"] + kv[1]["load_s"]))
    print(json.dumps({
        "note": "setup_programs", "setup_s": observed["end_to_end"]["setup_s"], **read,
        "load_s_by_source": by_source, "programs": len(by_program), "spans": len(programs), "spans_in_ring": len(log.spans()),
        "engine_init_s": seconds(inits),
        "programs_in_engine_init_s": seconds([s for s in programs if any(within(s, i["t0_ns"], i["wall_ns"]) for i in inits)]),
        "roots_before_window": len(roots), "roots_s": sum(r.wall_ns for r in roots) / 1e9,
        "root_gaps_s": sum(r.gap_ns for r in roots) / 1e9,
        "programs_in_roots_s": seconds([s for s in programs if any(within(s, r.t0_ns, r.wall_ns) for r in roots)]),
        "first_span_s": (min(s["t0_ns"] for s in spans) - opened_ns) / 1e9 + observed["end_to_end"]["setup_s"],
        "by_program": {str(name): {k: round(v, 3) if isinstance(v, float) else v for k, v in entry.items()}
                       for name, entry in heaviest[:16]},
    }), flush=True)
    return read
