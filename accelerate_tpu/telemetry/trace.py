"""Fleet-wide request tracing: one trace per ``submit()``, spans per
serving phase, exports an operator can load.

The serving stack already *aggregates* well (``ServingMetrics`` windows,
``telemetry summarize``), but aggregates cannot answer the first
production question: *where did this request's p95 TTFT go* once it
crossed router -> prefill replica -> KV handoff -> decode replica ->
(maybe) failover. This module holds the per-request answer:

* a :class:`Tracer` mints one trace id per ``FleetRouter.submit()`` /
  ``ServingEngine.submit()`` and collects :class:`Span` segments —
  ``queue_wait``, ``admit``, each prefill chunk window, ``kv_handoff``,
  ``decode`` (per-tick, aggregated into windows), ``preempt`` /
  ``resume``, ``failover``, and ``drain`` migration;
* segments are **frontier-contiguous**: each new segment covers the gap
  since the trace's last covered timestamp, so the segment sum
  reconciles with the request's end-to-end latency by construction. A
  ``prefill`` span of a fused bucket program carries ``compute_ms``,
  dispatch to the first-token sync, which a predictor cross-check reads
  (:mod:`~accelerate_tpu.telemetry.critpath`); a chunk window has no
  sync and carries ``dispatch_ms``, the enqueue time, under that name;
* the trace id rides the request record through
  ``FleetRouter``/``ServingEngine``/``scheduling.py``, is serialized
  inside the ``HandoffCodec`` blob (schema v2; v1 blobs still decode),
  and rides ``export_inflight`` snapshots — traces survive disaggregated
  dispatch and failover, and the ROADMAP-item-1 socket transport
  inherits a context field instead of retrofitting one;
* exports: JSONL (eventlog-compatible ``trace.*`` span records + one
  ``trace_complete`` event, merged by ``telemetry summarize``) and
  Chrome trace-event JSON loadable in Perfetto (one ``tid`` per
  request).

Beside the per-request traces, :func:`phase` marks the program's own
phases (:data:`PHASES`: one engine tick and one train step, cut into
non-overlapping children, each with its counts) and the programs a
process traces, lowers, loads and compiles. What is always kept, with
no switch: every phase stamps the host's monotonic clock at entry and
exit into the process's :class:`PhaseLog` (:func:`phase_log`), two
preallocated rings in memory. A root (``engine.tick``, ``train.step``)
closes into one :class:`PhaseRecord`: wall, the thread's CPU time, the
gap since the thread's previous root, its counts and its ``.done``
marker's, and calls and time per child; a root that closes
:data:`SLOW_ROOT_OVER_NS` beyond the median of its name is marked
``slow``, and the engine reports it (``tick_slow``). Every other span
(``engine.submit``, ``engine.init``, ``program.lower``,
``program.load``, jax's own compiles as ``program.jax``) is kept whole in
the second ring. Two to three microseconds a phase. What only a profiler
session adds (``Accelerator.profile()``, ``jax.profiler.start_trace``):
the same spans as ``TraceAnnotation`` events in the profiler's trace, on
the device lines' clock, with the device lines beside them.
Every ``engine.tick`` / ``train.step`` carries ``mono_ns``, this
module's default clock at the span's entry (:func:`phase` stamps it for
a root), and its record's ``t0_ns`` is the same reading, so a record and
its traced event join exactly:
``mono_ns`` less the span's start on the profiler's clock (the trace's
``profile_start_time`` plus the event's ``start_ns``) is the offset that
places the whole log, a :class:`Tracer` span, a ``ServingMetrics``
timestamp or a ``submit_ts`` on that timeline.

jax is imported only inside :func:`phase` and :meth:`PhaseLog.listen` —
``accelerate-tpu trace ...`` runs on a box with nothing but the stdlib.
"""

from __future__ import annotations

import functools
import itertools
import json
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

#: segment classes a trace may carry, in rough request-lifecycle order.
SEGMENTS = (
    "queue_wait",
    "admit",
    "prefill",
    "kv_handoff",
    "decode",
    "preempt",
    "resume",
    "failover",
    "drain",
)

#: program phase -> the PERF.md layer it belongs to, in the order a tick
#: runs them: every admission's ``engine.admit``,
#: ``engine.prefill.dispatch`` and ``engine.prefill.paste``, then
#: ``engine.decode.dispatch``, and only then one ``engine.prefill.sync``
#: an admission whose first token was still on the device (the host
#: waits for nothing between the programs of one tick; a hand-off's
#: token and that of a request of one token are read inside the
#: admission, before the next), ``engine.decode.sync`` and the walk. One
#: ``engine.tick`` is tiled by its children (``engine.tick.done`` is a
#: zero-length marker that carries the tick's counts: admissions and, of
#: them, ``first_tokens_deferred``, those whose first token the host
#: read behind the decode dispatch; ``clears_deferred``, retired slots
#: whose clear the tick sent behind its first prefill or with its decode
#: dispatch, and ``leaves_signed``, the leaves the compile cache's
#: dispatch signed for the tick's calls; ``head_rows``, the rows of
#: logits the tick's prefill programs computed: 1 a bucket's prefill of
#: a model that heads the row it is asked for; tokens, the pool,
#: the routed experts' load (``experts_touched``, ``expert_pairs_max``,
#: ``expert_tile_visits`` and ``expert_pairs``, the token-expert pairs
#: the grouped products multiplied: the decoding slots' alone),
#: ``state_slots_idle``, the slot-steps of recurrent state the tick
#: spent on slots in which no request decodes: 0 where a state-space
#: layer's step kernel is told which slots decode and visits no other)
#: and ``attention_rows_skipped``, the rows of the tick's steps in slots
#: whose table row is at the sink, which the paged decode kernels walk
#: no page for);
#: the spans of one request share ``uid``. No name equals a span of the
#: benchmark's own. Inside the programs, ``jax.named_scope`` names ride
#: in the device operations' ``op_name``: ``moe.*``, ``mla.absorb``, and
#: for a state-space layer ``ssm.proj``, ``ssm.conv``, ``ssm.scan``
#: (a prefill window) and ``ssm.step`` (a decode step).
PHASES = {
    "engine.submit": "scheduler",
    "engine.tick": "engine host loop",
    "engine.schedule": "scheduler",
    "engine.admit": "scheduler",
    "engine.prefill.dispatch": "jitted programs",
    "engine.prefill.paste": "jitted programs",
    "engine.decode.dispatch": "jitted programs",
    "engine.prefill.sync": "jitted programs",
    "engine.decode.sync": "jitted programs",
    "engine.decode.walk": "engine host loop",
    "engine.expire": "engine host loop",
    "engine.tick.done": "engine host loop",
    "train.step": "jitted programs",
    "train.step.args": "jitted programs",
    "train.step.call": "jitted programs",
    "train.step.swap": "jitted programs",
}


#: phases only some engines enter, and only in some ticks -> layer: an admission that waits for room for another
#: prefill's row cache (a model whose row cache is large: ``ServingEngine._row_cache_cap``), and the tick in which
#: an aligned window (EVA attention) closed. Children of ``engine.tick`` like those of :data:`PHASES`, kept apart
#: because a trace of another model's engine holds none of them.
RARE_PHASES = {
    "engine.prefill.room.sync": "jitted programs",
    "engine.window.close": "scheduler",
}

#: phases that close into a :class:`PhaseRecord` of their own; a root's
#: zero-length ``<root>.done`` marker hands it the counts known at its end.
ROOT_PHASES = ("engine.tick", "train.step")

#: set-up's spans -> layer, kept whole in the log's second ring.
#: ``engine.init`` is ``ServingEngine.__init__``; ``program.lower`` (count
#: ``program``) is a trace-and-lower through ``ProgramCache``;
#: ``program.load`` (``program``, ``source`` ``memory`` | ``disk`` |
#: ``compiled``, the executable's bytes) is what followed it; ``program.jax``
#: (``program``, ``stage`` ``trace`` | ``lower`` | ``compile``, and for a
#: compile ``source`` ``disk`` | ``compiled``) is one of jax's own duration
#: events outside those two, less what it held nested (those under a
#: millisecond summed, as ``program`` ``_small_`` with ``calls``).
SETUP_PHASES = {
    "engine.init": "engine host loop",
    "program.lower": "compile caches",
    "program.load": "compile caches",
    "program.jax": "compile caches",
}
PROGRAM_PHASES = tuple(name for name in SETUP_PHASES if name.startswith("program."))

ROOT_CAPACITY = 4096  # a whole benchmark run's ticks (about 1,200), under a megabyte
SPAN_CAPACITY = 2048
#: a root is slow when it closes this far beyond the median of the last
#: ``SLOW_MEDIAN_OVER`` roots of its name: the stalls met so far last 1-12 s,
#: the longest sound tick of a serve cell is under half a second. A root
#: that held a program span is not judged: it lowered, loaded or compiled,
#: and ``program.*`` and ``serving_bucket_compile`` say so.
SLOW_ROOT_OVER_NS = 750_000_000
SLOW_MEDIAN_OVER = 256
SLOW_MEDIAN_AT_LEAST = 8

_JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_JAX_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_JAX_SMALL_NS = 1_000_000  # a trace or a lowering shorter than this is summed with its like ...
_JAX_SMALL_KEPT_NS = 20_000_000  # ... into one ``program.jax`` span (``program`` ``_small_``, ``calls``) of this much


class PhaseRecord:
    """One closed root of the phase log. Times are ``time.monotonic_ns``
    (``cpu_ns``: ``time.thread_time_ns``). ``wall_ns`` far above ``cpu_ns``
    says the thread was blocked or descheduled, near it that it computed
    (Python, a collection, a copy); ``gap_ns`` is the time since this
    thread's previous root ended, so a stall outside the root shows there.
    ``children`` maps a direct child's name to ``[calls, ns]``; ``counts``
    are the root's own and ``done`` its ``.done`` marker's (the dicts the
    call sites built, not copies); ``programs`` counts the program spans it
    held."""

    __slots__ = ("name", "seq", "thread", "t0_ns", "wall_ns", "cpu_ns", "gap_ns", "counts", "done", "children",
                 "programs", "slow")

    def __init__(self, name: str, thread: int, t0_ns: int, gap_ns: int, counts: dict):
        self.name, self.thread, self.t0_ns, self.gap_ns, self.counts = name, thread, t0_ns, gap_ns, counts
        self.seq = self.wall_ns = self.cpu_ns = self.programs = 0
        self.done: dict = {}
        self.children: dict = {}
        self.slow = False

    def child_ms(self, suffix: str = "") -> float:
        """Milliseconds under the children whose names end in ``suffix`` (``".sync"``: the device
        or the runtime held the thread)."""
        return sum(ns for name, (_, ns) in self.children.items() if name.endswith(suffix)) / 1e6

    def fields(self) -> dict:
        """The record flat, in milliseconds, as an ``EventLog`` record's fields."""
        children = {name: round(ns / 1e6, 3) for name, (_, ns) in self.children.items()}
        return {
            "phase": self.name, "thread": self.thread, "t0_ns": self.t0_ns,
            "wall_ms": round(self.wall_ns / 1e6, 3), "cpu_ms": round(self.cpu_ns / 1e6, 3),
            "gap_ms": round(self.gap_ns / 1e6, 3), "longest_child": max(children, key=children.get, default=None),
            "children_ms": children, "calls": {name: calls for name, (calls, _) in self.children.items()},
            "programs": self.programs,
            "counts": {k: v for k, v in self.counts.items() if k != "mono_ns"}, "done": dict(self.done),
        }


class PhaseLog:
    """Every phase of the process on the host's clock: a ring of closed
    roots and a ring of the spans outside them (or nested deeper than a
    root's children), as ``(name, thread, t0_ns, wall_ns, counts)``. Both
    are preallocated and bounded; there is no file and no thread. Written
    by :func:`phase` alone; read with :meth:`roots` and :meth:`spans`."""

    def __init__(self):
        self._roots: list = [None] * ROOT_CAPACITY
        self._spans: list = [None] * SPAN_CAPACITY
        self._root_seq = itertools.count()  # next() is atomic: two threads never share a slot
        self._span_seq = itertools.count()
        self._threads = threading.local()
        self._listening = False

    # -- written by phase()
    def _thread(self):
        t = self._threads
        try:
            return t.state
        except AttributeError:
            t.state = state = _ThreadState()
            return state

    def _close_root(self, rec: PhaseRecord) -> None:
        rec.seq = seq = next(self._root_seq)
        ring = self._roots
        if rec.wall_ns > SLOW_ROOT_OVER_NS and not rec.programs:
            before = (ring[i % len(ring)] for i in range(max(seq - min(SLOW_MEDIAN_OVER, len(ring)), 0), seq))
            recent = sorted(r.wall_ns for r in before if r is not None and r.name == rec.name)
            rec.slow = len(recent) >= SLOW_MEDIAN_AT_LEAST and rec.wall_ns - recent[len(recent) // 2] > SLOW_ROOT_OVER_NS
        ring[seq % len(ring)] = rec

    def _close_span(self, name: str, thread: int, t0_ns: int, wall_ns: int, counts: dict) -> None:
        seq = next(self._span_seq)
        self._spans[seq % len(self._spans)] = (seq, name, thread, t0_ns, wall_ns, counts)

    # -- jax's own compiles
    def listen(self) -> None:
        """Hear jax's duration events from now on (once a process;
        ``import accelerate_tpu`` calls it): what jax traces, lowers and
        compiles outside a ``program.lower`` / ``program.load`` span
        becomes a ``program.jax`` span, so that every second a process
        spends on its programs is in the log."""
        if not self._listening:
            import jax

            self._listening = True
            jax.monitoring.register_scalar_listener(self._on_jax_start)
            jax.monitoring.register_event_duration_secs_listener(self._on_jax_duration)

    def _on_jax_start(self, event: str, _value, **_) -> None:
        """jax records a scalar under the event's name as a timed stage begins: one more is open."""
        if event in _JAX_STAGES:
            self._thread().jax_open.append(0)

    def _on_jax_duration(self, event: str, seconds: float, fun_name: str = "", **_) -> None:
        state = self._thread()
        if event == _JAX_CACHE_READ:
            state.cache_read = True
            return
        stage = _JAX_STAGES.get(event)
        if stage is None:
            return
        wall = int(seconds * 1e9)
        own = wall - (state.jax_open.pop() if state.jax_open else 0)  # less the stages that ran nested in this one
        if state.jax_open:
            state.jax_open[-1] += wall
        counts = {"program": fun_name, "stage": stage}
        if stage == "compile":
            counts["source"] = state.compile_source = "disk" if state.cache_read else "compiled"
            state.cache_read = False
        if state.in_program:
            return  # inside program.lower / program.load: that span holds these seconds
        t0 = time.monotonic_ns() - wall
        if state.root is not None:
            state.root.programs += 1
        if stage != "compile" and own < _JAX_SMALL_NS:
            # a thousand nested jits of a model trace in microseconds each: kept as one span a few
            few = state.jax_small
            few[0], few[1], few[2] = few[0] or t0, few[1] + max(own, 0), few[2] + 1
            if few[1] < _JAX_SMALL_KEPT_NS:
                return
            t0, own, counts = few[0], few[1], {"program": "_small_", "stage": "trace", "calls": few[2]}
            state.jax_small = [0, 0, 0]
        self._close_span("program.jax", state.ident, t0, max(own, 0), counts)

    def compile_source(self) -> str:
        """Where this thread's last backend compile came from: ``disk`` (jax's persistent
        cache had the executable) or ``compiled``."""
        return self._thread().compile_source

    # -- read surface
    def roots(self, name: Optional[str] = None, n: Optional[int] = None) -> list:
        """The newest ``n`` closed roots (all that the ring holds when ``None``), oldest
        first; of ``name`` alone when given."""
        found = sorted((r for r in list(self._roots) if r is not None and name in (None, r.name)), key=lambda r: r.seq)
        return found if n is None else found[len(found) - min(n, len(found)):]

    def spans(self, prefix: str = "") -> list:
        """The second ring's spans whose names start with ``prefix``, oldest first, as dicts:
        ``name``, ``thread``, ``t0_ns``, ``wall_ns`` and the span's counts."""
        found = sorted((s for s in list(self._spans) if s is not None and s[1].startswith(prefix)), key=lambda s: s[0])
        return [{**counts, "name": name, "thread": thread, "t0_ns": t0, "wall_ns": wall}
                for _, name, thread, t0, wall, counts in found]


class _ThreadState:
    """What :func:`phase` keeps per thread: the open root and the depth it opened at, how deep
    the open phases nest, when the last root ended, how many program spans are open, and of jax's
    timed stages the open ones (each with the nanoseconds of those that ran nested in it)."""

    __slots__ = ("ident", "root", "root_depth", "depth", "root_end_ns", "in_program", "cache_read", "compile_source",
                 "jax_open", "jax_small")

    def __init__(self):
        self.ident = threading.get_ident()
        self.root: Optional[PhaseRecord] = None
        self.root_depth = self.depth = self.root_end_ns = self.in_program = 0
        self.cache_read = False
        self.compile_source = "compiled"
        self.jax_open: list = []
        self.jax_small = [0, 0, 0]  # t0 of the first, ns, calls


_LOG = PhaseLog()
_annotation = None  # jax.profiler.TraceAnnotation, once phase() has imported jax


def phase_log() -> PhaseLog:
    """The process's one :class:`PhaseLog`."""
    return _LOG


class _Phase:
    """What :func:`phase` returns: the profiler's annotation and the log's stamps around one block.
    After the block a root's ``record`` is its :class:`PhaseRecord`."""

    __slots__ = ("name", "counts", "record", "_annotation", "_state", "_t0", "_cpu0")

    def __init__(self, name: str, counts: dict, annotation, cpu0: Optional[int] = None):
        self.name, self.counts, self._annotation, self._cpu0, self.record = name, counts, annotation, cpu0, None

    def __enter__(self):
        self._state = state = _LOG._thread()
        self._annotation.__enter__()
        state.depth += 1
        if self._cpu0 is not None and state.root is None:  # a root (phase() read its CPU clock), and none is open
            # the reading the span carries into the profiler's trace is the record's start
            t0 = self.counts["mono_ns"]
            gap = t0 - state.root_end_ns if state.root_end_ns else 0
            state.root = self.record = PhaseRecord(self.name, state.ident, t0, gap, self.counts)
            state.root_depth = state.depth
        elif self.name in PROGRAM_PHASES:
            state.in_program += 1
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        now = time.monotonic_ns()
        state, rec, name = self._state, self.record, self.name
        state.depth -= 1
        if rec is not None:
            self._annotation.__exit__(*exc)  # before the CPU clock is read: see phase()
            rec.cpu_ns = time.thread_time_ns() - self._cpu0
            rec.wall_ns = now - rec.t0_ns
            state.root, state.root_end_ns = None, now
            _LOG._close_root(rec)
            return None
        root = state.root
        if name in PROGRAM_PHASES:
            state.in_program -= 1
            if root is not None:
                root.programs += 1
        if root is None or state.depth != state.root_depth:
            _LOG._close_span(name, state.ident, self._t0, now - self._t0, self.counts)
        elif name.endswith(".done") and name[:-5] == root.name:
            root.done = self.counts
        else:  # a root's direct child: they tile it
            child = root.children.get(name)
            if child is None:
                root.children[name] = [1, now - self._t0]
            else:
                child[0] += 1
                child[1] += now - self._t0
        return self._annotation.__exit__(*exc)


def phase(name: str, **counts):
    """A span named ``name`` with ``counts``: a context manager that stamps
    the host clock into the :class:`PhaseLog` always, and is a
    ``TraceAnnotation`` with ``counts`` as its stats in the profiler's
    trace while a session runs. ``with phase(root) as p`` leaves the
    root's :class:`PhaseRecord` in ``p.record``. A root
    (:data:`ROOT_PHASES`) is given its ``mono_ns`` here, one reading for
    the traced span's count and the record's ``t0_ns``. Reading the
    thread's CPU clock is a system call (microseconds on the chip's host,
    now and then milliseconds on a crowded one), so a root's two readings
    are taken outside its span, before that stamp and after the span's
    exit: they fall into the gap between roots and not into the time its
    children tile."""
    global _annotation
    if _annotation is None:
        import jax

        _annotation = jax.profiler.TraceAnnotation
    cpu0 = None
    if name in ROOT_PHASES:
        cpu0 = time.thread_time_ns()
        counts["mono_ns"] = time.monotonic_ns()
    return _Phase(name, counts, _annotation(name, **counts), cpu0)


def phased(name: str):
    """Decorator: every call of the function is one :func:`phase` named ``name``."""

    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with phase(name):
                return fn(*args, **kwargs)

        return call

    return wrap


#: eventlog record-name prefix for exported span segments.
TRACE_EVENT_PREFIX = "trace."

#: terminal trace statuses (``open`` is the only non-terminal one).
STATUSES = ("open", "ok", "shed", "cancelled", "lost", "failed")


@dataclass
class Span:
    """One contiguous segment of a request's wall-clock timeline."""

    name: str
    t0: float
    t1: float
    meta: dict = field(default_factory=dict)

    @property
    def dur_ms(self) -> float:
        return max(0.0, (self.t1 - self.t0) * 1000.0)


@dataclass
class Trace:
    """One request's timeline: id, status, and its segment spans."""

    id: int
    t0: float
    meta: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)
    t1: Optional[float] = None
    status: str = "open"
    #: end of the last covered segment — the next span starts here.
    frontier: float = 0.0
    #: name of the mergeable open window (decode tick aggregation).
    window: Optional[str] = None

    def to_dict(self) -> dict:
        dur = ((self.t1 if self.t1 is not None else self.frontier) - self.t0) * 1000.0
        return {
            "id": self.id,
            "t0": self.t0,
            "status": self.status,
            "dur_ms": round(max(0.0, dur), 3),
            "meta": dict(self.meta),
            "spans": [
                {
                    "name": s.name,
                    "t0_ms": round((s.t0 - self.t0) * 1000.0, 3),
                    "dur_ms": round(s.dur_ms, 3),
                    **s.meta,
                }
                for s in self.spans
            ],
        }


@dataclass
class TraceConfig:
    """Knobs for ``FleetRouter(trace=...)`` / ``TelemetryKwargs``."""

    enabled: bool = True
    #: completed traces retained in memory (served by ``/traces``).
    max_traces: int = 4096
    #: per-replica flight recorder (see :mod:`~.flightrec`).
    flight_recorder: bool = True
    flight_capacity: int = 256
    #: directory for crash dumps; ``None`` keeps dumps in memory only.
    flight_dump_dir: Optional[str] = None
    #: cross-check each segment against its predictor (see :mod:`~.critpath`).
    drift_check: bool = True
    drift_thresholds: Optional[dict] = None

    def __post_init__(self):
        if self.max_traces < 1:
            raise ValueError(f"max_traces must be >= 1, got {self.max_traces}")
        if self.flight_capacity < 8:
            raise ValueError(f"flight_capacity must be >= 8, got {self.flight_capacity}")


class Tracer:
    """Thread-safe collector for request traces.

    Instrumentation sites call :meth:`seg` (one distinct span per call —
    prefill chunk windows, handoff, failover) or :meth:`window`
    (consecutive same-name calls merge — per-tick decode aggregation).
    Both are frontier-contiguous; mutation is O(1) under one ``RLock``
    and nothing blocking ever runs under it (export/formatting snapshot
    first, format outside — the TPU903 discipline).
    """

    def __init__(
        self,
        *,
        max_traces: int = 4096,
        clock: Callable[[], float] = time.monotonic,
        log=None,
        on_finish: Optional[Callable[[dict], None]] = None,
    ):
        self._lock = threading.RLock()
        self._clock = clock
        self._ids = itertools.count(1)
        self._open: dict[int, Trace] = {}
        self._done: list[dict] = []
        self._max_traces = max(1, int(max_traces))
        self.log = log
        self.on_finish = on_finish
        self.started = 0
        self.finished = 0

    # ------------------------------------------------------------------ #
    # recording surface (called from serving hot paths; cheap, guarded)
    # ------------------------------------------------------------------ #

    def start(self, **meta) -> int:
        """Mint a trace; the returned id is the context that rides the
        request record (and the handoff blob / failover snapshot)."""
        now = self._clock()
        with self._lock:
            tid = next(self._ids)
            self._open[tid] = Trace(id=tid, t0=now, meta=dict(meta), frontier=now)
            self.started += 1
        return tid

    def attach(self, trace_id: Optional[int], **meta) -> None:
        """Merge ``meta`` into an open trace (fuid, uid, ttft...)."""
        if trace_id is None:
            return
        with self._lock:
            tr = self._open.get(trace_id)
            if tr is not None:
                tr.meta.update(meta)

    def seg(self, trace_id: Optional[int], name: str, *, end: Optional[float] = None, **meta) -> None:
        """Close the segment ``[frontier, end]`` as one distinct span."""
        if trace_id is None:
            return
        end = self._clock() if end is None else end
        with self._lock:
            tr = self._open.get(trace_id)
            if tr is None:
                return
            tr.spans.append(Span(name, tr.frontier, max(tr.frontier, end), meta))
            tr.frontier = max(tr.frontier, end)
            tr.window = None

    def window(
        self, trace_id: Optional[int], name: str, *, end: Optional[float] = None, tokens: int = 0, **meta
    ) -> None:
        """Like :meth:`seg`, but consecutive same-name windows merge into
        one span (``tokens`` accumulates) — per-tick decode aggregation."""
        if trace_id is None:
            return
        end = self._clock() if end is None else end
        with self._lock:
            tr = self._open.get(trace_id)
            if tr is None:
                return
            end = max(tr.frontier, end)
            if tr.window == name and tr.spans and tr.spans[-1].name == name:
                span = tr.spans[-1]
                span.t1 = end
                span.meta["tokens"] = span.meta.get("tokens", 0) + int(tokens)
                span.meta.update(meta)
            else:
                m = dict(meta)
                m["tokens"] = int(tokens)
                tr.spans.append(Span(name, tr.frontier, end, m))
                tr.window = name
            tr.frontier = end

    def finish(self, trace_id: Optional[int], status: str = "ok", **meta) -> Optional[dict]:
        """Seal the trace, move it to the completed ring, export its span
        records to the attached eventlog, and run the ``on_finish`` hook
        (the critical-path drift monitor). Returns the trace dict."""
        if trace_id is None:
            return None
        now = self._clock()
        with self._lock:
            tr = self._open.pop(trace_id, None)
            if tr is None:
                return None
            tr.t1 = max(tr.frontier, now)
            tr.status = status
            tr.meta.update(meta)
            self.finished += 1
            out = tr.to_dict()
            self._done.append(out)
            if len(self._done) > self._max_traces:
                del self._done[: len(self._done) - self._max_traces]
        # formatting + hooks OUTSIDE the lock (log may flush to disk)
        log = self.log
        if log is not None:
            _emit_trace(log, out)
        hook = self.on_finish
        if hook is not None:
            hook(out)
        return out

    def discard(self, trace_id: Optional[int]) -> None:
        """Drop an open trace without exporting (duplicate-submit paths)."""
        if trace_id is None:
            return
        with self._lock:
            self._open.pop(trace_id, None)

    # ------------------------------------------------------------------ #
    # read surface
    # ------------------------------------------------------------------ #

    def completed(self, n: Optional[int] = None) -> list[dict]:
        """Most recent ``n`` completed traces (all when ``n`` is None)."""
        with self._lock:
            out = list(self._done)
        return out if n is None else out[-int(n):]

    def open_spans(self) -> list[dict]:
        """Snapshot of in-flight traces — the flight recorder dumps this
        next to the last-N event tail on a crash."""
        now = self._clock()
        with self._lock:
            snap = [
                {
                    "trace": tr.id,
                    "age_ms": round((now - tr.t0) * 1000.0, 3),
                    "segment": tr.spans[-1].name if tr.spans else None,
                    "spans": len(tr.spans),
                    "meta": dict(tr.meta),
                }
                for tr in self._open.values()
            ]
        return snap

    # ------------------------------------------------------------------ #
    # exports
    # ------------------------------------------------------------------ #

    def export_jsonl(self, path: str) -> int:
        """Write completed traces as eventlog-compatible JSONL (the same
        records the live log receives); returns the trace count."""
        from .eventlog import EventLog

        traces = self.completed()
        log = EventLog(path, rank=0, main_process_only=False, buffer_lines=1024)
        try:
            for tr in traces:
                _emit_trace(log, tr)
        finally:
            log.close()
        return len(traces)

    def export_chrome(self, path: Optional[str] = None) -> dict:
        """Chrome trace-event JSON (Perfetto-loadable); writes ``path``
        when given and returns the document."""
        doc = chrome_trace(self.completed())
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


def _emit_trace(log, trace: dict) -> None:
    """Emit one completed trace into an :class:`EventLog`: a ``trace.*``
    span record per segment, then one ``trace_complete`` event carrying
    the per-class totals."""
    totals: dict[str, float] = {}
    for sp in trace["spans"]:
        fields = {k: v for k, v in sp.items() if k != "name"}
        log.emit("span", TRACE_EVENT_PREFIX + sp["name"], trace=trace["id"], **fields)
        totals[sp["name"]] = round(totals.get(sp["name"], 0.0) + sp["dur_ms"], 3)
    log.event(
        "trace_complete",
        trace=trace["id"],
        status=trace["status"],
        dur_ms=trace["dur_ms"],
        segments=totals,
        **{k: v for k, v in trace["meta"].items() if isinstance(v, (int, float, str, bool))},
    )


def traces_from_events(events: list[dict]) -> list[dict]:
    """Reconstruct trace dicts from eventlog records (the inverse of
    :func:`_emit_trace`) — how the jax-free ``accelerate-tpu trace``
    CLI and the ``telemetry summarize`` traces section read a JSONL."""
    by_id: dict[int, dict] = {}
    for rec in events:
        name = rec.get("name", "")
        tid = rec.get("trace")
        if tid is None:
            continue
        if rec.get("kind") == "span" and name.startswith(TRACE_EVENT_PREFIX):
            tr = by_id.setdefault(tid, {"id": tid, "status": "open", "dur_ms": 0.0, "meta": {}, "spans": []})
            span = {k: v for k, v in rec.items() if k not in ("v", "seq", "ts", "rank", "kind", "name", "trace")}
            span["name"] = name[len(TRACE_EVENT_PREFIX):]
            tr["spans"].append(span)
        elif rec.get("kind") == "event" and name == "trace_complete":
            tr = by_id.setdefault(tid, {"id": tid, "status": "open", "dur_ms": 0.0, "meta": {}, "spans": []})
            tr["status"] = rec.get("status", "ok")
            tr["dur_ms"] = rec.get("dur_ms", tr["dur_ms"])
            # anchor an absolute start so chrome export can place the trace
            tr["t0"] = rec.get("ts", 0.0) - tr["dur_ms"] / 1000.0
            tr["meta"] = {
                k: v
                for k, v in rec.items()
                if k not in ("v", "seq", "ts", "rank", "kind", "name", "trace", "status", "dur_ms", "segments", "severity")
            }
    return list(by_id.values())


def chrome_trace(traces: list[dict]) -> dict:
    """Chrome trace-event document: ``ph:"X"`` complete events, one
    ``tid`` per request, span meta in ``args`` — drop the file on
    https://ui.perfetto.dev and read the decomposition off the timeline."""
    base = min((tr.get("t0", 0.0) for tr in traces), default=0.0)
    out: list[dict] = [
        {"ph": "M", "name": "process_name", "pid": 0, "tid": 0, "args": {"name": "accelerate_tpu serving"}}
    ]
    for tr in traces:
        label = tr.get("meta", {}).get("fuid", tr.get("meta", {}).get("uid", tr["id"]))
        out.append(
            {"ph": "M", "name": "thread_name", "pid": 0, "tid": tr["id"], "args": {"name": f"request {label}"}}
        )
        t0 = tr.get("t0", 0.0)
        for sp in tr["spans"]:
            args = {k: v for k, v in sp.items() if k not in ("name", "t0_ms", "dur_ms")}
            args["status"] = tr.get("status", "open")
            out.append(
                {
                    "name": sp["name"],
                    "cat": "request",
                    "ph": "X",
                    "ts": round((t0 - base) * 1e6 + sp.get("t0_ms", 0.0) * 1e3, 3),
                    "dur": round(sp.get("dur_ms", 0.0) * 1e3, 3),
                    "pid": 0,
                    "tid": tr["id"],
                    "args": args,
                }
            )
    return {"displayTimeUnit": "ms", "traceEvents": out}
