"""Serving-side counters: TTFT, tokens/sec, queue depth, KV utilisation,
preemptions — plus a Prometheus text-exposition dump.

The :class:`~accelerate_tpu.serving.ServingEngine` drives these hooks from
the places the events actually happen (submit, admit/first-token, decode
walk, retire, cancel, pool-blocked admission), so the numbers are exact
counts, not sampled approximations. Latency distributions (TTFT,
per-request e2e) are kept in bounded deques — a long-running server's
metrics memory is O(window), not O(requests).

``prometheus_text()`` renders the standard text exposition format
(``# HELP`` / ``# TYPE`` + samples) so a scrape endpoint is one
``web.Response(text=engine.metrics.prometheus_text())`` away; quantiles
are emitted as ``summary`` quantile samples over the retained window.
"""

from __future__ import annotations

import collections
import time
from typing import Optional

from .eventlog import EventLog

_PREFIX = "accelerate_tpu_serving"


def _pct(values, q: float) -> Optional[float]:
    vals = sorted(values)
    if not vals:
        return None
    k = max(0, min(len(vals) - 1, int(round(q / 100.0 * (len(vals) - 1)))))
    return vals[k]


def _render_prom(rows) -> str:
    """Render ``(name, mtype, help, suffix, label_str, value)`` rows as
    text exposition: one HELP/TYPE block per metric (first-seen order),
    then every sample of that metric — the grouping a multi-replica
    scrape needs."""
    by_name: dict = {}
    order = []
    for name, mtype, help_text, suffix, labels, value in rows:
        if name not in by_name:
            by_name[name] = (mtype, help_text, [])
            order.append(name)
        by_name[name][2].append((suffix, labels, value))
    lines = []
    for name in order:
        mtype, help_text, samples = by_name[name]
        lines.append(f"# HELP {_PREFIX}_{name} {help_text}")
        lines.append(f"# TYPE {_PREFIX}_{name} {mtype}")
        for suffix, labels, value in samples:
            if value is None:
                continue
            lines.append(f"{_PREFIX}_{name}{suffix}{labels} {value:g}")
    return "\n".join(lines) + "\n"


def fleet_prometheus_text(metrics) -> str:
    """One scrape body for N replicas' :class:`ServingMetrics`: a single
    HELP/TYPE block per metric with one ``replica``-labeled sample per
    replica — what a fleet exposes on its shared ``/metrics`` endpoint
    (aggregate with ``sum by`` in the scraper, or serve
    ``ServingMetrics.merge(...).prometheus_text()`` for a pre-merged
    view)."""
    rows = []
    for i, m in enumerate(metrics):
        if m.replica is None:
            m = _with_replica(m, f"r{i}")
        rows.extend(m._prom_samples())
    return _render_prom(rows)


def _with_replica(metrics: "ServingMetrics", name: str) -> "ServingMetrics":
    """Label an unlabeled instance for one render without mutating it."""
    import copy

    clone = copy.copy(metrics)
    clone.replica = name
    return clone


class ServingMetrics:
    """Counter/latency surface for one :class:`ServingEngine`.

    ``log`` (optional): mirror every snapshot to a telemetry
    :class:`EventLog` as ``serving.*`` counters, so a serving run and a
    training run summarize through the same CLI.

    ``replica`` (optional): a fleet replica name; when set, every
    Prometheus sample carries a ``replica="..."`` label so N replicas'
    engines scrape as one fleet view (:func:`fleet_prometheus_text`),
    and :meth:`merge` aggregates them into one fleet-level instance.
    """

    def __init__(
        self,
        engine=None,
        *,
        log: Optional[EventLog] = None,
        window: int = 1024,
        clock=time.monotonic,
        replica: Optional[str] = None,
    ):
        self._engine = engine
        self.log = log if log is not None else EventLog(None)
        self._clock = clock
        self.replica = replica
        # set by merge(): the source instances a fleet view aggregates
        # its live gauges (queue depth, tokens/sec) over
        self._sources: Optional[list] = None
        # monotonically increasing counters
        self.requests_submitted = 0
        self.requests_completed = 0
        self.requests_cancelled = 0
        self.tokens_generated = 0
        self.prefills = 0
        self.preemptions = 0  # admission passes blocked on pool exhaustion
        # scheduler decisions (accelerate_tpu.scheduling)
        self.requests_shed = 0  # SLO load shedding (submit reject + queue-wait shed)
        self.requests_deprioritized = 0
        self.decode_preemptions = 0  # decoding slots evicted + requeued
        self.resumes = 0  # preempted requests resumed by recompute
        # routed experts (models with a RoutedFFN; 0 otherwise), from the
        # decode ticks' ``expert_load``: distinct experts that got a token,
        # summed over expert layers and decode steps, the most tokens any
        # one expert got in a step, and the (expert, row tile) visits of one
        # grouped product, summed likewise: over experts_touched, how many
        # row tiles an expert's tokens lie in; and the token-expert pairs the
        # grouped products multiplied, summed likewise: the decoding slots'
        # alone (slots x experts a token x expert layers x steps less this
        # is what the tick's row mask kept from the experts)
        self.experts_touched = 0
        self.expert_pairs_max = 0
        self.expert_tile_visits = 0
        self.expert_pairs = 0
        # recurrent state (models with state-space layers; 0 otherwise): the
        # bytes of ssm_state + conv_state one slot holds over all layers, and
        # the slot-steps of it the decode ticks spent on slots in which no
        # request decodes (a convolution's carried inputs and the plain
        # state step move in every slot; the step kernel of a state-space
        # layer is told which slots decode and visits no other: 0 then)
        self.state_bytes_per_slot = 0
        self.state_slots_idle = 0
        # paged attention: the rows of the decode ticks' steps that stood in slots whose table row was at
        # the sink (nobody decodes there), which the paged decode kernels are handed "no keys" for and
        # walk no page of (the XLA gather off the chip still reads them); 0 in the dense layout
        self.attention_rows_skipped = 0
        # aligned windows (EVA attention; 0 otherwise), from the decode
        # ticks' kept steps: the rows of keys the steps attended to (a
        # summary for every chunk of the closed windows, the open window's
        # rows) beside the rows of their contexts, the chunks pooled and
        # the windows closed; and the pool's pages held by kind when the
        # last tick ended (a closed window leaves a sixteenth of its pages)
        self.attn_rows_read = 0
        self.context_rows = 0
        self.chunks_pooled = 0
        self.windows_closed = 0
        self.exact_pages_held = 0
        self.summary_pages_held = 0
        # layers of two kinds (window and full attention, a pool and a table a kind): rows inside the band for the
        # kept steps (``min(t + 1, window)``, beside ``context_rows``), and the pages held by kind at the last tick's end
        self.window_rows_read = 0
        self.full_pages_held = 0
        self.window_pages_held = 0
        # admissions whose first token the host read behind the decode
        # dispatch of their tick (``first_tokens_deferred`` of
        # ``engine.tick.done``); ``prefills`` less it took the road with a
        # wait inside the admission: a hand-off, a request of one token
        self.first_tokens_deferred = 0
        # the host's work around the programs (``engine.tick.done``):
        # retired slots whose clear went out behind a program of a later
        # tick and not inside the walk that found them, and leaves whose
        # signature the compile cache's dispatch computed for the ticks'
        # calls (the parameters' are kept from call to call)
        self.clears_deferred = 0
        self.leaves_signed = 0
        # rows of logits the prefill programs computed (``head_rows`` of
        # ``engine.tick.done``): one a bucket's prefill where the model
        # runs its output head on the row it is asked for, the bucket's
        # rows where it cannot be asked, a chunk window's rows
        self.head_rows = 0
        # the host loop itself, from the phase log's tick records
        # (telemetry.trace.PhaseLog): ticks made, the longest one's wall
        # time, and how many closed far beyond the median of the ticks
        # before them (each is one ``tick_slow`` event)
        self.ticks = 0
        self.tick_ms_max = 0.0
        self.slow_ticks = 0
        # cross-request prefix reuse (serving_fleet.RadixPrefixCache):
        # a hit means the request skipped re-prefilling that many shared
        # preamble tokens — the fleet's dominant p95-TTFT lever
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_evictions = 0
        self.prefix_registrations = 0
        self.prefix_tokens_reused = 0
        # fleet fault tolerance (serving_fleet): failover flow counters
        # and this replica's health level (0 healthy, 1 degraded,
        # 2 quarantined, 3 dead — a fleet view exposes the worst source)
        self.failovers_in = 0  # migrated requests imported by this engine
        self.failovers_out = 0  # in-flight requests migrated off this engine
        self.failovers_lost = 0  # in-flight requests unrecoverable at failover
        self.replica_errors = 0  # engine exceptions classified by the router
        self.replica_timeouts = 0  # tick wall-time SLO violations
        self._replica_state = 0
        # latency windows
        self.ttft_ms: collections.deque = collections.deque(maxlen=window)
        self.e2e_ms: collections.deque = collections.deque(maxlen=window)
        # inter-token latency: one sample per (request, tick) = elapsed
        # since the request's previous token delivery / tokens delivered
        # this tick — the per-token stream latency a client observes
        self.itl_ms: collections.deque = collections.deque(maxlen=window)
        # submit -> admission wait (the SLO the shed threshold guards)
        self.queue_wait_ms: collections.deque = collections.deque(maxlen=window)
        # per-inflight-request timing
        self._submit_ts: dict[int, float] = {}
        self._last_tok_ts: dict[int, float] = {}
        # tokens/sec over a sliding window of (ts, cumulative tokens)
        self._token_marks: collections.deque = collections.deque(maxlen=window)

    # ------------------------------------------------------------------ #
    # engine hooks
    # ------------------------------------------------------------------ #

    def on_submit(self, uid: int):
        self.requests_submitted += 1
        self._submit_ts[uid] = self._clock()

    def on_first_token(self, uid: int):
        """Called when a request's first generated token lands (the tail
        of its prefill) — the TTFT sample."""
        self.prefills += 1
        now = self._clock()
        self._last_tok_ts[uid] = now
        t0 = self._submit_ts.get(uid)
        if t0 is not None:
            self.ttft_ms.append((now - t0) * 1000.0)

    def on_admit(self, uid: int, priority: int = 0, queue_wait_ms: Optional[float] = None):
        """Queue-wait sample at the moment a request claims a slot."""
        if queue_wait_ms is not None:
            self.queue_wait_ms.append(queue_wait_ms)

    def on_tokens(self, n: int = 1):
        self.tokens_generated += n
        self._token_marks.append((self._clock(), self.tokens_generated))

    def on_tick(self, wall_ms: float, slow: bool = False):
        self.ticks += 1
        self.tick_ms_max = max(self.tick_ms_max, wall_ms)
        self.slow_ticks += bool(slow)

    def on_state_step(self, slots_idle: int):
        self.state_slots_idle += slots_idle

    def on_attention_rows_skipped(self, rows: int):
        self.attention_rows_skipped += rows

    def on_window_attention(self, rows_read: int, context_rows: int, chunks_pooled: int, windows_closed: int):
        self.attn_rows_read += rows_read
        self.context_rows += context_rows
        self.chunks_pooled += chunks_pooled
        self.windows_closed += windows_closed

    def on_pages_held(self, exact: int, summary: int):
        self.exact_pages_held, self.summary_pages_held = exact, summary

    def on_window_rows(self, rows: int):
        self.window_rows_read += rows

    def on_pages_by_kind(self, full: int, window: int):
        self.full_pages_held, self.window_pages_held = full, window

    def on_first_tokens_deferred(self, n: int):
        self.first_tokens_deferred += n

    def on_host_work(self, clears_deferred: int, leaves_signed: int, head_rows: int = 0):
        self.clears_deferred += clears_deferred
        self.leaves_signed += leaves_signed
        self.head_rows += head_rows

    def on_expert_load(self, touched: int, pairs_max: int, tile_visits: int, pairs: int):
        self.experts_touched += touched
        self.expert_pairs_max = max(self.expert_pairs_max, pairs_max)
        self.expert_tile_visits += tile_visits
        self.expert_pairs += pairs

    def on_tick_tokens(self, uid: int, n: int):
        """ITL sample: ``n`` tokens delivered to ``uid`` this tick."""
        now = self._clock()
        t0 = self._last_tok_ts.get(uid)
        if t0 is not None and n > 0:
            self.itl_ms.append((now - t0) * 1000.0 / n)
        self._last_tok_ts[uid] = now

    def on_complete(self, uid: int):
        self.requests_completed += 1
        self._last_tok_ts.pop(uid, None)
        t0 = self._submit_ts.pop(uid, None)
        if t0 is not None:
            self.e2e_ms.append((self._clock() - t0) * 1000.0)

    def on_cancel(self, uid: int):
        self.requests_cancelled += 1
        self._submit_ts.pop(uid, None)
        self._last_tok_ts.pop(uid, None)

    def on_pool_blocked(self):
        self.preemptions += 1

    def on_shed(self, uid: Optional[int]):
        """SLO load shed — submit-time reject (uid None) or a queued
        request dropped after blowing the wait threshold."""
        self.requests_shed += 1
        if uid is not None:
            self._submit_ts.pop(uid, None)

    def on_deprioritize(self, uid: Optional[int]):
        self.requests_deprioritized += 1

    def on_preempt_decode(self, uid: int):
        """A decoding slot was evicted and requeued; the preemption gap
        must not pollute the ITL window, so the chain restarts at the
        first post-resume delivery."""
        self.decode_preemptions += 1
        self._last_tok_ts.pop(uid, None)

    def on_resume(self, uid: int):
        self.resumes += 1
        self._last_tok_ts[uid] = self._clock()

    def on_prefix_hit(self, tokens_reused: int = 0):
        """A request matched a registered shared preamble and skipped
        re-prefilling ``tokens_reused`` tokens."""
        self.prefix_hits += 1
        self.prefix_tokens_reused += int(tokens_reused)

    def on_prefix_miss(self):
        self.prefix_misses += 1

    def on_prefix_evict(self):
        self.prefix_evictions += 1

    def on_prefix_register(self):
        self.prefix_registrations += 1

    def on_failover_in(self):
        """A migrated in-flight request was imported by this engine."""
        self.failovers_in += 1

    def on_failover_out(self):
        """An in-flight request was exported off this engine's replica."""
        self.failovers_out += 1

    def on_failover_lost(self):
        """An in-flight request could not be recovered at failover."""
        self.failovers_lost += 1

    def on_replica_error(self):
        self.replica_errors += 1

    def on_replica_timeout(self):
        self.replica_timeouts += 1

    def on_replica_state(self, level: int):
        """Router health transition: 0 healthy, 1 degraded, 2 quarantined,
        3 dead."""
        self._replica_state = int(level)

    # ------------------------------------------------------------------ #
    # read surface
    # ------------------------------------------------------------------ #

    @property
    def queue_depth(self) -> int:
        if self._sources:
            return sum(m.queue_depth for m in self._sources)
        return len(self._engine.queue) if self._engine is not None else 0

    @property
    def active_slots(self) -> int:
        if self._sources:
            return sum(m.active_slots for m in self._sources)
        return self._engine.active_count if self._engine is not None else 0

    @property
    def replica_state(self) -> int:
        """Health level of this replica (0 healthy, 1 degraded,
        2 quarantined, 3 dead); a fleet view reports its WORST source —
        the alerting-relevant aggregate."""
        if self._sources:
            return max(m.replica_state for m in self._sources)
        return self._replica_state

    @property
    def kv_block_utilization(self) -> Optional[float]:
        """Fraction of the paged pool in use (None in dense mode; a
        fleet view averages its paged replicas)."""
        if self._sources:
            utils = [m.kv_block_utilization for m in self._sources]
            utils = [u for u in utils if u is not None]
            return sum(utils) / len(utils) if utils else None
        if self._engine is None or not getattr(self._engine, "paged", False):
            return None
        total = self._engine._pcfg.num_blocks - 1  # minus the trash sink
        if total <= 0:
            return 0.0
        return 1.0 - self._engine._alloc.free_count / total

    def tokens_per_sec(self, window_s: float = 10.0) -> Optional[float]:
        """Decode throughput over the trailing ``window_s`` seconds of
        token marks (None until two marks exist; a fleet view sums its
        replicas' rates)."""
        if self._sources:
            rates = [m.tokens_per_sec(window_s) for m in self._sources]
            rates = [r for r in rates if r is not None]
            return sum(rates) if rates else None
        if len(self._token_marks) < 2:
            return None
        now = self._clock()
        marks = [(ts, tot) for ts, tot in self._token_marks if now - ts <= window_s]
        if len(marks) < 2:
            marks = list(self._token_marks)[-2:]
        (t0, c0), (t1, c1) = marks[0], marks[-1]
        if t1 <= t0:
            return None
        return (c1 - c0) / (t1 - t0)

    def snapshot(self) -> dict:
        """One flat dict of every metric — what the event log and the
        tracker forwarding consume."""
        snap = {
            "requests_submitted": self.requests_submitted,
            "requests_completed": self.requests_completed,
            "requests_cancelled": self.requests_cancelled,
            "tokens_generated": self.tokens_generated,
            "prefills": self.prefills,
            "preemptions": self.preemptions,
            "queue_depth": self.queue_depth,
            "active_slots": self.active_slots,
            "kv_block_utilization": self.kv_block_utilization,
            "tokens_per_sec": self.tokens_per_sec(),
            "ttft_ms_p50": _pct(self.ttft_ms, 50),
            "ttft_ms_p95": _pct(self.ttft_ms, 95),
            "e2e_ms_p50": _pct(self.e2e_ms, 50),
            "e2e_ms_p95": _pct(self.e2e_ms, 95),
            "itl_ms_p50": _pct(self.itl_ms, 50),
            "itl_ms_p95": _pct(self.itl_ms, 95),
            "queue_wait_ms_p50": _pct(self.queue_wait_ms, 50),
            "queue_wait_ms_p95": _pct(self.queue_wait_ms, 95),
            "requests_shed": self.requests_shed,
            "requests_deprioritized": self.requests_deprioritized,
            "decode_preemptions": self.decode_preemptions,
            "resumes": self.resumes,
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "prefix_evictions": self.prefix_evictions,
            "prefix_registrations": self.prefix_registrations,
            "prefix_tokens_reused": self.prefix_tokens_reused,
            "failovers_in": self.failovers_in,
            "failovers_out": self.failovers_out,
            "failovers_lost": self.failovers_lost,
            "replica_errors": self.replica_errors,
            "replica_timeouts": self.replica_timeouts,
            "replica_state": self.replica_state,
            "ticks": self.ticks,
            "tick_ms_max": self.tick_ms_max,
            "slow_ticks": self.slow_ticks,
        }
        if self.replica is not None:
            snap["replica"] = self.replica
        return snap

    #: counters merge() sums and prometheus exposes as *_total samples
    _COUNTERS = (
        "requests_submitted", "requests_completed", "requests_cancelled",
        "tokens_generated", "prefills", "preemptions", "requests_shed",
        "requests_deprioritized", "decode_preemptions", "resumes",
        "prefix_hits", "prefix_misses", "prefix_evictions",
        "prefix_registrations", "prefix_tokens_reused",
        "failovers_in", "failovers_out", "failovers_lost",
        "replica_errors", "replica_timeouts", "ticks", "slow_ticks",
    )
    _WINDOWS = ("ttft_ms", "e2e_ms", "itl_ms", "queue_wait_ms")

    @classmethod
    def merge(cls, metrics, replica: str = "fleet") -> "ServingMetrics":
        """One fleet-level view over N replicas' metrics: counters sum,
        latency windows pool (so fleet p50/p95 are quantiles over EVERY
        replica's samples, not an average of averages), and the live
        gauges (queue depth, active slots, tokens/sec) read through to
        the sources at scrape time. The result renders/scrapes exactly
        like a single engine's metrics."""
        metrics = list(metrics)
        out = cls(None, replica=replica)
        out._sources = metrics
        for name in cls._COUNTERS:
            setattr(out, name, sum(getattr(m, name) for m in metrics))
        out.tick_ms_max = max((m.tick_ms_max for m in metrics), default=0.0)
        for name in cls._WINDOWS:
            pooled = collections.deque(
                (v for m in metrics for v in getattr(m, name)),
                maxlen=sum(getattr(m, name).maxlen for m in metrics) or 1,
            )
            setattr(out, name, pooled)
        return out

    def emit(self):
        """Write the snapshot to the attached event log as ``serving.*``
        counters (no-op when the log is disabled). The ``replica`` name
        is attached as a tag on each counter, not emitted as a value."""
        tags = {"replica": self.replica} if self.replica is not None else {}
        for name, value in self.snapshot().items():
            if name != "replica" and value is not None:
                self.log.counter(f"serving.{name}", value, **tags)

    #: (metric name, type, help, attribute/window) rows the exposition
    #: renders — shared by the single-engine and fleet renderers so a
    #: fleet scrape emits ONE ``# HELP``/``# TYPE`` block per metric with
    #: a sample per replica (the Prometheus contract for labeled series).
    _PROM_COUNTERS = (
        ("requests_submitted_total", "Requests accepted by submit()", "requests_submitted"),
        ("requests_completed_total", "Requests retired with a result", "requests_completed"),
        ("requests_cancelled_total", "Requests cancelled mid-flight or queued", "requests_cancelled"),
        ("tokens_generated_total", "Generated tokens across all requests", "tokens_generated"),
        ("prefills_total", "Prompt prefills executed", "prefills"),
        ("preemptions_total", "Admission passes blocked on KV pool exhaustion", "preemptions"),
        ("requests_shed_total", "Requests rejected by SLO load shedding", "requests_shed"),
        ("requests_deprioritized_total", "Requests demoted by SLO load shedding", "requests_deprioritized"),
        ("decode_preemptions_total", "Decoding slots evicted and requeued", "decode_preemptions"),
        ("resumes_total", "Preempted requests resumed by recompute", "resumes"),
        ("prefix_hits_total", "Requests that reused a registered shared preamble", "prefix_hits"),
        ("prefix_misses_total", "Requests with no registered preamble match", "prefix_misses"),
        ("prefix_evictions_total", "Radix-cache prefix entries evicted (LRU)", "prefix_evictions"),
        ("prefix_registrations_total", "Shared preambles promoted into the radix cache", "prefix_registrations"),
        ("prefix_tokens_reused_total", "Prompt tokens served from cached prefixes (no re-prefill)", "prefix_tokens_reused"),
        ("failovers_in_total", "Migrated in-flight requests imported from a failed replica", "failovers_in"),
        ("failovers_out_total", "In-flight requests migrated off this replica at failure/drain", "failovers_out"),
        ("failovers_lost_total", "In-flight requests unrecoverable at failover", "failovers_lost"),
        ("replica_errors_total", "Engine exceptions classified by the fleet router", "replica_errors"),
        ("replica_timeouts_total", "Tick wall-time SLO violations", "replica_timeouts"),
        ("ticks_total", "Engine ticks (one step() each)", "ticks"),
        ("slow_ticks_total", "Ticks that closed far beyond the median of the ticks before them (tick_slow events)", "slow_ticks"),
    )
    _PROM_SUMMARIES = (
        ("ttft_ms", "Time to first token (ms)", "ttft_ms"),
        ("e2e_ms", "Request end-to-end latency (ms)", "e2e_ms"),
        ("itl_ms", "Inter-token latency (ms) per delivered token", "itl_ms"),
        ("queue_wait_ms", "Submit-to-admission queue wait (ms)", "queue_wait_ms"),
    )
    _PROM_GAUGES = (
        ("queue_depth", "Requests waiting for a slot", "queue_depth"),
        ("active_slots", "Slots currently decoding", "active_slots"),
        ("kv_block_utilization", "Fraction of the paged KV pool in use", "kv_block_utilization"),
        ("tokens_per_sec", "Decode throughput over the trailing window", "tokens_per_sec"),
        ("replica_state", "Replica health (0 healthy, 1 degraded, 2 quarantined, 3 dead)", "replica_state"),
        ("tick_ms_max", "Wall time of the longest engine tick so far (ms)", "tick_ms_max"),
    )

    def _label_str(self, extra: Optional[dict] = None) -> str:
        labels = {}
        if self.replica is not None:
            labels["replica"] = self.replica
        labels.update(extra or {})
        if not labels:
            return ""
        inner = ",".join(f'{k}="{v}"' for k, v in labels.items())
        return "{" + inner + "}"

    def _prom_samples(self):
        """``(name, mtype, help, suffix, label_str, value)`` rows for this
        instance (None values are dropped at render time)."""
        rows = []
        for name, help_text, attr in self._PROM_COUNTERS:
            rows.append((name, "counter", help_text, "", self._label_str(), getattr(self, attr)))
        for name, help_text, attr in self._PROM_GAUGES:
            val = getattr(self, attr)
            if callable(val):
                val = val()
            rows.append((name, "gauge", help_text, "", self._label_str(), val))
        for name, help_text, attr in self._PROM_SUMMARIES:
            window = getattr(self, attr)
            rows.append((name, "summary", help_text, "",
                         self._label_str({"quantile": "0.5"}), _pct(window, 50)))
            rows.append((name, "summary", help_text, "",
                         self._label_str({"quantile": "0.95"}), _pct(window, 95)))
            rows.append((name, "summary", help_text, "_count", self._label_str(), len(window)))
        return rows

    def prometheus_text(self) -> str:
        """Prometheus text exposition (v0.0.4) of the snapshot. With
        :attr:`replica` set, every sample carries the ``replica`` label."""
        return _render_prom(self._prom_samples())
