"""The static autotuner: search configuration space with the analyzers
as the oracle.

For every candidate :class:`~.searchspace.ConfigPoint` the space
enumerates (constraint-pruned first — see
:func:`~.searchspace.prune_reason`), the tuner scores the workload
*statically*, in milliseconds, with the machinery five PRs already
validated against observed step time (the PR-8 ``perf_model_drift``
cross-check is the trust anchor; the ``make tune-trust`` contract in
``tests/test_tune.py`` pins the ranking itself):

1. **feasibility prune** — ``flight_check``'s static peak-HBM liveness
   walk vs the generation's per-device capacity
   (:func:`~.tune_rules.hbm_budget_bytes`). Infeasible candidates are
   ranked last with a TPU701 finding and never traced further.
2. **score** — ``perf_check``'s roofline: predicted step time (the
   primary key), MFU upper bound, compute/memory/comms-bound
   classification, and ``costmodel`` bytes-on-wire (the tiebreak — at
   equal predicted time, fewer wire bytes wins, because the wire is
   what real hardware variance punishes first).
3. **rules** — the TPU7xx configuration rules run over every scored
   candidate (TPU702's "dominating neighbor" uses the scored
   neighborhood itself).
4. optionally **confirm** — short measured runs of the top-k through
   :class:`~accelerate_tpu.telemetry.StepTelemetry` (median steady
   step, post-warmup recompile count) and predicted-vs-measured rank
   agreement (top-1 + Spearman). On a single-core host the measured
   side can only express knobs that change *total* compute (buckets,
   token budgets, padding); cross-device parallelism and wire savings
   time-share one core there.

Workload conventions (the flight-check CLI's target conventions, plus
one extension for config-dependent shapes):

* a **plain step function** + sample args — the tuner varies the mesh
  (re-traced per candidate mesh), DCN axes, and batch bucket (sample
  args' leading batch dim padded to the candidate's covering bucket)
  around it;
* a **workload factory** — any callable with a truthy ``tune_factory``
  attribute is called as ``factory(point) -> (step_fn, sample_args)``
  per candidate, so shapes, wire legs (ZeRO/compression), and serving
  tick structure can all depend on the point. The factory owns the
  mapping from knobs to program; the tuner owns scoring and ranking.

The winner is emitted as a loadable ``[tune.chosen]`` block
(:func:`~.searchspace.chosen_toml`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

from .rules import Finding, filter_findings
from .searchspace import ConfigPoint, SearchSpace, chosen_toml
from .tune_rules import check_config_rules, check_dominated, hbm_budget_bytes

STATUS_OK = "ok"
STATUS_PRUNED = "pruned"
STATUS_INFEASIBLE = "infeasible"
STATUS_ERROR = "error"


def _human(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024:
            return f"{n:.1f} {unit}"
        n /= 1024
    return f"{n:.1f} PB"


@dataclass
class CandidateResult:
    """One scored (or pruned) candidate."""

    point: ConfigPoint
    status: str = STATUS_OK
    reason: Optional[str] = None
    predicted_step_us: Optional[float] = None
    mfu_upper_bound: Optional[float] = None
    bound: Optional[str] = None  # dominant roofline side: compute|memory|comms
    bubble_fraction: Optional[float] = None  # set when pipemodel rescored the point
    wire_bytes: int = 0
    peak_hbm_bytes: Optional[int] = None
    findings: list = field(default_factory=list)
    measured_step_us: Optional[float] = None
    measured_recompiles: Optional[int] = None

    @property
    def label(self) -> str:
        return self.point.label()

    def score_dict(self) -> dict:
        """The comparison view TPU702's domination check consumes."""
        return {
            "label": self.label,
            "bound": self.bound,
            "predicted_step_us": self.predicted_step_us,
            "wire_bytes": self.wire_bytes,
        }

    def as_dict(self) -> dict:
        out = {
            "config": self.point.as_dict(),
            "label": self.label,
            "status": self.status,
            "reason": self.reason,
            "predicted_step_us": round(self.predicted_step_us, 3)
            if self.predicted_step_us is not None else None,
            "mfu_upper_bound": round(self.mfu_upper_bound, 5)
            if self.mfu_upper_bound is not None else None,
            "bound": self.bound,
            "wire_bytes": self.wire_bytes,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "findings": [f.as_dict() for f in self.findings],
        }
        if self.bubble_fraction is not None:
            out["bubble_fraction"] = round(self.bubble_fraction, 5)
        if self.measured_step_us is not None:
            out["measured_step_us"] = round(self.measured_step_us, 3)
            out["measured_recompiles"] = self.measured_recompiles
        return out


def spearman(xs: Sequence[float], ys: Sequence[float]) -> Optional[float]:
    """Spearman rank correlation (average ranks for ties; no scipy)."""
    n = len(xs)
    if n != len(ys) or n < 2:
        return None

    def ranks(vals):
        order = sorted(range(n), key=lambda i: vals[i])
        r = [0.0] * n
        i = 0
        while i < n:
            j = i
            while j + 1 < n and vals[order[j + 1]] == vals[order[i]]:
                j += 1
            avg = (i + j) / 2.0 + 1.0
            for k in range(i, j + 1):
                r[order[k]] = avg
            i = j + 1
        return r

    rx, ry = ranks(list(xs)), ranks(list(ys))
    mx, my = sum(rx) / n, sum(ry) / n
    cov = sum((a - mx) * (b - my) for a, b in zip(rx, ry))
    vx = math.sqrt(sum((a - mx) ** 2 for a in rx))
    vy = math.sqrt(sum((b - my) ** 2 for b in ry))
    if vx == 0 or vy == 0:
        return 1.0 if rx == ry else 0.0
    return cov / (vx * vy)


@dataclass
class TuneReport:
    """Everything one ``tune`` run learned: every candidate (ranked ok
    first by predicted step time, wire bytes as tiebreak; then
    infeasible; then pruned), the aggregated TPU7xx findings, and the
    optional measured confirmation."""

    workload: str
    generation: str = "v5e"
    n_devices: int = 1
    hbm_budget_bytes: int = 0
    candidates: list = field(default_factory=list)
    findings: list = field(default_factory=list)
    confirm: Optional[dict] = None

    @property
    def ok(self) -> bool:
        return self.winner is not None and not any(f.is_error for f in self.findings)

    @property
    def ranked(self) -> list:
        return [c for c in self.candidates if c.status == STATUS_OK]

    @property
    def winner(self) -> Optional[CandidateResult]:
        ranked = self.ranked
        return ranked[0] if ranked else None

    @property
    def pruned_count(self) -> int:
        return sum(1 for c in self.candidates if c.status == STATUS_PRUNED)

    @property
    def infeasible_count(self) -> int:
        return sum(1 for c in self.candidates if c.status == STATUS_INFEASIBLE)

    def chosen_toml(self) -> Optional[str]:
        w = self.winner
        if w is None:
            return None
        ms = w.predicted_step_us / 1000.0 if w.predicted_step_us is not None else None
        return chosen_toml(w.point, predicted_step_ms=ms)

    def as_dict(self) -> dict:
        return {
            "workload": self.workload,
            "generation": self.generation,
            "n_devices": self.n_devices,
            "hbm_budget_bytes": self.hbm_budget_bytes,
            "candidates": [c.as_dict() for c in self.candidates],
            "winner": self.winner.as_dict() if self.winner else None,
            "pruned": self.pruned_count,
            "infeasible": self.infeasible_count,
            "confirm": self.confirm,
            "findings": [f.as_dict() for f in self.findings],
            "chosen_toml": self.chosen_toml(),
        }

    def render_text(self) -> str:
        lines = [
            f"tune: {self.workload} — {len(self.candidates)} candidates "
            f"({self.generation} roofline, {self.n_devices} devices, "
            f"HBM budget {_human(self.hbm_budget_bytes)}/device)"
        ]
        lines.append(
            f"  {'rank':<5}{'config':<42}{'pred ms':>9}{'MFU<=':>8}{'bound':>9}{'wire':>11}  status"
        )
        rank = 0
        for c in self.candidates:
            if c.status == STATUS_OK:
                rank += 1
                pred = f"{c.predicted_step_us / 1000.0:9.3f}"
                mfu = f"{c.mfu_upper_bound:7.1%}" if c.mfu_upper_bound is not None else "      -"
                row = (
                    f"  {rank:<5}{c.label:<42}{pred}{mfu:>8}{c.bound or '-':>9}"
                    f"{_human(c.wire_bytes):>11}  ok"
                )
                if c.measured_step_us is not None:
                    row += f"  (measured {c.measured_step_us / 1000.0:.3f} ms)"
            else:
                row = f"  {'-':<5}{c.label:<42}{'-':>9}{'-':>8}{'-':>9}{'-':>11}  {c.status}: {c.reason}"
            lines.append(row)
        if self.infeasible_count or self.pruned_count:
            lines.append(
                f"  pruned: {self.pruned_count} constraint, "
                f"{self.infeasible_count} HBM-infeasible (TPU701)"
            )
        w = self.winner
        if w is not None:
            lines.append(f"  winner: {w.label} — predicted {w.predicted_step_us / 1000.0:.3f} ms")
        else:
            lines.append("  winner: none (every candidate pruned or infeasible)")
        if self.confirm:
            ra = self.confirm.get("rank_agreement", {})
            lines.append(
                f"  confirm: measured top-{self.confirm.get('top_k')} over "
                f"{self.confirm.get('steps')} steps — top-1 "
                f"{'agrees' if ra.get('top1') else 'DISAGREES'}, "
                f"spearman {ra.get('spearman')}, "
                f"post-warmup recompiles {self.confirm.get('recompiles')}"
            )
        if self.findings:
            from .report import format_finding

            lines.append("  findings:")
            lines.extend(f"    {format_finding(f)}" for f in self.findings)
        else:
            lines.append("  findings: none")
        block = self.chosen_toml()
        if block:
            lines.append("")
            lines.append(block)
        return "\n".join(lines)


# -- workload resolution ----------------------------------------------------


def is_factory(workload) -> bool:
    return bool(getattr(workload, "tune_factory", False))


def _covering_bucket(buckets: Sequence[int], size: int) -> int:
    asc = sorted(int(b) for b in buckets)
    return next((b for b in asc if b >= size), asc[-1])


def _pad_batch(sample_args, buckets: Sequence[int]):
    """Pad the leading (batch) dim of the sample avals to the smallest
    covering bucket — the plain-step adapter for the buckets knob. The
    batch dim is the SMALLEST leading dim (over rank>=2 leaves) that
    some bucket can cover: weight matrices lead with feature dims, which
    are as large as — or larger than — any bucket, while the batch is
    the dim buckets exist to cover. Rank-1 leaves (biases) never pad."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(sample_args)
    top = max(int(b) for b in buckets)
    dims = sorted(
        leaf.shape[0]
        for leaf in leaves
        if len(getattr(leaf, "shape", ())) >= 2 and leaf.shape[0] <= top
    )
    if not dims:
        return sample_args
    batch = dims[0]
    bucket = _covering_bucket(buckets, batch)
    if bucket == batch:
        return sample_args

    def pad(leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) < 2 or shape[0] != batch:
            return leaf
        return jax.ShapeDtypeStruct((bucket,) + shape[1:], leaf.dtype)

    return jax.tree_util.tree_unflatten(treedef, [pad(leaf) for leaf in leaves])


def resolve_workload(workload, point: ConfigPoint, sample_args) -> tuple[Callable, tuple]:
    """``(step_fn, sample_args)`` for one candidate: factories are
    called with the point; plain steps get the bucket adapter."""
    if is_factory(workload):
        step_fn, args = workload(point)
        return step_fn, tuple(args)
    args = tuple(sample_args)
    if point.buckets:
        args = tuple(_pad_batch(args, point.buckets))
    return workload, args


def build_point_mesh(point: ConfigPoint, base_mesh=None):
    """The candidate's mesh: its own shape on a device-pool prefix
    (the ``MeshConfig(num_devices=...)`` elasticity lever), else the
    base mesh, else all devices on ``data``."""
    import jax

    from ..parallel.mesh import MeshConfig

    shape = point.mesh_shape
    if shape is None:
        if base_mesh is not None:
            return base_mesh
        return MeshConfig().build()
    return MeshConfig(**shape).build(jax.devices()[: point.mesh_devices])


# -- measured confirmation --------------------------------------------------


def _materialize(sample_args):
    """Concrete host arrays for abstract sample avals (deterministic
    seed — confirm runs must be reproducible)."""
    import jax
    import numpy as np

    rng = np.random.default_rng(0)

    def concrete(leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = np.dtype(getattr(leaf, "dtype", np.float32))
        if dtype.kind in "fc":
            return (rng.standard_normal(shape) * 0.1).astype(dtype)
        if dtype.kind in "iu":
            return rng.integers(0, 8, size=shape).astype(dtype)
        return np.zeros(shape, dtype)

    return jax.tree_util.tree_map(concrete, sample_args)


def _executable(step_fn, mesh):
    """A callable twin of ``step_fn`` that actually runs: jitted, with
    the ``_trace`` rebind for shard_map-style code (a bare ``pmean`` over
    a mesh axis) — replicated in_specs, so the measurement is an upper
    bound for such plain fns; factories that care return an
    already-executable callable and are used as-is."""
    import jax

    if hasattr(step_fn, "lower") or hasattr(step_fn, "_cache_size"):
        return step_fn  # already jit-wrapped by the factory

    jitted = jax.jit(step_fn)

    def run(*args):
        try:
            return jitted(*args)
        except NameError:
            from jax.experimental.shard_map import shard_map
            from jax.sharding import PartitionSpec as P

            wrapped = jax.jit(
                shard_map(step_fn, mesh=mesh, in_specs=P(), out_specs=P(), check_rep=False)
            )
            run.__wrapped_jit__ = wrapped
            return wrapped(*args)

    return run


def measure_candidate(
    workload,
    point: ConfigPoint,
    sample_args,
    *,
    base_mesh=None,
    steps: int = 8,
    warmup_steps: int = 2,
) -> dict:
    """One short measured run: median steady step time via
    :class:`~accelerate_tpu.telemetry.StepTelemetry` (per-step
    ``block_until_ready`` fencing) plus the post-warmup recompile count.
    Returns ``{"measured_step_us", "recompiles", "steps"}`` or an
    ``{"error": ...}`` dict when the candidate cannot execute."""
    import jax

    from ..telemetry import StepTelemetry

    mesh = build_point_mesh(point, base_mesh)
    step_fn, args = resolve_workload(workload, point, sample_args)
    concrete = _materialize(args)
    try:
        runner = _executable(step_fn, mesh)
        st = StepTelemetry(warmup_steps=warmup_steps)
        instrumented = st.wrap(runner, name=f"tune:{point.label()}")
        from ..parallel.sharding import mesh_context

        with mesh_context(mesh):
            for _ in range(warmup_steps + steps):
                out = instrumented(*concrete)
            jax.block_until_ready(out)
    except Exception as e:  # candidate cannot execute — report, don't crash the run
        return {"error": f"{type(e).__name__}: {e}"}
    steady = [r["dur_ms"] for r in st.records if not r["compile"]][-steps:]
    steady = sorted(steady)
    median = steady[len(steady) // 2] if steady else None
    return {
        "measured_step_us": median * 1000.0 if median is not None else None,
        "recompiles": st.recompiles,
        "steps": len(steady),
    }


# -- the tuner --------------------------------------------------------------


def tune(
    workload,
    space: SearchSpace,
    *sample_args: Any,
    base_mesh=None,
    generation: Optional[str] = None,
    hbm_gb: Optional[float] = None,
    dcn: Optional[Sequence[str]] = None,
    top_k: int = 0,
    confirm: bool = False,
    confirm_steps: int = 8,
    warmup_steps: int = 2,
    shape_histogram: Optional[dict] = None,
    waste_threshold: float = 0.25,
    optimizer=None,
    platform: Optional[str] = None,
    select: Optional[Sequence[str]] = None,
    ignore: Sequence[str] = (),
    rules: bool = True,
) -> TuneReport:
    """Search ``space`` for the fastest feasible configuration of
    ``workload`` (a plain step fn + ``sample_args``, or a workload
    factory — see the module docstring). Purely static unless
    ``confirm=True``, which measures the top-``top_k`` candidates with
    short :class:`StepTelemetry` runs and reports predicted-vs-measured
    rank agreement."""
    from .flightcheck import flight_check
    from .perfmodel import perf_check

    if generation is None:
        from .costmodel import device_generation

        generation = device_generation() or "v5e"
    if platform is None:
        platform = "cpu" if generation == "cpu" else generation
    budget = hbm_budget_bytes(generation, hbm_gb)

    import jax

    n_devices = len(jax.devices())
    name = getattr(workload, "__name__", "workload")
    if space.max_devices is None:
        space.max_devices = n_devices

    report = TuneReport(
        workload=name, generation=generation, n_devices=n_devices, hbm_budget_bytes=budget
    )

    scored: list[CandidateResult] = []
    for point, reason in space.enumerate_points():
        cand = CandidateResult(point=point)
        if reason is not None:
            cand.status, cand.reason = STATUS_PRUNED, reason
            report.candidates.append(cand)
            continue
        try:
            mesh = build_point_mesh(point, base_mesh)
            step_fn, args = resolve_workload(workload, point, sample_args)
            point_dcn = tuple(point.dcn_axes) or (tuple(dcn) if dcn else None)
            flight = flight_check(
                step_fn, *args, mesh=mesh, dcn=point_dcn, generation=generation
            )
        except Exception as e:
            cand.status, cand.reason = STATUS_ERROR, f"{type(e).__name__}: {e}"
            report.candidates.append(cand)
            continue
        cand.peak_hbm_bytes = flight.peak_hbm_bytes
        cand.findings.extend(f for f in flight.findings if f.is_error)
        if flight.peak_hbm_bytes > budget:
            # the TPU701 predicate IS the feasibility prune
            cand.status = STATUS_INFEASIBLE
            cand.reason = (
                f"static peak HBM {_human(flight.peak_hbm_bytes)} exceeds "
                f"{generation} budget {_human(budget)}"
            )
            if rules:
                cand.findings += check_config_rules(
                    point,
                    peak_hbm_bytes=flight.peak_hbm_bytes,
                    generation=generation,
                    hbm_gb=hbm_gb,
                )
            report.candidates.append(cand)
            continue
        try:
            perf = perf_check(
                step_fn, *args, mesh=mesh, dcn=point_dcn, generation=generation, rules=False
            )
        except Exception as e:
            cand.status, cand.reason = STATUS_ERROR, f"{type(e).__name__}: {e}"
            report.candidates.append(cand)
            continue
        cand.predicted_step_us = perf.predicted_step_us
        cand.mfu_upper_bound = perf.mfu_upper_bound
        if perf.unpriced:
            # an unpriced pallas call makes the score itself a lie —
            # this candidate's roofline is missing the kernel's cost
            cand.findings.append(
                Finding(
                    "TPU1005",
                    f"candidate scored with unpriced pallas call(s) "
                    f"{', '.join(sorted(set(perf.unpriced)))} — the roofline "
                    "ranking misses their FLOPs/bytes; register a "
                    "KernelCostSpec so tune can price them",
                )
            )
        by_bound = perf.time_by_bound()
        cand.bound = max(by_bound, key=by_bound.get) if perf.ops else None
        cand.wire_bytes = perf.total_wire_bytes
        # pipeline-aware rescoring: the serial roofline sums the stage
        # work but cannot see the fill/drain bubble. When the point
        # carries pipeline knobs (or its mesh has a pipe axis), score
        # with pipemodel's bubble-adjusted step time instead — that is
        # what makes num_microbatches/interleave/remat *rankable*.
        pipe_shape = point.mesh_shape or {}
        if point.has_pipeline_knobs or int(pipe_shape.get("pipe", 1)) > 1:
            try:
                from .pipemodel import pipe_check as _pipe_check

                pipe = _pipe_check(
                    step_fn,
                    *args,
                    mesh=mesh,
                    dcn=point_dcn,
                    generation=generation,
                    rules=False,
                    **point.pipeline_kwargs(),
                )
            except ValueError:
                pipe = None  # no pipelined region: keep the serial roofline
            if pipe is not None and pipe.predicted_step_us:
                cand.predicted_step_us = pipe.predicted_step_us
                cand.bubble_fraction = pipe.bubble_fraction
        scored.append(cand)
        report.candidates.append(cand)

    # configuration rules over the scored neighborhood
    if rules:
        for cand in scored:
            neighbors = [c.score_dict() for c in scored if c is not cand]
            cand.findings += check_dominated(cand.score_dict(), neighbors)
            cand.findings += check_config_rules(
                cand.point,
                shape_histogram=shape_histogram,
                waste_threshold=waste_threshold,
                platform=platform,
                optimizer=optimizer,
            )

    # rank: ok first by (predicted time, wire bytes), then infeasible, pruned
    order = {STATUS_OK: 0, STATUS_INFEASIBLE: 1, STATUS_ERROR: 2, STATUS_PRUNED: 3}
    report.candidates.sort(
        key=lambda c: (
            order.get(c.status, 4),
            c.predicted_step_us if c.predicted_step_us is not None else float("inf"),
            c.wire_bytes,
            c.label,
        )
    )

    # aggregate + filter findings (dedup by (rule, message)). A TPU701 on
    # an *enumerated* candidate is a successful prune, not a failure of
    # the run — it only gates (error severity, strict in `make
    # tune-selfcheck`) when the DECLARED config itself is infeasible:
    # a single-candidate run, or a space with no feasible point at all.
    single_or_dry = len(report.candidates) <= 1 or not report.ranked
    seen: set = set()
    findings: list[Finding] = []
    for cand in report.candidates:
        for f in cand.findings:
            if f.rule == "TPU701" and cand.status == STATUS_INFEASIBLE and not single_or_dry:
                continue
            key = (f.rule, f.message)
            if key not in seen:
                seen.add(key)
                findings.append(f)
    report.findings = filter_findings(findings, select=select, ignore=ignore)

    if confirm and report.ranked:
        k = max(1, int(top_k) or 3)
        targets = report.ranked[:k]
        recompiles = 0
        measured_pairs: list[tuple[float, float]] = []
        errors: dict[str, str] = {}
        for cand in targets:
            m = measure_candidate(
                workload, cand.point, sample_args,
                base_mesh=base_mesh, steps=confirm_steps, warmup_steps=warmup_steps,
            )
            if "error" in m:
                errors[cand.label] = m["error"]
                continue
            cand.measured_step_us = m["measured_step_us"]
            cand.measured_recompiles = m["recompiles"]
            recompiles += m["recompiles"]
            if cand.measured_step_us is not None:
                measured_pairs.append((cand.predicted_step_us, cand.measured_step_us))
        rank_agreement: dict[str, Any] = {"n": len(measured_pairs)}
        if measured_pairs:
            measured = [c for c in targets if c.measured_step_us is not None]
            pred_winner = min(measured, key=lambda c: c.predicted_step_us)
            meas_winner = min(measured, key=lambda c: c.measured_step_us)
            rank_agreement["top1"] = pred_winner is meas_winner
            rho = spearman([p for p, _ in measured_pairs], [m for _, m in measured_pairs])
            rank_agreement["spearman"] = round(rho, 4) if rho is not None else None
        report.confirm = {
            "top_k": k,
            "steps": confirm_steps,
            "recompiles": recompiles,
            "rank_agreement": rank_agreement,
            "errors": errors or None,
        }

    return report
