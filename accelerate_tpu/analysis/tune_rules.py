"""TPU7xx configuration rules: catch one-off misconfigurations without a
full search.

``accelerate-tpu tune`` ranks a whole neighborhood; these rules judge
*one declared configuration* (a
:class:`~accelerate_tpu.analysis.searchspace.ConfigPoint` plus whatever
evidence the caller already has — a flight report, a scored
neighborhood, a shape histogram, an optimizer) in the same static
milliseconds:

* **TPU701** — config infeasible (ERROR, the strict gate): the
  flight-check's static peak HBM exceeds the generation's per-device
  capacity (:data:`~.costmodel.HBM_GB_TABLE`, or an explicit budget).
  The tuner uses the same predicate as its feasibility prune, so a
  pruned candidate and a TPU701 finding can never disagree.
* **TPU702** — dominated comms-bound config: the config's predicted
  step time is comms-bound AND an enumerated neighbor (same workload,
  one knob changed) is strictly better on BOTH predicted time and wire
  bytes. Fires with the dominating neighbor's label and the predicted
  delta — the "you are one knob away" report.
* **TPU703** — bucket padding waste: against a declared batch/shape
  histogram (``{true_size: request_count}``), the bucket set's padded
  token count exceeds the true token count by more than the threshold.
  Suggests the minimal covering bucket per offending size.
* **TPU704** — quantized wire upcast: the requested compression's wire
  dtype is known (or measured, via ``telemetry.wire``) to be upcast by
  the platform's collective lowering — XLA:CPU runs bf16 all-reduces
  in f32, so the wire saving the scheme was
  chosen for never happens there. TPU backends keep the narrow dtype.
* **TPU705** — ZeRO-1 with a knowably non-elementwise optax transform:
  the static twin of the runtime fallback (``Accelerator`` demotes
  ``zero_stage=1`` to the passive layout when the optimizer's state
  leaves couple elements — adafactor's factored moments). Fires from a
  known-name table or, given a real optax transform, the same
  structural ``eval_shape`` probe the runtime uses.

Everything except the optional optax probe is host-side math — no jax.
"""

from __future__ import annotations

from typing import Optional, Sequence

from .costmodel import HBM_GB_TABLE
from .rules import Finding

#: platforms whose collective lowering is known to upcast narrow wire
#: dtypes (requested compression name -> the dtype actually moved).
#: XLA:CPU runs bf16 all-reduces in f32 — what
#: ``telemetry.wire.wire_dtype_upcast`` reads off the compiled HLO;
#: int8/fp8 travel as int8 bit-patterns and stay narrow everywhere.
KNOWN_WIRE_UPCASTS: dict[str, dict[str, str]] = {
    "cpu": {"bf16": "float32"},
}

#: optax transforms whose state structurally couples elements within a
#: parameter leaf — the flat-segment ZeRO-1 update would break them
#: (the runtime's ``_nonelementwise_state_nodes`` probe proves the same
#: thing from ``eval_shape``; this table covers the config-file path
#: where only a name is declared).
KNOWN_NON_ELEMENTWISE_OPTIMIZERS = frozenset({"adafactor", "sm3"})


def _human(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024:
            return f"{n:.1f} {unit}"
        n /= 1024
    return f"{n:.1f} PB"


def hbm_budget_bytes(generation: str, hbm_gb: Optional[float] = None) -> int:
    """The per-device HBM capacity a config must fit in: an explicit
    ``hbm_gb`` override, else the generation's
    :data:`~.costmodel.HBM_GB_TABLE` row (v5e fallback)."""
    gb = hbm_gb if hbm_gb is not None else HBM_GB_TABLE.get(generation, HBM_GB_TABLE["v5e"])
    return int(gb * 1024**3)


def check_hbm_feasible(
    peak_hbm_bytes: int,
    generation: str,
    *,
    hbm_gb: Optional[float] = None,
    label: str = "config",
) -> list[Finding]:
    """TPU701 — the flight-check static peak does not fit the
    generation's per-device HBM. Shared with the tuner's feasibility
    prune so the two verdicts cannot drift."""
    budget = hbm_budget_bytes(generation, hbm_gb)
    if peak_hbm_bytes <= budget:
        return []
    return [
        Finding(
            "TPU701",
            f"{label}: static peak HBM {_human(peak_hbm_bytes)}/device exceeds the "
            f"{generation} capacity of {_human(budget)} — this configuration cannot run; "
            "shard further (mesh/ZeRO), donate buffers, or pick a bigger generation",
        )
    ]


def check_dominated(
    candidate: dict,
    neighbors: Sequence[dict],
) -> list[Finding]:
    """TPU702 — ``candidate`` is comms-bound and some neighbor strictly
    dominates it. ``candidate``/``neighbors`` are scored dicts with
    ``label``, ``bound``, ``predicted_step_us``, ``wire_bytes`` (the
    tuner's :meth:`~.tuner.CandidateResult.score_dict`)."""
    if candidate.get("bound") != "comms":
        return []
    t = candidate.get("predicted_step_us")
    w = candidate.get("wire_bytes", 0)
    if t is None:
        return []
    best = None
    for n in neighbors:
        nt, nw = n.get("predicted_step_us"), n.get("wire_bytes", 0)
        if nt is None or nt >= t or nw >= w:
            continue
        if best is None or nt < best.get("predicted_step_us"):
            best = n
    if best is None:
        return []
    delta_us = t - best["predicted_step_us"]
    return [
        Finding(
            "TPU702",
            f"{candidate.get('label', 'config')} is comms-bound and strictly dominated by "
            f"{best.get('label', 'a neighbor')} in the enumerated neighborhood: predicted "
            f"step {t / 1000:.3f} -> {best['predicted_step_us'] / 1000:.3f} ms "
            f"(-{delta_us / 1000:.3f} ms) with {_human(w)} -> {_human(best.get('wire_bytes', 0))} "
            "wire bytes — one knob change is predicted faster AND cheaper on the wire",
        )
    ]


def padding_waste(buckets: Sequence[int], histogram: dict) -> tuple[float, dict]:
    """Waste fraction of a bucket set against a ``{true_size: count}``
    histogram: ``padded_tokens / true_tokens - 1``. Sizes above the
    largest bucket pad to it (the engine would reject or truncate —
    either way the largest bucket is the honest denominator). Also
    returns per-size detail ``{size: (bucket, waste_tokens)}``."""
    buckets = sorted(int(b) for b in buckets)
    true_tokens = padded_tokens = 0
    detail: dict = {}
    for size, count in sorted((int(s), int(c)) for s, c in histogram.items()):
        bucket = next((b for b in buckets if b >= size), buckets[-1] if buckets else size)
        true_tokens += size * count
        padded_tokens += max(bucket, size) * count
        detail[size] = (bucket, (max(bucket, size) - size) * count)
    if true_tokens <= 0:
        return 0.0, detail
    return padded_tokens / true_tokens - 1.0, detail


def check_bucket_waste(
    buckets: Sequence[int],
    histogram: dict,
    *,
    threshold: float = 0.25,
    label: str = "config",
) -> list[Finding]:
    """TPU703 — the bucket set wastes more than ``threshold`` of its
    compute on padding against the declared histogram."""
    if not buckets or not histogram:
        return []
    waste, detail = padding_waste(buckets, histogram)
    if waste <= threshold:
        return []
    worst_size, (worst_bucket, worst_tokens) = max(detail.items(), key=lambda kv: kv[1][1])
    return [
        Finding(
            "TPU703",
            f"{label}: buckets {sorted(int(b) for b in buckets)} pad the declared shape "
            f"histogram by {waste:.0%} (threshold {threshold:.0%}); worst offender: size "
            f"{worst_size} pads to bucket {worst_bucket} ({worst_tokens} wasted tokens) — "
            "add a covering bucket near the histogram's mass (aot.ShapeBucketer's "
            "histogram refinement mints one)",
        )
    ]


def check_wire_upcast(
    compression: Optional[str],
    *,
    platform: Optional[str] = None,
    sites: Optional[list] = None,
    label: str = "config",
) -> list[Finding]:
    """TPU704 — the requested compression's wire dtype is upcast by the
    platform. Judged from measured HLO collective ``sites``
    (``telemetry.wire.hlo_collective_sites``) when given — the strongest
    evidence — else from the :data:`KNOWN_WIRE_UPCASTS` table."""
    if not compression:
        return []
    if sites:
        from ..telemetry.wire import wire_dtype_upcast

        hit = wire_dtype_upcast(sites, compression)
        if hit is None:
            return []
        return [
            Finding(
                "TPU704",
                f"{label}: grad_compression={compression!r} requested but the compiled "
                f"program's dominant collective moves {hit['measured_dtype']} "
                f"({hit['measured_bytes']} B/elem vs the requested {hit['requested_bytes']}) — "
                "the platform upcasts the wire dtype, erasing the saving; use int8/fp8 "
                "(bit-cast wires stay narrow) or drop the knob on this platform",
            )
        ]
    upcast_to = KNOWN_WIRE_UPCASTS.get(str(platform or "").lower(), {}).get(compression)
    if upcast_to is None:
        return []
    return [
        Finding(
            "TPU704",
            f"{label}: grad_compression={compression!r} requested on platform "
            f"{platform!r}, whose collective lowering is known to upcast it to {upcast_to} "
            "(XLA:CPU runs bf16 all-reduces in f32 — the telemetry wire counter measures "
            "it); the wire saving never happens here — use int8/fp8 or drop the knob",
        )
    ]


def check_zero1_optimizer(
    zero_stage: Optional[int],
    optimizer,
    *,
    label: str = "config",
) -> list[Finding]:
    """TPU705 — ``zero_stage=1`` with a knowably non-elementwise optax
    transform. ``optimizer`` is a declared name (checked against
    :data:`KNOWN_NON_ELEMENTWISE_OPTIMIZERS`) or a real optax transform
    (probed structurally via the runtime's ``eval_shape`` walk — nothing
    runs)."""
    if zero_stage != 1 or optimizer is None:
        return []
    offending: Optional[str] = None
    if isinstance(optimizer, str):
        if optimizer.lower() in KNOWN_NON_ELEMENTWISE_OPTIMIZERS:
            offending = optimizer
    else:
        from ..accelerator import _nonelementwise_state_nodes

        bad = _nonelementwise_state_nodes(optimizer)
        if bad:
            offending = ", ".join(sorted(bad))
    if offending is None:
        return []
    return [
        Finding(
            "TPU705",
            f"{label}: zero_stage=1 requested with a non-elementwise optimizer "
            f"({offending}) — its state couples elements within a param leaf, so the "
            "flat-segment ZeRO-1 update would corrupt it; the runtime falls back to the "
            "passive shard_optimizer_state layout (a one-time warning), which keeps "
            "correctness but not the explicit-wire HBM/bytes win — pick an elementwise "
            "transform (sgd/adam/adamw) or drop zero_stage",
        )
    ]


def check_config_rules(
    point,
    *,
    peak_hbm_bytes: Optional[int] = None,
    generation: str = "v5e",
    hbm_gb: Optional[float] = None,
    neighbors: Sequence[dict] = (),
    candidate_score: Optional[dict] = None,
    shape_histogram: Optional[dict] = None,
    waste_threshold: float = 0.25,
    platform: Optional[str] = None,
    wire_sites: Optional[list] = None,
    optimizer=None,
) -> list[Finding]:
    """Run every TPU7xx rule the caller has evidence for against one
    :class:`~.searchspace.ConfigPoint`. The tuner calls this per
    candidate; ``accelerate-tpu tune --selfcheck`` drives each rule with
    a seeded misconfig and its clean twin."""
    label = point.label()
    findings: list[Finding] = []
    if peak_hbm_bytes is not None:
        findings += check_hbm_feasible(peak_hbm_bytes, generation, hbm_gb=hbm_gb, label=label)
    if candidate_score is not None and neighbors:
        findings += check_dominated(candidate_score, neighbors)
    if point.buckets and shape_histogram:
        findings += check_bucket_waste(
            point.buckets, shape_histogram, threshold=waste_threshold, label=label
        )
    findings += check_wire_upcast(
        point.compression, platform=platform, sites=wire_sites, label=label
    )
    findings += check_zero1_optimizer(point.zero_stage, optimizer, label=label)
    return findings
