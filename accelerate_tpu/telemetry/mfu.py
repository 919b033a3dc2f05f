"""MFU / goodput accounting and live-HBM sampling.

MFU here is the standard definition: achieved model FLOP/s divided by the
chip generation's peak (``analysis.costmodel.PEAK_FLOPS_TABLE`` — the same
table the static cost model prices against, so static predictions and
runtime measurements can never disagree about what "peak" means). The
model FLOPs per step come from whichever source the caller has:

* an analytic count (``6 * params * tokens``);
* ``flops_from_compiled(step._jitted...)`` when XLA's
  ``compiled.cost_analysis()`` is available (exact, includes attention);

The HBM sampler reads ``device.memory_stats()`` (present on TPU backends,
``None`` on CPU — sampling then degrades to a no-op) and cross-checks the
observed peak against the **static** flight-check estimate: when the two
disagree by more than ``drift_threshold`` (default 20%) it emits a
``hbm_drift`` warning event — either the static model is missing a buffer
(fix the liveness walk) or the program is materialising something the
author didn't intend (fix the program).
"""

from __future__ import annotations

from typing import Optional

from ..analysis.costmodel import HBM_GB_TABLE, PEAK_FLOPS_TABLE, device_generation, peak_flops
from .eventlog import EventLog

__all__ = [
    "PEAK_FLOPS_TABLE",
    "HBM_GB_TABLE",
    "device_generation",
    "peak_flops",
    "mfu",
    "goodput",
    "flops_from_compiled",
    "HBMSampler",
]


def mfu(
    flops_per_step: float,
    step_time_s: float,
    n_devices: int = 1,
    *,
    generation: Optional[str] = None,
    dtype: str = "bf16",
    peak: Optional[float] = None,
) -> float:
    """Model FLOPs utilisation in [0, ~1]. ``peak`` (FLOP/s per device)
    overrides the generation table; otherwise ``generation`` (or the
    attached device's kind) picks the table row."""
    if step_time_s <= 0:
        raise ValueError(f"step_time_s must be > 0, got {step_time_s}")
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    if peak is None:
        generation = generation or device_generation()
        if generation is None:
            raise ValueError(
                "mfu: the attached device's generation is not in PEAK_FLOPS_TABLE; "
                "pass generation= or peak= (a utilisation against another chip's peak is wrong)"
            )
        peak = peak_flops(generation, dtype)
    return flops_per_step / step_time_s / (peak * n_devices)


def goodput(records: list[dict]) -> Optional[float]:
    """Fraction of wall time spent dispatching+executing (vs waiting for
    data) over a list of :class:`StepTelemetry` records."""
    total = sum(r.get("dur_ms", 0.0) for r in records)
    if total <= 0:
        return None
    busy = sum(r.get("dispatch_ms", 0.0) + r.get("execute_ms", 0.0) for r in records)
    return min(1.0, busy / total)


def flops_from_compiled(compiled) -> Optional[float]:
    """Per-call FLOPs from an XLA compiled executable's
    ``cost_analysis()``, or None when the backend doesn't report it.
    Accepts a ``jax.jit`` wrapper (uses its first cached executable), a
    lowered+compiled object, or anything exposing ``cost_analysis``."""
    ca = getattr(compiled, "cost_analysis", None)
    if ca is None:
        return None
    try:
        analysis = ca()
    except Exception:
        return None
    # jax versions differ: a dict, or a list with one dict per device
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, dict):
        return None
    flops = analysis.get("flops")
    return float(flops) if flops else None


def _default_stats():
    """Max live/peak bytes over local devices from ``memory_stats()``;
    None on backends (CPU) that don't report."""
    import jax

    best = None
    for d in jax.local_devices():
        try:
            s = d.memory_stats()
        except Exception:
            s = None
        if not s:
            continue
        cur = {
            "bytes_in_use": int(s.get("bytes_in_use") or 0),
            "peak_bytes_in_use": int(s.get("peak_bytes_in_use") or 0),
            "bytes_limit": int(s.get("bytes_limit") or 0),
        }
        if best is None or cur["peak_bytes_in_use"] > best["peak_bytes_in_use"]:
            best = cur
    return best


class HBMSampler:
    """Periodic live-memory sampler + static-vs-observed drift check.

    ``static_peak_bytes`` is flight-check's per-device estimate
    (``FlightReport.peak_hbm_bytes``); when given, it is logged once as an
    ``hbm_static_estimate`` event and every :meth:`sample` cross-checks the
    observed peak against it, emitting ONE ``hbm_drift`` warning the first
    time relative disagreement exceeds ``drift_threshold``. ``stats_fn``
    is injectable for tests (and for backends with no ``memory_stats``).
    """

    def __init__(
        self,
        log: Optional[EventLog] = None,
        *,
        static_peak_bytes: Optional[int] = None,
        drift_threshold: float = 0.2,
        stats_fn=None,
    ):
        self.log = log if log is not None else EventLog(None)
        self.static_peak_bytes = static_peak_bytes
        self.drift_threshold = drift_threshold
        self._stats_fn = stats_fn or _default_stats
        self.observed_peak_bytes = 0
        self.samples = 0
        self.drift_event: Optional[dict] = None
        if static_peak_bytes is not None:
            self.log.event("hbm_static_estimate", bytes=int(static_peak_bytes))

    def sample(self) -> Optional[dict]:
        """Read live memory; returns the stats dict (or None when the
        backend reports nothing)."""
        stats = self._stats_fn()
        if stats is None:
            return None
        self.samples += 1
        self.observed_peak_bytes = max(self.observed_peak_bytes, stats["peak_bytes_in_use"])
        self.log.counter("hbm_bytes_in_use", stats["bytes_in_use"])
        self.log.counter(
            "hbm_peak_bytes",
            self.observed_peak_bytes,
            bytes_limit=stats.get("bytes_limit"),
        )
        self._check_drift()
        return stats

    def _check_drift(self):
        if (
            self.drift_event is not None
            or not self.static_peak_bytes
            or not self.observed_peak_bytes
        ):
            return
        rel = abs(self.observed_peak_bytes - self.static_peak_bytes) / self.static_peak_bytes
        if rel > self.drift_threshold:
            self.drift_event = self.log.event(
                "hbm_drift",
                severity="warning",
                observed_peak_bytes=self.observed_peak_bytes,
                static_peak_bytes=int(self.static_peak_bytes),
                rel_error=round(rel, 4),
                threshold=self.drift_threshold,
            )

    def headroom_bytes(self, hbm_gb: Optional[float] = None) -> Optional[int]:
        """Bytes between the observed peak and the device HBM capacity
        (table lookup by attached generation when ``hbm_gb`` is omitted)."""
        if hbm_gb is None:
            gen = device_generation()
            if gen is None:
                return None
            hbm_gb = HBM_GB_TABLE[gen]
        if not self.observed_peak_bytes:
            return None
        return int(hbm_gb * 1024**3) - self.observed_peak_bytes
