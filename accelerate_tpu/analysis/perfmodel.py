"""Static roofline: price every op in a traced step — FLOPs, HBM bytes,
bytes-on-wire — and predict the step-time/MFU ceiling *before* anything
compiles or runs.

The flight-check (TPU3xx) proves a step is *safe*; this module prices
whether it is *fast*. ``perf_check(fn, *sample_args, mesh=...)`` traces
``fn`` abstractly with the PR-1 linter machinery (nothing executes,
nothing compiles), walks the jaxpr the same way
``costmodel.collect_traffic`` does — recursing through pjit/shard_map and
multiplying ``scan`` bodies by their trip counts — and emits one
:class:`OpRecord` per priced equation:

* **FLOPs** — exact for ``dot_general`` (``2·batch·M·N·K``) and
  ``conv_general_dilated`` (``2·out_numel·C_in/groups·∏kernel``); nominal
  VPU weights elsewhere (1 FLOP/element for arithmetic, 10 for
  transcendentals, input-numel for reductions, 0 for pure data movement).
* **HBM bytes** — operand + result bytes per equation, sharding-aware
  (a value known sharded over mesh axes is divided by the axis-size
  product, propagated from argument shardings and
  ``with_sharding_constraint`` sites exactly like the flight-check's
  liveness walk). This is the *unfused* traffic — XLA's fusion pass can
  only reduce it, so the memory-side time is an upper bound.
* **bytes-on-wire** — collectives priced by ``costmodel.price_collective``
  (ring formulas, ICI-vs-DCN from the mesh transport metadata).

Per-op roofline: an op's time is ``max(flops/peak, hbm_bytes/hbm_bw)``
(the generation's :data:`~.costmodel.PEAK_FLOPS_TABLE` /
:data:`~.costmodel.HBM_BW_TABLE` rows); whichever side wins classifies it
**compute**- or **memory**-bound; collectives are **comms**-bound at
``wire_bytes/link_bw``. The predicted step time is the serial sum (no
overlap modelled — finding the overlap that IS available is rule TPU504's
job) and the **MFU upper bound** is ``total_flops / (predicted_time ·
peak)`` — the ceiling the runtime telemetry's measured MFU is compared
against, and the number ``StepTelemetry`` cross-checks at runtime via the
``perf_model_drift`` event.

Scope (stated honestly, same caveat as ``costmodel``): the walk sees the
ops the user wrote. Per-device FLOPs assume each op parallelises over
the mesh axes of its most finely sharded participant (inputs or output);
byte counts divide per value. Collectives GSPMD inserts during
partitioning (e.g. the psum a contracted-dim layout needs) are not in
the jaxpr and are not priced. f32 matmuls are priced at half the bf16
MXU peak (the multi-pass lowering) — which is exactly the gap rule
TPU505 reports when bf16-with-f32-accumulate would be equivalent.

jax is imported lazily; everything works on abstract values only.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

from .costmodel import COLLECTIVE_PRIMS, hbm_bandwidth, peak_flops, price_collective
from .rules import Finding, filter_findings

#: MXU systolic array: 128 lanes (last dim, every dtype) x a dtype-paced
#: sublane count (second-to-last dim). A matmul dim not a multiple of its
#: tile is padded by the compiler and the padded MACs are pure waste —
#: rule TPU501 prices that.
MXU_LANE = 128
SUBLANE = {
    "float32": 8,
    "float64": 8,
    "bfloat16": 16,
    "float16": 16,
    "int8": 32,
    "uint8": 32,
    "float8_e4m3fn": 32,
    "float8_e5m2": 32,
}

BOUND_COMPUTE = "compute"
BOUND_MEMORY = "memory"
BOUND_COMMS = "comms"

#: dtypes priced at the bf16 MXU rate
_BF16_CLASS = ("bfloat16", "float16", "float8_e4m3fn", "float8_e5m2")
_INT8_CLASS = ("int8", "uint8")

#: pure data movement — no FLOPs, and (reshape/squeeze) not even a copy
_FREE_PRIMS = frozenset({"reshape", "squeeze"})
_MOVE_PRIMS = frozenset(
    {
        "broadcast_in_dim", "transpose", "slice", "dynamic_slice",
        "dynamic_update_slice", "concatenate", "pad", "gather", "scatter",
        "scatter-add", "rev", "iota", "copy", "convert_element_type",
        "bitcast_convert_type", "select_n", "stop_gradient",
    }
)
#: nominal VPU cost weights (FLOPs per output element). Transcendentals
#: run on the VPU's special-function path; 10 is the conventional
#: roofline weight, not a measurement.
_TRANSCENDENTAL = frozenset(
    {"exp", "log", "log1p", "expm1", "tanh", "logistic", "erf", "erfc",
     "erf_inv", "sin", "cos", "tan", "pow", "rsqrt", "sqrt", "cbrt",
     "digamma", "lgamma"}
)
_REDUCE_PRIMS = frozenset(
    {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
     "reduce_or", "reduce_xor", "argmax", "argmin", "reduce_precision",
     "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp"}
)


def _prod(it) -> int:
    out = 1
    for v in it:
        out *= int(v)
    return out


def _nbytes(aval) -> int:
    import numpy as np

    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:  # extended dtypes (PRNG keys)
        itemsize = int(getattr(dtype, "itemsize", 0) or 0)
    return _prod(shape or (1,)) * itemsize


def _numel(aval) -> int:
    return _prod(getattr(aval, "shape", ()) or (1,))


def _is_literal(v) -> bool:
    return type(v).__name__ == "Literal"


def _human(n) -> str:
    n = float(n or 0)
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024:
            return f"{n:.1f} {unit}"
        n /= 1024
    return f"{n:.1f} PB"


def _human_flops(n) -> str:
    n = float(n or 0)
    for unit in ("", "K", "M", "G", "T"):
        if abs(n) < 1000:
            return f"{n:.1f} {unit}FLOP" if unit else f"{n:.0f} FLOP"
        n /= 1000
    return f"{n:.1f} PFLOP"


# -- per-primitive FLOP models ---------------------------------------------


def dot_dims(eqn) -> Optional[dict]:
    """The M/N/K/batch split of a ``dot_general``: dim lists (sizes) for
    the lhs non-contracted (M), rhs non-contracted (N), contracted (K)
    and batch groups, plus operand dtypes. None for non-dots."""
    if eqn.primitive.name != "dot_general":
        return None
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs = tuple(getattr(eqn.invars[0].aval, "shape", ()))
    rhs = tuple(getattr(eqn.invars[1].aval, "shape", ()))
    m = [lhs[i] for i in range(len(lhs)) if i not in set(lc) | set(lb)]
    n = [rhs[i] for i in range(len(rhs)) if i not in set(rc) | set(rb)]
    k = [lhs[i] for i in lc]
    b = [lhs[i] for i in lb]
    return {
        "m": m, "n": n, "k": k, "batch": b,
        "lhs_dtype": str(getattr(eqn.invars[0].aval, "dtype", "")),
        "rhs_dtype": str(getattr(eqn.invars[1].aval, "dtype", "")),
    }


def conv_dims(eqn) -> Optional[dict]:
    """Output numel, implicit-GEMM split (out-channels, out positions,
    in-channels-per-group, kernel spatial dims) of a
    ``conv_general_dilated``; None for non-convs."""
    if eqn.primitive.name != "conv_general_dilated":
        return None
    dn = eqn.params.get("dimension_numbers")
    rhs = tuple(getattr(eqn.invars[1].aval, "shape", ()))
    out = tuple(getattr(eqn.outvars[0].aval, "shape", ()))
    groups = int(eqn.params.get("feature_group_count", 1) or 1)
    rhs_spec = getattr(dn, "rhs_spec", None)
    out_spec = getattr(dn, "out_spec", None)
    if rhs_spec is not None:
        in_c = rhs[rhs_spec[1]]
        spatial = [rhs[i] for i in rhs_spec[2:]]
    else:  # default (out_c, in_c, *spatial) layout
        in_c = rhs[1] if len(rhs) > 1 else 1
        spatial = list(rhs[2:])
    if out_spec is not None:
        out_c = out[out_spec[1]]
    else:  # default (batch, out_c, *spatial)
        out_c = out[1] if len(out) > 1 else 1
    out_numel = _prod(out)
    return {
        "out_numel": out_numel, "out_c": int(out_c),
        "out_positions": out_numel // max(1, int(out_c)),
        "in_c": int(in_c), "spatial": spatial, "groups": groups,
        "lhs_dtype": str(getattr(eqn.invars[0].aval, "dtype", "")),
        "rhs_dtype": str(getattr(eqn.invars[1].aval, "dtype", "")),
    }


def op_flops(eqn) -> int:
    """Global (unsharded) FLOPs of one equation under the nominal model
    documented in the module docstring."""
    name = eqn.primitive.name
    d = dot_dims(eqn)
    if d is not None:
        return 2 * _prod(d["batch"]) * _prod(d["m"]) * _prod(d["n"]) * _prod(d["k"])
    c = conv_dims(eqn)
    if c is not None:
        return 2 * c["out_numel"] * (c["in_c"] // max(1, c["groups"]) or 1) * _prod(c["spatial"])
    if name in _FREE_PRIMS or name in _MOVE_PRIMS or name in COLLECTIVE_PRIMS:
        return 0
    if name in _REDUCE_PRIMS:
        return sum(_numel(getattr(v, "aval", None)) for v in eqn.invars if not _is_literal(v))
    weight = 10 if name in _TRANSCENDENTAL else 1
    out_numel = sum(_numel(getattr(o, "aval", None)) for o in eqn.outvars)
    return weight * out_numel


def matmul_dtype_class(dtype: str) -> str:
    """Peak-table row an MXU op with this input dtype prices against:
    bf16-class at full rate, int8 at the int8 row, f32/f64 at HALF the
    bf16 rate (the multi-pass f32 lowering)."""
    if dtype in _BF16_CLASS:
        return "bf16"
    if dtype in _INT8_CLASS:
        return "int8"
    return "f32"


def op_peak_flops(eqn, generation: str) -> float:
    """Peak FLOP/s the op's dtype can reach on ``generation``."""
    d = dot_dims(eqn) or conv_dims(eqn)
    if d is not None:
        cls = matmul_dtype_class(d["lhs_dtype"])
        if cls == "f32":
            return peak_flops(generation, "bf16") / 2.0
        return peak_flops(generation, cls)
    # VPU work prices against the bf16 MXU peak too — a deliberate
    # *optimistic* choice that keeps elementwise chains from dominating
    # the prediction (XLA fuses them into the adjacent matmul anyway)
    return peak_flops(generation, "bf16")


# -- the walk --------------------------------------------------------------


@dataclass
class OpRecord:
    """One priced equation (already multiplied by its scan trip count)."""

    primitive: str
    location: str
    count: int
    flops: int  # per device, per step
    hbm_bytes: int  # per device, per step (unfused)
    wire_bytes: int  # per device, per step (collectives only)
    transport: Optional[str]  # "ici"/"dcn" for collectives, else None
    bound: str  # compute | memory | comms
    time_us: float

    def as_dict(self) -> dict:
        return {
            "primitive": self.primitive,
            "location": self.location,
            "count": self.count,
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "wire_bytes": self.wire_bytes,
            "transport": self.transport,
            "bound": self.bound,
            "time_us": round(self.time_us, 3),
        }


@dataclass
class PerfReport:
    """Everything ``perf_check`` learns about one step function."""

    fn_name: str
    mesh_axes: dict[str, int] = field(default_factory=dict)
    generation: str = "v5e"
    ops: list[OpRecord] = field(default_factory=list)
    findings: list[Finding] = field(default_factory=list)
    #: kernel names of pallas calls with no registered KernelCostSpec —
    #: priced at ZERO above; the tuner folds these into TPU1005 findings
    unpriced: list[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not any(f.is_error for f in self.findings)

    @property
    def total_flops(self) -> int:
        return sum(o.flops for o in self.ops)

    @property
    def total_hbm_bytes(self) -> int:
        return sum(o.hbm_bytes for o in self.ops)

    @property
    def total_wire_bytes(self) -> int:
        return sum(o.wire_bytes for o in self.ops)

    @property
    def predicted_step_us(self) -> float:
        return sum(o.time_us for o in self.ops)

    @property
    def predicted_step_ms(self) -> float:
        return self.predicted_step_us / 1000.0

    @property
    def mfu_upper_bound(self) -> Optional[float]:
        """total FLOPs / (predicted time x bf16 peak) — the MFU ceiling
        this program can reach on this generation under the model."""
        t = self.predicted_step_us / 1e6
        if t <= 0:
            return None
        return self.total_flops / t / peak_flops(self.generation, "bf16")

    def time_by_bound(self) -> dict[str, float]:
        out = {BOUND_COMPUTE: 0.0, BOUND_MEMORY: 0.0, BOUND_COMMS: 0.0}
        for o in self.ops:
            out[o.bound] += o.time_us
        return {k: round(v, 3) for k, v in out.items()}

    def wire_bytes_by_transport(self) -> dict[str, int]:
        out = {"ici": 0, "dcn": 0}
        for o in self.ops:
            if o.transport:
                out[o.transport] += o.wire_bytes
        return out

    def as_dict(self) -> dict:
        return {
            "fn": self.fn_name,
            "mesh": dict(self.mesh_axes),
            "generation": self.generation,
            "totals": {
                "flops_per_device": self.total_flops,
                "hbm_bytes_per_device": self.total_hbm_bytes,
                "wire_bytes_per_device": self.total_wire_bytes,
                "wire_bytes_by_transport": self.wire_bytes_by_transport(),
                "predicted_step_ms": round(self.predicted_step_ms, 4),
                "mfu_upper_bound": round(self.mfu_upper_bound, 5) if self.mfu_upper_bound else None,
                "time_by_bound_us": self.time_by_bound(),
            },
            "ops": [o.as_dict() for o in self.ops],
            "unpriced_kernels": list(self.unpriced),
            "findings": [f.as_dict() for f in self.findings],
        }

    def render_text(self, top_k: int = 8) -> str:
        mesh = ", ".join(f"{a}={n}" for a, n in self.mesh_axes.items() if n > 1) or "1 device"
        by_bound = self.time_by_bound()
        total_us = self.predicted_step_us
        lines = [
            f"perf-check: {self.fn_name} on mesh ({mesh}), {self.generation} roofline",
            f"  FLOPs / device / step : {_human_flops(self.total_flops)}",
            f"  HBM traffic (unfused) : {_human(self.total_hbm_bytes)}",
        ]
        wires = self.wire_bytes_by_transport()
        if self.total_wire_bytes:
            lines.append(
                f"  wire bytes            : {_human(wires['ici'])} ici, {_human(wires['dcn'])} dcn"
            )
        lines.append(
            f"  predicted step time   : {self.predicted_step_ms:.3f} ms"
            f"  (compute {by_bound[BOUND_COMPUTE]:.1f}us"
            f" | memory {by_bound[BOUND_MEMORY]:.1f}us"
            f" | comms {by_bound[BOUND_COMMS]:.1f}us)"
        )
        if self.mfu_upper_bound is not None:
            lines.append(f"  MFU upper bound       : {self.mfu_upper_bound:.1%}")
        if self.unpriced:
            lines.append(
                "  unpriced pallas calls : "
                + ", ".join(self.unpriced)
                + "  (no KernelCostSpec — run `accelerate-tpu kernel-check`)"
            )
        hot = sorted(self.ops, key=lambda o: -o.time_us)[:top_k]
        if hot:
            lines.append("  hottest ops:")
            for o in hot:
                count = f" x{o.count}" if o.count > 1 else ""
                detail = (
                    f"{_human(o.wire_bytes)} wire ({o.transport})"
                    if o.bound == BOUND_COMMS
                    else f"{_human_flops(o.flops)}, {_human(o.hbm_bytes)} hbm"
                )
                share = f"{o.time_us / total_us:.0%}" if total_us > 0 else "-"
                lines.append(
                    f"    {o.time_us:>9.1f}us {share:>4}  {o.primitive:<20}{count} "
                    f"[{o.bound}] {detail}{(' ' + o.location) if o.location else ''}"
                )
        if self.findings:
            from .report import format_finding

            lines.append("  findings:")
            lines.extend(f"    {format_finding(f)}" for f in self.findings)
        else:
            lines.append("  findings: none")
        return "\n".join(lines)


def eqn_path_line(eqn) -> tuple[Optional[str], Optional[int]]:
    """(path, line) of the user frame that created this equation, or
    (None, None) — lets TPU5xx findings anchor to real source so inline
    ``# tpu-lint: disable`` comments and SARIF locations work."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is None:
            return None, None
        path = getattr(frame, "file_name", None)
        line = getattr(frame, "start_line", None)
        if not path or path.startswith("<"):
            return None, None
        return path, int(line) if line else None
    except Exception:
        return None, None


def _eqn_loc(eqn) -> str:
    from .jaxpr_lint import _eqn_location

    return _eqn_location(eqn).strip()


def _spec_factor(spec_axes: set, mesh) -> int:
    n = 1
    for a in spec_axes:
        n *= int(mesh.shape.get(a, 1))
    return max(1, n)


def walk_ops(
    closed,
    sample_args,
    mesh,
    *,
    in_shardings: Any = None,
    dcn: Optional[Sequence[str]] = None,
    generation: str = "v5e",
    unpriced: Optional[list] = None,
) -> list[OpRecord]:
    """Price every equation of the (unwrapped) jaxpr; see the module
    docstring for the model. Returns records in program order.

    A ``pallas_call`` is priced from its registered
    :class:`~accelerate_tpu.kernels.contracts.KernelCostSpec` (declared
    FLOPs/HBM bytes on the roofline) — never by walking its body, whose
    ref-typed equations the nominal model would misprice. An unregistered
    call costs ZERO: a one-time ``UnknownOpWarning`` names the blindness
    and the kernel name is appended to ``unpriced`` when a list is
    passed (``perf_check`` surfaces it on the report; the tuner turns it
    into TPU1005)."""
    from .flightcheck import _arg_spec_axes, _main_jaxpr
    from .jaxpr_lint import _axis_names_in_params, _iter_subjaxprs, _sharding_axes

    jaxpr = _main_jaxpr(closed)
    hbm_bw = hbm_bandwidth(generation)

    var_axes: dict[Any, set] = {}
    for v, axes in zip(jaxpr.invars, _arg_spec_axes(sample_args, in_shardings, len(jaxpr.invars))):
        if axes:
            var_axes[v] = axes

    records: list[OpRecord] = []

    def shard_of(v) -> int:
        return _spec_factor(var_axes.get(v, set()), mesh)

    def propagate(eqn):
        if eqn.primitive.name == "sharding_constraint":
            axes = _sharding_axes(eqn.params.get("sharding"))
            for o in eqn.outvars:
                var_axes[o] = axes
            return
        in_axes = [
            (v, var_axes[v]) for v in eqn.invars
            if not _is_literal(v) and v in var_axes and var_axes[v]
        ]
        if not in_axes:
            return
        for o in eqn.outvars:
            for v, axes in in_axes:
                if getattr(o.aval, "shape", None) == getattr(v.aval, "shape", ()):
                    var_axes.setdefault(o, axes)
                    break

    def walk(jx, multiplier: int):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            subs = list(_iter_subjaxprs(eqn.params))
            propagate(eqn)
            if name in COLLECTIVE_PRIMS:
                axes = tuple(_axis_names_in_params(eqn.params))
                operand = sum(
                    _nbytes(getattr(v, "aval", None)) // shard_of(v)
                    for v in eqn.invars
                    if not _is_literal(v)
                )
                rec = price_collective(
                    name, axes, operand, mesh, count=multiplier, dcn=dcn,
                    location=_eqn_loc(eqn),
                )
                if rec is not None:
                    records.append(
                        OpRecord(
                            primitive=name,
                            location=rec.location,
                            count=multiplier,
                            flops=0,
                            hbm_bytes=0,
                            wire_bytes=rec.wire_bytes,
                            transport=rec.transport,
                            bound=BOUND_COMMS,
                            time_us=rec.time_us(generation),
                        )
                    )
                continue
            if name == "pallas_call":
                from ..kernels.contracts import (
                    eqn_kernel_name,
                    pallas_in_avals,
                    registered_spec,
                    warn_unknown_op,
                )

                kname = eqn_kernel_name(eqn.params) or "<pallas_call>"
                spec = registered_spec(kname)
                work_shard = max(
                    [shard_of(v) for v in eqn.invars if not _is_literal(v)]
                    + [shard_of(o) for o in eqn.outvars]
                    or [1]
                )
                flops = hbm = 0
                if spec is not None:
                    try:
                        avals = pallas_in_avals(eqn.params)
                        flops = int(spec.flops(*avals)) // work_shard
                        hbm = int(spec.hbm_bytes(*avals)) // work_shard
                    except Exception:
                        spec = None  # a spec that cannot price is no spec
                if spec is None:
                    warn_unknown_op("perf-check", f"pallas_call:{kname}", "FLOPs / HBM bytes")
                    if unpriced is not None and kname not in unpriced:
                        unpriced.append(kname)
                    continue
                t_compute = flops / peak_flops(generation, "bf16") * 1e6
                t_memory = hbm / hbm_bw * 1e6
                records.append(
                    OpRecord(
                        primitive=f"pallas_call:{kname}",
                        location=_eqn_loc(eqn),
                        count=multiplier,
                        flops=flops * multiplier,
                        hbm_bytes=hbm * multiplier,
                        wire_bytes=0,
                        transport=None,
                        bound=BOUND_COMPUTE if t_compute >= t_memory else BOUND_MEMORY,
                        time_us=max(t_compute, t_memory) * multiplier,
                    )
                )
                continue
            if subs:
                sub_mult = multiplier
                if name == "scan":
                    sub_mult = multiplier * int(eqn.params.get("length", 1) or 1)
                for sub in subs:
                    walk(sub, sub_mult)
                continue
            flops = op_flops(eqn)
            # per-device scaling: the op parallelises over whichever
            # participating tensor is most finely sharded (a batch-sharded
            # matmul's output shape differs from its inputs, so output-only
            # propagation would miss it; contracted-dim sharding divides
            # the compute too — the psum GSPMD inserts for it is outside
            # the jaxpr, the module-docstring scope caveat)
            work_shard = max(
                [shard_of(v) for v in eqn.invars if not _is_literal(v)]
                + [shard_of(o) for o in eqn.outvars]
                or [1]
            )
            flops = flops // work_shard
            hbm = sum(
                _nbytes(getattr(v, "aval", None)) // shard_of(v)
                for v in eqn.invars
                if not _is_literal(v)
            ) + sum(_nbytes(getattr(o, "aval", None)) // shard_of(o) for o in eqn.outvars)
            if name in _FREE_PRIMS:
                hbm = 0
            if flops == 0 and hbm == 0:
                continue
            t_compute = flops / op_peak_flops(eqn, generation) * 1e6
            t_memory = hbm / hbm_bw * 1e6
            records.append(
                OpRecord(
                    primitive=name,
                    location=_eqn_loc(eqn),
                    count=multiplier,
                    flops=flops * multiplier,
                    hbm_bytes=hbm * multiplier,
                    wire_bytes=0,
                    transport=None,
                    bound=BOUND_COMPUTE if t_compute >= t_memory else BOUND_MEMORY,
                    time_us=max(t_compute, t_memory) * multiplier,
                )
            )

    walk(jaxpr, 1)
    return records


# -- entry point -----------------------------------------------------------


def _apply_inline_suppressions(findings: list[Finding]) -> list[Finding]:
    """Honour ``# tpu-lint: disable=...`` comments for findings that carry
    a real path:line (perf findings anchor to the user frame that created
    the op, so the same suppression story as the AST tier applies)."""
    import os

    from .rules import apply_suppressions

    by_path: dict[str, list[Finding]] = {}
    rest: list[Finding] = []
    for f in findings:
        if f.path and f.line and os.path.exists(f.path):
            by_path.setdefault(f.path, []).append(f)
        else:
            rest.append(f)
    kept = list(rest)
    for path, group in by_path.items():
        try:
            with open(path) as fh:
                lines = fh.read().splitlines()
        except OSError:
            kept.extend(group)
            continue
        kept.extend(apply_suppressions(group, lines))
    order = {id(f): i for i, f in enumerate(findings)}
    kept.sort(key=lambda f: order[id(f)])
    return kept


def perf_check(
    fn,
    *sample_args: Any,
    mesh=None,
    in_shardings: Any = None,
    dcn: Optional[Sequence[str]] = None,
    generation: Optional[str] = None,
    select: Optional[Sequence[str]] = None,
    ignore: Sequence[str] = (),
    rules: bool = True,
) -> PerfReport:
    """Trace ``fn(*sample_args)`` abstractly and return a
    :class:`PerfReport` — the per-op roofline plus the TPU501–505
    findings. Same calling convention as
    :func:`~accelerate_tpu.analysis.flightcheck.flight_check`;
    ``generation=None`` resolves the attached backend's generation
    (explicit ``cpu`` row under ``JAX_PLATFORMS=cpu``, v5e when nothing
    is attached)."""
    if mesh is None:
        from ..parallel.sharding import context_mesh

        mesh = context_mesh()
    if mesh is None:
        raise ValueError("perf_check needs a mesh (pass mesh=... or enter parallel.sharding.mesh_context)")
    if generation is None:
        from .costmodel import device_generation

        generation = device_generation() or "v5e"

    from .jaxpr_lint import _trace

    name = getattr(fn, "__name__", "step_fn")
    closed, findings = _trace(fn, sample_args, mesh)
    report = PerfReport(fn_name=name, mesh_axes=dict(mesh.shape), generation=generation)
    if closed is not None:
        report.ops = walk_ops(
            closed, sample_args, mesh,
            in_shardings=in_shardings, dcn=dcn, generation=generation,
            unpriced=report.unpriced,
        )
        if rules:
            from .perf_rules import check_perf_rules

            findings = findings + check_perf_rules(
                closed, mesh, dcn=dcn, generation=generation
            )
    findings = _apply_inline_suppressions(findings)
    report.findings = filter_findings(findings, select=select, ignore=ignore)
    return report
