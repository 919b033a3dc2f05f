"""Collective cost model: bytes-on-wire, ICI-vs-DCN transport, and time
estimates for every collective in a traced step.

The unit that matters on TPU is bytes over the interconnect per device per
step (the EQuARX framing: a quantized all-reduce wins exactly because it
moves fewer wire bytes, so the cost model must price collectives in bytes,
not call counts). For each collective primitive this module knows the ring
wire-bytes formula, classifies the axes it runs over as ICI or DCN from
the mesh's transport metadata (``parallel.mesh.axis_transport``), and
converts bytes to an estimated time on a per-generation bandwidth table.

Scope (stated honestly): the jaxpr tier sees the collectives the user
wrote — ``psum``/``all_gather``/``ppermute``/… under ``shard_map`` — plus
``lax.scan`` trip-count multipliers. Collectives GSPMD *inserts* during
partitioning are not in the jaxpr; the flight-check approximates the big
one (forced all-gathers from conflicting shardings) as rule TPU302.

jax is imported lazily; everything here works on abstract values only.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

from ..parallel.mesh import DCN, ICI, axis_transport

#: Interconnect bandwidth per device, bytes/second. ICI figures are the
#: published per-chip aggregate ICI bandwidths (v4 ~ 2.4 Tbit/s, v5e is a
#: cost-optimised part, v5p ~ 4.8 Tbit/s); DCN is the typical per-host NIC
#: share. These price *relative* layout choices — absolute step times need
#: a profile. The ``cpu`` row is a NOMINAL fixture (round numbers, not a
#: measurement) so perf-check/flight-check output under
#: ``JAX_PLATFORMS=cpu`` is deterministic instead of silently aliasing the
#: host backend to v5e.
BANDWIDTH_TABLE: dict[str, dict[str, float]] = {
    "v4": {ICI: 300e9, DCN: 25e9},
    "v5e": {ICI: 200e9, DCN: 25e9},
    "v5p": {ICI: 600e9, DCN: 50e9},
    "v6e": {ICI: 450e9, DCN: 50e9},
    "cpu": {ICI: 100e9, DCN: 10e9},
}

#: Peak dense-matmul FLOP/s per chip by generation and compute dtype — the
#: published bf16 figures (v4 275, v5e 197, v5p 459, v6e 918 TFLOP/s), int8
#: at 2x where the generation supports it. This is the SHARED denominator
#: for MFU: the runtime telemetry (telemetry.mfu) and any static roofline
#: both read this table, so "peak" means the same thing everywhere. The
#: ``cpu`` row is a nominal 1 TFLOP/s fixture for deterministic host-
#: backend output, not a measurement.
PEAK_FLOPS_TABLE: dict[str, dict[str, float]] = {
    "v4": {"bf16": 275e12, "int8": 275e12},
    "v5e": {"bf16": 197e12, "int8": 394e12},
    "v5p": {"bf16": 459e12, "int8": 918e12},
    "v6e": {"bf16": 918e12, "int8": 1836e12},
    "cpu": {"bf16": 1e12, "int8": 1e12},
}

#: HBM bandwidth per chip, bytes/second (published: v4 1228, v5e 819,
#: v5p 2765, v6e 1640 GB/s). The roofline's memory axis: an op whose
#: arithmetic intensity (FLOPs / HBM byte) is below
#: ``peak_flops / hbm_bandwidth`` is memory-bound. ``cpu`` is the nominal
#: deterministic fixture row (100 GB/s).
HBM_BW_TABLE: dict[str, float] = {
    "v4": 1.228e12,
    "v5e": 0.819e12,
    "v5p": 2.765e12,
    "v6e": 1.640e12,
    "cpu": 100e9,
}

#: Per-chip HBM capacity (GB) by generation — flight-check go/no-go and the
#: telemetry HBM-headroom report share this. (``cpu``: nominal host-RAM
#: share, fixture row.)
HBM_GB_TABLE: dict[str, float] = {"v4": 32.0, "v5e": 16.0, "v5p": 95.0, "v6e": 32.0, "cpu": 16.0}

#: Per-core VMEM capacity (KiB) by generation — the on-chip vector memory
#: every ``pl.pallas_call`` block must fit in (double-buffered while the
#: grid pipeline is running). Published Pallas figures: ~16 MiB/core on
#: v4, ~128 MiB on v5e/v5p/v6e. The ``cpu`` row is a deliberately SMALL
#: nominal fixture (512 KiB) so kernel-check selfcheck fixtures can
#: overflow it with tiny deterministic blocks under ``JAX_PLATFORMS=cpu``.
VMEM_KB_TABLE: dict[str, float] = {
    "v4": 16384.0,
    "v5e": 131072.0,
    "v5p": 131072.0,
    "v6e": 131072.0,
    "cpu": 512.0,
}


def device_generation(device=None) -> Optional[str]:
    """Map a jax device (default: the first local device of an
    already-initialised backend) to a generation key of the tables above,
    or None when unknown (GPU backends, or jax not yet imported — this
    helper must never be the thing that initialises the backend). The CPU
    backend maps to the explicit ``cpu`` fixture row, so host-backend
    analysis output is deterministic rather than a silent v5e alias."""
    kind = None
    if device is not None:
        kind = str(getattr(device, "device_kind", device))
    else:
        import sys

        jax = sys.modules.get("jax")
        if jax is None:
            return None
        try:
            kind = str(getattr(jax.devices()[0], "device_kind", ""))
        except Exception:
            return None
    kind = kind.lower()
    # longest-match so "v5p" never matches a "v5e" row and vice versa
    for gen in sorted(PEAK_FLOPS_TABLE, key=len, reverse=True):
        if gen in kind:
            return gen
    if "v5litepod" in kind or "v5 lite" in kind:
        return "v5e"
    return None


def peak_flops(generation: str, dtype: str = "bf16") -> float:
    """Peak FLOP/s per device for ``generation`` (a key of
    ``PEAK_FLOPS_TABLE``; ``cpu`` has its own explicit nominal row). A
    generation the table does not know is an error: a utilisation priced
    against some other chip's peak is a wrong number, not a conservative
    one. Offline analyzers that want a default name one (``"v5e"``)."""
    try:
        row = PEAK_FLOPS_TABLE[generation]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s known for generation {generation!r} (known: {sorted(PEAK_FLOPS_TABLE)})"
        ) from None
    return row.get(dtype, row["bf16"])


def hbm_bandwidth(generation: str) -> float:
    """HBM bytes/second per device for ``generation``; raises for one the
    table does not know (explicit ``cpu`` row for the host backend)."""
    try:
        return HBM_BW_TABLE[generation]
    except KeyError:
        raise ValueError(
            f"no HBM bandwidth known for generation {generation!r} (known: {sorted(HBM_BW_TABLE)})"
        ) from None


def vmem_bytes(generation: str) -> int:
    """Per-core VMEM capacity in bytes for ``generation`` (v5e fallback
    for unknown generations, explicit nominal ``cpu`` fixture row)."""
    return int(VMEM_KB_TABLE.get(generation, VMEM_KB_TABLE["v5e"]) * 1024)

#: Collectives the traffic walk prices. Maps primitive name -> wire-bytes
#: multiplier ``f(n)`` applied to the (per-device) operand bytes ``B`` for
#: an axis group of size ``n``, from the standard ring algorithms:
#: all-reduce moves ``2(n-1)/n * B``, all-gather / reduce-scatter move
#: ``(n-1)/n`` of the gathered/scattered total, a permute moves ``B``.
_WIRE_FACTORS = {
    "psum": lambda n: 2.0 * (n - 1) / n,
    "pmean": lambda n: 2.0 * (n - 1) / n,
    "pmax": lambda n: 2.0 * (n - 1) / n,
    "pmin": lambda n: 2.0 * (n - 1) / n,
    "all_gather": lambda n: float(n - 1),  # B is the per-shard input
    "all_to_all": lambda n: (n - 1) / n,
    "psum_scatter": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,
    "pshuffle": lambda n: 1.0,
}

COLLECTIVE_PRIMS = frozenset(_WIRE_FACTORS)

#: n->inf limits of the ring factors ON THE TOTAL PAYLOAD (the
#: convention :func:`ring_wire_bytes` prices): an all-reduce tends to 2
#: payload transfers, reduce-scatter / all-gather / all-to-all to 1, a
#: permute is always 1. Stated by hand (not computed) so the historical
#: asymptotic accounting in ``parallel.compression.wire_bytes`` stays
#: exact integers.
_WIRE_FACTOR_LIMITS = {
    "psum": 2.0,
    "pmean": 2.0,
    "pmax": 2.0,
    "pmin": 2.0,
    "all_gather": 1.0,
    "all_to_all": 1.0,
    "psum_scatter": 1.0,
    "reduce_scatter": 1.0,
    "ppermute": 1.0,
    "pshuffle": 1.0,
}


def ring_wire_bytes(prim_name: str, total_bytes: int, n: Optional[int] = None) -> int:
    """Per-device ring wire bytes for ``prim_name`` moving/reducing a
    TOTAL payload of ``total_bytes`` over an ``n``-group — THE shared
    formula: ``parallel.compression.wire_bytes`` and the telemetry HLO
    wire counter both delegate here, so the units of truth cannot drift
    from :data:`_WIRE_FACTORS` (which price the jaxpr *operand*: note the
    all_gather operand there is the per-shard input, ``total/n``).

    ``n=None`` is the large-``n`` limit (:data:`_WIRE_FACTOR_LIMITS`) —
    the mesh-independent accounting the compression docs quote."""
    if n is None:
        return int(round(total_bytes * _WIRE_FACTOR_LIMITS[prim_name]))
    if n <= 1:
        return 0
    factor = _WIRE_FACTORS[prim_name]
    # _WIRE_FACTORS operand conventions: all_gather takes the per-shard
    # input; everything else takes the full payload
    if prim_name == "all_gather":
        return int(round((total_bytes / n) * factor(n)))
    return int(round(total_bytes * factor(n)))


@dataclass
class CollectiveRecord:
    """One collective site in the traced step, priced.

    ``count`` folds in enclosing ``scan`` trip counts (a psum inside a
    length-``K`` scan fires ``K`` times per step); ``bytes_per_call`` is
    the operand bytes moved per firing, ``wire_bytes`` the per-step ring
    traffic after the collective's wire factor.
    """

    primitive: str
    axes: tuple[str, ...]
    group_size: int
    transport: str  # "ici" | "dcn" (dcn wins when any axis crosses it)
    bytes_per_call: int
    wire_bytes: int
    count: int = 1
    location: str = ""

    def time_us(self, generation: str = "v5e") -> float:
        bw = BANDWIDTH_TABLE.get(generation, BANDWIDTH_TABLE["v5e"])[self.transport]
        return self.wire_bytes / bw * 1e6


@dataclass
class TrafficReport:
    """Per-step collective traffic, summed."""

    records: list[CollectiveRecord] = field(default_factory=list)

    @property
    def total_wire_bytes(self) -> int:
        return sum(r.wire_bytes for r in self.records)

    def bytes_by_transport(self) -> dict[str, int]:
        out = {ICI: 0, DCN: 0}
        for r in self.records:
            out[r.transport] += r.wire_bytes
        return out

    def time_us(self, generation: str = "v5e") -> float:
        return sum(r.time_us(generation) for r in self.records)


def _aval_bytes(aval) -> int:
    import numpy as np

    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        # extended dtypes (PRNG keys) aren't numpy dtypes; they expose
        # itemsize directly (or contribute nothing to the byte model)
        itemsize = int(getattr(dtype, "itemsize", 0) or 0)
    return int(np.prod(shape or (1,))) * itemsize


def _axis_group_size(mesh, axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        n *= int(mesh.shape.get(a, 1))
    return n


def price_collective(
    prim_name: str,
    axes: Sequence[str],
    operand_bytes: int,
    mesh,
    *,
    count: int = 1,
    dcn: Optional[Sequence[str]] = None,
    location: str = "",
) -> Optional[CollectiveRecord]:
    """Price one collective site; ``None`` for unknown primitives or
    trivial (size-1) axis groups, which move no bytes."""
    factor = _WIRE_FACTORS.get(prim_name)
    if factor is None:
        return None
    axes = tuple(a for a in axes if isinstance(a, str))
    n = _axis_group_size(mesh, axes)
    if n <= 1:
        return None
    transports = {axis_transport(mesh, a, dcn) for a in axes if mesh.shape.get(a, 1) > 1}
    transport = DCN if DCN in transports else ICI
    wire = int(round(operand_bytes * factor(n))) * count
    return CollectiveRecord(
        primitive=prim_name,
        axes=axes,
        group_size=n,
        transport=transport,
        bytes_per_call=operand_bytes,
        wire_bytes=wire,
        count=count,
        location=location,
    )


def reshard_cost(global_bytes: int, mesh_shape: dict, dcn: Optional[Sequence[str]] = None) -> dict:
    """Wire bytes to re-gather one global array onto a mesh of
    ``mesh_shape`` (a plain ``{axis: size}`` dict — no jax needed), split
    into the two stages of a hierarchical ring all-gather: an ICI stage
    within each slice and a DCN stage across slices. This is the upper
    bound the elastic checkpoint restore pays when a checkpoint written
    on one topology is loaded onto another (``ft.topology.predict_reshard``)
    — each device re-gathers the full array then keeps its new shard;
    overlapping source/target layouts move less.

    Ring formula per stage: ``B * (n - 1) / n`` for stage fan-in ``n``
    (the all-gather row of ``_WIRE_FACTORS`` applied to per-shard bytes
    ``B / n``). Trivial stages (fan-in 1) move nothing."""
    dcn_names = tuple(dcn or ())
    n_ici = n_dcn = 1
    for axis, size in (mesh_shape or {}).items():
        if int(size) <= 1:
            continue
        if axis in dcn_names:
            n_dcn *= int(size)
        else:
            n_ici *= int(size)
    ici = int(round(global_bytes * (n_ici - 1) / n_ici)) if n_ici > 1 else 0
    dcn_bytes = int(round(global_bytes * (n_dcn - 1) / n_dcn)) if n_dcn > 1 else 0
    return {ICI: ici, DCN: dcn_bytes}


def price_kv_handoff(
    bytes_per_token: int,
    tokens: int,
    *,
    fixed_bytes: int = 0,
    transport: str = ICI,
    generation: str = "v5e",
) -> dict:
    """Price one prefill→decode KV-block handoff BEFORE it happens — the
    fleet router's decision input (the ``reshard_cost`` pattern applied
    to serving): a disaggregated prefill replica ships ``tokens`` rows of
    per-layer K/V (``bytes_per_token`` each, plus ``fixed_bytes`` of
    per-cache constants like write indices) to a decode replica over
    ``transport`` (``"ici"`` within a slice / host, ``"dcn"`` across).
    Returns ``{"bytes", "time_us", "transport"}``; plain host math, no
    jax — the router's accounting and this prediction must agree
    byte-for-byte."""
    if transport not in (ICI, DCN):
        raise ValueError(f"transport must be {ICI!r}|{DCN!r}, got {transport!r}")
    total = int(bytes_per_token) * int(tokens) + int(fixed_bytes)
    bw = BANDWIDTH_TABLE.get(generation, BANDWIDTH_TABLE["v5e"])[transport]
    return {"bytes": int(total), "time_us": total / bw * 1e6, "transport": transport}


def prefill_compute_us(
    param_count: int, tokens: int, *, generation: str = "v5e", dtype: str = "bf16"
) -> float:
    """Roofline lower bound for (re)prefilling ``tokens`` through a
    ``param_count``-parameter decoder: ``2·P·T`` MACs-as-FLOPs over the
    generation's peak — the router's *alternative* cost when deciding a
    KV handoff vs re-prefilling locally on the decode replica. A lower
    bound is the honest comparator here: if the handoff beats even the
    best-case local prefill, shipping the blocks wins for sure."""
    return 2.0 * int(param_count) * int(tokens) / peak_flops(generation, dtype) * 1e6


def price_failover(
    bytes_per_token: int,
    prompt_tokens: int,
    generated_tokens: int,
    param_count: int,
    *,
    fixed_bytes: int = 0,
    transport: str = ICI,
    generation: str = "v5e",
    dtype: str = "bf16",
    kv_exportable: bool = True,
) -> dict:
    """Price BOTH legs of migrating one in-flight request off a failing
    replica BEFORE the router moves anything — the fleet failover
    decision input: ship the request's exact KV frontier (``prompt +
    generated - 1`` rows; the last generated token is re-fed, its row not
    yet written) over ``transport``, or recompute the same rows on the
    survivor (the PR-10 resume path). Returns ``{"rows", "handoff"
    (a :func:`price_kv_handoff` dict), "recompute_us", "path"}`` with
    ``path`` the cheaper leg — forced to ``"recompute"`` when the dying
    replica cannot export (``kv_exportable=False``: poisoned numerics, or
    a paged layout with no dense row export). Plain host
    math, no jax; when the handoff leg runs, the router's post-migration
    byte accounting must equal ``handoff["bytes"]`` exactly."""
    rows = max(1, int(prompt_tokens) + max(0, int(generated_tokens) - 1))
    pred = price_kv_handoff(
        bytes_per_token, rows, fixed_bytes=fixed_bytes,
        transport=transport, generation=generation,
    )
    alt = prefill_compute_us(param_count, rows, generation=generation, dtype=dtype)
    if not kv_exportable or pred["time_us"] > alt:
        path = "recompute"
    else:
        path = "handoff"
    return {"rows": rows, "handoff": pred, "recompute_us": alt, "path": path}


def collect_traffic(jaxpr, mesh, *, dcn: Optional[Sequence[str]] = None) -> TrafficReport:
    """Walk ``jaxpr`` (recursing through pjit/shard_map/control flow) and
    price every explicit collective. ``scan`` bodies multiply the firing
    count by the trip count; ``while`` bodies count once (the trip count is
    value-dependent — and a collective there is a TPU301 finding anyway)."""
    from .jaxpr_lint import _axis_names_in_params, _eqn_location, _iter_subjaxprs

    records: list[CollectiveRecord] = []

    def walk(jx, multiplier: int):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in COLLECTIVE_PRIMS:
                axes = tuple(_axis_names_in_params(eqn.params))
                operand = sum(_aval_bytes(v.aval) for v in eqn.invars if hasattr(v, "aval"))
                rec = price_collective(
                    name, axes, operand, mesh,
                    count=multiplier, dcn=dcn, location=_eqn_location(eqn).strip(),
                )
                if rec is not None:
                    records.append(rec)
            sub_mult = multiplier
            if name == "scan":
                sub_mult = multiplier * int(eqn.params.get("length", 1) or 1)
            for sub in _iter_subjaxprs(eqn.params):
                walk(sub, sub_mult)

    walk(jaxpr, 1)
    return TrafficReport(records=records)
