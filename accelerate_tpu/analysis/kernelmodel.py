"""Pallas kernel model: extract every ``pl.pallas_call`` site from a
traced program and count what the kernel actually does.

The other analysis tiers price what XLA lowers; a pallas call is the one
equation whose cost XLA cannot report — so this module reads the call's
own metadata out of the jaxpr instead:

* the **grid** and per-operand **BlockSpecs** (block shape, backing array
  shape/dtype, indexing mode) from the equation's ``grid_mapping``;
* each block's **index map**, re-evaluated *concretely* per grid step
  (``jax.core.eval_jaxpr`` over the map's closed jaxpr — integer in,
  block index out), which is what lets ``kernel_rules`` prove coverage,
  overlap and alias-hazard facts rather than guess them;
* **input/output aliases** and the interpret flag;
* the **counted cost**: the kernel body jaxpr walked with perfmodel's
  nominal FLOP model (MXU dots exact, VPU weights nominal, ref
  get/swap free) times the grid size, plus the per-step block bytes
  times the grid size for HBM — the "interpret-mode count" a registered
  :class:`~accelerate_tpu.kernels.contracts.KernelCostSpec` declaration
  is checked against (TPU1006).

``kernel_check(fn, *sample_args, mesh=...)`` is the entry point (same
calling convention as ``flight_check``/``perf_check``); ``scan_paths``
is the AST-level registration scan behind ``kernel-check <paths>`` and
``--changed``. jax is imported lazily; extraction works on abstract
values only.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

from ..kernels.contracts import (
    KernelCostSpec,
    block_array_aval,
    eqn_kernel_name,
    pallas_in_avals,
    registered_spec,
)
from .rules import Finding, filter_findings

#: memory-ref primitives inside a kernel body: loads/stores, not FLOPs
_REF_PRIMS = frozenset(
    {"get", "swap", "addupdate", "load", "store", "masked_load", "masked_swap"}
)

#: grids larger than this are not enumerated concretely (TPU1003/1004
#: skip, recorded on the site) — the walk stays O(small)
MAX_ENUMERATED_GRID = 4096


def _prod(it) -> int:
    out = 1
    for v in it:
        out *= int(v)
    return out


def _human(n) -> str:
    n = float(n or 0)
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024:
            return f"{n:.1f} {unit}"
        n /= 1024
    return f"{n:.1f} PB"


@dataclass
class BlockInfo:
    """One operand's blocking: what the kernel sees per grid step."""

    origin: str  # BlockSpec origin name ("x_ref", "outputs", ...)
    block_shape: tuple  # per-step block (None entries = squeezed dims)
    array_shape: tuple  # the backing global array
    dtype: str
    block_bytes: int  # bytes of one block in VMEM
    index_map: Optional[Callable] = None  # (grid ints) -> block index tuple

    def blocks_per_dim(self) -> tuple[int, ...]:
        """ceil(array/block) per non-squeezed dim — the output block grid
        TPU1003's coverage check expects to be written exactly once."""
        out = []
        for arr, blk in zip(self.array_shape, self.block_shape):
            b = int(blk) if blk else 1
            out.append(-(-int(arr) // max(1, b)))
        return tuple(out)

    def as_dict(self) -> dict:
        return {
            "origin": self.origin,
            "block_shape": [None if b is None else int(b) for b in self.block_shape],
            "array_shape": [int(d) for d in self.array_shape],
            "dtype": self.dtype,
            "block_bytes": self.block_bytes,
        }


@dataclass
class KernelSite:
    """One traced ``pallas_call`` equation, fully extracted."""

    kernel_name: str
    location: str  # human location suffix (" (path:line)" style)
    path: Optional[str] = None  # user frame, for suppressions/SARIF
    line: Optional[int] = None
    grid: tuple = ()
    count: int = 1  # enclosing scan trip multiplier
    in_blocks: list[BlockInfo] = field(default_factory=list)
    out_blocks: list[BlockInfo] = field(default_factory=list)
    io_aliases: tuple = ()  # ((in_idx, out_idx), ...)
    interpret: bool = False
    dynamic_index_maps: bool = False  # scalar-prefetch operands present
    spec: Optional[KernelCostSpec] = None
    inner_jaxpr: Any = None
    in_avals: tuple = ()  # operand avals, pallas-call argument order

    @property
    def grid_steps(self) -> int:
        return _prod(self.grid) if self.grid else 1

    def as_dict(self) -> dict:
        flops, hbm = counted_cost(self)
        return {
            "kernel": self.kernel_name,
            "location": self.location.strip(),
            "grid": [int(g) for g in self.grid],
            "count": self.count,
            "registered": self.spec is not None,
            "interpret": self.interpret,
            "in_blocks": [b.as_dict() for b in self.in_blocks],
            "out_blocks": [b.as_dict() for b in self.out_blocks],
            "io_aliases": [list(p) for p in self.io_aliases],
            "vmem_occupancy_bytes": vmem_occupancy_bytes(self),
            "counted_flops": flops,
            "counted_hbm_bytes": hbm,
        }


# -- extraction -------------------------------------------------------------


def _index_map_fn(index_map_jaxpr, n_args: int) -> Optional[Callable]:
    """Concrete evaluator for one block index map: ``f(*grid_ints) ->
    tuple[int]`` via ``eval_jaxpr`` over the map's closed jaxpr. None when
    the map takes operands beyond the grid indices (scalar prefetch)."""
    closed = index_map_jaxpr
    if closed is None or len(closed.jaxpr.invars) != n_args:
        return None

    def run(*idx):
        import jax

        res = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *(int(i) for i in idx))
        return tuple(int(v) for v in res)

    return run


def _block_info(bm, n_grid: int) -> BlockInfo:
    aval = block_array_aval(bm)
    # block dims arrive wrapped: Blocked(n) / Element(n) carry the size,
    # Squeezed() is a dim the kernel does not see (None here)
    block_shape = tuple(
        int(b.block_size) if hasattr(b, "block_size") else (b if isinstance(b, int) else None)
        for b in getattr(bm, "block_shape", ()) or ()
    )
    array_shape = tuple(getattr(aval, "shape", ()) or ())
    dtype = str(getattr(aval, "dtype", ""))
    import numpy as np

    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        itemsize = 0
    block_numel = _prod(b for b in block_shape if b) if block_shape else 0
    return BlockInfo(
        origin=str(getattr(bm, "origin", "") or ""),
        block_shape=block_shape,
        array_shape=array_shape,
        dtype=dtype,
        block_bytes=block_numel * itemsize,
        index_map=_index_map_fn(getattr(bm, "index_map_jaxpr", None), n_grid),
    )


def _site_from_eqn(eqn, count: int) -> KernelSite:
    from .jaxpr_lint import _eqn_location
    from .perfmodel import eqn_path_line

    params = eqn.params
    gm = params.get("grid_mapping")
    grid = tuple(int(g) for g in getattr(gm, "grid", ()) or ())
    n_in = int(getattr(gm, "num_inputs", 0) or 0)
    n_out = int(getattr(gm, "num_outputs", 0) or 0)
    mappings = list(getattr(gm, "block_mappings", ()) or ())
    blocks = [_block_info(bm, len(grid)) for bm in mappings]
    aliases = params.get("input_output_aliases") or ()
    if isinstance(aliases, dict):
        aliases = tuple(sorted(aliases.items()))
    else:
        aliases = tuple(tuple(p) for p in aliases)
    path, line = eqn_path_line(eqn)
    name = eqn_kernel_name(params) or "<pallas_call>"
    return KernelSite(
        kernel_name=name,
        location=_eqn_location(eqn),
        path=path,
        line=line,
        grid=grid,
        count=count,
        in_blocks=blocks[:n_in],
        out_blocks=blocks[n_in : n_in + n_out],
        io_aliases=aliases,
        interpret=bool(params.get("interpret", False)),
        dynamic_index_maps=int(getattr(gm, "num_index_operands", 0) or 0) > 0,
        spec=registered_spec(name),
        inner_jaxpr=params.get("jaxpr"),
        in_avals=pallas_in_avals(params),
    )


def extract_kernel_sites(closed) -> list[KernelSite]:
    """Every ``pallas_call`` equation of the traced program (recursing
    through pjit/shard_map/control flow, multiplying ``scan`` bodies by
    their trip counts), in program order."""
    from .jaxpr_lint import _iter_subjaxprs

    sites: list[KernelSite] = []

    def walk(jx, multiplier: int):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                sites.append(_site_from_eqn(eqn, multiplier))
                continue  # the kernel body is the site's, not the program's
            sub_mult = multiplier
            if name == "scan":
                sub_mult = multiplier * int(eqn.params.get("length", 1) or 1)
            for sub in _iter_subjaxprs(eqn.params):
                walk(sub, sub_mult)

    walk(closed.jaxpr, 1)
    return sites


# -- the counted cost (what TPU1006 checks declarations against) ------------


def counted_flops_per_step(inner_jaxpr) -> int:
    """Nominal FLOPs of ONE grid step: the kernel body jaxpr walked with
    :func:`~accelerate_tpu.analysis.perfmodel.op_flops` — exact for MXU
    dots, nominal VPU weights elsewhere, ref get/swap free."""
    from .jaxpr_lint import _iter_subjaxprs
    from .perfmodel import op_flops

    total = 0

    def walk(jx, multiplier: int):
        nonlocal total
        for eqn in jx.eqns:
            name = eqn.primitive.name
            subs = list(_iter_subjaxprs(eqn.params))
            if subs:
                sub_mult = multiplier
                if name == "scan":
                    sub_mult = multiplier * int(eqn.params.get("length", 1) or 1)
                for sub in subs:
                    walk(sub, sub_mult)
                continue
            if name in _REF_PRIMS:
                continue
            total += op_flops(eqn) * multiplier

    if inner_jaxpr is not None:
        walk(inner_jaxpr, 1)
    return total


def counted_cost(site: KernelSite) -> tuple[int, int]:
    """(flops, hbm_bytes) of the whole call — per-step counts × grid
    steps × the enclosing scan multiplier. HBM is the block traffic the
    pipelined grid streams: every in/out block is fetched/written once
    per grid step (re-visited blocks stay resident in a real pipeline;
    this counts the naive upper bound the contract must also price)."""
    per_step_hbm = sum(b.block_bytes for b in site.in_blocks + site.out_blocks)
    flops = counted_flops_per_step(site.inner_jaxpr) * site.grid_steps * site.count
    hbm = per_step_hbm * site.grid_steps * site.count
    return flops, hbm


def vmem_occupancy_bytes(site: KernelSite) -> int:
    """The analyzer's VMEM occupancy model TPU1001 gates on: every in/out
    block resident at once, double-buffered while the grid pipeline has
    more than one step (Pallas prefetches step i+1's blocks while step i
    computes)."""
    blocks = sum(b.block_bytes for b in site.in_blocks + site.out_blocks)
    return blocks * (2 if site.grid_steps > 1 else 1)


# -- report + entry point ---------------------------------------------------


@dataclass
class KernelReport:
    """Everything ``kernel_check`` learns about one step function."""

    fn_name: str
    generation: str = "v5e"
    vmem_capacity_bytes: int = 0
    sites: list[KernelSite] = field(default_factory=list)
    findings: list[Finding] = field(default_factory=list)
    interpret_probe: str = "skipped"

    @property
    def ok(self) -> bool:
        return not any(f.is_error for f in self.findings)

    def as_dict(self) -> dict:
        return {
            "fn": self.fn_name,
            "generation": self.generation,
            "vmem_capacity_bytes": self.vmem_capacity_bytes,
            "interpret_probe": self.interpret_probe,
            "sites": [s.as_dict() for s in self.sites],
            "findings": [f.as_dict() for f in self.findings],
        }

    def render_text(self) -> str:
        lines = [
            f"kernel-check: {self.fn_name} — {len(self.sites)} pallas call"
            f"{'s' if len(self.sites) != 1 else ''}, {self.generation} VMEM "
            f"{_human(self.vmem_capacity_bytes)}/core"
        ]
        for s in self.sites:
            flops, hbm = counted_cost(s)
            occ = vmem_occupancy_bytes(s)
            reg = "registered" if s.spec is not None else "UNREGISTERED"
            count = f" x{s.count}" if s.count > 1 else ""
            lines.append(
                f"  {s.kernel_name}{count} grid={'x'.join(str(g) for g in s.grid) or '1'}"
                f" [{reg}]{s.location}"
            )
            lines.append(
                f"    VMEM occupancy {_human(occ)} (double-buffered blocks)"
                f"  counted {flops / 1e6:.2f} MFLOP, {_human(hbm)} hbm"
            )
            if s.spec is not None:
                try:
                    lines.append(
                        f"    declared {float(s.spec.flops(*s.in_avals)) / 1e6:.2f} MFLOP, "
                        f"{_human(s.spec.hbm_bytes(*s.in_avals))} hbm, "
                        f"VMEM peak {_human(s.spec.vmem_peak_bytes(*s.in_avals))}"
                    )
                except Exception as e:  # a broken spec is reported, not fatal
                    lines.append(f"    declared: spec raised {type(e).__name__}: {e}")
        lines.append(f"  interpret probe: {self.interpret_probe}")
        if self.findings:
            from .report import format_finding

            lines.append("  findings:")
            lines.extend(f"    {format_finding(f)}" for f in self.findings)
        else:
            lines.append("  findings: none")
        return "\n".join(lines)


def _materialize_tiny(sample_args):
    """Deterministic concrete arrays for the interpret probe."""
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


def interpret_probe(fn, sample_args, sites: Sequence[KernelSite]) -> str:
    """Run ``fn`` on tiny concrete operands when every site runs under
    Pallas interpret mode (CPU) and report output finiteness — the
    execution half of the verification teeth (the counting half is
    :func:`counted_cost`). Non-fatal by design: a probe that cannot run
    reports why instead of failing the check."""
    if not sites:
        return "skipped (no pallas calls)"
    if not all(s.interpret for s in sites):
        return "skipped (compiled kernel: not every site is interpret-mode)"
    try:
        import jax
        import numpy as np

        out = fn(*_materialize_tiny(sample_args))
        leaves = jax.tree_util.tree_leaves(out)
        bad = sum(
            int(np.logical_not(np.isfinite(np.asarray(leaf))).sum())
            for leaf in leaves
            if np.issubdtype(np.asarray(leaf).dtype, np.floating)
        )
        if bad:
            return f"ran: {bad} non-finite output element(s)"
        return "ran: outputs finite"
    except Exception as e:
        return f"failed: {type(e).__name__}: {e}"


def kernel_check(
    fn,
    *sample_args: Any,
    mesh=None,
    generation: Optional[str] = None,
    select: Optional[Sequence[str]] = None,
    ignore: Sequence[str] = (),
    probe: bool = True,
    rules: bool = True,
) -> KernelReport:
    """Trace ``fn(*sample_args)`` abstractly and return a
    :class:`KernelReport` — every pallas site extracted plus the
    TPU1001–1006 findings. Same calling convention as
    :func:`~accelerate_tpu.analysis.flightcheck.flight_check`;
    ``generation=None`` resolves the attached backend (explicit ``cpu``
    VMEM fixture row under ``JAX_PLATFORMS=cpu``)."""
    if mesh is None:
        from ..parallel.sharding import context_mesh

        mesh = context_mesh()
    if mesh is None:
        raise ValueError(
            "kernel_check needs a mesh (pass mesh=... or enter parallel.sharding.mesh_context)"
        )
    if generation is None:
        from .costmodel import device_generation

        generation = device_generation() or "v5e"
    from .costmodel import vmem_bytes
    from .jaxpr_lint import _trace

    name = getattr(fn, "__name__", "step_fn")
    closed, findings = _trace(fn, sample_args, mesh)
    report = KernelReport(
        fn_name=name, generation=generation, vmem_capacity_bytes=vmem_bytes(generation)
    )
    if closed is not None:
        report.sites = extract_kernel_sites(closed)
        if rules:
            from .kernel_rules import check_kernel_rules

            findings = findings + check_kernel_rules(report.sites, generation=generation)
        if probe:
            report.interpret_probe = interpret_probe(fn, sample_args, report.sites)
    from .perfmodel import _apply_inline_suppressions

    findings = _apply_inline_suppressions(findings)
    report.findings = filter_findings(findings, select=select, ignore=ignore)
    return report


# -- AST registration scan (paths mode / --changed) -------------------------


def _call_kernel_name(call: ast.Call) -> Optional[str]:
    """The kernel argument's name at a ``pallas_call`` call site: the
    first positional arg (or ``kernel=`` keyword) when it is a plain
    name/attribute/partial-of-name; None for dynamic expressions."""
    node = call.args[0] if call.args else None
    for kw in call.keywords:
        if kw.arg == "kernel":
            node = kw.value
    if isinstance(node, ast.Call):  # functools.partial(kernel_fn, ...) et al.
        node = node.args[0] if node.args else None
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def scan_paths(paths: Sequence[str]) -> list[Finding]:
    """AST scan for unregistered ``pallas_call`` sites (TPU1005) in
    ``paths`` (files or directories). This is the cheap registration
    gate ``--changed`` scopes: it proves every kernel in the diff carries
    a contract; the traced :func:`kernel_check` proves the contract is
    *right*. Import side effects are trusted to have registered the
    specs (the tree's kernels register at import via the decorator), so
    the scan imports nothing itself."""
    files: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, names in os.walk(p):
                files += [os.path.join(root, n) for n in sorted(names) if n.endswith(".py")]
        elif p.endswith(".py"):
            files.append(p)
    findings: list[Finding] = []
    for path in sorted(set(files)):
        try:
            with open(path) as fh:
                src = fh.read()
            tree = ast.parse(src, filename=path)
        except (OSError, SyntaxError):
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            fname = func.attr if isinstance(func, ast.Attribute) else getattr(func, "id", "")
            if fname != "pallas_call":
                continue
            kname = _call_kernel_name(node)
            if kname is not None and registered_spec(kname) is not None:
                continue
            label = kname or "<dynamic kernel expression>"
            findings.append(
                Finding(
                    "TPU1005",
                    f"pallas_call of `{label}` has no registered KernelCostSpec — "
                    "perfmodel/flight-check/numerics price it as zero; register a "
                    "contract with accelerate_tpu.kernels.kernel_cost",
                    path=path,
                    line=node.lineno,
                )
            )
    from .rules import apply_suppressions

    by_path: dict[str, list[Finding]] = {}
    for f in findings:
        by_path.setdefault(f.path, []).append(f)
    kept: list[Finding] = []
    for path, group in by_path.items():
        try:
            with open(path) as fh:
                lines = fh.read().splitlines()
        except OSError:
            kept.extend(group)
            continue
        kept.extend(apply_suppressions(group, lines))
    return kept
