"""Tier-1 jaxpr analysis: trace a step function and check TPU invariants
against the active ``jax.sharding.Mesh`` *before* paying a multi-chip
compile.

``lint_step(fn, *sample_args, mesh=...)`` traces ``fn`` with
``jax.make_jaxpr`` (abstract — nothing executes, nothing compiles) and
walks every equation, recursing through ``pjit`` / ``shard_map`` /
control-flow sub-jaxprs:

* ``TPU101`` — a ``psum``/``pmean``/``all_gather``/``ppermute``/… names a
  mesh axis that does not exist. Caught two ways: axis names carried in
  equation params are checked against the mesh, and the trace-time
  ``NameError: unbound axis name`` jax raises for free-standing
  collectives is converted into a finding when the name is not a mesh
  axis (when it *is* one, the trace is retried inside a replicated
  ``shard_map`` that binds the mesh axes).
* ``TPU102`` — a bf16/fp8 value silently widens to f32/f64 somewhere in
  the graph (equation with a low-precision input and a wide float
  output). On TPU this doubles the HBM and ICI bytes of the tensor from
  that point on.
* ``TPU103`` — donation advisor: an argument whose leaves all have
  shape/dtype-identical counterparts among the outputs (the
  read-and-replace pattern of params/opt state) but is not in
  ``donate_argnums`` — the buffer is kept live across the step for no
  reason, doubling its HBM footprint.
* ``TPU104`` — a mesh axis the *inputs* are sharded over never appears in
  any sharding annotation (``with_sharding_constraint``, ``pjit``
  out-shardings, ``shard_map`` out-names) anywhere in the graph, leaving
  the output layout entirely to GSPMD's propagation pass.

jax is imported lazily — importing this module must work (and stay cheap)
where no backend exists; analysis needs only abstract values.
"""

from __future__ import annotations

import re
from typing import Any, Optional, Sequence

from .rules import Finding, filter_findings

_LOW_DTYPES = (
    "bfloat16",
    "float8_e4m3fn",
    "float8_e5m2",
    "float8_e4m3b11fnuz",
    "float8_e4m3fnuz",
    "float8_e5m2fnuz",
)
_WIDE_DTYPES = ("float32", "float64")

COLLECTIVE_PRIMS = frozenset(
    {"psum", "pmean", "pmax", "pmin", "ppermute", "pshuffle", "all_gather", "all_to_all", "psum_scatter", "reduce_scatter", "axis_index"}
)
_COLLECTIVE_PRIMS = COLLECTIVE_PRIMS  # historical private alias

_UNBOUND_AXIS_RE = re.compile(r"unbound axis name:?\s*([\w\-]+)")


def _jax():
    import jax

    return jax


# -- jaxpr plumbing -------------------------------------------------------


def _iter_subjaxprs(params: dict):
    """Yield every (Closed)Jaxpr nested in an equation's params —
    pjit/shard_map bodies, scan/while/cond branches."""
    from jax.extend import core

    def coerce(v):
        if isinstance(v, core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for item in v:
                yield from coerce(item)

    for v in params.values():
        yield from coerce(v)


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _iter_subjaxprs(eqn.params):
            yield from _walk_eqns(sub)


def _eqn_location(eqn) -> str:
    try:
        from jax._src import source_info_util

        loc = source_info_util.summarize(eqn.source_info)
        return f" at {loc}" if loc else ""
    except Exception:
        return ""


def _axis_names_in_params(params: dict) -> list[str]:
    names: list[str] = []
    for key in ("axes", "axis_name"):
        val = params.get(key)
        if isinstance(val, str):
            names.append(val)
        elif isinstance(val, (tuple, list)):
            names.extend(v for v in val if isinstance(v, str))
    return names


def _spec_axes(spec) -> set[str]:
    """Mesh axis names mentioned in a PartitionSpec-like object."""
    axes: set[str] = set()
    for entry in tuple(spec or ()):
        if isinstance(entry, str):
            axes.add(entry)
        elif isinstance(entry, (tuple, list)):
            axes.update(e for e in entry if isinstance(e, str))
    return axes


def _sharding_axes(obj) -> set[str]:
    spec = getattr(obj, "spec", None)
    if spec is not None:
        return _spec_axes(spec)
    if obj is not None and type(obj).__name__ == "PartitionSpec":
        return _spec_axes(obj)
    return set()


def _strings_in(tree) -> set[str]:
    out: set[str] = set()
    if isinstance(tree, str):
        out.add(tree)
    elif isinstance(tree, dict):
        for v in tree.values():
            out |= _strings_in(v)
    elif isinstance(tree, (tuple, list)):
        for v in tree:
            out |= _strings_in(v)
    return out


# -- tracing --------------------------------------------------------------


def _trace(fn, sample_args, mesh):
    """``(closed_jaxpr, findings)`` — trace ``fn``, converting trace-time
    unbound-axis errors into TPU101 findings; when the axis *is* a mesh
    axis, rebind by tracing inside a fully-replicated shard_map."""
    jax = _jax()
    mesh_axes = set(mesh.shape) if mesh is not None else set()

    def attempt(f):
        return jax.make_jaxpr(f)(*sample_args)

    try:
        return attempt(fn), []
    except NameError as e:
        m = _UNBOUND_AXIS_RE.search(str(e))
        if m is None:
            raise
        axis = m.group(1)
        if axis not in mesh_axes:
            return None, [
                Finding(
                    "TPU101",
                    f"collective references axis {axis!r} which is not a mesh axis "
                    f"(mesh axes: {sorted(mesh_axes)})",
                )
            ]
    # the axis exists — the function is written shard_map-style; bind the
    # mesh axes with a replicated wrap and re-trace
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    wrapped = shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(), check_rep=False)
    try:
        return attempt(wrapped), []
    except NameError as e:
        m = _UNBOUND_AXIS_RE.search(str(e))
        if m is None:
            raise
        return None, [
            Finding(
                "TPU101",
                f"collective references axis {m.group(1)!r} which is not a mesh axis "
                f"(mesh axes: {sorted(mesh_axes)})",
            )
        ]


# -- per-rule passes ------------------------------------------------------


def _check_collective_axes(closed, mesh) -> list[Finding]:
    findings = []
    mesh_axes = set(mesh.shape)
    seen = set()
    for eqn in _walk_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name not in _COLLECTIVE_PRIMS:
            continue
        for axis in _axis_names_in_params(eqn.params):
            if axis not in mesh_axes and (name, axis) not in seen:
                seen.add((name, axis))
                findings.append(
                    Finding(
                        "TPU101",
                        f"{name} over axis {axis!r} which is not a mesh axis "
                        f"(mesh axes: {sorted(mesh_axes)}){_eqn_location(eqn)}",
                    )
                )
    return findings


def _var_dtype(v) -> str:
    return str(getattr(getattr(v, "aval", None), "dtype", ""))


def _is_literal(v) -> bool:
    return type(v).__name__ == "Literal"


def _escapes_wide(start_vars, consumers, out_set) -> bool:
    """Does a wide (f32/f64) value reach the jaxpr outputs without being
    converted back down? jnp reductions legitimately widen bf16 for
    accumulation and immediately narrow again — that transient f32 region
    is not a finding; one that escapes (or enters a sub-computation) is."""
    stack = list(start_vars)
    seen = set()
    while stack:
        v = stack.pop()
        if v in seen:
            continue
        seen.add(v)
        if v in out_set:
            return True
        for eqn in consumers.get(v, ()):
            if eqn.primitive.name == "convert_element_type" and all(
                _var_dtype(o) not in _WIDE_DTYPES for o in eqn.outvars
            ):
                continue  # narrowed back — taint dies here
            if any(True for _ in _iter_subjaxprs(eqn.params)):
                return True  # conservatively: wide value enters a sub-jaxpr
            stack.extend(o for o in eqn.outvars if _var_dtype(o) in _WIDE_DTYPES)
    return False


def _check_dtype_promotion(closed) -> list[Finding]:
    findings = []
    seen = set()

    def analyze(jaxpr):
        consumers: dict = {}
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not _is_literal(v):
                    consumers.setdefault(v, []).append(eqn)
        out_set = {v for v in jaxpr.outvars if not _is_literal(v)}
        for eqn in jaxpr.eqns:
            subs = list(_iter_subjaxprs(eqn.params))
            if subs:  # call eqns aren't origins — the inner analysis reports them
                for sub in subs:
                    analyze(sub)
                continue
            low = sorted({_var_dtype(v) for v in eqn.invars} & set(_LOW_DTYPES))
            wide_outs = [v for v in eqn.outvars if _var_dtype(v) in _WIDE_DTYPES]
            if low and wide_outs and _escapes_wide(wide_outs, consumers, out_set):
                key = (eqn.primitive.name, low[0], _var_dtype(wide_outs[0]), _eqn_location(eqn))
                if key not in seen:
                    seen.add(key)
                    findings.append(
                        Finding(
                            "TPU102",
                            f"{eqn.primitive.name} promotes {low[0]} -> {_var_dtype(wide_outs[0])}"
                            f"{_eqn_location(eqn)} and the widened value escapes; if unintended, "
                            "keep the computation low-precision (check mixed operands and "
                            "preferred_element_type)",
                        )
                    )

    analyze(closed.jaxpr)
    return findings


def _leaf_shape_dtypes(arg) -> list[tuple[tuple, str]]:
    jax = _jax()
    keys = []
    for leaf in jax.tree_util.tree_leaves(arg):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        keys.append((tuple(shape), str(dtype)))
    return keys


def _check_donation(closed, sample_args, donate_argnums, min_bytes) -> list[Finding]:
    import numpy as np

    out_pool: dict[tuple, int] = {}
    for aval in closed.out_avals:
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            continue
        key = (tuple(shape), str(dtype))
        out_pool[key] = out_pool.get(key, 0) + 1

    findings = []
    donated = set(donate_argnums)
    for i, arg in enumerate(sample_args):
        if i in donated:
            continue
        keys = _leaf_shape_dtypes(arg)
        if not keys:
            continue
        nbytes = sum(int(np.prod(s or (1,))) * np.dtype(d).itemsize for s, d in keys)
        if nbytes < min_bytes:
            continue
        pool = dict(out_pool)
        for key in keys:
            if pool.get(key, 0) <= 0:
                break
            pool[key] -= 1
        else:
            findings.append(
                Finding(
                    "TPU103",
                    f"argument {i} ({nbytes:,} bytes) is read and replaced by a "
                    "shape/dtype-identical output but not donated; pass "
                    f"donate_argnums=({i},) to jit so XLA reuses the buffer in place",
                )
            )
    return findings


def _collect_spec_axes(tree) -> set[str]:
    """Axes from a user-supplied pytree of PartitionSpec/NamedSharding.
    PartitionSpec subclasses tuple, so recurse by hand rather than through
    tree_util (which would flatten the spec itself)."""
    if tree is None:
        return set()
    if type(tree).__name__ in ("PartitionSpec",) or hasattr(tree, "spec"):
        return _sharding_axes(tree)
    if isinstance(tree, dict):
        return set().union(*(_collect_spec_axes(v) for v in tree.values())) if tree else set()
    if isinstance(tree, (tuple, list)):
        return set().union(*(_collect_spec_axes(v) for v in tree)) if tree else set()
    return set()


def _input_spec_axes(sample_args, in_shardings, mesh) -> set[str]:
    jax = _jax()
    axes = _collect_spec_axes(in_shardings)
    for arg in sample_args:
        for leaf in jax.tree_util.tree_leaves(arg):
            axes |= _sharding_axes(getattr(leaf, "sharding", None))
    return {a for a in axes if mesh.shape.get(a, 1) > 1}


def _check_output_shardings(closed, sample_args, in_shardings, mesh) -> list[Finding]:
    input_axes = _input_spec_axes(sample_args, in_shardings, mesh)
    if not input_axes:
        return []
    annotated: set[str] = set()
    for eqn in _walk_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name == "sharding_constraint":
            annotated |= _sharding_axes(eqn.params.get("sharding"))
        elif name == "pjit":
            for s in tuple(eqn.params.get("out_shardings") or ()) + tuple(eqn.params.get("in_shardings") or ()):
                annotated |= _sharding_axes(s)
        elif name == "shard_map":
            annotated |= _strings_in(eqn.params.get("out_names")) & set(mesh.shape)
    findings = []
    for axis in sorted(input_axes - annotated):
        findings.append(
            Finding(
                "TPU104",
                f"inputs are sharded over mesh axis {axis!r} but no sharding constraint "
                "anywhere in the graph mentions it; add jax.lax.with_sharding_constraint "
                "(or jit out_shardings) so outputs don't silently gather/replicate",
            )
        )
    return findings


# -- entry point ----------------------------------------------------------


def lint_step(
    fn,
    *sample_args: Any,
    mesh=None,
    donate_argnums: Sequence[int] = (),
    in_shardings: Any = None,
    min_donation_bytes: int = 1024,
    select: Optional[Sequence[str]] = None,
    ignore: Sequence[str] = (),
) -> list[Finding]:
    """Trace ``fn(*sample_args)`` abstractly and return tier-1 findings.

    ``sample_args`` may be concrete arrays (their ``NamedSharding``s feed
    the TPU104 check), ``jax.ShapeDtypeStruct``s, or any pytree of either.
    ``mesh`` defaults to the ambient mesh from
    ``parallel.sharding.mesh_context`` when one is active.
    """
    if mesh is None:
        from ..parallel.sharding import context_mesh

        mesh = context_mesh()
    if mesh is None:
        raise ValueError("lint_step needs a mesh (pass mesh=... or enter parallel.sharding.mesh_context)")

    closed, findings = _trace(fn, sample_args, mesh)
    if closed is not None:
        findings = findings + _check_collective_axes(closed, mesh)
        findings += _check_dtype_promotion(closed)
        findings += _check_donation(closed, sample_args, donate_argnums, min_donation_bytes)
        findings += _check_output_shardings(closed, sample_args, in_shardings, mesh)
    return filter_findings(findings, select=select, ignore=ignore)
