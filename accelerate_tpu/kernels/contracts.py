"""Registered kernel cost contracts: the declared FLOPs/HBM-bytes/VMEM
of every Pallas kernel the analysis stack is allowed to see.

XLA can tell the static analyzers the cost of every op it lowers — a
``pl.pallas_call`` is the one thing it cannot. Before this module, every
tier quietly priced a pallas call at zero: perfmodel rooflines missed its
FLOPs, flight-check missed its working set, numerics went to ⊤ through
it. A :class:`KernelCostSpec` is the hand-declared contract that closes
the hole — FLOPs, HBM bytes and VMEM peak *as functions of the operand
avals* (so one registration covers every shape), plus an optional
interval transfer so the numerics tier can keep proving bounds through
the call.

The contract is **checked, not trusted**: ``accelerate-tpu kernel-check``
re-counts the kernel's FLOPs/bytes by walking its inner jaxpr under the
same nominal model perfmodel uses (the interpret-mode count) and fires
TPU1006 when the declaration drifts beyond ``tolerance``; an unregistered
pallas call in a checked program is TPU1005 — blindness is a lint
failure, never silence.

Registration is keyed by the *kernel body function's name* (what
``pl.pallas_call`` stamps into the traced equation's
``name_and_src_info``), so the analyzers can resolve a spec from a jaxpr
alone. This module is deliberately stdlib-only — the AST tier and the
registry lookups must work where jax is not importable.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Callable, Optional, Sequence


class UnknownOpWarning(UserWarning):
    """An analysis walk met an opaque primitive it cannot price."""


@dataclass(frozen=True)
class KernelCostSpec:
    """The declared cost contract of one Pallas kernel.

    ``flops``/``hbm_bytes``/``vmem_peak_bytes`` are called with the
    kernel operands' avals (anything with ``.shape``/``.dtype``) in
    pallas-call argument order and return the *per-call* totals over the
    whole grid. ``interval`` (optional) maps the operand value intervals
    — a list of ``(lo, hi)`` tuples — to the output's ``(lo, hi)`` so
    the numerics abstract interpretation continues through the call
    instead of going to ⊤. ``tolerance`` is the relative disagreement
    with the interpret-mode jaxpr-walk count that TPU1006 permits.
    """

    name: str
    flops: Callable[..., float]
    hbm_bytes: Callable[..., float]
    vmem_peak_bytes: Callable[..., float]
    interval: Optional[Callable[[Sequence[tuple]], tuple]] = None
    tolerance: float = 0.25
    notes: str = ""


#: kernel body function name -> its registered contract
KERNEL_REGISTRY: dict[str, KernelCostSpec] = {}


def register_kernel_cost(spec: KernelCostSpec) -> KernelCostSpec:
    """Register ``spec`` (latest registration wins; returns the spec)."""
    KERNEL_REGISTRY[spec.name] = spec
    return spec


def kernel_cost(
    *,
    flops: Callable[..., float],
    hbm_bytes: Callable[..., float],
    vmem_peak_bytes: Callable[..., float],
    interval: Optional[Callable[[Sequence[tuple]], tuple]] = None,
    tolerance: float = 0.25,
    notes: str = "",
) -> Callable:
    """Decorator form of :func:`register_kernel_cost` for the kernel BODY
    function (the first argument of ``pl.pallas_call`` — its ``__name__``
    is what the traced equation carries)::

        @kernel_cost(flops=lambda x, w: ..., hbm_bytes=..., vmem_peak_bytes=...)
        def my_kernel(x_ref, w_ref, o_ref): ...
    """

    def wrap(fn):
        register_kernel_cost(
            KernelCostSpec(
                name=fn.__name__,
                flops=flops,
                hbm_bytes=hbm_bytes,
                vmem_peak_bytes=vmem_peak_bytes,
                interval=interval,
                tolerance=tolerance,
                notes=notes,
            )
        )
        return fn

    return wrap


def registered_spec(name: Optional[str]) -> Optional[KernelCostSpec]:
    """The contract registered for kernel ``name``, or None."""
    if not name:
        return None
    return KERNEL_REGISTRY.get(name)


def unregister_kernel_cost(name: str) -> None:
    """Drop a registration (test hygiene for deliberately-broken specs)."""
    KERNEL_REGISTRY.pop(name, None)


def eqn_kernel_name(params: dict) -> Optional[str]:
    """The kernel body function name a traced ``pallas_call`` equation
    carries, or None: ``name_and_src_info.name`` (jax <= 0.8), the
    ``name=`` the call was given, else the body jaxpr's
    ``debug_info.func_name`` (jax 0.9). Works on the params dict alone —
    no jax import. With :func:`pallas_in_avals`, the one place that knows
    how jax shapes a ``pallas_call``'s params."""
    name = getattr(params.get("name_and_src_info"), "name", None) or params.get("name")
    if not name:
        debug_info = getattr(params.get("jaxpr"), "debug_info", None)
        name = getattr(debug_info, "func_name", None)
    return str(name) if name else None


def block_array_aval(bm):
    """The whole-array aval one ``BlockMapping`` tiles (``array_aval`` on
    jax 0.9, ``array_shape_dtype`` before), or None."""
    aval = getattr(bm, "array_aval", None)
    return aval if aval is not None else getattr(bm, "array_shape_dtype", None)


def pallas_in_avals(params: dict) -> tuple:
    """The pallas call's operand avals (``ShapeDtypeStruct``-likes) in
    argument order, read off the traced equation's ``grid_mapping`` — the
    arguments every :class:`KernelCostSpec` cost function is called with.
    getattr-only: works on the params dict, no jax import."""
    gm = params.get("grid_mapping")
    n_in = int(getattr(gm, "num_inputs", 0) or 0)
    mappings = list(getattr(gm, "block_mappings", ()) or ())
    return tuple(block_array_aval(bm) for bm in mappings[:n_in])


# -- satellite: audible blindness ------------------------------------------

_WARNED_UNKNOWN: set = set()


def warn_unknown_op(analysis: str, primitive: str, blind: str) -> None:
    """One-time :class:`UnknownOpWarning` (per analysis x primitive) when
    a walk meets an opaque primitive it cannot price — names the
    primitive and the quantity the analysis is now blind to. Registered
    kernels never come through here; the warn-once set keeps a scan-heavy
    program from printing the same blindness hundreds of times."""
    key = (analysis, primitive)
    if key in _WARNED_UNKNOWN:
        return
    _WARNED_UNKNOWN.add(key)
    warnings.warn(
        f"{analysis}: opaque primitive '{primitive}' has no registered "
        f"KernelCostSpec — its {blind} is counted as ZERO. Register a "
        "contract (accelerate_tpu.kernels.kernel_cost) or run "
        "`accelerate-tpu kernel-check` (TPU1005) to gate on it.",
        UnknownOpWarning,
        stacklevel=3,
    )


def reset_unknown_op_warnings() -> None:
    """Clear the warn-once memory (regression tests pin warn-once)."""
    _WARNED_UNKNOWN.clear()
