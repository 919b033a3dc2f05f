"""Small queries about the installed jax that several modules share.

jax is imported lazily: this module sits under the package's eager import
path and must not initialise a backend.
"""

from __future__ import annotations


def supports_memory_kind(kind: str = "pinned_host") -> bool:
    """Whether the default device can address ``kind`` memory. TPU backends
    expose ``pinned_host`` for optimizer-state offload; the CPU backend may
    address only ``unpinned_host``, where offload must degrade gracefully
    instead of dying in ``NamedSharding.with_memory_kind``."""
    import jax

    try:
        return any(m.kind == kind for m in jax.devices()[0].addressable_memories())
    except Exception:
        return False


def in_manual_region() -> bool:
    """True when tracing inside a shard_map body — mesh axes are Manual
    there, and nesting another shard_map over the same mesh is an error, so
    sharded-dispatch wrappers must use the bare kernel."""
    import jax

    manual = jax.sharding.AxisType.Manual
    return any(t == manual for t in jax.sharding.get_abstract_mesh().axis_types)
