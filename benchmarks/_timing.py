"""Shared timing helper: the fence every benchmark loop ends on.

JAX returns before the device finishes, so a timing loop that does not
block measures the enqueue. ``jax.block_until_ready`` is the fence; fetch a
value only where the value itself is needed.
"""

from __future__ import annotations


def force(x) -> None:
    """Block until ``x`` (any pytree of jax arrays) has finished computing."""
    import jax

    jax.block_until_ready(x)
