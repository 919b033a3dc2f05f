"""Seeded weights, made by the benchmark on the device in one jitted call.

A family's ``spec(cfg)`` (``reference/<family>.py``) lists every tensor by the benchmark's
own name with its shape and how it is drawn. The plain references read these names; a
builder maps them onto the program's parameter tree. Nothing here imports the program.
"""

from __future__ import annotations


def seed_key(seed: int):
    """A key from any whole-number seed, also one beyond 32 signed bits."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make(spec: dict, seed: int, dtype: str, shardings: dict | None = None) -> dict:
    """Every tensor of ``spec`` from ``seed``, in ``dtype``, in one jitted call.
    The same seed gives the same values whatever ``shardings`` lays them out as."""
    import jax
    import jax.numpy as jnp

    names = sorted(spec)
    dt = jnp.dtype(dtype)

    def draw(key):
        out = {}
        for i, name in enumerate(names):
            shape, (kind, amount) = spec[name]
            noise = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * amount
            out[name] = ((1.0 + noise) if kind == "one_plus" else noise).astype(dt)
        return out

    out_shardings = None if shardings is None else {n: shardings[n] for n in names}
    return jax.jit(draw, out_shardings=out_shardings)(seed_key(seed))


def count(spec: dict) -> int:
    total = 0
    for shape, _ in spec.values():
        n = 1
        for s in shape:
            n *= s
        total += n
    return total
