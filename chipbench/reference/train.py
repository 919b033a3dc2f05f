"""Follow a training run's first steps with the plain reference: float32 weights,
gradients summed over blocks of rows, AdamW as published (Loshchilov & Hutter 2017,
arXiv:1711.05101, algorithm 2 with decoupled decay; bias-corrected moments)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def leaf_norms(tree: dict, layer_names) -> dict:
    """One norm per tensor, and per layer for the tensors stacked over layers."""
    out = {}
    for name, x in tree.items():
        x = x.astype(jnp.float32)
        if name in layer_names:
            norms = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
            out.update({f"{name}[{i}]": float(v) for i, v in enumerate(jax.device_get(norms))})
        else:
            out[name] = float(jnp.sqrt(jnp.sum(x * x)))
    return out


def _rows(batch, lo: int, hi: int):
    return jax.tree.map(lambda x: x[lo:hi], batch)


def _n_rows(batch) -> int:
    return jax.tree.leaves(batch)[0].shape[0]


def follow(module, cfg: dict, weights: dict, batches, opt: dict, row_block: int, dot_name: str = "exact") -> dict:
    """Three (``len(batches)``) AdamW steps from ``weights`` (float32). Returns each step's
    loss, the per-leaf norms of the first gradient and of the parameters' change. ``module`` is
    the family's: its ``loss_fn`` and ``LAYER_NAMES``."""

    @jax.jit
    def grad_block(w, block):
        return jax.value_and_grad(lambda w: module.loss_fn(w, cfg, block, dot_name))(w)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adamw(w, m, v, g, t):
        b1, b2 = opt["b1"], opt["b2"]
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        def new(w, m, v):
            update = (m / (1 - b1**t)) / (jnp.sqrt(v / (1 - b2**t)) + opt["eps"]) + opt["weight_decay"] * w
            return w - opt["lr"] * update
        return jax.tree.map(new, w, m, v), m, v

    start = weights
    w = jax.tree.map(jnp.copy, weights)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, first_gradient = [], None
    for t, batch in enumerate(batches, start=1):
        n = _n_rows(batch)
        if n % row_block:
            raise ValueError(f"{n} rows do not divide into blocks of {row_block}")
        blocks = n // row_block
        loss, grads = 0.0, None
        for b in range(blocks):
            l, g = grad_block(w, _rows(batch, b * row_block, (b + 1) * row_block))
            loss += float(l) / blocks
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        grads = jax.tree.map(lambda g: g / blocks, grads)
        losses.append(loss)
        if t == 1:
            first_gradient = leaf_norms(grads, module.LAYER_NAMES)
            first_gradient_tree = grads
        w, m, v = adamw(w, m, v, grads, float(t))
    change = leaf_norms(jax.tree.map(jnp.subtract, w, start), module.LAYER_NAMES)
    return {"losses": losses, "first_gradient": first_gradient, "change": change,
            "first_gradient_tree": first_gradient_tree}


def leaf_difference_norms(tree: dict, other: dict, layer_names) -> dict:
    """Per leaf (per layer where stacked) the norm of ``tree - other``, a leaf at a time."""
    import numpy as np

    @jax.jit
    def distance(a, b):
        d = a.astype(jnp.float32) - b.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(d * d))

    out = {}
    for name in other:
        if name in layer_names:
            for i in range(other[name].shape[0]):
                out[f"{name}[{i}]"] = float(distance(np.asarray(tree[name][i]) if isinstance(tree[name], np.ndarray) else tree[name][i], other[name][i]))
        else:
            out[name] = float(distance(tree[name], other[name]))
    return out


def worst_leaf_difference(difference: dict, reference: dict, skip=frozenset()) -> tuple:
    """The largest norm of a leaf's difference against the reference's norm of that leaf or of
    the median leaf, whichever is larger; and that leaf."""
    import statistics

    floor = statistics.median(reference.values())
    shares = {k: difference[k] / max(reference[k], floor) for k in reference if k not in skip}
    worst = max(shares, key=shares.get)
    return shares[worst], worst


ALL_BUT_ZERO = 1e-4  # of the median leaf's first-gradient norm


def all_but_zero_leaves(reference_first_gradient: dict) -> set:
    """Leaves whose gradient is zero in exact arithmetic (a key bias moves no softmax) and
    rounding noise in any float type. Adam divides that noise by its own size, so such a
    leaf's change is noise at full step size, in the program and in the reference alike:
    its change is not compared. Its gradient is, against the median leaf's norm."""
    import statistics

    floor = ALL_BUT_ZERO * statistics.median(reference_first_gradient.values())
    return {k for k, v in reference_first_gradient.items() if v < floor}


def matrix_leaves(spec: dict, layer_names) -> set:
    """Leaf keys (``name`` or ``name[i]``) of tensors with two dimensions or more a layer. A
    vector's gradient (a bias, a norm's gain) is a sum over every token in which terms cancel:
    its norm swings with the seed in any float type, so vectors are compared apart."""
    out = set()
    for name, (shape, _) in spec.items():
        if name in layer_names and len(shape) >= 3:
            out.update(f"{name}[{i}]" for i in range(shape[0]))
        elif name not in layer_names and len(shape) >= 2:
            out.add(name)
    return out


def worst_leaf_gap(program: dict, reference: dict, skip=frozenset()) -> tuple:
    """The widest gap between the program's norm of a leaf and the reference's, against the
    reference's norm of that leaf or of the median leaf, whichever is larger; and that leaf."""
    import statistics

    if set(program) != set(reference):
        raise ValueError(f"leaves differ: {sorted(set(program) ^ set(reference))}")
    floor = statistics.median(reference.values())
    gaps = {k: abs(program[k] - reference[k]) / max(reference[k], floor) for k in reference if k not in skip}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst
