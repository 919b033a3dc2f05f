"""Between the benchmark's flat weight names and a program's parameter tree.

A table row is ``(name, path, per_layer)``: the tensor ``name`` lives at ``path`` (keys
joined by ``|``); with ``per_layer`` the path holds ``{i}`` and layer ``i`` takes ``name[i]``.
"""

from __future__ import annotations


def _set(tree: dict, path: str, value) -> None:
    keys = path.split("|")
    for k in keys[:-1]:
        tree = tree.setdefault(k, {})
    tree[keys[-1]] = value


def _get(tree, path: str):
    for k in path.split("|"):
        tree = tree[k]
    return tree


def to_tree(flat: dict, table, layers: int) -> dict:
    tree: dict = {}
    for name, path, per_layer in table:
        if per_layer:
            for i in range(layers):
                _set(tree, path.format(i=i), flat[name][i])
        else:
            _set(tree, path, flat[name])
    return tree


def to_flat(tree, table, layers: int) -> dict:
    import jax.numpy as jnp

    flat = {}
    for name, path, per_layer in table:
        if per_layer:
            flat[name] = jnp.stack([_get(tree, path.format(i=i)) for i in range(layers)])
        else:
            flat[name] = _get(tree, path)
    return flat


def check_same_shapes(tree, wanted) -> None:
    """Raise unless ``tree`` has exactly the structure and shapes of the program's own ``wanted``."""
    import jax

    got = {jax.tree_util.keystr(p): tuple(x.shape) for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    want = {jax.tree_util.keystr(p): tuple(x.shape) for p, x in jax.tree_util.tree_flatten_with_path(wanted)[0]}
    if got != want:
        diff = {k: (got.get(k), want.get(k)) for k in set(got) | set(want) if got.get(k) != want.get(k)}
        raise ValueError(f"the benchmark's weights do not fit the program's parameter tree: {diff}")


def find_adam_mu(opt_state):
    """The first-moment tree of the optax Adam state inside ``opt_state``."""
    import jax

    found = [s for s in jax.tree_util.tree_leaves(opt_state, is_leaf=lambda s: hasattr(s, "mu") and hasattr(s, "nu"))
             if hasattr(s, "mu")]
    if len(found) != 1:
        raise ValueError(f"expected one Adam state in the optimizer state, found {len(found)}")
    return found[0].mu


def reset_accelerator_state() -> None:
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
