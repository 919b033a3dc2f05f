"""What both training builders hand to the ``train_steps`` generator."""

from __future__ import annotations

import gc


class Trainer:
    """One compiled step with its state, built once and driven by set-up and window alike."""

    def __init__(self, *, accelerator, model, step, table, layers, b1, batches, device_batch, tokens_per_step,
                 ref_batch):
        self.accelerator, self.model, self._step = accelerator, model, step
        self._table, self._layers, self._b1 = table, layers, b1
        self._batches, self._device_batch = batches, device_batch
        self.tokens_per_step, self._ref_batch = tokens_per_step, ref_batch

    def feed(self, i: int):
        """Batch ``i`` of the seeded stream, placed as the program's own data path places it."""
        return self._device_batch(self._batches[i % len(self._batches)])

    def step(self, batch):
        return self._step(batch)

    def reference_batch(self, i: int) -> dict:
        return self._ref_batch(self._batches[i % len(self._batches)])

    def _leaf_norms(self, tree, layer_names, minus=None, scale: float = 1.0) -> dict:
        """Norms a leaf at a time (per layer where a tensor has layers), so that reading
        them never holds a second copy of the state. ``minus``: flat weights to take off."""
        import jax.numpy as jnp

        from ._tree import _get

        def norm(x, y=None):
            x = x.astype(jnp.float32) if y is None else x.astype(jnp.float32) - y.astype(jnp.float32)
            return float(jnp.sqrt(jnp.sum(x * x))) * scale

        out = {}
        for name, path, per_layer in self._table:
            if per_layer:
                for i in range(self._layers):
                    out[f"{name}[{i}]"] = norm(_get(tree, path.format(i=i)), None if minus is None else minus[name][i])
            elif name in layer_names:  # one tensor stacked over layers
                for i in range(self._layers):
                    out[f"{name}[{i}]"] = norm(_get(tree, path)[i], None if minus is None else minus[name][i])
            else:
                out[name] = norm(_get(tree, path), None if minus is None else minus[name])
        return out

    def first_gradient_norms(self, layer_names) -> dict:
        """Per-leaf norms of the gradient the optimizer was given at its first step, from
        Adam's first moment after exactly one step: mu = (1 - b1) * g."""
        from ._tree import find_adam_mu

        mu = find_adam_mu(self.accelerator._optimizers[-1].opt_state)
        return self._leaf_norms(mu, layer_names, scale=1.0 / (1.0 - self._b1))

    def first_gradient_on_host(self) -> dict:
        """That gradient itself, in the benchmark's names, copied to the host so that it costs
        the device nothing while the window runs."""
        import numpy as np

        from ._tree import _get, find_adam_mu

        mu = find_adam_mu(self.accelerator._optimizers[-1].opt_state)
        scale = 1.0 / (1.0 - self._b1)
        out = {}
        for name, path, per_layer in self._table:
            if per_layer:
                out[name] = np.stack([np.asarray(_get(mu, path.format(i=i)), np.float32) for i in range(self._layers)]) * scale
            else:
                out[name] = np.asarray(_get(mu, path), np.float32) * scale
        return out

    def change_norms(self, start: dict, layer_names) -> dict:
        """Per-leaf norms of parameters now less the flat weights ``start``."""
        return self._leaf_norms(self.model.params, layer_names, minus=start)

    def param_shardings(self) -> dict:
        from ._tree import _get

        return {name: _get(self.model.params, path.format(i=0)).sharding for name, path, _ in self._table}

    def free(self) -> None:
        from ._tree import reset_accelerator_state

        self.accelerator._optimizers[-1].opt_state = None
        self.model.params = None
        self._step = self.model = self.accelerator = None
        reset_accelerator_state()
        gc.collect()
