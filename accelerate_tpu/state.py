"""Process/topology singletons: ``PartialState``, ``AcceleratorState``,
``GradientState``.

Reference analogue: src/accelerate/state.py (1347 LoC). The reference's
``PartialState`` must probe seven native backends and run a rendezvous
(state.py:746-812, init_process_group at :236); here the entire bootstrap is
``jax.distributed.initialize`` (DCN rendezvous) + mesh construction — ICI
collectives need no process groups at all, XLA inserts them from shardings.

The shared-dict (borg) pattern is kept (reference: state.py:163,179): every
``PartialState()`` constructed anywhere in the process sees the same state,
and ``Accelerator()`` can be constructed many times cheaply.
"""

from __future__ import annotations

import logging
import os
from contextlib import contextmanager
from typing import Any, Callable, Optional


from .utils.dataclasses import DistributedType, MixedPrecisionPolicy, ParallelismPlugin, PrecisionType
from .utils.environment import parse_flag_from_env

logger = logging.getLogger(__name__)


def _jax():
    import jax

    return jax


class PartialState:
    """Topology singleton (reference: state.py:124).

    Handles multi-host rendezvous (``jax.distributed.initialize``), exposes
    rank/world/device info, and the process-control helpers
    (``wait_for_everyone``, ``main_process_first``, ``split_between_processes``,
    ``on_main_process`` — reference: state.py:417-560).
    """

    _shared_state: dict[str, Any] = {}
    _know_initialized = False

    def __init__(self, cpu: bool = False, **kwargs):
        self.__dict__ = self._shared_state
        if self.initialized:
            return
        jax = _jax()

        # Multi-host rendezvous over DCN (reference boundary analogue:
        # torch.distributed.init_process_group, state.py:236).
        # NB: no jax.devices()/process_count() calls may happen before
        # jax.distributed.initialize() — backend init is one-shot, so the
        # guard is an env flag, not a backend query.
        coordinator = kwargs.pop("coordinator_address", None) or os.environ.get("ACCELERATE_COORDINATOR_ADDRESS")
        num_processes_env = kwargs.pop("num_processes", None) or os.environ.get("ACCELERATE_NUM_PROCESSES")
        process_id = kwargs.pop("process_id", None) or os.environ.get("ACCELERATE_PROCESS_ID")
        if coordinator is not None and not parse_flag_from_env("ACCELERATE_DISTRIBUTED_INITIALIZED"):
            local_ids = kwargs.pop("local_device_ids", None)
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=int(num_processes_env) if num_processes_env is not None else None,
                process_id=int(process_id) if process_id is not None else None,
                local_device_ids=local_ids,
            )
            os.environ["ACCELERATE_DISTRIBUTED_INITIALIZED"] = "1"

        if cpu:
            # an explicit request for the host platform (test/debug path);
            # JAX_PLATFORMS=cpu in the environment needs no help from here
            jax.config.update("jax_platforms", "cpu")

        self.debug = parse_flag_from_env("ACCELERATE_DEBUG_MODE")
        self._cpu = cpu
        self.fork_launched = parse_flag_from_env("FORK_LAUNCHED")
        self.backend = jax.default_backend()
        self._devices = jax.devices()
        self._local_devices = jax.local_devices()
        self.num_processes_host = jax.process_count()
        self.process_index_host = jax.process_index()
        self.initialized = True

    # -- identity ----------------------------------------------------------

    @property
    def initialized(self) -> bool:
        return self._shared_state.get("_initialized", False)

    @initialized.setter
    def initialized(self, value: bool):
        self._shared_state["_initialized"] = value

    @property
    def device(self):
        """The first local device (reference ``self.device``, state.py:814)."""
        return self._local_devices[0]

    @property
    def devices(self):
        return self._devices

    @property
    def local_devices(self):
        return self._local_devices

    @property
    def num_devices(self) -> int:
        return len(self._devices)

    @property
    def local_device_count(self) -> int:
        return len(self._local_devices)

    @property
    def num_processes(self) -> int:
        """Number of *host processes*. NB: the reference's "process" is one
        per accelerator; on TPU one process drives several chips, so
        data-parallel sharding happens per-device, not per-process."""
        return self.num_processes_host

    @property
    def process_index(self) -> int:
        return self.process_index_host

    @property
    def local_process_index(self) -> int:
        # one process per host on TPU pods; the N-local-process testing
        # launcher sets the env so rank gating (print/tqdm/local-main
        # contexts) behaves like the reference's torchrun LOCAL_RANK
        return int(os.environ.get("ACCELERATE_LOCAL_PROCESS_ID", 0))

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        return self.local_process_index == 0

    @property
    def is_last_process(self) -> bool:
        return self.process_index == self.num_processes - 1

    @property
    def distributed_type(self) -> DistributedType:
        state = AcceleratorState._shared_state
        if state.get("_initialized") and state.get("mesh") is not None:
            return DistributedType.from_mesh_sizes(dict(state["mesh"].shape))
        return DistributedType.DATA_PARALLEL if self.num_devices > 1 else DistributedType.NO

    @property
    def use_distributed(self) -> bool:
        return self.num_devices > 1 or self.num_processes > 1

    # -- process control ---------------------------------------------------

    def wait_for_everyone(self):
        """Cross-host barrier (reference: utils/other.py:302 incl.
        ``xm.rendezvous``). Single-process: no-op."""
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("accelerate_tpu.wait_for_everyone")

    @contextmanager
    def main_process_first(self):
        """Main process runs the body first, others wait (reference:
        state.py:508) — e.g. dataset download/caching."""
        if not self.is_main_process:
            self.wait_for_everyone()
        yield
        if self.is_main_process:
            self.wait_for_everyone()

    @contextmanager
    def local_main_process_first(self):
        if not self.is_local_main_process:
            self.wait_for_everyone()
        yield
        if self.is_local_main_process:
            self.wait_for_everyone()

    @staticmethod
    def _pad_tail(chunk, target: int, full):
        """Grow ``chunk`` to ``target`` rows by repeating ``full``'s last row.
        Arrays stay arrays (the reference pads tensors with torch.cat,
        state.py:446-462); lists/tuples pad to a list."""
        if target <= len(chunk) or not len(full):
            return chunk
        if hasattr(chunk, "shape") and hasattr(chunk, "dtype"):  # np/jax array
            import numpy as _np

            reps = target - len(chunk)
            last = full[-1:]
            if isinstance(chunk, _np.ndarray):
                return _np.concatenate([chunk] + [_np.asarray(last)] * reps, axis=0)
            import jax.numpy as jnp

            return jnp.concatenate([chunk] + [jnp.asarray(last)] * reps, axis=0)
        out = list(chunk)
        while len(out) < target:
            out.append(full[-1])
        return out

    @contextmanager
    def split_between_processes(self, inputs, apply_padding: bool = False):
        """Split a list/tuple/dict/array evenly across processes (reference:
        state.py:417-506). Yields this process's slice; ``apply_padding``
        repeats the last element/row so every process gets equal length —
        tensor inputs are padded as tensors, matching the reference."""
        if self.num_processes == 1:
            yield inputs
            return
        if isinstance(inputs, dict):
            # split dict VALUES row-wise (len(dict) would count keys);
            # reference requires equal-length values (state.py:468-474)
            lengths = {k: len(v) for k, v in inputs.items()}
            if len(set(lengths.values())) > 1:
                raise ValueError(f"cannot split dict with unequal value lengths: {lengths}")
            length = next(iter(lengths.values())) if lengths else 0
        else:
            length = len(inputs)
        num_per = length // self.num_processes
        remainder = length % self.num_processes
        start = self.process_index * num_per + min(self.process_index, remainder)
        end = start + num_per + (1 if self.process_index < remainder else 0)
        if isinstance(inputs, dict):
            chunk = {k: v[start:end] for k, v in inputs.items()}
        else:
            chunk = inputs[start:end]
        if apply_padding and length:
            target = num_per + (1 if remainder else 0)
            if isinstance(chunk, dict):
                chunk = {k: self._pad_tail(v, target, inputs[k]) for k, v in chunk.items()}
            else:
                chunk = self._pad_tail(chunk, target, inputs)
        yield chunk

    def on_main_process(self, function: Callable) -> Callable:
        """Decorator: run only on the main process (reference: state.py:549)."""

        def wrapper(*args, **kwargs):
            if self.is_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_local_main_process(self, function: Callable) -> Callable:
        def wrapper(*args, **kwargs):
            if self.is_local_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_process(self, function: Callable = None, process_index: int = None) -> Callable:
        def wrapper(*args, **kwargs):
            if self.process_index == process_index:
                return function(*args, **kwargs)

        return wrapper

    def on_last_process(self, function: Callable) -> Callable:
        def wrapper(*args, **kwargs):
            if self.is_last_process:
                return function(*args, **kwargs)

        return wrapper

    def print(self, *args, **kwargs):
        if self.is_local_main_process:
            print(*args, **kwargs)

    def __repr__(self) -> str:
        return (
            f"Distributed environment: {self.distributed_type}\n"
            f"Backend: {self.backend}\n"
            f"Num processes: {self.num_processes}\n"
            f"Process index: {self.process_index}\n"
            f"Num devices: {self.num_devices}\n"
            f"Device: {self.device}\n"
        )

    def destroy_process_group(self):
        """Shut down the distributed runtime (tests / clean exit)."""
        jax = _jax()
        if self.num_processes > 1:
            try:
                jax.distributed.shutdown()
            except Exception:  # pragma: no cover
                pass

    @classmethod
    def _reset_state(cls):
        """Reset the singleton (test harness; reference: state.py
        ``_reset_state`` used by AccelerateTestCase, testing.py:639)."""
        cls._shared_state.clear()


class AcceleratorState:
    """Adds precision policy + mesh to :class:`PartialState`
    (reference: state.py:863)."""

    _shared_state: dict[str, Any] = {}

    def __init__(
        self,
        mixed_precision: Optional[str] = None,
        cpu: bool = False,
        parallelism_plugin: Optional[ParallelismPlugin] = None,
        _from_accelerator: bool = False,
        **kwargs,
    ):
        self.__dict__ = self._shared_state
        if self.initialized:
            if mixed_precision is not None and mixed_precision != self.mixed_precision:
                logger.warning(
                    "AcceleratorState already initialized with mixed_precision=%s; ignoring %s",
                    self.mixed_precision,
                    mixed_precision,
                )
            return
        self.partial_state = PartialState(cpu=cpu, **kwargs)
        mixed_precision = (
            mixed_precision
            if mixed_precision is not None
            else os.environ.get("ACCELERATE_MIXED_PRECISION", "no")
        )
        self.mixed_precision = str(PrecisionType(mixed_precision))
        self.dtype_policy = MixedPrecisionPolicy.from_mixed_precision(self.mixed_precision)
        self.parallelism_plugin = parallelism_plugin or ParallelismPlugin.from_env()
        self.mesh = self.parallelism_plugin.mesh_config.build(self.partial_state.devices)
        self.initialized = True

    @property
    def initialized(self) -> bool:
        return self._shared_state.get("_initialized", False)

    @initialized.setter
    def initialized(self, value: bool):
        self._shared_state["_initialized"] = value

    @property
    def distributed_type(self) -> DistributedType:
        return DistributedType.from_mesh_sizes(dict(self.mesh.shape))

    def __getattr__(self, name: str):
        # delegate topology attrs to PartialState (reference does the same
        # via __getattr__, state.py)
        if name.startswith("_") or "partial_state" not in self.__dict__:
            raise AttributeError(name)
        return getattr(self.partial_state, name)

    def __repr__(self) -> str:
        return repr(self.partial_state) + f"Mixed precision: {self.mixed_precision}\nMesh: {dict(self.mesh.shape)}\n"

    @classmethod
    def _reset_state(cls, reset_partial_state: bool = False):
        cls._shared_state.clear()
        if reset_partial_state:
            PartialState._reset_state()


class GradientState:
    """Gradient-accumulation bookkeeping singleton (reference: state.py:1207).

    Tracks the accumulation counter, the ``sync_gradients`` flag, active
    dataloaders and the uneven-tail ``remainder`` that drives
    ``gather_for_metrics`` truncation (reference: state.py:1300-1340,
    data_loader.py:365-405)."""

    _shared_state: dict[str, Any] = {}

    def __init__(self, gradient_accumulation_plugin=None):
        self.__dict__ = self._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self.active_dataloader = None
            self.dataloader_references = [None]
            self.current_step = 0
            self.plugin_kwargs = {}
            self.initialized = True
        if gradient_accumulation_plugin is not None:
            self.plugin_kwargs = gradient_accumulation_plugin.to_dict()

    @property
    def initialized(self) -> bool:
        return self._shared_state.get("_initialized", False)

    @initialized.setter
    def initialized(self, value: bool):
        self._shared_state["_initialized"] = value

    @property
    def num_steps(self) -> int:
        return self.plugin_kwargs.get("num_steps", 1)

    @property
    def adjust_scheduler(self) -> bool:
        return self.plugin_kwargs.get("adjust_scheduler", True)

    @property
    def sync_with_dataloader(self) -> bool:
        return self.plugin_kwargs.get("sync_with_dataloader", True)

    @property
    def end_of_dataloader(self) -> bool:
        if not self.in_dataloader:
            return False
        return self.active_dataloader.end_of_dataloader

    @property
    def remainder(self) -> int:
        """Number of padding samples in the final uneven batch (negative
        convention matches the reference: -1 = unknown)."""
        if not self.in_dataloader:
            return -1
        return self.active_dataloader.remainder

    @property
    def in_dataloader(self) -> bool:
        return self.active_dataloader is not None

    def _set_sync_gradients(self, sync_gradients: bool):
        self.sync_gradients = sync_gradients

    def _add_dataloader(self, dataloader):
        self.active_dataloader = dataloader
        self.dataloader_references.append(dataloader)

    def _remove_dataloader(self, dataloader):
        if dataloader in self.dataloader_references:
            self.dataloader_references.remove(dataloader)
        self.active_dataloader = self.dataloader_references[-1]

    def __repr__(self) -> str:
        return (
            f"Sync gradients: {self.sync_gradients}\n"
            f"At end of current dataloader: {self.end_of_dataloader}\n"
            f"Extra samples added: {self.remainder}\n"
        )

    @classmethod
    def _reset_state(cls):
        cls._shared_state.clear()
