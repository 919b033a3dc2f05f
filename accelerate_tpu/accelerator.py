"""The ``Accelerator``: prepare / train-step / gather / checkpoint engine.

Reference analogue: src/accelerate/accelerator.py (4015 LoC, class at :184).
The contract preserved: a user writes a plain training loop, calls
``prepare()`` once, and gets sharding + mixed precision + grad accumulation +
checkpointing + tracking for free. What changes is *how*: the reference
dispatches to per-strategy wrapper branches (DDP/FSDP/DeepSpeed/Megatron,
accelerator.py:1447-2285); here ``prepare`` lays parameters out on one mesh
with ``NamedSharding``s and the whole hot loop (forward/backward/allreduce/
optimizer — reference call stack §3.4) becomes **one jitted function** with
gradient accumulation folded in as a branchless on-device buffer.

Two ways to drive training:

* **fast path** — ``step = accelerator.build_train_step(loss_fn)``; call
  ``step(batch)`` per dataloader batch. One XLA program per step; grad sync
  is an XLA-inserted reduction over the batch axes.
* **imperative parity path** — ``accumulate()`` / ``backward(loss_fn,
  batch)`` / ``optimizer.step()`` / ``clip_grad_norm_`` mirror the
  reference's eager API; each piece is itself jit-cached so the cost over
  the fast path is only the python between calls.
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Callable, Optional

import numpy as np

from .data_loader import BaseDataLoader, prepare_data_loader, skip_first_batches as _skip_first_batches
from .logging import get_logger
from .modeling import Model, as_model
from .optimizer import AcceleratedOptimizer
from .parallel.mesh import data_parallel_size
from .parallel.sharding import fsdp_rules_for, infer_shardings
from .scheduler import AcceleratedScheduler
from .state import AcceleratorState, GradientState
from .telemetry.trace import phase
from .utils.dataclasses import (
    AutocastKwargs,
    DataLoaderConfiguration,
    DistributedInitKwargs,
    DistributedType,
    GradientAccumulationPlugin,
    GradScalerKwargs,
    ParallelismPlugin,
    ProfileKwargs,
    ProjectConfiguration,
)
from .utils.operations import gather, gather_object, pad_across_processes, reduce

logger = get_logger(__name__)


def _jax():
    import jax

    return jax


def _jnp():
    import jax.numpy as jnp

    return jnp


class Accelerator:
    """(reference: accelerator.py:184)."""

    def __init__(
        self,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: Optional[str] = None,
        gradient_accumulation_steps: int = 1,
        cpu: bool = False,
        dataloader_config: Optional[DataLoaderConfiguration] = None,
        log_with=None,
        project_dir: Optional[str] = None,
        project_config: Optional[ProjectConfiguration] = None,
        gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None,
        parallelism_plugin: Optional[ParallelismPlugin] = None,
        rng_types: Optional[list] = None,
        kwargs_handlers: Optional[list] = None,
        step_scheduler_with_optimizer: bool = True,
    ):
        # kwargs handlers (reference: accelerator.py:415-452)
        from .utils.dataclasses import CompileKwargs, FaultToleranceKwargs, TelemetryKwargs

        self.autocast_handler = AutocastKwargs()
        self.scaler_handler = GradScalerKwargs()
        self.profile_handler = ProfileKwargs()
        self.init_handler = DistributedInitKwargs()
        self.telemetry_handler = TelemetryKwargs()
        self.ft_handler = FaultToleranceKwargs()
        self.compile_handler = CompileKwargs()
        # opt-in behaviors (signal handlers, tracker retries) only activate
        # when the user passed the handler explicitly
        self._ft_explicit = False
        self._compile_explicit = False
        self.fp8_recipe_handler = None
        for handler in kwargs_handlers or []:
            if isinstance(handler, AutocastKwargs):
                self.autocast_handler = handler
            elif isinstance(handler, GradScalerKwargs):
                self.scaler_handler = handler
            elif isinstance(handler, ProfileKwargs):
                self.profile_handler = handler
            elif isinstance(handler, DistributedInitKwargs):
                self.init_handler = handler
            elif isinstance(handler, TelemetryKwargs):
                self.telemetry_handler = handler
            elif isinstance(handler, FaultToleranceKwargs):
                self.ft_handler = handler
                self._ft_explicit = True
            elif isinstance(handler, CompileKwargs):
                self.compile_handler = handler
                self._compile_explicit = True
            else:
                from .utils.dataclasses import Fp8RecipeKwargs, MixedPrecisionPolicy

                if isinstance(handler, Fp8RecipeKwargs):
                    self.fp8_recipe_handler = handler
                elif isinstance(handler, MixedPrecisionPolicy):
                    # full dtype-policy override (e.g. softmax_dtype="bfloat16"
                    # — the HBM-bandwidth lever, see the policy's docstring)
                    self._dtype_policy_override = handler

        if gradient_accumulation_plugin is None:
            env_steps = int(os.environ.get("ACCELERATE_GRADIENT_ACCUMULATION_STEPS", gradient_accumulation_steps))
            gradient_accumulation_plugin = GradientAccumulationPlugin(num_steps=env_steps)
        elif gradient_accumulation_steps != 1:
            raise ValueError("Pass either gradient_accumulation_steps or a GradientAccumulationPlugin, not both")

        self.project_configuration = project_config or ProjectConfiguration(project_dir=project_dir)
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)

        init_kwargs = {}
        if self.init_handler.coordinator_address is not None:
            init_kwargs = dict(
                coordinator_address=self.init_handler.coordinator_address,
                num_processes=self.init_handler.num_processes,
                process_id=self.init_handler.process_id,
                local_device_ids=self.init_handler.local_device_ids,
            )
        self.state = AcceleratorState(
            mixed_precision=mixed_precision,
            cpu=cpu,
            parallelism_plugin=parallelism_plugin,
            _from_accelerator=True,
            **init_kwargs,
        )
        if getattr(self, "_dtype_policy_override", None) is not None:
            # the handler must AGREE with mixed_precision on the core dtype
            # fields — a wholesale override that silently flips them (e.g.
            # dropping fp8, or bf16 compute under mixed_precision="no")
            # would be a footgun for users adding the handler just for
            # softmax_dtype
            derived, override = self.state.dtype_policy, self._dtype_policy_override
            for field_name in ("param_dtype", "compute_dtype", "output_dtype", "fp8"):
                if getattr(override, field_name) != getattr(derived, field_name):
                    raise ValueError(
                        f"MixedPrecisionPolicy({field_name}={getattr(override, field_name)!r}) "
                        f"conflicts with mixed_precision={self.state.mixed_precision!r} "
                        f"(which implies {field_name}={getattr(derived, field_name)!r}); "
                        f"set the field to match, or change mixed_precision"
                    )
            self.state.dtype_policy = override
        self.gradient_state = GradientState(gradient_accumulation_plugin)
        if getattr(self.state.dtype_policy, "fp8", False):
            # attach the recipe where trace-time code (the zoo's dense
            # factory) can reach it: the globally-visible dtype policy.
            # Delayed scaling is OPT-IN via an explicit Fp8RecipeKwargs —
            # bare mixed_precision="fp8" keeps the stateless dynamic recipe
            # (delayed needs the fp8 collection threaded as model.state,
            # which plain generate()/loss paths don't do)
            from .utils.dataclasses import Fp8RecipeKwargs

            self.state.dtype_policy.fp8_recipe = self.fp8_recipe_handler or Fp8RecipeKwargs(
                delayed_scaling=False
            )
        self.device_placement = device_placement
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer
        self.rng_types = rng_types or ["numpy", "python"]

        self.dataloader_config = dataloader_config or DataLoaderConfiguration(split_batches=split_batches)
        if split_batches:
            self.dataloader_config.split_batches = True

        # registries (reference keeps the same lists: accelerator.py:520-540)
        self._models: list[Model] = []
        self._optimizers: list[AcceleratedOptimizer] = []
        self._schedulers: list[AcceleratedScheduler] = []
        self._dataloaders: list[BaseDataLoader] = []
        self._custom_objects: list = []
        self._save_model_hooks: list = []
        self._load_model_hooks: list = []

        # imperative-path machinery — gradient buffers are per-model
        # (multi-model setups like GANs must not share one buffer)
        self.step = 0
        self._grad_buffers: dict[int, Any] = {}
        self._grad_count = 0
        self._clip_max_norm = None
        self._last_grad_norm = None
        self._jit_cache: dict = {}
        self._trigger_flag = False

        # fp16 dynamic loss scale (host-side; bf16 needs none of this —
        # reference scaler: accelerator.py:551-604)
        self._loss_scale = self.scaler_handler.init_scale if self.mixed_precision == "fp16" else 1.0
        self._scale_growth_tracker = 0

        self.trackers: list = []
        self._log_with = log_with

        # runtime telemetry (lazy — see the `telemetry` property)
        self._telemetry = None

        # compile management (docs/usage_guides/compilation.md): the shared
        # ProgramCache + persistent caches activate when a CompileKwargs
        # handler was passed or ACCELERATE_COMPILE_CACHE_DIR is set — a
        # bare Accelerator() must never start writing cache files
        self._program_cache = None
        if self._compile_explicit or os.environ.get("ACCELERATE_COMPILE_CACHE_DIR"):
            from .aot import ExecutableStore, ProgramCache, configure_persistent_cache, resolve_cache_dir

            ch = self.compile_handler
            cache_dir = resolve_cache_dir(
                ch.cache_dir, self.project_dir, self.project_configuration.compile_cache_dir_name
            )
            store = None
            if cache_dir is not None and ch.executable_store:
                store = ExecutableStore(os.path.join(cache_dir, "executables"))
            self._program_cache = ProgramCache(store=store)
            if cache_dir is not None and ch.persistent_xla_cache:
                configure_persistent_cache(os.path.join(cache_dir, "xla"), ch.min_compile_time_secs)

        # fault tolerance (docs/usage_guides/fault_tolerance.md): the
        # checkpoint a run resumed from (protected from pruning), the
        # one-final-checkpoint latch, and the preemption handler
        self._resumed_from: Optional[str] = None
        self._preempt_checkpointed = False
        self._preempt_agreed = False
        self._preemption = None
        if self._ft_explicit and self.ft_handler.handle_preemption:
            from .ft.preemption import PreemptionHandler

            def _on_preempt(signame: str):
                if self._telemetry is not None:
                    self._telemetry.log.event("preempt", severity="warning", signal=signame)

            self._preemption = PreemptionHandler(
                signals=self.ft_handler.preemption_signals, on_preempt=_on_preempt
            )
            self._preemption.install()

        self.flag_tensor = None

    # ------------------------------------------------------------------ #
    # topology / state passthroughs (reference: accelerator.py:600-1030)
    # ------------------------------------------------------------------ #

    @property
    def mesh(self):
        return self.state.mesh

    @property
    def device(self):
        return self.state.device

    @property
    def distributed_type(self) -> DistributedType:
        return self.state.distributed_type

    @property
    def num_processes(self) -> int:
        return self.state.num_processes

    @property
    def process_index(self) -> int:
        return self.state.process_index

    @property
    def local_process_index(self) -> int:
        return self.state.local_process_index

    @property
    def is_main_process(self) -> bool:
        return self.state.is_main_process

    @property
    def is_local_main_process(self) -> bool:
        return self.state.is_local_main_process

    @property
    def is_last_process(self) -> bool:
        return self.state.is_last_process

    @property
    def mixed_precision(self) -> str:
        return self.state.mixed_precision

    @property
    def use_distributed(self) -> bool:
        return self.state.use_distributed

    @property
    def num_data_shards(self) -> int:
        return data_parallel_size(self.mesh)

    @property
    def sync_gradients(self) -> bool:
        return self.gradient_state.sync_gradients

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, value: int):
        self.gradient_state.plugin_kwargs.update({"num_steps": value})

    @property
    def project_dir(self):
        return self.project_configuration.project_dir

    @property
    def logging_dir(self):
        return self.project_configuration.logging_dir

    @property
    def save_iteration(self):
        return self.project_configuration.iteration

    def print(self, *args, **kwargs):
        self.state.print(*args, **kwargs)

    def wait_for_everyone(self):
        self.state.wait_for_everyone()

    def split_between_processes(self, inputs, apply_padding: bool = False):
        return self.state.split_between_processes(inputs, apply_padding=apply_padding)

    def on_main_process(self, function):
        return self.state.on_main_process(function)

    def on_local_main_process(self, function):
        return self.state.on_local_main_process(function)

    def on_process(self, function=None, process_index=None):
        return self.state.on_process(function, process_index)

    def on_last_process(self, function):
        return self.state.on_last_process(function)

    def main_process_first(self):
        return self.state.main_process_first()

    def local_main_process_first(self):
        return self.state.local_main_process_first()

    # ------------------------------------------------------------------ #
    # prepare (reference: accelerator.py:1316)
    # ------------------------------------------------------------------ #

    def _is_model_like(self, obj) -> bool:
        if isinstance(obj, Model):
            return True
        if self._is_optimizer_like(obj):  # optax tx is itself a 2-tuple
            return False
        return isinstance(obj, tuple) and len(obj) == 2 and (hasattr(obj[0], "apply") or callable(obj[0]))

    def _is_optimizer_like(self, obj) -> bool:
        if isinstance(obj, AcceleratedOptimizer):
            return True
        return hasattr(obj, "init") and hasattr(obj, "update") and not hasattr(obj, "apply")

    def _is_dataloader_like(self, obj) -> bool:
        if isinstance(obj, BaseDataLoader):
            return True
        try:
            import torch.utils.data as tud

            if isinstance(obj, tud.DataLoader):
                return True
        except ImportError:
            pass
        return False

    def prepare(self, *args, device_placement=None):
        """Shard/wrap models, optimizers, dataloaders, schedulers; returns
        them in the same order (reference: accelerator.py:1316).

        Two-pass like the reference (scheduler after optimizer,
        accelerator.py:1456-1459) so a scheduler can bind to its prepared
        optimizer. Idempotent via the ``_is_accelerate_prepared`` marker
        (reference: accelerator.py:1470-1475).
        """
        staged = {}
        # models first (argument order must not matter: an optimizer passed
        # before its model still binds to it), then optimizers/loaders,
        # then schedulers — mirrors the reference's two-pass ordering.
        for i, obj in enumerate(args):
            if getattr(obj, "_is_accelerate_prepared", False):
                staged[i] = obj
            elif self._is_model_like(obj):
                staged[i] = self.prepare_model(obj)
        for i, obj in enumerate(args):
            if i in staged:
                continue
            if self._is_optimizer_like(obj):
                staged[i] = self.prepare_optimizer(obj)
            elif (
                self._is_dataloader_like(obj)
                or hasattr(obj, "__iter__")
                or (hasattr(obj, "__getitem__") and hasattr(obj, "__len__"))
            ):
                staged[i] = self.prepare_data_loader(obj)
        for i, obj in enumerate(args):
            if i in staged:
                continue
            staged[i] = self.prepare_scheduler(obj)
        if self._telemetry is not None:
            # telemetry already live: mark the prepare so the timeline can
            # attribute the layout/device_put cost (never force-create it —
            # prepare() must not start writing files as a side effect)
            self._telemetry.log.event(
                "prepare",
                models=len(self._models),
                optimizers=len(self._optimizers),
                dataloaders=len(self._dataloaders),
                schedulers=len(self._schedulers),
                mesh={k: int(v) for k, v in dict(self.mesh.shape).items()},
                mixed_precision=self.mixed_precision,
            )
        result = [staged[i] for i in range(len(args))]
        return result[0] if len(result) == 1 else tuple(result)

    def _sharding_rules_for(self, model: Model):
        plugin = self.state.parallelism_plugin
        if plugin.sharding_rules is not None:
            return list(plugin.sharding_rules)
        rules = list(model.sharding_rules or [])
        if self.mesh.shape.get("fsdp", 1) > 1:
            # exact-path rules first: fsdp splits what the model's own
            # (tensor) rules leave whole, it does not lose to them
            rules = list(fsdp_rules_for(model.params, self.mesh, base_rules=rules)) + rules
        return rules

    def prepare_model(self, model, device_placement: Optional[bool] = None, evaluation_mode: bool = False) -> Model:
        """(reference: accelerator.py:1549). Cast params to the fp32 master
        dtype, compute per-param shardings from the layout rules, and
        ``device_put`` — the DDP/FSDP/TP wrap branches (reference
        :1647-1750) all reduce to the sharding choice."""
        model = as_model(model)
        if model._is_accelerate_prepared:
            return model
        jax = _jax()
        jnp = _jnp()
        if device_placement is None:
            device_placement = self.device_placement

        param_dtype = jnp.dtype(self.state.dtype_policy.param_dtype)

        def cast(p):
            if hasattr(p, "dtype") and jnp.issubdtype(np.asarray(p).dtype if not hasattr(p, "dtype") else p.dtype, jnp.floating):
                return np.asarray(p, dtype=param_dtype) if isinstance(p, np.ndarray) else p.astype(param_dtype)
            return p

        params = jax.tree_util.tree_map(cast, model.params)
        if device_placement:
            rules = self._sharding_rules_for(model)
            shardings = infer_shardings(params, rules, self.mesh)
            params = jax.device_put(params, shardings)
            model.param_shardings = shardings
        model.params = params
        model._is_accelerate_prepared = True
        model.accelerator = self
        if not evaluation_mode:
            self._models.append(model)
        return model

    def prepare_optimizer(self, optimizer, device_placement: Optional[bool] = None) -> AcceleratedOptimizer:
        """(reference: accelerator.py:2464). The optax state is created
        *from sharded params* inside jit, so XLA propagates param layouts
        into the optimizer moments — ZeRO/FSDP optimizer-state sharding
        with no extra code (this replaces the reference's FSDP2
        optimizer-param-swap dance, accelerator.py:1479-1547)."""
        if isinstance(optimizer, AcceleratedOptimizer):
            if not optimizer._is_accelerate_prepared:
                optimizer._is_accelerate_prepared = True
                optimizer.accelerator = self
                self._optimizers.append(optimizer)
            return optimizer
        opt = AcceleratedOptimizer(optimizer, accelerator=self)
        self._ensure_opt_state(opt)
        opt._is_accelerate_prepared = True
        self._optimizers.append(opt)
        return opt

    def _ensure_opt_state(self, opt: AcceleratedOptimizer, model: Optional[Model] = None):
        """Bind the optimizer to a prepared model and init its (sharded)
        state. Deferred when no model has been prepared yet, so argument
        order in ``prepare()`` doesn't matter.

        With ``ParallelismPlugin(shard_optimizer_state=True)`` (ZeRO-1/2;
        reference: utils/deepspeed.py:253-294) the state is born sharded
        over the ``data`` axis via ``out_shardings`` — params stay
        replicated, per-device optimizer memory divides by the dp degree.

        With ``ParallelismPlugin(offload_optimizer=True)`` (ZeRO-offload /
        FSDP cpu-offload analogue; reference: utils/dataclasses.py:1100-1180
        ``offload_optimizer_device``, accelerator.py:1694-1750 cpu_offload)
        the state is *born on* ``pinned_host`` memory-kind shardings — it
        never materialises in HBM — and the jitted step streams it through
        the device around the update (``_offload_transfers``). Composes
        with ZeRO: the host copy keeps the data-axis layout."""
        if opt.opt_state is not None:
            return
        model = model or getattr(opt, "_model", None) or (self._models[-1] if self._models else None)
        if model is None:
            return
        jax = _jax()
        zero1_fallback = None
        if self._zero1_active():
            # ZeRO-1's flat-segment update is only correct for transforms
            # that treat every parameter element independently; a factored
            # / coupled state (adafactor's row-col moments) would compute
            # a DIFFERENT update on the flat segments than on the real
            # leaves. Detect it structurally and fall back LOUDLY to the
            # passive shard_optimizer_state layout instead of silently
            # changing the optimizer's semantics.
            zero1_fallback = _nonelementwise_state_nodes(opt.optimizer)
            if zero1_fallback:
                names = ", ".join(sorted(zero1_fallback))
                if names not in _ZERO1_FALLBACK_WARNED:
                    _ZERO1_FALLBACK_WARNED.add(names)
                    logger.warning(
                        "zero_stage=1 requires an elementwise optax transform, but this "
                        "optimizer's state couples elements within a leaf (%s); falling "
                        "back to the passive shard_optimizer_state layout — the optimizer "
                        "state is GSPMD-sharded over the data axis but the update wire "
                        "stays the replicated all-reduce (no reduce-scatter/all-gather "
                        "split, no quantized update legs)",
                        names,
                    )
                opt._zero1_fallback = tuple(sorted(zero1_fallback))
        if self._zero1_active() and not zero1_fallback:
            layout = self._zero1_layout_for(model)
            if layout is not None:
                # ZeRO-1 explicit mode: the state is created over the FLAT
                # padded parameter vector and *born sharded* over the data
                # axes (jit + out_shardings) — per-device optimizer HBM is
                # 1/n from step 0, never materialised replicated
                def init_flat(p):
                    return opt.optimizer.init(layout.flatten_pad(p))

                state_shapes = jax.eval_shape(init_flat, model.params)
                shardings = layout.state_shardings(state_shapes, self.mesh)
                opt.opt_state = jax.jit(init_flat, out_shardings=shardings)(model.params)
                opt._zero_shardings = shardings
                opt._zero1_layout = layout
                # per-state-leaf true sizes: what elastic restore needs to
                # re-pad a shard checkpoint onto a different mesh
                opt._zero1_state_sizes = layout.state_true_sizes(state_shapes)
                opt._model = model
                return
        shardings = self._zero_state_shardings(opt.optimizer, model, force=bool(zero1_fallback))
        init_shardings = shardings
        if init_shardings is None and getattr(model, "param_shardings", None) is not None:
            # no ZeRO split: the state is still born in its parameters'
            # layout. Left to itself the zeros program puts every moment
            # whole on one device, uncommitted — more than a device holds
            # for a model that needed fsdp, and a second compile of the
            # train step once the first step hands the state back sharded
            from .parallel.sharding import zero_optimizer_shardings

            init_shardings = zero_optimizer_shardings(
                jax.eval_shape(opt.optimizer.init, model.params), model.param_shardings, self.mesh, axis=None
            )
        plugin = self.state.parallelism_plugin
        offload = plugin is not None and getattr(plugin, "offload_optimizer", False)
        if offload:
            from .utils.compat import supports_memory_kind

            if not supports_memory_kind("pinned_host"):
                logger.warning(
                    "offload_optimizer requested but the %s backend has no pinned_host "
                    "memory; optimizer state stays in device memory",
                    jax.default_backend(),
                )
                offload = False
        if offload:
            from .parallel.sharding import zero_optimizer_shardings

            state_shapes = jax.eval_shape(opt.optimizer.init, model.params)
            base = shardings
            if base is None:  # param-matched layout, no ZeRO split
                base = zero_optimizer_shardings(
                    state_shapes, getattr(model, "param_shardings", None), self.mesh, axis=None
                )
            # scalar leaves (adam's step count) stay in device memory: XLA's
            # SPMD partitioner rejects pinned_host placement on scalars
            # ("Side-effect HLO must have sharding"), and they're 4 bytes
            opt._offload_shardings = jax.tree_util.tree_map(
                lambda s, shape: s if getattr(shape, "ndim", 0) == 0 else s.with_memory_kind("pinned_host"),
                base,
                state_shapes,
            )
        opt.opt_state = jax.jit(opt.optimizer.init, out_shardings=init_shardings)(model.params)
        if getattr(opt, "_offload_shardings", None) is not None:
            # move to the pinned_host home OUTSIDE jit: memory-kind
            # out_shardings on init trip XLA's SPMD partitioner on the
            # constant scalar leaves ("Side-effect HLO must have sharding").
            # The transient HBM copy is just-born state (zeros for adam).
            opt.opt_state = jax.device_put(opt.opt_state, opt._offload_shardings)
        opt._zero_shardings = shardings
        opt._model = model

    def _offload_transfers(self, opt: AcceleratedOptimizer):
        """``(pull, push)`` for a host-offloaded optimizer state, or
        ``(None, None)`` when offload is off.

        ``pull`` runs INSIDE the jitted step, at its top level (never inside
        ``lax.cond`` — host-offload transfers are not legal in every
        control-flow position): a host->device stream XLA's latency-hiding
        scheduler can overlap with the forward/backward. ``push`` runs
        OUTSIDE jit, after the step returns: XLA's CPU backend has no
        device->pinned_host placement lowering inside a program (the
        ``annotate_device_placement`` custom call is unimplemented for Host
        targets, and the SPMD partitioner rejects it besides), while a plain
        ``jax.device_put`` after the fact is an async D2H copy on every
        backend. The updated state's device buffers are freed as soon as the
        copy lands, restoring the between-steps HBM saving."""
        host = getattr(opt, "_offload_shardings", None)
        if host is None:
            return None, None
        jax = _jax()
        kind = jax.devices()[0].default_memory().kind

        def pull(st):
            # per-leaf: only host-resident leaves transfer; scalar leaves
            # (device-kind home) pass through untouched
            return jax.tree_util.tree_map(
                lambda x, s: (
                    jax.device_put(x, s.with_memory_kind(kind)) if s.memory_kind == "pinned_host" else x
                ),
                st,
                host,
            )

        return pull, (lambda st: jax.device_put(st, host))

    def _zero_state_shardings(self, optax_tx, model: Model, force: bool = False):
        """ZeRO-1/2 ``NamedSharding`` pytree for ``optax_tx``'s state, or
        None when ``shard_optimizer_state`` is off / no data axis.
        ``force`` takes the passive layout regardless of the plugin flag
        (the zero_stage=1 non-elementwise fallback)."""
        plugin = self.state.parallelism_plugin
        if not force and (plugin is None or not getattr(plugin, "shard_optimizer_state", False)):
            return None
        from .parallel.mesh import data_parallel_size

        if data_parallel_size(self.mesh) <= 1:
            return None
        jax = _jax()
        from .parallel.sharding import zero_optimizer_shardings

        state_shapes = jax.eval_shape(optax_tx.init, model.params)
        return zero_optimizer_shardings(
            state_shapes, getattr(model, "param_shardings", None), self.mesh
        )

    def _zero1_active(self) -> bool:
        plugin = self.state.parallelism_plugin
        return plugin is not None and getattr(plugin, "zero_stage", 0) == 1

    def zero1_fallback_reason(self, optimizer) -> Optional[tuple]:
        """The offending optax state node names if ``zero_stage=1`` fell
        back to the passive layout for this (prepared) optimizer, else
        None."""
        return getattr(optimizer, "_zero1_fallback", None)

    def _zero1_layout_for(self, model: Model):
        """The :class:`~accelerate_tpu.parallel.zero.Zero1Layout` for this
        model on this mesh, or ``None`` when the data-parallel degree is 1
        (ZeRO-1 degenerates to the replicated update — nothing to shard).
        Validates the mode's preconditions: the only non-trivial mesh axes
        are the batch axes, and params are replicated over them."""
        from .parallel.mesh import BATCH_AXES
        from .parallel.zero import Zero1Layout, zero1_axes

        axes = zero1_axes(self.mesh)
        if not axes:
            return None
        bad = [a for a, s in dict(self.mesh.shape).items() if s > 1 and a not in BATCH_AXES]
        if bad:
            raise ValueError(
                f"zero_stage=1 shards the update over the batch axes only; "
                f"shard-bearing axes {bad} would need their own update semantics"
            )
        shardings = getattr(model, "param_shardings", None)
        if shardings is not None:
            import jax as _j

            for kp, s in _j.tree_util.tree_flatten_with_path(shardings)[0]:
                spec_axes = {
                    a
                    for entry in tuple(getattr(s, "spec", s) or ())
                    if entry is not None
                    for a in (entry if isinstance(entry, tuple) else (entry,))
                }
                used = spec_axes & set(axes)
                if used:
                    from .parallel.sharding import path_str

                    raise ValueError(
                        f"zero_stage=1 needs params replicated over the data axes, but "
                        f"{path_str(kp)} is sharded over {sorted(used)} — use plain FSDP "
                        "(ZeRO-3 layout) for parameter sharding instead"
                    )
        return Zero1Layout(model.params, self.mesh, axes=axes)

    def prepare_data_loader(
        self, data_loader, device_placement: Optional[bool] = None, slice_fn_for_dispatch=None, **kwargs
    ):
        """Extra ``kwargs`` (``batch_size``, ``shuffle``, ``seed``,
        ``collate_fn``, ``drop_last``) pass through to
        :func:`~accelerate_tpu.data_loader.prepare_data_loader` when the
        input is a raw dataset rather than a built loader."""
        if isinstance(data_loader, BaseDataLoader):
            if data_loader not in self._dataloaders:
                self._dataloaders.append(data_loader)
            return data_loader
        prepared = prepare_data_loader(
            data_loader,
            put_on_device=device_placement if device_placement is not None else self.device_placement,
            data_loader_config=self.dataloader_config,
            rng_types=self.rng_types,
            **kwargs,
        )
        self._dataloaders.append(prepared)
        return prepared

    def prepare_scheduler(self, scheduler) -> AcceleratedScheduler:
        if isinstance(scheduler, AcceleratedScheduler):
            return scheduler
        prepared = AcceleratedScheduler(
            scheduler,
            optimizers=self._optimizers,
            step_with_optimizer=self.step_scheduler_with_optimizer,
            split_batches=self.dataloader_config.split_batches,
        )
        prepared._is_accelerate_prepared = True
        self._schedulers.append(prepared)
        return prepared

    # ------------------------------------------------------------------ #
    # the jitted train step (fast path)
    # ------------------------------------------------------------------ #

    def _matmul_precision_ctx(self):
        """``mixed_precision="no"`` must mean REAL fp32: JAX's DEFAULT
        matmul precision decomposes fp32 operands into bf16 passes (TPU
        MXU and oneDNN CPU alike), which silently injects ~1e-3 relative
        error into every matmul. Tracing the jitted step inside this
        context pins fp32-mode matmuls to full precision; bf16/fp16
        policies keep the fast default. (The reference's fp32 is torch
        fp32 — true fp32 — so this is a parity requirement, not a
        preference.)"""
        import contextlib

        if self.mixed_precision == "no":
            return _jax().default_matmul_precision("highest")
        return contextlib.nullcontext()

    def _compute_cast(self, params):
        """fp32 master -> compute dtype, keeping norm-like params in fp32
        (the autocast policy; reference: accelerator.py:1590-1601)."""
        jnp = _jnp()
        jax = _jax()
        compute = jnp.dtype(self.state.dtype_policy.compute_dtype)
        if compute == jnp.float32 or not self.autocast_handler.enabled:
            return params
        from .parallel.sharding import path_str

        keep = tuple(self.autocast_handler.keep_fp32_patterns)

        def cast(kp, p):
            if not hasattr(p, "dtype") or not jnp.issubdtype(p.dtype, jnp.floating):
                return p
            path = path_str(kp).lower()
            if any(pat in path for pat in keep):
                return p
            return p.astype(compute)

        return jax.tree_util.tree_map_with_path(cast, params)

    def build_eval_step(self, eval_fn: Callable, model: Optional[Model] = None) -> Callable:
        """Jitted inference counterpart of :meth:`build_train_step`.

        ``eval_fn(params, *args)`` — or ``eval_fn(params, state, *args)``
        when the model carries mutable state (BatchNorm). Returns
        ``step(*args)`` reading the model's CURRENT params/state each call.
        The reference's eval loop just calls the module (torch eager is
        fine there); in JAX an unjitted forward dispatches op-by-op, which
        is pathological on TPU — always evaluate through a jitted step.
        """
        jax = _jax()
        model = model or self._models[-1]
        compute_cast = self._compute_cast
        jitted = jax.jit(lambda p, *args, **kwargs: eval_fn(compute_cast(p), *args, **kwargs))
        if self._program_cache is not None and self.compile_handler.aot_train_step:
            jitted = self._program_cache.wrap_jit(jitted, name="eval_step")
        ctx = self._matmul_precision_ctx

        def run(*args, **kwargs):
            with ctx():
                if getattr(model, "state", None) is not None:
                    return jitted(model.params, model.state, *args, **kwargs)
                return jitted(model.params, *args, **kwargs)

        return run

    def lint(
        self,
        step_fn: Callable,
        *sample_args,
        donate_argnums=(),
        in_shardings=None,
        ignore=(),
        divergence: bool = True,
    ):
        """Statically lint ``step_fn`` against this accelerator's mesh
        *before* paying a multi-chip compile (tier-1 jaxpr analysis:
        collective axis names, silent bf16/fp8->f32 promotion, buffer
        donation, output sharding constraints — see
        docs/usage_guides/static_analysis.md for the rule catalogue).

        ``sample_args`` are traced abstractly (``jax.ShapeDtypeStruct``s
        or real arrays — nothing executes, nothing compiles); concrete
        arrays contribute their ``NamedSharding`` to the TPU104 check.

        With ``divergence=True`` (the default) the multi-host divergence
        analyzer (TPU4xx, ``analysis.divergence``) also runs over the
        *calling module's* source: collectives or barriers that not every
        rank reaches, rank-divergent loop trip counts, unguarded host
        writes — the deadlocks a single-program trace cannot see.

        Returns the list of :class:`~accelerate_tpu.analysis.Finding`;
        error-severity findings are also logged. Suppress individual rules
        with ``ignore=("TPU103",)``.
        """
        from .analysis import lint_step, render_text

        findings = lint_step(
            step_fn,
            *sample_args,
            mesh=self.mesh,
            donate_argnums=donate_argnums,
            in_shardings=in_shardings,
            ignore=ignore,
        )
        if divergence:
            findings += self._lint_calling_module(ignore=ignore, depth=2)
        if any(f.is_error for f in findings):
            logger.warning("lint found issues in %s:\n%s", getattr(step_fn, "__name__", "step_fn"), render_text(findings))
        return findings

    def _lint_calling_module(self, ignore=(), depth: int = 1):
        """Run the TPU4xx divergence analyzer over the source file of the
        caller ``depth`` frames up. Quietly returns ``[]`` when the caller
        has no readable ``.py`` source (REPL, notebook, frozen app)."""
        import sys

        try:
            frame = sys._getframe(depth)
        except ValueError:
            return []
        path = frame.f_globals.get("__file__") if frame is not None else None
        if not path or not str(path).endswith(".py") or not os.path.exists(path):
            return []
        from .analysis.divergence import analyze_file
        from .analysis.project_config import load_project_config

        cfg = load_project_config(os.path.dirname(os.path.abspath(path)))
        try:
            findings = analyze_file(path, n_ranks=max(3, cfg.resolve_ranks(None)), ignore=cfg.merge_ignore(ignore))
        except (OSError, RecursionError):
            return []
        return cfg.apply_suppressions(findings)

    def flight_check(
        self,
        step_fn: Callable,
        *sample_args,
        donate_argnums=(),
        in_shardings=None,
        generation: str = "v5e",
        ignore=(),
    ):
        """Static SPMD flight-check of ``step_fn`` against this
        accelerator's mesh, *before* paying a multi-chip compile: a
        per-device peak-HBM estimate (liveness walk with donated-buffer
        reuse and sharding-aware byte counts), the collective traffic bill
        (bytes on wire, ICI vs DCN, per-step totals), and the TPU3xx
        safety rules — collective under value-dependent ``cond``/``while``
        (deadlock), implicit reshards, donation defeated by a late read.

        Same calling convention as :meth:`lint`; returns a
        :class:`~accelerate_tpu.analysis.FlightReport` (``.render_text()``
        for the human report, ``.as_dict()`` for tooling,
        ``.fits(hbm_gb)`` for a go/no-go). Error-severity findings are
        logged. See ``docs/usage_guides/static_analysis.md``.
        """
        from .analysis import flight_check as _flight_check
        from .analysis import render_text

        report = _flight_check(
            step_fn,
            *sample_args,
            mesh=self.mesh,
            donate_argnums=donate_argnums,
            in_shardings=in_shardings,
            generation=generation,
            ignore=ignore,
        )
        if not report.ok:
            logger.warning(
                "flight-check found issues in %s:\n%s",
                getattr(step_fn, "__name__", "step_fn"),
                render_text(report.findings),
            )
        if self._telemetry is not None and report.peak_hbm_bytes:
            # seed the runtime HBM drift check with the static prediction
            self._telemetry.set_static_hbm_estimate(report.peak_hbm_bytes)
        return report

    def perf_check(
        self,
        step_fn: Callable,
        *sample_args,
        in_shardings=None,
        dcn=None,
        generation: Optional[str] = None,
        ignore=(),
    ):
        """Static roofline of ``step_fn`` against this accelerator's mesh,
        *before* paying a multi-chip compile: per-op FLOPs / HBM bytes /
        bytes-on-wire, compute/memory/comms-bound classification, the
        predicted step time and MFU upper bound for the attached
        generation, plus the TPU5xx efficiency rules (MXU tile
        misalignment, redundant collectives, latency-bound small DCN
        collectives, missed collective/compute overlap, f32 matmuls that
        are safely bf16).

        Same calling convention as :meth:`flight_check`; returns a
        :class:`~accelerate_tpu.analysis.PerfReport` (``.render_text()``
        for the human report, ``.as_dict()`` for tooling /
        ``accelerate-tpu perf-check --baseline`` diffs). Error-severity
        findings are logged. When telemetry is live
        (:class:`~accelerate_tpu.utils.TelemetryKwargs`), the predicted
        step time seeds the runtime ``perf_model_drift`` cross-check —
        the measured steady-state step split is compared against this
        static prediction so the model stays honest. See
        ``docs/usage_guides/static_analysis.md`` and
        ``docs/usage_guides/performance.md``.
        """
        from .analysis import render_text
        from .analysis.perfmodel import perf_check as _perf_check

        report = _perf_check(
            step_fn,
            *sample_args,
            mesh=self.mesh,
            in_shardings=in_shardings,
            dcn=dcn,
            generation=generation,
            ignore=ignore,
        )
        if not report.ok:
            logger.warning(
                "perf-check found issues in %s:\n%s",
                getattr(step_fn, "__name__", "step_fn"),
                render_text(report.findings),
            )
        if self._telemetry is not None and report.predicted_step_ms > 0:
            # seed the runtime perf-model drift check with the prediction
            self._telemetry.set_static_step_estimate(report.predicted_step_ms)
        return report

    def pipe_check(
        self,
        target,
        *sample_args,
        num_microbatches: Optional[int] = None,
        axis_name: str = "pipe",
        interleave: int = 1,
        remat: bool = False,
        stage_layers=None,
        dcn=None,
        generation: Optional[str] = None,
        hbm_gb: Optional[float] = None,
        ignore=(),
    ):
        """Static pipeline-schedule analysis of ``target`` *before*
        paying a multi-chip compile: per-stage rooflines and remat-aware
        peak HBM, bubble fraction vs the ideal ``(S-1)/(M+S-1)``,
        exposed-vs-hidden handoff time under ``interleave``, and the
        bubble-adjusted predicted step time ``(M+S-1) x max-stage tick``,
        plus the TPU8xx schedule rules (pipeline cut on the fast link
        while DCN exists, stage imbalance, bubble over threshold with
        the covering ``num_microbatches`` priced, collectives over the
        pipe axis inside the tick body — error severity — and per-stage
        activations over the HBM budget).

        ``target`` is a step function whose trace contains the
        ``parallel.pipeline`` schedule (analyzed against this
        accelerator's mesh), a
        :class:`~accelerate_tpu.analysis.PipelineSpec`, or a
        :class:`~accelerate_tpu.parallel.pipeline.PipelinedModel` (plus
        its sample inputs) — specs and models carry their own mesh.
        Returns a :class:`~accelerate_tpu.analysis.PipeReport`
        (``.render_text()`` / ``.as_dict()``). Error-severity findings
        are logged. When telemetry is live, the bubble-adjusted
        prediction seeds the runtime ``perf_model_drift`` cross-check,
        same as :meth:`perf_check`. See
        ``docs/usage_guides/pipeline.md`` and
        ``docs/usage_guides/static_analysis.md``.
        """
        from .analysis import render_text
        from .analysis.pipemodel import PipelineSpec, pipe_check as _pipe_check
        from .parallel.pipeline import PipelinedModel

        report = _pipe_check(
            target,
            *sample_args,
            mesh=None if isinstance(target, (PipelineSpec, PipelinedModel)) else self.mesh,
            num_microbatches=num_microbatches,
            axis_name=axis_name,
            interleave=interleave,
            remat=remat,
            stage_layers=stage_layers,
            dcn=dcn,
            generation=generation,
            hbm_gb=hbm_gb,
            ignore=ignore,
        )
        if not report.ok:
            logger.warning(
                "pipe-check found issues in %s:\n%s",
                report.fn_name,
                render_text(report.findings),
            )
        if self._telemetry is not None and report.predicted_step_ms > 0:
            # the bubble-adjusted prediction seeds the drift watchdog
            self._telemetry.set_static_step_estimate(report.predicted_step_ms)
        return report

    def kernel_check(
        self,
        step_fn: Callable,
        *sample_args,
        generation: Optional[str] = None,
        probe: bool = True,
        ignore=(),
    ):
        """Static Pallas kernel analysis of ``step_fn`` against this
        accelerator's mesh, *before* paying a compile: every
        ``pl.pallas_call`` site is extracted from the traced jaxpr (grid,
        BlockSpecs, concretely re-evaluated index maps, in/out aliases)
        and checked with the TPU10xx rules — per-block VMEM occupancy vs
        the generation's capacity, MXU/VPU tile alignment, index-map
        coverage/races, grid-loop-carried alias hazards, and the
        registered :class:`~accelerate_tpu.kernels.KernelCostSpec`
        contracts (an unregistered call is TPU1005 error-severity; a
        declaration drifting from the interpret-mode count is TPU1006).
        On CPU the kernels are also executed in Pallas interpret mode as
        a finiteness probe.

        Same calling convention as :meth:`flight_check`; returns a
        :class:`~accelerate_tpu.analysis.KernelReport`
        (``.render_text()`` / ``.as_dict()``). Error-severity findings
        are logged. See ``docs/usage_guides/kernels.md`` and
        ``docs/usage_guides/static_analysis.md``.
        """
        from .analysis import render_text
        from .analysis.kernelmodel import kernel_check as _kernel_check

        report = _kernel_check(
            step_fn,
            *sample_args,
            mesh=self.mesh,
            generation=generation,
            probe=probe,
            ignore=ignore,
        )
        if not report.ok:
            logger.warning(
                "kernel-check found issues in %s:\n%s",
                getattr(step_fn, "__name__", "step_fn"),
                render_text(report.findings),
            )
        return report

    def numerics_check(
        self,
        step_fn: Callable,
        *sample_args,
        assume=None,
        ignore=(),
    ):
        """Static numerics & precision analysis of ``step_fn`` against
        this accelerator's mesh, *before* paying a multi-chip compile:
        a value-interval + dtype-provenance abstract interpretation of
        the traced jaxpr (widening through ``scan``/``while``, joins
        across ``cond`` branches, relational softmax refinements) plus
        the TPU6xx precision rules — low-precision accumulation over
        long axes, provable fp16/fp8 overflow, unguarded div/log/rsqrt
        over zero, weight updates below the param ulp, PRNG key reuse,
        and compressed collectives without error feedback. Every finding
        prices its impact (relative-error bound, overflow margin, or
        lost-update ulp).

        ``assume=(lo, hi)`` states the input-value assumption the proofs
        are relative to (default ±16). Same calling convention as
        :meth:`perf_check`; returns a
        :class:`~accelerate_tpu.analysis.NumericsReport`
        (``.render_text()`` for the human report, ``.as_dict()`` for
        tooling). Error-severity findings are logged. The runtime
        counterpart is the opt-in telemetry
        :class:`~accelerate_tpu.telemetry.NonFiniteWatchdog`
        (``TelemetryKwargs(nonfinite_every=N)``). See
        ``docs/usage_guides/static_analysis.md`` and
        ``docs/usage_guides/low_precision.md``.
        """
        from .analysis import render_text
        from .analysis.numerics import numerics_check as _numerics_check

        report = _numerics_check(
            step_fn,
            *sample_args,
            mesh=self.mesh,
            assume=assume,
            ignore=ignore,
        )
        if not report.ok:
            logger.warning(
                "numerics-check found issues in %s:\n%s",
                getattr(step_fn, "__name__", "step_fn"),
                render_text(report.findings),
            )
        return report

    def tune(
        self,
        workload: Callable,
        *sample_args,
        space=None,
        generation: Optional[str] = None,
        hbm_gb: Optional[float] = None,
        top_k: int = 3,
        confirm: bool = False,
        confirm_steps: int = 8,
        shape_histogram=None,
        optimizer=None,
        ignore=(),
    ):
        """Search configuration space for the fastest feasible config of
        ``workload`` with the static analyzers as the oracle — ROADMAP
        item 4 paid off: every candidate the
        :class:`~accelerate_tpu.analysis.SearchSpace` enumerates is
        constraint-pruned, flight-checked (static peak HBM vs the
        generation's capacity — the TPU701 feasibility prune), and
        rooflined (:meth:`perf_check`'s predicted step time / MFU bound,
        costmodel wire bytes as the tiebreak), all statically, in
        milliseconds per candidate, before anything compiles.

        ``workload`` is a plain step function (``sample_args`` traced
        abstractly; the mesh/bucket knobs vary around it) or a workload
        factory — any callable with a truthy ``tune_factory`` attribute,
        called as ``workload(point) -> (step_fn, sample_args)`` per
        candidate. ``space=None`` searches the default neighborhood over
        this accelerator's device pool
        (:func:`~accelerate_tpu.analysis.default_space`). With
        ``confirm=True`` the top-``top_k`` candidates are measured with
        short :class:`~accelerate_tpu.telemetry.StepTelemetry` runs and
        the report carries predicted-vs-measured rank agreement.

        Returns a :class:`~accelerate_tpu.analysis.TuneReport`
        (``.render_text()``, ``.as_dict()``, ``.winner``,
        ``.chosen_toml()`` — the ``[tune.chosen]`` block to commit into
        ``.tpulint.toml``; ``analysis.load_chosen()`` +
        ``ConfigPoint.parallelism_kwargs()`` feed it back into
        :class:`~accelerate_tpu.utils.ParallelismPlugin`). The winner is
        logged. See ``docs/usage_guides/autotuning.md``.
        """
        from .analysis import default_space
        from .analysis.tuner import tune as _tune

        jax = _jax()
        if space is None:
            space = default_space(len(jax.devices()))
        report = _tune(
            workload,
            space,
            *sample_args,
            base_mesh=self.mesh,
            generation=generation,
            hbm_gb=hbm_gb,
            top_k=top_k,
            confirm=confirm,
            confirm_steps=confirm_steps,
            shape_histogram=shape_histogram,
            optimizer=optimizer,
            ignore=ignore,
        )
        if report.winner is not None:
            logger.info(
                "tune: winner %s — predicted %.3f ms (of %d candidates, %d pruned, %d infeasible)",
                report.winner.label,
                (report.winner.predicted_step_us or 0.0) / 1000.0,
                len(report.candidates),
                report.pruned_count,
                report.infeasible_count,
            )
        else:
            logger.warning("tune: no feasible candidate (of %d)", len(report.candidates))
        return report

    def build_train_step(
        self,
        loss_fn: Callable,
        model: Optional[Model] = None,
        optimizer: Optional[AcceleratedOptimizer] = None,
        scheduler: Optional[AcceleratedScheduler] = None,
        has_aux: bool = False,
        has_state: bool = False,
        donate: bool = True,
    ) -> Callable:
        """Build the single jitted train step (reference hot loop §3.4
        collapsed into one XLA program).

        ``loss_fn(params, batch) -> loss`` (or ``(loss, aux)``). The
        returned ``step(batch)`` mutates the prepared model/optimizer in
        place (their pytrees are swapped each call) and returns the loss
        (plus aux), keeping per-step python under a microsecond-scale
        dispatch. Gradient accumulation runs as a branchless on-device
        buffer: every call accumulates; on sync boundaries the update
        applies and the buffer zeroes — ``1/accum``-weighted so the applied
        gradient is the mean over microbatches.

        ``has_state=True`` threads non-trainable mutable collections
        (flax ``batch_stats`` et al.) through the step: ``model.state`` is
        passed as the second argument — ``loss_fn(params, state, batch[,
        rng])`` — and the loss_fn returns ``(loss, new_state)`` (or
        ``(loss, (new_state, aux))`` with ``has_aux``). The state updates
        every microbatch, gradient-free. The reference has no analogue
        (torch BN mutates buffers in place); in JAX the state is explicit.

        With ``ParallelismPlugin(zero_stage=1)`` the grad-pmean →
        replicated-update wire is replaced by reduce-scatter grads →
        per-replica 1/n flat-segment optimizer update (state born
        sharded) → all-gather updates, optionally with int8/fp8/bf16
        quantized legs carrying error feedback
        (``grad_compression``) — see
        ``docs/usage_guides/zero_redundancy.md``. fp32 parity with the
        replicated path is bit-exact; ``do_sync`` turns static (two
        compiled variants, the offload pattern).
        """
        jax = _jax()
        jnp = _jnp()
        model = model or self._models[-1]
        optimizer = optimizer or (self._optimizers[-1] if self._optimizers else None)
        if optimizer is None:
            raise ValueError("prepare() an optimizer before building a train step")
        self._ensure_opt_state(optimizer, model)
        scheduler = scheduler or (self._schedulers[-1] if self._schedulers else None)
        accum = self.gradient_accumulation_steps
        use_fp16 = self.mixed_precision == "fp16"
        compute_cast = self._compute_cast
        apply_gradients = self._make_gradient_applier(optimizer.optimizer)
        # loss_fn(params, batch) or loss_fn(params, batch, rng) — the rng
        # variant gets a per-step folded key (dropout etc.). With has_state
        # the state slots in before batch: loss_fn(params, state, batch[, rng]).
        # Opt-in is by arity (a required positional beyond batch) OR by a
        # parameter literally named ``rng`` (covers optional-rng losses like
        # functools.partial(bert_classification_loss, apply_fn=...), whose
        # ``rng=None`` is keyword-with-default). Bound keyword arguments
        # from partial must NOT count toward arity.
        import inspect

        try:
            sig_params = inspect.signature(loss_fn).parameters
            n_loss_args = sum(
                1
                for p in sig_params.values()
                if p.default is inspect.Parameter.empty
                and p.kind in (inspect.Parameter.POSITIONAL_ONLY, inspect.Parameter.POSITIONAL_OR_KEYWORD)
            )
            has_rng_param = "rng" in sig_params
        except (TypeError, ValueError):  # builtins / C callables
            n_loss_args, has_rng_param = (3 if has_state else 2), False
        if n_loss_args >= (4 if has_state else 3):
            rng_mode = "positional"
        elif has_rng_param:
            # optional rng must go by keyword: a partial that bound an
            # earlier parameter by keyword rejects extra positionals
            rng_mode = "keyword"
        else:
            rng_mode = "none"

        def call_loss(p, mstate, batch, rng):
            lead = (p, mstate, batch) if has_state else (p, batch)
            if rng_mode == "positional":
                return loss_fn(*lead, rng)
            if rng_mode == "keyword":
                return loss_fn(*lead, rng=rng)
            return loss_fn(*lead)

        h = self.scaler_handler
        growth_factor = float(getattr(h, "growth_factor", 2.0))
        backoff_factor = float(getattr(h, "backoff_factor", 0.5))
        growth_interval = int(getattr(h, "growth_interval", 2000))

        def update_scale_state(scale_state, finite, do_sync):
            """The fp16 dynamic-loss-scale transition (torch GradScaler
            semantics, applied only on sync boundaries) — shared by the
            replicated, compressed, and ZeRO-1 paths."""
            if not use_fp16:
                return scale_state
            loss_scale = scale_state["scale"]
            grown = scale_state["growth"] + 1
            do_grow = grown >= growth_interval
            upd_scale = jnp.where(
                finite,
                jnp.where(do_grow, loss_scale * growth_factor, loss_scale),
                jnp.maximum(1.0, loss_scale * backoff_factor),
            )
            upd_growth = jnp.where(finite & ~do_grow, grown, 0)
            return {
                "scale": jnp.where(do_sync, upd_scale, loss_scale),
                "growth": jnp.where(do_sync, upd_growth, scale_state["growth"]),
            }

        compress_method = getattr(self.state.parallelism_plugin, "grad_compression", None)
        zero_layout = getattr(optimizer, "_zero1_layout", None)
        psgd_rank = None
        if compress_method is not None and zero_layout is None:
            bad = [a for a, s in dict(self.mesh.shape).items() if s > 1 and a != "data"]
            if bad:
                raise ValueError(
                    f"grad_compression reduces over the 'data' axis only; shard-bearing axes {bad} "
                    "would need their own reduction semantics (or compose with zero_stage=1, "
                    "which shards the update over the batch axes)"
                )
            from .parallel.compression import powersgd_rank

            psgd_rank = powersgd_rank(compress_method)

        def parse_out(out, mstate_in):
            """Normalise a loss_fn return to ``(loss, new_state, aux)``
            under the has_state/has_aux contract — shared by the implicit
            path, the compressed-psum path, and the ZeRO-1 path (one
            definition, so the three can never disagree on the protocol)."""
            if has_state:
                loss, rest = out
                new_state, aux = rest if has_aux else (rest, None)
            else:
                loss, aux = out if has_aux else (out, None)
                new_state = mstate_in
            return loss, new_state, aux

        offload_pull, offload_push = self._offload_transfers(optimizer)

        zero_fns = None
        if zero_layout is not None:
            # ZeRO-1 explicit wire: reduce-scatter grads -> per-segment
            # optimizer update -> all-gather updates, the whole update
            # inside ONE shard_map over the batch axes. Two compiled
            # variants keyed on a STATIC do_sync (the offload pattern):
            # the non-sync microbatch program is grads + reduce-scatter +
            # accumulate only, and no collective ever sits under a
            # value-dependent cond (TPU301).
            from jax.sharding import PartitionSpec as P

            from .parallel.collectives import pmean_floats
            from .parallel.zero import (
                all_gather_updates,
                reduce_scatter_grads,
                shard_index,
                sharded_global_norm,
                zero1_comp_specs,
            )

            zaxes, z_n = zero_layout.axes, zero_layout.n
            z_tx = optimizer.optimizer
            inv_n = 1.0 / z_n  # powers of two stay exact scalings
            opt_specs = zero_layout.state_specs(optimizer.opt_state)
            buf_specs = jax.tree_util.tree_unflatten(
                zero_layout.treedef, [zero_layout.flat_spec()] * len(zero_layout.padded)
            )
            comp_specs = zero1_comp_specs(zero_layout, compress_method)

            def zero_body(sync):
                def body(params, opt_local, buf_local, mstate_in, local_batch, ls, key, clip, cstate):
                    def local_loss(q):
                        out = call_loss(compute_cast(q), mstate_in, local_batch, key)
                        loss, new_state, aux = parse_out(out, mstate_in)
                        return loss.astype(jnp.float32) * ls, (loss, new_state, aux)

                    g, (loss, new_state, aux) = jax.grad(local_loss, has_aux=True)(params)
                    # 1/n BEFORE the wire: local losses are means over the
                    # LOCAL shard, so the reduce-scatter sum of g/n equals
                    # the baseline's implicit global pmean — and the later
                    # /denom lands AFTER the reduction, exactly where the
                    # replicated path divides (bit-exact fp32 parity)
                    if compress_method is None:
                        g = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32) * inv_n, g)
                        g_shard, _ = reduce_scatter_grads(
                            zero_layout.flatten_pad(g), zaxes, z_n, None, None
                        )
                        denom = jnp.maximum(ls, 1.0) * accum
                        g_shard = jax.tree_util.tree_map(lambda l: l / denom, g_shard)
                        new_cstate = cstate
                    else:
                        # unscale BEFORE quantizing: the error-feedback
                        # residual must live in true gradient units, or a
                        # dynamic loss-scale change mis-weights the carry.
                        # The scaler clamps the scale at >= 1 (backoff
                        # floor), so the maximum() is an exact no-op that
                        # makes the division provably guarded (TPU603)
                        g = jax.tree_util.tree_map(
                            lambda l: l.astype(jnp.float32) / jnp.maximum(ls, 1.0) * inv_n, g
                        )
                        rs_err = jax.tree_util.tree_map(lambda e: e[0], cstate["rs_error"])
                        if use_fp16:
                            # one overflowed microbatch must not poison the
                            # carried residual (the PowerSGD discipline):
                            # keep the old carry and hand NaN shards to the
                            # sync-boundary finite gate
                            ok = jnp.bool_(True)
                            for l in jax.tree_util.tree_leaves(g):
                                ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(l)))
                            ok = jax.lax.psum(ok.astype(jnp.int32), zaxes) == jax.lax.psum(1, zaxes)
                        g_shard, new_rs = reduce_scatter_grads(
                            zero_layout.flatten_pad(g), zaxes, z_n, compress_method, rs_err
                        )
                        if use_fp16:
                            g_shard = jax.tree_util.tree_map(
                                lambda l: jnp.where(ok, l, jnp.float32(jnp.nan)), g_shard
                            )
                            new_rs = jax.tree_util.tree_map(
                                lambda new, old: jnp.where(ok, new, old), new_rs, rs_err
                            )
                        g_shard = jax.tree_util.tree_map(lambda l: l / accum, g_shard)
                        new_cstate = {
                            "rs_error": jax.tree_util.tree_map(lambda e: e[None], new_rs),
                            "ag_error": cstate["ag_error"],
                        }
                    buf_local = jax.tree_util.tree_map(lambda b, s: b + s, buf_local, g_shard)
                    loss = jax.lax.pmean(loss, zaxes)
                    new_state = pmean_floats(new_state, zaxes)
                    aux = pmean_floats(aux, zaxes)
                    if not sync:
                        return (
                            params, opt_local, buf_local, new_state, loss,
                            jnp.float32(0.0), jnp.bool_(True), aux, new_cstate,
                        )
                    # sync boundary: the global norm is a psum of local
                    # partial sums over the shards — never a gather
                    gnorm = sharded_global_norm(buf_local, zaxes)
                    cscale = jnp.where(clip >= 0, jnp.minimum(1.0, clip / (gnorm + 1e-6)), 1.0)
                    gbuf = jax.tree_util.tree_map(lambda t: t * cscale, buf_local)
                    finite = jnp.isfinite(gnorm)
                    idx = shard_index(zaxes, zero_layout.mesh_shape)
                    p_local = zero_layout.local_slice(zero_layout.flatten_pad(params), idx)

                    def do_update(_):
                        return z_tx.update(gbuf, opt_local, p_local)

                    def hold(_):
                        return jax.tree_util.tree_map(jnp.zeros_like, gbuf), opt_local

                    if use_fp16:
                        updates, new_opt = jax.lax.cond(finite, do_update, hold, operand=None)
                    else:
                        updates, new_opt = do_update(None)
                    if compress_method is None:
                        # exact path: apply the update to the param segment
                        # INSIDE the shard body — the add fuses with the
                        # optimizer chain exactly as the replicated path's
                        # does (same FMA opportunities, bit-exact fp32
                        # parity) — and all-gather the new segments
                        new_seg = jax.tree_util.tree_map(
                            lambda p, u: p + u.astype(p.dtype), p_local, updates
                        )
                        p_full, _ = all_gather_updates(new_seg, zaxes, z_n, None, None)
                        new_params = zero_layout.unflatten(p_full)
                    else:
                        # quantized path: gather the quantized UPDATES (not
                        # params — update deltas are small-range and carry
                        # per-rank error feedback; every replica applies the
                        # IDENTICAL decoded vector, so params never drift)
                        ag_err = cstate["ag_error"]
                        if use_fp16:
                            # a held step must not flush the pending
                            # residual into the params
                            ag_err = jax.tree_util.tree_map(
                                lambda e: jnp.where(finite, e, jnp.zeros_like(e)), ag_err
                            )
                        u_full, new_ag = all_gather_updates(
                            updates, zaxes, z_n, compress_method, ag_err
                        )
                        if use_fp16:
                            new_ag = jax.tree_util.tree_map(
                                lambda a, b: jnp.where(finite, a, b), new_ag, cstate["ag_error"]
                            )
                        new_cstate = {**new_cstate, "ag_error": new_ag}
                        new_params = jax.tree_util.tree_map(
                            lambda p, u: p + u.astype(p.dtype), params, zero_layout.unflatten(u_full)
                        )
                    zero_buf = jax.tree_util.tree_map(jnp.zeros_like, buf_local)
                    return new_params, new_opt, zero_buf, new_state, loss, gnorm, finite, aux, new_cstate

                return jax.shard_map(
                    body,
                    mesh=self.mesh,
                    in_specs=(P(), opt_specs, buf_specs, P(), P(zaxes), P(), P(), P(), comp_specs),
                    out_specs=(P(), opt_specs, buf_specs, P(), P(), P(), P(), P(), comp_specs),
                    check_vma=False,
                )

            zero_fns = {True: zero_body(True), False: zero_body(False)}

        def train_step(params, opt_state, grad_buf, mstate, batch, scale_state, do_sync, rng, clip_norm, comp_state):
            # With offload, do_sync is a STATIC python bool (two compiled
            # variants): a non-sync microbatch's program never touches the
            # host-resident state, so grad accumulation amortizes the
            # host<->HBM stream to once per sync boundary instead of
            # multiplying it. Without offload it stays a traced scalar.
            static_sync = isinstance(do_sync, bool)
            if offload_pull is not None and (not static_sync or do_sync):
                # host->HBM stream at the top of the program (not inside the
                # sync cond — see _offload_transfers)
                opt_state = offload_pull(opt_state)
            loss_scale = scale_state["scale"]
            new_comp_state = comp_state

            if zero_fns is not None:
                # the whole reduce-scatter/update/all-gather step runs in
                # one shard_map; do_sync is static (see zero_fns above)
                (new_params, new_opt, new_buf, new_state, loss, gnorm, finite, aux, new_comp_state) = (
                    zero_fns[bool(do_sync)](
                        params, opt_state, grad_buf, mstate, batch, loss_scale, rng, clip_norm, comp_state
                    )
                )
                return (
                    new_params, new_opt, new_buf, new_state, loss, gnorm, finite, aux,
                    update_scale_state(scale_state, finite, do_sync), new_comp_state,
                )

            def scaled_loss(p):
                out = call_loss(compute_cast(p), mstate, batch, rng)
                loss, new_state, aux = parse_out(out, mstate)
                return loss.astype(jnp.float32) * loss_scale, (loss, new_state, aux)

            if compress_method is not None:
                # explicit per-shard grads + compressed psum (the DDP comm
                # hook analogue) instead of XLA's implicit f32 reduction.
                # Mutable state / aux ride along per microbatch: each shard
                # computes them on its local batch and the float leaves are
                # pmean'd (cross-replica BatchNorm-sync semantics — the
                # closest SPMD analogue of the implicit path's global-batch
                # statistics).
                from jax.sharding import PartitionSpec as P

                from .parallel.collectives import pmean_floats
                from .parallel.compression import compressed_psum_mean, powersgd_psum_mean

                def local_grads(p, mstate_in, local_batch, ls, key, cstate):
                    def local_loss(q):
                        out = call_loss(compute_cast(q), mstate_in, local_batch, key)
                        loss, new_state, aux = parse_out(out, mstate_in)
                        return loss.astype(jnp.float32) * ls, (loss, new_state, aux)

                    g, (local_l, new_state, aux) = jax.grad(local_loss, has_aux=True)(p)
                    local_l = jax.lax.pmean(local_l, "data")
                    new_state = pmean_floats(new_state, "data")
                    aux = pmean_floats(aux, "data")
                    # unscale BEFORE compressing: the PowerSGD residual (and
                    # the int8 quantization error) must live in true gradient
                    # units, or every dynamic loss-scale change mis-weights
                    # the carried/rounded feedback by scale_old/scale_new
                    g = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32) / ls, g)
                    if psgd_rank is None:
                        g = compressed_psum_mean(g, "data", compress_method)
                        return g, local_l, new_state, aux, cstate
                    # PowerSGD: one non-finite microbatch (fp16 overflow)
                    # must not poison the carried residual/Q — keep the old
                    # state and let the non-finite reduced gradient trip the
                    # sync-boundary finite gate (params held, buffer zeroed,
                    # scale backed off) exactly like the uncompressed path
                    ok = jnp.bool_(True)
                    for l in jax.tree_util.tree_leaves(g):
                        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(l)))
                    ok = jax.lax.psum(ok.astype(jnp.int32), "data") == jax.lax.psum(1, "data")
                    local = {
                        "error": jax.tree_util.tree_map(lambda e: e[0], cstate["error"]),
                        "q": cstate["q"],
                    }
                    g, new_local = powersgd_psum_mean(g, "data", local, psgd_rank)
                    new_local = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(ok, new, old), new_local, local
                    )
                    new_cstate = {
                        "error": jax.tree_util.tree_map(lambda e: e[None], new_local["error"]),
                        "q": new_local["q"],
                    }
                    return g, local_l, new_state, aux, new_cstate

                comp_spec = {"error": P("data"), "q": P()} if psgd_rank is not None else {}
                sm = jax.shard_map(
                    local_grads,
                    mesh=self.mesh,
                    in_specs=(P(), P(), P(("data", "fsdp")), P(), P(), comp_spec),
                    out_specs=(P(), P(), P(), P(), comp_spec),
                    check_vma=False,
                )
                grads, loss, new_state, aux, new_comp_state = sm(
                    params, mstate, batch, loss_scale, rng, comp_state
                )
            else:
                grads, (loss, new_state, aux) = jax.grad(scaled_loss, has_aux=True)(params)
            # compressed grads are already unscaled inside local_grads.
            # The scaler clamps the loss scale at >= 1 (backoff floor), so
            # the maximum() is an exact no-op that encodes the invariant —
            # and makes the division provably guarded (numerics TPU603)
            denom = accum if compress_method is not None else (jnp.maximum(loss_scale, 1.0) * accum)
            grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32) / denom, grads)
            grad_buf = jax.tree_util.tree_map(lambda b, g: b + g, grad_buf, grads)

            def hold(operand):
                params, opt_state, grad_buf = operand
                return params, opt_state, grad_buf, jnp.float32(0.0), jnp.bool_(True)

            if accum == 1 or (static_sync and do_sync):
                new_params, new_opt, new_buf, gnorm, finite = apply_gradients(
                    (params, opt_state, grad_buf), clip_norm
                )
            elif static_sync:  # non-sync microbatch, compiled without the update
                new_params, new_opt, new_buf, gnorm, finite = hold((params, opt_state, grad_buf))
            else:
                new_params, new_opt, new_buf, gnorm, finite = jax.lax.cond(
                    do_sync,
                    lambda op: apply_gradients(op, clip_norm),
                    hold,
                    (params, opt_state, grad_buf),
                )
            applied = accum == 1 or not static_sync or do_sync
            if zero_shardings is not None:
                # pin the ZeRO-1/2 layout so XLA keeps moments (and the
                # accumulation buffer: ZeRO-2) data-sharded across steps.
                # Skip the (unchanged, possibly host-resident) state on a
                # static non-sync program — the constraint would force a
                # pointless transfer.
                if applied:
                    new_opt = jax.lax.with_sharding_constraint(new_opt, zero_shardings)
                new_buf = jax.lax.with_sharding_constraint(new_buf, buf_shardings)
            elif zero_layout is None and buf_shardings is not None:
                new_buf = jax.lax.with_sharding_constraint(new_buf, buf_shardings)

            # dynamic loss scale lives ON DEVICE (torch GradScaler
            # semantics, applied only on sync boundaries): no host
            # round-trip per boundary
            new_scale_state = update_scale_state(scale_state, finite, do_sync)
            return new_params, new_opt, new_buf, new_state, loss, gnorm, finite, aux, new_scale_state, new_comp_state

        zero_shardings = None if zero_layout is not None else getattr(optimizer, "_zero_shardings", None)
        buf_shardings = None
        if zero_layout is not None:
            # the accumulation buffer lives in the flat 1/n-per-device
            # layout (the ZeRO-2 flavour rides along for free: grads are
            # reduce-scattered every microbatch, so the buffer never
            # materialises replicated)
            buf_shardings = zero_layout.flat_shardings(self.mesh)
        elif zero_shardings is not None:
            from .parallel.sharding import zero_optimizer_shardings

            buf_shardings = zero_optimizer_shardings(
                model.params, getattr(model, "param_shardings", None), self.mesh
            )
        elif getattr(model, "param_shardings", None) is not None:
            # the buffer is laid out like the parameters it accumulates
            # for. Left to itself the zeros program below puts it whole on
            # one device, uncommitted, and the step hands it back
            # replicated: a second compile at step 2, and a buffer the size
            # of the parameters on every device of an fsdp mesh
            buf_shardings = model.param_shardings

        donate_args = ((0, 1, 2, 3) if has_state else (0, 1, 2)) if donate else ()
        if donate and (psgd_rank is not None or (zero_layout is not None and compress_method is not None)):
            donate_args = donate_args + (9,)  # the params-sized error-feedback carry
        if offload_pull is not None:
            # the host-resident state can't be donated to device outputs
            # (memory-kind mismatch); its buffers are replaced by the push.
            # do_sync turns static (two program variants) so non-sync
            # microbatches never stream the state — see train_step.
            donate_args = tuple(i for i in donate_args if i != 1)
            jitted = jax.jit(train_step, donate_argnums=donate_args, static_argnums=(6,))
            step_statics = (6,)
        elif zero_layout is not None:
            # static do_sync: two program variants, no collective under a
            # value-dependent cond (see zero_fns)
            jitted = jax.jit(train_step, donate_argnums=donate_args, static_argnums=(6,))
            step_statics = (6,)
        else:
            jitted = jax.jit(train_step, donate_argnums=donate_args)
            step_statics = ()
        if self._program_cache is not None and self.compile_handler.aot_train_step:
            # AOT warm-start: dispatch goes signature -> executable through
            # the shared ProgramCache, so a restarted process re-creating
            # this step deserializes from the store instead of recompiling
            # (the wrapper keeps `_cache_size` for the recompile watchdog)
            jitted = self._program_cache.wrap_jit(
                jitted, name="train_step", static_argnums=step_statics
            )

        if zero_layout is not None:
            # flat-padded buffer leaves, born 1/n-per-device
            grad_buf = jax.jit(
                lambda p: jax.tree_util.tree_map(
                    lambda x: jnp.zeros_like(x, dtype=jnp.float32), zero_layout.flatten_pad(p)
                ),
                out_shardings=buf_shardings,
            )(model.params)
        else:
            grad_buf = jax.jit(
                lambda p: jax.tree_util.tree_map(lambda x: jnp.zeros_like(x, dtype=jnp.float32), p),
                out_shardings=buf_shardings,
            )(model.params)
        if not hasattr(self, "_fast_scale_boxes"):
            self._fast_scale_boxes = []
        comp_state0 = {}
        if zero_layout is not None and compress_method is not None:
            from .parallel.zero import zero1_comp_shardings, zero1_comp_template

            template = zero1_comp_template(zero_layout, compress_method)
            # build the residual carries ALREADY sharded (jit +
            # out_shardings): the rs_error carry is n x params f32 global —
            # materializing it replicated first would put all of it on one
            # device
            comp_state0 = jax.jit(
                lambda: jax.tree_util.tree_map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), template
                ),
                out_shardings=zero1_comp_shardings(zero_layout, compress_method, self.mesh),
            )()
        elif psgd_rank is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from .parallel.compression import powersgd_init_state

            n_data = int(dict(self.mesh.shape).get("data", 1))
            # build the params-sized error carry ALREADY sharded (jit +
            # out_shardings, the grad_buf pattern above): materializing it
            # replicated first would put n_data x params f32 on one device
            comp_state0 = jax.jit(
                lambda p: powersgd_init_state(p, psgd_rank, n_data),
                out_shardings={
                    "error": jax.tree_util.tree_map(
                        lambda _: NamedSharding(self.mesh, P("data")), model.params
                    ),
                    "q": jax.tree_util.tree_map(
                        lambda _: NamedSharding(self.mesh, P()), model.params
                    ),
                },
            )(model.params)
        from jax.sharding import NamedSharding as _NS, PartitionSpec as _PS

        state_box = {
            "grad_buf": grad_buf,
            "micro": 0,
            # fp16 dynamic loss scale as carried device arrays (no host
            # fetch per boundary); refreshed to the host copy every
            # _SCALE_REFRESH boundaries for introspection/checkpointing.
            # Committed mesh-replicated UP FRONT: after the first step the
            # carried scale comes back replicated over the whole mesh, and
            # a device-0-committed initial value would give the program a
            # second (then third, with static do_sync variants) cache
            # entry — a recompile the watchdog rightly flags
            "scale_state": jax.device_put(
                {
                    "scale": jnp.float32(self._loss_scale),
                    "growth": jnp.int32(self._scale_growth_tracker),
                },
                _NS(self.mesh, _PS()),
            ),
            "boundaries": 0,
            # PowerSGD error-feedback + warm-start factors (empty unless
            # grad_compression="powersgd[:r]")
            "comp_state": comp_state0,
        }
        self._fast_scale_boxes.append(state_box)
        _SCALE_REFRESH = 64

        def step(batch):
            # sync on the accumulation boundary OR at end-of-dataloader
            # (reference sync_with_dataloader semantics: accelerator.py:1123)
            do_sync = (state_box["micro"] + 1) % accum == 0
            if (
                self.gradient_state.sync_with_dataloader
                and self.gradient_state.in_dataloader
                and self.gradient_state.end_of_dataloader
            ):
                do_sync = True
            self.gradient_state._set_sync_gradients(do_sync)
            from .utils.random import key_for_step

            with phase("train.step", step=self.step, do_sync=int(do_sync)):  # a root: phase() adds its ``mono_ns``
                with phase("train.step.args"):
                    sync_arg = bool(do_sync) if (offload_push is not None or zero_layout is not None) else jnp.bool_(do_sync)
                    rng = key_for_step(self.step)
                    clip_norm = jnp.float32(-1.0 if self._clip_max_norm is None else self._clip_max_norm)
                with phase("train.step.call"), self._matmul_precision_ctx():
                    new_params, new_opt, new_buf, new_state, loss, gnorm, finite, aux, new_scale_state, new_comp = jitted(
                        model.params,
                        optimizer.opt_state,
                        state_box["grad_buf"],
                        getattr(model, "state", None) if has_state else None,
                        batch,
                        state_box["scale_state"],
                        sync_arg,
                        rng,
                        clip_norm,
                        state_box["comp_state"],
                    )
                with phase("train.step.swap"):
                    model.params = new_params
                    if has_state:
                        model.state = new_state
                    if offload_push is None:
                        optimizer.opt_state = new_opt
                    elif do_sync:
                        optimizer.opt_state = offload_push(new_opt)
                    # offload + non-sync: the state passed through the program
                    # untouched (and unstreamed) — nothing to write back
                    state_box["grad_buf"] = new_buf
                    state_box["scale_state"] = new_scale_state
                    state_box["comp_state"] = new_comp
                    state_box["micro"] = 0 if do_sync else state_box["micro"] + 1
                    self.step += 1
                    self._last_grad_norm = gnorm
                    # opt-in runtime finiteness probe (TelemetryKwargs
                    # nonfinite_every=N) — the runtime counterpart of the static
                    # TPU602 overflow proof. Gated inside observe(): off-cadence
                    # steps coerce nothing, so no host sync is added
                    if self._telemetry is not None and self._telemetry.nonfinite.enabled:
                        self._telemetry.nonfinite.observe(
                            self.step,
                            loss=loss,
                            grad_norm=gnorm,
                            loss_scale=new_scale_state["scale"] if use_fp16 else None,
                            # the fp16 scaler skips the update and backs off on a
                            # grad overflow — that's calibration, not divergence
                            scaler_handled=use_fp16,
                        )
                    if do_sync:
                        if use_fp16:
                            # device value, coerced lazily by the property — reading
                            # step_was_skipped is what forces the fetch, not the step
                            optimizer._step_was_skipped = jnp.logical_not(finite)
                            state_box["boundaries"] += 1
                            if state_box["boundaries"] % _SCALE_REFRESH == 0:
                                self._loss_scale = float(new_scale_state["scale"])
                                self._scale_growth_tracker = int(new_scale_state["growth"])
                        if scheduler is not None:
                            scheduler.step()
            return (loss, aux) if has_aux else loss

        step._jitted = jitted
        return step

    def _make_gradient_applier(self, optax_tx):
        """The shared clip + finite-check + update + zero-buffer body used by
        both the fast path and the imperative path — one definition so the
        two paths can never diverge.

        ``clip_norm`` is a *traced* scalar (negative = clipping disabled,
        0.0 = zero all gradients, torch semantics), not a build-time
        constant: calling ``clip_grad_norm_`` inside the training loop —
        the reference idiom (accelerator.py:2677) — takes effect on the
        very next step without rebuilding the jitted program."""
        jax = _jax()
        jnp = _jnp()
        use_fp16 = self.mixed_precision == "fp16"

        def apply_gradients(operand, clip_norm):
            params, opt_state, grad_buf = operand
            g = grad_buf
            gnorm = optax_global_norm(g)
            # clip_norm < 0 = clipping disabled; 0.0 zeroes gradients
            # (torch clip_grad_norm_ semantics)
            scale = jnp.where(clip_norm >= 0, jnp.minimum(1.0, clip_norm / (gnorm + 1e-6)), 1.0)
            g = jax.tree_util.tree_map(lambda t: t * scale, g)
            finite = jnp.isfinite(gnorm)

            def do_update(_):
                updates, new_opt = optax_tx.update(g, opt_state, params)
                new_params = jax.tree_util.tree_map(lambda p, u: p + u.astype(p.dtype), params, updates)
                return new_params, new_opt

            if use_fp16:
                new_params, new_opt = jax.lax.cond(finite, do_update, lambda _: (params, opt_state), operand=None)
            else:
                new_params, new_opt = do_update(None)
            zero_buf = jax.tree_util.tree_map(jnp.zeros_like, grad_buf)
            return new_params, new_opt, zero_buf, gnorm, finite

        return apply_gradients

    def _update_loss_scale(self, finite: bool):
        h = self.scaler_handler
        if not finite:
            self._loss_scale = max(1.0, self._loss_scale * h.backoff_factor)
            self._scale_growth_tracker = 0
        else:
            self._scale_growth_tracker += 1
            if self._scale_growth_tracker >= h.growth_interval:
                self._loss_scale *= h.growth_factor
                self._scale_growth_tracker = 0

    # ------------------------------------------------------------------ #
    # imperative parity path (reference: accumulate/backward/step §3.4)
    # ------------------------------------------------------------------ #

    def _do_sync(self):
        """(reference: accelerator.py:1123-1131)."""
        if self.gradient_state.sync_with_dataloader and self.gradient_state.end_of_dataloader:
            self.step = 0
            self.gradient_state._set_sync_gradients(True)
        else:
            self.step += 1
            sync = (self.step % self.gradient_accumulation_steps) == 0
            sync = sync or self.gradient_state.plugin_kwargs.get("sync_each_batch", False)
            self.gradient_state._set_sync_gradients(sync)

    @contextlib.contextmanager
    def accumulate(self, *models):
        """(reference: accelerator.py:1149). Gradient-sync bookkeeping for
        the imperative path: inside the context, ``backward`` accumulates;
        ``optimizer.step()`` applies only on sync boundaries.

        When telemetry is live (the ``telemetry`` property has been
        accessed), each ``accumulate`` block is recorded as one step on
        the runtime timeline, fenced on the active model's params — the
        imperative twin of ``telemetry.wrap(step)``."""
        self._do_sync()
        if self._telemetry is None:
            yield
            return
        with self._telemetry.steps.step() as handle:
            yield
            target = (models[0] if models else None) or (self._models[-1] if self._models else None)
            handle.done(getattr(target, "params", None))

    @contextlib.contextmanager
    def no_sync(self, model=None):
        """(reference: accelerator.py:1033). Forces accumulation-only for
        the body. On TPU there is no DDP hook to disable — the flag simply
        gates the buffered apply."""
        old = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(False)
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(old)

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables, even_batches: Optional[bool] = None):
        """(reference: accelerator.py:1194). Uneven batches never reach the
        step on TPU (padding+mask in the dataloader), so this is a
        compatibility context that optionally overrides ``even_batches``."""
        loaders = [dl for dl in self._dataloaders if hasattr(dl, "even_batches")]
        old = [dl.even_batches for dl in loaders]
        if even_batches is not None:
            for dl in loaders:
                dl.even_batches = even_batches
        try:
            yield
        finally:
            for dl, val in zip(loaders, old):
                dl.even_batches = val

    def backward(self, loss_fn: Callable, batch=None, model: Optional[Model] = None, **kwargs):
        """Imperative gradient computation + accumulation
        (reference: accelerator.py:2549).

        JAX cannot differentiate an already-computed loss value, so the
        imperative contract takes the *loss function* plus the batch:
        ``accelerator.backward(loss_fn, batch)`` computes
        ``grad(loss_fn)(params, batch)``, scales by
        ``1/gradient_accumulation_steps`` (reference :2571), and adds into
        the on-device gradient buffer.
        """
        jax = _jax()
        jnp = _jnp()
        model = model or self._models[-1]
        accum = self.gradient_accumulation_steps
        # the cache entry holds a strong reference to loss_fn: a freed
        # lambda's id() can be reused, so identity is re-checked on hit
        cache_key = ("backward", id(loss_fn), id(model), accum)
        entry = self._jit_cache.get(cache_key)
        if entry is None or entry[0] is not loss_fn:
            compute_cast = self._compute_cast

            def grad_step(params, grad_buf, batch, loss_scale):
                def scaled(p):
                    loss = loss_fn(compute_cast(p), batch)
                    return loss.astype(jnp.float32) * loss_scale, loss

                grads, loss = jax.grad(scaled, has_aux=True)(params)
                grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32) / (loss_scale * accum), grads)
                new_buf = jax.tree_util.tree_map(lambda b, g: b + g, grad_buf, grads)
                return new_buf, loss

            entry = (loss_fn, jax.jit(grad_step, donate_argnums=(1,)))
            self._jit_cache[cache_key] = entry
        if self._grad_buffers.get(id(model)) is None:
            self._grad_buffers[id(model)] = jax.jit(
                lambda p: jax.tree_util.tree_map(lambda x: jnp.zeros_like(x, dtype=jnp.float32), p)
            )(model.params)
        with self._matmul_precision_ctx():
            self._grad_buffers[id(model)], loss = entry[1](
                model.params, self._grad_buffers[id(model)], batch, jnp.float32(self._loss_scale)
            )
        self._grad_count += 1
        return loss

    def _buffer_for(self, model: Optional[Model] = None):
        """The gradient buffer for ``model`` (default: the single active
        buffer, or the last prepared model's)."""
        if model is not None:
            return id(model), self._grad_buffers.get(id(model))
        if len(self._grad_buffers) == 1:
            return next(iter(self._grad_buffers.items()))
        if self._models:
            mid = id(self._models[-1])
            return mid, self._grad_buffers.get(mid)
        return None, None

    def _zero_grad_buffer(self, model: Optional[Model] = None):
        jax = _jax()
        jnp = _jnp()
        keys = [id(model)] if model is not None else list(self._grad_buffers)
        for k in keys:
            if self._grad_buffers.get(k) is not None:
                self._grad_buffers[k] = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x), self._grad_buffers[k])
        self._grad_count = 0

    def _apply_accumulated_gradients(self, opt: AcceleratedOptimizer) -> bool:
        """Apply the imperative-path gradient buffer through the optimizer.
        Returns False when skipped (non-finite, fp16)."""
        jax = _jax()
        jnp = _jnp()
        model = getattr(opt, "_model", None) or self._models[-1]
        self._ensure_opt_state(opt, model)
        if getattr(opt, "_zero1_layout", None) is not None:
            raise NotImplementedError(
                "zero_stage=1 shards the update across replicas inside the jitted fast "
                "path; drive training through build_train_step (the imperative "
                "backward/step path would need a replicated optimizer state)"
            )
        _, grad_buffer = self._buffer_for(model)
        if grad_buffer is None:
            return True
        cache_key = ("apply", id(opt))
        if cache_key not in self._jit_cache:
            apply_gradients = self._make_gradient_applier(opt.optimizer)
            pull, _ = self._offload_transfers(opt)

            def _apply(params, opt_state, grad_buf, clip):
                if pull is not None:
                    opt_state = pull(opt_state)
                return apply_gradients((params, opt_state, grad_buf), clip)

            donate = (0, 2) if pull is not None else (0, 1, 2)
            self._jit_cache[cache_key] = jax.jit(_apply, donate_argnums=donate)
        with self._matmul_precision_ctx():
            new_params, new_opt, zero_buf, gnorm, finite = self._jit_cache[cache_key](
                model.params,
                opt.opt_state,
                grad_buffer,
                _jnp().float32(-1.0 if self._clip_max_norm is None else self._clip_max_norm),
            )
        model.params = new_params
        _, push = self._offload_transfers(opt)
        opt.opt_state = new_opt if push is None else push(new_opt)
        self._grad_buffers[id(model)] = zero_buf
        self._grad_count = 0
        self._last_grad_norm = gnorm
        ok = bool(finite)
        if self.mixed_precision == "fp16":
            self._update_loss_scale(ok)
        return ok

    def clip_grad_norm_(self, parameters=None, max_norm: float = 1.0, norm_type: float = 2.0):
        """(reference: accelerator.py:2677). Sets the max norm consumed by
        the next gradient apply — the norm is a traced input of the jitted
        step, so calling this inside the loop (the reference idiom) takes
        effect immediately on both the fast and imperative paths. On the
        imperative path the current buffer is also clipped in place and its
        pre-clip norm returned."""
        if norm_type != 2.0:
            raise NotImplementedError("only the L2 global norm is supported on TPU")
        self._clip_max_norm = max_norm
        model = parameters if isinstance(parameters, Model) else None
        key, buf = self._buffer_for(model)
        if buf is not None:
            jax = _jax()
            gnorm = optax_global_norm(buf)
            scale = _jnp().minimum(1.0, max_norm / (gnorm + 1e-6))
            self._grad_buffers[key] = jax.tree_util.tree_map(lambda t: t * scale, buf)
            self._last_grad_norm = gnorm
            return gnorm
        return self._last_grad_norm

    def clip_grad_value_(self, parameters, clip_value: float):
        """(reference: accelerator.py:2754)."""
        model = parameters if isinstance(parameters, Model) else None
        key, buf = self._buffer_for(model)
        if buf is not None:
            jax = _jax()
            jnp = _jnp()
            self._grad_buffers[key] = jax.tree_util.tree_map(lambda t: jnp.clip(t, -clip_value, clip_value), buf)

    # ------------------------------------------------------------------ #
    # metrics / gathering (reference: accelerator.py:2799-2871)
    # ------------------------------------------------------------------ #

    def gather(self, tensor):
        return gather(tensor)

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """Gather + drop the duplicated tail of the final uneven batch
        (reference: accelerator.py:2799; remainder from
        data_loader.py:365-405)."""
        if use_gather_object or not _has_array_leaves(input_data):
            data = gather_object(input_data if isinstance(input_data, list) else [input_data])
        else:
            data = gather(input_data)
        if self.gradient_state.end_of_dataloader and self.gradient_state.remainder > 0:
            rem = self.gradient_state.remainder

            def trunc(x):
                return x[:rem] if hasattr(x, "shape") and getattr(x, "ndim", 0) >= 1 else x

            import jax

            return jax.tree_util.tree_map(trunc, data)
        return data

    def reduce(self, tensor, reduction: str = "mean", scale: float = 1.0):
        return reduce(tensor, reduction, scale)

    def pad_across_processes(self, tensor, dim: int = 0, pad_index: int = 0, pad_first: bool = False):
        return pad_across_processes(tensor, dim, pad_index, pad_first)

    # ------------------------------------------------------------------ #
    # precision helpers
    # ------------------------------------------------------------------ #

    @contextlib.contextmanager
    def autocast(self, autocast_handler: Optional[AutocastKwargs] = None):
        """(reference: accelerator.py:3832). Compute-dtype casting is baked
        into the jitted step (``_compute_cast``); this context exists for
        API parity and temporarily overrides the policy for code that calls
        :meth:`cast_to_compute`."""
        old = self.autocast_handler
        if autocast_handler is not None:
            self.autocast_handler = autocast_handler
        try:
            yield
        finally:
            self.autocast_handler = old

    def cast_to_compute(self, tree):
        return self._compute_cast(tree)

    # ------------------------------------------------------------------ #
    # triggers (reference: accelerator.py:2583-2640)
    # ------------------------------------------------------------------ #

    def set_trigger(self):
        self._trigger_flag = True

    def check_trigger(self) -> bool:
        flags = gather_object([self._trigger_flag])
        fired = any(flags)
        if fired:
            self._trigger_flag = False
        return fired

    # ------------------------------------------------------------------ #
    # model export / unwrap
    # ------------------------------------------------------------------ #

    def unwrap_model(self, model, keep_fp32_wrapper: bool = True):
        """(reference: accelerator.py:2744 via utils/other.py:217). Models
        are never wrapped on TPU; returns as-is."""
        return model

    def free_memory(self, *objects):
        """(reference: accelerator.py:3633)."""
        self._models.clear()
        self._optimizers.clear()
        self._schedulers.clear()
        self._dataloaders.clear()
        self._grad_buffers.clear()
        self._jit_cache.clear()
        self.step = 0
        from .utils.memory import release_memory

        return release_memory(*objects)

    def clear(self, *objects):
        return self.free_memory(*objects)

    # ------------------------------------------------------------------ #
    # checkpointing (reference: accelerator.py:3308/3474)
    # ------------------------------------------------------------------ #

    def register_for_checkpointing(self, *objects):
        """(reference: accelerator.py:3795)."""
        invalid = [o for o in objects if not (hasattr(o, "state_dict") and hasattr(o, "load_state_dict"))]
        if invalid:
            raise ValueError(f"Objects must expose state_dict/load_state_dict: {invalid}")
        self._custom_objects.extend(objects)

    def register_save_state_pre_hook(self, hook):
        self._save_model_hooks.append(hook)
        return _RemovableHandle(self._save_model_hooks, hook)

    def register_load_state_pre_hook(self, hook):
        self._load_model_hooks.append(hook)
        return _RemovableHandle(self._load_model_hooks, hook)

    def _sync_loss_scale_to_host(self):
        """Pull the fast path's on-device fp16 scale into the host mirror
        (the periodic refresh may lag by up to _SCALE_REFRESH boundaries —
        a checkpoint must persist the TRUE current scale)."""
        boxes = getattr(self, "_fast_scale_boxes", None)
        if boxes and self.mixed_precision == "fp16":
            ss = boxes[-1]["scale_state"]
            self._loss_scale = float(ss["scale"])
            self._scale_growth_tracker = int(ss["growth"])

    def _seed_loss_scale_to_device(self):
        """Push the host scale into every built train step's carried device
        state (load_state must take effect on steps built BEFORE the load).
        Mesh-replicated like the build-time init, so re-seeding never
        hands the jitted step a differently-committed scale (= recompile)."""
        jax = _jax()
        jnp = _jnp()
        from jax.sharding import NamedSharding, PartitionSpec

        for box in getattr(self, "_fast_scale_boxes", []) or []:
            box["scale_state"] = jax.device_put(
                {
                    "scale": jnp.float32(self._loss_scale),
                    "growth": jnp.int32(self._scale_growth_tracker),
                },
                NamedSharding(self.mesh, PartitionSpec()),
            )

    def save_state(self, output_dir: Optional[str] = None, **save_model_func_kwargs):
        """Atomic checkpoint save (tmp-dir write -> barrier -> manifest ->
        rename; see ``docs/usage_guides/fault_tolerance.md``).

        ``async_save=True`` returns once device->host copies finish;
        disk writes AND the commit continue in the background (drained by
        :meth:`wait_for_checkpoint` or the next save/load). Under
        preemption the async request is demoted to a synchronous save —
        the grace window is for committing, not for queueing."""
        from .checkpointing import save_accelerator_state

        if self.preempted:
            save_model_func_kwargs.pop("async_save", None)
        self._sync_loss_scale_to_host()
        out = save_accelerator_state(self, output_dir, **save_model_func_kwargs)
        if self.preempted:
            self._preempt_checkpointed = True
        return out

    def wait_for_checkpoint(self):
        """Block until pending ``save_state(async_save=True)`` writes commit."""
        from .checkpointing import wait_for_checkpoint

        wait_for_checkpoint()

    def load_state(self, input_dir: Optional[str] = None, **load_model_func_kwargs):
        """Restore a checkpoint. With ``input_dir=None``, **auto-resume**:
        find the newest checkpoint whose integrity manifest verifies under
        ``{project_dir}/checkpoints`` (walking back past corrupt or
        uncommitted ones), restore it, and continue the ``checkpoint_N``
        numbering from there."""
        from .checkpointing import load_accelerator_state

        out = load_accelerator_state(self, input_dir, **load_model_func_kwargs)
        self._seed_loss_scale_to_device()
        if self._program_cache is not None:
            # warm-start after (elastic) restore: the restored trainer's
            # step programs should deserialize from the executable store
            # instead of recompiling — surface how warm that store is so
            # a resume that DID recompile is explainable from telemetry
            stats = self._program_cache.stats()
            if self._telemetry is not None:
                self._telemetry.log.event("compile_cache_warmstart", **stats)
            logger.info(
                "compile cache at resume: %s stored executable(s), %s deserialized this process",
                stats.get("store_entries", 0), stats.get("deserialized", 0),
            )
        return out

    @property
    def checkpoint_manager(self):
        """A :class:`~accelerate_tpu.ft.CheckpointManager` over this
        project's automatic-naming checkpoint directory (``None`` without
        a ``project_dir``)."""
        if self.project_dir is None:
            return None
        from .ft.manager import CheckpointManager

        return CheckpointManager(
            os.path.join(self.project_dir, self.project_configuration.checkpoints_dir_name)
        )

    # ------------------------------------------------------------------ #
    # preemption (docs/usage_guides/fault_tolerance.md; no reference
    # analogue — the reference dies with the SIGTERM)
    # ------------------------------------------------------------------ #

    @property
    def preemption_handler(self):
        """The installed :class:`~accelerate_tpu.ft.PreemptionHandler`, or
        ``None`` (pass ``FaultToleranceKwargs()`` to install one)."""
        return self._preemption

    @property
    def preempted(self) -> bool:
        """True once SIGTERM/SIGINT was received (always False without a
        preemption handler)."""
        return self._preemption is not None and self._preemption.preempted

    def _preempted_everywhere(self) -> bool:
        """The fleet-wide preemption flag. Multi-host, a SIGTERM usually
        lands on a SUBSET of hosts; every rank runs the same max-reduce
        of its local flag here (``parallel.collectives.agree_preempt_max``)
        so the flag flips on all ranks in the same step and the fleet
        takes one coherent final checkpoint. Called unconditionally by
        ``should_checkpoint``/``should_stop`` — never guard a call to
        those behind rank-divergent state. Latches after the first
        agreed-True so later checks are free; single-process runs skip
        the collective entirely."""
        if self._preemption is None:
            return False
        if self._preempt_agreed:
            return True
        local = self._preemption.preempted
        if self.num_processes == 1 or not self.ft_handler.agree_preemption:
            return local
        from .parallel.collectives import agree_preempt_max

        agreed = bool(agree_preempt_max(1 if local else 0))
        if agreed:
            self._preempt_agreed = True
            if not local:
                # this rank never saw the signal: latch its handler so
                # telemetry/logging and `preempted` agree fleet-wide
                self._preemption.mark_remote()
        return agreed

    @property
    def should_checkpoint(self) -> bool:
        """True when a preemption signal arrived — on ANY host (see
        :meth:`_preempted_everywhere`) — and the final synchronous
        checkpoint has not been taken yet; check after each step::

            if accelerator.should_checkpoint:
                accelerator.save_state()   # drains async saves, saves sync
            if accelerator.should_stop:
                break

        Every rank must read this at the same step boundary: multi-host it
        performs the preemption-agreement collective."""
        return self._preempted_everywhere() and not self._preempt_checkpointed

    @property
    def should_stop(self) -> bool:
        """True once preemption was signalled anywhere in the fleet: exit
        the training loop at the next step boundary (after the
        :attr:`should_checkpoint` save)."""
        return self._preempted_everywhere()

    def save_model(self, model, save_directory: str, max_shard_size="10GB", safe_serialization: bool = True):
        from .checkpointing import save_model as _save_model

        return _save_model(model, save_directory, max_shard_size=max_shard_size, safe_serialization=safe_serialization)

    def skip_first_batches(self, dataloader, num_batches: int = 0):
        """(reference: accelerator.py:3929)."""
        return _skip_first_batches(dataloader, num_batches)

    # ------------------------------------------------------------------ #
    # runtime telemetry (no reference analogue; docs/usage_guides/telemetry.md)
    # ------------------------------------------------------------------ #

    @property
    def telemetry(self):
        """The run's :class:`~accelerate_tpu.telemetry.Telemetry` facade
        (created on first access from the ``TelemetryKwargs`` handler).

        Typical use — instrument the fast path and let everything else
        happen automatically (event log under ``logging_dir``, HBM
        sampling, recompile watchdog, tracker forwarding)::

            step = accelerator.telemetry.wrap(accelerator.build_train_step(loss_fn))

        The imperative path needs no call at all: ``accumulate()`` blocks
        are timed as steps once telemetry has been touched. Pass
        ``TelemetryKwargs(enabled=False)`` to keep even explicit accesses
        event-log-free (in-memory records still accumulate, so
        ``telemetry.summary()`` keeps working)."""
        if self._telemetry is None:
            from .telemetry import Telemetry, default_path

            h = self.telemetry_handler
            path = None
            if h.enabled:
                path = h.output_path or default_path(self.logging_dir)
            self._telemetry = Telemetry(
                path,
                rank=self.process_index,
                main_process_only=h.main_process_only,
                warmup_steps=h.warmup_steps,
                fence=h.fence,
                watchdog=h.recompile_watchdog,
                n_devices=self.state.num_devices,
                hbm_sample_every=h.hbm_sample_every,
                forward_fn=(lambda values, step: self.log(values, step=step)),
                forward_every=h.forward_to_trackers_every,
                nonfinite_every=h.nonfinite_every,
            )
            if self._program_cache is not None:
                # compile_cache_* events land in the same run JSONL as the
                # step timeline, so a summarize pass explains both
                self._program_cache.log = self._telemetry.log
        return self._telemetry

    @property
    def program_cache(self):
        """The shared :class:`~accelerate_tpu.aot.ProgramCache` (``None``
        unless a :class:`~accelerate_tpu.utils.CompileKwargs` handler was
        passed or ``ACCELERATE_COMPILE_CACHE_DIR`` is set). When active,
        ``build_train_step`` routes program dispatch through it, so a
        restarted process deserializes the step executable instead of
        recompiling — see ``docs/usage_guides/compilation.md``."""
        return self._program_cache

    # ------------------------------------------------------------------ #
    # tracking (reference: accelerator.py:3002-3114)
    # ------------------------------------------------------------------ #

    def init_trackers(self, project_name: str, config: Optional[dict] = None, init_kwargs: dict = {}):
        from .tracking import filter_trackers

        self.trackers = filter_trackers(self._log_with, self.logging_dir, project_name, config, init_kwargs)

    def get_tracker(self, name: str, unwrap: bool = False):
        """(reference: accelerator.py:3069). With NO active trackers,
        returns a no-op blank ``GeneralTracker`` (reference behavior) so
        user code can call ``get_tracker(...).log(...)`` unconditionally;
        the ``ValueError`` is kept only for a *named* tracker genuinely
        missing among active ones."""
        if self.trackers:
            for tracker in self.trackers:
                if tracker.name == name:
                    return tracker.tracker if unwrap else tracker
            raise ValueError(f"{name} is not an active tracker: {[t.name for t in self.trackers]}")
        from .tracking import GeneralTracker

        return GeneralTracker(_blank=True)

    def log(self, values: dict, step: Optional[int] = None, log_kwargs: dict = {}):
        if not self.is_main_process:
            return
        retries = self.ft_handler.tracker_retries if self._ft_explicit else 1
        for tracker in self.trackers:
            kw = log_kwargs.get(tracker.name, {})
            if retries <= 1:
                tracker.log(values, step=step, **kw)
                continue
            # FT mode: a tracker backend hiccup (wandb 5xx, mlflow timeout)
            # is retried with backoff and, on giveup, logged and swallowed —
            # metrics loss must not kill a multi-hour run
            from .utils.retry import retry_call

            def _on_retry(attempt, delay, exc, _name=tracker.name):
                if self._telemetry is not None:
                    self._telemetry.log.event(
                        "tracker_retry", severity="warning", tracker=_name,
                        attempt=attempt, delay_s=round(delay, 3), error=str(exc),
                    )

            try:
                retry_call(
                    tracker.log, values, step=step,
                    attempts=retries,
                    base_delay=self.ft_handler.retry_base_delay,
                    max_delay=self.ft_handler.retry_max_delay,
                    exceptions=(Exception,),
                    on_retry=_on_retry,
                    **kw,
                )
            except Exception as e:
                logger.warning(f"tracker {tracker.name}.log failed after {retries} attempts: {e}")
                if self._telemetry is not None:
                    self._telemetry.log.event(
                        "tracker_giveup", severity="error", tracker=tracker.name, error=str(e)
                    )

    def _media_trackers(self, method: str):
        """Active trackers that override ``method`` beyond the base class
        (the base raises NotImplementedError); others are skipped with a
        one-line note so mixed tracker sets don't error on media calls."""
        from .tracking import GeneralTracker

        capable = []
        for tracker in self.trackers:
            if getattr(type(tracker), method) is getattr(GeneralTracker, method):
                logger.debug("%s does not support %s; skipping", tracker.name, method)
            else:
                capable.append(tracker)
        return capable

    def log_images(self, values: dict, step: Optional[int] = None, log_kwargs: dict = {}):
        """Route ``{name: [images]}`` to every active tracker with media
        support (reference: per-tracker ``log_images``, tracking.py:272/:373;
        the reference has no Accelerator-level helper — this closes the
        round-4 media-parity gap with one call)."""
        if self.is_main_process:
            for tracker in self._media_trackers("log_images"):
                tracker.log_images(values, step=step, **log_kwargs.get(tracker.name, {}))

    def log_table(
        self,
        table_name: str,
        columns: Optional[list] = None,
        data: Optional[list] = None,
        dataframe=None,
        step: Optional[int] = None,
        log_kwargs: dict = {},
    ):
        """Route a table to every active tracker with table support
        (reference: tracking.py:392 WandB / :1016 ClearML)."""
        if self.is_main_process:
            for tracker in self._media_trackers("log_table"):
                tracker.log_table(
                    table_name, columns=columns, data=data, dataframe=dataframe, step=step,
                    **log_kwargs.get(tracker.name, {}),
                )

    def end_training(self):
        if self.is_main_process:
            for tracker in self.trackers:
                tracker.finish()
        self.wait_for_everyone()

    # ------------------------------------------------------------------ #
    # profiling (reference: accelerator.py:3859)
    # ------------------------------------------------------------------ #

    @contextlib.contextmanager
    def profile(self, profile_handler: Optional[ProfileKwargs] = None):
        """Trace the body with ``jax.profiler``. Every ``ProfileKwargs``
        field is honoured: ``create_perfetto_link``/``create_perfetto_trace``
        go straight to ``start_trace``, the tracer levels ride on
        ``jax.profiler.ProfileOptions``."""
        if isinstance(profile_handler, str):  # path shorthand
            profile_handler = ProfileKwargs(output_trace_dir=profile_handler)
        handler = profile_handler or self.profile_handler
        import jax

        trace_dir = handler.output_trace_dir or os.path.join(self.logging_dir or ".", "profile")
        options = jax.profiler.ProfileOptions()
        for f in ("host_tracer_level", "python_tracer_level", "device_tracer_level"):
            setattr(options, f, getattr(handler, f))
        kwargs = {
            "create_perfetto_trace": handler.create_perfetto_trace,
            "create_perfetto_link": handler.create_perfetto_link,
            "profiler_options": options,
        }
        jax.profiler.start_trace(trace_dir, **kwargs)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
            if handler.on_trace_ready is not None:
                handler.on_trace_ready(trace_dir)

    def __repr__(self):
        return f"Accelerator(mesh={dict(self.mesh.shape)}, mixed_precision={self.mixed_precision!r})"


#: zero_stage=1 non-elementwise fallbacks already warned about (one
#: warning per offending state-node set per process)
_ZERO1_FALLBACK_WARNED: set = set()


def _nonelementwise_state_nodes(optax_tx) -> set:
    """Names of optax state nodes whose leaves couple elements within a
    parameter leaf — the structural probe behind the zero_stage=1
    fallback. An elementwise transform's state leaves are scalars (step
    counts) or param-shaped (adam moments); anything else (adafactor's
    ``(rows,)``/``(cols,)`` factored moments) proves the update reads
    across elements, which the flat-segment ZeRO-1 update would break.
    Probed via ``eval_shape`` on a tiny 2-D template — nothing runs.
    Shape-preserving couplings (a per-leaf trust ratio) are outside what
    a structural probe can see; those transforms keep their documented
    ``shard_optimizer_state`` contract."""
    jax = _jax()
    jnp = _jnp()
    probe_shape = (4, 6)
    try:
        state = jax.eval_shape(optax_tx.init, {"w": jax.ShapeDtypeStruct(probe_shape, jnp.float32)})
    except Exception:
        return set()  # unprobeable init: leave the explicit-layout path to its own validation
    bad: set = set()

    def walk(node, owner: str):
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            for v in node:
                walk(v, type(node).__name__)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v, owner)
        elif isinstance(node, dict):
            for v in node.values():
                walk(v, owner)
        else:
            shape = getattr(node, "shape", None)
            if shape is not None and tuple(shape) not in ((), probe_shape):
                bad.add(owner or "optax state")

    walk(state, "")
    return bad


class _RemovableHandle:
    def __init__(self, hooks_list, hook):
        self._list = hooks_list
        self._hook = hook

    def remove(self):
        if self._hook in self._list:
            self._list.remove(self._hook)


def optax_global_norm(tree):
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))


def _has_array_leaves(data) -> bool:
    import jax

    return any(hasattr(l, "shape") for l in jax.tree_util.tree_leaves(data))
