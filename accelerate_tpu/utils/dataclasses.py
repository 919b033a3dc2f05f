"""Config dataclasses, enums, and kwargs handlers.

Plays the role of the reference's ``utils/dataclasses.py`` (2833 LoC —
reference: src/accelerate/utils/dataclasses.py). The biggest structural
difference: the reference needs a 14-value ``DistributedType`` plus five
strategy plugins because each strategy is a separate code path; here a
strategy is a :class:`~accelerate_tpu.parallel.mesh.MeshConfig` layout, so
``DistributedType`` collapses to a descriptive label derived from the mesh.
"""

from __future__ import annotations

import copy
import dataclasses
import enum
import os
from dataclasses import dataclass, field
from datetime import timedelta
from typing import Any, Callable, Optional

from .environment import parse_flag_from_env
from ..parallel.mesh import MeshConfig


class BaseEnum(str, enum.Enum):
    def __str__(self) -> str:  # so f-strings print the value
        return self.value

    @classmethod
    def list(cls) -> list[str]:
        return [e.value for e in cls]


class DistributedType(BaseEnum):
    """Descriptive label for the active parallelism layout
    (reference enum with 14 backend-specific values:
    src/accelerate/utils/dataclasses.py:555-588)."""

    NO = "NO"
    DATA_PARALLEL = "DATA_PARALLEL"
    FSDP = "FSDP"
    TENSOR_PARALLEL = "TENSOR_PARALLEL"
    SEQUENCE_PARALLEL = "SEQUENCE_PARALLEL"
    PIPELINE_PARALLEL = "PIPELINE_PARALLEL"
    EXPERT_PARALLEL = "EXPERT_PARALLEL"
    HYBRID = "HYBRID"

    @classmethod
    def from_mesh_sizes(cls, sizes: dict[str, int]) -> "DistributedType":
        active = [a for a, n in sizes.items() if n > 1]
        if not active:
            return cls.NO
        if len(active) > 1:
            return cls.HYBRID
        return {
            "data": cls.DATA_PARALLEL,
            "fsdp": cls.FSDP,
            "tensor": cls.TENSOR_PARALLEL,
            "seq": cls.SEQUENCE_PARALLEL,
            "pipe": cls.PIPELINE_PARALLEL,
            "expert": cls.EXPERT_PARALLEL,
        }[active[0]]


class PrecisionType(BaseEnum):
    """(reference: utils/dataclasses.py:724). fp16 exists for API parity but
    bf16 is the TPU-native mixed-precision mode — no loss scaling needed."""

    NO = "no"
    BF16 = "bf16"
    FP16 = "fp16"
    FP8 = "fp8"


class RNGType(BaseEnum):
    JAX = "jax"
    NUMPY = "numpy"
    PYTHON = "python"


class LoggerType(BaseEnum):
    ALL = "all"
    TENSORBOARD = "tensorboard"
    WANDB = "wandb"
    MLFLOW = "mlflow"
    AIM = "aim"
    COMETML = "comet_ml"
    CLEARML = "clearml"
    DVCLIVE = "dvclive"
    SWANLAB = "swanlab"
    TRACKIO = "trackio"
    JSONL = "jsonl"


# ---------------------------------------------------------------------------
# Kwargs handlers (reference: utils/dataclasses.py:109-552)
# ---------------------------------------------------------------------------


class KwargsHandler:
    """Base for kwargs containers passed to ``Accelerator(kwargs_handlers=[...])``."""

    def to_dict(self) -> dict:
        return copy.deepcopy(dataclasses.asdict(self))

    def to_kwargs(self) -> dict:
        """Only the fields that differ from the defaults."""
        default = self.__class__()
        this = dataclasses.asdict(self)
        return {k: v for k, v in this.items() if getattr(default, k) != v}


@dataclass
class AutocastKwargs(KwargsHandler):
    """Compute-dtype policy tweaks (reference: utils/dataclasses.py:109).
    On TPU "autocast" is a dtype policy applied when building the jitted
    step, not a runtime context."""

    enabled: bool = True
    # dtypes kept out of low precision even under mixed precision
    keep_fp32_patterns: tuple = ("layernorm", "layer_norm", "ln_", "norm", "embedding_norm")


@dataclass
class DistributedInitKwargs(KwargsHandler):
    """Multi-host rendezvous options — the ``jax.distributed.initialize``
    analogue of ``InitProcessGroupKwargs`` (reference:
    utils/dataclasses.py:260)."""

    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    local_device_ids: Optional[list] = None
    timeout: timedelta = timedelta(minutes=10)


@dataclass
class GradScalerKwargs(KwargsHandler):
    """Dynamic loss-scaling knobs for fp16 (reference:
    utils/dataclasses.py:228). bf16 runs need none of this."""

    init_scale: float = 2.0**15
    growth_factor: float = 2.0
    backoff_factor: float = 0.5
    growth_interval: int = 2000
    enabled: bool = True


@dataclass
class Fp8RecipeKwargs(KwargsHandler):
    """TE-style fp8 recipe knobs (reference: ``TERecipeKwargs``,
    utils/dataclasses.py:317 + utils/transformer_engine.py:26-163).

    ``delayed_scaling=True`` keeps an amax history per tensor (a flax
    ``fp8`` collection threaded through the train step) and derives the
    quantization scale from ``max(history) * 2**margin`` — the TE
    "DelayedScaling" recipe; ``False`` recomputes per-tensor amax every
    call (the dynamic recipe, no state)."""

    delayed_scaling: bool = True
    amax_history_len: int = 16
    amax_compute_algo: str = "max"  # "max" | "most_recent"
    margin: int = 0

    def __post_init__(self):
        if self.amax_compute_algo not in ("max", "most_recent"):
            raise ValueError(f"amax_compute_algo must be max|most_recent, got {self.amax_compute_algo!r}")
        if self.amax_history_len < 1:
            raise ValueError(f"amax_history_len must be >= 1, got {self.amax_history_len}")


@dataclass
class ProfileKwargs(KwargsHandler):
    """``jax.profiler`` options (reference torch.profiler kwargs:
    utils/dataclasses.py:439-552). Traces are TensorBoard/Perfetto-viewable.

    The tracer levels map to XLA profiler options
    (``host_tracer_level`` 0-3, ``python_tracer_level`` 0/1,
    ``device_tracer_level`` 0/1); ``Accelerator.profile`` passes them
    through when the installed jax supports profiler options and warns
    ONCE per process about any option it has to drop — a silently-ignored
    knob is worse than no knob."""

    output_trace_dir: Optional[str] = None
    create_perfetto_link: bool = False
    create_perfetto_trace: bool = True
    host_tracer_level: int = 2
    python_tracer_level: int = 0
    device_tracer_level: int = 1
    on_trace_ready: Optional[Callable] = None


@dataclass
class TelemetryKwargs(KwargsHandler):
    """Runtime-telemetry knobs consumed by ``Accelerator.telemetry``
    (see :mod:`accelerate_tpu.telemetry`). No reference analogue — the
    reference has no runtime observability layer.

    ``output_path=None`` writes to ``{logging_dir}/telemetry.jsonl``
    (``runs/telemetry.jsonl`` when no logging/project dir is set);
    ``fence=False`` drops the per-step ``block_until_ready`` (the
    data-wait/dispatch/execute split then degrades but overhead reaches
    zero); ``forward_to_trackers_every=N`` pushes a rolling summary
    through ``Accelerator.log`` every N steps (0 disables);
    ``nonfinite_every=N`` opts in to the
    :class:`~accelerate_tpu.telemetry.NonFiniteWatchdog` — every N steps
    the fast-path train step probes loss / grad-norm finiteness and the
    fp16 loss-scale trajectory (a probe is a host sync, so 0 = off is
    the default; the static counterpart is
    ``Accelerator.numerics_check``'s TPU602 proof)."""

    enabled: bool = True
    output_path: Optional[str] = None
    # 2, not 1: the train step's second call may legitimately compile a
    # second program variant (sharding propagation re-lays-out the carried
    # gradient buffer) — see StepTelemetry's docstring
    warmup_steps: int = 2
    fence: bool = True
    recompile_watchdog: bool = True
    hbm_sample_every: int = 10
    forward_to_trackers_every: int = 10
    nonfinite_every: int = 0
    main_process_only: bool = True
    # serving-side request tracing (telemetry.trace): trace_requests=True
    # turns :meth:`trace_config` into a TraceConfig suitable for
    # ``FleetRouter(trace=...)`` — per-request spans, per-replica crash
    # flight recorders, and the critical-path drift cross-checks
    trace_requests: bool = False
    trace_max_traces: int = 4096
    trace_drift_check: bool = True
    flight_recorder: bool = True
    flight_capacity: int = 256
    flight_dump_dir: Optional[str] = None

    def __post_init__(self):
        if self.warmup_steps < 0:
            raise ValueError(f"warmup_steps must be >= 0, got {self.warmup_steps}")
        if self.hbm_sample_every < 0 or self.forward_to_trackers_every < 0:
            raise ValueError("hbm_sample_every / forward_to_trackers_every must be >= 0")
        if self.nonfinite_every < 0:
            raise ValueError(f"nonfinite_every must be >= 0, got {self.nonfinite_every}")
        if self.trace_max_traces < 1:
            raise ValueError(f"trace_max_traces must be >= 1, got {self.trace_max_traces}")
        if self.flight_capacity < 8:
            raise ValueError(f"flight_capacity must be >= 8, got {self.flight_capacity}")

    def trace_config(self):
        """The serving-trace half of these knobs as a
        :class:`~accelerate_tpu.telemetry.TraceConfig` (None when
        ``trace_requests`` is off) — pass as ``FleetRouter(trace=...)``."""
        if not self.trace_requests:
            return None
        from ..telemetry.trace import TraceConfig

        return TraceConfig(
            max_traces=self.trace_max_traces,
            drift_check=self.trace_drift_check,
            flight_recorder=self.flight_recorder,
            flight_capacity=self.flight_capacity,
            flight_dump_dir=self.flight_dump_dir,
        )


@dataclass
class ServingSchedulerKwargs(KwargsHandler):
    """Continuous-batching scheduler knobs for
    :class:`~accelerate_tpu.serving.ServingEngine` — the kwargs-handler
    mirror of :class:`~accelerate_tpu.scheduling.SchedulerConfig`, so
    serving deployments configure the scheduler the same way training
    configures telemetry/compile management. Pass it as
    ``ServingEngine(..., scheduler=ServingSchedulerKwargs(...))``.

    ``token_budget``: model-compute tokens per engine tick — active
    decodes claim ``n_decoding x tick_block`` first, the remainder runs
    prefill *chunks*, so long prompts stream in without stalling running
    decodes (``None`` = unlimited: prefills complete at admission).
    ``max_queue_depth`` / ``max_queue_wait_s``: SLO shed thresholds for
    priorities >= ``shed_priority_floor`` (``shed_action`` picks
    reject-with-:class:`~accelerate_tpu.scheduling.ShedError` or
    demote-to-``deprioritize_to``). ``enable_preemption``: evict the
    youngest decode with priority >= ``preempt_priority_floor`` when a
    strictly more important request cannot admit; it requeues and
    resumes token-exactly by recompute."""

    token_budget: Optional[int] = None
    max_queue_depth: Optional[int] = None
    max_queue_wait_s: Optional[float] = None
    shed_priority_floor: int = 1
    shed_action: str = "reject"
    deprioritize_to: int = 99
    enable_preemption: bool = False
    preempt_priority_floor: int = 1

    def to_scheduler_config(self):
        """The :class:`~accelerate_tpu.scheduling.SchedulerConfig` the
        engine consumes (validation happens there)."""
        from ..scheduling import SchedulerConfig

        return SchedulerConfig(**dataclasses.asdict(self))


@dataclass
class CompileKwargs(KwargsHandler):
    """Compile-management knobs consumed by ``Accelerator.program_cache``
    (see :mod:`accelerate_tpu.aot` and ``docs/usage_guides/compilation.md``).
    No reference analogue — the reference delegates compilation to torch.

    Passing this handler *activates* the subsystem: jax's persistent XLA
    compilation cache is pointed at the resolved cache dir, an
    :class:`~accelerate_tpu.aot.ExecutableStore` of serialized executables
    is opened next to it, and ``build_train_step`` routes its programs
    through the shared :class:`~accelerate_tpu.aot.ProgramCache` so a
    restarted process (new serving replica, preemption-resumed trainer)
    deserializes instead of recompiling. Setting
    ``ACCELERATE_COMPILE_CACHE_DIR`` activates the same default
    configuration without code changes.

    ``cache_dir=None`` resolves via ``ACCELERATE_COMPILE_CACHE_DIR``,
    then ``{ProjectConfiguration.project_dir}/compile_cache`` (see
    :func:`accelerate_tpu.aot.resolve_cache_dir`); with no dir at all the
    cache still deduplicates and emits telemetry, memory-only."""

    cache_dir: Optional[str] = None
    #: also wire jax's own persistent compilation cache (at
    #: ``{cache_dir}/xla``) — saves the XLA optimization pass even for
    #: programs the executable store doesn't cover
    persistent_xla_cache: bool = True
    #: keep serialized ``lower().compile()`` executables on disk so a new
    #: process warm-starts with zero XLA compiles
    executable_store: bool = True
    #: only persist XLA-cache entries that took at least this long to
    #: compile (jax's own default; 0 keeps everything, which floods the
    #: dir with micro-program entries)
    min_compile_time_secs: float = 1.0
    #: route ``build_train_step``'s program dispatch through the
    #: ProgramCache (the AOT warm-start path); False keeps plain jax.jit
    aot_train_step: bool = True

    def __post_init__(self):
        if self.min_compile_time_secs < 0:
            raise ValueError(f"min_compile_time_secs must be >= 0, got {self.min_compile_time_secs}")


@dataclass
class FaultToleranceKwargs(KwargsHandler):
    """Fault-tolerance knobs (see :mod:`accelerate_tpu.ft` and
    ``docs/usage_guides/fault_tolerance.md``). No reference analogue —
    the reference has no preemption/atomic-commit layer.

    Passing this handler to ``Accelerator(kwargs_handlers=[...])`` also
    *activates* the opt-in behaviors: the SIGTERM/SIGINT preemption
    handler (``handle_preemption``) and retried tracker network calls
    (``tracker_retries``). The atomic commit protocol itself is always
    on — correctness is not opt-in — these knobs only tune its retries
    and GC."""

    #: install a PreemptionHandler so SIGTERM/SIGINT surface as
    #: ``Accelerator.should_checkpoint`` / ``should_stop``
    handle_preemption: bool = True
    preemption_signals: tuple = ("SIGTERM", "SIGINT")
    #: multi-host: max-reduce the local preempt flag across every process
    #: each time ``should_checkpoint``/``should_stop`` is read, so a
    #: SIGTERM delivered to a subset of hosts flips the flag on ALL ranks
    #: in the same step (one scalar all-gather per check; single-process
    #: runs never pay it)
    agree_preemption: bool = True
    #: jittered-exponential-backoff attempts for checkpoint filesystem IO
    io_retries: int = 3
    retry_base_delay: float = 0.1
    retry_max_delay: float = 5.0
    #: retried attempts for tracker ``log`` network calls (giving up logs a
    #: warning instead of killing the run); 1 disables
    tracker_retries: int = 3
    #: sweep stale ``checkpoint_*.tmp`` leftovers at the start of each
    #: automatic-naming save (recovering any fully committed one)
    gc_tmp_on_save: bool = True
    #: deep-verify manifests (sizes + crc32) during auto-resume discovery;
    #: False trusts manifest presence alone (faster on huge checkpoints)
    verify_on_resume: bool = True

    def __post_init__(self):
        if self.io_retries < 1 or self.tracker_retries < 1:
            raise ValueError("io_retries / tracker_retries must be >= 1")
        if self.retry_base_delay < 0 or self.retry_max_delay < self.retry_base_delay:
            raise ValueError("need 0 <= retry_base_delay <= retry_max_delay")


# ---------------------------------------------------------------------------
# Plugins
# ---------------------------------------------------------------------------


@dataclass
class GradientAccumulationPlugin(KwargsHandler):
    """(reference: utils/dataclasses.py:931). ``sync_with_dataloader`` forces
    a sync on the last batch of each dataloader pass."""

    num_steps: int = 1
    adjust_scheduler: bool = True
    sync_with_dataloader: bool = True
    sync_each_batch: bool = False

    def __post_init__(self):
        if self.num_steps < 1:
            raise ValueError(f"gradient accumulation num_steps must be >= 1, got {self.num_steps}")


@dataclass
class DataLoaderConfiguration(KwargsHandler):
    """(reference: utils/dataclasses.py:773)."""

    split_batches: bool = False
    dispatch_batches: Optional[bool] = None
    even_batches: bool = True
    use_seedable_sampler: bool = True
    prefetch_size: int = 2
    non_blocking: bool = True  # kept for API parity; device_put is async
    #: pad ragged batch dims to a learned bucket set
    #: (:class:`~accelerate_tpu.aot.ShapeBucketer`) so a variable tail
    #: batch (or a variable-size stream) compiles at most len(buckets)
    #: programs instead of one per distinct size — the auto-bucketing
    #: loop-closer for the PR-3 recompile watchdog. Padded rows wrap
    #: around from the batch start (``even_batches`` tail semantics) and
    #: are truncated by the existing ``remainder`` bookkeeping.
    auto_bucketing: bool = False


@dataclass
class ProjectConfiguration(KwargsHandler):
    """Checkpoint/log directory layout (reference: utils/dataclasses.py:868)."""

    project_dir: Optional[str] = None
    logging_dir: Optional[str] = None
    automatic_checkpoint_naming: bool = False
    total_limit: Optional[int] = None
    iteration: int = 0
    save_on_each_node: bool = False
    #: subdirectory of ``project_dir`` holding the ``checkpoint_N`` family
    #: (save, auto-resume, and ``Accelerator.checkpoint_manager`` all use it)
    checkpoints_dir_name: str = "checkpoints"
    #: subdirectory of ``project_dir`` for the compile cache (persistent
    #: XLA cache + serialized-executable store) when a ``CompileKwargs``
    #: handler is active and neither ``CompileKwargs.cache_dir`` nor
    #: ``ACCELERATE_COMPILE_CACHE_DIR`` names one explicitly
    compile_cache_dir_name: str = "compile_cache"

    def set_directories(self, project_dir: Optional[str] = None):
        self.project_dir = project_dir
        if self.logging_dir is None:
            self.logging_dir = project_dir

    def __post_init__(self):
        if self.logging_dir is None:
            self.logging_dir = self.project_dir


@dataclass
class MixedPrecisionPolicy(KwargsHandler):
    """The dtype policy used to build the jitted step: params stay in
    ``param_dtype`` (fp32 master copy), matmuls run in ``compute_dtype``,
    outputs/loss come back in fp32 — the structural equivalent of the
    reference's autocast-wrap + ``convert_outputs_to_fp32``
    (reference: accelerator.py:1590-1601, operations.py:814)."""

    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    output_dtype: str = "float32"
    # Attention softmax math dtype. None (default) keeps the f32 logits /
    # softmax chain — the numerically conservative choice. "bfloat16" skips
    # the f32 materialisation of the [B, H, S, S] logits, the biggest
    # avoidable HBM traffic of a short-sequence encoder step (the
    # benchmark's BERT-base cell runs with it). Opt in when your
    # convergence gates pass with it.
    softmax_dtype: Optional[str] = None
    # fp8 mode: the blanket cast stays bf16 (casting raw params/activations
    # to e4m3 without per-tensor scaling destroys training); hot matmuls use
    # the scaled e4m3 path (utils.quantization.fp8_dot — the TE-recipe
    # equivalent, reference: utils/transformer_engine.py:26-163)
    fp8: bool = False

    @classmethod
    def from_mixed_precision(cls, mixed_precision: str) -> "MixedPrecisionPolicy":
        mp = PrecisionType(mixed_precision or "no")
        if mp == PrecisionType.NO:
            return cls(compute_dtype="float32")
        if mp == PrecisionType.BF16:
            return cls(compute_dtype="bfloat16")
        if mp == PrecisionType.FP16:
            return cls(compute_dtype="float16")
        if mp == PrecisionType.FP8:
            return cls(compute_dtype="bfloat16", fp8=True)
        raise ValueError(mixed_precision)


@dataclass
class ParallelismPlugin(KwargsHandler):
    """The one strategy plugin: a mesh layout + sharding rules + remat policy.

    Subsumes the reference's ``FullyShardedDataParallelPlugin`` (~580 lines,
    utils/dataclasses.py:1489), ``TorchTensorParallelPlugin`` (:2070),
    ``DeepSpeedPlugin`` (:1059) and ``MegatronLMPlugin`` (:2112)."""

    mesh_config: MeshConfig = field(default_factory=MeshConfig)
    # explicit (regex, PartitionSpec) rules; None -> auto (model-provided
    # rules if available, else fsdp auto-rules when fsdp axis > 1)
    sharding_rules: Optional[Any] = None
    # ZeRO-1/2: shard optimizer state over the data axis even when params
    # are replicated ("cross-replica weight-update sharding"). This is the
    # PASSIVE layout mode: the update itself stays replicated and GSPMD
    # moves shards around it. Works with any optax transformation.
    shard_optimizer_state: bool = False
    # ZeRO-1, the EXPLICIT wire mode (docs/usage_guides/zero_redundancy.md):
    # reduce-scatter grads over the data axes -> each replica updates only
    # its 1/n flat segment of params + optimizer state (state *born*
    # sharded, so per-device optimizer HBM divides by n from step 0) ->
    # all-gather the updates. Composes with grad_compression
    # ("bf16"|"int8"|"fp8"): both wire legs carry quantized payloads with
    # per-rank error feedback. Requires an elementwise optax
    # transformation (sgd/adam/adamw/...; use shard_optimizer_state for
    # factored/coupled ones) and the fast path (build_train_step).
    zero_stage: int = 0
    # ZeRO-offload analogue (reference: DeepSpeedPlugin
    # offload_optimizer_device / FSDP cpu_offload,
    # utils/dataclasses.py:1100-1180): optimizer moments live on
    # ``pinned_host`` memory-kind shardings and stream through HBM inside
    # the jitted step — HBM high-water mark drops by the state bytes
    # (2x fp32 params for Adam) at the cost of PCIe/host traffic per
    # sync boundary. Composes with shard_optimizer_state (the host copy
    # keeps the ZeRO layout).
    offload_optimizer: bool = False
    # activation rematerialisation policy name (see accelerator.build_train_step)
    remat_policy: Optional[str] = None
    donate_state: bool = True
    # compress the data-parallel gradient reduction ("bf16" | "int8" |
    # "powersgd[:rank]") — the reference's DDP comm hooks incl. PowerSGD
    # (utils/dataclasses.py:130-226), for multi-host data axes where DCN
    # bytes are the bottleneck
    grad_compression: Optional[str] = None

    @classmethod
    def from_env(cls) -> "ParallelismPlugin":
        return cls(
            mesh_config=MeshConfig.from_env(),
            shard_optimizer_state=parse_flag_from_env("ACCELERATE_SHARD_OPTIMIZER_STATE"),
            zero_stage=int(os.environ.get("ACCELERATE_ZERO_STAGE", "0") or 0),
            offload_optimizer=parse_flag_from_env("ACCELERATE_OFFLOAD_OPTIMIZER"),
            remat_policy=os.environ.get("ACCELERATE_REMAT_POLICY") or None,
            grad_compression=os.environ.get("ACCELERATE_GRAD_COMPRESSION") or None,
        )

    def __post_init__(self):
        if self.grad_compression is not None and self.grad_compression not in ("bf16", "int8", "fp8"):
            from ..parallel.compression import powersgd_rank

            if powersgd_rank(self.grad_compression) is None:
                raise ValueError(
                    f"grad_compression must be bf16|int8|fp8|powersgd[:rank], got {self.grad_compression!r}"
                )
        if self.zero_stage not in (0, 1):
            raise ValueError(f"zero_stage must be 0 or 1, got {self.zero_stage!r}")
        if self.zero_stage:
            from ..parallel.compression import powersgd_rank

            if powersgd_rank(self.grad_compression) is not None:
                raise ValueError(
                    "zero_stage=1 does not compose with grad_compression='powersgd' "
                    "(low-rank factors are psum-shaped, not reduce-scatterable); "
                    "use bf16|int8|fp8"
                )
            if self.offload_optimizer:
                raise ValueError(
                    "zero_stage=1 already shards the optimizer state 1/n per device; "
                    "it does not compose with offload_optimizer (pick one)"
                )
            if self.shard_optimizer_state:
                raise ValueError(
                    "pass either zero_stage=1 (explicit reduce-scatter/all-gather wire) "
                    "or shard_optimizer_state=True (passive GSPMD layout), not both"
                )


def add_model_config_to_megatron_parser(*a, **k):  # pragma: no cover
    raise NotImplementedError("Megatron-LM integration does not exist on TPU; use ParallelismPlugin mesh axes")
