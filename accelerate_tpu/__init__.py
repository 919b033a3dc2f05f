"""accelerate_tpu — TPU-native training orchestration.

The capabilities of HF Accelerate (reference: sbhavani/accelerate @
1.10.0.dev0), re-designed for the TPU execution model: one
``jax.sharding.Mesh``, declarative ``NamedSharding`` layouts, and a single
jitted train step. Every reference "strategy" (DDP/FSDP/ZeRO/TP/SP) is a
mesh layout policy here, not a separate code path.
"""

__version__ = "0.1.0"

from .state import AcceleratorState, GradientState, PartialState
from .logging import get_logger
from .utils import (
    CompileKwargs,
    DataLoaderConfiguration,
    DistributedType,
    GradientAccumulationPlugin,
    MixedPrecisionPolicy,
    ParallelismPlugin,
    PrecisionType,
    ProjectConfiguration,
    find_executable_batch_size,
    set_seed,
)
from .parallel import MeshConfig

# Heavier modules (accelerator, data_loader, checkpointing, tracking, models)
# are imported lazily to keep `import accelerate_tpu` light; the canonical
# user entrypoint is re-exported here once defined.
from .accelerator import Accelerator  # noqa: E402
from .modeling import Model  # noqa: E402
from .data_loader import prepare_data_loader, skip_first_batches  # noqa: E402
from .optimizer import AcceleratedOptimizer  # noqa: E402
from .scheduler import AcceleratedScheduler  # noqa: E402
from .local_sgd import LocalSGD  # noqa: E402
from .generation import beam_search, generate, generate_seq2seq, per_token_latency  # noqa: E402
from .scheduling import (  # noqa: E402
    FleetRoutingPolicy,
    RoutingConfig,
    Scheduler,
    SchedulerConfig,
    ShedError,
)
from .serving import ServingEngine  # noqa: E402
from .serving_fleet import FleetConfig, FleetRouter, RadixPrefixCache  # noqa: E402
from .speculative import speculative_generate  # noqa: E402
from .launchers import debug_launcher, notebook_launcher  # noqa: E402

# the phase log hears jax's compile events from here on (telemetry/trace.py)
from .telemetry.trace import phase_log as _phase_log  # noqa: E402

_phase_log().listen()
