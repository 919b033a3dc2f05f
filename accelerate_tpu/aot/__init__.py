"""Compile management: persistent executable cache, AOT warm-start, and
shape auto-bucketing.

On TPU, XLA *is* the delegated execution layer — which makes JIT latency
a first-class cost this framework manages instead of an accident the
user eats. Three coupled pieces (see
``docs/usage_guides/compilation.md``):

* :class:`ExecutableStore` / :func:`configure_persistent_cache` — the
  persistence layer: jax's own on-disk compilation cache plus a
  content-keyed store of serialized executables;
* :class:`ProgramCache` — the shared compile-or-fetch front-end
  (``Accelerator.build_train_step``, ``ServingEngine`` buckets, and the
  ``accelerate-tpu compile-cache`` CLI all route through it), with
  ``compile_cache_*`` telemetry on every hit/miss/deserialize;
* :class:`ShapeBucketer` / :func:`pad_batch_tree` — pad ragged
  batch/sequence dims to a learned bucket set so the PR-3 recompile
  watchdog's warning becomes a one-time pad, not a compile storm.
"""

from .bucketing import ShapeBucketer, next_pow2, pad_batch_tree, round_up_to
from .cache import (
    CorruptEntryError,
    ExecutableStore,
    StaleEntryError,
    backend_descriptor,
    configure_persistent_cache,
    content_key,
    default_compile_cache_dir,
    deserialize_compiled,
    resolve_cache_dir,
    serialize_compiled,
)
from .program_cache import ProgramCache, default_program_cache

__all__ = [
    "CorruptEntryError",
    "ExecutableStore",
    "ProgramCache",
    "ShapeBucketer",
    "StaleEntryError",
    "backend_descriptor",
    "configure_persistent_cache",
    "content_key",
    "default_compile_cache_dir",
    "default_program_cache",
    "deserialize_compiled",
    "next_pow2",
    "pad_batch_tree",
    "resolve_cache_dir",
    "round_up_to",
    "serialize_compiled",
]
