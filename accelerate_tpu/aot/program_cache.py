"""ProgramCache: the shared front-end every compiled program goes through.

One object answers "give me the executable for this function at these
avals" three ways, cheapest first:

1. **memory** — same process already built it: return it;
2. **disk** — another process built it (:class:`~accelerate_tpu.aot.cache.
   ExecutableStore`): deserialize instead of compiling — the warm-start
   path a restarted trainer or a new serving replica takes;
3. **compile** — ``lowered.compile()``, then serialize into the store so
   the NEXT process hits (2).

Every outcome lands in telemetry: ``compile_cache_hit`` (with
``source: "memory"|"disk"`` and ``deserialize_ms``), ``compile_cache_miss``
(with ``compile_ms``), ``compile_cache_store``, and ``compile_cache_reject``
for a poisoned/stale entry that was healed. A program that was compiled or
loaded from disk also reports its executable's ``alias_bytes`` (argument
bytes it updates in place: what its donations bought) and ``temp_bytes``,
where the backend gives a memory analysis. Counters mirror onto the
instance (``hits`` / ``misses`` / ``deserialized`` / ``rejected``) so code
with no event log still has the numbers.

:meth:`wrap_jit` is the bridge for functions whose input avals are only
known at call time (``build_train_step``): it shadows ``jax.jit``'s
dispatch with a signature-keyed executable table, so a restarted process
re-creating the same step function dispatches straight into deserialized
executables — 0 XLA compiles, recompile watchdog silent.
"""

from __future__ import annotations

import os
import time
import weakref
from operator import is_ as _is
from typing import Callable, Optional

from ..telemetry.trace import phase, phase_log
from .cache import (
    CorruptEntryError,
    ExecutableStore,
    StaleEntryError,
    content_key,
    deserialize_compiled,
    resolve_cache_dir,
    serialize_compiled,
)


def _jax():
    import jax

    return jax


def _noop_log():
    from ..telemetry.eventlog import EventLog

    return EventLog(None)


def _memory_fields(compiled) -> dict:
    """``alias_bytes`` / ``temp_bytes`` of an executable for its compile
    or load event; empty where the backend has no memory analysis."""
    try:
        m = compiled.memory_analysis()
        return {"alias_bytes": int(m.alias_size_in_bytes), "temp_bytes": int(m.temp_size_in_bytes)}
    except Exception:  # noqa: BLE001 — telemetry never fails a compile
        return {}


def _deref(ref):
    return ref()


class _ArgSignature:
    """What one top-level argument of a :meth:`ProgramCache.wrap_jit` call
    contributes to the executable table's key: its treedef and, a leaf,
    the aval (shape, dtype, weak type: a jax array carries it ready made)
    and the sharding, or shape and dtype of a numpy leaf, or a Python
    scalar's type and value. The hash is computed once: a signature kept
    from the call before is the very object in the table's key.
    ``by_identity``: every leaf is a jax array, which nothing can change
    in place, so the same leaves are the same signature."""

    __slots__ = ("key", "by_identity", "_hash")

    def __init__(self, treedef, leaves):
        sig = [treedef]
        self.by_identity = True
        for x in leaves:
            aval = getattr(x, "aval", None)
            if aval is not None:
                sig.append(aval)
                sig.append(getattr(x, "sharding", None))
                continue
            self.by_identity = False
            shape, dtype = getattr(x, "shape", None), getattr(x, "dtype", None)
            if shape is None or dtype is None:
                sig.append(("py", type(x).__name__, x if isinstance(x, (bool, int, float, str)) else None))
            else:
                sig.append((tuple(shape), dtype))
        self.key = tuple(sig)
        self._hash = hash(self.key)

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        return self is other or (isinstance(other, _ArgSignature) and self.key == other.key)


class ProgramCache:
    """Compile-or-fetch for jitted programs, with an optional persistent
    executable store and full telemetry.

    ``store=None`` keeps the cache memory-only (still deduplicates and
    still counts); pass an :class:`ExecutableStore` (or use
    :meth:`from_env`) to make executables survive the process.
    """

    def __init__(self, store: Optional[ExecutableStore] = None, log=None, name: str = "programs"):
        self.store = store
        self.log = log if log is not None else _noop_log()
        self.name = name
        self._mem: dict = {}
        self.hits = 0
        self.misses = 0
        self.deserialized = 0
        self.rejected = 0
        self.leaves_signed = 0  # leaves whose signature a wrap_jit call computed
        self.signatures_kept = 0  # top-level arguments of wrap_jit calls whose signature was the last call's
        self._serialize_broken = False  # backend can't serialize; warn once

    @classmethod
    def from_env(cls, log=None, project_dir: Optional[str] = None, name: str = "programs") -> "ProgramCache":
        """A cache whose store follows ``ACCELERATE_COMPILE_CACHE_DIR``
        (or ``{project_dir}/compile_cache``); memory-only when neither is
        set — the zero-config construction serving/CLI paths use."""
        cache_dir = resolve_cache_dir(project_dir=project_dir)
        return cls(store=ExecutableStore(cache_dir) if cache_dir else None, log=log, name=name)

    # ------------------------------------------------------------------ #
    # compile-or-fetch
    # ------------------------------------------------------------------ #

    def compile(
        self,
        fn: Callable,
        *avals,
        name: str = "program",
        donate_argnums=(),
        static_argnums=(),
        key_salt=(),
    ):
        """``jit(fn).lower(*avals)`` then :meth:`compile_lowered` — the
        explicit-avals path (AOT prepare, CLI ``warm``, serving buckets)."""
        jax = _jax()
        jit_kwargs = {}
        if donate_argnums:
            jit_kwargs["donate_argnums"] = tuple(donate_argnums)
        if static_argnums:
            jit_kwargs["static_argnums"] = tuple(static_argnums)
        with phase("program.lower", program=name):
            lowered = jax.jit(fn, **jit_kwargs).lower(*avals)
        return self.compile_lowered(lowered, name=name, key_salt=key_salt)

    def compile_lowered(self, lowered, name: str = "program", key_salt=()):
        """Memory -> disk -> compile for an already-lowered program.
        Returns the loaded executable; never returns a stale or corrupt
        deserialization (those entries are deleted and recompiled). The
        whole of it, from the key's hash to the store's write, is one
        ``program.load`` span of the phase log, with the ``source`` the
        executable came from and its bytes."""
        with phase("program.load", program=name) as span:
            compiled, came_from = self._fetch_or_compile(lowered, name, key_salt)
            span.counts.update(came_from)
        return compiled

    def _fetch_or_compile(self, lowered, name: str, key_salt) -> tuple:
        """:meth:`compile_lowered`'s body: the executable, and ``source`` with the memory fields for its span."""
        key = content_key(lowered, extra=key_salt)
        cached = self._mem.get(key)
        if cached is not None:
            self.hits += 1
            self.log.event("compile_cache_hit", program=name, key=key[:16], source="memory")
            return cached, {"source": "memory"}

        if self.store is not None:
            blob = None
            try:
                blob = self.store.get(key)
            except (CorruptEntryError, StaleEntryError) as e:
                # poisoned/stale entry: reject cleanly, heal, fall through
                self.rejected += 1
                self.store.remove(key)
                self.log.event(
                    "compile_cache_reject", severity="warning", program=name, key=key[:16],
                    reason=type(e).__name__, detail=str(e)[:200],
                )
            if blob is not None:
                t0 = time.perf_counter()
                try:
                    compiled = deserialize_compiled(blob)
                except Exception as e:  # undeserializable payload = poison too
                    self.rejected += 1
                    self.store.remove(key)
                    self.log.event(
                        "compile_cache_reject", severity="warning", program=name, key=key[:16],
                        reason=type(e).__name__, detail=str(e)[:200],
                    )
                else:
                    ms = (time.perf_counter() - t0) * 1000.0
                    self.hits += 1
                    self.deserialized += 1
                    self._mem[key] = compiled
                    came_from = {"source": "disk", **_memory_fields(compiled)}
                    self.log.event(
                        "compile_cache_hit", program=name, key=key[:16], deserialize_ms=round(ms, 3), **came_from
                    )
                    self.log.counter("compile_cache.deserialize_ms", round(ms, 3), program=name)
                    return compiled, came_from

        t0 = time.perf_counter()
        compiled = self._compile_fresh(lowered)
        ms = (time.perf_counter() - t0) * 1000.0
        self.misses += 1
        self._mem[key] = compiled
        memory = _memory_fields(compiled)
        self.log.event("compile_cache_miss", program=name, key=key[:16], compile_ms=round(ms, 3), **memory)
        self.log.counter("compile_cache.compile_ms", round(ms, 3), program=name)
        if self.store is not None and not self._serialize_broken:
            try:
                self.store.put(key, serialize_compiled(compiled), name=name)
                self.log.event("compile_cache_store", program=name, key=key[:16])
            except Exception as e:
                # some backends can't serialize every executable; the cache
                # degrades to memory-only rather than failing the compile
                self._serialize_broken = True
                self.log.event(
                    "compile_cache_store_failed", severity="warning", program=name,
                    reason=type(e).__name__, detail=str(e)[:200],
                )
        # jax's persistent cache may have had it: then the span says ``disk``, as for a hit in the store
        return compiled, {"source": phase_log().compile_source(), **memory}

    def _compile_fresh(self, lowered):
        """``lowered.compile()``, asking jax to leave its persistent XLA
        cache out of it when an executable store is attached: XLA:CPU
        executables *restored from that disk cache* serialize into blobs
        that fail to load ("Symbols not found" / "Function ... not found")
        — only a fresh compile yields a serializable executable there. On
        the TPU restored executables serialize into blobs that load (PR 21
        chip run: 14 + 5 store entries written from cache hits, all
        deserialized, 0 rejected). Under the installed jax the flag is
        latched at the process's first compile
        (``compilation_cache.is_cache_used``), so this request only takes
        effect in a process whose first compile is this one. A CPU process
        that must ship loadable blobs turns the cache off for itself before
        its first compile (``serving_proc.worker_main``)."""
        jax = _jax()
        if self.store is None or self._serialize_broken or not jax.config.jax_enable_compilation_cache:
            return lowered.compile()
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            return lowered.compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)

    # ------------------------------------------------------------------ #
    # call-time dispatch (avals unknown until the first call)
    # ------------------------------------------------------------------ #

    def wrap_jit(self, jitted, name: str = "step", static_argnums=()):
        """Shadow a ``jax.jit`` function's dispatch with this cache.

        The wrapper keys on the concrete input signature (per argument:
        treedef + per-leaf aval and sharding; plus the static arg values)
        and keeps one executable per signature: a first-seen signature
        lowers and goes through :meth:`compile_lowered` (so a restarted
        process deserializes instead of compiling), later calls dispatch
        straight to the executable.

        A call costs the host by what changed: the signature of a
        top-level argument whose leaves are, one for one, the objects
        they were in the previous call (the parameters of an engine's
        life) is kept and not computed again; only its flattening and an
        identity check a leaf remain, so a tree mutated in place is
        signed anew (and an argument with a leaf that is no jax array is
        signed every call). The last call's leaves are held weakly:
        nothing is kept alive for it. ``leaves_signed`` and
        ``signatures_kept`` count both roads on the cache. Exposes
        ``_cache_size`` so the PR-3 recompile watchdog's jit-cache probe
        keeps working through the wrapper."""
        jax = _jax()
        flatten = jax.tree_util.tree_flatten
        statics = tuple(static_argnums)
        table: dict = {}
        last: dict = {}  # argument position or keyword -> (treedef, weak refs to its leaves, its _ArgSignature)

        def sign(where, arg):
            leaves, treedef = flatten(arg)
            seen = last.get(where)
            if seen is not None and seen[0] == treedef and all(map(_is, map(_deref, seen[1]), leaves)):
                self.signatures_kept += 1
                return seen[2]
            self.leaves_signed += len(leaves)
            signed = _ArgSignature(treedef, leaves)
            if signed.by_identity:
                last[where] = (treedef, [weakref.ref(leaf) for leaf in leaves], signed)
            else:  # a numpy array's shape or a Python scalar's value can change under the same object: signed every call
                last.pop(where, None)
            return signed

        def dispatch(*args, **kwargs):
            if kwargs and statics:
                # keyword args + positional statics don't compose in the
                # AOT call convention; fall back to plain jit dispatch
                return jitted(*args, **kwargs)
            dyn = tuple(a for i, a in enumerate(args) if i not in statics) if statics else args
            stat = tuple(args[i] for i in statics)
            sig = (
                tuple(sign(i, a) for i, a in enumerate(dyn)),
                tuple((k, sign(k, kwargs[k])) for k in sorted(kwargs)) if kwargs else (),
                stat,
            )
            compiled = table.get(sig)
            if compiled is None:
                with phase("program.lower", program=name):
                    lowered = jitted.lower(*args, **kwargs)
                compiled = self.compile_lowered(lowered, name=name)
                table[sig] = compiled
            return compiled(*dyn, **kwargs)

        dispatch._cache_size = lambda: len(table)
        dispatch._program_cache = self
        dispatch.__wrapped__ = jitted
        return dispatch

    # ------------------------------------------------------------------ #
    # explicit AOT surface + stats
    # ------------------------------------------------------------------ #

    def aot_export(self, out_path: str, keys=None) -> int:
        """Bundle the store's executables into a portable archive (ship to
        a replica fleet, bake into an image). Requires a store."""
        if self.store is None:
            raise ValueError("aot_export needs a persistent store (set ACCELERATE_COMPILE_CACHE_DIR or CompileKwargs.cache_dir)")
        n = self.store.export_archive(out_path, keys=keys)
        self.log.event("compile_cache_export", path=out_path, entries=n)
        return n

    def aot_load(self, in_path: str) -> int:
        """Import an :meth:`aot_export` archive into the store; programs
        built afterwards deserialize instead of compiling."""
        if self.store is None:
            raise ValueError("aot_load needs a persistent store (set ACCELERATE_COMPILE_CACHE_DIR or CompileKwargs.cache_dir)")
        n = self.store.import_archive(in_path)
        self.log.event("compile_cache_import", path=in_path, entries=n)
        return n

    def stats(self) -> dict:
        out = {
            "hits": self.hits,
            "misses": self.misses,
            "deserialized": self.deserialized,
            "rejected": self.rejected,
            "in_memory": len(self._mem),
            "leaves_signed": self.leaves_signed,
            "signatures_kept": self.signatures_kept,
        }
        if self.store is not None:
            out["store_dir"] = self.store.path
            out["store_entries"] = len(self.store.keys())
            out["store_bytes"] = self.store.total_bytes()
        return out


def default_program_cache(log=None, project_dir: Optional[str] = None) -> Optional[ProgramCache]:
    """A :class:`ProgramCache` when the environment opted into persistence
    (``ACCELERATE_COMPILE_CACHE_DIR`` set), else None — the hook cheap
    call sites (ServingEngine's default) use without forcing a cache on
    every user."""
    if not os.environ.get("ACCELERATE_COMPILE_CACHE_DIR") and not project_dir:
        return None
    return ProgramCache.from_env(log=log, project_dir=project_dir)
