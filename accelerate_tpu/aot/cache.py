"""On-disk executable store + content keys: the persistence layer under
:class:`~accelerate_tpu.aot.ProgramCache`.

Two caches cooperate to kill repeat compiles, and they answer different
questions:

* **jax's persistent compilation cache** (:func:`configure_persistent_cache`)
  keys on XLA's own fingerprint and saves the *compile* — a second
  ``jit`` of the same program still pays tracing + lowering + a cache
  probe inside XLA, but not optimization. It is transparent and safe to
  leave on everywhere.
* the **executable store** here keys on OUR content key and saves the
  *executable*: ``jit(fn).lower(...).compile()`` results serialized via
  ``jax.experimental.serialize_executable``, so a *different process* —
  a new serving replica, or a preemption-resumed trainer — deserializes
  and runs with **zero** XLA compiles. This is the AOT warm-start path.

The content key is a sha256 over everything that makes two programs
interchangeable: the lowered StableHLO text (which bakes in the jaxpr,
input avals, shardings, and donation), the backend platform, the device
count, and the jax + jaxlib versions. Any drift — a new jax, a different
mesh, a changed shape — lands on a different key, so a stale entry can
never be replayed. Entries additionally carry a crc32-guarded header;
a truncated or poisoned entry fails validation and is rejected (and
healed) instead of feeding XLA garbage.

Entry layout (one file per program, ``<key>.aotx``)::

    ATPX1\\n
    {"key": ..., "name": ..., "crc32": ..., "size": ..., "jax": ...}\\n
    <pickled (xla payload, in_tree, out_tree, device ids)>

Writes are atomic (tmp + rename) so a killed process never publishes a
half-written entry.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
import time
import zlib
from typing import Optional

_MAGIC = b"ATPX1"
_SUFFIX = ".aotx"


class CorruptEntryError(Exception):
    """The entry bytes fail structural/crc validation (poisoned cache)."""


class StaleEntryError(Exception):
    """The entry was written by a different jax/jaxlib/backend and must
    not be deserialized into this process."""


def _versions() -> dict:
    import jax

    try:
        import jaxlib

        jaxlib_v = getattr(jaxlib, "__version__", "")
    except Exception:  # pragma: no cover - jaxlib always ships with jax
        jaxlib_v = ""
    return {"jax": jax.__version__, "jaxlib": jaxlib_v}


def backend_descriptor() -> dict:
    """``{"platform", "ndev"}`` for the live backend — part of the content
    key because a serialized executable is only loadable onto the same
    platform with the same device population."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "ndev": len(devices)}


def content_key(lowered, extra=()) -> str:
    """Content key for a ``jax.jit(fn).lower(...)`` result.

    The StableHLO text already pins the jaxpr, the input avals, the input/
    output shardings (and therefore the mesh layout), and the donation
    plan; versions + backend + ``extra`` salt ride along so upgrades and
    topology changes invalidate naturally instead of deserializing an
    incompatible executable.
    """
    h = hashlib.sha256()
    h.update(lowered.as_text().encode())
    v = _versions()
    b = backend_descriptor()
    for part in (v["jax"], v["jaxlib"], b["platform"], str(b["ndev"]), *extra):
        h.update(b"\x00" + str(part).encode())
    return h.hexdigest()


def serialize_compiled(compiled) -> bytes:
    """A compiled executable -> storable bytes (XLA payload, the arg
    pytree defs ``deserialize_and_load`` needs on the other side, and the
    ids of the devices the program was compiled for)."""
    from jax.experimental import serialize_executable

    payload, in_tree, out_tree = serialize_executable.serialize(compiled)
    device_ids = [d.id for d in compiled._executable._unloaded_executable.device_list]
    return pickle.dumps((payload, in_tree, out_tree, device_ids), protocol=4)


def deserialize_compiled(blob: bytes):
    """Inverse of :func:`serialize_compiled`: bytes -> a loaded, callable
    executable (no XLA compile happens here). The program is loaded onto
    the devices it was compiled for: left to itself, jax loads it onto
    every device of the backend, and a one-device program then refuses its
    arguments on a host with several."""
    import jax
    from jax.experimental import serialize_executable

    payload, in_tree, out_tree, device_ids = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return serialize_executable.deserialize_and_load(
        payload, in_tree, out_tree, execution_devices=[by_id[i] for i in device_ids]
    )


class ExecutableStore:
    """Content-addressed directory of serialized executables.

    ``get`` raises :class:`CorruptEntryError` / :class:`StaleEntryError`
    rather than returning bad bytes — the caller (ProgramCache) treats
    both as a miss, deletes the offender, and recompiles; a poisoned
    cache degrades to a cold one, never to wrong execution.
    """

    def __init__(self, path: str):
        self.path = str(path)
        os.makedirs(self.path, exist_ok=True)

    # ------------------------------------------------------------------ #
    # entry IO
    # ------------------------------------------------------------------ #

    def _entry_path(self, key: str) -> str:
        return os.path.join(self.path, key + _SUFFIX)

    def put(self, key: str, blob: bytes, name: str = "program", meta: Optional[dict] = None) -> str:
        header = {
            "key": key,
            "name": name,
            "crc32": zlib.crc32(blob),
            "size": len(blob),
            "created": time.time(),
            **_versions(),
            **backend_descriptor(),
        }
        if meta:
            header.update(meta)
        final = self._entry_path(key)
        fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(_MAGIC + b"\n")
                f.write(json.dumps(header, sort_keys=True).encode() + b"\n")
                f.write(blob)
            os.replace(tmp, final)  # atomic publish
        except BaseException:
            if os.path.exists(tmp):
                os.remove(tmp)
            raise
        return final

    def read_header(self, key: str) -> Optional[dict]:
        path = self._entry_path(key)
        if not os.path.exists(path):
            return None
        with open(path, "rb") as f:
            magic = f.readline().rstrip(b"\n")
            if magic != _MAGIC:
                raise CorruptEntryError(f"{path}: bad magic {magic!r}")
            try:
                return json.loads(f.readline())
            except json.JSONDecodeError as e:
                raise CorruptEntryError(f"{path}: unreadable header ({e})") from e

    def get(self, key: str) -> Optional[bytes]:
        path = self._entry_path(key)
        if not os.path.exists(path):
            return None
        with open(path, "rb") as f:
            magic = f.readline().rstrip(b"\n")
            if magic != _MAGIC:
                raise CorruptEntryError(f"{path}: bad magic {magic!r}")
            try:
                header = json.loads(f.readline())
            except json.JSONDecodeError as e:
                raise CorruptEntryError(f"{path}: unreadable header ({e})") from e
            blob = f.read()
        # version gate BEFORE the crc: a stale entry may be perfectly
        # intact, but deserializing another jax's executable is undefined
        v = _versions()
        for field in ("jax", "jaxlib"):
            if header.get(field) != v[field]:
                raise StaleEntryError(
                    f"{path}: written by {field}={header.get(field)!r}, running {v[field]!r}"
                )
        if header.get("size") != len(blob) or header.get("crc32") != zlib.crc32(blob):
            raise CorruptEntryError(f"{path}: crc/size mismatch (truncated or poisoned)")
        return blob

    def remove(self, key: str) -> bool:
        path = self._entry_path(key)
        if os.path.exists(path):
            os.remove(path)
            return True
        return False

    # ------------------------------------------------------------------ #
    # bulk surface (CLI stats / clear / export)
    # ------------------------------------------------------------------ #

    def keys(self) -> list[str]:
        return sorted(
            f[: -len(_SUFFIX)] for f in os.listdir(self.path) if f.endswith(_SUFFIX)
        )

    def entries(self) -> list[dict]:
        """Header dicts for every entry (corrupt headers reported with an
        ``"error"`` field instead of raising — stats must always print)."""
        out = []
        for key in self.keys():
            try:
                header = self.read_header(key) or {}
            except CorruptEntryError as e:
                header = {"key": key, "error": str(e)}
            header["file_bytes"] = os.path.getsize(self._entry_path(key))
            out.append(header)
        return out

    def total_bytes(self) -> int:
        return sum(
            os.path.getsize(os.path.join(self.path, f))
            for f in os.listdir(self.path)
            if f.endswith(_SUFFIX)
        )

    def clear(self) -> int:
        n = 0
        for key in self.keys():
            self.remove(key)
            n += 1
        return n

    def export_archive(self, out_path: str, keys: Optional[list] = None) -> int:
        """Bundle entries into a ``.tar.gz`` a replica fleet can ship
        around (the ``aot_export`` surface). Returns the entry count."""
        import tarfile

        keys = list(keys) if keys is not None else self.keys()
        os.makedirs(os.path.dirname(os.path.abspath(out_path)) or ".", exist_ok=True)
        with tarfile.open(out_path, "w:gz") as tar:
            for key in keys:
                path = self._entry_path(key)
                if os.path.exists(path):
                    tar.add(path, arcname=key + _SUFFIX)
        return len(keys)

    def import_archive(self, in_path: str) -> int:
        """Unpack an :meth:`export_archive` bundle into this store. Each
        entry is validated (magic + header) before it is published; junk
        members are skipped. Returns the imported entry count."""
        import tarfile

        n = 0
        with tarfile.open(in_path, "r:gz") as tar:
            for member in tar.getmembers():
                base = os.path.basename(member.name)
                if not (member.isfile() and base.endswith(_SUFFIX)):
                    continue
                blob = tar.extractfile(member).read()
                head, _, _ = blob.partition(b"\n")
                if head != _MAGIC:
                    continue
                key = base[: -len(_SUFFIX)]
                fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".tmp")
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, self._entry_path(key))
                n += 1
        return n


def resolve_cache_dir(
    explicit: Optional[str] = None,
    project_dir: Optional[str] = None,
    dir_name: str = "compile_cache",
) -> Optional[str]:
    """The ONE precedence rule for where the executable store lives:
    explicit argument > ``ACCELERATE_COMPILE_CACHE_DIR`` > the project's
    ``ProjectConfiguration`` dir (``{project_dir}/{dir_name}``) > None
    (memory-only cache, no persistence)."""
    if explicit:
        return explicit
    env = os.environ.get("ACCELERATE_COMPILE_CACHE_DIR")
    if env:
        return env
    if project_dir:
        return os.path.join(project_dir, dir_name)
    return None


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def default_compile_cache_dir() -> str:
    """Where jax's persistent compilation cache lives when nobody placed it:
    ``JAX_COMPILATION_CACHE_DIR`` if the environment sets it, else
    ``<checkout>/.cache/jax`` resolved from this package's location. The
    directory is part of the cache's key, so it is a fixed path — never a
    temporary name, a pid or the time."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(_CHECKOUT, ".cache", "jax")


def configure_persistent_cache(cache_dir: Optional[str] = None, min_compile_time_secs: float = 0.0) -> str:
    """Turn on jax's persistent XLA compilation cache and return the
    directory in effect. The ONE place in the tree that sets it.

    Yields to an existing choice: where ``JAX_COMPILATION_CACHE_DIR`` is
    set, jax already reads it and nothing is set in code; where the
    process configured a directory earlier, that one stays (re-pointing
    a shared cache mid-process would split the warm set). Otherwise the
    cache goes to ``cache_dir`` or :func:`default_compile_cache_dir`."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    already = jax.config.jax_compilation_cache_dir
    if already:
        return already
    cache_dir = cache_dir or default_compile_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", float(min_compile_time_secs))
    return cache_dir
