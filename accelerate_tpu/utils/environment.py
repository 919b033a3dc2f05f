"""Environment parsing and manipulation helpers.

Plays the role of the reference's ``utils/environment.py``
(reference: src/accelerate/utils/environment.py:59-360): string->bool parsing,
flag parsing from env, and context managers to clear/patch the process
environment. CUDA/NUMA-specific helpers from the reference have no TPU
meaning and are intentionally absent.
"""

from __future__ import annotations

import functools
import os
from contextlib import contextmanager

_TRUE = {"1", "true", "yes", "on", "y", "t"}
_FALSE = {"0", "false", "no", "off", "n", "f", ""}


def str_to_bool(value: str) -> int:
    """Convert a string to 1/0 (reference: utils/environment.py:59)."""
    value = str(value).lower().strip()
    if value in _TRUE:
        return 1
    if value in _FALSE:
        return 0
    raise ValueError(f"invalid truth value {value!r}")


def get_int_from_env(env_keys, default: int) -> int:
    """First set env var among ``env_keys`` parsed as int, else ``default``."""
    for key in env_keys:
        val = os.environ.get(key)
        if val is not None and val != "":
            return int(val)
    return default


def parse_flag_from_env(key: str, default: bool = False) -> bool:
    """Parse a boolean flag from the environment (reference: utils/environment.py:83)."""
    value = os.environ.get(key)
    if value is None:
        return default
    return bool(str_to_bool(value))


def parse_choice_from_env(key: str, default: str = "no") -> str:
    return os.environ.get(key, default)


def are_libraries_initialized(*library_names: str) -> list[str]:
    """Return the subset of ``library_names`` already imported in this process."""
    import sys

    return [name for name in library_names if name in sys.modules]


@contextmanager
def clear_environment():
    """Temporarily empty ``os.environ`` (reference: utils/environment.py:291)."""
    saved = dict(os.environ)
    os.environ.clear()
    try:
        yield
    finally:
        os.environ.clear()
        os.environ.update(saved)


@contextmanager
def patch_environment(**kwargs):
    """Temporarily set env vars; keys are upper-cased (reference: utils/environment.py:326)."""
    saved = {}
    missing = object()
    for key, value in kwargs.items():
        key = key.upper()
        saved[key] = os.environ.get(key, missing)
        os.environ[key] = str(value)
    try:
        yield
    finally:
        for key, value in saved.items():
            if value is missing:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value


def purge_accelerate_environment(func):
    """Decorator: run ``func`` with all ``ACCELERATE_*`` env vars removed
    (reference: utils/environment.py:362). Used by the test harness so state
    leakage between tests cannot occur through the env-var protocol."""

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        saved = {k: v for k, v in os.environ.items() if k.startswith("ACCELERATE_")}
        for k in saved:
            del os.environ[k]
        try:
            return func(*args, **kwargs)
        finally:
            os.environ.update(saved)

    return wrapper


def force_host_platform(n_devices: int = 8) -> None:
    """Ask for the JAX CPU (host) platform with ``n_devices`` virtual devices.

    An explicit request, made by the fake-mesh entry points
    (tests/conftest.py, ``__graft_entry__.dryrun_multichip``, the analyzer
    CLIs) and by nothing on the accelerator path. A process started with
    ``JAX_PLATFORMS=cpu`` already runs on the host and needs this only for
    the device count. Must run before the first backend use in this
    process; if a backend was already initialised it is dropped so the CPU
    platform (re-)initialises with the requested count.
    """
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    opt = f"--xla_force_host_platform_device_count={n_devices}"
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", opt, flags)
    else:
        flags = f"{flags} {opt}"
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.extend.backend

    jax.config.update("jax_platforms", "cpu")
    jax.extend.backend.clear_backends()


def get_free_port() -> int:
    """An OS-assigned free TCP port (reference: utils/other.py:474
    ``get_free_port``) — used by the launcher so concurrent local process
    groups don't collide on the default coordinator port."""
    import socket

    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
