"""JAX platform selection and the compile cache, for every entry point.

One process holds a chip, and a chip that cannot be had is an error:

- ``ensure_backend(requested)`` is what a JAX-using entry point calls
  first. An explicit request (``--platform`` / ``DLI_PLATFORM``) is
  pinned before backend init; otherwise JAX's default stands. JAX itself
  drops to the CPU when it finds no accelerator, so a default that turns
  out to be ``cpu`` while nobody asked for ``cpu`` raises
  ``BackendUnavailable`` (a ``SystemExit``) — the process exits non-zero
  instead of serving or measuring on the wrong device.
- ``enable_compilation_cache()`` is the one place the persistent compile
  cache is placed: wherever ``JAX_COMPILATION_CACHE_DIR`` says, else a
  fixed directory inside the checkout (the path is part of the cache
  key, so a directory that moves never hits).
"""

from __future__ import annotations

import os
from typing import Optional

# <repo>/.jax_cache (git-ignored): utils/ -> package -> checkout root
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


class BackendUnavailable(SystemExit):
    """JAX fell back to the CPU and nobody asked for the CPU. Only entry
    points ask, so left uncaught it IS the contract: the message on
    stderr and a non-zero exit code, no result."""


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache so restarted workers
    and repeated runs reuse compiled executables, and cache every entry
    however small or quick. With ``JAX_COMPILATION_CACHE_DIR`` set the
    directory is JAX's to read from the environment and none is set
    here. Returns the directory in use."""
    import jax
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def force_platform(platform: str) -> None:
    """Pin the JAX platform before any backend init (cpu|tpu|...)."""
    import jax
    jax.config.update("jax_platforms", platform)


def free_port() -> int:
    """An OS-assigned free localhost TCP port, for services that must
    know their address BEFORE binding (an HA master advertises its URL
    to peers; a subprocess under test is launched with an explicit
    port). Inherently racy against other binders — fine for tests and
    local fleets, not a general allocator."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def pin_platform(requested: Optional[str] = None) -> Optional[str]:
    """Pin an explicit request (argument, else ``DLI_PLATFORM``) and
    place the compile cache, WITHOUT initializing the backend — a
    multi-host worker must join ``jax.distributed`` first. Returns what
    was asked for (``JAX_PLATFORMS`` counts: JAX honors it itself), or
    None when the default backend is wanted."""
    requested = requested or os.environ.get("DLI_PLATFORM") or None
    if requested:
        force_platform(requested)
    enable_compilation_cache()
    return requested or os.environ.get("JAX_PLATFORMS") or None


def check_backend(asked: Optional[str]) -> str:
    """Initialize the backend and return its platform. Raises whatever
    JAX raises for a requested platform it cannot initialize, and
    ``BackendUnavailable`` when the default came out as ``cpu`` without
    ``cpu`` having been asked for."""
    import jax
    platform = jax.default_backend()
    if platform == "cpu" and "cpu" not in (asked or "").split(","):
        raise BackendUnavailable(
            "error: JAX found no accelerator and fell back to cpu, and "
            "cpu was not requested; pass --platform cpu (or "
            "DLI_PLATFORM=cpu / JAX_PLATFORMS=cpu) to run on the CPU on "
            "purpose")
    return platform


def ensure_backend(requested: Optional[str] = None) -> str:
    """Decide the platform for this process; call BEFORE any
    ``jax.devices()``. Returns the platform the backend came up on."""
    return check_backend(pin_platform(requested))
