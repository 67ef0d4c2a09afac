"""JAX platform provisioning: the forced-CPU test platform and the
persistent compile cache.

Tests force the CPU platform (``force_virtual_cpu``): the sandbox they
run in has no accelerator, and a virtual N-device CPU platform
(``--xla_force_host_platform_device_count``) is what lets the mesh tests
execute the sharded paths there.  A ``jax_platforms`` config value
outranks the ``JAX_PLATFORMS`` env var, so forcing must happen before
jax initializes AND override the config.  Shared by ``tests/conftest.py``
and ``__graft_entry__.dryrun_multichip``.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"

# <checkout>/.jax_cache: a cache directory that moves between processes
# (tempfile, pid, time) never hits, so it is derived from the package
# location only.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def compile_cache_dir() -> "str | None":
    """The cache directory this code sets: None when
    ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads it itself and the
    cache lives there and nowhere else), ``<checkout>/.jax_cache``
    otherwise."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return DEFAULT_COMPILE_CACHE_DIR


def configure_compile_cache() -> None:
    """Keep compiled scheduler kernels across processes (daemon restarts,
    one tool call after another): every shape bucket otherwise recompiles
    at each start.  The fused kernel compiles in about a second — right
    at JAX's default 1 s minimum-compile-time threshold for persisting an
    entry — so both thresholds are opened: every executable the
    scheduler compiles is kept.

    A process forced onto the CPU platform (tests, ``--multichip``
    children) is a correctness run and keeps nothing: XLA:CPU logs a
    machine-feature mismatch error for every cached executable it loads,
    and no chip time is saved there."""
    import jax

    if jax.config.jax_platforms == "cpu":
        return
    path = compile_cache_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def force_virtual_cpu(n_devices: int) -> None:
    """Force jax onto a virtual ``n_devices``-device CPU platform.

    Must be called before jax first initializes a backend.  Raises if jax
    already initialized on a different platform or with too few devices
    (the env/config knobs are silently inert once a backend exists).
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if _COUNT_FLAG in flags:
        # Replace an ambient count (which may be smaller) rather than
        # trusting it.
        flags = re.sub(rf"{_COUNT_FLAG}=\d+", f"{_COUNT_FLAG}={n_devices}", flags)
        os.environ["XLA_FLAGS"] = flags
    else:
        os.environ["XLA_FLAGS"] = (flags + f" {_COUNT_FLAG}={n_devices}").strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    if devices[0].platform != "cpu":
        raise RuntimeError(
            f"jax already initialized on platform {devices[0].platform!r}; "
            "force_virtual_cpu must run before any jax backend use"
        )
    if len(devices) < n_devices:
        raise RuntimeError(
            f"virtual CPU platform has {len(devices)} devices, need "
            f"{n_devices}: jax initialized before force_virtual_cpu could "
            f"set {_COUNT_FLAG}"
        )
