"""Per-process JAX set-up: which platform, and where compiled programs go.

JAX honours ``JAX_PLATFORMS`` by itself; what the framework adds is the
policy around it. One process owns each chip (libtpu gives a chip to a
process for that process's lifetime), so drivers and plain workers stay on
the cpu platform or off JAX entirely, TPU workers are spawned with
``JAX_PLATFORMS=tpu`` (core/worker_env.py) and every process that compiles
shares one persistent compilation cache.
"""
from __future__ import annotations

import os
import sys

from ray_tpu import flags as _flags

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Cache every program: the defaults skip compiles under one second, and a
# serving replica's many small programs (splice, pads) add up on every start.
_CACHE_SETTINGS = {
    "jax_persistent_cache_min_compile_time_secs": 0.0,
    "jax_persistent_cache_min_entry_size_bytes": -1,
}


def ensure_platform(platform: str) -> None:
    """Pin this process to one JAX platform before any backend starts (an
    env-runner actor that must stay on the host, a test's cpu mesh)."""
    import jax

    jax.config.update("jax_platforms", platform)


def cpu_mesh_env(n_devices: int = 8) -> None:
    """Configure this process, and the children that inherit its
    environment, for an n-device virtual CPU mesh (test ring 2, SURVEY.md
    §4.4). Must run before jax initializes a backend."""
    xf = _flags.get("XLA_FLAGS", default="")
    if "xla_force_host_platform_device_count" not in xf:
        _flags.set_env(
            "XLA_FLAGS",
            (xf + f" --xla_force_host_platform_device_count={n_devices}"
             ).strip())
    _flags.set_env("JAX_PLATFORMS", "cpu")
    ensure_platform("cpu")


def require_tpu():
    """The process's first device, which must be a TPU: measurement paths
    fail off the chip instead of reporting a host number."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax.devices()[0] is {dev.platform!r} "
            f"(JAX_PLATFORMS={_flags.get('JAX_PLATFORMS')!r}); this program "
            "measures the chip and does not fall back to the host")
    return dev


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when the environment places the cache,
    else one fixed directory inside the checkout. Never a temp name: the
    path is part of the cache key."""
    return (_flags.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_REPO_ROOT, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and the
    children that inherit its environment; call before the first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    path is set here. Settings go through the environment (read by ``import
    jax``, here or in a child) and, when jax is already imported, through
    jax.config as well."""
    placed = _flags.is_set("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache_dir()
    settings = dict(_CACHE_SETTINGS)
    if not placed:
        settings["jax_compilation_cache_dir"] = path
    for name, value in settings.items():
        _flags.set_env(name.upper(), value)
    if "jax" in sys.modules:
        import jax

        for name, value in settings.items():
            jax.config.update(name, value)
    return path
