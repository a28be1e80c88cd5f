"""Per-process JAX set-up: which platform, and where compiled programs go.

JAX honours ``JAX_PLATFORMS`` by itself; what the framework adds is the
policy around it. One process owns each chip (libtpu gives a chip to a
process for that process's lifetime), so drivers and plain workers stay on
the cpu platform or off JAX entirely, TPU workers are spawned with
``JAX_PLATFORMS=tpu`` (core/worker_env.py) and every process that compiles
shares one persistent compilation cache.
"""
from __future__ import annotations

import collections
import os
import sys
import threading
import time

from ray_tpu import flags as _flags
from ray_tpu.util import tracing

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


# ------------------------------------------------------- the runtime's phases
# What JAX does before a program first runs, and the start of the runtime
# itself, as host phases of util/tracing.py (table, slow ring, SLOW_PHASE
# events): no flag and no second registry, jax.monitoring is the source.

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_SPANS = {_TRACE: "xla.trace", _LOWER: "xla.lower"}
_LONG_KEPT = 1024  # long spans a thread remembers for their parents' sake

_watching = False
_listed = False
_compiling = threading.local()  # .long, .asked, .read: one compiling thread's


def watch_compiles() -> None:
    """Turn every program's way to the device into host phases, from
    jax.monitoring's events, with the program's name (``fun_name``):

    - ``xla.trace`` (Python -> jaxpr) and ``xla.lower`` (jaxpr -> StableHLO),
      each where the work was, on the thread that did it;
    - ``xla.cache_read``: the persistent cache's read and the executable's
      load, on a hit (``cache="hit"``; ``span_ns`` is JAX's whole compile
      stretch around it, the cache key's making included);
    - ``xla.compile``: the backend's compile, only when the program was
      compiled (``cache="miss"``, or ``"off"`` where no cache was asked).

    A second call of a compiled function emits nothing. JAX reports nested
    work inside its parent's span and the child first (a ``jit`` called in
    a ``jit``'s trace, a compile that a trace's concrete operation needs):
    **whole durations double-count** (on the CPU a gradient's trace held
    its inner calls' 1.7 ms of 13.3 a second time), so a span that contains
    spans long enough for the slow ring (``tracing.SLOW_NS``) carries
    ``self_ns``, its time less theirs, and sums over the slow ring are sums
    of ``self_ns``; the table's ``total_ns`` is of whole durations. The
    listeners run inside JAX on the compiling thread: they never raise, take
    no lock but the table's own, and import nothing. Idempotent."""
    global _watching
    if _watching:
        return
    _watching = True
    import jax.monitoring as mon

    monotonic_ns, wall = time.monotonic_ns, time.time

    def emit(name, start_ns, dur_ns, **attrs):
        st = _compiling
        try:
            long_ = st.long
        except AttributeError:
            long_ = st.long = collections.deque(maxlen=_LONG_KEPT)
        inside = 0
        while long_ and long_[-1][0] >= start_ns:
            inside += long_.pop()[1]
        if inside:
            attrs["self_ns"] = max(0, dur_ns - inside)
        if dur_ns >= tracing.SLOW_NS:
            long_.append((start_ns, dur_ns))
        tracing.observe(name, dur_ns, start_ns, **attrs)

    def on_span(event, start, end, **kw):
        try:
            name = _SPANS.get(event)
            if name is None and event != _COMPILE:
                return
            # the span's wall-clock start laid on CLOCK_MONOTONIC, now
            start_ns = monotonic_ns() - int((wall() - start) * 1e9)
            dur_ns = int((end - start) * 1e9)
            fun = str(kw.get("fun_name", ""))
            if name is not None:
                emit(name, start_ns, dur_ns, fun_name=fun)
                return
            st = _compiling
            read = getattr(st, "read", None)
            asked = getattr(st, "asked", False)
            st.read, st.asked = None, False
            if read is not None:  # a hit: the read alone
                emit("xla.cache_read", read[0], read[1], fun_name=fun,
                     cache="hit", span_ns=dur_ns)
            else:
                emit("xla.compile", start_ns, dur_ns, fun_name=fun,
                     cache="miss" if asked else "off")
        except Exception:
            pass  # never into JAX: a listener's failure fails no compile

    def on_duration(event, secs, **_kw):
        try:
            if event == _CACHE_READ:  # no name: its compile span names it
                ns = int(secs * 1e9)
                _compiling.read = (monotonic_ns() - ns, ns)
        except Exception:
            pass

    def on_event(event, **_kw):
        try:
            if event == _CACHE_ASKED:
                _compiling.asked = True
        except Exception:
            pass

    mon.register_event_time_span_listener(on_span)
    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)


def devices(local: bool = False):
    """``jax.devices()`` (``jax.local_devices()``), with the start of the
    runtime told as host phases where it happens: ``runtime.import_jax``
    around the process's first ``import jax``, ``watch_compiles()``, then
    ``runtime.backend_init`` (attributes ``platform``, ``count``) around the
    first listing of the devices through here, which starts the backend (on
    a chip: libtpu). Later calls are the bare query."""
    global _listed
    if "jax" not in sys.modules:
        with tracing.phase("runtime.import_jax"):
            import jax
    import jax

    watch_compiles()
    query = jax.local_devices if local else jax.devices
    if _listed:
        return query()
    _listed = True
    with tracing.phase("runtime.backend_init") as init:
        devs = query()
        init.attrs.update(platform=devs[0].platform, count=len(devs))
    return devs
