"""Worker process entrypoint (reference:
python/ray/_private/workers/default_worker.py). Spawned by the controller with
RTPU_CONTROLLER / RTPU_NODE_ID in the environment."""
from __future__ import annotations

from ray_tpu import flags

import os
import sys
import time


def _process_age_ns() -> int:
    """Nanoseconds since this process was created, from /proc (10 ms
    resolution): the interpreter's start and every import before main()."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    return (time.clock_gettime_ns(time.CLOCK_BOOTTIME)
            - ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK"))


def main() -> int:
    from ray_tpu.util import tracing

    try:  # boot.*: a worker's start as host phases (util/tracing.py)
        tracing.observe("boot.interpreter", _process_age_ns())
    except (OSError, ValueError, IndexError, AttributeError):
        pass  # no /proc or no CLOCK_BOOTTIME here: the phase is left out
    addr = flags.get("RTPU_CONTROLLER")
    node_id = flags.get("RTPU_NODE_ID")
    if not addr or not node_id:
        sys.stderr.write("worker_main: RTPU_CONTROLLER / RTPU_NODE_ID not set\n")
        return 2
    extra_path = flags.get("RTPU_SYS_PATH")
    if extra_path:
        for p in reversed(extra_path.split(os.pathsep)):
            if p and p not in sys.path:
                sys.path.insert(0, p)
    if flags.get("RTPU_TPU_WORKER"):
        # This process owns chips and will compile for them: join the
        # shared persistent compile cache before anything imports jax.
        from ray_tpu.util.jaxenv import enable_compile_cache

        enable_compile_cache()
    with tracing.phase("boot.imports"):
        from .worker import WorkerRuntime

    try:
        with tracing.phase("boot.connect"):
            rt = WorkerRuntime(addr, node_id)
    except (ConnectionError, OSError):
        # Controller already gone (cluster shut down while we were spawning):
        # exit quietly, mirroring raylet workers dying with their raylet.
        return 0
    rt.serve_forever()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
