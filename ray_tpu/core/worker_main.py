"""Worker process entrypoint (reference:
python/ray/_private/workers/default_worker.py). Spawned by the controller with
RTPU_CONTROLLER / RTPU_NODE_ID in the environment."""
from __future__ import annotations

from ray_tpu import flags

import os
import sys


def main() -> int:
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
    from .worker import WorkerRuntime

    try:
        rt = WorkerRuntime(addr, node_id)
    except (ConnectionError, OSError):
        # Controller already gone (cluster shut down while we were spawning):
        # exit quietly, mirroring raylet workers dying with their raylet.
        return 0
    rt.serve_forever()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
