"""Environment of a spawned worker process.

The one place that decides whether a worker may touch the chip, shared by
both spawners (the controller's local spawn and the host agent) so the two
cannot drift: a worker started for a TPU request owns its chips and can only
run JAX on them; a plain worker is pinned to the cpu platform and inherits no
chip grant.

libtpu gives a chip to one process for that process's lifetime and expects a
process to drive the whole host unless told otherwise. A grant of the whole
host therefore changes nothing (the machine's own TPU_* variables describe
it), and a smaller grant names its chips (``TPU_VISIBLE_CHIPS``) and
declares their shape (reference: _private/accelerators/tpu.py
set_current_process_visible_accelerator_ids, same rule, same variables).
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence

from ray_tpu import flags

_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# x,y,z shape of a sub-host grant, by chip count. Both verified on a 2x2 v5e
# host (chip runs, PR 21): four one-chip processes, and two two-chip ones on
# chips 0,1 and 2,3, each seeing exactly its chips. TPU_VISIBLE_CHIPS alone
# is not enough: the first process gets a device, the others die "The TPU is
# already in use by process with pid N".
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1"}


def grant_chips(free: List[int], k: int) -> List[int]:
    """Carve ``k`` chip ids out of the free pool, in place. Returns [] when
    fewer than k are free (idle workers still pin theirs): the worker then
    spawns unrestricted rather than on a partial slice — visibility is
    isolation, the float resource is the hard limit."""
    if len(free) < k:
        return []
    ids = free[:k]
    del free[:k]
    return ids


def worker_env(
    *,
    controller: str,
    node_id: str,
    spawn_token: str,
    tpu_chips: Optional[Sequence[int]],
    node_chips: int,
    sys_path: Optional[str] = None,
    runtime_env: Optional[Dict[str, Any]] = None,
    **plumbing: str,
) -> Dict[str, str]:
    """The environment for ``python -m ray_tpu.core.worker_main``.

    ``tpu_chips`` is None for a plain worker; for a worker started for a TPU
    request it is the granted chip ids ([] = unrestricted). ``node_chips`` is
    the host's chip count. ``plumbing`` carries spawner-specific RTPU_*
    variables (host id, arena name).
    """
    env = flags.child_env(**plumbing)
    env["RTPU_CONTROLLER"] = controller
    env["RTPU_NODE_ID"] = node_id
    env["RTPU_SPAWN_TOKEN"] = spawn_token
    env["PYTHONPATH"] = _PKG_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if sys_path:
        # The driver's import path, so functions defined in driver-local
        # modules resolve on workers (the lightweight analog of the
        # reference's working_dir runtime env).
        env["RTPU_SYS_PATH"] = sys_path
    if runtime_env:
        env["RTPU_RUNTIME_ENV"] = json.dumps(runtime_env)
    # Never inherit a chip grant: an inherited TPU_VISIBLE_CHIPS would be
    # reported at registration and freed into a pool that never held it.
    env.pop("TPU_VISIBLE_CHIPS", None)
    if tpu_chips is None:
        env.pop("RTPU_TPU_WORKER", None)
        env["JAX_PLATFORMS"] = "cpu"
        return env
    env["RTPU_TPU_WORKER"] = "1"
    # Only the tpu platform: a backend that cannot start raises in the
    # worker's first JAX call and fails its task or actor, instead of the
    # work running on the host.
    env["JAX_PLATFORMS"] = "tpu"
    if tpu_chips and len(tpu_chips) < node_chips:
        env["TPU_VISIBLE_CHIPS"] = ",".join(map(str, tpu_chips))
        bounds = _CHIP_BOUNDS.get(len(tpu_chips))
        if bounds:
            env["TPU_CHIPS_PER_HOST_BOUNDS"] = bounds
            env["TPU_HOST_BOUNDS"] = "1,1,1"
    return env
