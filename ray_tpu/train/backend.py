"""Pluggable collective bring-up on the worker group.

Parity: reference train/backend.py (Backend: on_start/on_training_start/
on_shutdown) and torch/config.py:150 _TorchBackend (_setup_torch_process_group
:65 — worker-0 addr handed to every rank). The TPU-native analog
(SURVEY.md §5.8): hand out `jax.distributed.initialize(coordinator, n, id)`
parameters exactly where the reference hands out MASTER_ADDR, then each
worker (one process per TPU host) forms a `jax.sharding.Mesh` over its
devices; cross-host collectives ride ICI/DCN via XLA, not this layer.
"""
from __future__ import annotations

import socket
from dataclasses import dataclass
from typing import Any, Dict, Optional

from .session import _get_session
from .worker_group import WorkerGroup


class Backend:
    """Hooks around the worker group lifecycle."""

    def on_start(self, worker_group: WorkerGroup) -> None:  # noqa: B027
        pass

    def on_training_start(self, worker_group: WorkerGroup) -> None:  # noqa: B027
        pass

    def on_shutdown(self, worker_group: WorkerGroup) -> None:  # noqa: B027
        pass


@dataclass
class HostCollectiveBackend(Backend):
    """Joins every worker into a host collective group (ray_tpu.util.collective)
    — the gloo-analog for CPU smoke tests and control-sized payloads."""

    group_name: str = "train_default"

    def on_start(self, worker_group: WorkerGroup) -> None:
        import ray_tpu as rt

        n = len(worker_group)
        refs = [
            m.actor.join_collective.remote(n, m.world_rank, "host", self.group_name)
            for m in worker_group.workers
        ]
        rt.get(refs)

    def on_training_start(self, worker_group: WorkerGroup) -> None:
        import ray_tpu as rt

        rt.get([
            m.actor.setup_session_extras.remote(None, self.group_name)
            for m in worker_group.workers
        ])

    def on_shutdown(self, worker_group: WorkerGroup) -> None:
        # Driver-side kill of the rendezvous actor: a failed attempt can leave
        # it holding partial rounds that would wedge the next attempt's seq
        # numbers (workers may already be dead, so no worker-side teardown).
        import ray_tpu as rt
        from ray_tpu.util.collective import _GROUP_ACTOR_PREFIX

        try:
            rt.kill(rt.get_actor(_GROUP_ACTOR_PREFIX + self.group_name))
        except Exception:
            pass


@dataclass
class JaxBackend(Backend):
    """Brings up jax across the worker group.

    Multi-host (`distributed=True`): rank 0 picks a coordinator port; every
    worker calls jax.distributed.initialize(coordinator, world_size, rank) —
    the direct analog of _setup_torch_process_group (torch/config.py:65), after
    which jax.devices() spans all hosts and one Mesh covers the slice.
    Single-host: each worker builds a Mesh over its visible devices.
    """

    distributed: bool = False
    mesh_shape: Optional[Dict[str, int]] = None

    def on_start(self, worker_group: WorkerGroup) -> None:
        if not self.distributed:
            return  # JAX reads its platform from the worker's environment

        def pick_addr() -> str:
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.bind(("", 0))
            port = s.getsockname()[1]
            s.close()
            return f"{socket.gethostbyname(socket.gethostname())}:{port}"

        coordinator = worker_group.execute_single(0, pick_addr)
        n = len(worker_group)

        def setup(rank: int, coord: str) -> None:
            import jax

            jax.distributed.initialize(
                coordinator_address=coord, num_processes=n, process_id=rank
            )

        import ray_tpu as rt

        rt.get([
            m.actor.execute.remote(setup, m.world_rank, coordinator)
            for m in worker_group.workers
        ])

    def on_training_start(self, worker_group: WorkerGroup) -> None:
        shape = self.mesh_shape

        def build_mesh() -> None:
            from ray_tpu.util import jaxenv, tracing

            devs = jaxenv.devices()  # runtime.import_jax, .backend_init
            from ray_tpu.parallel import MeshSpec, best_effort_spec, make_mesh

            spec = MeshSpec(**shape) if shape else best_effort_spec(len(devs))
            with tracing.phase("runtime.mesh"):
                mesh = make_mesh(spec, devices=devs)
            _get_session().mesh = mesh

        import ray_tpu as rt

        rt.get([
            m.actor.execute.remote(build_mesh) for m in worker_group.workers
        ])

    def on_shutdown(self, worker_group: WorkerGroup) -> None:
        if not self.distributed:
            return

        def teardown() -> None:
            import jax

            try:
                jax.distributed.shutdown()
            except Exception:
                pass

        try:
            worker_group.execute(teardown)
        except Exception:
            pass


BACKENDS = {
    "host": HostCollectiveBackend,
    "jax": JaxBackend,
}
