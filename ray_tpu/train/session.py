"""Worker-side training session: report/get_checkpoint/get_context.

Parity: reference train/_internal/session.py (_TrainSession :110, report :666,
get_checkpoint :753, get_dataset_shard) and the TrainContext rank accessors.
The session lives in the train-worker process; `report` enqueues a result the
driver drains via actor calls (reference moves these through a queue too).
"""
from __future__ import annotations

import queue
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from ray_tpu.util import tracing

from .checkpoint import Checkpoint


@dataclass
class TrainContext:
    world_size: int = 1
    world_rank: int = 0
    local_rank: int = 0
    local_world_size: int = 1
    node_rank: int = 0
    experiment_name: str = ""
    trial_name: str = ""

    def get_world_size(self) -> int:
        return self.world_size

    def get_world_rank(self) -> int:
        return self.world_rank

    def get_local_rank(self) -> int:
        return self.local_rank

    def get_local_world_size(self) -> int:
        return self.local_world_size

    def get_node_rank(self) -> int:
        return self.node_rank

    def get_experiment_name(self) -> str:
        return self.experiment_name

    def get_trial_name(self) -> str:
        return self.trial_name


@dataclass
class _Session:
    context: TrainContext
    results: "queue.Queue[Dict[str, Any]]" = field(default_factory=queue.Queue)
    checkpoint: Optional[Checkpoint] = None
    dataset_shards: Dict[str, Any] = field(default_factory=dict)
    mesh: Any = None
    collective_group: Optional[str] = None
    iteration: int = 0
    stop_requested: bool = False


_session_lock = threading.Lock()
_session: Optional[_Session] = None


def _init_session(context: TrainContext, checkpoint: Optional[Checkpoint] = None,
                  dataset_shards: Optional[Dict[str, Any]] = None) -> _Session:
    global _session
    with _session_lock:
        _session = _Session(context=context, checkpoint=checkpoint,
                            dataset_shards=dict(dataset_shards or {}))
        return _session


def _shutdown_session() -> None:
    global _session
    with _session_lock:
        _session = None


def _get_session(strict: bool = True) -> Optional[_Session]:
    if _session is None and strict:
        raise RuntimeError(
            "not inside a training session; this API must be called from a "
            "train_loop_per_worker function"
        )
    return _session


# ---------------------------------------------------------------- public API


def report(metrics: Dict[str, Any], *, checkpoint: Optional[Checkpoint] = None) -> None:
    """reference: session.report session.py:666 — stream metrics (and
    optionally a checkpoint) to the driver."""
    s = _get_session()
    s.iteration += 1
    with tracing.phase("train.report"):
        s.results.put({
            "type": "report",
            "metrics": dict(metrics),
            "checkpoint": checkpoint,
            "iteration": s.iteration,
            "rank": s.context.world_rank,
        })
    if s.stop_requested:
        raise StopIteration("training stop requested by the driver")


def get_checkpoint() -> Optional[Checkpoint]:
    """reference: session.get_checkpoint :753 — the checkpoint to resume
    from (set on restart after failure)."""
    return _get_session().checkpoint


def get_context() -> TrainContext:
    return _get_session().context


def get_dataset_shard(dataset_name: str = "train") -> Any:
    """reference: session.get_dataset_shard — this worker's streaming split
    of a Dataset passed to the trainer."""
    s = _get_session()
    shard = s.dataset_shards.get(dataset_name)
    if shard is None:
        raise KeyError(
            f"no dataset shard named {dataset_name!r}; pass datasets={{...}} "
            "to the trainer"
        )
    return shard


def get_mesh() -> Any:
    """TPU-native addition: the jax.sharding.Mesh formed by the backend over
    this worker's devices (None when the backend did not build one)."""
    return _get_session().mesh


def collective_group_name() -> Optional[str]:
    """Name of the host-collective group joined by this worker (backend-set)."""
    return _get_session().collective_group


def start_profile(logdir: str) -> None:
    """Start an xprof/TensorBoard trace capture on this train worker
    (SURVEY.md §5.1: the TPU-native replacement for the reference's py-spy /
    torch-profiler hooks — jax.profiler traces show XLA ops, TPU step time,
    and host/device transfers; view with tensorboard --logdir)."""
    _get_session()  # must be inside a training session
    import jax

    jax.profiler.start_trace(logdir)


def stop_profile() -> None:
    """Stop the trace started by start_profile and flush it to the logdir."""
    import jax

    jax.profiler.stop_trace()


class profile:
    """Context manager: ``with session.profile(logdir): train_steps()``."""

    def __init__(self, logdir: str):
        self.logdir = logdir

    def __enter__(self):
        start_profile(self.logdir)
        return self

    def __exit__(self, *exc):
        try:
            stop_profile()
        except Exception:
            pass
        return False
