"""Cluster controller: control plane for the distributed futures core.

Role-equivalent to the reference's GCS server + cluster scheduler
(ray: src/ray/gcs/gcs_server/gcs_server.h:78, gcs_actor_manager.h:281,
gcs_placement_group_manager.h:230, raylet/scheduling/cluster_task_manager.h:70),
collapsed into one asyncio service for the single-host/virtual-multi-node
topology that round 1 targets. Responsibilities:

- membership: virtual nodes + worker processes (the reference's raylet worker
  pool, worker_pool.h:159, becomes a per-node on-demand process pool here),
- the object directory / memory store for inlined objects,
- task scheduling with resource accounting, dependency resolution, and
  scheduling strategies (DEFAULT/SPREAD/node-affinity/placement-group; the
  reference's policy suite is raylet/scheduling/policy/),
- the actor directory with named/detached actors and ordered per-actor
  dispatch (gcs_actor_manager.h semantics),
- placement groups with PACK/SPREAD/STRICT_PACK/STRICT_SPREAD bundle
  reservation (bundle_scheduling_policy.h:82-106),
- an internal KV store (gcs_kv_manager) and a tiny pubsub.

TPU-first note: the controller is deliberately *off* the training hot path.
Mesh formation (ray_tpu.parallel) uses it only to place host processes and
exchange coordinator addresses; every per-step byte moves inside XLA programs.
"""
from __future__ import annotations

from ray_tpu import flags

import asyncio
import collections
import json
import os
import subprocess
import sys
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from ..util import tracing
from . import protocol, worker_env
from .ids import ActorID, NodeID, ObjectID, PlacementGroupID, TaskID, WorkerID
from .object_store import ObjectLocation, free_location

# Worker processes a node may grow to (the reference caps via resources; this
# is a backstop against runaway spawning on the 1-CPU CI host).
MAX_WORKERS_PER_NODE = flags.get("RTPU_MAX_WORKERS_PER_NODE")

# Flight-recorder phase -> derived Prometheus histogram (reference: the
# GcsTaskManager-fed task latency breakdowns behind `ray summary`). Served
# from app_metrics so the exposition/grafana paths pick them up unchanged.
PHASE_METRIC_NAMES = {
    "scheduling_delay_s": "rtpu_task_scheduling_delay_s",
    "queue_wait_s": "rtpu_task_queue_wait_s",
    "arg_fetch_s": "rtpu_task_arg_fetch_s",
    "exec_s": "rtpu_task_exec_s",
    "result_store_s": "rtpu_task_result_store_s",
}
PHASE_METRIC_HELP = {
    "rtpu_task_scheduling_delay_s": "Task submit -> dispatch arrival at a worker",
    "rtpu_task_queue_wait_s": "Worker-local queue wait before execution",
    "rtpu_task_arg_fetch_s": "Argument location lookup + fetch + deserialize",
    "rtpu_task_exec_s": "User-code execution",
    "rtpu_task_result_store_s": "Result serialize + object-store put",
}
PHASE_BOUNDARIES = [0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05,
                    0.1, 0.5, 1.0, 5.0, 10.0, 30.0, 60.0]

# Every core metric family the controller exports: name -> (type, help).
# Single source of truth for the /metrics exposition, the telemetry ring
# (core/telemetry.py samples _metrics_families each step), grafana panel
# derivation, and the metrics lint (tests/test_metrics_lint.py) that
# refuses rtpu_* names without help text.
CORE_METRIC_META: Dict[str, Tuple[str, str]] = {
    "rtpu_tasks": ("gauge", "Tasks currently in each lifecycle state "
                            "(bounded event window)"),
    "rtpu_pending_tasks": ("gauge", "Tasks waiting in the scheduler queue"),
    "rtpu_workers": ("gauge", "Registered worker processes"),
    "rtpu_actors": ("gauge", "Registered actors"),
    "rtpu_nodes_alive": ("gauge", "Nodes currently alive"),
    "rtpu_objects": ("gauge", "Objects tracked by the object directory"),
    "rtpu_nodes": ("gauge", "Nodes by drain-lifecycle state "
                            "(alive/draining/drained/dead)"),
    "rtpu_node_drains_total": ("counter", "Node drains initiated, "
                                          "by reason"),
    "rtpu_uptime_seconds": ("counter", "Controller uptime"),
    "rtpu_objects_spilled_total": ("counter", "Objects spilled to disk"),
    "rtpu_broadcast_bytes_total": (
        "counter", "Object bytes moved by broadcast chains, by role "
                   "(source/hop)"),
    "rtpu_object_replicas": ("gauge", "Extra object replicas held by "
                                      "broadcast chain hops"),
    "rtpu_actor_checkpoints_total": (
        "counter", "Durable actor checkpoints stored by the controller"),
    "rtpu_actor_checkpoint_bytes": (
        "counter", "Cumulative bytes of stored actor checkpoint records"),
    "rtpu_leases_active": ("gauge", "Active direct-dispatch worker "
                                    "leases"),
    "rtpu_lease_events_total": (
        "counter", "Direct-dispatch lease lifecycle: blocks/leases "
                   "granted, reclaim nudges sent, grants refused under "
                   "memory pressure"),
    "rtpu_arena_used_bytes": ("gauge", "Controller-host object arena "
                                       "bytes in use"),
    "rtpu_arena_capacity_bytes": ("gauge", "Controller-host object arena "
                                           "capacity"),
    "rtpu_node_arena_used_bytes": ("gauge", "Per-node object arena bytes "
                                            "in use (agent heartbeats)"),
    "rtpu_node_mem_fraction": (
        "gauge", "Per-node host memory utilization 0-1 (agent "
                 "heartbeats; controller-host sample for local nodes)"),
    "rtpu_node_cpu_percent": (
        "gauge", "Per-node host CPU percent (agent heartbeats; "
                 "controller-host sample for local nodes)"),
    "rtpu_worker_log_bytes": ("gauge", "Bytes of worker log files per "
                                       "node"),
    "rtpu_events_total": ("counter", "Cluster events recorded, by source "
                                     "and severity"),
    "rtpu_worker_cpu_percent": ("gauge", "Worker process CPU percent "
                                         "(host-agent heartbeats)"),
    "rtpu_worker_rss_bytes": ("gauge", "Worker process resident set size "
                                       "(host-agent heartbeats)"),
    "rtpu_rpc_handled_total": ("counter", "Control-plane RPCs handled, "
                                          "by message kind"),
    "rtpu_rpc_handler_seconds_total": (
        "counter", "Cumulative RPC handler seconds, by message kind"),
    "rtpu_object_store_bytes": (
        "gauge", "Object-store bytes tracked by the directory, by node "
                 "and storage tier (inline/shm/arena/spill/replica) — "
                 "the census gauge behind `rtpu memory`"),
    "rtpu_object_store_fill_fraction": (
        "gauge", "Per-node object arena fill fraction 0-1 (used/capacity "
                 "from agent heartbeats) — drives the "
                 "object_store_mem_high alert rule"),
    "rtpu_node_spill_bytes": (
        "gauge", "Per-node bytes of spilled objects on disk (host-wide "
                 "spill-dir scan riding agent heartbeats)"),
    "rtpu_object_leaks_total": (
        "counter", "Objects flagged OBJECT_LEAK_SUSPECT by the leak "
                   "watchdog (old refs whose owner is dead/unreachable)"),
    "rtpu_jobs": ("gauge", "Jobs in the controller job table, by status "
                           "(PENDING/RUNNING/RETRYING/SUCCEEDED/FAILED/"
                           "STOPPED)"),
    "rtpu_job_attempts_total": (
        "counter", "Entrypoint launches across all jobs, by cause "
                   "(initial/exit/worker_died/preempted/"
                   "supervisor_restart) — the rate behind the "
                   "job_flapping alert"),
    "rtpu_job_runtime_s": (
        "histogram", "End-to-end runtime of terminal jobs, "
                     "submitted-to-finished (seconds)"),
}

# Families whose HELP/TYPE lines are emitted even with no samples yet
# (the exposition always carried these headers; conditional families —
# drains, arena, per-node/per-pid gauges — appear once they have data).
_ALWAYS_EXPORT = frozenset({
    "rtpu_tasks", "rtpu_pending_tasks", "rtpu_workers", "rtpu_actors",
    "rtpu_nodes_alive", "rtpu_objects", "rtpu_nodes",
    "rtpu_uptime_seconds", "rtpu_objects_spilled_total",
    "rtpu_broadcast_bytes_total", "rtpu_object_replicas",
    "rtpu_actor_checkpoints_total", "rtpu_actor_checkpoint_bytes",
    "rtpu_leases_active", "rtpu_lease_events_total",
})


def _hist_quantile(bounds: List[float], h: Dict[str, Any], q: float) -> float:
    """Percentile estimate from cumulative bucket counts (the
    histogram_quantile linear interpolation, server-side)."""
    total = h.get("count", 0)
    if not total:
        return 0.0
    target = q * total
    cum = 0.0
    lo = 0.0
    for i, b in enumerate(bounds):
        c = h["buckets"][i]
        if c and cum + c >= target:
            return lo + (b - lo) * ((target - cum) / c)
        cum += c
        lo = b
    return bounds[-1] if bounds else 0.0  # +Inf bucket clamps to last edge


def _res_fits(avail: Dict[str, float], need: Dict[str, float]) -> bool:
    return all(avail.get(k, 0.0) + 1e-9 >= v for k, v in need.items())


def _res_sub(avail: Dict[str, float], need: Dict[str, float]) -> None:
    for k, v in need.items():
        avail[k] = avail.get(k, 0.0) - v


def _res_add(avail: Dict[str, float], need: Dict[str, float]) -> None:
    for k, v in need.items():
        avail[k] = avail.get(k, 0.0) + v


@dataclass
class NodeInfo:
    node_id: str
    resources: Dict[str, float]
    available: Dict[str, float]
    index: int
    labels: Dict[str, str] = field(default_factory=dict)
    alive: bool = True
    spawning: int = 0
    spawning_tpu: int = 0
    # env_hash -> in-flight spawn count: one pending env spawn satisfies all
    # queued wakeups for that env (same rationale as spawning_tpu).
    spawning_envs: Dict[str, int] = field(default_factory=dict)
    workers: Set[str] = field(default_factory=set)
    # Host-agent fields (None for in-controller virtual nodes): the agent's
    # control connection, its pull-server address, and its host identity
    # (reference: raylet registration with the GCS, gcs_node_manager.h).
    agent_conn: Optional[protocol.Connection] = None
    agent_addr: Optional[Tuple[str, int]] = None
    host_id: Optional[str] = None
    last_heartbeat: float = 0.0
    arena_stats: Dict[str, int] = field(default_factory=dict)
    # Host memory usage fraction (agent heartbeats / controller psutil for
    # local nodes); drives the memory monitor's kill decisions.
    mem_fraction: float = 0.0
    # Host CPU utilization percent (agent heartbeats; local nodes sample
    # at cluster_state time) — the `rtpu status` CPU% column.
    cpu_percent: float = 0.0
    # Unallocated TPU chip ids on locally-spawned (agent-less) nodes: the
    # unit-instance side of the "TPU" float resource (reference: per-instance
    # GPU accounting, resource_instance_set.h). Agent-managed nodes track
    # this on the agent, which owns the worker processes.
    tpu_free: List[int] = field(default_factory=list)
    # Chips of dead workers whose process has not exited yet: libtpu holds
    # a chip until its process is gone, so they rejoin tpu_free only then.
    tpu_returning: int = 0
    # Per-worker-process cpu%/rss from the agent heartbeat (dashboard
    # reporter parity); pid -> {cpu_percent, rss}.
    proc_stats: Dict[str, Dict[str, float]] = field(default_factory=dict)
    # Total bytes of worker log files on the host (agent heartbeats;
    # exported as the rtpu_worker_log_bytes gauge).
    log_bytes: int = 0
    # Drain state machine (reference: autoscaler.proto:334 DrainNode +
    # node_manager.proto:391 DrainRaylet): alive -> draining -> drained.
    # A draining node takes no new placements; at the deadline its running
    # work re-queues with the preempted flag and the node leaves.
    draining: bool = False
    drained: bool = False
    drain_reason: str = ""
    drain_deadline: float = 0.0  # wall clock (survives a controller bounce)
    # Two-phase failure detector (SWIM-style suspect phase in front of the
    # death declaration): heartbeat silence past RTPU_NODE_TIMEOUT_S marks
    # the node suspect — scheduling pauses, actor calls buffer, nothing is
    # killed — and only silence past RTPU_DEAD_TIMEOUT_S declares death, so
    # a healed partition rejoins without actor churn or double-allocation.
    suspect: bool = False
    suspect_since: float = 0.0  # monotonic
    # Host-wide spill usage {files, bytes} (agent heartbeats; local nodes
    # sample at metrics/census time) — census "spill" tier + `rtpu status`.
    spill_stats: Dict[str, int] = field(default_factory=dict)
    # Channel-fabric footprint {segments, bytes}: live rtpu_ch_* shm rings
    # on the host (agent heartbeats; local nodes scan at cluster_state
    # time) — the node-level view of the compiled-DAG channel plane.
    channel_stats: Dict[str, int] = field(default_factory=dict)


@dataclass
class WorkerInfo:
    worker_id: str
    node_id: str
    conn: protocol.Connection
    state: str = "idle"  # idle | task | actor
    current_task: Optional[str] = None
    actor_ids: Set[str] = field(default_factory=set)
    proc: Optional[subprocess.Popen] = None
    spawn_token: Optional[str] = None  # set for agent-spawned workers
    # Runtime-env identity: a worker only runs tasks with the same env hash
    # (reference: worker_pool.h runtime_env_hash pool keying).
    env_hash: str = ""
    pid: int = 0  # worker OS pid (joins agent heartbeat proc_stats)
    # TPU-capable workers own chips and start libtpu on first JAX use
    # (seconds); plain workers are cpu-pinned and start in ~0.3s.
    tpu_capable: bool = False
    # Chip ids assigned at spawn (TPU_VISIBLE_CHIPS); returned to the
    # node's tpu_free pool when the worker dies. Local-spawn nodes only.
    chip_ids: List[int] = field(default_factory=list)
    # Port of the worker's direct-dispatch server (0 = none); peers push
    # actor tasks there without a controller hop.
    direct_port: int = 0
    # When the current task was dispatched (memory-monitor victim order)
    # and whether the monitor chose this worker (OOM error attribution).
    task_started: float = 0.0
    oom_killed: bool = False


@dataclass
class ActorInfo:
    actor_id: str
    name: Optional[str]
    state: str = "pending"  # pending | alive | restarting | dead
    worker_id: Optional[str] = None
    node_id: Optional[str] = None
    resources: Dict[str, float] = field(default_factory=dict)
    pg: Optional[Tuple[str, int]] = None  # (pg_id, bundle_index)
    creation_error: Optional[Exception] = None
    pending_calls: List[Dict[str, Any]] = field(default_factory=list)
    detached: bool = False
    reserved: bool = False
    creation_task_id: Optional[str] = None
    order_lock: asyncio.Lock = field(default_factory=asyncio.Lock)
    # Fault tolerance (reference: gcs_actor_manager.h:88 restart-on-failure):
    # the creation spec is kept so the actor can be rebuilt elsewhere.
    max_restarts: int = 0
    restart_count: int = 0
    creation_spec: Optional[Dict[str, Any]] = None
    # Newest durable checkpoint shipped by the hosting worker:
    # {epoch, blob, bytes, ts}. A crash restart restores it instead of
    # re-running the constructor (core/checkpoint.py record format).
    checkpoint: Optional[Dict[str, Any]] = None


@dataclass
class GeneratorState:
    """Server-side state of one streaming task (reference: streaming
    generator returns, core_worker.proto ReportGeneratorItemReturns +
    _raylet.pyx:273). Items are ordinary objects; this tracks their order,
    completion, and the consumer-driven backpressure window."""

    task_id: str
    window: int = 16
    items: List[str] = field(default_factory=list)
    consumed: int = 0
    done: bool = False
    closed: bool = False  # consumer dropped the generator
    error: Optional[Exception] = None
    wake: asyncio.Event = field(default_factory=asyncio.Event)  # consumers
    drain: asyncio.Event = field(default_factory=asyncio.Event)  # producer


@dataclass
class Bundle:
    resources: Dict[str, float]
    node_id: Optional[str] = None
    available: Dict[str, float] = field(default_factory=dict)


@dataclass
class PGInfo:
    pg_id: str
    bundles: List[Bundle]
    strategy: str
    name: Optional[str]
    state: str = "pending"  # pending | ready | removed
    ready_event: asyncio.Event = field(default_factory=asyncio.Event)


class _PendingQueue:
    """Scheduling queue grouped by placement signature.

    All tasks with the same (resources, strategy, pg, env) signature are
    interchangeable to the scheduler; one failed placement attempt rules
    out the whole group for that pass. Grouping makes a pass
    O(#groups + #placements) instead of O(#pending) — a 10k-task
    homogeneous wave costs one signature lookup per pass, not 10k
    re-examinations (reference: lease-by-shape batching in
    cluster_task_manager/direct_task_transport: one lease request per
    TaskSpec shape, not per task).
    """

    def __init__(self) -> None:
        self.groups: "collections.OrderedDict[tuple, collections.deque]" = (
            collections.OrderedDict())
        self._count = 0

    @staticmethod
    def sig_of(spec: Dict[str, Any]) -> tuple:
        return (
            tuple(sorted(spec.get("resources", {}).items())),
            repr(spec.get("scheduling")),
            spec.get("pg"),
            spec.get("env_hash") or "",
        )

    def append(self, spec: Dict[str, Any]) -> None:
        self.groups.setdefault(self.sig_of(spec),
                               collections.deque()).append(
            spec["task_id"])
        self._count += 1

    def remove(self, task_id: str) -> None:
        for sig, q in list(self.groups.items()):
            if task_id in q:
                q.remove(task_id)
                self._count -= 1
                if not q:
                    del self.groups[sig]
                return

    def discard_missing(self, task_id: str, sig: tuple) -> None:
        """Drop a task popped during scheduling whose spec is gone."""
        self._count -= 1

    def ids(self) -> List[str]:
        return [tid for q in self.groups.values() for tid in q]

    def __len__(self) -> int:
        return self._count



class Controller:
    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        self.host = host
        self.port = port
        self.server: Optional[asyncio.base_events.Server] = None
        self.nodes: Dict[str, NodeInfo] = {}
        self.workers: Dict[str, WorkerInfo] = {}
        self.actors: Dict[str, ActorInfo] = {}
        self.named_actors: Dict[Tuple[str, str], str] = {}  # (namespace, name) -> actor_id
        # Compiled DAGs with live channel plans (dag_id -> registration):
        # bookkeeping only — the channel data plane never touches the
        # controller between compile and teardown.
        self.compiled_dags: Dict[str, Dict[str, Any]] = {}
        self.objects: Dict[str, ObjectLocation] = {}
        # Broadcast replicas: oid -> {node_id: ObjectLocation} — full extra
        # copies of an object's bytes on other hosts (reference: the object
        # directory tracking multiple locations per object,
        # object_directory.h). get_locations prefers the consumer-local
        # copy; remote consumers get the list for parallel pulls.
        self.object_replicas: Dict[str, Dict[str, ObjectLocation]] = {}
        # In-flight broadcast rounds: bid -> shared completion state.
        self._broadcasts: Dict[str, Dict[str, Any]] = {}
        # Cumulative broadcast byte accounting for /metrics
        # (rtpu_broadcast_bytes_total{role}).
        self.broadcast_bytes: Dict[str, int] = {"source": 0, "hop": 0}
        self.object_waiters: Dict[str, List[asyncio.Event]] = {}
        # oid -> callbacks fired (once) when the object's location lands;
        # the incremental path used by wait (vs the Event-based get path).
        self.object_callbacks: Dict[str, List[Any]] = {}
        # Last-touched times drive cold-object selection for arena spilling.
        self.object_touch: Dict[str, float] = {}
        # Census + leak-watchdog bookkeeping: first-registration wall time
        # per directory object, the registering connection for driver/worker
        # put paths (a closed conn whose old objects linger = leak suspect),
        # the once-per-object dedup set, and the cumulative
        # rtpu_object_leaks_total counter.
        self.object_created: Dict[str, float] = {}
        self.object_src: Dict[str, Any] = {}
        self._leak_reported: Set[str] = set()
        self.leak_count = 0
        self._leak_task: Optional[asyncio.Task] = None
        self.spilled_count = 0
        self.rpc_counts: Dict[str, int] = {}  # message kind -> count
        # (due_time, arena_oid) for spilled arena copies awaiting deletion.
        self._deferred_arena_deletes: List[Tuple[float, int]] = []
        self.tasks: Dict[str, Dict[str, Any]] = {}  # pending/running task specs
        self.pending_queue = _PendingQueue()  # tasks awaiting scheduling
        self.generators: Dict[str, GeneratorState] = {}  # streaming tasks
        # Bounded lineage: completed task specs keyed by their return object
        # ids, so a lost object's producing task can re-execute (reference:
        # object_recovery_manager.h + lineage in reference_count.h).
        import collections as _collections

        self.lineage: "_collections.OrderedDict[str, Dict[str, Any]]" = (
            _collections.OrderedDict())
        self.lineage_max = flags.get("RTPU_LINEAGE_MAX")
        # Ownership tree for recursive cancel: parent task id -> live child
        # task ids, plus child -> parent back-pointers for pruning. Edges
        # come from spec["parent_task_id"] (controller-path submissions) or
        # fire-and-forget task_lineage notes (direct pushes). A finished
        # task drops its own parent edge but keeps its children set so a
        # recursive cancel can still traverse THROUGH a finished middle
        # task to running grandchildren; the set self-cleans as they finish.
        self.task_children: Dict[str, Set[str]] = {}
        self.task_parent: Dict[str, str] = {}
        # Finished-task return-oid -> task id (bounded FIFO): a recursive
        # cancel of an ALREADY-FINISHED parent must still locate the
        # subtree root to kill its running descendants.
        self.done_oid2task: "_collections.OrderedDict[str, str]" = (
            _collections.OrderedDict())
        self.functions: Dict[str, bytes] = {}  # function/class table (gcs_function_manager)
        self.kv: Dict[Tuple[str, str], bytes] = {}
        self.pgs: Dict[str, PGInfo] = {}
        self.named_pgs: Dict[str, str] = {}
        self.subs: Dict[str, List[protocol.Connection]] = {}  # pubsub channel -> conns
        # Per-connection publish coalescing buffers: id(conn) -> [conn, items]
        self._pubsub_pending: Dict[int, list] = {}
        self.driver_conns: Set[protocol.Connection] = set()
        # Direct-dispatch worker leases (lease_id -> {worker_id, node_id,
        # resources, owner conn}) and on-demand profiling collection state.
        self._leases: Dict[str, Dict[str, Any]] = {}
        # Lease-block accounting (/metrics rtpu_lease_* counters): blocks
        # granted, individual leases granted, reclaim nudges, and grants
        # refused at admission (the direct path's spillback analog).
        self.lease_stats: Dict[str, int] = {
            "blocks": 0, "granted": 0, "reclaims": 0, "mem_refused": 0}
        # Actor-checkpoint accounting (rtpu_actor_checkpoints_total /
        # rtpu_actor_checkpoint_bytes on /metrics).
        self.ckpt_stats: Dict[str, int] = {"count": 0, "bytes": 0}
        self._profiles: Dict[str, Dict[str, Any]] = {}
        self._last_reclaim_nudge = 0.0
        # App-defined metrics (util/metrics.py): name -> {type, help,
        # boundaries, data {tags_tuple: value|histogram-state}}.
        self.app_metrics: Dict[str, dict] = {}
        self._node_counter = 0
        # Drain bookkeeping: per-reason completed-drain counters (the
        # rtpu_node_drains_total{reason} metric) and the in-progress drain
        # table (node_id -> {reason, deadline}) persisted across controller
        # bounces so a drain survives a head restart.
        self.drain_counts: Dict[str, int] = {}
        self.pending_drains: Dict[str, Dict[str, Any]] = {}
        self._drain_tasks: Dict[str, asyncio.Task] = {}
        self._spawned_procs: Dict[str, subprocess.Popen] = {}  # spawn_token -> proc
        self._chip_alloc: Dict[str, List[int]] = {}  # spawn_token -> TPU chip ids
        # Local-spawn TPU worker processes not yet seen to exit: shutdown()
        # does not return while one of them still holds its chips.
        self._chip_procs: Set[subprocess.Popen] = set()
        self._tpu_spawn_tokens: Set[str] = set()  # tokens of TPU-capable spawns
        self._agent_spawns: Dict[str, str] = {}  # outstanding agent spawn token -> node_id
        self._spawn_env_hash: Dict[str, str] = {}  # spawn token -> env hash
        self._sched_wakeup = asyncio.Event()
        self._sched_stuck = False  # last pass left unplaceable queued work
        self._sched_task: Optional[asyncio.Task] = None
        self._health_task: Optional[asyncio.Task] = None
        self._closing = False
        self.start_time = time.time()
        # Bounded task-event history: feeds the state API (`ray list tasks`,
        # summarize) and chrome-trace timeline export (reference:
        # TaskEventBuffer -> GcsTaskManager, task_event_buffer.h:206).
        import collections

        self.task_events: "collections.deque" = collections.deque(
            maxlen=flags.get("RTPU_TASK_EVENTS_MAX"))
        # Cluster-wide finished tracing spans shipped by worker flight
        # recorders (util/tracing.py get_cluster_spans backend).
        self.cluster_spans: "collections.deque" = collections.deque(
            maxlen=flags.get("RTPU_SPANS_MAX"))
        # Serve request ledger (serve/trace.py): request_id -> folded row
        # of hop spans + the terminal record. Bounded by
        # RTPU_SERVE_LEDGER_MAX with slow/shed/deadline rows retained
        # ahead of LRU eviction (slow-request auto-capture).
        self.serve_ledger: "collections.OrderedDict[str, Dict[str, Any]]" = (
            collections.OrderedDict())
        # Cluster log index: worker_id -> {node_id, name} of its log file,
        # kept after the worker dies so `rtpu logs --task-id/--worker-id`
        # can route post-mortem fetches to the owning host (bounded).
        self.worker_log_names: "collections.OrderedDict[str, Dict[str, str]]" = (
            collections.OrderedDict())
        # Node-wide native object arena (plasma-equivalent, src/store).
        # Created here so worker spawns inherit RTPU_ARENA via env; falls
        # back to per-object segments when the native lib is unavailable.
        from . import native_store
        from .object_store import current_host_id

        self._arena = native_store.create_node_arena(uuid.uuid4().hex)
        self.host_id = current_host_id()
        # Durable control-plane state (reference: gcs_storage Redis
        # persistence, ray_config_def.h:402): KV, function table, and
        # detached actors survive controller restarts when a state path is
        # configured (RTPU_STATE_PATH or the CLI's --state-path).
        self.persist_path = flags.get("RTPU_STATE_PATH")
        self._state_dirty = False
        # Durable job table (core/job_manager.py): job records, attempt
        # accounting, and wait_job cursors live here and ride the state
        # snapshot — constructed before _restore_state so a bounce
        # restores the table alongside KV/actors.
        from .job_manager import JobManager

        self.jobs = JobManager(self)
        self._restore_state()
        # Cluster event log (reference: `ray list cluster-events` + the
        # dashboard event feed): bounded ring + JSONL persistence next to
        # the state snapshot, so the feed survives a controller bounce.
        from .events import EventLog

        self.events = EventLog(
            maxlen=flags.get("RTPU_EVENTS_MAX"),
            persist_path=(self.persist_path + ".events.jsonl")
            if self.persist_path else None)
        # Hang-watchdog de-dup: task ids already reported this incarnation
        # (a hung task yields ONE event, not one per sweep).
        self._hang_reported: Set[str] = set()
        self._watchdog_task: Optional[asyncio.Task] = None
        # Telemetry plane (core/telemetry.py): metrics-history ring +
        # alert rules, persisted beside --state-path so `rtpu top`
        # history and firing alerts survive a controller bounce.
        self.tsdb = None
        self.alerts = None
        self._telemetry_task: Optional[asyncio.Task] = None
        if flags.get("RTPU_TSDB"):
            from . import telemetry

            self.tsdb = telemetry.MetricsTSDB(
                step_s=flags.get("RTPU_TSDB_STEP_S"),
                retain=flags.get("RTPU_TSDB_RETAIN"),
                persist_path=(self.persist_path + ".tsdb")
                if self.persist_path else None,
                persist_every_s=flags.get("RTPU_TSDB_PERSIST_S"))
            self.alerts = telemetry.AlertEngine(
                telemetry.load_alert_rules(flags.get("RTPU_ALERT_RULES")),
                self._emit_event)
            self.alerts.restore(self.tsdb.restored_alert_state)

    # ------------------------------------------------------------------ setup

    @staticmethod
    def _periodic(name: str, coro) -> "asyncio.Task":
        """A periodic loop as a task whose every stretch on the event loop
        is observed as ctrl.periodic.<name> (its sleeps are not counted)."""
        async def run():
            return await tracing.steps("ctrl.periodic." + name, coro)

        return asyncio.get_running_loop().create_task(run())

    def _lag_probe(self) -> None:
        """ctrl.loop_lag: how late a 50 ms timer fires on this loop. A probe
        that fires late covers exactly the time the loop was held."""
        now = time.monotonic_ns()
        tracing.observe("ctrl.loop_lag", now - self._lag_due, self._lag_due)
        self._lag_due = now + 50_000_000
        self._lag_handle = asyncio.get_running_loop().call_later(
            0.05, self._lag_probe)

    async def start(self) -> Tuple[str, int]:
        self.server = await asyncio.start_server(self._on_connection, self.host, self.port)
        self.port = self.server.sockets[0].getsockname()[1]
        loop = asyncio.get_running_loop()
        self._lag_due = time.monotonic_ns() + 50_000_000
        self._lag_handle = loop.call_later(0.05, self._lag_probe)
        self._sched_task = self._periodic("scheduler", self._scheduler_loop())
        self._health_task = self._periodic("health", self._health_check_loop())
        if getattr(self, "_restored_detached", None):
            # Restored detached actors re-create right after the adoption
            # grace window, independent of the health loop's cadence.
            async def _resume_after_grace():
                await asyncio.sleep(
                    max(0.0, self._adopt_grace_until - time.monotonic())
                    + 0.05)
                self._resume_detached_actors()

            loop.create_task(_resume_after_grace())
        if flags.get("RTPU_MEMORY_MONITOR"):
            self._memory_task = self._periodic("memory_monitor", self._memory_monitor_loop())
        if flags.get("RTPU_HANG_WATCHDOG") and flags.get("RTPU_EVENTS"):
            # Off => no task, no per-sweep work: the disabled-path perf
            # floor is literally zero controller cycles.
            self._watchdog_task = self._periodic("hang_poll", self._hang_watchdog_loop())
        if flags.get("RTPU_LEAK_WATCHDOG") and flags.get("RTPU_EVENTS"):
            # Same off-switch contract as the hang watchdog: disabled means
            # no task and zero per-sweep work.
            self._leak_task = self._periodic("leak_poll", self._leak_watchdog_loop())
        if self.tsdb is not None:
            # RTPU_TSDB=0 => no task, no per-step sampling work: the
            # disabled path is zero controller cycles (perf-floor test).
            self._telemetry_task = self._periodic("telemetry", self._telemetry_loop())
        # Resume drains interrupted by a controller bounce: restored
        # (non-agent) nodes become unschedulable immediately, but the
        # drain task itself waits out the reconnect grace — the node's
        # surviving workers haven't re-registered yet, and an instant
        # quiesce check would see an empty node and cut the grace window
        # short mid-task. Agent nodes re-arm on re-register.
        resume: List[str] = []
        for nid in list(self.pending_drains):
            node = self.nodes.get(nid)
            if node is not None:
                st = self.pending_drains[nid]
                node.draining = True
                node.drain_reason = st.get("reason", "manual")
                node.drain_deadline = float(st.get("deadline", 0.0))
                resume.append(nid)
        if resume:
            async def _resume_drains():
                await asyncio.sleep(flags.get("RTPU_RECONNECT_GRACE_S"))
                for nid in resume:
                    if nid in self.pending_drains and nid in self.nodes:
                        self._arm_drain(self.nodes[nid])

            loop.create_task(_resume_drains())
        # Prometheus scrape endpoint (GET /metrics) on an ephemeral port,
        # advertised via cluster_state.metrics_port.
        try:
            self._metrics_server = await asyncio.start_server(
                self._serve_metrics_http, self.host,
                flags.get("RTPU_METRICS_PORT"))
            self.metrics_port = self._metrics_server.sockets[0].getsockname()[1]
        except Exception as e:
            # raw read: flags.get would re-raise on a malformed value, and
            # this handler exists precisely to survive that.
            sys.stderr.write(
                f"[controller] metrics endpoint disabled: {e!r} "
                f"(RTPU_METRICS_PORT={flags.raw('RTPU_METRICS_PORT')})\n")
            self._metrics_server = None
            self.metrics_port = 0
        return self.host, self.port

    def add_node(
        self,
        resources: Dict[str, float],
        labels: Optional[Dict[str, str]] = None,
        node_id: Optional[str] = None,
    ) -> str:
        nid = node_id or NodeID.generate()
        self._node_counter += 1
        self.nodes[nid] = NodeInfo(
            node_id=nid,
            resources=dict(resources),
            available=dict(resources),
            index=self._node_counter,
            labels=labels or {},
            tpu_free=list(range(int(resources.get("TPU", 0)))),
        )
        self._state_dirty = True  # node table persists across restarts
        if getattr(self, "events", None) is not None:
            self._emit_event(
                "INFO", "NODE_ADDED",
                f"node {nid[:8]} joined with {resources}",
                node_id=nid, data={"resources": dict(resources)})
        self._wake_scheduler()
        return nid

    def ensure_head_node(
        self,
        resources: Dict[str, float],
        labels: Optional[Dict[str, str]] = None,
    ) -> str:
        """add_node, unless the state snapshot restored a head node — then
        reuse its identity so workers of the pre-restart controller can
        reconnect under the node id they were spawned with. Capacity is
        refreshed to the caller's view; consumption by adopted workers and
        actors is re-applied as they re-register."""
        for n in self.nodes.values():
            if n.labels.get("head") == "1" and n.agent_conn is None:
                n.resources = dict(resources)
                n.available = dict(resources)
                n.labels.update(labels or {})
                n.alive = True
                # Workers/actors that re-registered before this call keep
                # their grants: re-apply their chip and resource claims to
                # the refreshed capacity instead of clobbering them.
                held = {
                    c for wid in n.workers
                    for c in (self.workers[wid].chip_ids
                              if wid in self.workers else ())
                }
                n.tpu_free = [c for c in
                              range(int(resources.get("TPU", 0)))
                              if c not in held]
                for a in self.actors.values():
                    if a.reserved and a.node_id == n.node_id:
                        _res_sub(n.available, a.resources)
                self._wake_scheduler()
                return n.node_id
        return self.add_node(resources, labels)

    async def shutdown(self) -> None:
        self._closing = True
        for t in getattr(self, "_bcast_push_tasks", ()):  # in-flight chains
            t.cancel()
        self._snapshot_state()
        for w in list(self.workers.values()):
            try:
                await w.conn.send({"kind": "shutdown"})
            except Exception:
                pass
        for n in self.nodes.values():
            if n.agent_conn is not None:
                try:
                    await n.agent_conn.send({"kind": "shutdown"})
                except Exception:
                    pass
        await asyncio.sleep(0.05)
        for w in list(self.workers.values()):
            if w.proc is not None and w.proc.poll() is None:
                try:
                    w.proc.terminate()
                except Exception:
                    pass
        for loc in self.objects.values():
            if loc.host_id is not None and loc.host_id != self.host_id:
                continue  # remote bytes die with their agent's arena
            free_location(loc)
        self.objects.clear()
        from . import native_store

        native_store.close_arena(destroy=True)
        if getattr(self, "_lag_handle", None) is not None:
            self._lag_handle.cancel()
        if self._sched_task is not None:
            self._sched_task.cancel()
        if self._health_task is not None:
            self._health_task.cancel()
        if getattr(self, "_memory_task", None) is not None:
            self._memory_task.cancel()
        if self._watchdog_task is not None:
            self._watchdog_task.cancel()
        if self._leak_task is not None:
            self._leak_task.cancel()
        if self._telemetry_task is not None:
            self._telemetry_task.cancel()
        if self.tsdb is not None:
            # Clean shutdown persists unconditionally (maybe_persist is
            # period-gated); a bounce resumes history where it stopped.
            self.tsdb.save(self.alerts.snapshot() if self.alerts else None)
        if getattr(self, "_metrics_server", None) is not None:
            self._metrics_server.close()
        if self.server is not None:
            self.server.close()
        # Last, so nothing above waits on it: libtpu frees a chip when its
        # process is gone, which can be seconds after the signal, and the
        # next init() (or the next program) must find the chips free. Wait
        # for the chip owners, and only them (at most a minute).
        await asyncio.gather(*(self._await_exit(p) for p in self._chip_procs))

    async def _shutdown_worker(self, w: WorkerInfo) -> None:
        """Gracefully stop one worker process (already removed from pools)."""
        try:
            await w.conn.send({"kind": "shutdown"})
        except Exception:
            pass
        await asyncio.sleep(0.05)
        if w.proc is not None and w.proc.poll() is None:
            try:
                w.proc.terminate()
            except Exception:
                pass

    # ------------------------------------------------------- connection layer

    async def _on_connection(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter) -> None:
        conn = protocol.Connection(reader, writer, self._handle, name="controller-peer")
        conn.start()
        await conn.closed.wait()
        await self._on_disconnect(conn)

    async def _on_disconnect(self, conn: protocol.Connection) -> None:
        if self._closing:
            return
        self.driver_conns.discard(conn)
        # A departing driver's worker leases: resources return, but the
        # workers are recycled (they may be executing orphaned pushes).
        for lid, lease in list(self._leases.items()):
            if lease["owner"] is conn:
                self._release_lease(lid, to_idle=False)
        for node in self.nodes.values():
            if node.agent_conn is conn:
                await self._on_node_death(node)
                return
        dead = [w for w in self.workers.values() if w.conn is conn]
        for w in dead:
            await self._on_worker_death(w)

    async def _on_node_death(self, node: NodeInfo) -> None:
        """Agent connection lost (or heartbeat timed out): the whole host is
        gone. Reference: GCS node-failure handling, gcs_node_manager.h —
        every worker and actor on the node dies with it."""
        if not node.alive:
            return
        node.alive = False
        node.suspect = False  # terminal: past suspicion
        if node.draining:
            # The node left while (or because) it was draining — a
            # preemption that fired before the grace window closed, or the
            # drain's own shutdown. Either way the departure was planned:
            # record it as drained so worker cleanup below re-queues work
            # through the budget-free preempted paths.
            node.draining = False
            node.drained = True
            self.pending_drains.pop(node.node_id, None)
            task = self._drain_tasks.pop(node.node_id, None)
            if task is not None and not task.done():
                task.cancel()
            self._state_dirty = True
        self._export_event("NODE", {"node_id": node.node_id,
                                    "event": "dead", "ts": time.time()})
        if node.drained:
            self._emit_event(
                "INFO", "NODE_REMOVED",
                f"node {node.node_id[:8]} left after draining "
                f"({node.drain_reason or 'drain'})",
                node_id=node.node_id,
                data={"reason": node.drain_reason})
        else:
            self._emit_event(
                "ERROR", "NODE_DIED",
                f"node {node.node_id[:8]} died "
                f"({len(node.workers)} worker(s) lost)",
                node_id=node.node_id,
                data={"workers": len(node.workers),
                      "host_id": node.host_id})
        node.agent_conn = None
        node.agent_addr = None
        for wid in list(node.workers):
            w = self.workers.get(wid)
            if w is not None:
                await self._on_worker_death(w)
                try:
                    await w.conn.close()
                except Exception:
                    pass
        node.workers.clear()
        node.spawning = 0
        node.spawning_tpu = 0
        for tok, nid in list(self._agent_spawns.items()):
            if nid == node.node_id:
                self._agent_spawns.pop(tok, None)
                self._tpu_spawn_tokens.discard(tok)
        # Replicas hosted on the dead host are gone; prune them first so
        # promotion below never hands out a dead copy.
        for oid, reps in list(self.object_replicas.items()):
            for nid in [k for k, r in reps.items()
                        if r.host_id == node.host_id]:
                reps.pop(nid, None)
            if not reps:
                self.object_replicas.pop(oid, None)
        # Objects whose bytes lived only on the dead host are lost. A
        # surviving broadcast replica is promoted to primary (no recompute,
        # no re-pull); else if the producing task's spec is in the lineage
        # table and its deps are still resolvable, re-execute it
        # (reference: object_recovery_manager.h ReconstructObject);
        # otherwise store a clear error so a later get() doesn't dial a
        # dead pull server.
        resubmitted: Set[str] = set()
        for oid, loc in list(self.objects.items()):
            if (
                loc.inline is None
                and loc.host_id is not None
                and loc.host_id == node.host_id
            ):
                if self._promote_replica(oid):
                    continue
                if self._maybe_reconstruct(oid, resubmitted):
                    continue
                lspec = self.lineage.get(oid)
                if lspec is None:
                    reason = "no lineage recorded"
                else:
                    reason = (f"reconstruction cap reached "
                              f"({lspec.get('_reconstructions', 0)}/"
                              f"{flags.get('RTPU_MAX_RECONSTRUCTIONS')})")
                self._emit_event(
                    "ERROR", "OBJECT_LOST",
                    f"object {oid[:8]} lost with node {node.node_id[:8]} "
                    f"({reason})",
                    node_id=node.node_id,
                    task_id=lspec["task_id"] if lspec else None,
                    data={"object_id": oid, "reason": reason,
                          "attempts": int(lspec.get("_reconstructions", 0))
                          if lspec else 0})
                self._store_error(
                    oid,
                    ObjectLostError(
                        f"object {oid[:8]} was lost when node "
                        f"{node.node_id[:8]} died"
                    ),
                )
        self._wake_scheduler()

    def _promote_replica(self, oid: str) -> bool:
        """Primary copy lost: promote a surviving broadcast replica to the
        object table so consumers (and lineage) never notice."""
        reps = self.object_replicas.get(oid)
        if not reps:
            return False
        for nid, rep in list(reps.items()):
            if self._host_alive(rep.host_id):
                reps.pop(nid, None)
                if not reps:
                    self.object_replicas.pop(oid, None)
                self.objects[oid] = rep
                return True
        return False

    def _maybe_reconstruct(self, oid: str, resubmitted: Set[str]) -> bool:
        """Resubmit the producing task of a lost object. Single-level: deps
        must still be present (a missing dep chain errors out rather than
        recursing)."""
        spec = self.lineage.get(oid)
        if spec is None:
            return False
        if spec["task_id"] in resubmitted:
            self.objects.pop(oid, None)  # resubmit already queued covers it
            return True
        if spec["task_id"] in self.tasks:
            self.objects.pop(oid, None)
            return True
        recon = int(spec.get("_reconstructions", 0))
        if recon >= flags.get("RTPU_MAX_RECONSTRUCTIONS"):
            return False
        for dep in spec.get("deps", []):
            loc = self.objects.get(dep)
            if loc is None:
                # Gone entirely: ok only if its producer is already being
                # re-run (the dep waiter picks up the new location); a freed
                # dep would stall the resubmit forever.
                dspec = self.lineage.get(dep)
                if dspec is None or (
                    dspec["task_id"] not in resubmitted
                    and dspec["task_id"] not in self.tasks
                ):
                    return False
            elif loc.is_error:
                return False
        spec["_reconstructions"] = recon + 1
        spec["state"] = "pending"
        spec.pop("sched_node", None)
        spec.pop("blocked", None)
        # Drop the stale locations so consumers re-wait on the new result.
        for rid in spec["return_ids"]:
            self.objects.pop(rid, None)
        resubmitted.add(spec["task_id"])
        self.tasks[spec["task_id"]] = spec
        self.pending_queue.append(spec)
        self._record_task_event(spec, "reconstruct")
        self._emit_event(
            "WARNING", "OBJECT_RECONSTRUCTING",
            f"object {oid[:8]} lost; re-executing producing task "
            f"{spec.get('label') or spec['task_id'][:8]} "
            f"(attempt {spec['_reconstructions']}/"
            f"{flags.get('RTPU_MAX_RECONSTRUCTIONS')})",
            task_id=spec["task_id"],
            data={"object_id": oid,
                  "attempt": spec["_reconstructions"],
                  "label": spec.get("label")})
        return True

    async def _on_worker_death(self, w: WorkerInfo) -> None:
        self.workers.pop(w.worker_id, None)
        # Flip hosted actors to restarting BEFORE the awaited post-mortem
        # fetch below: a call resubmitted in that window (the client's
        # recovery thread races the death handler) must buffer in
        # pending_calls, not observe an alive actor with no worker.
        for aid in list(w.actor_ids):
            _a = self.actors.get(aid)
            if _a is not None and _a.state == "alive":
                _a.state = "restarting"
        # Crash post-mortem (reference: worker-death exit_detail quoting
        # the crashed process's stderr in RayTaskError / ActorDiedError):
        # fetched only when the death actually fails user work.
        detail = ""
        _node = self.nodes.get(w.node_id)
        _planned = _node is not None and (_node.draining or _node.drained)
        if (w.current_task and w.current_task in self.tasks) or w.actor_ids:
            detail = await self._worker_exit_detail(w)
            if w.oom_killed:
                # Worker-OOM post-mortem (PR 3's log-tail fetch) as a
                # first-class cluster event: the kill decision, victim,
                # and the crashed process's last log lines in one record.
                self._emit_event(
                    "ERROR", "WORKER_OOM",
                    f"worker {w.worker_id[:8]} on node {w.node_id[:8]} "
                    f"was killed by the memory monitor while running "
                    f"{(self.tasks.get(w.current_task or '') or {}).get('label') or 'actor work'}",
                    worker_id=w.worker_id, node_id=w.node_id,
                    task_id=w.current_task,
                    data={"log_tail": detail.strip()})
            elif not _planned:
                self._emit_event(
                    "ERROR", "WORKER_DIED",
                    f"worker {w.worker_id[:8]} on node {w.node_id[:8]} "
                    f"died with work in flight",
                    worker_id=w.worker_id, node_id=w.node_id,
                    task_id=w.current_task,
                    data={"actors": len(w.actor_ids),
                          "log_tail": detail.strip()})
        node = self.nodes.get(w.node_id)
        if node:
            node.workers.discard(w.worker_id)
            if w.chip_ids and node.agent_conn is None:
                # Local-spawn pool only: agent-spawned workers' chips are
                # owned and recycled by their agent's reap loop.
                self._return_chips(node, w)
        # A leased worker's death frees the lease's reserved resources; the
        # holder notices via its broken direct connection and resubmits
        # through the controller (tasks are retryable, unlike actor calls).
        for lid, lease in list(self._leases.items()):
            if lease["worker_id"] == w.worker_id:
                self._release_lease(lid)
        # Planned departure? A worker dying on a draining/drained node was
        # preempted, not crashed: its work re-queues without consuming
        # retry/restart budgets (reference: DrainNode graceful-departure
        # semantics vs node failure).
        preempted = node is not None and (node.draining or node.drained)
        # Fail — or retry — the running task (reference: task resubmission on
        # worker failure, core_worker/task_manager.h max_retries).
        if w.current_task and w.current_task in self.tasks:
            spec = self.tasks.pop(w.current_task)
            self._release_task_resources(spec)
            if preempted:
                err: Exception = NodePreemptedError(
                    f"worker {w.worker_id[:8]} left with draining node "
                    f"{w.node_id[:8]} "
                    f"({node.drain_reason or 'drain'}) while running task "
                    f"{spec.get('label', '')}")
            elif w.oom_killed:
                err = OutOfMemoryError(
                    f"worker {w.worker_id[:8]} was killed by the memory "
                    f"monitor while running task {spec.get('label', '')} "
                    f"(host memory pressure){detail}")
            else:
                err = WorkerCrashedError(
                    f"worker {w.worker_id[:8]} died while running task "
                    f"{spec.get('label', '')}{detail}")
            if not self._maybe_retry_task(spec, preempted=preempted):
                self._finalize_generator(spec["task_id"], err)
                for oid in spec["return_ids"]:
                    self._store_error(oid, err)
        # Restart or mark dead hosted actors.
        for aid in list(w.actor_ids):
            actor = self.actors.get(aid)
            if actor and actor.state != "dead":
                if preempted:
                    err = NodePreemptedError(
                        f"actor {aid[:8]} left with draining node "
                        f"{w.node_id[:8]} ({node.drain_reason or 'drain'})")
                else:
                    err = WorkerCrashedError(
                        f"actor {aid[:8]} process died{detail}")
                if not self._maybe_restart_actor(actor, err,
                                                 preempted=preempted):
                    self._mark_actor_dead(actor, err)
        self._wake_scheduler()

    def _return_chips(self, node: NodeInfo, w: WorkerInfo) -> None:
        """Give a dead local-spawn worker's chips back to the node pool —
        once its process is really gone. A worker is declared dead when it
        is killed or its connection drops, which can be before the process
        has exited; a successor spawned onto its chips in that window dies
        in libtpu with the device still in use."""
        chips, w.chip_ids = w.chip_ids, []
        proc = w.proc
        if proc is None or proc.poll() is not None:
            node.tpu_free.extend(chips)
            return
        node.tpu_returning += len(chips)

        async def wait_exit() -> None:
            gone = await self._await_exit(proc)
            node.tpu_returning -= len(chips)
            if gone:
                node.tpu_free.extend(chips)
            else:
                sys.stderr.write(
                    f"[controller] worker pid {proc.pid} has not exited a "
                    f"minute after it was killed; leaking chips {chips} "
                    f"rather than double-allocating\n")
            self._wake_scheduler()

        asyncio.get_running_loop().create_task(wait_exit())

    async def _await_exit(self, proc: subprocess.Popen) -> bool:
        """Wait up to a minute for a chip-owning process to exit (SIGKILL
        after 10s in case it ignores SIGTERM); True once it is gone. The
        exit itself is what takes the time: the kernel unmaps the runtime's
        DMA buffers while the process is already unkillable (6s for a
        one-chip worker, over 15s for a four-chip one on the v5e host
        without transparent hugepages — chip runs, PR 21)."""
        start = time.monotonic()
        killed = False
        while proc.poll() is None:
            waited = time.monotonic() - start
            if waited > 60.0:
                return False
            if waited > 10.0 and not killed:
                killed = True
                try:
                    proc.kill()
                except OSError:
                    pass
            await asyncio.sleep(0.02)
        self._chip_procs.discard(proc)
        return True

    async def _worker_exit_detail(self, w: WorkerInfo) -> str:
        """Bounded tail of a dead worker's log file, fetched from its host
        (the controller reads head-host files itself, agent hosts answer
        over their control connection) — so OOM-killed and segfaulted
        workers are attributable from the driver without SSH. Never fatal,
        never unbounded."""
        limit = int(flags.get("RTPU_EXIT_DETAIL_BYTES"))
        if not limit or not w.spawn_token:
            return ""
        from . import worker_logs as wl

        name = wl.log_file_name(w.spawn_token)
        node = self.nodes.get(w.node_id)
        try:
            if node is not None and node.agent_conn is not None:
                text = await node.agent_conn.request(
                    {"kind": "tail_log", "name": name, "bytes": limit},
                    timeout=3)
            else:
                text = await asyncio.to_thread(
                    wl.read_tail, os.path.join(wl.log_dir(), name), limit)
        except Exception:
            return ""
        text = (text or "").strip()
        if not text or text.startswith("<log unavailable"):
            return ""
        return (f"\n--- last log lines of the dead worker ({name}) ---\n"
                f"{text}")

    def _fail_env_tasks(self, env_hash: str, err: Exception) -> None:
        """A runtime env cannot materialize: every task queued for it would
        otherwise retry the broken install forever."""
        for tid in self.pending_queue.ids():
            spec = self.tasks.get(tid)
            if spec is not None and (spec.get("env_hash") or "") == env_hash:
                self.pending_queue.remove(tid)
                self._fail_task(
                    spec,
                    RuntimeEnvSetupError(f"runtime env setup failed: {err}"),
                )

    def _maybe_retry_task(self, spec: Dict[str, Any],
                          preempted: bool = False) -> bool:
        """Resubmit a task killed by a system failure (worker/node death),
        up to max_retries times. Application errors never retry here — they
        reach _h_task_done as error locations, not a dead connection.
        ``preempted`` (planned node departure): the task ALWAYS re-queues
        and the retry budget is untouched — the result was never observed,
        so replaying it is safe and free."""
        if spec.get("is_actor_creation") or spec.get("actor_id"):
            return False
        retries = int(spec.get("max_retries", 0))
        used = int(spec.get("_retry_count", 0))
        if not preempted and used >= retries:
            return False
        if spec.get("streaming") and spec["task_id"] in self.generators:
            gen = self.generators[spec["task_id"]]
            if gen.items:
                # Items already observed by the consumer can't be replayed
                # consistently; only an unstarted stream retries.
                return False
        if not preempted:
            spec["_retry_count"] = used + 1
        spec["state"] = "pending"
        spec.pop("sched_node", None)
        spec.pop("blocked", None)
        spec.pop("__dispatch_ts", None)
        self.tasks[spec["task_id"]] = spec
        self.pending_queue.append(spec)
        self._record_task_event(spec, "retry")
        if preempted:
            self._emit_event(
                "WARNING", "TASK_PREEMPTED",
                f"task {spec.get('label') or spec['task_id'][:8]} "
                f"re-queued after planned node departure "
                f"(no retry budget consumed)",
                task_id=spec["task_id"],
                data={"label": spec.get("label")})
        else:
            self._emit_event(
                "WARNING", "TASK_RETRY",
                f"task {spec.get('label') or spec['task_id'][:8]} "
                f"re-queued after worker/node failure "
                f"(retry {spec.get('_retry_count', 0)}/"
                f"{spec.get('max_retries', 0)})",
                task_id=spec["task_id"],
                data={"label": spec.get("label"),
                      "retry": spec.get("_retry_count", 0)})
        self._wake_scheduler()
        return True

    def _maybe_restart_actor(self, actor: ActorInfo, err: Exception,
                             preempted: bool = False) -> bool:
        """Re-instantiate a crashed actor from its creation spec (reference:
        gcs_actor_manager RestartActor, max_restarts semantics). In-flight
        calls fail (at-most-once actor tasks); calls submitted while
        restarting buffer and replay on actor_ready. ``preempted``
        (planned node departure): detached/restartable actors re-create
        without consuming restart budget."""
        spec = actor.creation_spec
        if spec is None:
            return False
        if preempted:
            if not (actor.detached
                    or actor.restart_count < actor.max_restarts):
                return False
        elif actor.restart_count >= actor.max_restarts:
            return False
        # Restore the newest reachable state instead of re-running the
        # constructor. An UNCONSUMED migration/restore blob in the spec
        # wins: it is popped at actor_ready, so its presence proves the
        # restored instance never confirmed — never mutated past the
        # snapshot, and always at least as new as the last checkpoint
        # (previously the crash path dropped it here, silently losing
        # migrated state when the restore target died between dispatch
        # and actor_ready). Otherwise the newest durable checkpoint — its
        # record carries the exactly-once journal, so replayed calls
        # dedup against everything it covers.
        if spec.get("state_blob") is None and actor.checkpoint is not None \
                and actor.checkpoint.get("blob") is not None:
            spec["state_blob"] = actor.checkpoint["blob"]
        if not preempted:
            actor.restart_count += 1
        actor.state = "restarting"
        self._export_event("ACTOR", {"actor_id": actor.actor_id,
                                     "event": "restarting",
                                     "ts": time.time()})
        self._emit_event(
            "WARNING", "ACTOR_RESTARTING",
            f"actor {actor.name or actor.actor_id[:8]} restarting after "
            f"{'preemption' if preempted else 'crash'}: {err} "
            f"(restart {actor.restart_count}/{actor.max_restarts})",
            actor_id=actor.actor_id, node_id=actor.node_id,
            worker_id=actor.worker_id,
            data={"cause": f"{type(err).__name__}: {err}",
                  "preempted": preempted,
                  "restarts": actor.restart_count})
        from .job_manager import SUPERVISOR_PREFIX

        if (actor.name or "").startswith(SUPERVISOR_PREFIX):
            # Job supervisor going around the restart loop: record the
            # pending attempt cause (preempted restarts bill no job
            # budget) and sweep the orphaned entrypoint process group.
            self.jobs.note_supervisor_died(actor, err, preempted,
                                           fatal=False)
        # Fail calls already forwarded to the dead worker — but NOT calls
        # still buffered in pending_calls (never dispatched): those replay
        # after restart, and erroring them here would double-signal.
        # Replay-enabled calls (max_task_retries actors) re-buffer instead
        # of failing: the restored actor's journal short-circuits any that
        # actually executed, so redelivery is exactly-once, not at-least.
        buffered = {p["task_id"] for p in actor.pending_calls}
        for tid, t in list(self.tasks.items()):
            if (
                t.get("actor_id") == actor.actor_id
                and not t.get("is_actor_creation")
                and tid not in buffered
            ):
                if t.get("replay"):
                    t.pop("sched_node", None)
                    t.pop("__dispatch_ts", None)
                    actor.pending_calls.append(t)
                else:
                    self._fail_task(t, err)
        node = self.nodes.get(actor.node_id or "")
        if node and actor.reserved:
            actor.reserved = False
            self._release_reservation(actor.resources, node, actor.pg)
        actor.worker_id = None
        actor.node_id = None
        spec["state"] = "pending"
        spec.pop("sched_node", None)
        self.tasks[spec["task_id"]] = spec
        self.pending_queue.append(spec)
        self._record_task_event(spec, "actor_restart")
        self._wake_scheduler()
        return True

    # ------------------------------------------------------------ msg routing

    async def _handle(self, conn: protocol.Connection, msg: Dict[str, Any]) -> Any:
        kind = msg["kind"]
        fn = getattr(self, f"_h_{kind}", None)
        if fn is None:
            raise ValueError(f"controller: unknown message kind {kind!r}")
        # Per-kind message counter: observability (dashboard /metrics) and
        # the ownership-protocol tests' proof that ref passing between
        # workers makes NO controller round-trips.
        self.rpc_counts[kind] = self.rpc_counts.get(kind, 0) + 1
        # ctrl.rpc.<kind>: every stretch this handler holds the loop (its
        # awaits are not counted), so a blocked loop names its blocker.
        return await tracing.steps("ctrl.rpc." + kind, fn(conn, msg))

    # --------------------------------------------------------------- handlers

    async def _h_register(self, conn, msg):
        role = msg["role"]
        if role == "driver":
            self.driver_conns.add(conn)
            return {"ok": True, "controller_host_id": self.host_id}
        worker_id = msg["worker_id"]
        node_id = msg["node_id"]
        reconnect = bool(msg.get("reconnect"))
        node = self.nodes.get(node_id)
        w = self.workers.get(worker_id)
        if reconnect and w is None and node is None:
            # The worker outlived a controller restart but its node hasn't
            # (re-)registered yet — its host agent may still be dialing.
            # Ask the worker to retry instead of adopting it onto a node
            # the scheduler doesn't know (reconcile, don't trust blindly).
            return {"ok": False, "retry": True}
        adopted = reconnect and w is None
        if w is not None:
            w.conn = conn  # reconnect
            w.direct_port = int(msg.get("direct_port") or 0)
            w.pid = int(msg.get("pid") or 0)
        else:
            w = WorkerInfo(worker_id=worker_id, node_id=node_id, conn=conn,
                           tpu_capable=bool(msg.get("tpu_capable")),
                           env_hash=msg.get("env_hash") or "",
                           pid=int(msg.get("pid") or 0),
                           direct_port=int(msg.get("direct_port") or 0))
            self.workers[worker_id] = w
        # Exact proc adoption via startup token (reference: worker startup
        # tokens, worker_pool.h:251) — heuristic matching can swap proc handles
        # between workers, making kill() terminate the wrong process.
        token = msg.get("spawn_token")
        was_tpu_spawn = False
        if token:
            proc = self._spawned_procs.pop(token, None)
            if proc is not None:
                w.proc = proc
                # The controller's view of one start, beside the worker's
                # boot.* phases on the same clock.
                t0 = proc.spawned_ns  # _launch_worker's stamp
                tracing.observe(
                    "ctrl.worker_spawn", time.monotonic_ns() - t0, t0,
                    pid=proc.pid, tpu=w.tpu_capable)
                if w.tpu_capable:
                    self._chip_procs.add(proc)
            else:
                self._agent_spawns.pop(token, None)  # no longer outstanding
            # Kept for BOTH spawn flavors: names the worker's log file for
            # the cluster log index (kill routing still checks proc first).
            w.spawn_token = token
            from .worker_logs import log_file_name

            self.worker_log_names[worker_id] = {
                "node_id": node_id, "name": log_file_name(token)}
            self.worker_log_names.move_to_end(worker_id)
            while len(self.worker_log_names) > 8192:
                self.worker_log_names.popitem(last=False)
            was_tpu_spawn = token in self._tpu_spawn_tokens
            self._tpu_spawn_tokens.discard(token)
            # Local spawns: adopt the controller-side allocation (also
            # removes it from the never-registered-exit path). Agent
            # spawns: the agent allocated; trust the worker's report.
            # Non-TPU workers never hold chips regardless of env noise.
            w.chip_ids = (self._chip_alloc.pop(token, None)
                          or list(msg.get("chip_ids") or [])) \
                if w.tpu_capable else []
        if node:
            node.workers.add(worker_id)
            if not reconnect:
                node.spawning = max(0, node.spawning - 1)
                if was_tpu_spawn:
                    node.spawning_tpu = max(0, node.spawning_tpu - 1)
                if token:
                    self._release_env_spawn(node, token)
            elif adopted and w.chip_ids and node.agent_conn is None:
                # Chip reconciliation on re-register after a controller
                # restart: the restored node's free pool starts full, and
                # this worker's grant must leave it — free-pool and granted
                # sets stay disjoint (no chip double-allocation).
                taken = set(w.chip_ids)
                node.tpu_free = [c for c in node.tpu_free if c not in taken]
        if reconnect:
            # Re-claim plain tasks still executing on the re-registering
            # worker (reference: the GCS rebuilding lease state from raylet
            # re-reports on failover). The driver resubmits in-flight specs
            # on ITS reconnect — without this claim the controller would
            # both schedule the duplicate AND consider the worker idle
            # (breaking drain's quiesce wait); with it, the running
            # instance finishes and its task_done retires the spec.
            for tid in msg.get("running") or ():
                spec = self.tasks.get(tid)
                if spec is not None and spec.get("actor_id"):
                    continue  # actor calls are claimed via msg["actors"]
                if spec is not None and not spec.get("sched_node"):
                    self.pending_queue.remove(tid)
                    spec["state"] = "running"
                    spec["sched_node"] = None  # resources never reserved
                w.current_task = tid
                if w.state == "idle":
                    w.state = "task"
                break
        drop = await self._adopt_worker_actors(w, node, msg)
        self._wake_scheduler()
        return {"ok": True, "drop_actors": drop}

    async def _adopt_worker_actors(
        self, w: WorkerInfo, node: Optional[NodeInfo], msg: Dict[str, Any]
    ) -> List[str]:
        """Reconcile actors a re-registering worker claims to host
        (reference: gcs_actor_manager rebuilding the actor directory from
        worker re-reports on GCS failover). The live instance wins over a
        queued re-creation; a re-creation already dispatched (or finished)
        elsewhere wins over the stale claimant, which is told to drop it."""
        drop: List[str] = []
        adopted: List[ActorInfo] = []
        for aspec in msg.get("actors") or ():
            aid = aspec["actor_id"]
            actor = self.actors.get(aid)
            if actor is None:
                # Non-detached actor (not persisted): rebuild the directory
                # entry from the worker's report. No creation spec — a later
                # crash of this worker kills the actor for good.
                actor = ActorInfo(
                    actor_id=aid,
                    name=aspec.get("name"),
                    resources=dict(aspec.get("resources") or {}),
                    detached=bool(aspec.get("detached")),
                    max_restarts=int(aspec.get("max_restarts", 0)),
                )
                self.actors[aid] = actor
                if aspec.get("name"):
                    key = (aspec.get("namespace", "default"), aspec["name"])
                    cur = self.named_actors.get(key)
                    if cur is None or self.actors[cur].state == "dead":
                        self.named_actors[key] = aid
            if actor.state == "dead":
                drop.append(aid)
                continue
            if actor.state == "alive" and actor.worker_id not in (
                    None, w.worker_id):
                drop.append(aid)  # already re-created elsewhere
                continue
            ctid = actor.creation_task_id
            cspec = self.tasks.get(ctid) if ctid else None
            if cspec is not None:
                if cspec.get("sched_node"):
                    # Re-creation already dispatched: that instance wins.
                    drop.append(aid)
                    continue
                # Still queued: cancel it — the live instance keeps serving
                # with its state intact (the whole point of adoption).
                self.tasks.pop(ctid, None)
                self.pending_queue.remove(ctid)
            actor.worker_id = w.worker_id
            actor.node_id = w.node_id
            w.actor_ids.add(aid)
            w.state = "actor"
            if node is not None and not actor.reserved and actor.pg is None:
                _res_sub(node.available, actor.resources)
                actor.reserved = True
            adopted.append(actor)
        for actor in adopted:
            # Same drain-before-alive ordering as _h_actor_ready: queued
            # calls dispatch before the direct address is handed out.
            while actor.pending_calls:
                calls, actor.pending_calls = actor.pending_calls, []
                for call in calls:
                    await self._dispatch_actor_call(actor, call)
            actor.state = "alive"
            self._export_event("ACTOR", {"actor_id": actor.actor_id,
                                         "event": "adopted",
                                         "name": actor.name,
                                         "node_id": actor.node_id,
                                         "ts": time.time()})
            self._emit_event(
                "INFO", "ACTOR_ADOPTED",
                f"actor {actor.name or actor.actor_id[:8]} re-claimed by "
                f"its surviving worker after a controller bounce",
                actor_id=actor.actor_id, node_id=actor.node_id,
                worker_id=actor.worker_id, data={"name": actor.name})
        return drop

    def _release_env_spawn(self, node: Optional[NodeInfo], token: str) -> None:
        eh = self._spawn_env_hash.pop(token, None)
        if eh and node is not None and node.spawning_envs.get(eh, 0) > 0:
            node.spawning_envs[eh] -= 1
            if not node.spawning_envs[eh]:
                node.spawning_envs.pop(eh, None)

    async def _h_metric_update(self, conn, msg):
        """App-metric deltas from workers/drivers (util/metrics.py;
        reference python/ray/util/metrics.py -> metrics_agent). Counters
        accumulate, gauges overwrite, histogram observations bucket-count
        against the metric's boundaries."""
        for m in msg.get("metrics", []):
            name = m["name"]
            st = self.app_metrics.setdefault(
                name, {"type": m["type"], "help": m.get("help", ""),
                       "boundaries": m.get("boundaries") or [],
                       "data": {}})
            for tags_list, value in m.get("data", []):
                tags = tuple(tuple(t) for t in tags_list)
                if m["type"] == "gauge":
                    st["data"][tags] = value
                elif m["type"] == "counter":
                    st["data"][tags] = st["data"].get(tags, 0.0) + value
                else:  # histogram: per-tag {bucket_counts, sum, count}
                    h = st["data"].setdefault(
                        tags, {"buckets": [0] * (len(st["boundaries"]) + 1),
                               "sum": 0.0, "count": 0})
                    if isinstance(value, dict):
                        # Pre-aggregated bucket counts (util/metrics.py
                        # aggregates at record time): merge elementwise,
                        # overflow into the +Inf bucket on length mismatch.
                        for i, c in enumerate(value.get("buckets", ())):
                            if c:
                                h["buckets"][min(i, len(h["buckets"]) - 1)] \
                                    += c
                        h["sum"] += value.get("sum", 0.0)
                        h["count"] += value.get("count", 0)
                        continue
                    for obs in value:  # legacy raw observation list
                        i = 0
                        for i, b in enumerate(st["boundaries"]):
                            if obs <= b:
                                break
                        else:
                            i = len(st["boundaries"])
                        h["buckets"][i] += 1
                        h["sum"] += obs
                        h["count"] += 1
        return {"ok": True}

    async def _h_worker_log(self, conn, msg):
        """Forward a worker's stdout/stderr line to every connected driver
        (reference: _private/log_monitor.py tailing worker logs to the
        driver). Fire-and-forget fanout; a dead driver conn is skipped."""
        out = {"kind": "log", "line": msg.get("line", ""),
               "pid": msg.get("pid"), "worker_id": msg.get("worker_id", ""),
               "stream": msg.get("stream", "stdout")}
        for dconn in list(self.driver_conns):
            try:
                # Drop lines to a stalled driver rather than queueing them:
                # logs are lossy-by-contract, controller memory is not.
                if (dconn.writer.transport.get_write_buffer_size()
                        > 1 << 20):
                    continue
                dconn._buffered_write(dconn._frame(out))
            except Exception:
                pass
        return None

    async def _h_put_location(self, conn, msg):
        loc: ObjectLocation = msg["loc"]
        if msg.get("if_absent") and loc.object_id in self.objects:
            # Direct-dispatch failure reports must not clobber a real
            # result the worker managed to deliver before dying.
            return {"ok": True}
        self._store_location(loc)
        # Leak watchdog: remember who registered the object — a put whose
        # connection later closes while the object lingers past
        # RTPU_LEAK_AGE_S is a leak suspect (only the put path records a
        # source; unattributed objects are never flagged — safe direction).
        self.object_src.setdefault(loc.object_id, conn)
        return {"ok": True}

    async def _wait_for_object(self, oid: str, deadline: Optional[float] = None) -> ObjectLocation:
        """Block until `oid` is in the object table; waiter registrations are
        cleaned up on timeout/cancel so polling callers don't leak Events."""
        while oid not in self.objects:
            ev = asyncio.Event()
            lst = self.object_waiters.setdefault(oid, [])
            lst.append(ev)
            try:
                if deadline is None:
                    await ev.wait()
                else:
                    remaining = max(0.0, deadline - time.monotonic())
                    await asyncio.wait_for(ev.wait(), remaining or 1e-6)
            finally:
                if not ev.is_set():
                    try:
                        lst.remove(ev)
                    except ValueError:
                        pass
                    if not lst:
                        self.object_waiters.pop(oid, None)
        return self.objects[oid]

    async def _h_get_locations(self, conn, msg):
        ids: List[str] = msg["object_ids"]
        timeout = msg.get("timeout")
        owners: Dict[str, str] = msg.get("owners") or {}
        # Consumer node (when the requester reports it): replica-aware
        # resolution hands back the copy local to that host, so a
        # broadcast object is read over shm instead of re-pulled.
        req_node = msg.get("node_id")
        deadline = None if timeout is None else time.monotonic() + timeout
        out: Dict[str, ObjectLocation] = {}
        now = time.monotonic()
        for oid in ids:
            if oid not in self.objects and owners.get(oid):
                # Directory miss with a known owner: the owner is the
                # authority for its objects (reference ownership protocol —
                # the GCS directory is a cache, owners are truth). Covers
                # registration races and directory loss across a controller
                # restart.
                await self._owner_locate(oid, owners[oid])
            try:
                loc = await self._wait_for_object(oid, deadline)
                out[oid] = self._replica_view(oid, loc, req_node)
                self.object_touch[oid] = now
            except asyncio.TimeoutError:
                raise GetTimeoutError(f"object {oid[:8]} not ready within {timeout}s") from None
        return out

    async def _owner_locate(self, oid: str, owner_addr: str) -> None:
        hostport = owner_addr.partition("|")[0]
        host, _, port = hostport.rpartition(":")
        try:
            conn = await protocol.connect(host, int(port), name="owner-locate")
            try:
                res = await conn.request({"kind": "ref_locate", "oid": oid},
                                         timeout=2)
            finally:
                await conn.close()
            loc = (res or {}).get("loc")
            if loc is not None and oid not in self.objects:
                self._store_location(loc)
        except Exception:
            pass  # owner gone/unreachable: fall through to the normal wait

    async def _h_rpc_stats(self, conn, msg):
        return dict(self.rpc_counts)

    async def _h_worker_logs(self, conn, msg):
        """Legacy list/tail of worker log files on one host (the original
        dashboard viewer contract: a list of names, or one tail string).
        The cluster-wide surface is list_logs / resolve_log / get_log."""
        import os as _os

        from .worker_logs import log_dir, list_log_files, read_tail

        node_id = msg.get("node_id") or ""
        name = msg.get("name")
        node = self.nodes.get(node_id)
        if node is not None and node.agent_conn is not None:
            try:
                if name:
                    return await node.agent_conn.request(
                        {"kind": "tail_log", "name": name,
                         "bytes": msg.get("bytes", 65536)}, timeout=10)
                res = await node.agent_conn.request(
                    {"kind": "list_logs"}, timeout=10)
                return [f["name"] if isinstance(f, dict) else f
                        for f in res]
            except Exception as e:
                return f"<agent unavailable: {e}>" if name else []
        # Local (controller-spawned workers).
        if not name:
            return [f["name"] for f in list_log_files()]
        safe = _os.path.basename(name)
        nbytes = min(int(msg.get("bytes", 65536)), 1 << 20)
        try:
            return read_tail(_os.path.join(log_dir(), safe), nbytes)
        except OSError as e:
            return f"<log unavailable: {e}>"

    # -------------------------------------------------- cluster log subsystem
    # Reference: the `ray logs` CLI + dashboard log API — any log file on
    # any node is listable and fetchable through the head, with task/actor
    # attribution resolving an id to the owning host's file.

    async def _h_list_logs(self, conn, msg):
        """Cluster log index: node_id -> [{name, size, mtime}] for every
        alive node (agent hosts answer over their control connection; the
        controller lists the head host itself)."""
        out: Dict[str, Any] = {}
        local: Optional[List[Dict[str, Any]]] = None
        for node in list(self.nodes.values()):
            if not node.alive:
                continue
            if node.agent_conn is not None:
                try:
                    out[node.node_id] = await node.agent_conn.request(
                        {"kind": "list_logs"}, timeout=5)
                except Exception:
                    out[node.node_id] = []
            else:
                if local is None:
                    from .worker_logs import list_log_files

                    local = list_log_files()
                out[node.node_id] = local
        return out

    def _resolve_log_target(self, msg) -> Optional[Dict[str, str]]:
        """task/actor/worker id -> {node_id, name} of the log file the
        owning worker writes (the attribution the cluster log index keeps
        beyond worker death)."""
        wid = msg.get("worker_id")
        if not wid and msg.get("actor_id"):
            a = self.actors.get(msg["actor_id"])
            wid = a.worker_id if a is not None else None
        if not wid and msg.get("task_id"):
            tid = msg["task_id"]
            for ev in reversed(self.task_events):
                if ev.get("task_id") == tid and ev.get("worker_id"):
                    wid = ev["worker_id"]
                    break
        if not wid:
            return None
        return self.worker_log_names.get(wid)

    async def _h_resolve_log(self, conn, msg):
        t = self._resolve_log_target(msg)
        if t is None:
            return {"found": False}
        return {"found": True, **t}

    async def _h_get_log(self, conn, msg):
        """Fetch a chunk of one worker log from whichever host owns it
        (offset/max_bytes ranged; task_id/actor_id filters to attributed
        output via the sidecar index; wait_s long-polls for follow mode).
        Ids resolve on every call, so a follow stream re-resolves cleanly
        after a controller bounce rebuilt the index from re-registers."""
        m = {k: msg.get(k) for k in
             ("name", "node_id", "offset", "max_bytes", "task_id",
              "actor_id", "worker_id", "wait_s", "strip_markers")
             if msg.get(k) is not None}
        if not m.get("name"):
            t = self._resolve_log_target(m)
            if t is None:
                return {"error": "no log file known for that id",
                        "data": "", "offset": int(m.get("offset") or 0),
                        "size": 0, "eof": True}
            m["name"] = t["name"]
            m["node_id"] = t["node_id"]
        return await self._fetch_log(m)

    async def _fetch_log(self, m: Dict[str, Any]) -> Dict[str, Any]:
        """Route one ranged log read to the owning host agent (or serve
        locally for head-host/virtual-node files). Shared by _h_get_log
        and the job-log walker, which follows a job's output across
        supervisor failovers file by file."""
        node = self.nodes.get(m.get("node_id") or "")
        if node is not None and node.agent_conn is not None:
            try:
                return await node.agent_conn.request(
                    {"kind": "get_log", **m},
                    timeout=float(m.get("wait_s") or 0) + 10)
            except Exception as e:
                return {"error": f"agent unavailable: {e!r}", "data": "",
                        "offset": int(m.get("offset") or 0), "size": 0,
                        "eof": True}
        from .worker_logs import serve_get_log_wait

        return await serve_get_log_wait(m)

    # jobs (core/job_manager.py) ----------------------------------------------
    # Thin delegates: the job table, attempt protocol, and log walker all
    # live in JobManager; these exist so `_handle` dispatch finds them.

    async def _h_job_submit(self, conn, msg):
        return self.jobs.submit(msg)

    async def _h_job_attempt_start(self, conn, msg):
        return await self.jobs.attempt_start(msg)

    async def _h_job_exec(self, conn, msg):
        return self.jobs.attempt_exec(msg)

    async def _h_job_attempt_done(self, conn, msg):
        return self.jobs.attempt_done(msg)

    async def _h_job_status(self, conn, msg):
        return self.jobs.status(msg.get("job_id") or "")

    async def _h_job_list(self, conn, msg):
        return {"jobs": self.jobs.list()}

    async def _h_job_wait(self, conn, msg):
        return await self.jobs.wait(msg)

    async def _h_job_stop(self, conn, msg):
        return await self.jobs.stop(msg)

    async def _h_job_stop_ack(self, conn, msg):
        return self.jobs.stop_ack(msg)

    async def _h_job_logs(self, conn, msg):
        return await self.jobs.logs(msg)

    async def _h_wait(self, conn, msg):
        """O(n) wait: one callback registration per missing object, arrivals
        drained incrementally (the previous design re-registered a waiter
        future for every not-ready id on every wake — O(n^2) registrations
        for large batches; reference envelope is a 10k-object wait,
        release/benchmarks/README.md)."""
        ids: List[str] = msg["object_ids"]
        num_returns: int = msg["num_returns"]
        timeout = msg.get("timeout")
        deadline = None if timeout is None else time.monotonic() + timeout
        ready: List[str] = []
        missing: List[str] = []
        for oid in ids:
            (ready if oid in self.objects else missing).append(oid)
        if len(ready) >= num_returns:
            return ready[:num_returns]
        arrived: List[str] = []
        wake = asyncio.Event()

        def notify(oid: str) -> None:
            arrived.append(oid)
            wake.set()

        for oid in missing:
            self.object_callbacks.setdefault(oid, []).append(notify)
        def drain() -> None:
            ready.extend(arrived)
            arrived.clear()

        try:
            while True:
                if deadline is None:
                    await wake.wait()
                else:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        drain()  # arrivals that raced the deadline count
                        return ready[:num_returns]
                    try:
                        await asyncio.wait_for(wake.wait(), remaining)
                    except asyncio.TimeoutError:
                        drain()
                        return ready[:num_returns]
                wake.clear()
                drain()
                if len(ready) >= num_returns:
                    return ready[:num_returns]
        finally:
            for oid in missing:
                cbs = self.object_callbacks.get(oid)
                if cbs is not None:
                    try:
                        cbs.remove(notify)
                    except ValueError:
                        pass
                    if not cbs:
                        self.object_callbacks.pop(oid, None)

    async def _h_free_objects(self, conn, msg):
        for oid in msg["object_ids"]:
            loc = self.objects.pop(oid, None)
            self.object_touch.pop(oid, None)
            self.object_created.pop(oid, None)
            self.object_src.pop(oid, None)
            self._leak_reported.discard(oid)
            # Broadcast replicas die with the primary: each copy frees on
            # its own host (same routing as the primary's bytes).
            reps = self.object_replicas.pop(oid, None)
            for rep in (reps or {}).values():
                await self._free_one_location(rep)
            if loc is None:
                continue
            await self._free_one_location(loc)
        return {"ok": True}

    async def _free_one_location(self, loc: ObjectLocation) -> None:
        if loc.host_id is not None and loc.host_id != self.host_id:
            # Bytes live on another host: route the free to its agent.
            node = self.nodes.get(loc.node_id or "")
            if node is not None and node.agent_conn is not None:
                try:
                    await node.agent_conn.send(
                        {"kind": "free_object", "loc": loc})
                except Exception:
                    pass
            return
        free_location(loc)

    async def _h_register_function(self, conn, msg):
        self.functions[msg["func_id"]] = msg["blob"]
        self._state_dirty = True
        return {"ok": True}

    async def _h_fetch_function(self, conn, msg):
        blob = self.functions.get(msg["func_id"])
        if blob is None:
            raise KeyError(f"function {msg['func_id']} not found in function table")
        return blob

    def _record_task_event(self, spec, event: str, **extra) -> None:
        ev = {
            "task_id": spec.get("task_id"),
            "label": spec.get("label"),
            "actor_id": spec.get("actor_id"),
            "event": event,
            "ts": time.time(),
            "worker_id": extra.get("worker_id") or spec.get("_worker_id"),
            "node_id": extra.get("node_id") or spec.get("sched_node"),
        }
        self.task_events.append(ev)
        self._export_event("TASK", ev)

    def _export_event(self, source: str, payload: Dict[str, Any]) -> None:
        """Structured export-event pipeline (reference: src/ray/util/event.h
        RAY_EVENT + the export-event JSONL files external systems tail):
        when RTPU_EVENT_EXPORT_PATH is set, every control-plane event
        appends one {source_type, timestamp, event_data} JSON line. Opened
        lazily, line-buffered; failures disable export rather than touch
        the control plane."""
        path = flags.get("RTPU_EVENT_EXPORT_PATH")
        if not path:
            return
        f = getattr(self, "_export_file", None)
        if f is None:
            try:
                f = self._export_file = open(path, "a", buffering=1)
            except OSError:
                self._export_file = False
                return
        if f is False:
            return
        try:
            f.write(json.dumps({
                "source_type": source,
                "timestamp": payload.get("ts") or time.time(),
                "event_data": {k: v for k, v in payload.items()
                               if k != "ts"},
            }, default=str) + "\n")
        except Exception:
            self._export_file = False

    async def _h_submit_task(self, conn, msg):
        spec = msg["spec"]
        # Idempotent by task id (partition hardening): a blind re-send
        # after an RPC timeout — or a driver-reconnect resubmission racing
        # a controller that never actually lost the first copy — must not
        # double-schedule.
        tid = spec["task_id"]
        if tid in self.tasks:
            return {"ok": True, "dup": True}
        rids = spec.get("return_ids") or ()
        if rids and all(r in self.objects for r in rids):
            return {"ok": True, "dup": True}
        self.tasks[spec["task_id"]] = spec
        self._note_child(spec)
        spec["state"] = "waiting_deps"
        if spec.get("streaming"):
            self.generators[spec["task_id"]] = GeneratorState(
                task_id=spec["task_id"],
                window=int(spec.get("backpressure", 16)),
            )
        self._record_task_event(spec, "submitted")
        await self._resolve_deps_then_queue(spec)
        return {"ok": True}

    # streaming generators ----------------------------------------------------

    async def _h_generator_item(self, conn, msg):
        """Producer reports one yielded item (reference:
        ReportGeneratorItemReturns, core_worker.proto:462). The reply is
        withheld while the consumer lags more than the backpressure window,
        which stalls the producing worker thread — flow control without a
        second channel."""
        gen = self.generators.get(msg["task_id"])
        self._store_location(msg["loc"])
        if gen is None:
            return {"ok": True}
        gen.items.append(msg["loc"].object_id)
        gen.wake.set()
        while (
            len(gen.items) - gen.consumed > gen.window
            and not gen.done
            and not gen.closed
        ):
            gen.drain.clear()
            await gen.drain.wait()
        return {"ok": True, "closed": gen.closed}

    async def _h_generator_next(self, conn, msg):
        """Consumer requests item `index`; blocks until produced, raises the
        task's error, or reports exhaustion."""
        gen = self.generators.get(msg["task_id"])
        if gen is None:
            raise ValueError(f"unknown streaming task {msg['task_id'][:8]}")
        index = msg["index"]
        while True:
            if index < len(gen.items):
                gen.consumed = max(gen.consumed, index + 1)
                gen.drain.set()
                return {"object_id": gen.items[index]}
            if gen.error is not None:
                self.generators.pop(msg["task_id"], None)
                raise gen.error
            if gen.done:
                self.generators.pop(msg["task_id"], None)
                return {"done": True}
            gen.wake.clear()
            await gen.wake.wait()

    async def _h_generator_close(self, conn, msg):
        """Consumer dropped the generator: release a producer stalled in the
        backpressure wait and let state be reclaimed (reference: streaming
        generator cancellation on deleted ObjectRefGenerator)."""
        gen = self.generators.get(msg["task_id"])
        if gen is None:
            return {"ok": True}
        gen.closed = True
        gen.drain.set()
        gen.wake.set()
        if gen.done:
            self.generators.pop(msg["task_id"], None)
        return {"ok": True}

    async def _resolve_deps_then_queue(self, spec: Dict[str, Any]) -> None:
        deps: List[str] = [d for d in spec.get("deps", []) if d not in self.objects]
        if deps:
            async def waiter():
                for oid in list(deps):
                    await self._wait_for_object(oid)
                # Dependency errors propagate without running the task.
                err = self._first_dep_error(spec)
                if err is not None:
                    self._fail_task(spec, err)
                    return
                spec["state"] = "pending"
                self.pending_queue.append(spec)
                self._wake_scheduler()

            asyncio.get_running_loop().create_task(waiter())
        else:
            err = self._first_dep_error(spec)
            if err is not None:
                self._fail_task(spec, err)
                return
            spec["state"] = "pending"
            self.pending_queue.append(spec)
            self._wake_scheduler()

    def _first_dep_error(self, spec) -> Optional[Exception]:
        for oid in spec.get("deps", []):
            loc = self.objects.get(oid)
            if loc is not None and loc.is_error:
                return DependencyError(f"upstream task failed for object {oid[:8]}")
        return None

    def _note_child(self, spec: Dict[str, Any]) -> None:
        ptid = spec.get("parent_task_id")
        if not ptid:
            return
        # Hard cap: a pathological fan-out must not let the tree outgrow
        # the task table it mirrors.
        if len(self.task_parent) > 4 * self.lineage_max:
            return
        self.task_children.setdefault(ptid, set()).add(spec["task_id"])
        self.task_parent[spec["task_id"]] = ptid

    async def _h_task_lineage(self, conn, msg):
        """Fire-and-forget ownership note for directly-pushed child tasks
        (the controller never sees their submission): parent -> child edges
        feeding the recursive-cancel tree."""
        for parent, child in msg.get("edges") or ():
            if parent and child and len(self.task_parent) <= 4 * self.lineage_max:
                self.task_children.setdefault(parent, set()).add(child)
                self.task_parent[child] = parent
        return {"ok": True}

    def _prune_child(self, task_id: str) -> None:
        ptid = self.task_parent.pop(task_id, None)
        if ptid is None:
            return
        kids = self.task_children.get(ptid)
        if kids is not None:
            kids.discard(task_id)
            if not kids:
                self.task_children.pop(ptid, None)

    def _fail_task(self, spec, err: Exception) -> None:
        self.tasks.pop(spec["task_id"], None)
        self._prune_child(spec["task_id"])
        self._record_task_event(spec, "failed")
        self._finalize_generator(spec["task_id"], err)
        for oid in spec["return_ids"]:
            self._store_error(oid, err)

    def _finalize_generator(self, task_id: str, err: Optional[Exception]) -> None:
        gen = self.generators.get(task_id)
        if gen is not None and not gen.done:
            gen.error = gen.error or err
            gen.done = True
            gen.wake.set()
            gen.drain.set()

    async def _h_cancel_task(self, conn, msg):
        """ray.cancel (reference: python/ray/_private/worker.py cancel +
        CancelTask RPC): a QUEUED task is failed in place with
        TaskCancelledError — no worker round-trip; a RUNNING one gets an
        async-raise in its executing thread (force=True kills the worker
        process instead — for code that swallows exceptions). An actor
        call's cancel removes the still-queued spec or interrupts the
        hosting worker's mailbox entry. recursive=True additionally walks
        the ownership tree and cancels every live descendant. Every path
        is idempotent: double-cancel and cancel-of-finished are no-ops."""
        force = bool(msg.get("force"))
        recursive = bool(msg.get("recursive"))
        oid = msg.get("object_id")
        task_id = msg.get("task_id")
        spec = None
        if task_id is not None:
            spec = self.tasks.get(task_id)
        if spec is None and oid is not None:
            for t in self.tasks.values():
                if oid in (t.get("return_ids") or ()):
                    spec = t
                    task_id = t["task_id"]
                    break
        if spec is None and task_id is None and oid is not None:
            # Finished parent: resolve the subtree root from the bounded
            # done-oid map so recursive still reaches live descendants.
            task_id = self.done_oid2task.get(oid)
        if spec is None and oid is not None and oid in self.objects \
                and not (recursive and task_id):
            # Already finished: a cancel is a no-op, not an error.
            return {"ok": True, "state": "finished"}
        if spec is None and not (recursive and task_id):
            return {"ok": False, "reason": "unknown or already finished"}
        state = await self._cancel_one(spec, force) or "finished"
        descendants = 0
        if recursive and task_id:
            seen = {task_id}
            frontier = list(self.task_children.get(task_id, ()))
            while frontier:
                child = frontier.pop()
                if child in seen:
                    continue
                seen.add(child)
                frontier.extend(self.task_children.get(child, ()))
                cspec = self.tasks.get(child)
                if cspec is not None:
                    if await self._cancel_one(cspec, force):
                        descendants += 1
                elif child in self.task_parent:
                    # A live edge but no controller-side spec: the child
                    # was pushed directly to a leased worker. Broadcast the
                    # mark — its host refuses it at dequeue or async-raises
                    # the running thread; everyone else ignores it.
                    await self._broadcast_cancel(child)
                    descendants += 1
        return {"ok": True, "state": state, "descendants": descendants}

    async def _cancel_one(self, spec, force: bool) -> Optional[str]:
        """Cancel a single live spec; returns the resulting state, or None
        when there was nothing to do."""
        if spec is None:
            return None
        task_id = spec["task_id"]
        if spec.get("__cancelled__"):
            return "already_cancelled"
        if spec.get("actor_id"):
            actor = self.actors.get(spec["actor_id"])
            spec["__cancelled__"] = True
            spec["max_retries"] = 0
            if actor is not None and spec in actor.pending_calls:
                try:
                    actor.pending_calls.remove(spec)
                except ValueError:
                    pass
                self._fail_task(spec, TaskCancelledError(
                    f"actor call {task_id[:8]} was cancelled before it started"))
                self._record_task_event(spec, "cancelled")
                return "queued"
            w = self.workers.get(actor.worker_id or "") if actor else None
            if w is None:
                return "marked"
            # The hosting worker either refuses the mailbox entry at
            # dequeue or async-raises the running call. force degrades to
            # the async-raise: killing the worker would take the whole
            # actor (that is rtpu.kill's job).
            try:
                await w.conn.send({"kind": "cancel_task", "task_id": task_id})
            except Exception:
                pass
            self._record_task_event(spec, "cancel_requested",
                                    worker_id=w.worker_id)
            return "running"
        w = next((x for x in self.workers.values()
                  if x.current_task == task_id), None)
        if w is None:
            # Still queued: remove + fail the returns at the controller.
            self.pending_queue.remove(task_id)
            self._release_task_resources(spec)
            self._fail_task(spec, TaskCancelledError(
                f"task {task_id[:8]} was cancelled before it started"))
            self._record_task_event(spec, "cancelled")
            return "queued"
        spec["max_retries"] = 0  # a cancel must not resurrect it
        spec["__cancelled__"] = True
        if force:
            await self._shutdown_worker(w)
            return "force_killed"
        try:
            await w.conn.send({"kind": "cancel_task", "task_id": task_id})
        except Exception:
            pass
        self._record_task_event(spec, "cancel_requested",
                                worker_id=w.worker_id)
        return "running"

    async def _broadcast_cancel(self, task_id: str) -> None:
        for w in list(self.workers.values()):
            try:
                await w.conn.send({"kind": "cancel_task", "task_id": task_id})
            except Exception:
                pass

    async def _h_task_spillback(self, conn, msg):
        """A worker's admission check rejected a dispatched task
        (reference: raylet spillback — the scheduler retries elsewhere
        with the rejecting node excluded). Resources are returned, the
        worker goes back to idle, and the spec re-queues."""
        task_id = msg["task_id"]
        spec = self.tasks.get(task_id)
        w = self.workers.get(msg.get("worker_id", ""))
        if w is not None and w.current_task == task_id:
            w.current_task = None
            if w.state == "task":
                w.state = "idle"
        if spec is None:
            return {"ok": False}
        self._release_task_resources(spec)
        node_id = spec.pop("sched_node", None)
        spec.pop("blocked", None)
        if node_id:
            spec.setdefault("spillback_excluded", []).append(node_id)
        spec["spillback_count"] = spec.get("spillback_count", 0) + 1
        spec["state"] = "waiting_deps"
        self._record_task_event(spec, "spillback",
                                worker_id=msg.get("worker_id"),
                                node_id=node_id)
        await self._resolve_deps_then_queue(spec)
        self._wake_scheduler()
        return {"ok": True}

    async def _h_task_done(self, conn, msg):
        task_id = msg["task_id"]
        gen = self.generators.get(task_id)
        if gen is not None:
            if msg.get("is_error") or msg.get("error_locations"):
                err_locs = msg.get("error_locations") or []
                if err_locs:
                    import pickle as _p

                    try:
                        gen.error = _p.loads(err_locs[0].inline)
                    except Exception:
                        gen.error = WorkerCrashedError("streaming task failed")
                else:
                    gen.error = WorkerCrashedError("streaming task failed")
            gen.done = True
            gen.wake.set()
            gen.drain.set()
            if gen.closed:
                self.generators.pop(task_id, None)
        spec = self.tasks.pop(task_id, None)
        # retry_exceptions (reference: @ray.remote(retry_exceptions=True),
        # task_manager.cc RetryTask on application error): a failed task
        # with retry budget re-queues instead of surfacing the error —
        # cancelled tasks excepted (a cancel must stick).
        if (spec is not None and msg.get("is_error")
                and spec.get("retry_exceptions")
                and int(spec.get("max_retries", 0)) > 0
                and not spec.get("__cancelled__")
                and not gen):
            spec["max_retries"] = int(spec["max_retries"]) - 1
            if w := self.workers.get(msg["worker_id"]):
                if w.current_task == task_id:
                    w.current_task = None
                    if w.state == "task":
                        w.state = "idle"
            self._release_task_resources(spec)
            spec.pop("sched_node", None)
            spec.pop("blocked", None)
            spec["state"] = "waiting_deps"
            self.tasks[task_id] = spec
            self._record_task_event(spec, "retry",
                                    worker_id=msg.get("worker_id"))
            await self._resolve_deps_then_queue(spec)
            self._wake_scheduler()
            return {"ok": True}
        self._prune_child(task_id)
        if spec is not None:
            for oid in spec.get("return_ids") or ():
                self.done_oid2task[oid] = task_id
            while len(self.done_oid2task) > 4 * self.lineage_max:
                self.done_oid2task.popitem(last=False)
            self._record_task_event(
                spec, "failed" if msg.get("is_error") else "finished",
                worker_id=msg.get("worker_id"))
        for loc in msg.get("locations", []):
            self._store_location(loc)
        if msg.get("error_locations"):
            for loc in msg["error_locations"]:
                self._store_location(loc)
        w = self.workers.get(msg["worker_id"])
        if w is not None:
            # It delivered a result: the memory-monitor kill (if any) did
            # not take — a later unrelated death must not be blamed on OOM.
            w.oom_killed = False
        if w is not None and w.current_task == task_id:
            w.current_task = None
            if w.state == "task":
                w.state = "idle"
        if spec is not None:
            self._release_task_resources(spec)
            self._record_lineage(spec, msg)
        elif msg.get("spec") is not None:
            # Directly-pushed (leased) task: the controller never saw the
            # submission, so the completion report carries the spec — enough
            # to register lineage (object reconstruction after node loss)
            # and the task events. Resources stay pinned by the lease. The
            # worker's start timestamp synthesizes the "running" event the
            # timeline pairs with the terminal one.
            for oid in msg["spec"].get("return_ids") or ():
                # Leased tasks resolve through done_oid2task too: without
                # this, a recursive cancel rooted at a FINISHED direct-push
                # parent cannot find the subtree.
                self.done_oid2task[oid] = msg["spec"].get("task_id", task_id)
            while len(self.done_oid2task) > 4 * self.lineage_max:
                self.done_oid2task.popitem(last=False)
            if msg.get("started_ts"):
                w_lease = self.workers.get(msg.get("worker_id", ""))
                self.task_events.append({
                    "task_id": msg["spec"].get("task_id"),
                    "label": msg["spec"].get("label"),
                    "actor_id": None,
                    "event": "running",
                    "ts": msg["started_ts"],
                    "worker_id": msg.get("worker_id"),
                    "node_id": w_lease.node_id if w_lease else None,
                })
            self._record_task_event(
                msg["spec"], "failed" if msg.get("is_error") else "finished",
                worker_id=msg.get("worker_id"))
            self._record_lineage(msg["spec"], msg)
        self._wake_scheduler()
        return {"ok": True}

    async def _h_task_done_batch(self, conn, msg):
        """Multi-entry completion report: one framed message carries many
        task_done payloads (acks + result-location publishes) shipped by a
        worker's completion batcher — one unpickle and one handler pass for
        a whole burst of finishes (reference: CoreWorker's batched task
        status/export reports riding one gRPC call)."""
        for item in msg.get("items") or ():
            await self._h_task_done(conn, item)
        return {"ok": True}

    def _record_lineage(self, spec: Dict[str, Any], msg: Dict[str, Any]) -> None:
        """Remember the spec of a successfully finished plain task so its
        outputs can be reconstructed after a node loss."""
        if (
            msg.get("is_error")
            or msg.get("error_locations")
            or spec.get("actor_id")
            or spec.get("is_actor_creation")
            or spec.get("streaming")
            or not spec.get("return_ids")
            # Slim leased-completion reports (inline-only results carry
            # their bytes in the stored location) have no func_id — there
            # is nothing to re-execute and nothing that can be lost.
            or not spec.get("func_id")
        ):
            return
        for oid in spec["return_ids"]:
            self.lineage[oid] = spec
            self.lineage.move_to_end(oid)
        while len(self.lineage) > self.lineage_max:
            self.lineage.popitem(last=False)

    async def _h_task_blocked(self, conn, msg):
        # A task blocked in get() releases its CPU so child tasks can run
        # (reference: NotifyDirectCallTaskBlocked, raylet_client.h:380).
        spec = self.tasks.get(msg["task_id"])
        if spec is not None and not spec.get("blocked"):
            spec["blocked"] = True
            node = self.nodes.get(spec.get("sched_node", ""))
            cpu = spec.get("resources", {}).get("CPU", 0.0)
            if node and cpu:
                _res_add(node.available, {"CPU": cpu})
                self._wake_scheduler()
        return {"ok": True}

    async def _h_task_unblocked(self, conn, msg):
        spec = self.tasks.get(msg["task_id"])
        if spec is not None and spec.get("blocked"):
            spec["blocked"] = False
            node = self.nodes.get(spec.get("sched_node", ""))
            cpu = spec.get("resources", {}).get("CPU", 0.0)
            if node and cpu:
                # May drive available negative transiently; oversubscription on
                # wake avoids deadlock (same tradeoff the reference makes).
                _res_sub(node.available, {"CPU": cpu})
        return {"ok": True}

    # actors ------------------------------------------------------------------

    async def _h_create_actor(self, conn, msg):
        spec = msg["spec"]
        actor_id = spec["actor_id"]
        if actor_id in self.actors:
            # Idempotent by actor id (partition hardening): a retried
            # create after an RPC timeout joins the original creation.
            return {"ok": True, "dup": True}
        name = spec.get("name")
        namespace = spec.get("namespace", "default")
        if name:
            key = (namespace, name)
            if key in self.named_actors and self.actors[self.named_actors[key]].state != "dead":
                raise ValueError(f"actor name {name!r} already taken")
            self.named_actors[key] = actor_id
        actor = ActorInfo(
            actor_id=actor_id,
            name=name,
            resources=spec.get("resources", {}),
            pg=spec.get("pg"),
            detached=spec.get("detached", False),
            creation_task_id=spec["task_id"],
            max_restarts=int(spec.get("max_restarts", 0)),
            creation_spec=spec,
        )
        self.actors[actor_id] = actor
        if actor.detached:
            self._state_dirty = True
        self._emit_event(
            "INFO", "ACTOR_CREATED",
            f"actor {name or actor_id[:8]} creation submitted"
            + (" (detached)" if actor.detached else ""),
            actor_id=actor_id,
            data={"name": name, "detached": actor.detached})
        spec["is_actor_creation"] = True
        self.tasks[spec["task_id"]] = spec
        await self._resolve_deps_then_queue(spec)
        return {"ok": True}

    async def _h_actor_ready(self, conn, msg):
        actor = self.actors.get(msg["actor_id"])
        if actor is None:
            return {"ok": False}
        # Stale-sender guard: an actor_ready that raced the sender's death
        # (e.g. delayed in flight while the worker was killed and the
        # restart already re-queued the creation) must not flip a
        # restarting actor alive — the restart path owns it now, and the
        # consumed-blob pop below would discard state the re-queued
        # creation still needs.
        sender = next((w for w in self.workers.values() if w.conn is conn),
                      None)
        if actor.worker_id is None or (
                sender is not None and sender.worker_id != actor.worker_id):
            return {"ok": False, "stale": True}
        if actor.creation_task_id:
            spec = self.tasks.pop(actor.creation_task_id, None)
            if spec is not None:
                self._record_task_event(spec, "finished")
        # Drain queued calls BEFORE flipping to alive: resolve_actor must
        # not hand out the direct address while controller-queued calls are
        # still being dispatched, or a fresh direct call could overtake them
        # at the worker (per-caller ordering). Dispatch awaits, so new
        # submissions can interleave and re-append — hence the loop.
        while actor.pending_calls:
            calls, actor.pending_calls = actor.pending_calls, []
            for call in calls:
                await self._dispatch_actor_call(actor, call)
        actor.state = "alive"
        # The restore is CONFIRMED (the worker loaded the record before
        # sending actor_ready): the blob is consumed now — the instance
        # mutates from here on, so a later crash re-creation must restore
        # from a durable checkpoint (or the constructor), never this copy.
        # Until this point the blob stays in the spec, so a restore target
        # dying between dispatch and actor_ready retries with state intact.
        if actor.creation_spec is not None:
            actor.creation_spec.pop("state_blob", None)
        if msg.get("restored_epoch") is not None:
            self._emit_event(
                "INFO", "ACTOR_RESTORED",
                f"actor {actor.name or actor.actor_id[:8]} restored from "
                f"checkpoint epoch {msg['restored_epoch']} on node "
                f"{(actor.node_id or '?')[:8]}",
                actor_id=actor.actor_id, node_id=actor.node_id,
                worker_id=actor.worker_id,
                data={"epoch": int(msg["restored_epoch"])})
        self._export_event("ACTOR", {"actor_id": actor.actor_id,
                                     "event": "alive", "name": actor.name,
                                     "node_id": actor.node_id,
                                     "ts": time.time()})
        self._emit_event(
            "INFO", "ACTOR_ALIVE",
            f"actor {actor.name or actor.actor_id[:8]} alive on node "
            f"{(actor.node_id or '?')[:8]}",
            actor_id=actor.actor_id, node_id=actor.node_id,
            worker_id=actor.worker_id, data={"name": actor.name})
        return {"ok": True}

    async def _h_actor_exit(self, conn, msg):
        """Intentional actor termination via exit_actor: dead WITHOUT
        restart regardless of max_restarts (reference semantics)."""
        actor = self.actors.get(msg["actor_id"])
        if actor is None:
            return {"ok": False}
        actor.max_restarts = 0  # an intentional exit must stick
        self._mark_actor_dead(actor, ActorDiedError(
            f"actor {actor.actor_id[:8]} exited via exit_actor()"))
        w = self.workers.get(actor.worker_id or "")
        if w is not None:
            w.actor_ids.discard(actor.actor_id)
            if not w.actor_ids:
                w.state = "idle"
        self._export_event("ACTOR", {"actor_id": actor.actor_id,
                                     "event": "exited",
                                     "ts": time.time()})
        self._wake_scheduler()
        return {"ok": True}

    async def _h_actor_error(self, conn, msg):
        actor = self.actors.get(msg["actor_id"])
        if actor is None:
            return {"ok": False}
        if actor.creation_task_id:
            spec = self.tasks.pop(actor.creation_task_id, None)
            if spec is not None:
                self._record_task_event(spec, "failed")
        actor.creation_error = msg["error"]
        self._mark_actor_dead(actor, msg["error"])
        w = self.workers.get(actor.worker_id or "")
        if w is not None:
            w.actor_ids.discard(actor.actor_id)
            if not w.actor_ids:
                w.state = "idle"
        self._wake_scheduler()
        return {"ok": True}

    def _store_actor_checkpoint(self, actor: ActorInfo, epoch: int,
                                blob: bytes) -> bool:
        """Record one shipped checkpoint (newest epoch wins; duplicates and
        stragglers are dropped). Detached actors additionally persist the
        record next to --state-path so it survives a controller bounce."""
        epoch = int(epoch)
        cur = actor.checkpoint
        if cur is not None and cur["epoch"] >= epoch:
            return False
        actor.checkpoint = {"epoch": epoch, "blob": blob,
                            "bytes": len(blob), "ts": time.time()}
        self.ckpt_stats["count"] += 1
        self.ckpt_stats["bytes"] += len(blob)
        if actor.detached and self.persist_path:
            # 8-byte big-endian epoch header + opaque record: the restore
            # path reads the epoch without unpickling user state into the
            # controller process.
            import struct as _struct

            path = f"{self.persist_path}.ckpt.{actor.actor_id}"
            tmp = path + f".tmp{os.getpid()}"
            try:
                with open(tmp, "wb") as f:
                    f.write(_struct.pack("!Q", epoch) + blob)
                os.replace(tmp, path)
            except OSError:
                pass
        self._emit_event(
            "DEBUG", "ACTOR_CHECKPOINTED",
            f"actor {actor.name or actor.actor_id[:8]} checkpointed "
            f"(epoch {epoch}, {len(blob)} bytes)",
            actor_id=actor.actor_id, node_id=actor.node_id,
            worker_id=actor.worker_id,
            data={"epoch": epoch, "bytes": len(blob)})
        return True

    async def _h_actor_checkpoint(self, conn, msg):
        """Async copy of a worker's durable actor checkpoint (the host-
        local file is the fast copy; this one survives whole-node loss)."""
        actor = self.actors.get(msg["actor_id"])
        if actor is None or actor.state == "dead":
            return None
        self._store_actor_checkpoint(actor, msg["epoch"], msg["blob"])
        return None

    async def _h_submit_actor_task(self, conn, msg):
        spec = msg["spec"]
        # Idempotent by task id (partition hardening): a timed-out-and-
        # retried submit whose original landed must not run twice — known
        # in-flight specs and already-published results answer ok.
        tid = spec["task_id"]
        if tid in self.tasks:
            return {"ok": True, "dup": True}
        rids = spec.get("return_ids") or ()
        if rids and all(r in self.objects for r in rids):
            return {"ok": True, "dup": True}
        actor = self.actors.get(spec["actor_id"])
        if actor is None:
            raise ValueError(f"unknown actor {spec['actor_id']}")
        if spec.get("streaming"):
            self.generators[spec["task_id"]] = GeneratorState(
                task_id=spec["task_id"],
                window=int(spec.get("backpressure", 16)),
            )
        if actor.state == "dead":
            err = actor.creation_error or ActorDiedError(f"actor {actor.actor_id[:8]} is dead")
            self._finalize_generator(spec["task_id"], err)
            for oid in spec["return_ids"]:
                self._store_error(oid, err)
            return {"ok": True}
        self.tasks[spec["task_id"]] = spec
        self._note_child(spec)
        if actor.state in ("pending", "restarting"):
            actor.pending_calls.append(spec)
        else:
            await self._dispatch_actor_call(actor, spec)
        return {"ok": True}

    async def _dispatch_actor_call(self, actor: ActorInfo, spec: Dict[str, Any]) -> None:
        dl = spec.get("deadline_ts")
        if dl is not None and time.time() > dl:
            # Expired while parked in pending_calls (or on arrival): the
            # mailbox never sees dead work.
            self._fail_task(spec, DeadlineExceededError(
                f"actor call {spec['task_id'][:8]} deadline passed while queued"))
            self._record_task_event(spec, "deadline_exceeded")
            return
        w = self.workers.get(actor.worker_id or "")
        if w is None:
            if spec.get("replay") and actor.state != "dead":
                # Worker death mid-handling: a replayable call parks and
                # redelivers after the restart (journal dedups).
                actor.pending_calls.append(spec)
            else:
                self._fail_task(spec, ActorDiedError("actor worker gone"))
            return
        node = self.nodes.get(actor.node_id or "")
        if node is not None and node.suspect:
            # Suspect host (heartbeat-silent, possibly partitioned): a
            # fire-and-forget dispatch there would vanish. Buffer — the
            # heal path flushes in order; the death path re-buffers or
            # fails per the actor's replay setting.
            actor.pending_calls.append(spec)
            return
        # Per-actor ordered dispatch (direct_actor_task_submitter.h sequencing).
        async with actor.order_lock:
            # Wait for deps before forwarding so the worker never blocks.
            for oid in spec.get("deps", []):
                await self._wait_for_object(oid)
            err = self._first_dep_error(spec)
            if err is not None:
                self._fail_task(spec, err)
                return
            spec["sched_node"] = actor.node_id
            spec["__dispatch_ts"] = time.time()  # hang-watchdog age base
            self._record_task_event(spec, "running", worker_id=w.worker_id,
                                    node_id=actor.node_id)
            await w.conn.send({"kind": "execute_actor_task", "spec": spec})

    # ---- worker leases for direct task dispatch -----------------------------
    # Reference: direct_task_transport.h:75 — the owner leases a worker from
    # the raylet, then pushes tasks to it directly; the lease pins the
    # worker's resources until returned. Controller keeps directory/health/
    # lineage; the per-call path is peer-to-peer.

    def _grant_one_lease(self, conn, resources: Dict[str, float],
                         env_hash: str, arg_bytes: Dict[str, int],
                         block_id: str = "") -> Optional[Dict[str, Any]]:
        """One lease grant against current availability; None when no node
        can serve it. Shared by the single-lease and lease-block handlers —
        a block grant is just this loop run N times against the availability
        it is itself decrementing."""
        needs_tpu = resources.get("TPU", 0) > 0
        mem_limit = flags.get("RTPU_SPILLBACK_MEM_FRACTION")
        candidates = [n for n in self.nodes.values()
                      if self._schedulable(n)]
        for node in self._hybrid_order(candidates, arg_bytes):
            if not _res_fits(node.available, resources):
                continue
            # Grant-time admission for the direct path (the spillback
            # analog — pushed tasks never pass the worker's execute_task
            # check, so screen the node's reported memory pressure here).
            if mem_limit and node.mem_fraction >= mem_limit:
                self.lease_stats["mem_refused"] += 1
                continue
            # Server-side lease bound (advisor r4): once a node already
            # holds a lease, never lease away its LAST schedulable CPU.
            # Multiple drivers can otherwise collectively pin every idle
            # worker, leaving queued actor creations dependent solely on
            # the holder-cooperative, 0.2s-throttled reclaim nudge. (A
            # node's FIRST lease may still take the last CPU so tiny test
            # hosts keep direct dispatch; CPU-less requests can't take the
            # last CPU, so the guard doesn't apply to them.)
            req_cpu = resources.get("CPU", 0.0)
            has_lease = any(l["node_id"] == node.node_id
                            for l in self._leases.values())
            if (has_lease and req_cpu > 0
                    and node.available.get("CPU", 0.0) - req_cpu < 1.0):
                continue
            w = self._find_idle_worker(node, needs_tpu, env_hash,
                                       tpu_chips=int(resources.get("TPU", 0)))
            if w is None or not w.direct_port:
                continue
            _res_sub(node.available, resources)
            w.state = "leased"
            lease_id = uuid.uuid4().hex[:12]
            self._leases[lease_id] = {"worker_id": w.worker_id,
                                      "node_id": node.node_id,
                                      "resources": dict(resources),
                                      "block_id": block_id,
                                      "owner": conn}
            self.lease_stats["granted"] += 1
            peer = w.conn.writer.get_extra_info("peername")
            host = peer[0] if peer else "127.0.0.1"
            return {"lease_id": lease_id, "worker_id": w.worker_id,
                    "host": host, "port": w.direct_port,
                    "node_id": node.node_id}
        return None

    def _nudge_lease_spawns(self, resources: Dict[str, float],
                            runtime_env, arg_bytes: Dict[str, int],
                            count: int = 1) -> None:
        """Nothing idle: nudge spawns so a later lease request can succeed —
        in the SAME locality order as grants, so "grow toward the data
        node" creates the worker where the bytes are."""
        needs_tpu = resources.get("TPU", 0) > 0
        candidates = [n for n in self.nodes.values()
                      if self._schedulable(n)]
        for node in self._hybrid_order(candidates, arg_bytes):
            if count <= 0:
                break
            if _res_fits(node.available, resources):
                self._maybe_spawn_worker(node, needs_tpu, runtime_env,
                                         tpu_chips=int(resources.get("TPU", 0)))
                count -= 1

    async def _h_lease_worker(self, conn, msg):
        """Grant an idle worker to the requesting driver for direct task
        pushes. Returns {lease_id, worker_id, host, port} or {lease_id:
        None} when nothing is available (caller falls back to the queued
        controller path, which can also spawn new workers)."""
        resources: Dict[str, float] = msg.get("resources") or {"CPU": 1.0}
        # Locality term for the DIRECT path: the driver ships the byte
        # placement of the task's (cached-location) args so lease grants
        # rank nodes the same way queue placement does.
        arg_bytes: Dict[str, int] = msg.get("arg_bytes") or {}
        got = self._grant_one_lease(conn, resources,
                                    msg.get("env_hash") or "", arg_bytes)
        if got is not None:
            return got
        self._nudge_lease_spawns(resources, msg.get("runtime_env"),
                                 arg_bytes)
        return {"lease_id": None}

    async def _h_lease_block(self, conn, msg):
        """Bulk lease negotiation: grant up to ``count`` workers for one
        (resources, env) signature in a single round trip (reference: the
        raylet's lease tables keyed by scheduling class — the owner asks
        once per class, not once per worker, direct_task_transport.h:75).
        The driver fans its submission wave across the returned block with
        zero further controller involvement; partial grants are normal
        (the driver spills the remainder back through the queued path) and
        a shortfall nudges spawns so the next negotiation finds workers."""
        resources: Dict[str, float] = msg.get("resources") or {"CPU": 1.0}
        env_hash = msg.get("env_hash") or ""
        arg_bytes: Dict[str, int] = msg.get("arg_bytes") or {}
        count = max(1, int(msg.get("count", 1)))
        block_id = uuid.uuid4().hex[:12]
        grants: List[Dict[str, Any]] = []
        while len(grants) < count:
            got = self._grant_one_lease(conn, resources, env_hash,
                                        arg_bytes, block_id=block_id)
            if got is None:
                break
            grants.append(got)
        if grants:
            self.lease_stats["blocks"] += 1
        else:
            # Spawn nudges only on an EMPTY grant: a partial block means
            # the cluster is resource-saturated for this signature, where
            # a speculative spawn would burn ~50ms in this handler and
            # produce a worker the lease guard cannot grant anyway.
            self._nudge_lease_spawns(resources, msg.get("runtime_env"),
                                     arg_bytes)
        return {"block_id": block_id if grants else None, "grants": grants}

    def _release_lease(self, lease_id: str, to_idle: bool = True) -> None:
        """to_idle=False: the holder vanished without draining (driver
        disconnect) — the worker may still be executing an orphaned pushed
        task, so it is recycled rather than re-leased/scheduled (marking it
        idle would double-book its CPU)."""
        lease = self._leases.pop(lease_id, None)
        if lease is None:
            return
        node = self.nodes.get(lease["node_id"])
        if node is not None and node.alive:
            _res_add(node.available, lease["resources"])
        w = self.workers.get(lease["worker_id"])
        if w is not None and w.state == "leased":
            if to_idle:
                w.state = "idle"
            else:
                w.state = "dying"
                asyncio.get_running_loop().create_task(
                    self._shutdown_worker(w))
        self._wake_scheduler()

    async def _h_release_lease(self, conn, msg):
        # Accepts one lease_id or a lease_ids list (a block released in one
        # framed message — pool shutdown / reclaim hand back N at once).
        for lid in (msg.get("lease_ids") or
                    ([msg["lease_id"]] if msg.get("lease_id") else [])):
            self._release_lease(lid)
        return {"ok": True}

    async def _h_resolve_actor(self, conn, msg):
        """Lease-resolution for direct dispatch: where does this actor live?

        Callers resolve once, cache, and push calls straight to the worker's
        direct server (reference: direct_actor_task_submitter.h:74 — the
        submitter caches the actor's rpc address from the GCS and pushes).
        """
        actor = self.actors.get(msg["actor_id"])
        if actor is None:
            raise ValueError(f"unknown actor {msg['actor_id']}")
        # A just-created actor is usually mid-instantiation on its worker:
        # wait briefly for aliveness so the FIRST call can already go
        # direct (the caller pays instantiation latency either way).
        deadline = time.monotonic() + float(msg.get("wait", 1.0))
        while actor.state == "pending" and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        w = self.workers.get(actor.worker_id or "")
        direct = None
        if actor.state == "alive" and w is not None and w.direct_port:
            peer = w.conn.writer.get_extra_info("peername")
            host = peer[0] if peer else "127.0.0.1"
            # node_id lets callers decide locality (compiled-DAG edges
            # choose shm rings for same-node hops, streams otherwise).
            direct = {"worker_id": w.worker_id, "host": host,
                      "port": w.direct_port, "node_id": w.node_id}
        return {"state": actor.state, "direct": direct,
                "restarts": actor.restart_count}

    async def _h_dag_compiled(self, conn, msg):
        """A driver compiled a channel-based DAG: record the plan shape so
        `rtpu status` / state.list_state can show what pipelines hold
        resident loops on which actors. Steady-state execution never calls
        here — this pair of RPCs (with dag_torndown) is the controller's
        ENTIRE involvement in a compiled DAG's lifetime."""
        self.compiled_dags[msg["dag_id"]] = {
            "dag_id": msg["dag_id"],
            "stages": msg.get("stages", []),
            "edges": msg.get("edges", {}),
            "depth": msg.get("depth", 0),
            "since": time.time(),
        }
        return {"ok": True}

    async def _h_dag_torndown(self, conn, msg):
        self.compiled_dags.pop(msg["dag_id"], None)
        return {"ok": True}

    async def _h_dag_recovery(self, conn, msg):
        """A driver's self-healing pipeline reports a recovery phase
        transition (participant died / rebuilding / resumed / gave up).
        Bookkeeping + events only — the healing itself is driver-driven."""
        dag_id = msg["dag_id"]
        phase = msg.get("phase")
        d = self.compiled_dags.get(dag_id)
        if d is not None:
            if phase == "died":
                d["recovering"] = True
            elif phase == "recovering":
                d["recovering"] = True
            elif phase == "recovered":
                d["recovering"] = False
                d["recoveries"] = int(d.get("recoveries", 0)) + 1
                d["last_recovery_s"] = float(msg.get("duration_s", 0.0))
                d["last_cause"] = msg.get("cause")
            elif phase == "failed":
                d["recovering"] = False
                d["recovery_failures"] = (
                    int(d.get("recovery_failures", 0)) + 1)
        actors = msg.get("actors") or []
        short = ",".join(a[:8] for a in actors) or "?"
        cause = msg.get("cause", "?")
        if phase == "died":
            self._emit_event(
                "WARNING", "DAG_PARTICIPANT_DIED",
                f"compiled DAG {dag_id[:8]}: stage actor(s) {short} died "
                f"({cause}); pausing pipeline for in-place recovery")
        elif phase == "recovering":
            self._emit_event(
                "INFO", "DAG_RECOVERING",
                f"compiled DAG {dag_id[:8]}: quiescing survivors, "
                f"restarting {short}, rebuilding affected channels")
        elif phase == "recovered":
            # data= carries the structured cause so `rtpu events --kind
            # DAG_RECOVERED` can surface last_cause without parsing the
            # human message.
            self._emit_event(
                "INFO", "DAG_RECOVERED",
                f"compiled DAG {dag_id[:8]}: recovered from {cause} in "
                f"{float(msg.get('duration_s', 0.0)):.2f}s "
                f"(stage actor(s) {short} restarted, channels rebuilt, "
                f"retained items replayed)",
                data={"dag_id": dag_id, "cause": cause,
                      "actors": list(actors),
                      "duration_s": float(msg.get("duration_s", 0.0))})
        elif phase == "failed":
            self._emit_event(
                "ERROR", "DAG_RECOVERY_FAILED",
                f"compiled DAG {dag_id[:8]}: recovery from {cause} "
                f"failed; tearing the pipeline down")
        return {"ok": True}

    async def _h_get_named_actor(self, conn, msg):
        key = (msg.get("namespace", "default"), msg["name"])
        aid = self.named_actors.get(key)
        if aid is None or self.actors[aid].state == "dead":
            raise ValueError(f"no actor named {msg['name']!r}")
        actor = self.actors[aid]
        return {"actor_id": aid, "methods": self.kv.get(("__actor_methods__", aid), b"")}

    async def _h_kill_actor(self, conn, msg):
        actor = self.actors.get(msg["actor_id"])
        if actor is None or actor.state == "dead":
            return {"ok": True}
        w = self.workers.get(actor.worker_id or "")
        self._mark_actor_dead(actor, ActorDiedError(f"actor {actor.actor_id[:8]} was killed"))
        if w is not None:
            try:
                await w.conn.send({"kind": "shutdown"})
            except Exception:
                pass
            if w.proc is not None:
                try:
                    w.proc.terminate()
                except Exception:
                    pass
            elif w.spawn_token is not None:
                node = self.nodes.get(w.node_id)
                if node is not None and node.agent_conn is not None:
                    try:
                        await node.agent_conn.send(
                            {"kind": "kill_worker", "spawn_token": w.spawn_token}
                        )
                    except Exception:
                        pass
            await self._on_worker_death(w)
        return {"ok": True}

    def _mark_actor_dead(self, actor: ActorInfo, err: Exception) -> None:
        actor.state = "dead"
        actor.checkpoint = None  # retired for good: nothing may restore it
        if actor.detached and self.persist_path:
            try:
                os.unlink(f"{self.persist_path}.ckpt.{actor.actor_id}")
            except OSError:
                pass
        self._export_event("ACTOR", {"actor_id": actor.actor_id,
                                     "event": "dead", "ts": time.time()})
        self._emit_event(
            "ERROR", "ACTOR_DIED",
            f"actor {actor.name or actor.actor_id[:8]} died: {err}",
            actor_id=actor.actor_id, node_id=actor.node_id,
            worker_id=actor.worker_id,
            data={"name": actor.name, "cause": f"{type(err).__name__}: "
                  f"{err}", "restarts": actor.restart_count})
        if actor.detached:
            self._state_dirty = True
        from .job_manager import SUPERVISOR_PREFIX

        if (actor.name or "").startswith(SUPERVISOR_PREFIX):
            # Supervisor permanently dead (restart budget gone / actor
            # dropped): the job can never run again — fail it now so
            # wait_job callers don't hang on a supervisor that will
            # never report attempt_done.
            self.jobs.note_supervisor_died(actor, err, preempted=False,
                                           fatal=True)
        actor.creation_error = actor.creation_error or err
        for call in actor.pending_calls:
            self._fail_task(call, err)
        actor.pending_calls = []
        # Fail in-flight calls already forwarded to the worker.
        for tid, spec in list(self.tasks.items()):
            if spec.get("actor_id") == actor.actor_id:
                self._fail_task(spec, err)
        node = self.nodes.get(actor.node_id or "")
        if node and actor.reserved:
            actor.reserved = False
            self._release_reservation(actor.resources, node, actor.pg)

    # placement groups --------------------------------------------------------

    async def _h_create_placement_group(self, conn, msg):
        pg_id = msg["pg_id"]
        bundles = [Bundle(resources=dict(b), available=dict(b)) for b in msg["bundles"]]
        pg = PGInfo(pg_id=pg_id, bundles=bundles, strategy=msg["strategy"], name=msg.get("name"))
        self.pgs[pg_id] = pg
        if pg.name:
            self.named_pgs[pg.name] = pg_id
        self._emit_event(
            "INFO", "PG_CREATED",
            f"placement group {pg.name or pg_id[:8]} requested "
            f"({len(pg.bundles)} bundles, {pg.strategy})",
            data={"placement_group_id": pg_id, "strategy": pg.strategy,
                  "bundles": len(pg.bundles)})
        self._try_reserve_pg(pg)
        self._wake_scheduler()
        return {"ok": True}

    async def _h_pg_wait(self, conn, msg):
        pg = self.pgs[msg["pg_id"]]
        timeout = msg.get("timeout")
        if timeout is None:
            await pg.ready_event.wait()
        else:
            try:
                await asyncio.wait_for(pg.ready_event.wait(), timeout)
            except asyncio.TimeoutError:
                raise GetTimeoutError("placement group not ready") from None
        return {"state": pg.state, "bundle_nodes": [b.node_id for b in pg.bundles]}

    async def _h_remove_placement_group(self, conn, msg):
        pg = self.pgs.get(msg["pg_id"])
        if pg is None or pg.state == "removed":
            return {"ok": True}
        for b in pg.bundles:
            node = self.nodes.get(b.node_id or "")
            if node is not None:
                _res_add(node.available, b.resources)
        pg.state = "removed"
        if pg.name:
            self.named_pgs.pop(pg.name, None)
        self._emit_event(
            "INFO", "PG_REMOVED",
            f"placement group {pg.name or pg.pg_id[:8]} removed",
            data={"placement_group_id": pg.pg_id})
        self._wake_scheduler()
        return {"ok": True}

    def _try_reserve_pg(self, pg: PGInfo) -> None:
        """All-or-nothing bundle reservation (2-phase in the reference,
        gcs_placement_group_scheduler.h:274; atomic here since state is local)."""
        if pg.state != "pending":
            return
        nodes = [n for n in self.nodes.values()
                 if self._schedulable(n)]
        nodes.sort(key=lambda n: n.index)
        trial = {n.node_id: dict(n.available) for n in nodes}
        assignment: List[str] = []
        strategy = pg.strategy
        used_nodes: Set[str] = set()
        for b in pg.bundles:
            placed = None
            candidates = nodes
            if strategy == "STRICT_PACK" and assignment:
                candidates = [n for n in nodes if n.node_id == assignment[0]]
            elif strategy == "STRICT_SPREAD":
                candidates = [n for n in nodes if n.node_id not in used_nodes]
            elif strategy == "PACK" and assignment:
                candidates = sorted(nodes, key=lambda n: (n.node_id != assignment[-1], n.index))
            elif strategy == "SPREAD":
                candidates = sorted(nodes, key=lambda n: (n.node_id in used_nodes, n.index))
            for n in candidates:
                if _res_fits(trial[n.node_id], b.resources):
                    placed = n.node_id
                    break
            if placed is None:
                return  # cannot satisfy yet; retried on resource release
            _res_sub(trial[placed], b.resources)
            assignment.append(placed)
            used_nodes.add(placed)
        # Commit.
        for b, nid in zip(pg.bundles, assignment):
            b.node_id = nid
            b.available = dict(b.resources)
            _res_sub(self.nodes[nid].available, b.resources)
        pg.state = "ready"
        pg.ready_event.set()
        self._emit_event(
            "INFO", "PG_READY",
            f"placement group {pg.name or pg.pg_id[:8]} reserved on "
            f"{len(set(assignment))} node(s)",
            data={"placement_group_id": pg.pg_id,
                  "bundle_nodes": assignment})

    # kv / pubsub / introspection ---------------------------------------------

    async def _h_kv_put(self, conn, msg):
        key = (msg.get("ns", ""), msg["key"])
        exists = key in self.kv
        if msg.get("overwrite", True) or not exists:
            self.kv[key] = msg["value"]
            self._state_dirty = True
            return {"added": not exists}
        return {"added": False}

    async def _h_kv_get(self, conn, msg):
        return self.kv.get((msg.get("ns", ""), msg["key"]))

    async def _h_kv_del(self, conn, msg):
        deleted = self.kv.pop((msg.get("ns", ""), msg["key"]), None) is not None
        if deleted:
            self._state_dirty = True
        return {"deleted": deleted}

    async def _h_kv_keys(self, conn, msg):
        ns = msg.get("ns", "")
        prefix = msg.get("prefix", "")
        return [k for (n, k) in self.kv if n == ns and k.startswith(prefix)]

    async def _h_profile_workers(self, conn, msg):
        """On-demand cluster profiling (reference: dashboard-triggered
        py-spy stack dumps, dashboard/modules/reporter): push a stack-dump
        request to every live worker, gather replies for up to `timeout`
        seconds, return {worker_id: all-thread stack text}. Workers that
        are busy in native code simply miss the window — partial results
        are returned, never an error."""
        req_id, targets, workers = await self._gather_from_workers(
            "stack_dump", float(msg.get("timeout", 2.0)))
        return {"req_id": req_id, "requested": len(targets),
                "workers": workers}

    async def _gather_from_workers(self, kind: str, timeout: float,
                                   extra: Optional[Dict[str, Any]] = None,
                                   worker_ids: Optional[List[str]] = None):
        """Fan a request to the target workers (default: all live) and
        gather replies (arriving as profile_result messages) until all
        respond or the deadline passes — partial results, never an
        error. ``extra`` fields ride along on the request frame. Returns
        (req_id, target worker-id list, replies) — the target list (not
        just a count) so callers like the object census can name exactly
        which shards never answered (dead/SIGKILLed workers)."""
        req_id = uuid.uuid4().hex[:12]
        self._profiles[req_id] = {}
        targets = []
        pool = (list(self.workers.values()) if worker_ids is None
                else [self.workers[w] for w in worker_ids
                      if w in self.workers])
        for w in pool:
            try:
                await w.conn.send(
                    dict(extra or {}, kind=kind, req_id=req_id))
                targets.append(w.worker_id)
            except Exception:
                pass
        deadline = time.monotonic() + timeout
        while (len(self._profiles[req_id]) < len(targets)
               and time.monotonic() < deadline):
            await asyncio.sleep(0.05)
        return req_id, targets, self._profiles.pop(req_id)

    async def _h_profile_result(self, conn, msg):
        bucket = self._profiles.get(msg["req_id"])
        if bucket is not None:
            bucket[msg["worker_id"]] = msg["text"]
        return {"ok": True}

    async def _h_dag_timeline(self, conn, msg):
        """Gather the channel meter's recent per-stage step spans (recv /
        compute / send / blocked ns per microbatch) from every worker
        hosting resident DAG stages. Same fan-out/partial-result contract
        as the stack dump; feeds state.dag_timeline()'s chrome trace."""
        req_id, targets, replies = await self._gather_from_workers(
            "dag_spans", float(msg.get("timeout", 2.0)),
            extra={"dag": msg.get("dag")})
        spans: List[dict] = []
        for wid, text in replies.items():
            try:
                for s in json.loads(text):
                    s["worker_id"] = str(wid)
                    spans.append(s)
            except Exception:
                pass
        spans.sort(key=lambda s: s.get("end_s", 0.0))
        return {"requested": len(targets), "responded": len(replies),
                "spans": spans}

    def _profile_targets(self, msg) -> Optional[List[str]]:
        """Resolve a profile request's scope to worker ids (None = every
        live worker). Entity ids match on prefix, same as the event
        filters."""
        tid = msg.get("task_id")
        aid = msg.get("actor_id")
        nid = msg.get("node_id")
        wid = msg.get("worker_id")
        if not (tid or aid or nid or wid):
            return None
        out: Set[str] = set()
        if wid:
            out |= {w for w in self.workers if w.startswith(wid)}
        if nid:
            out |= {w.worker_id for w in self.workers.values()
                    if w.node_id.startswith(nid)}
        if aid:
            for a in self.actors.values():
                if a.actor_id.startswith(aid) and a.worker_id:
                    out.add(a.worker_id)
        if tid:
            for w in self.workers.values():
                if w.current_task and w.current_task.startswith(tid):
                    out.add(w.worker_id)
        return sorted(out)

    async def _h_profile(self, conn, msg):
        """Cluster flamegraph profiler (reference: the dashboard's
        py-spy flamegraph button, dashboard/modules/reporter — here a
        pure-Python wall-clock sampler inside our own workers): fan the
        sampling request to the target workers, gather their collapsed
        stacks, merge. Partial results are still a profile; a worker
        stuck in native code just misses the window."""
        if not flags.get("RTPU_PROFILER"):
            return {"error": "profiler disabled (RTPU_PROFILER=0)"}
        duration = min(120.0, max(0.1, float(msg.get("duration", 2.0))))
        hz = float(msg.get("hz") or flags.get("RTPU_PROFILER_HZ"))
        targets = self._profile_targets(msg)
        if targets is not None and not targets:
            return {"error": "no live workers match the requested "
                             "task/actor/node/worker filter"}
        from . import profiler

        _, sent_to, replies = await self._gather_from_workers(
            "profile", duration + 5.0,
            extra={"duration": duration, "hz": hz},
            worker_ids=targets)
        merged = profiler.merge_collapsed(replies)
        return {"requested": len(sent_to), "duration": duration, "hz": hz,
                "stacks": merged["stacks"], "samples": merged["samples"],
                "workers": merged["workers"]}

    # ------------------------------------------------------ telemetry plane

    async def _telemetry_loop(self) -> None:
        """Sample every metric family into the TSDB ring each step and
        run the alert rules over it (core/telemetry.py)."""
        while True:
            await asyncio.sleep(self.tsdb.step_s)
            try:
                now = time.time()
                self.tsdb.sample(now, self._metrics_families())
                if self.alerts is not None:
                    self.alerts.evaluate(now, self.tsdb)
                self.tsdb.maybe_persist(
                    now, self.alerts.snapshot() if self.alerts else None)
            except Exception as e:
                # History must never hurt the control plane.
                sys.stderr.write(f"[controller] telemetry step failed: "
                                 f"{e!r}\n")

    async def _h_query_metrics(self, conn, msg):
        """Metrics history (rtpu top / dashboard sparklines / alert
        tooling): plottable series from the TSDB ring with counter->rate
        and histogram->p50/p99 derivation done server-side."""
        if self.tsdb is None:
            return {"enabled": False, "series": [], "now": time.time(),
                    "step_s": 0.0}
        series = self.tsdb.query(
            name=msg.get("name"), prefix=msg.get("prefix"),
            tags=msg.get("tags"), since=msg.get("since"),
            stat=msg.get("stat"),
            window_s=float(msg.get("window_s", 60.0)),
            limit_series=int(msg.get("limit_series", 64)))
        return {"enabled": True, "series": series, "now": time.time(),
                "step_s": self.tsdb.step_s,
                "retain": self.tsdb.retain}

    async def _h_list_alerts(self, conn, msg):
        """Alert rules + current firing state (rtpu top header, tests)."""
        if self.alerts is None:
            return {"enabled": False, "rules": [], "firing": []}
        return {"enabled": True, "rules": list(self.alerts.rules),
                "firing": self.alerts.firing()}

    async def _h_memory_summary(self, conn, msg):
        """`rtpu memory` backend (reference: `ray memory` reference-table
        dump, _private/state.py memory summary): the object directory
        (id/size/storage/node) joined with each worker's local ownership
        stats, gathered with the same fan-out/partial-result contract as
        profiling — a worker busy in native code misses the window."""
        _, _, owners = await self._gather_from_workers(
            "ref_dump", float(msg.get("timeout", 2.0)))
        limit = int(msg.get("limit", 1000))
        # Largest first BEFORE truncating: the memory-debugging view must
        # never drop the biggest objects to insertion order.
        from .object_store import storage_kind

        ranked = sorted(self.objects.items(),
                        key=lambda kv: -kv[1].size)[:limit]
        objs = [{"object_id": oid, "size": loc.size,
                 "storage": storage_kind(loc), "node_id": loc.node_id}
                for oid, loc in ranked]
        arenas = {nid: n.arena_stats for nid, n in self.nodes.items()
                  if n.arena_stats}
        return {"objects": objs, "num_objects": len(self.objects),
                "total_bytes": sum(l.size for l in self.objects.values()),
                "workers": owners, "arenas": arenas}

    def _local_spill_stats(self) -> Dict[str, int]:
        """Spill usage of the controller's own host (agent-less nodes have
        no heartbeat to ride; same local-sampling contract as cpu/mem)."""
        try:
            from .object_store import spill_stats

            return spill_stats()
        except Exception:
            return {}

    def _local_channel_stats(self) -> Dict[str, int]:
        """Channel-fabric footprint of the controller's own host (same
        local-sampling contract as _local_spill_stats)."""
        try:
            from .object_store import host_channel_stats

            return host_channel_stats()
        except Exception:
            return {}

    async def _h_object_census(self, conn, msg):
        """Cluster object census (`rtpu memory --group-by ...`,
        state.summarize_objects, the dashboard /objects page): the object
        directory (size/tier/node ground truth) joined with every live
        process's ownership shard (owner label, pin/borrow/hold counts,
        optional RTPU_CALLSITE creation sites). Partial-tolerant by
        construction: shards that never answer — SIGKILLed or wedged
        workers — are reported as per-shard error strings while survivors'
        rows still aggregate. The requesting driver ships its OWN shard
        inline in the request (the controller cannot fan out to drivers)."""
        if not flags.get("RTPU_CENSUS"):
            return {"enabled": False, "objects": [], "groups": {},
                    "errors": ["census disabled (RTPU_CENSUS=0)"],
                    "num_objects": 0, "total_bytes": 0}
        timeout = float(msg.get("timeout")
                        or flags.get("RTPU_CENSUS_TIMEOUT_S"))
        _, targets, replies = await self._gather_from_workers(
            "census_dump", timeout)
        shards: List[Dict[str, Any]] = []
        errors: List[str] = []
        for wid in targets:
            shard = replies.get(wid)
            if shard is None:
                errors.append(f"worker {wid[:8]}: no census reply within "
                              f"{timeout:.1f}s (dead or unreachable)")
            elif not isinstance(shard, dict):
                errors.append(f"worker {wid[:8]}: malformed shard "
                              f"({type(shard).__name__})")
            elif shard.get("error"):
                errors.append(f"worker {wid[:8]}: {shard['error']}")
            else:
                shards.append(shard)
        drv = msg.get("shard")
        if isinstance(drv, dict):
            shards.append(drv)
        from .object_store import storage_kind

        now = time.time()
        rows: Dict[str, Dict[str, Any]] = {}
        for oid, loc in self.objects.items():
            rows[oid] = {
                "object_id": oid, "size": int(loc.size or 0),
                "tier": storage_kind(loc), "node_id": loc.node_id or "",
                "owner": "", "local_refs": 0, "borrowers": 0, "holds": 0,
                "pins": 0, "callsite": None,
                "age_s": round(now - self.object_created.get(oid, now), 1)}
        # Broadcast replicas are EXTRA bytes on other hosts: one census row
        # per copy under the "replica" tier, keyed so they never collide
        # with the primary.
        for oid, reps in self.object_replicas.items():
            for nid, rep in reps.items():
                rows[f"{oid}+replica:{nid[:8]}"] = {
                    "object_id": oid, "size": int(rep.size or 0),
                    "tier": "replica", "node_id": nid,
                    "owner": "", "local_refs": 0, "borrowers": 0,
                    "holds": 0, "pins": 0, "callsite": None,
                    "age_s": round(
                        now - self.object_created.get(oid, now), 1)}
        for shard in shards:
            label = str(shard.get("label") or "?")
            for r in shard.get("rows") or ():
                oid = r.get("oid")
                if not oid:
                    continue
                base = rows.get(oid)
                if base is None:
                    # Owned-but-unregistered (inline results, directory
                    # races): the shard row is all we know.
                    base = rows[oid] = {
                        "object_id": oid, "size": 0, "tier": "",
                        "node_id": "", "owner": "", "local_refs": 0,
                        "borrowers": 0, "holds": 0, "pins": 0,
                        "callsite": None, "age_s": 0.0}
                if r.get("owned"):
                    base["owner"] = base["owner"] or label
                    base["local_refs"] = int(r.get("local") or 0)
                    base["borrowers"] = int(r.get("borrowers") or 0)
                    base["holds"] = int(r.get("holds") or 0)
                    base["pins"] = int(r.get("pins") or 0)
                    if r.get("callsite"):
                        base["callsite"] = r["callsite"]
                if not base["size"]:
                    base["size"] = int(r.get("size") or 0)
                if not base["tier"]:
                    base["tier"] = r.get("tier") or ""
        # Owner fallback from the put-path source connection: a census
        # asked for by a DIFFERENT client (the `rtpu memory` CLI, the
        # dashboard) cannot ship the driver's shard, but the directory
        # remembers which connection registered each object — enough to
        # keep driver/worker puts attributed instead of "(unknown)".
        src_label: Dict[int, str] = {}
        for w in self.workers.values():
            if w.conn is not None:
                src_label[id(w.conn)] = f"worker:{w.worker_id[:8]}"
        for dconn in self.driver_conns:
            src_label.setdefault(id(dconn), "driver")
        for r in rows.values():
            if r["owner"]:
                continue
            src = self.object_src.get(r["object_id"])
            if src is not None:
                r["owner"] = src_label.get(id(src), "")
        # Per-tier breakdown inside every grouping: `--group-by owner`
        # still answers "which tier is that owner's 3 GB sitting in?".
        def _agg(key: str) -> Dict[str, Dict[str, Any]]:
            out: Dict[str, Dict[str, Any]] = {}
            for r in rows.values():
                k = r.get(key) or "(unknown)"
                if key == "node_id":
                    k = k[:12] if k != "(unknown)" else k
                g = out.setdefault(k, {"bytes": 0, "count": 0, "tiers": {}})
                g["bytes"] += r["size"]
                g["count"] += 1
                t = r.get("tier") or "(unknown)"
                g["tiers"][t] = g["tiers"].get(t, 0) + r["size"]
            return out

        groups = {"owner": _agg("owner"), "tier": _agg("tier"),
                  "node": _agg("node_id"), "callsite": _agg("callsite")}
        min_size = int(msg.get("min_size") or 0)
        limit = int(msg.get("limit") or 1000)
        detail = sorted((r for r in rows.values() if r["size"] >= min_size),
                        key=lambda r: -r["size"])[:limit]
        arenas = {nid: n.arena_stats for nid, n in self.nodes.items()
                  if n.arena_stats}
        spill = {nid: (n.spill_stats if n.agent_conn is not None
                       else self._local_spill_stats())
                 for nid, n in self.nodes.items() if n.alive}
        total = sum(r["size"] for r in rows.values())
        return {"enabled": True, "objects": detail, "groups": groups,
                "errors": errors, "num_objects": len(rows),
                "total_bytes": total,
                "shards": len(shards), "requested": len(targets) + 1,
                "arenas": arenas, "spill": spill, "t": now}

    # ------------------------------------------------------- leak watchdog

    async def _leak_watchdog_loop(self) -> None:
        """Flag directory objects past RTPU_LEAK_AGE_S whose registering
        connection is gone as OBJECT_LEAK_SUSPECT — once per object (the
        hang watchdog's self-cleaning dedup-set pattern). Only put-path
        objects carry a source connection; everything else is never
        flagged (objects can only be under-reported, never smeared)."""
        poll = float(flags.get("RTPU_LEAK_POLL_S"))
        while True:
            await asyncio.sleep(poll)
            try:
                self._leak_sweep()
            except Exception as e:
                sys.stderr.write(
                    f"[controller] leak sweep failed: {e!r}\n")

    def _leak_sweep(self) -> None:
        age_s = float(flags.get("RTPU_LEAK_AGE_S"))
        now = time.time()
        live = set(self.objects)
        self._leak_reported &= live
        for d in (self.object_created, self.object_src):
            for oid in [o for o in d if o not in live]:
                d.pop(oid, None)
        for oid, src in list(self.object_src.items()):
            if oid in self._leak_reported:
                continue
            created = self.object_created.get(oid)
            if created is None or now - created < age_s:
                continue
            try:
                dead = src is None or src.closed.is_set()
            except Exception:
                dead = True
            if not dead:
                continue
            loc = self.objects.get(oid)
            self._leak_reported.add(oid)
            self.leak_count += 1
            size = int(getattr(loc, "size", 0) or 0)
            self._emit_event(
                "WARNING", "OBJECT_LEAK_SUSPECT",
                f"object {oid[:8]} ({size} bytes) is "
                f"{now - created:.0f}s old and its owning connection is "
                f"closed — suspected leaked ref",
                data={"object_id": oid, "size": size,
                      "age_s": round(now - created, 1)})

    async def _h_subscribe(self, conn, msg):
        self.subs.setdefault(msg["channel"], []).append(conn)
        return {"ok": True}

    async def _h_publish(self, conn, msg):
        """Batched fan-out (reference: src/ray/pubsub/README.md — the
        long-poll publisher coalesces queued messages per subscriber).
        Publishes within one loop iteration append to per-connection
        buffers; ONE flush task per connection drains them as a single
        pubsub_batch frame, so a burst of M messages to S subscribers
        costs S sends instead of M*S."""
        item = {"channel": msg["channel"], "data": msg["data"]}
        for c in list(self.subs.get(msg["channel"], [])):
            buf = self._pubsub_pending.setdefault(id(c), [c, []])
            buf[1].append(item)
            if len(buf[1]) == 1:  # first item: schedule this conn's flush
                asyncio.get_running_loop().create_task(
                    self._flush_pubsub(id(c)))
        return {"ok": True}

    async def _flush_pubsub(self, conn_key: int) -> None:
        buf = self._pubsub_pending.pop(conn_key, None)
        if buf is None:
            return
        c, items = buf
        try:
            if len(items) == 1:
                await c.send({"kind": "pubsub", **items[0]})
            else:
                await c.send({"kind": "pubsub_batch", "items": items})
        except Exception:
            pass

    async def _h_list_state(self, conn, msg):
        """State API backend (reference: python/ray/util/state/api.py:110 —
        list tasks/actors/nodes/workers/objects + task summaries), reading
        the live tables and the bounded task-event history."""
        what = msg["what"]
        limit = int(msg.get("limit", 1000))
        if what == "tasks":
            latest = self._latest_task_events()
            out = [
                {
                    "task_id": tid,
                    "name": ev.get("label"),
                    "state": {"submitted": "PENDING", "running": "RUNNING",
                              "finished": "FINISHED", "failed": "FAILED",
                              "retry": "PENDING", "reconstruct": "PENDING",
                              "actor_restart": "PENDING"}.get(
                                  ev["event"], ev["event"].upper()),
                    "actor_id": ev.get("actor_id"),
                    "worker_id": ev.get("worker_id"),
                    "node_id": ev.get("node_id"),
                    "ts": ev["ts"],
                }
                for tid, ev in latest.items()
            ]
            return out[-limit:]
        if what == "actors":
            return [
                {
                    "actor_id": a.actor_id,
                    "state": a.state.upper(),
                    "name": a.name,
                    "node_id": a.node_id,
                    "worker_id": a.worker_id,
                    "restarts": a.restart_count,
                    # Newest durable checkpoint the controller holds (0 =
                    # none): tests/operators poll this to know a restart
                    # will restore rather than re-run the constructor.
                    "checkpoint_epoch": (a.checkpoint or {}).get("epoch", 0),
                }
                for a in list(self.actors.values())[:limit]
            ]
        if what == "nodes":
            return (await self._h_cluster_state(conn, msg))["nodes"][:limit]
        if what == "workers":
            return [
                {
                    "worker_id": w.worker_id,
                    "node_id": w.node_id,
                    "state": w.state,
                    "current_task": w.current_task,
                    "tpu_capable": w.tpu_capable,
                    "chip_ids": list(w.chip_ids),
                    # Joins the agent heartbeat proc_stats (cpu/rss by pid).
                    "pid": w.pid,
                }
                for w in list(self.workers.values())[:limit]
            ]
        if what == "objects":
            from .object_store import storage_kind

            return [
                {
                    "object_id": oid,
                    "size": loc.size,
                    "backend": storage_kind(loc),
                    "node_id": loc.node_id,
                    "is_error": loc.is_error,
                }
                for oid, loc in list(self.objects.items())[:limit]
            ]
        if what == "placement_groups":
            return [
                {
                    "placement_group_id": pg.pg_id,
                    "name": pg.name,
                    "state": pg.state.upper(),
                    "strategy": pg.strategy,
                    "bundles": [
                        {"bundle_index": i, "resources": dict(b.resources),
                         "node_id": b.node_id}
                        for i, b in enumerate(pg.bundles)
                    ],
                }
                for pg in list(self.pgs.values())[:limit]
            ]
        if what == "dags":
            return [
                dict({
                    "dag_id": d["dag_id"],
                    "stages": [dict(s) for s in d.get("stages", ())],
                    "edges": dict(d.get("edges", {})),
                    "depth": d.get("depth", 0),
                    "since": d.get("since", 0.0),
                    "recoveries": d.get("recoveries", 0),
                    "recovering": d.get("recovering", False),
                    "last_recovery_s": d.get("last_recovery_s"),
                    "last_cause": d.get("last_cause"),
                }, **self._dag_rollup(d))
                for d in list(self.compiled_dags.values())[:limit]
            ]
        if what == "summary":
            counts: Dict[str, Dict[str, int]] = {}
            for ev in self._latest_task_events().values():
                row = counts.setdefault(ev.get("label") or "?", {})
                row[ev["event"]] = row.get(ev["event"], 0) + 1
            return counts
        if what == "summary_breakdown":
            # Per-label per-phase latency percentiles (reference: the
            # `ray summary tasks` timing columns the GcsTaskManager feeds).
            return self._phase_breakdown()
        raise ValueError(f"unknown state listing {what!r}")

    def _dag_rollup(self, d: dict) -> Dict[str, Any]:
        """Channel-meter rollup for one compiled DAG, merged into its
        `list_state("dags")` row: latest per-stage busy fractions and
        per-edge ring stats from the app-metric store (gauges keep last,
        counters accumulate — see _h_metric_update), steps/s from the
        TSDB rate, and THE bottleneck verdict
        (dag.meter.attribute_bottleneck). All fields degrade to empty /
        None when RTPU_DAG_METER=0 or nothing has sampled yet."""
        short = d["dag_id"][:12]
        busy: Dict[str, Dict[str, float]] = {}
        fam = self.app_metrics.get("rtpu_dag_stage_busy_fraction")
        for tags, v in (fam or {}).get("data", {}).items():
            t = dict(tags)
            if t.get("dag") != short:
                continue
            busy.setdefault(t.get("stage", "?"), {})[
                t.get("phase", "?")] = float(v)
        edges: Dict[str, Dict[str, float]] = {}
        for name, field in (
                ("rtpu_dag_edge_items_total", "items"),
                ("rtpu_dag_edge_bytes_total", "bytes"),
                ("rtpu_dag_edge_occupancy", "occupancy"),
                ("rtpu_dag_edge_lag_seqs", "lag"),
                ("rtpu_dag_edge_blocked_fraction", "blocked_fraction")):
            fam = self.app_metrics.get(name)
            for tags, v in (fam or {}).get("data", {}).items():
                t = dict(tags)
                if t.get("dag") != short:
                    continue
                edges.setdefault(t.get("edge", "?"), {})[field] = float(v)
        steps_per_s = None
        if self.tsdb is not None:
            try:
                # The fastest stage's rate IS the pipeline's steady-state
                # throughput floor-to-ceiling band top; during warmup /
                # recovery slower stages would underreport it.
                for ser in self.tsdb.query(
                        name="rtpu_dag_stage_steps_total",
                        tags={"dag": short}):
                    pts = ser.get("points") or ()
                    if pts:
                        steps_per_s = max(steps_per_s or 0.0,
                                          float(pts[-1][1]))
            except Exception:
                pass
        bottleneck = None
        if busy:
            from ray_tpu.dag import meter as dag_meter
            bottleneck = dag_meter.attribute_bottleneck(busy)
        return {"stage_busy": busy, "edge_stats": edges,
                "steps_per_s": steps_per_s, "bottleneck": bottleneck}

    def _latest_task_events(self) -> Dict[str, Dict[str, Any]]:
        """task_id -> its most recent LIFECYCLE event (events append in
        order). Flight-recorder "phases" entries are annotations riding the
        same ring — they must not shadow a task's state."""
        latest: Dict[str, Dict[str, Any]] = {}
        for ev in self.task_events:
            if ev["event"] != "phases":
                latest[ev["task_id"]] = ev
        return latest

    async def _h_autoscaler_state(self, conn, msg):
        """Demand/usage snapshot for the autoscaler (reference: the load
        metrics the monitor feeds StandardAutoscaler,
        autoscaler/_private/load_metrics.py)."""
        demands = []
        for tid in self.pending_queue.ids():
            spec = self.tasks.get(tid)
            if spec is not None:
                demands.append(dict(spec.get("resources", {})))
        # Pending placement-group bundles are demand too (reference:
        # load_metrics pending_placement_groups) — the GCE slice loop
        # scales up on a TPU-{type}-head bundle before any task exists.
        for pg in self.pgs.values():
            if pg.state == "pending":
                for b in pg.bundles:
                    if b.node_id is None:
                        demands.append(dict(b.resources))
        nodes = []
        for n in self.nodes.values():
            busy = False
            for wid in n.workers:
                w = self.workers.get(wid)
                if w is not None and (w.state != "idle" or w.actor_ids):
                    busy = True
                    break
            nodes.append({
                "node_id": n.node_id,
                "alive": n.alive,
                "state": self._node_state(n),
                "is_agent": n.agent_conn is not None,
                "busy": busy,
                "resources": dict(n.resources),
                "available": dict(n.available),
                "labels": dict(n.labels),
            })
        return {"demands": demands, "nodes": nodes}

    # ------------------------------------------------------------- node drain
    # Reference: the DrainNode protocol (autoscaler.proto:334 DrainNode,
    # node_manager.proto:391 DrainRaylet): a node leaves gracefully —
    # scheduling stops, hosted restartable actors migrate (with their state),
    # running tasks get a grace window then re-queue with the preempted
    # flag, sole-copy objects are re-replicated, and only then do the
    # chips/capacity leave the cluster.

    @staticmethod
    def _node_state(node: NodeInfo) -> str:
        if node.drained:
            return "drained"
        if not node.alive:
            return "dead"
        if node.draining:
            return "draining"
        if node.suspect:
            return "suspect"
        return "alive"

    @staticmethod
    def _schedulable(node: NodeInfo) -> bool:
        """May NEW work land on this node? Draining nodes are leaving;
        suspect nodes (heartbeat-silent, possibly partitioned) pause
        placements so a heal rejoins without double-scheduled work."""
        return node.alive and not node.draining and not node.suspect

    async def _h_drain_node(self, conn, msg):
        """Start (or report) a node drain. Idempotent: re-draining a
        draining node returns its current state; deadlines only shrink."""
        nid = msg.get("node_id") or ""
        node = self.nodes.get(nid)
        if node is None:
            # Prefix match so operators can pass the short id `rtpu status`
            # prints.
            matches = [n for n in self.nodes.values()
                       if n.node_id.startswith(nid)] if nid else []
            if len(matches) != 1:
                return {"ok": False, "error": f"unknown node {nid!r}"}
            node = matches[0]
        if node.drained or not node.alive:
            return {"ok": True, "node_id": node.node_id,
                    "state": self._node_state(node)}
        if node.labels.get("head") == "1":
            return {"ok": False, "error": "refusing to drain the head node"}
        reason = msg.get("reason") or "manual"
        deadline_s = msg.get("deadline_s")
        if deadline_s is None:
            deadline_s = flags.get("RTPU_DRAIN_DEADLINE_S")
        deadline = time.time() + max(0.0, float(deadline_s))
        if node.draining:
            node.drain_deadline = min(node.drain_deadline, deadline)
            st = self.pending_drains.get(node.node_id)
            if st is not None and node.drain_deadline < st["deadline"]:
                st["deadline"] = node.drain_deadline
                self._state_dirty = True
            return {"ok": True, "node_id": node.node_id, "state": "draining"}
        node.draining = True
        node.drain_reason = reason
        node.drain_deadline = deadline
        self.drain_counts[reason] = self.drain_counts.get(reason, 0) + 1
        self.pending_drains[node.node_id] = {"reason": reason,
                                             "deadline": deadline}
        self._state_dirty = True
        self._export_event("NODE", {"node_id": node.node_id,
                                    "event": "draining", "reason": reason,
                                    "ts": time.time()})
        self._emit_event(
            "WARNING", "NODE_DRAINING",
            f"node {node.node_id[:8]} draining (reason={reason}, "
            f"deadline in {max(0.0, deadline - time.time()):.1f}s)",
            node_id=node.node_id,
            data={"reason": reason, "deadline": deadline})
        self._arm_drain(node)
        return {"ok": True, "node_id": node.node_id, "state": "draining"}

    def _arm_drain(self, node: NodeInfo) -> None:
        st = self.pending_drains.get(node.node_id)
        if st is None:
            return
        node.draining = True
        node.drain_reason = st.get("reason", "manual")
        node.drain_deadline = float(st.get("deadline", 0.0))
        task = self._drain_tasks.get(node.node_id)
        if task is not None and not task.done():
            return
        self._drain_tasks[node.node_id] = (
            asyncio.get_running_loop().create_task(self._drain_node(node)))

    async def _drain_node(self, node: NodeInfo) -> None:
        try:
            # 1. Proactively migrate restartable/detached actors: their
            # state is snapshotted on the still-healthy worker and restored
            # on the new placement — a planned departure is a move, not a
            # crash-recovery (restart_count untouched).
            for actor in list(self.actors.values()):
                if (actor.node_id == node.node_id
                        and actor.state == "alive"
                        and actor.creation_spec is not None
                        and (actor.detached
                             or actor.restart_count < actor.max_restarts)):
                    await self._migrate_actor(actor, node)
            # 2. Grace window: let running tasks (and direct leases) finish.
            while time.time() < node.drain_deadline:
                if node.node_id not in self.pending_drains:
                    return  # node died mid-drain; death path took over
                if self._node_quiesced(node):
                    break
                await asyncio.sleep(0.1)
            # 3. Re-replicate objects whose only copy lives on the draining
            # host BEFORE the node (and its chips) leave the free pool.
            await self._evacuate_objects(node)
        except Exception as e:  # pragma: no cover — drain must terminate
            sys.stderr.write(f"[controller] drain error on "
                             f"{node.node_id[:8]}: {e!r}\n")
        await self._finish_drain(node)

    def _node_quiesced(self, node: NodeInfo) -> bool:
        for wid in node.workers:
            w = self.workers.get(wid)
            if w is not None and (w.current_task or w.state == "leased"):
                return False
        for lease in self._leases.values():
            if lease["node_id"] == node.node_id:
                return False
        for actor in self.actors.values():
            if actor.node_id == node.node_id and actor.state in (
                    "alive", "pending"):
                return False
        return True

    async def _migrate_actor(self, actor: ActorInfo, node: NodeInfo) -> None:
        """Move one actor off a draining node: snapshot its instance state
        on the hosting worker (best-effort; falls back to a fresh
        constructor run), retire the old instance, and re-queue the
        creation spec — the scheduler places it on a non-draining node.
        Unlike _maybe_restart_actor this consumes NO restart budget and
        fails no buffered calls (in-flight calls complete on the old
        instance before the snapshot closure reaches the mailbox)."""
        spec = actor.creation_spec
        if spec is None:
            return
        actor.state = "restarting"  # new controller-path calls buffer now
        self._export_event("ACTOR", {"actor_id": actor.actor_id,
                                     "event": "migrating",
                                     "node_id": node.node_id,
                                     "ts": time.time()})
        self._emit_event(
            "INFO", "ACTOR_MIGRATING",
            f"actor {actor.name or actor.actor_id[:8]} migrating off "
            f"draining node {node.node_id[:8]}",
            actor_id=actor.actor_id, node_id=node.node_id,
            data={"name": actor.name,
                  "reason": node.drain_reason})
        from .job_manager import SUPERVISOR_PREFIX

        if (actor.name or "").startswith(SUPERVISOR_PREFIX):
            # The supervisor instance migrates, its entrypoint subprocess
            # cannot: the restored instance relaunches, and a planned
            # drain departure bills no attempt budget (PR 4/16 rule).
            self.jobs.note_supervisor_migrating(actor, node)
        w = self.workers.get(actor.worker_id or "")
        blob = None
        if w is not None:
            try:
                res = await w.conn.request(
                    {"kind": "snapshot_actor", "actor_id": actor.actor_id},
                    timeout=10)
                if isinstance(res, dict):
                    blob = res.get("blob")
            except Exception:
                blob = None
            # Retire the old instance so post-snapshot mutations can't be
            # silently lost; a direct call racing this window fails with
            # ActorDiedError (at-most-once actor-call semantics).
            try:
                await w.conn.send({"kind": "drop_actor",
                                   "actor_id": actor.actor_id})
            except Exception:
                pass
            w.actor_ids.discard(actor.actor_id)
            if not w.actor_ids and w.state == "actor":
                w.state = "idle"
        if actor.reserved:
            actor.reserved = False
            self._release_reservation(actor.resources, node, actor.pg)
        actor.worker_id = None
        actor.node_id = None
        if blob is not None:
            spec["state_blob"] = blob
        else:
            spec.pop("state_blob", None)
        spec["state"] = "pending"
        spec.pop("sched_node", None)
        self.tasks[spec["task_id"]] = spec
        self.pending_queue.append(spec)
        self._record_task_event(spec, "actor_migrate")
        if actor.detached:
            self._state_dirty = True
        self._wake_scheduler()

    async def _evacuate_objects(self, node: NodeInfo) -> None:
        """Pull the raw bytes of every object whose only copy lives on the
        draining host and re-home them in the head's spill directory (the
        same byte layout spilling uses, so every read path already
        understands the rewritten location). Objects that cannot be pulled
        fall back to lineage reconstruction in the node-death path."""
        if node.agent_conn is None or not node.host_id \
                or node.host_id == self.host_id:
            return  # bytes live on the head host and survive worker death
        head = next((n for n in self.nodes.values()
                     if n.agent_conn is None and n.alive), None)
        from .object_store import spill_dir

        CHUNK = 4 * 1024 * 1024
        for oid, loc in list(self.objects.items()):
            if (loc.inline is not None or loc.is_error
                    or loc.host_id != node.host_id):
                continue
            # A broadcast replica on a surviving host already re-homes the
            # bytes: promote it instead of pulling them to head spill.
            reps = self.object_replicas.get(oid) or {}
            rep = next((r for nid, r in reps.items()
                        if nid != node.node_id and r.host_id != node.host_id
                        and self._host_alive(r.host_id)), None)
            if rep is not None:
                self.objects[oid] = rep
                continue
            path = os.path.join(spill_dir(), f"{oid[:32]}.bin")
            try:
                with open(path, "wb") as f:
                    off = 0
                    while off < loc.size:
                        n = min(CHUNK, loc.size - off)
                        raw = await node.agent_conn.request(
                            {"kind": "pull_chunk", "loc": loc,
                             "offset": off, "length": n}, timeout=30)
                        if not raw:
                            raise ConnectionError("short pull")
                        f.write(raw)
                        off += len(raw)
            except Exception:
                try:
                    os.unlink(path)
                except OSError:
                    pass
                continue  # node-death reconstruction is the fallback
            if self.objects.get(oid) is not loc:
                try:
                    os.unlink(path)
                except OSError:
                    pass
                continue  # freed/replaced while pulling
            import dataclasses as _dc

            self.objects[oid] = _dc.replace(
                loc, arena=None, arena_oid=0, shm_name=None,
                spill_path=path, host_id=self.host_id,
                node_id=head.node_id if head else None)

    async def _finish_drain(self, node: NodeInfo) -> None:
        """Terminal step: the grace window closed (or the node quiesced) —
        kill remaining workers, release the node, run the death path. The
        drained flag routes every resulting task/actor failure through the
        preempted (budget-free) retry paths."""
        self._drain_tasks.pop(node.node_id, None)
        if node.node_id not in self.pending_drains:
            return  # death path already cleaned up mid-drain
        node.drained = True
        self.pending_drains.pop(node.node_id, None)
        self._state_dirty = True
        self._export_event("NODE", {"node_id": node.node_id,
                                    "event": "drained",
                                    "reason": node.drain_reason,
                                    "ts": time.time()})
        self._emit_event(
            "INFO", "NODE_DRAINED",
            f"node {node.node_id[:8]} drained "
            f"(reason={node.drain_reason or 'manual'})",
            node_id=node.node_id, data={"reason": node.drain_reason})
        for wid in list(node.workers):
            w = self.workers.get(wid)
            if w is not None:
                # Graceful stop + proc terminate (local spawns); agent
                # spawns are reaped by their agent's shutdown below.
                await self._shutdown_worker(w)
        if node.agent_conn is not None:
            # The agent kills its workers and exits; its connection drop
            # runs _on_node_death, which sees node.drained.
            try:
                await node.agent_conn.send({"kind": "shutdown"})
            except Exception:
                pass
        else:
            await self._on_node_death(node)
        self._wake_scheduler()

    async def _h_drop_node(self, conn, msg):
        """Legacy immediate scale-down of an agent node — now a
        zero-deadline drain, so even the abrupt path migrates actors and
        re-queues work with the preempted flag instead of crashing it."""
        node = self.nodes.get(msg["node_id"])
        if node is None or node.agent_conn is None:
            return {"ok": False}
        return await self._h_drain_node(conn, {
            "node_id": node.node_id, "reason": msg.get("reason") or "manual",
            "deadline_s": 0.0})

    async def _h_task_events(self, conn, msg):
        """Raw event stream for the chrome-trace timeline export
        (reference: GlobalState.chrome_tracing_dump, _private/state.py:434)."""
        return list(self.task_events)

    async def _h_task_phase_events(self, conn, msg):
        """Flight-recorder batch from a worker (reference: TaskEventBuffer
        batches landing in GcsTaskManager): merge phase events into the
        task-event ring (keyed by task_id, consumed by timeline()), fold
        each phase duration into its derived Prometheus histogram, and
        collect shipped tracing spans for get_cluster_spans()."""
        import bisect

        hists: Dict[Tuple[str, str], dict] = {}  # (metric,label) -> state
        for ev in msg.get("events", ()):
            entry = {
                "task_id": ev.get("task_id"),
                "label": ev.get("label"),
                "actor_id": ev.get("actor_id"),
                "event": "phases",
                "ts": ev.get("end_ts"),
                "worker_id": ev.get("worker_id"),
                "node_id": ev.get("node_id"),
                "start_ts": ev.get("start_ts"),
                "outcome": ev.get("outcome"),
                "phases": dict(ev.get("phases") or {}),
            }
            self.task_events.append(entry)
            self._export_event("TASK_PHASES", entry)
            label = entry["label"] or "?"
            for key, mname in PHASE_METRIC_NAMES.items():
                v = entry["phases"].get(key)
                if v is None:
                    continue
                # Resolve each (metric, label) histogram once per shipped
                # batch, not once per observation — a worker's flush lands
                # hundreds of same-label events at once and this handler
                # runs on the controller's hot thread.
                hk = (mname, label)
                hist = hists.get(hk)
                if hist is None:
                    st = self.app_metrics.setdefault(
                        mname, {"type": "histogram",
                                "help": PHASE_METRIC_HELP.get(mname, ""),
                                "boundaries": list(PHASE_BOUNDARIES),
                                "data": {}})
                    h = st["data"].setdefault(
                        (("label", label),),
                        {"buckets": [0] * (len(st["boundaries"]) + 1),
                         "sum": 0.0, "count": 0})
                    hist = hists[hk] = {"bounds": st["boundaries"], "h": h}
                v = float(v)
                h = hist["h"]
                bounds = hist["bounds"]
                h["buckets"][min(bisect.bisect_left(bounds, v),
                                 len(bounds))] += 1
                h["sum"] += v
                h["count"] += 1
        for d in msg.get("spans", ()):
            self.cluster_spans.append(d)
        return {"ok": True}

    def _observe_phase(self, name: str, label: str, value: float) -> None:
        """One observation into a derived phase histogram; stored in
        app_metrics so the /metrics exposition and grafana generation pick
        it up like any user Histogram."""
        import bisect

        st = self.app_metrics.setdefault(
            name, {"type": "histogram",
                   "help": PHASE_METRIC_HELP.get(name, ""),
                   "boundaries": list(PHASE_BOUNDARIES), "data": {}})
        tags = (("label", label),)
        h = st["data"].setdefault(
            tags, {"buckets": [0] * (len(st["boundaries"]) + 1),
                   "sum": 0.0, "count": 0})
        i = min(bisect.bisect_left(st["boundaries"], value),
                len(st["boundaries"]))
        h["buckets"][i] += 1
        h["sum"] += value
        h["count"] += 1

    def _phase_breakdown(self) -> Dict[str, Dict[str, Dict[str, float]]]:
        """label -> phase -> {count, mean, p50, p99} from the derived
        histograms (state.summarize_tasks(breakdown=True) backend)."""
        out: Dict[str, Dict[str, Dict[str, float]]] = {}
        for key, mname in PHASE_METRIC_NAMES.items():
            st = self.app_metrics.get(mname)
            if not st:
                continue
            bounds = st["boundaries"]
            for tags, h in st["data"].items():
                label = dict(tags).get("label", "?")
                if not h.get("count"):
                    continue
                out.setdefault(label, {})[key] = {
                    "count": h["count"],
                    "mean": h["sum"] / h["count"],
                    "p50": _hist_quantile(bounds, h, 0.5),
                    "p99": _hist_quantile(bounds, h, 0.99),
                }
        return out

    async def _h_get_spans(self, conn, msg):
        """Cluster-wide finished tracing spans (util/tracing.py
        get_cluster_spans): spans shipped by worker flight recorders,
        optionally filtered by trace_id."""
        trace_id = msg.get("trace_id")
        spans = list(self.cluster_spans)
        if trace_id:
            spans = [s for s in spans if s.get("trace_id") == trace_id]
        limit = int(msg.get("limit", 10000))
        return spans[-limit:]

    # ------------------------------------------------ serve request ledger

    def _serve_ledger_row(self, request_id: str) -> Dict[str, Any]:
        """Fetch-or-create one ledger row. Rows created by an early span
        (record still in flight on another process) start "inflight"."""
        row = self.serve_ledger.get(request_id)
        if row is None:
            row = self.serve_ledger[request_id] = {
                "request_id": request_id, "trace_id": "",
                "deployment": "", "method": "", "proto": "",
                "status": "inflight", "error": "", "start_ts": None,
                "wall_s": None, "slo_miss": False, "retained": False,
                "spans": [],
            }
            self._serve_ledger_evict()
        return row

    def _serve_ledger_evict(self) -> None:
        """LRU with slow-request auto-capture: oldest UNFLAGGED row goes
        first; retained rows (SLO miss / shed / deadline) are reclaimed
        only once every unflagged row is gone."""
        cap = max(16, int(flags.get("RTPU_SERVE_LEDGER_MAX")))
        while len(self.serve_ledger) > cap:
            victim = None
            for rid, row in self.serve_ledger.items():
                if not row.get("retained"):
                    victim = rid
                    break
            if victim is None:  # every row is retained: evict oldest
                self.serve_ledger.popitem(last=False)
            else:
                self.serve_ledger.pop(victim, None)

    async def _h_serve_request_events(self, conn, msg):
        """Ingest one shipped batch of serve hop spans + ledger records
        (serve/trace.py _Shipper). Spans fold into their request's row
        (bounded per row); serve.stream spans contribute the token stats;
        the record sets the terminal fields and the retention flag."""
        for d in msg.get("spans", ()):
            rid = d.get("request_id")
            if not rid:
                continue
            row = self._serve_ledger_row(rid)
            if not row["trace_id"]:
                row["trace_id"] = d.get("trace_id") or ""
            if len(row["spans"]) < 128:
                row["spans"].append(d)
            if d.get("name") == "serve.stream":
                a = d.get("attributes") or {}
                for k in ("tokens", "ttft_s", "itl_mean_s", "itl_p50_s",
                          "itl_p99_s", "itl_max_s", "abort_cause",
                          "sent"):
                    if a.get(k) not in (None, ""):
                        row[k] = a[k]
        for r in msg.get("records", ()):
            rid = r.get("request_id")
            if not rid:
                continue
            row = self._serve_ledger_row(rid)
            row.update({k: r[k] for k in
                        ("trace_id", "deployment", "method", "proto",
                         "status", "error", "start_ts", "wall_s",
                         "slo_miss") if k in r})
            row["retained"] = bool(
                r.get("slo_miss")
                or r.get("status") in ("shed", "deadline"))
            self.serve_ledger.move_to_end(rid)
        return {"ok": True}

    async def _h_serve_requests(self, conn, msg):
        """Query the request ledger (state.list_serve_requests / `rtpu
        serve requests` / the dashboard page). Filters: ``model``
        (deployment prefix), ``status``, ``min_latency_s``, ``since``
        (start_ts lower bound), ``request_id`` (prefix — includes the
        per-hop spans for the trace waterfall). Newest first."""
        model = msg.get("model")
        status = msg.get("status")
        min_lat = msg.get("min_latency_s")
        since = msg.get("since")
        rid_pfx = msg.get("request_id")
        with_spans = bool(msg.get("with_spans") or rid_pfx)
        limit = int(msg.get("limit", 100))
        out = []
        for row in reversed(self.serve_ledger.values()):
            if model and not (row.get("deployment") or "").startswith(
                    model):
                continue
            if status and row.get("status") != status:
                continue
            if min_lat is not None and (
                    row.get("wall_s") is None
                    or row["wall_s"] < float(min_lat)):
                continue
            if since is not None and (
                    row.get("start_ts") is None
                    or row["start_ts"] < float(since)):
                continue
            if rid_pfx and not row["request_id"].startswith(rid_pfx):
                continue
            r = dict(row)
            if not with_spans:
                r.pop("spans", None)
                r["n_spans"] = len(row.get("spans") or ())
            out.append(r)
            if len(out) >= limit:
                break
        return out

    # --------------------------------------------------- cluster event log
    # Reference: the cluster-event framework (`ray list cluster-events`,
    # gcs_ray_event_converter.h, the dashboard event feed) — lifecycle
    # transitions as structured, filterable, followable records.

    def _emit_event(self, severity: str, kind: str, message: str,
                    **entities) -> None:
        """One controller-side cluster event (no-op when RTPU_EVENTS=0)."""
        if not flags.get("RTPU_EVENTS"):
            return
        try:
            self.events.emit(severity, kind, message, **entities)
        except Exception:
            pass  # the event feed must never hurt the control plane

    async def _h_get_events(self, conn, msg):
        """Filtered (and optionally long-polled) read of the cluster event
        ring: severity is a minimum level, kinds match exactly, entity ids
        match on prefix, `after_seq` is the follow cursor. Returns
        {events, seq} where seq is the cursor for the next follow poll."""
        kinds = msg.get("kinds")
        if isinstance(kinds, str):
            kinds = [kinds]
        sel = dict(
            severity=msg.get("severity"), kinds=kinds,
            task_id=msg.get("task_id"), actor_id=msg.get("actor_id"),
            node_id=msg.get("node_id"), worker_id=msg.get("worker_id"),
            since=msg.get("since"), after_seq=msg.get("after_seq"),
            limit=int(msg.get("limit", 1000)))
        evs = self.events.query(**sel)
        wait_s = float(msg.get("wait_s") or 0)
        if not evs and wait_s > 0:
            await self.events.wait_for_new(wait_s)
            evs = self.events.query(**sel)
        return {"events": evs, "seq": self.events.seq}

    async def _h_cluster_events(self, conn, msg):
        """Batched events shipped by workers/drivers (events._Shipper) and
        host agents (heartbeat-path flush) — merged into the same ring the
        controller's own emit sites feed."""
        if flags.get("RTPU_EVENTS"):
            for ev in msg.get("events", ()):
                if isinstance(ev, dict) and ev.get("kind"):
                    self.events.append(dict(ev))
                    if ev["kind"] == "SLOW_PHASE":
                        tracing.ingest_slow_event(ev)
        return {"ok": True}

    async def _h_phase_table(self, conn, msg):
        """Host phases (util/tracing.py phase_table / slow_phases) of this
        process and, with `workers`, of every live worker (the stack_dump
        fan-out: partial results, never an error)."""
        out = {"controller": {"table": tracing.phase_table(),
                              "slow": tracing.slow_phases()}, "workers": {}}
        if msg.get("workers"):
            _, _, out["workers"] = await self._gather_from_workers(
                "phase_dump", float(msg.get("timeout", 2.0)))
        return out

    # ------------------------------------------------- hang/straggler watchdog
    # Reference failure mode (LlamaRL): at scale the dominant outage is a
    # SILENTLY hung step — one straggler blocking a collective. The
    # controller already derives per-label exec-latency histograms from the
    # flight recorder (PR 2); this loop closes the loop by using them to
    # DETECT anomalies: any running task older than
    # max(RTPU_HANG_MIN_S, RTPU_HANG_P99_FACTOR x label-p99) is flagged,
    # and the existing stack_dump worker RPC fires automatically so the
    # event carries every thread's stack — a hung collective shows all
    # members blocked at the same frame without anyone ssh'ing anywhere.

    async def _hang_watchdog_loop(self) -> None:
        while True:
            await asyncio.sleep(flags.get("RTPU_HANG_POLL_S"))
            try:
                await self._hang_sweep()
            except Exception as e:  # pragma: no cover — keep watching
                sys.stderr.write(f"[controller] hang watchdog error: "
                                 f"{e!r}\n")

    def _label_exec_p99(self, label: str) -> Tuple[float, int]:
        """(p99 seconds, observation count) of the label's exec-latency
        histogram — the PR 2 flight-recorder rtpu_task_exec_s series."""
        st = self.app_metrics.get(PHASE_METRIC_NAMES["exec_s"])
        if not st:
            return 0.0, 0
        h = st["data"].get((("label", label),))
        if not h or not h.get("count"):
            return 0.0, 0
        return _hist_quantile(st["boundaries"], h, 0.99), int(h["count"])

    def _hang_threshold(self, label: str) -> Tuple[float, bool]:
        """(threshold seconds, has_history): the cutoff a running task of
        this label may age to before it is flagged. With label history the
        task is a STRAGGLER (slow relative to its peers); without any
        completions to compare against it is simply HUNG."""
        floor = float(flags.get("RTPU_HANG_MIN_S"))
        p99, count = self._label_exec_p99(label)
        if count >= 5 and p99 > 0:
            return max(floor, float(flags.get("RTPU_HANG_P99_FACTOR"))
                       * p99), True
        return floor, False

    async def _hang_sweep(self) -> None:
        now = time.time()
        # __dispatch_ts exists exactly while a spec is out on a worker:
        # stamped at dispatch, popped on every re-queue path.
        running = [
            spec for spec in list(self.tasks.values())
            if spec.get("__dispatch_ts")
        ]
        live = {s["task_id"] for s in running}
        # De-dup set self-cleans: ids of finished/retired tasks drop out,
        # so a task that re-queues (retry) can be flagged again.
        self._hang_reported &= live
        for spec in running:
            tid = spec["task_id"]
            if tid in self._hang_reported:
                continue
            age = now - float(spec["__dispatch_ts"])
            label = spec.get("label") or "?"
            threshold, has_history = self._hang_threshold(label)
            if age < threshold:
                continue
            self._hang_reported.add(tid)
            w = self._executing_worker(spec)
            stack = ""
            if w is not None:
                stack = await self._stack_dump_worker(w)
            kind = "TASK_STRAGGLER" if has_history else "TASK_HUNG"
            what = ("actor call (mailbox stalled)"
                    if spec.get("actor_id") else "task")
            self._emit_event(
                "WARNING" if has_history else "ERROR", kind,
                f"{what} {label!r} ({tid[:8]}) has been running "
                f"{age:.1f}s on worker "
                f"{(w.worker_id[:8] if w else '?')} / node "
                f"{(w.node_id[:8] if w else '?')} "
                f"(threshold {threshold:.1f}s"
                + (f", label p99-based" if has_history else "")
                + "); all-thread stacks attached",
                task_id=tid, actor_id=spec.get("actor_id"),
                worker_id=w.worker_id if w else None,
                node_id=w.node_id if w else spec.get("sched_node"),
                data={"age_s": age, "threshold_s": threshold,
                      "label": label, "stack": stack})

    def _executing_worker(self, spec: Dict[str, Any]) -> Optional[WorkerInfo]:
        aid = spec.get("actor_id")
        if aid and not spec.get("is_actor_creation"):
            actor = self.actors.get(aid)
            if actor is not None:
                return self.workers.get(actor.worker_id or "")
            return None
        tid = spec["task_id"]
        for w in self.workers.values():
            if w.current_task == tid or (spec.get("is_actor_creation")
                                         and aid in w.actor_ids):
                return w
        return None

    async def _stack_dump_worker(self, w: WorkerInfo,
                                 timeout: float = 3.0) -> str:
        """Targeted stack_dump on ONE worker (the fan-out variant is
        _h_profile_workers); same partial-result contract — a worker stuck
        in native code misses the window and the event ships without the
        stack rather than never."""
        req_id = uuid.uuid4().hex[:12]
        self._profiles[req_id] = {}
        try:
            await w.conn.send({"kind": "stack_dump", "req_id": req_id})
        except Exception:
            self._profiles.pop(req_id, None)
            return ""
        deadline = time.monotonic() + timeout
        while (not self._profiles.get(req_id)
               and time.monotonic() < deadline):
            await asyncio.sleep(0.05)
        return (self._profiles.pop(req_id, None) or {}).get(w.worker_id, "")

    def _metrics_families(self) -> Dict[str, Dict[str, Any]]:
        """Every exportable metric family, in exposition order:
        {name: {"type", "help", "boundaries", "data": {tags_tuple: v}}}.
        Single source for the Prometheus text endpoint AND the telemetry
        ring (core/telemetry.py samples this each step), so history
        covers exactly what /metrics shows."""
        def fam(name: str, data: Dict) -> Dict[str, Any]:
            mtype, help_ = CORE_METRIC_META[name]
            return {"type": mtype, "help": help_, "boundaries": [],
                    "data": data}

        families: Dict[str, Dict[str, Any]] = {}
        counts: Dict[str, int] = {}
        for ev in self._latest_task_events().values():
            counts[ev["event"]] = counts.get(ev["event"], 0) + 1
        # Gauge, not counter: "tasks currently in state X" over a bounded
        # event window goes down on transitions/eviction, which would
        # break Prometheus rate() on a counter type.
        families["rtpu_tasks"] = fam("rtpu_tasks", {
            (("state", s),): n for s, n in counts.items()})
        families["rtpu_pending_tasks"] = fam(
            "rtpu_pending_tasks", {(): len(self.pending_queue)})
        families["rtpu_workers"] = fam("rtpu_workers",
                                       {(): len(self.workers)})
        families["rtpu_actors"] = fam("rtpu_actors",
                                      {(): len(self.actors)})
        families["rtpu_nodes_alive"] = fam("rtpu_nodes_alive", {
            (): sum(1 for n in self.nodes.values() if n.alive)})
        families["rtpu_objects"] = fam("rtpu_objects",
                                       {(): len(self.objects)})
        node_states: Dict[str, int] = {}
        for n in self.nodes.values():
            st = self._node_state(n)
            node_states[st] = node_states.get(st, 0) + 1
        families["rtpu_nodes"] = fam("rtpu_nodes", {
            (("state", s),): c for s, c in node_states.items()})
        families["rtpu_node_drains_total"] = fam(
            "rtpu_node_drains_total",
            {(("reason", r),): c for r, c in self.drain_counts.items()})
        families["rtpu_uptime_seconds"] = fam(
            "rtpu_uptime_seconds",
            {(): round(time.time() - self.start_time, 1)})
        families["rtpu_objects_spilled_total"] = fam(
            "rtpu_objects_spilled_total", {(): self.spilled_count})
        # Broadcast byte accounting: 'source' is what left the origin
        # host (~one object size per broadcast regardless of fan-out),
        # 'hop' is the sum received across all chain hops.
        families["rtpu_broadcast_bytes_total"] = fam(
            "rtpu_broadcast_bytes_total",
            {(("role", "source"),): self.broadcast_bytes["source"],
             (("role", "hop"),): self.broadcast_bytes["hop"]})
        families["rtpu_object_replicas"] = fam(
            "rtpu_object_replicas",
            {(): sum(len(r) for r in self.object_replicas.values())})
        families["rtpu_actor_checkpoints_total"] = fam(
            "rtpu_actor_checkpoints_total", {(): self.ckpt_stats["count"]})
        families["rtpu_actor_checkpoint_bytes"] = fam(
            "rtpu_actor_checkpoint_bytes", {(): self.ckpt_stats["bytes"]})
        families["rtpu_leases_active"] = fam("rtpu_leases_active",
                                             {(): len(self._leases)})
        families["rtpu_lease_events_total"] = fam(
            "rtpu_lease_events_total",
            {(("event", k),): v for k, v in self.lease_stats.items()})
        if self._arena is not None:
            st = self._arena.stats()
            families["rtpu_arena_used_bytes"] = fam(
                "rtpu_arena_used_bytes", {(): st["used"]})
            families["rtpu_arena_capacity_bytes"] = fam(
                "rtpu_arena_capacity_bytes", {(): st["capacity"]})
        families["rtpu_node_arena_used_bytes"] = fam(
            "rtpu_node_arena_used_bytes",
            {(("node", n.node_id[:12]),): n.arena_stats.get("used", 0)
             for n in self.nodes.values() if n.arena_stats})
        # Node-level host cpu/mem/log-volume (agent heartbeats; the
        # controller samples its own host once per pass for agent-less
        # nodes — same contract as cluster_state).
        local_cpu = local_mem = None
        local_log_bytes: Optional[int] = None
        mem_data: Dict[Tuple, Any] = {}
        cpu_data: Dict[Tuple, Any] = {}
        log_data: Dict[Tuple, Any] = {}
        for n in self.nodes.values():
            if not n.alive:
                continue
            key = (("node", n.node_id[:12]),)
            if n.agent_conn is not None:
                mem_data[key] = n.mem_fraction
                cpu_data[key] = n.cpu_percent
                log_data[key] = n.log_bytes
            else:
                if local_cpu is None:
                    try:
                        import psutil

                        local_cpu = psutil.cpu_percent(None)
                        local_mem = psutil.virtual_memory().percent / 100.0
                    except Exception:
                        local_cpu = local_mem = -1.0
                mem_data[key] = (n.mem_fraction if local_mem in (None, -1.0)
                                 else local_mem)
                cpu_data[key] = (n.cpu_percent if local_cpu in (None, -1.0)
                                 else local_cpu)
                if local_log_bytes is None:
                    from .worker_logs import log_volume_bytes

                    try:
                        local_log_bytes = log_volume_bytes()
                    except Exception:
                        local_log_bytes = 0
                log_data[key] = local_log_bytes
        families["rtpu_node_mem_fraction"] = fam("rtpu_node_mem_fraction",
                                                 mem_data)
        families["rtpu_node_cpu_percent"] = fam("rtpu_node_cpu_percent",
                                                cpu_data)
        families["rtpu_worker_log_bytes"] = fam("rtpu_worker_log_bytes",
                                                log_data)
        families["rtpu_events_total"] = fam("rtpu_events_total", {
            (("source", src), ("severity", sev)): n
            for (src, sev), n in
            (self.events.counts.items()
             if getattr(self, "events", None) is not None else ())})
        wcpu: Dict[Tuple, Any] = {}
        wrss: Dict[Tuple, Any] = {}
        for n in self.nodes.values():
            if not n.alive:
                continue
            for pid, st in n.proc_stats.items():
                key = (("node", n.node_id[:12]), ("pid", str(pid)))
                wcpu[key] = st.get("cpu_percent", 0.0)
                wrss[key] = st.get("rss", 0.0)
        families["rtpu_worker_cpu_percent"] = fam(
            "rtpu_worker_cpu_percent", wcpu)
        families["rtpu_worker_rss_bytes"] = fam(
            "rtpu_worker_rss_bytes", wrss)
        rpc = protocol.handler_stats()
        families["rtpu_rpc_handled_total"] = fam(
            "rtpu_rpc_handled_total",
            {(("kind", k),): n for k, (n, _) in rpc.items()})
        families["rtpu_rpc_handler_seconds_total"] = fam(
            "rtpu_rpc_handler_seconds_total",
            {(("kind", k),): round(s, 6) for k, (_, s) in rpc.items()})
        # Object-census gauges: directory bytes by (node, tier) plus
        # broadcast replica copies, per-node arena fill fraction (the
        # object_store_mem_high alert input), and per-node spill bytes.
        from .object_store import storage_kind as _sk

        store_data: Dict[Tuple, Any] = {}
        for loc in self.objects.values():
            key = (("node", (loc.node_id or "?")[:12]),
                   ("tier", _sk(loc)))
            store_data[key] = store_data.get(key, 0) + int(loc.size or 0)
        for reps in self.object_replicas.values():
            for nid, rep in reps.items():
                key = (("node", nid[:12]), ("tier", "replica"))
                store_data[key] = (store_data.get(key, 0)
                                   + int(rep.size or 0))
        families["rtpu_object_store_bytes"] = fam(
            "rtpu_object_store_bytes", store_data)
        fill_data: Dict[Tuple, Any] = {}
        spill_data: Dict[Tuple, Any] = {}
        local_spill: Optional[Dict[str, int]] = None
        for n in self.nodes.values():
            if not n.alive:
                continue
            key = (("node", n.node_id[:12]),)
            ast = n.arena_stats
            if n.agent_conn is None and self._arena is not None:
                ast = self._arena.stats()
            cap = float(ast.get("capacity", 0) or 0) if ast else 0.0
            if cap > 0:
                fill_data[key] = round(ast.get("used", 0) / cap, 4)
            if n.agent_conn is not None:
                sp = n.spill_stats
            else:
                if local_spill is None:
                    local_spill = self._local_spill_stats()
                sp = local_spill
            if sp:
                spill_data[key] = sp.get("bytes", 0)
        families["rtpu_object_store_fill_fraction"] = fam(
            "rtpu_object_store_fill_fraction", fill_data)
        families["rtpu_node_spill_bytes"] = fam(
            "rtpu_node_spill_bytes", spill_data)
        families["rtpu_object_leaks_total"] = fam(
            "rtpu_object_leaks_total", {(): self.leak_count})
        # Job plane (core/job_manager.py): table gauge, attempt-cause
        # counter, and terminal-runtime histogram (built by hand — fam()
        # leaves boundaries empty, histograms need theirs).
        families["rtpu_jobs"] = fam("rtpu_jobs",
                                    self.jobs.status_counts())
        families["rtpu_job_attempts_total"] = fam(
            "rtpu_job_attempts_total", self.jobs.attempt_count_data())
        from .job_manager import JOB_RUNTIME_BOUNDARIES

        _jr_type, _jr_help = CORE_METRIC_META["rtpu_job_runtime_s"]
        families["rtpu_job_runtime_s"] = {
            "type": _jr_type, "help": _jr_help,
            "boundaries": list(JOB_RUNTIME_BOUNDARIES),
            "data": self.jobs.runtime_hist_data()}
        # Conditional families appear once they have samples; the
        # always-set keeps its HELP/TYPE headers from day one.
        for name in [n for n, f in families.items()
                     if not f["data"] and n not in _ALWAYS_EXPORT]:
            del families[name]
        # App-defined metrics (util/metrics.py), sorted by name after the
        # core families.
        for name, m in sorted(self.app_metrics.items()):
            families[name] = m
        return families

    def _metrics_text(self) -> str:
        """Prometheus text exposition (reference: _private/metrics_agent.py
        + ray_metrics_export — collapsed to a controller-local scrape),
        rendered generically from _metrics_families()."""
        def esc(v) -> str:
            # Prometheus label-value escaping: one bad value must not
            # corrupt the whole scrape payload.
            return (str(v).replace("\\", "\\\\").replace('"', '\\"')
                    .replace("\n", "\\n"))

        lines: List[str] = []
        for name, m in self._metrics_families().items():
            if m["help"]:
                lines.append(f"# HELP {name} {m['help']}")
            ptype = "histogram" if m["type"] == "histogram" else m["type"]
            lines.append(f"# TYPE {name} {ptype}")
            for tags, v in sorted(m["data"].items()):
                lbl = ",".join(f'{k}="{esc(val)}"' for k, val in tags)
                if m["type"] == "histogram":
                    cum = 0
                    for i, b in enumerate(m["boundaries"]):
                        cum += v["buckets"][i]
                        le = (lbl + "," if lbl else "") + f'le="{b}"'
                        lines.append(f"{name}_bucket{{{le}}} {cum}")
                    le_inf = (lbl + "," if lbl else "") + 'le="+Inf"'
                    lines.append(f"{name}_bucket{{{le_inf}}} {v['count']}")
                    suffix = f"{{{lbl}}}" if lbl else ""
                    lines.append(f"{name}_sum{suffix} {v['sum']}")
                    lines.append(f"{name}_count{suffix} {v['count']}")
                else:
                    suffix = f"{{{lbl}}}" if lbl else ""
                    lines.append(f"{name}{suffix} {v}")
        return "\n".join(lines) + "\n"

    async def _serve_metrics_http(self, reader, writer) -> None:
        """Minimal HTTP/1.0 responder for GET /metrics — no web framework in
        the core control plane."""
        try:
            await asyncio.wait_for(reader.readline(), 5)
            while True:
                line = await asyncio.wait_for(reader.readline(), 5)
                if not line or line in (b"\r\n", b"\n"):
                    break
            body = self._metrics_text().encode()
            writer.write(
                b"HTTP/1.0 200 OK\r\nContent-Type: text/plain; version=0.0.4"
                b"\r\nContent-Length: " + str(len(body)).encode()
                + b"\r\n\r\n" + body
            )
            await writer.drain()
        except Exception:
            pass
        finally:
            try:
                writer.close()
            except Exception:
                pass

    async def _h_cluster_state(self, conn, msg):
        # Local (agent-less) nodes share the controller's host: sample its
        # cpu/mem ONCE per call so `rtpu status` surfaces node-level
        # numbers for them too (agent nodes report via heartbeat).
        local_cpu = local_mem = None
        try:
            import psutil

            local_cpu = psutil.cpu_percent(None)
            local_mem = psutil.virtual_memory().percent / 100.0
        except Exception:
            pass
        return {
            "nodes": [
                {
                    "node_id": n.node_id,
                    "resources": dict(n.resources),
                    "available": dict(n.available),
                    "labels": dict(n.labels),
                    "alive": n.alive,
                    # Drain lifecycle: alive | draining | drained | dead
                    # (rtpu status / dashboard node table / serve routing).
                    "state": self._node_state(n),
                    "drain_reason": n.drain_reason,
                    "index": n.index,
                    "num_workers": len(n.workers),
                    "mem_fraction": (
                        n.mem_fraction if n.agent_conn is not None
                        else (local_mem if local_mem is not None
                              else n.mem_fraction)),
                    # Host CPU% (heartbeats for agent nodes, sampled here
                    # for local ones) — the `rtpu status` CPU column.
                    "cpu_percent": (
                        n.cpu_percent if n.agent_conn is not None
                        else (local_cpu if local_cpu is not None
                              else n.cpu_percent)),
                    # Unallocated chip ids (local-spawn nodes): chaos tests
                    # assert free-pool/granted disjointness across restarts.
                    "tpu_free": list(n.tpu_free),
                    # Per-worker-process cpu%/rss (agent heartbeats;
                    # dashboard reporter parity). Empty for virtual nodes.
                    "proc_stats": dict(n.proc_stats),
                    # Object-store occupancy (`rtpu status` STORE/SPILL
                    # columns): arena used/capacity + host spill usage
                    # (heartbeats for agent nodes, sampled locally here).
                    "arena": (dict(n.arena_stats)
                              if n.agent_conn is not None
                              else (self._arena.stats()
                                    if self._arena is not None else {})),
                    "spill": (dict(n.spill_stats)
                              if n.agent_conn is not None
                              else self._local_spill_stats()),
                    # Channel-fabric footprint (live rtpu_ch_* rings).
                    "channels": (dict(n.channel_stats)
                                 if n.agent_conn is not None
                                 else self._local_channel_stats()),
                }
                for n in self.nodes.values()
            ],
            "num_workers": len(self.workers),
            "actors": {
                aid: {"state": a.state, "name": a.name, "node_id": a.node_id}
                for aid, a in self.actors.items()
            },
            "pending_tasks": len(self.pending_queue),
            "uptime_s": time.time() - self.start_time,
            "metrics_port": getattr(self, "metrics_port", 0),
            "compiled_dags": {
                did: {"stages": len(d.get("stages", ())),
                      "edges": d.get("edges", {}),
                      "depth": d.get("depth", 0),
                      "since": d.get("since", 0.0),
                      "recoveries": d.get("recoveries", 0),
                      "recovering": d.get("recovering", False)}
                for did, d in self.compiled_dags.items()
            },
        }

    async def _h_add_node(self, conn, msg):
        nid = self.add_node(msg["resources"], msg.get("labels"))
        return {"node_id": nid}

    async def _h_ping(self, conn, msg):
        return {"pong": True, "t": time.time()}

    # host agents -------------------------------------------------------------

    async def _h_register_node(self, conn, msg):
        """A host agent joins — or, after a controller/agent bounce,
        re-joins — the cluster (reference: raylet node registration with
        the GCS, gcs_node_manager.h; re-registration on NotifyGCSRestart,
        node_manager.proto:373)."""
        nid = msg["node_id"]
        node = self.nodes.get(nid)
        if node is not None:
            # Re-registration under the same identity: refresh the control
            # connection and capacity in place. The agent's surviving
            # workers re-register themselves right after and re-claim their
            # node slots; spawn counters reset (in-flight spawn bookkeeping
            # did not survive the bounce — the agent's reap loop reports
            # any orphaned spawn exits).
            node.agent_conn = conn
            node.agent_addr = tuple(msg["agent_addr"])
            node.host_id = msg.get("host_id") or node.host_id
            node.resources = dict(msg["resources"])
            node.available = dict(msg["resources"])
            node.labels = msg.get("labels") or node.labels
            node.alive = True
            node.suspect = False  # a re-register IS a heartbeat
            node.suspect_since = 0.0
            node.last_heartbeat = time.monotonic()
            node.spawning = 0
            node.spawning_tpu = 0
            node.spawning_envs.clear()
            for a in self.actors.values():
                if a.reserved and a.node_id == nid and a.pg is None:
                    _res_sub(node.available, a.resources)
            self._emit_event(
                "INFO", "NODE_RECONNECTED",
                f"node {nid[:8]} re-registered after a bounce",
                node_id=nid, data={"host_id": node.host_id})
            await self._flush_suspect_calls(node)
            if nid in self.pending_drains:
                # The drain outlived a controller bounce: the re-registered
                # node resumes draining with its original deadline.
                self._arm_drain(node)
        else:
            self._node_counter += 1
            self.nodes[nid] = NodeInfo(
                node_id=nid,
                resources=dict(msg["resources"]),
                available=dict(msg["resources"]),
                index=self._node_counter,
                labels=msg.get("labels") or {},
                agent_conn=conn,
                agent_addr=tuple(msg["agent_addr"]),
                host_id=msg.get("host_id"),
                last_heartbeat=time.monotonic(),
            )
            self._emit_event(
                "INFO", "NODE_ADDED",
                f"node {nid[:8]} joined with {msg['resources']} "
                f"(host agent)",
                node_id=nid,
                data={"resources": dict(msg["resources"]),
                      "host_id": msg.get("host_id")})
        self._wake_scheduler()
        return {"ok": True, "controller_host_id": self.host_id}

    async def _h_heartbeat(self, conn, msg):
        node = self.nodes.get(msg["node_id"])
        if node is not None:
            node.last_heartbeat = time.monotonic()
            if node.suspect and node.alive:
                # The partition/stall healed before the death deadline:
                # un-suspect, resume scheduling, flush buffered actor
                # calls — no actor churn, no double-allocation.
                node.suspect = False
                node.suspect_since = 0.0
                self._emit_event(
                    "INFO", "NODE_HEALED",
                    f"node {node.node_id[:8]} heartbeating again after "
                    f"suspect phase; scheduling resumed",
                    node_id=node.node_id)
                await self._flush_suspect_calls(node)
                self._wake_scheduler()
            node.arena_stats = msg.get("arena") or {}
            node.spill_stats = msg.get("spill") or {}
            node.channel_stats = msg.get("channels") or {}
            if msg.get("mem_fraction") is not None:
                node.mem_fraction = float(msg["mem_fraction"])
            if msg.get("cpu_percent") is not None:
                node.cpu_percent = float(msg["cpu_percent"])
            if msg.get("proc_stats") is not None:
                node.proc_stats = msg["proc_stats"]
            if msg.get("log_bytes") is not None:
                node.log_bytes = int(msg["log_bytes"])
        return None

    async def _h_spawn_exited(self, conn, msg):
        """Agent reports a spawned worker process exited. If it never
        registered, unwind the spawning counters (local spawns use
        _watch_spawn for the same purpose). Registered workers are cleaned
        up via their own conn drop — their token is no longer outstanding,
        so this must not decrement some other pending spawn's count."""
        token = msg["spawn_token"]
        node_id = self._agent_spawns.pop(token, None)
        node = self.nodes.get(node_id or "")
        if node is not None:
            node.spawning = max(0, node.spawning - 1)
            if token in self._tpu_spawn_tokens:
                node.spawning_tpu = max(0, node.spawning_tpu - 1)
        self._release_env_spawn(node, token)
        self._tpu_spawn_tokens.discard(token)
        if msg.get("env_failed"):
            # The agent could not materialize the runtime env: fail the
            # queued tasks rather than retrying the broken install forever.
            self._emit_event(
                "ERROR", "RUNTIME_ENV_FAILED",
                f"runtime env build failed on node "
                f"{(node_id or '?')[:8]}: "
                f"{msg.get('env_error') or 'setup failed'}",
                node_id=node_id,
                data={"env_hash": msg["env_failed"],
                      "error": msg.get("env_error")})
            self._fail_env_tasks(
                msg["env_failed"],
                RuntimeError(msg.get("env_error") or "runtime env setup failed"),
            )
        self._wake_scheduler()
        return None

    async def _h_get_node_agent(self, conn, msg):
        """Resolve the pull-serving address for a node: its agent, or this
        controller for in-controller (head/virtual) nodes."""
        node = self.nodes.get(msg.get("node_id") or "")
        if node is not None and node.agent_addr is not None:
            return {"host": node.agent_addr[0], "port": node.agent_addr[1]}
        return {"host": self.host, "port": self.port}

    async def _h_pull_chunk(self, conn, msg):
        """Serve object bytes for head-host locations (the controller is the
        head node's agent)."""
        from .transfer import read_location_range

        return read_location_range(msg["loc"], msg["offset"], msg["length"])

    async def _h_pull_stream(self, conn, msg):
        """Streamed pull of head-host object bytes: chunks ship back-to-back
        under the consumer's credit window (transfer.py protocol)."""
        from . import transfer

        return await transfer.handle_pull_server_message(conn, msg)

    async def _h_pull_credit(self, conn, msg):
        from . import transfer

        return await transfer.handle_pull_server_message(conn, msg)

    # ------------------------------------------------- broadcast / replicas
    # One-hop broadcast (reference: ray.experimental.channel's bounded
    # broadcast + the pull manager's location fan-out): the source streams
    # each byte once down a pipelined chain of hosts; every hop stores a
    # full local replica and reports it here, so later consumer-local
    # get_locations never cross the network again.

    def _head_node_id(self) -> str:
        for n in self.nodes.values():
            if n.agent_conn is None and n.alive:
                return n.node_id
        return "head"

    def _node_host(self, node: "NodeInfo") -> Optional[str]:
        """A node's host identity; agent-less (head/virtual) nodes live in
        the controller's process and share its host."""
        return node.host_id or self.host_id

    async def _replicate_report(self, payload):
        await self._h_replica_added(None, payload)

    async def _h_replicate_begin(self, conn, msg):
        from . import transfer

        return await transfer.handle_replicate_message(
            conn, msg, node_id=self._head_node_id(),
            report=self._replicate_report)

    async def _h_replicate_chunk(self, conn, msg):
        from . import transfer

        return await transfer.handle_replicate_message(
            conn, msg, node_id=self._head_node_id(),
            report=self._replicate_report)

    async def _h_replicate_end(self, conn, msg):
        from . import transfer

        return await transfer.handle_replicate_message(
            conn, msg, node_id=self._head_node_id(),
            report=self._replicate_report)

    async def _h_replica_added(self, conn, msg):
        """A chain hop sealed its local copy: record the replica location
        and resolve the owning broadcast's pending set."""
        oid = msg["object_id"]
        loc: ObjectLocation = msg["loc"]
        node_id = msg["node_id"]
        if oid in self.objects:
            self.object_replicas.setdefault(oid, {})[node_id] = loc
        else:
            # Object freed while the chain was in flight: release the
            # hop's freshly sealed storage instead of leaking it.
            await self._free_one_location(loc)
        self.broadcast_bytes["hop"] += int(msg.get("bytes_in") or 0)
        st = self._broadcasts.get(msg.get("bid") or "")
        if st is not None:
            st["done"][node_id] = "ok"
            st["pending"].discard(node_id)
            st["event"].set()
        return {"ok": True}

    async def _h_replicate_push_done(self, conn, msg):
        """Source-side completion report: bytes the source actually shipped
        (each byte once, independent of chain length)."""
        self.broadcast_bytes["source"] += int(msg.get("bytes") or 0)
        st = self._broadcasts.get(msg.get("bid") or "")
        if st is not None:
            st["stats"]["source_bytes"] += int(msg.get("bytes") or 0)
            if msg.get("error"):
                st["stats"].setdefault("errors", []).append(msg["error"])
            st["pushes"] -= 1
            st["event"].set()
        return None

    def _broadcast_targets(self, loc: ObjectLocation,
                           node_ids: Optional[List[str]],
                           reps: Dict[str, ObjectLocation]):
        """Resolve + filter broadcast targets: alive, not draining, with a
        reachable sink, one per host, skipping hosts that already hold the
        bytes. Returns ([NodeInfo...], {node_id: skip_reason})."""
        if node_ids:
            nodes = []
            skipped: Dict[str, str] = {}
            for nid in node_ids:
                node = self.nodes.get(nid) or next(
                    (n for k, n in self.nodes.items() if k.startswith(nid)),
                    None)
                if node is None:
                    skipped[nid] = "unknown node"
                else:
                    nodes.append(node)
        else:
            nodes, skipped = list(self.nodes.values()), {}
        have = {loc.host_id} | {r.host_id for r in reps.values()}
        out, seen_hosts = [], set()
        for node in nodes:
            host = self._node_host(node)
            if not node.alive or node.drained:
                skipped[node.node_id] = "node not alive"
            elif node.node_id in self.pending_drains:
                skipped[node.node_id] = "node draining"
            elif node.suspect:
                skipped[node.node_id] = "node suspect"
            elif host in have or node.node_id in reps:
                skipped[node.node_id] = "already local"
            elif host in seen_hosts:
                skipped[node.node_id] = "host already targeted"
            elif node.agent_conn is not None and node.agent_addr is None:
                skipped[node.node_id] = "no sink address"
            else:
                seen_hosts.add(host)
                out.append(node)
        return out, skipped

    def _broadcast_sink(self, node: "NodeInfo") -> Dict[str, Any]:
        if node.agent_addr is not None:
            return {"node_id": node.node_id, "host": node.agent_addr[0],
                    "port": node.agent_addr[1]}
        return {"node_id": node.node_id, "host": self.host,
                "port": self.port}

    async def _launch_broadcast_chain(self, bid: str, loc: ObjectLocation,
                                      chain: List[Dict[str, Any]],
                                      st: Dict[str, Any]) -> bool:
        """Start one chain round from wherever the bytes live: the
        controller itself for head-host sources, else the source host's
        agent (replicate_push)."""
        from . import transfer

        if loc.host_id == self.host_id:
            st["pushes"] += 1

            async def _push():
                try:
                    sent = await transfer.push_replicate_chain(loc, chain, bid)
                    st["stats"]["source_bytes"] += sent
                    self.broadcast_bytes["source"] += sent
                except asyncio.CancelledError:
                    raise
                except Exception as e:  # noqa: BLE001 — retried by the round loop
                    st["stats"].setdefault("errors", []).append(repr(e)[:300])
                st["pushes"] -= 1
                st["event"].set()

            task = asyncio.get_running_loop().create_task(_push())
            tasks = getattr(self, "_bcast_push_tasks", None)
            if tasks is None:
                tasks = self._bcast_push_tasks = set()
            tasks.add(task)
            task.add_done_callback(tasks.discard)
            return True
        src_node = next(
            (n for n in self.nodes.values()
             if n.alive and n.agent_conn is not None
             and n.host_id == loc.host_id), None)
        if src_node is None:
            return False
        try:
            await src_node.agent_conn.request(
                {"kind": "replicate_push", "bid": bid, "loc": loc,
                 "chain": chain, "chunk": flags.get("RTPU_PULL_CHUNK"),
                 "window": flags.get("RTPU_PULL_WINDOW")}, timeout=10)
            st["pushes"] += 1
            return True
        except Exception:
            return False

    async def _h_broadcast_object(self, conn, msg):
        """rtpu.broadcast backend: replicate one object's bytes onto N
        hosts over a pipelined chain. Re-routes remaining targets on a
        fresh chain when a hop dies or drains mid-flight; source-side
        bytes stay ~one object size per round regardless of N."""
        oid = msg["object_id"]
        timeout = float(msg.get("timeout") or 120.0)
        deadline = time.monotonic() + timeout
        loc = await self._wait_for_object(oid, deadline)
        if loc.is_error:
            raise ObjectLostError(f"cannot broadcast errored object {oid[:8]}")
        reps = self.object_replicas.setdefault(oid, {})
        if loc.inline is not None:
            # Inline bytes ride the control plane with the location itself:
            # every consumer already gets a local copy.
            return {"ok": True, "inline": True, "replicas": {}, "skipped": {},
                    "stats": {"source_bytes": 0}}
        targets, skipped = self._broadcast_targets(
            loc, msg.get("node_ids"), reps)
        st = {
            "pending": {n.node_id for n in targets},
            "done": {nid: "already local" for nid in skipped
                     if skipped[nid] == "already local"},
            "event": asyncio.Event(),
            "stats": {"source_bytes": 0},
            "pushes": 0,  # launched chains still owing a byte report
        }
        rounds = 0
        bids: List[str] = []
        while st["pending"] and rounds < 3 and time.monotonic() < deadline:
            rounds += 1
            live = []
            for nid in sorted(st["pending"]):
                node = self.nodes.get(nid)
                if node is None or not node.alive or node.drained \
                        or nid in self.pending_drains:
                    st["pending"].discard(nid)
                    st["done"][nid] = "node left during broadcast"
                    continue
                live.append(node)
            if not live:
                break
            bid = ObjectID.generate()[:16]
            # Registered until the RPC returns (not per round): late
            # replica_added / push-done reports must still resolve state.
            self._broadcasts[bid] = st
            bids.append(bid)
            chain = [self._broadcast_sink(n) for n in live]
            src = loc
            if not await self._launch_broadcast_chain(bid, src, chain, st):
                # Source host gone: any sealed replica can re-seed.
                reseed = next((r for r in reps.values()
                               if self._host_alive(r.host_id)), None)
                if reseed is None or not await self._launch_broadcast_chain(
                        bid, reseed, chain, st):
                    break
            round_deadline = min(deadline,
                                 time.monotonic() + max(10.0, timeout / 3))
            while st["pending"] and time.monotonic() < round_deadline:
                st["event"].clear()
                # Nodes that die or drain mid-round are re-routed next round.
                changed = False
                for nid in list(st["pending"]):
                    node = self.nodes.get(nid)
                    if node is None or not node.alive \
                            or nid in self.pending_drains:
                        changed = True
                if changed:
                    break
                try:
                    await asyncio.wait_for(
                        st["event"].wait(),
                        max(0.05, min(0.5, round_deadline - time.monotonic())))
                except asyncio.TimeoutError:
                    pass
        # Let in-flight source pushes report their byte counts before the
        # reply is built (stats.source_bytes is the acceptance signal that
        # each byte left the source once).
        drain_deadline = time.monotonic() + 5.0
        while st["pushes"] > 0 and time.monotonic() < drain_deadline:
            st["event"].clear()
            try:
                await asyncio.wait_for(st["event"].wait(), 0.25)
            except asyncio.TimeoutError:
                pass
        for b in bids:
            self._broadcasts.pop(b, None)
        for nid in st["pending"]:
            st["done"][nid] = "timed out"
        return {
            "ok": not st["pending"],
            "replicas": {nid: v for nid, v in st["done"].items()
                         if v == "ok"},
            "skipped": {**skipped,
                        **{nid: v for nid, v in st["done"].items()
                           if v not in ("ok",)}},
            "stats": st["stats"],
            "rounds": rounds,
        }

    def _host_alive(self, host_id: Optional[str]) -> bool:
        if host_id == self.host_id:
            return True
        return any(n.alive and n.host_id == host_id
                   for n in self.nodes.values())

    def _replica_view(self, oid: str, loc: ObjectLocation,
                      req_node_id: Optional[str]) -> ObjectLocation:
        """Consumer-aware location: hand back the copy local to the
        requester's host when one exists; otherwise attach the replica
        list so the pull can fan across source hosts."""
        reps = self.object_replicas.get(oid)
        if not reps or loc.inline is not None:
            return loc
        req_host = None
        if req_node_id:
            node = self.nodes.get(req_node_id)
            if node is not None:
                req_host = self._node_host(node)
        if req_host:
            if loc.host_id == req_host:
                return loc
            for rep in reps.values():
                if rep.host_id == req_host:
                    return rep
        extra = [r for r in reps.values()
                 if r.host_id != loc.host_id
                 and self._host_alive(r.host_id)]
        if not extra:
            return loc
        import dataclasses as _dc

        return _dc.replace(loc, replicas=extra)

    def _restore_state(self) -> None:
        self._restored_detached: List[Dict[str, Any]] = []
        self._adopt_grace_until = 0.0
        if not self.persist_path or not os.path.exists(self.persist_path):
            return
        import pickle as _p

        try:
            with open(self.persist_path, "rb") as f:
                snap = _p.load(f)
        except Exception as e:
            sys.stderr.write(f"[controller] state restore failed: {e!r}\n")
            return
        self.kv.update(snap.get("kv", {}))
        self.functions.update(snap.get("functions", {}))
        # Job table + attempt counters + runtime histogram: restored
        # before anything can touch them, so an in-flight wait_job's
        # after_seq cursor stays meaningful across the bounce.
        self.jobs.restore(snap.get("jobs"))
        # In-progress drains resume after the bounce (wall-clock deadlines,
        # so the grace window keeps shrinking through the downtime).
        drains = snap.get("drains") or {}
        self.drain_counts.update(drains.get("counts") or {})
        self.pending_drains.update(drains.get("pending") or {})
        # Node table (non-agent nodes only — agents re-register themselves):
        # restored so that surviving workers of the previous controller can
        # reconnect under their original node ids and so the head node keeps
        # its identity across a bounce (reference: the GCS node table in
        # gcs_storage surviving failover).
        for nd in snap.get("nodes", []):
            if nd["node_id"] in self.nodes:
                continue
            self._node_counter += 1
            self.nodes[nd["node_id"]] = NodeInfo(
                node_id=nd["node_id"],
                resources=dict(nd["resources"]),
                available=dict(nd["resources"]),
                index=self._node_counter,
                labels=dict(nd.get("labels") or {}),
                tpu_free=list(range(int(nd["resources"].get("TPU", 0)))),
            )
        # Only resume detached actors that can actually be rebuilt: creation
        # deps died with the old process's object plane, and placement
        # groups are not persisted — resuming those would leave actors
        # permanently pending with callers hanging.
        resumable = []
        for spec in snap.get("detached_actors", []):
            if spec.get("deps") or spec.get("pg"):
                sys.stderr.write(
                    f"[controller] not resuming detached actor "
                    f"{spec.get('name') or spec['actor_id'][:8]}: creation "
                    f"{'deps' if spec.get('deps') else 'placement group'} "
                    f"did not survive the restart\n")
                continue
            resumable.append(spec)
        resumed_ids = {s["actor_id"] for s in resumable}
        # Names must only point at actors that exist (now or imminently);
        # dangling entries would KeyError every lookup forever.
        self.named_actors.update({
            k: v for k, v in snap.get("named_actors", {}).items()
            if v in resumed_ids
        })
        # Register the ActorInfos NOW so get_actor() between start and the
        # first scheduler pass sees a restarting actor, not a missing name
        # (calls submitted meanwhile buffer in pending_calls). Re-CREATION
        # is deferred for an adoption grace window: the previous
        # controller's workers may still be alive and hosting these very
        # instances — they re-claim them on reconnect, preserving actor
        # state (reference: GCS failover waits for raylet/worker
        # re-registration before reconstructing actors).
        for spec in resumable:
            actor_id = spec["actor_id"]
            if actor_id in self.actors:
                continue
            actor = ActorInfo(
                actor_id=actor_id,
                name=spec.get("name"),
                state="restarting",
                resources=spec.get("resources", {}),
                pg=spec.get("pg"),
                detached=True,
                creation_task_id=spec["task_id"],
                max_restarts=int(spec.get("max_restarts", 0)),
                creation_spec=spec,
            )
            # A persisted checkpoint record survives the bounce: the
            # re-created instance restores it instead of re-running the
            # constructor. The 8-byte epoch header keeps the record itself
            # opaque to the controller (user state never unpickles here).
            try:
                import struct as _struct

                with open(f"{self.persist_path}.ckpt.{actor_id}",
                          "rb") as f:
                    raw = f.read()
                (epoch,) = _struct.unpack_from("!Q", raw)
                actor.checkpoint = {"epoch": int(epoch), "blob": raw[8:],
                                    "bytes": len(raw) - 8,
                                    "ts": time.time()}
            except Exception:
                pass
            self.actors[actor_id] = actor
        self._restored_detached = resumable
        if resumable:
            self._adopt_grace_until = (
                time.monotonic() + flags.get("RTPU_RECONNECT_GRACE_S"))

    def _resume_detached_actors(self) -> None:
        """Queue creation tasks for restored detached actors that no
        surviving worker re-claimed within the adoption grace window
        (reference: GCS restart reconstructing actors from storage,
        gcs_actor_manager RestartActor on GCS failover)."""
        specs = getattr(self, "_restored_detached", None) or []
        if not specs:
            return
        if time.monotonic() < self._adopt_grace_until:
            return  # reconnecting workers get first claim
        self._restored_detached = []
        queued = False
        for spec in specs:
            actor_id = spec["actor_id"]
            actor = self.actors.get(actor_id)
            if actor is None or actor.state in ("alive", "dead"):
                continue  # adopted by a reconnected worker (or retired)
            if actor.checkpoint is not None \
                    and actor.checkpoint.get("blob") is not None:
                # Restored persisted checkpoint: the re-creation restores
                # state instead of re-running the constructor.
                spec["state_blob"] = actor.checkpoint["blob"]
            spec["state"] = "pending"
            spec.pop("sched_node", None)
            self.tasks[spec["task_id"]] = spec
            self.pending_queue.append(spec)
            queued = True
        if queued:
            self._wake_scheduler()

    def _snapshot_state(self, force: bool = False) -> None:
        if not self.persist_path:
            return
        if not force and not self._state_dirty:
            return  # nothing changed: skip the pickle + disk write
        self._state_dirty = False
        import pickle as _p

        detached = [
            a.creation_spec for a in self.actors.values()
            if a.detached and a.creation_spec is not None
            and a.state != "dead"
        ]
        live_ids = {s["actor_id"] for s in detached}
        snap = {
            "kv": dict(self.kv),
            "functions": dict(self.functions),
            "named_actors": {
                k: v for k, v in self.named_actors.items() if v in live_ids
            },
            "detached_actors": detached,
            # Non-agent nodes (head + virtual): identity + capacity only.
            # Agent nodes re-register themselves after a restart.
            "nodes": [
                {"node_id": n.node_id, "resources": dict(n.resources),
                 "labels": dict(n.labels)}
                for n in self.nodes.values()
                if n.agent_conn is None and n.agent_addr is None and n.alive
            ],
            "drains": {"counts": dict(self.drain_counts),
                       "pending": dict(self.pending_drains)},
            "jobs": self.jobs.snapshot(),
        }
        tmp = self.persist_path + f".tmp{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                _p.dump(snap, f)
            os.replace(tmp, self.persist_path)
        except Exception as e:
            sys.stderr.write(f"[controller] state snapshot failed: {e!r}\n")

    async def _memory_monitor_loop(self) -> None:
        """Kill a worker when a host crosses the memory threshold
        (reference: src/ray/common/memory_monitor.h:52 + the retriable-FIFO
        worker killing policy, raylet/worker_killing_policy_retriable_fifo.h:
        prefer the NEWEST retriable task — it has made the least progress
        and will be retried — then the newest task of any kind; actors are
        killed last since their state is not reconstructible). ONE victim
        per tick, then resample: freed memory must be observed before the
        next kill, or a single spike over-kills the whole pool."""
        while True:
            # Read per-iteration: operators tune these live (and tests
            # lift the pressure mid-run to let a retried victim finish).
            period = flags.get("RTPU_MEMORY_MONITOR_S")
            threshold = flags.get("RTPU_MEMORY_USAGE_THRESHOLD")
            await asyncio.sleep(period)
            try:
                local_frac = self._local_mem_fraction()
                for node in self.nodes.values():
                    if not node.alive:
                        continue
                    if node.agent_conn is not None:
                        # Agent node: trust its heartbeat only — falling
                        # back to the controller host's own usage would
                        # misattribute local pressure to healthy remote
                        # hosts (agents without psutil report nothing).
                        frac = node.mem_fraction
                    else:
                        frac = local_frac
                    if frac < threshold:
                        continue
                    victim = self._pick_oom_victim(node)
                    if victim is None:
                        continue
                    victim.oom_killed = True
                    sys.stderr.write(
                        f"[controller] memory monitor: host at "
                        f"{frac:.0%} >= {threshold:.0%}, killing worker "
                        f"{victim.worker_id[:8]} "
                        f"(task {victim.current_task or 'idle'})\n")
                    # Best-effort final checkpoint before the kill: an
                    # actor victim's state survives when headroom still
                    # allows the serialize (never when the host is already
                    # past the hard ceiling — a checkpoint allocates).
                    if (victim.actor_ids
                            and flags.get("RTPU_ACTOR_CHECKPOINT")
                            and frac < min(0.99, threshold + 0.03)):
                        for aid in list(victim.actor_ids):
                            actor = self.actors.get(aid)
                            if actor is None:
                                continue
                            try:
                                res = await victim.conn.request(
                                    {"kind": "checkpoint_actor",
                                     "actor_id": aid}, timeout=3)
                            except Exception:
                                continue
                            if isinstance(res, dict) and res.get("blob"):
                                self._store_actor_checkpoint(
                                    actor, res["epoch"], res["blob"])
                    await self._shutdown_worker(victim)
                    if victim.spawn_token is not None:
                        # Agent-spawned: no local proc handle — escalate to
                        # the owning agent's SIGTERM (a busy worker ignores
                        # the graceful shutdown message).
                        if node.agent_conn is not None:
                            try:
                                await node.agent_conn.send(
                                    {"kind": "kill_worker",
                                     "spawn_token": victim.spawn_token})
                            except Exception:
                                pass
                    break  # one victim per tick, then resample
            except Exception as e:  # pragma: no cover — keep monitoring
                sys.stderr.write(f"[controller] memory monitor error: {e!r}\n")

    @staticmethod
    def _local_mem_fraction() -> float:
        try:
            import psutil

            return psutil.virtual_memory().percent / 100.0
        except Exception:
            return 0.0

    def _pick_oom_victim(self, node: NodeInfo) -> Optional[WorkerInfo]:
        running = [
            w for wid in node.workers
            if (w := self.workers.get(wid)) is not None and w.current_task
        ]

        def retriable(w: WorkerInfo) -> bool:
            spec = self.tasks.get(w.current_task or "")
            if spec is None:
                return False
            return (int(spec.get("max_retries", 0))
                    - int(spec.get("_retry_count", 0))) > 0

        pool = [w for w in running if retriable(w)] or running
        if pool:
            return max(pool, key=lambda w: w.task_started)
        # Last resort: an actor worker. Prefer one whose actors ALL have a
        # durable checkpoint — its state survives the kill (restored on
        # restart), while an uncheckpointed actor's state is simply lost;
        # ties break to the newest task as before.
        actors = [
            w for wid in node.workers
            if (w := self.workers.get(wid)) is not None and w.actor_ids
        ]

        def checkpointed(w: WorkerInfo) -> bool:
            return all(
                (a := self.actors.get(aid)) is not None
                and a.checkpoint is not None
                for aid in w.actor_ids)

        return max(actors,
                   key=lambda w: (checkpointed(w), w.task_started),
                   default=None)

    async def _flush_suspect_calls(self, node: NodeInfo) -> None:
        """Dispatch actor calls buffered while the node was suspect."""
        for actor in list(self.actors.values()):
            if actor.node_id != node.node_id or actor.state != "alive":
                continue
            while actor.pending_calls:
                calls, actor.pending_calls = actor.pending_calls, []
                for call in calls:
                    await self._dispatch_actor_call(actor, call)

    async def _health_check_loop(self) -> None:
        """Two-phase failure detector over agent heartbeats (reference:
        gcs_health_check_manager.h:39 periodic checks, with a SWIM-style
        suspect phase in front): silence past RTPU_NODE_TIMEOUT_S marks a
        node SUSPECT — scheduling pauses, actor calls buffer, nothing is
        killed — and only silence past RTPU_DEAD_TIMEOUT_S declares it
        DEAD, so a partition shorter than that heals with no actor churn.
        Also runs the arena memory-pressure check (spill cold objects past
        the high watermark, reference local_object_manager.h:103-122)."""
        while True:
            suspect_after = flags.get("RTPU_NODE_TIMEOUT_S")
            dead_after = max(flags.get("RTPU_DEAD_TIMEOUT_S"), suspect_after)
            await asyncio.sleep(min(2.0, suspect_after / 3))
            now = time.monotonic()
            for node in list(self.nodes.values()):
                if (
                    node.alive
                    and node.agent_conn is not None
                    and node.last_heartbeat
                ):
                    silence = now - node.last_heartbeat
                    if silence > dead_after:
                        self._emit_event(
                            "ERROR", "NODE_DEAD_TIMEOUT",
                            f"node {node.node_id[:8]} silent for "
                            f"{silence:.1f}s (> RTPU_DEAD_TIMEOUT_S); "
                            f"declaring it dead",
                            node_id=node.node_id,
                            data={"silence_s": round(silence, 2)})
                        await self._on_node_death(node)
                    elif silence > suspect_after and not node.suspect:
                        node.suspect = True
                        node.suspect_since = now
                        self._emit_event(
                            "WARNING", "NODE_SUSPECT",
                            f"node {node.node_id[:8]} missed heartbeats "
                            f"for {silence:.1f}s: suspect — scheduling "
                            f"paused until it heals or "
                            f"RTPU_DEAD_TIMEOUT_S passes",
                            node_id=node.node_id,
                            data={"silence_s": round(silence, 2)})
            try:
                await self._maybe_spill_cold_objects()
            except Exception as e:  # pragma: no cover — keep the loop alive
                sys.stderr.write(f"[controller] spill error: {e!r}\n")
            self._resume_detached_actors()
            self._snapshot_state()

    async def _maybe_spill_cold_objects(self) -> None:
        """When the head arena passes the high watermark, move the coldest
        sealed objects to disk until usage drops below the low watermark.
        (Agent arenas spill at put time on their own hosts; proactive remote
        eviction rides the same loc rewrite via the agent's free+spill.)

        The arena copy is NOT deleted immediately: a worker may hold the old
        location for an in-flight read, so deletion defers for a grace
        period and retries while zero-copy pins block it."""
        if self._arena is not None:
            await self._drain_deferred_deletes()
            high = flags.get("RTPU_SPILL_HIGH")
            low = flags.get("RTPU_SPILL_LOW")
            st = self._arena.stats()
            cap = st["capacity"] or 1
            if st["used"] / cap < high:
                return
            my_arena = self._arena.name
            victims = sorted(
                (
                    (self.object_touch.get(oid, 0.0), oid, loc)
                    for oid, loc in self.objects.items()
                    if loc.arena == my_arena and not loc.is_error
                ),
            )
            from .object_store import spill_dir
            from .transfer import read_location_range

            grace = flags.get("RTPU_SPILL_DELETE_GRACE_S")
            spilled_bytes = 0
            need = st["used"] - low * cap
            for _, oid, loc in victims:
                if spilled_bytes >= need:
                    break
                path = os.path.join(spill_dir(), f"{oid[:32]}.bin")

                def write_one(loc=loc, path=path):
                    raw = read_location_range(loc, 0, loc.size)
                    with open(path, "wb") as f:
                        f.write(raw)

                try:
                    # Whole-object read+write off the event loop: a spill
                    # sweep must not stall RPC handling.
                    await asyncio.to_thread(write_one)
                except Exception:
                    continue
                if self.objects.get(oid) is not loc:
                    # Freed (or replaced) while the write was in flight:
                    # the free path already handled the arena copy — don't
                    # resurrect the object or defer a bogus delete.
                    try:
                        os.unlink(path)
                    except OSError:
                        pass
                    continue
                import dataclasses as _dc

                new_loc = _dc.replace(loc, arena=None, arena_oid=0,
                                      spill_path=path)
                self.objects[oid] = new_loc
                self._deferred_arena_deletes.append(
                    (time.monotonic() + grace, loc.arena_oid))
                spilled_bytes += loc.size
                self.spilled_count += 1

    async def _drain_deferred_deletes(self) -> None:
        now = time.monotonic()
        keep = []
        for due, arena_oid in self._deferred_arena_deletes:
            if due > now:
                keep.append((due, arena_oid))
                continue
            # delete() refuses while a zero-copy pin holds the object; retry
            # later rather than leaking the slot forever.
            if not self._arena.delete(arena_oid):
                keep.append((now + 5.0, arena_oid))
        self._deferred_arena_deletes = keep

    # ---------------------------------------------------------- object helpers

    def _store_location(self, loc: ObjectLocation) -> None:
        self.objects[loc.object_id] = loc
        # Fresh objects are the HOTTEST, not coldest: without this a
        # just-put batch ties at 0.0 and gets spilled first.
        self.object_touch.setdefault(loc.object_id, time.monotonic())
        # Census age + leak-watchdog clock (setdefault: a spill rewrite or
        # replica promote must not reset an object's age).
        self.object_created.setdefault(loc.object_id, time.time())
        for ev in self.object_waiters.pop(loc.object_id, []):
            ev.set()
        for cb in self.object_callbacks.pop(loc.object_id, []):
            try:
                cb(loc.object_id)
            except Exception:
                pass

    def _store_error(self, object_id: str, err: Exception) -> None:
        import pickle as _p

        data = _p.dumps(err)
        loc = ObjectLocation(object_id=object_id, size=len(data), inline=data, is_error=True)
        self._store_location(loc)

    # -------------------------------------------------------------- scheduler

    def _wake_scheduler(self) -> None:
        self._sched_wakeup.set()

    async def _scheduler_loop(self) -> None:
        """Single scheduling fiber (the reference's ScheduleAndDispatchTasks,
        cluster_task_manager.h:117, without the cross-raylet spillback — all
        state is local to the controller here)."""
        while True:
            if self._sched_stuck and len(self.pending_queue):
                # Unplaceable work is queued and nothing is guaranteed to
                # wake us: a lease_reclaim nudge that reached the holder
                # while its routes still had pushes in flight releases
                # nothing, and the holder only reaps idle leases on its
                # next submit — which never comes if the driver is blocked
                # in get() on the queued task's output. Poll so the next
                # pass re-nudges once the holder's routes drain.
                try:
                    await asyncio.wait_for(self._sched_wakeup.wait(), 0.5)
                except asyncio.TimeoutError:
                    pass
            else:
                await self._sched_wakeup.wait()
            self._sched_wakeup.clear()
            try:
                await self._schedule_once()
            except Exception as e:  # pragma: no cover — keep scheduling alive
                sys.stderr.write(f"[controller] scheduler error: {e!r}\n")

    async def _schedule_once(self) -> None:
        # Retry pending placement groups first (resources may have freed).
        for pg in self.pgs.values():
            self._try_reserve_pg(pg)
        # One group = one placement signature: place from the head until
        # the first failure, then the rest of the group is infeasible for
        # this pass too (identical asks). See _PendingQueue docstring.
        stuck = False
        for sig in list(self.pending_queue.groups):
            q = self.pending_queue.groups.get(sig)
            while q:
                spec = self.tasks.get(q[0])
                if spec is None:
                    q.popleft()
                    self.pending_queue._count -= 1
                    continue
                dl = spec.get("deadline_ts")
                if dl is not None and time.time() > dl:
                    # Expired while queued: dead work never places.
                    q.popleft()
                    self.pending_queue._count -= 1
                    self._fail_task(spec, DeadlineExceededError(
                        f"task {spec['task_id'][:8]} deadline passed while queued"))
                    self._record_task_event(spec, "deadline_exceeded")
                    continue
                placed = await self._try_place(spec)
                if not placed:
                    stuck = True
                    break
                q.popleft()
                self.pending_queue._count -= 1
            if q is not None and not q:
                self.pending_queue.groups.pop(sig, None)
        self._sched_stuck = stuck
        if stuck:
            await self._nudge_lease_reclaim()

    async def _nudge_lease_reclaim(self) -> None:
        """Work is queued but unplaceable while drivers hold task leases:
        ask each holder to give back idle leases (it releases any with no
        in-flight pushes). Holder-coordinated, so no double-booking — the
        reference's lease revocation works the same way via ReturnWorker."""
        leases = self._leases
        if not leases:
            return
        now = time.monotonic()
        if now - self._last_reclaim_nudge < 0.2:
            return
        self._last_reclaim_nudge = now
        owners: Dict[Any, List[str]] = {}
        for lid, lease in leases.items():
            owners.setdefault(lease["owner"], []).append(lid)
        for conn, lids in owners.items():
            self.lease_stats["reclaims"] += len(lids)
            try:
                await conn.send({"kind": "lease_reclaim", "lease_ids": lids})
            except Exception:
                pass

    def _eligible_nodes(self, spec,
                        arg_bytes: Optional[Dict[str, int]] = None
                        ) -> List[NodeInfo]:
        strategy = spec.get("scheduling", {"type": "DEFAULT"})
        # Draining nodes take no new placements (reference: DrainNode makes
        # the raylet unschedulable while its deadline runs down).
        nodes = [n for n in self.nodes.values()
                 if self._schedulable(n)]
        st = strategy.get("type", "DEFAULT")
        # Nodes that spilled this spec back are out for the retry pass
        # (reference: spillback carries the rejecting raylet in the lease
        # request's excluded set) — but ONLY for placement-choice
        # strategies. Hard affinity / label constraints have no alternative
        # node: honoring the exclusion there would strand the task forever,
        # while re-dispatching lets the worker-side spill cap (2) force
        # progress.
        excluded = spec.get("spillback_excluded")
        if excluded and st in ("DEFAULT", "SPREAD"):
            keep = [n for n in nodes if n.node_id not in excluded]
            nodes = keep or nodes  # every node rejected: try them again
        if st == "NODE_AFFINITY":
            hard = [n for n in nodes if n.node_id == strategy["node_id"]]
            if hard or not strategy.get("soft", False):
                return hard
            return sorted(nodes, key=lambda n: n.index)
        if st == "SPREAD":
            # Least-loaded first: spread by available CPU fraction.
            def load(n: NodeInfo) -> float:
                tot = n.resources.get("CPU", 1.0) or 1.0
                return 1.0 - n.available.get("CPU", 0.0) / tot

            return sorted(nodes, key=lambda n: (load(n), n.index))
        if st == "NODE_LABEL":
            want: Dict[str, str] = strategy.get("labels", {})
            return [n for n in nodes if all(n.labels.get(k) == v for k, v in want.items())]
        # DEFAULT: the reference's hybrid policy, with the lease-policy
        # locality term — among equally-cold nodes, prefer the one already
        # holding the most argument bytes (reference: the locality-aware
        # LeasePolicy picks the raylet with the largest located share of
        # the task's args; here the directory is controller-local, so the
        # ranking is one dict walk, no RPCs).
        if arg_bytes is None:
            arg_bytes = self._arg_bytes_by_node(spec)
        return self._hybrid_order(nodes, arg_bytes)

    def _arg_bytes_by_node(self, spec) -> Dict[str, int]:
        """node_id -> bytes of this task's dependencies resident there."""
        by_node: Dict[str, int] = {}
        for oid in spec.get("deps", []) or []:
            loc = self.objects.get(oid)
            if loc is not None and loc.node_id and loc.inline is None:
                by_node[loc.node_id] = by_node.get(loc.node_id, 0) + loc.size
        return by_node

    @staticmethod
    def _cpu_util(n: NodeInfo) -> float:
        """CPU utilization fraction — THE hybrid-policy signal. One
        definition shared by ordering and the spawn-wait gate so they can
        never disagree about a node's bucket."""
        tot = n.resources.get("CPU", 1.0) or 1.0
        return 1.0 - n.available.get("CPU", 0.0) / tot

    @staticmethod
    def _hybrid_order(nodes: List[NodeInfo],
                      arg_bytes: Optional[Dict[str, int]] = None
                      ) -> List[NodeInfo]:
        """Reference hybrid_scheduling_policy.h:29-49: PACK onto nodes
        below the utilization threshold (locality/binpacking) — ordered by
        descending local argument bytes, then index — then SPREAD across
        hot nodes by ascending utilization. RTPU_SCHED_TOP_K > 1
        randomizes among the best k to avoid thundering-herd placement
        when many schedulers race (the reference's top-k term). Shared by
        queue placement AND lease grants so direct dispatch follows the
        same policy."""
        thr = flags.get("RTPU_SCHED_HYBRID_THRESHOLD")
        arg_bytes = arg_bytes or {}

        def hybrid_key(n: NodeInfo):
            util = Controller._cpu_util(n)
            if util < thr:
                return (0, -arg_bytes.get(n.node_id, 0), n.index, 0.0)
            return (1, 0, 0, util)

        ordered = sorted(nodes, key=hybrid_key)
        k = int(flags.get("RTPU_SCHED_TOP_K"))
        if k > 1 and len(ordered) > 1:
            import random

            head = ordered[:k]
            random.shuffle(head)
            ordered = head + ordered[k:]
        return ordered

    async def _try_place(self, spec: Dict[str, Any]) -> bool:
        resources: Dict[str, float] = spec.get("resources", {})
        pg_ref: Optional[Tuple[str, int]] = spec.get("pg")
        if pg_ref is not None:
            pg = self.pgs.get(pg_ref[0])
            if pg is None or pg.state == "removed":
                self._fail_task(spec, ValueError("placement group removed"))
                return True
            if pg.state != "ready":
                return False
            idx = pg_ref[1]
            if idx == -1:
                # "Any bundle" (reference bundle_index=-1): first fitting
                # bundle wins. The spec is rebound only at DISPATCH — a
                # failed attempt must stay -1 so the next pass can pick a
                # different bundle (pinning here would re-create the
                # starve-on-bundle-0 behavior the feature removes).
                idx = next(
                    (i for i, b in enumerate(pg.bundles)
                     if _res_fits(b.available, resources)),
                    None,
                )
                if idx is None:
                    return False
            bundle = pg.bundles[idx]
            node = self.nodes[bundle.node_id]
            if not _res_fits(bundle.available, resources):
                return False
            needs_tpu = resources.get("TPU", 0) > 0
            env_hash = spec.get("env_hash") or ""
            w = self._find_idle_worker(node, needs_tpu, env_hash,
                                       tpu_chips=int(resources.get("TPU", 0)))
            if w is None:
                self._maybe_spawn_worker(node, needs_tpu, spec.get("runtime_env"),
                                         tpu_chips=int(resources.get("TPU", 0)))
                return False
            _res_sub(bundle.available, resources)
            spec["pg"] = (pg_ref[0], idx)  # bind so release credits this bundle
            spec["sched_node"] = node.node_id
            await self._dispatch(spec, node, w)
            return True
        needs_tpu = resources.get("TPU", 0) > 0
        env_hash = spec.get("env_hash") or ""
        # Worker availability must not OVERRIDE the placement policy across
        # utilization buckets: a cold (pack-bucket) node that merely needs a
        # worker spawned beats a hot (spread-bucket) node with a warm
        # worker — the reference commits to the policy's node and starts a
        # worker there. WITHIN a bucket, preferring the node with a warm
        # worker is pure win UNLESS the locality term separates them: a
        # node holding strictly more of this task's argument bytes keeps
        # precedence even while its worker spawns (otherwise the data node
        # loses exactly when it's busy and the bytes cross the network).
        thr = flags.get("RTPU_SCHED_HYBRID_THRESHOLD")
        arg_bytes = self._arg_bytes_by_node(spec)
        # The locality hold only applies where locality ordered the nodes:
        # the DEFAULT hybrid policy. SPREAD deliberately ignores data
        # placement; label/affinity orders have no locality meaning.
        locality_st = spec.get("scheduling",
                               {"type": "DEFAULT"}).get("type") == "DEFAULT"

        def bucket(n: NodeInfo) -> int:
            return 0 if self._cpu_util(n) < thr else 1

        spawning_at: Optional[Tuple[int, int]] = None  # (bucket, arg bytes)
        for node in self._eligible_nodes(spec, arg_bytes):
            if not _res_fits(node.available, resources):
                continue
            if spawning_at is not None:
                sb, sbytes = spawning_at
                if bucket(node) > sb or (
                        locality_st and bucket(node) == sb
                        and arg_bytes.get(node.node_id, 0) < sbytes):
                    return False  # wait for the better node's spawn
            w = self._find_idle_worker(node, needs_tpu, env_hash,
                                       tpu_chips=int(resources.get("TPU", 0)))
            if w is None:
                spawning = self._maybe_spawn_worker(
                    node, needs_tpu, spec.get("runtime_env"),
                    tpu_chips=int(resources.get("TPU", 0)))
                # Hold later (worse) nodes ONLY when a spawn is really
                # coming here; a capped node with nothing in flight must
                # not starve the task off warm workers elsewhere.
                if spawning and spawning_at is None:
                    spawning_at = (bucket(node),
                                   arg_bytes.get(node.node_id, 0))
                continue
            _res_sub(node.available, resources)
            spec["sched_node"] = node.node_id
            await self._dispatch(spec, node, w)
            return True
        return False

    def _find_idle_worker(
        self, node: NodeInfo, needs_tpu: bool = False, env_hash: str = "",
        tpu_chips: int = 0,
    ) -> Optional[WorkerInfo]:
        # Plain work prefers plain workers so the scarce, seconds-to-start
        # TPU-capable workers stay free for TPU tasks. Runtime envs match
        # strictly: an env worker's cwd/sys.path/venv are already mutated.
        # A chip-restricted worker (spawn-time TPU_VISIBLE_CHIPS) only takes
        # tasks its slice can serve: a num_tpus=4 task must not land on a
        # worker that sees one chip (reference: per-lease accelerator-id
        # grants; here the grant is per-worker, so matching does the work).
        fallback: Optional[WorkerInfo] = None
        best: Optional[WorkerInfo] = None
        for wid in node.workers:
            w = self.workers.get(wid)
            if w is None or w.state != "idle" or w.env_hash != env_hash:
                continue
            if needs_tpu:
                if w.tpu_capable and (
                        not w.chip_ids
                        or len(w.chip_ids) >= max(1, tpu_chips)):
                    # Prefer the smallest sufficient restricted worker over
                    # unrestricted ones: an unrestricted process touches
                    # every chip JAX can see, so handing it a small request
                    # while an exact-fit slice idles invites physical
                    # contention with concurrently-running slices.
                    if best is None or (
                            (len(w.chip_ids) or 1 << 30)
                            < (len(best.chip_ids) or 1 << 30)):
                        best = w
            elif w.tpu_capable:
                fallback = fallback or w
            else:
                return w
        return best if needs_tpu else fallback

    def _maybe_spawn_worker(
        self,
        node: NodeInfo,
        needs_tpu: bool = False,
        runtime_env: Optional[Dict[str, Any]] = None,
        tpu_chips: int = 0,
    ) -> bool:
        """True iff a suitable worker spawn is now (or already was) in
        flight on this node — i.e. waiting on this node is sensible.
        False means no spawn will happen (cap reached with no reapable
        victim): callers must NOT hold placement for this node."""
        if node.spawning >= 4:
            return True  # several already coming
        # One in-flight TPU-capable spawn satisfies any number of queued TPU
        # tasks' wakeups during its multi-second startup; without this guard
        # every scheduler pass reaps another idle plain worker and launches a
        # surplus TPU worker. Env spawns (venv builds can take tens of
        # seconds) get the same dedup, keyed by env hash.
        if needs_tpu and node.spawning_tpu > 0:
            return True
        want_env = (runtime_env or {}).get("hash", "")
        if want_env and node.spawning_envs.get(want_env, 0) > 0:
            return True
        if len(node.workers) + node.spawning >= MAX_WORKERS_PER_NODE:
            # At the cap, a task needing a worker flavor (TPU or a runtime
            # env) that no idle worker matches must not starve behind idle
            # mismatched workers: reap one to make room (reference:
            # worker_pool.cc idle worker killing to satisfy the pool cap).
            # Scarce TPU workers are victimized only as a last resort, and
            # only by a TPU-flavored request.
            if not needs_tpu and not want_env:
                # A plain spawn can also ride any in-flight plain spawn.
                return node.spawning > 0
            victim = None
            last_resort = None
            for wid in list(node.workers):
                w = self.workers.get(wid)
                if w is None or w.state != "idle":
                    continue
                if w.tpu_capable:
                    if needs_tpu and w.env_hash != want_env:
                        last_resort = last_resort or w
                    continue
                if not needs_tpu and w.env_hash == want_env:
                    continue  # never reap the flavor being requested
                victim = w
                break
            victim = victim or last_resort
            if victim is None:
                return node.spawning > 0
            node.workers.discard(victim.worker_id)
            self.workers.pop(victim.worker_id, None)
            asyncio.get_running_loop().create_task(self._shutdown_worker(victim))
            if victim.chip_ids and node.agent_conn is None:
                # This path pops the worker before shutdown, so the death
                # handler can't return its chips — do it here.
                self._return_chips(node, victim)
        if needs_tpu:
            # Chip-pressure check: spawning a TPU worker whose visibility
            # would overlap chips pinned by LIVE workers trades isolation
            # for "device in use" crashes (libtpu holds devices for process
            # lifetime). If disjoint chips can't be granted, reap an idle
            # chip-holder to replenish the pool and let the scheduler retry
            # after its death; with only busy holders, wait.
            total = int(node.resources.get("TPU", 0))
            k = max(1, tpu_chips)
            if total:
                held = node.tpu_returning
                for wid in node.workers:
                    lw = self.workers.get(wid)
                    if lw is None or not lw.tpu_capable:
                        continue
                    # An unrestricted TPU worker's JAX runtime grabbed every
                    # visible chip — it holds `total`, not zero.
                    held += len(lw.chip_ids) or total
                if total - held < k:
                    dying = None
                    for wid in node.workers:
                        w = self.workers.get(wid)
                        if (w is not None and w.state == "idle"
                                and w.chip_ids):
                            if dying is None or \
                                    len(w.chip_ids) < len(dying.chip_ids):
                                dying = w
                    if dying is not None:
                        dying.state = "dying"  # matcher must skip it now
                        asyncio.get_running_loop().create_task(
                            self._shutdown_worker(dying))
                        return True  # chips free on its death; retry then
                    # Chips on their way back wake the scheduler on arrival.
                    return node.spawning > 0 or node.tpu_returning > 0
        node.spawning += 1
        if needs_tpu:
            node.spawning_tpu += 1
        spawn_token = uuid.uuid4().hex
        if want_env:
            node.spawning_envs[want_env] = (
                node.spawning_envs.get(want_env, 0) + 1)
            self._spawn_env_hash[spawn_token] = want_env
        if node.agent_conn is not None:
            # Delegate to the host agent (lease-style spawn: the reference's
            # raylet owns its worker pool, worker_pool.h:159; the controller
            # only grants the lease).
            self._agent_spawns[spawn_token] = node.node_id
            if needs_tpu:
                self._tpu_spawn_tokens.add(spawn_token)
            sys_path = os.pathsep.join(p or os.getcwd() for p in sys.path)
            asyncio.get_running_loop().create_task(
                node.agent_conn.send(
                    {
                        "kind": "spawn_worker",
                        "spawn_token": spawn_token,
                        "tpu": needs_tpu,
                        "tpu_chips": max(1, tpu_chips) if needs_tpu else 0,
                        "sys_path": sys_path,
                        "runtime_env": runtime_env,
                    }
                )
            )
            return True
        chips = None
        if needs_tpu:
            self._tpu_spawn_tokens.add(spawn_token)
            # Unit-instance chip assignment (reference: per-instance GPU
            # accounting + CUDA_VISIBLE_DEVICES; tpu.py TPU_VISIBLE_CHIPS):
            # the worker sees only its chips. Freed when the worker dies.
            chips = worker_env.grant_chips(node.tpu_free, max(1, tpu_chips))
            if chips:
                self._chip_alloc[spawn_token] = chips
        env = worker_env.worker_env(
            controller=f"{self.host}:{self.port}", node_id=node.node_id,
            spawn_token=spawn_token, tpu_chips=chips,
            node_chips=int(node.resources.get("TPU", 0)),
            sys_path=os.pathsep.join(p or os.getcwd() for p in sys.path),
            runtime_env=runtime_env)
        if runtime_env and runtime_env.get("container"):
            # Worker-in-container (reference runtime_env/container.py):
            # wrap the launch in the configured container runtime. A
            # missing runtime binary fails the env's tasks with a clear
            # error instead of a silent uncontained spawn.
            async def _spawn_container():
                from . import runtime_env as renv

                cmd = renv.container_command(
                    runtime_env, [sys.executable, "-m",
                                  "ray_tpu.core.worker_main"])
                try:
                    proc = self._launch_worker(cmd, env, spawn_token)
                except OSError as e:
                    node.spawning = max(0, node.spawning - 1)
                    self._release_env_spawn(node, spawn_token)
                    self._free_spawn_chips(node, spawn_token)
                    self._fail_env_tasks(
                        runtime_env.get("hash", ""),
                        RuntimeError(
                            f"container runtime {cmd[0]!r} unavailable: "
                            f"{e}"))
                    self._wake_scheduler()
                    return
                self._spawned_procs[spawn_token] = proc
                asyncio.get_running_loop().create_task(
                    self._watch_spawn(node.node_id, spawn_token, proc))

            asyncio.get_running_loop().create_task(_spawn_container())
            return True
        if runtime_env and (runtime_env.get("pip")
                            or runtime_env.get("conda")):
            # venv/conda materialization can take tens of seconds: run it
            # off the event loop, then launch with that env's interpreter.
            async def _spawn_with_venv():
                from . import runtime_env as renv

                try:
                    python = await asyncio.to_thread(
                        renv.spawner_python, runtime_env)
                except Exception as e:
                    sys.stderr.write(
                        f"[controller] runtime env build failed: {e!r}\n")
                    node.spawning = max(0, node.spawning - 1)
                    if spawn_token in self._tpu_spawn_tokens:
                        self._tpu_spawn_tokens.discard(spawn_token)
                        node.spawning_tpu = max(0, node.spawning_tpu - 1)
                    self._release_env_spawn(node, spawn_token)
                    self._free_spawn_chips(node, spawn_token)
                    self._fail_env_tasks(runtime_env.get("hash", ""), e)
                    self._wake_scheduler()
                    return
                proc = self._launch_worker(
                    [python, "-m", "ray_tpu.core.worker_main"], env,
                    spawn_token)
                self._spawned_procs[spawn_token] = proc
                asyncio.get_running_loop().create_task(
                    self._watch_spawn(node.node_id, spawn_token, proc))

            asyncio.get_running_loop().create_task(_spawn_with_venv())
            return True
        try:
            proc = self._launch_worker(
                [sys.executable, "-m", "ray_tpu.core.worker_main"], env,
                spawn_token)
        except OSError:
            # Unwind: a failed launch must not leak the carved-out chips
            # or the spawning counters.
            node.spawning = max(0, node.spawning - 1)
            if spawn_token in self._tpu_spawn_tokens:
                self._tpu_spawn_tokens.discard(spawn_token)
                node.spawning_tpu = max(0, node.spawning_tpu - 1)
            self._release_env_spawn(node, spawn_token)
            self._free_spawn_chips(node, spawn_token)
            return False
        self._spawned_procs[spawn_token] = proc
        # The worker registers itself carrying the token (exact adoption in
        # _h_register); this task only reaps processes that die pre-register.
        asyncio.get_running_loop().create_task(self._watch_spawn(node.node_id, spawn_token, proc))
        return True

    def _launch_worker(self, cmd: List[str], env: Dict[str, str],
                       spawn_token: str) -> subprocess.Popen:
        """Start a worker process; `spawned_ns` on the handle is where
        `ctrl.worker_spawn` (to that worker's registration) starts."""
        t0 = time.monotonic_ns()  # before the fork: it holds the process's start
        proc = subprocess.Popen(
            cmd, env=env, stdout=self._worker_log_file(spawn_token),
            stderr=subprocess.STDOUT)
        proc.spawned_ns = t0
        return proc

    def _free_spawn_chips(self, node: Optional[NodeInfo],
                          spawn_token: str) -> None:
        """Return a never-started/never-registered local spawn's chip grant
        to the node pool."""
        ids = self._chip_alloc.pop(spawn_token, [])
        if ids and node is not None:
            node.tpu_free.extend(ids)

    def _worker_log_file(self, spawn_token: str):
        from .worker_logs import worker_log_file

        return worker_log_file(spawn_token)

    async def _watch_spawn(self, node_id: str, spawn_token: str, proc: subprocess.Popen) -> None:
        # ~2 min of polling: generous for a loaded CI host (TPU workers
        # import jax, ~3-10s; venv workers build first), but bounded so the
        # kill-on-exhaustion below can't hit a healthy slow starter.
        for _ in range(1200):
            await asyncio.sleep(0.1)
            if spawn_token not in self._spawned_procs:
                return  # adopted by a registered worker
            if proc.poll() is not None:
                self._spawned_procs.pop(spawn_token, None)
                node = self.nodes.get(node_id)
                if node:
                    node.spawning = max(0, node.spawning - 1)
                    if spawn_token in self._tpu_spawn_tokens:
                        node.spawning_tpu = max(0, node.spawning_tpu - 1)
                # Died before registering: its chips were never adopted.
                self._free_spawn_chips(node, spawn_token)
                self._release_env_spawn(node, spawn_token)
                self._tpu_spawn_tokens.discard(spawn_token)
                self._wake_scheduler()
                return
        # Watch window exhausted with the process still alive and
        # unregistered: a 60s silent startup is pathological (reference:
        # worker_pool startup timeouts kill slow starters). Kill it and
        # unwind — freeing the chip grant while the process lived on could
        # double-allocate its chips if it registered late.
        if spawn_token in self._spawned_procs:
            try:
                proc.terminate()
            except Exception:
                pass
            for _ in range(20):  # up to 2s for a graceful exit
                await asyncio.sleep(0.1)
                if proc.poll() is not None:
                    break
            else:
                try:
                    proc.kill()
                except Exception:
                    pass
                for _ in range(50):  # SIGKILL is definitive, reap it
                    await asyncio.sleep(0.1)
                    if proc.poll() is not None:
                        break
            self._spawned_procs.pop(spawn_token, None)
            node = self.nodes.get(node_id)
            if node:
                node.spawning = max(0, node.spawning - 1)
                if spawn_token in self._tpu_spawn_tokens:
                    node.spawning_tpu = max(0, node.spawning_tpu - 1)
            if proc.poll() is not None:
                proc.wait()  # reap the zombie
                # Chips return ONLY once the process is truly gone: a
                # still-alive process may hold the devices open, and
                # re-granting its chips double-allocates them.
                self._free_spawn_chips(node, spawn_token)
            else:
                self._chip_alloc.pop(spawn_token, None)
                sys.stderr.write(
                    f"[controller] spawned worker {spawn_token[:8]} "
                    f"survived SIGKILL; leaking its chip grant rather than "
                    f"double-allocating\n")
            self._release_env_spawn(node, spawn_token)
            self._tpu_spawn_tokens.discard(spawn_token)
            self._wake_scheduler()

    async def _dispatch(self, spec: Dict[str, Any], node: NodeInfo, w: WorkerInfo) -> None:
        # Wall-clock dispatch stamp: the hang watchdog ages running work
        # against it (wall clock so it stays meaningful across a bounce).
        spec["__dispatch_ts"] = time.time()
        self._record_task_event(spec, "running", worker_id=w.worker_id,
                                node_id=node.node_id)
        if spec.get("is_actor_creation"):
            actor = self.actors[spec["actor_id"]]
            actor.worker_id = w.worker_id
            actor.node_id = node.node_id
            actor.reserved = True
            # bundle_index=-1 rebinds to the bundle actually used at
            # placement; the actor's release must credit that bundle.
            actor.pg = spec.get("pg", actor.pg)
            w.state = "actor"
            w.actor_ids.add(actor.actor_id)
            await w.conn.send({"kind": "instantiate_actor", "spec": spec})
        else:
            w.state = "task"
            w.current_task = spec["task_id"]
            w.task_started = time.monotonic()
            await w.conn.send({"kind": "execute_task", "spec": spec})

    def _release_task_resources(self, spec: Dict[str, Any]) -> None:
        node = self.nodes.get(spec.get("sched_node", ""))
        if node is None:
            return
        resources = dict(spec.get("resources", {}))
        if spec.get("blocked"):
            resources.pop("CPU", None)  # CPU already released at block time
        self._release_reservation(resources, node, spec.get("pg"))

    def _release_reservation(
        self, resources: Dict[str, float], node: NodeInfo, pg_ref: Optional[Tuple[str, int]]
    ) -> None:
        if pg_ref is not None:
            pg = self.pgs.get(pg_ref[0])
            if pg is not None and pg.state == "ready":
                _res_add(pg.bundles[pg_ref[1]].available, resources)
            # PG removed/pending: the bundle's full reservation was (or will
            # be) returned to the node wholesale at remove time — releasing
            # here too would double-credit the node and oversubscribe it.
            return
        _res_add(node.available, resources)


# ------------------------------------------------------------------ exceptions


class RayTpuError(Exception):
    pass


class GetTimeoutError(RayTpuError, TimeoutError):
    pass


class WorkerCrashedError(RayTpuError):
    pass


class NodePreemptedError(WorkerCrashedError):
    """The hosting node left the cluster on a PLANNED departure — a spot
    preemption notice, a manual `rtpu drain`, or autoscaler idle
    scale-down. Carries ``preempted = True`` so planned departures never
    consume task ``max_retries`` / actor ``max_restarts`` budgets
    (reference: the DrainNode protocol's graceful-departure semantics vs
    unexpected node failure)."""

    preempted = True


class ActorDiedError(RayTpuError):
    pass


class ActorNotHostedError(ActorDiedError):
    """A worker REFUSED an actor call because it no longer hosts the actor
    (it migrated off a draining node, or was killed). The refusal happens
    before any user code runs, so the call PROVABLY never executed —
    callers may safely resubmit it through the controller, which routes to
    the actor's new host (or buffers while it re-creates)."""


class OutOfMemoryError(RayTpuError):
    """A worker was killed by the memory monitor to relieve host memory
    pressure (reference: ray.exceptions.OutOfMemoryError +
    src/ray/common/memory_monitor.h)."""


class TaskCancelledError(RayTpuError):
    """The task was cancelled via ray_tpu.cancel (reference:
    ray.exceptions.TaskCancelledError)."""


class DeadlineExceededError(RayTpuError, TimeoutError):
    """The request's end-to-end deadline passed before (or while) it ran.
    Raised at every queue boundary — scheduler pop, actor-mailbox dequeue,
    serve router/replica/batcher — so expired work is dropped instead of
    executed (reference: Serve request timeouts + gRPC DEADLINE_EXCEEDED
    semantics)."""


class ObjectLostError(RayTpuError):
    """The bytes of an object died with their host and no lineage could
    reconstruct them (reference: ray.exceptions.ObjectLostError)."""


class RuntimeEnvSetupError(RayTpuError):
    """A task's runtime environment could not be materialized (reference:
    ray.exceptions.RuntimeEnvSetupError)."""


class DependencyError(RayTpuError):
    pass


class TaskError(RayTpuError):
    """Wraps an exception raised inside a remote task (reference: RayTaskError)."""

    def __init__(self, label: str, cause: Exception, traceback_str: str = ""):
        super().__init__(f"task {label} failed: {cause!r}\n{traceback_str}")
        self.label = label
        self.cause = cause
        self.traceback_str = traceback_str

    def __reduce__(self):
        return (TaskError, (self.label, self.cause, self.traceback_str))
