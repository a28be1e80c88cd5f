"""Central registry of every ray_tpu configuration knob.

One place defining each ``RTPU_*`` environment flag with its type, default,
and documentation — the reference concentrates its ~217 knobs in
``src/ray/common/ray_config_def.h`` for the same reason: scattering
``os.environ.get(...)`` at point of use means no single list of what can be
tuned, no defaults audit, and typo'd names that silently fall back.

Rules:
- Every module reads flags through :func:`get` (call-time lookup, so flags
  set by a parent before spawning a worker, or by a test, are honored).
- Writes (the few flags that double as process-tree plumbing, e.g.
  ``RTPU_HOST_ID``) go through :func:`set_env` / :func:`unset_env`.
- External variables we consume-but-don't-own (``JAX_PLATFORMS``,
  ``XLA_FLAGS``, ``TPU_ACCELERATOR_TYPE``) are registered as EXTERNAL for
  documentation and read through the same accessors.
- ``child_env()`` is the sanctioned way to snapshot the environment when
  spawning subprocesses.

``python -m ray_tpu.flags`` prints the full flag table.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional


@dataclasses.dataclass(frozen=True)
class Flag:
    name: str
    type: type
    default: Any
    doc: str
    external: bool = False  # owned by another system (jax/libtpu/GCE)


REGISTRY: Dict[str, Flag] = {}


def _define(name: str, type_: type, default: Any, doc: str,
            external: bool = False) -> None:
    REGISTRY[name] = Flag(name, type_, default, doc, external)


# -- session / addressing ----------------------------------------------------
_define("RTPU_ADDRESS", str, None,
        "Controller address host:port a driver connects to when "
        "init(address=...) is not given (reference RAY_ADDRESS).")
_define("RTPU_CONTROLLER", str, None,
        "Controller address injected into spawned workers/job drivers; "
        "internal process-tree plumbing.")
_define("RTPU_NODE_ID", str, None,
        "Node id a spawning agent assigns to its workers (internal).")
_define("RTPU_HOST_ID", str, None,
        "Logical host id of this process; set by the host agent so object "
        "plane chooses shm vs TCP pulls (multi-host tests force distinct "
        "ids to exercise real transfers).")
_define("RTPU_SPAWN_TOKEN", str, None,
        "Opaque token tying a spawned worker back to its lease (internal).")
_define("RTPU_SYS_PATH", str, None,
        "Extra sys.path entry for workers (working_dir runtime env).")
_define("RTPU_STATE_PATH", str, None,
        "Controller persistence snapshot path; enables restart recovery.")
_define("RTPU_TPU_WORKER", bool, False,
        "Set by the spawner on a worker started for a TPU request "
        "(core/worker_env.py): the process owns the chips named by "
        "TPU_VISIBLE_CHIPS for its lifetime, runs with JAX_PLATFORMS=tpu so "
        "its first JAX call starts the TPU backend or raises, shares the "
        "persistent compile cache, and registers as TPU-capable. Never set "
        "on plain workers, which are pinned to the cpu platform.")

_define("RTPU_DIRECT_DISPATCH", bool, True,
        "Push actor calls directly to the hosting worker (lease-then-push); "
        "0 routes every call through the controller.")
_define("RTPU_CONTAINER_RUNTIME", str, "podman",
        "Container runtime binary used to wrap worker launches when a "
        "runtime_env requests 'container' (reference: worker-in-podman).")
_define("RTPU_TASK_LEASE_MAX", int, 16,
        "Max leased workers per (resources, env) signature for direct "
        "stateless-task dispatch; 0 disables task leasing entirely.")
_define("RTPU_LEASE_BLOCK", int, 8,
        "Workers requested per lease_block controller RPC: one round trip "
        "grants a block of direct-dispatch workers for a (resources, env) "
        "signature, so a submission wave fans across the pool with no "
        "further controller involvement (reference: the raylet granting "
        "leases per scheduling class, direct_task_transport.h:75). 1 "
        "degenerates to the old one-lease-per-RPC negotiation.")
_define("RTPU_SUBMIT_BATCH", bool, True,
        "Coalesce direct task/actor-call pushes, their completion acks, "
        "and result-location publishes into multi-entry framed messages: "
        "specs submitted in the same event-loop beat ride one pickle and "
        "one syscall per hop (reference: the batched lease/push RPCs in "
        "direct_task_transport + CoreWorker's batched task-status "
        "reports). 0 reverts to one message per call; the submit path "
        "then pays one flag check.")
_define("RTPU_SUBMIT_BATCH_MAX", int, 512,
        "Entries per open submit batch: a batch reaching this many pending "
        "specs is sealed and a new one opened, bounding both frame size "
        "and the per-batch reply payload.")
_define("RTPU_DISTRIBUTED_REFS", bool, True,
        "Distributed ownership: ObjectRef handles are counted per process, "
        "borrowers register with owners worker-to-worker, and drained "
        "objects are freed with one batched controller message. 0 reverts "
        "to never-free-until-pressure semantics.")
_define("RTPU_FREE_DELAY_S", float, 1.0,
        "Grace window between an object draining (no handles, borrowers or "
        "holds anywhere) and the batched free, absorbing in-flight races.")
_define("RTPU_HOLD_RELEASE_GRACE_S", float, 2.0,
        "Grace before a submit-hold is released on locally OBSERVING a "
        "task's outcome (vs the worker's ordered release message): bounds "
        "how late an executing worker's borrow_add may arrive.")
_define("RTPU_DIRECT_BIND", str, None,
        "Interface the worker direct-dispatch server binds. Default: the "
        "local address of the worker's controller connection, so loopback "
        "clusters never expose the direct endpoint off-host.")

_define("RTPU_SCHED_HYBRID_THRESHOLD", float, 0.5,
        "Hybrid scheduling threshold: nodes below this CPU utilization are "
        "packed in index order; above it, placement spreads by load "
        "(reference hybrid_scheduling_policy).")
_define("RTPU_SCHED_TOP_K", int, 1,
        "Randomize DEFAULT placement among the best k nodes (anti-herding "
        "at scale); 1 keeps placement deterministic.")
_define("RTPU_EVENT_EXPORT_PATH", str, None,
        "Append structured control-plane events (task/actor/node "
        "lifecycle) as JSONL to this file for external pipelines "
        "(reference export-event files).")
_define("RTPU_TRACING", bool, False,
        "OpenTelemetry span propagation through task submission "
        "(util/tracing.py setup_tracing); workers inherit via env.")
_define("RTPU_SPILLBACK_MEM_FRACTION", float, 0.97,
        "A worker whose host memory use exceeds this fraction rejects "
        "dispatched tasks back to the scheduler (raylet spillback shape); "
        "0 disables admission checks.")

# -- controller tunables -----------------------------------------------------
_define("RTPU_MAX_WORKERS_PER_NODE", int, 32,
        "Upper bound on workers the controller spawns per node.")
_define("RTPU_LINEAGE_MAX", int, 10000,
        "Bounded lineage log length for object reconstruction.")
_define("RTPU_TASK_EVENTS_MAX", int, 50000,
        "Ring-buffer size of task events feeding the state API/timeline.")
_define("RTPU_METRICS_PORT", int, 0,
        "Controller Prometheus port (0 = disabled).")
_define("RTPU_MAX_RECONSTRUCTIONS", int, 3,
        "Max lineage re-executions per object before giving up.")
_define("RTPU_NODE_TIMEOUT_S", float, 10.0,
        "Heartbeat silence after which a node is marked SUSPECT: the "
        "scheduler stops placing work on it and actor calls buffer, but "
        "nothing is killed — a healed partition rejoins without actor "
        "churn (reference: the SWIM-style suspect phase in front of "
        "gcs_health_check_manager death declarations).")
_define("RTPU_DEAD_TIMEOUT_S", float, 30.0,
        "Heartbeat silence after which a suspect node is declared DEAD "
        "and its work re-queues/restarts elsewhere. The suspect->dead "
        "two-phase detector means a partition shorter than this heals "
        "with no duplicate actor instance and no double-allocation; "
        "must be >= RTPU_NODE_TIMEOUT_S (clamped if not).")
_define("RTPU_HEARTBEAT_S", float, 2.0,
        "Host-agent heartbeat period.")
_define("RTPU_MEMORY_MONITOR", bool, True,
        "Kill a worker when a host crosses the memory threshold "
        "(reference memory_monitor + retriable-FIFO kill policy).")
_define("RTPU_MEMORY_USAGE_THRESHOLD", float, 0.95,
        "Host memory fraction that triggers the memory monitor.")
_define("RTPU_MEMORY_MONITOR_S", float, 2.0,
        "Memory monitor sampling period.")

# -- controller fault tolerance ----------------------------------------------
_define("RTPU_RECONNECT_MAX_S", float, 20.0,
        "Total time a disconnected client/worker/host-agent keeps retrying "
        "the controller before giving up (reference: GCS client reconnect "
        "window, gcs_rpc_server reconnection timeout). Workers and agents "
        "fate-share once the deadline passes; drivers raise ConnectionError.")
_define("RTPU_RECONNECT_BACKOFF_S", float, 0.1,
        "Initial reconnect backoff; doubles per attempt, capped at 2s.")
_define("RTPU_RECONNECT_GRACE_S", float, 2.0,
        "After a controller restart with persisted state, how long restored "
        "detached actors wait for their (possibly still-alive) hosting "
        "workers to reconnect and re-claim them before being re-created "
        "from scratch (reference: GCS waits for raylet re-registration on "
        "NotifyGCSRestart before reconstructing actors).")
_define("RTPU_TESTING_RPC_DELAY_MS", str, None,
        "Fault-injection: per-message-kind handler delays, e.g. "
        "'register=200,heartbeat=50' or '*=20' (reference: "
        "RAY_testing_asio_delay_us). Applied server-side in the protocol "
        "layer before the handler runs; testing only.")
_define("RTPU_TESTING_RPC_DROP", str, None,
        "Fault-injection: per-message-kind DROP probabilities, e.g. "
        "'submit_actor_task=0.3,*=0.05'. A dropped message is read off "
        "the wire and silently discarded before its handler runs — no "
        "response is ever sent, modeling a lossy/partitioned network. "
        "Survivable only for idempotent request kinds retried under "
        "RTPU_RPC_TIMEOUT_S; testing only.")
_define("RTPU_TESTING_NET_ID", str, None,
        "Fault-injection: this process's identity for NetworkPartitioner "
        "blackholes (testing.NetworkPartitioner). Inherited by spawned "
        "children, so tagging a host agent partitions its whole host.")
_define("RTPU_TESTING_PARTITION_FILE", str, None,
        "Fault-injection: JSON file naming partitioned net ids "
        "({\"isolated\": [...]}). A process whose RTPU_TESTING_NET_ID is "
        "listed drops every inbound AND outbound protocol frame (a "
        "symmetric blackhole: TCP stays open, bytes vanish) until the "
        "entry is removed; testing only.")
_define("RTPU_RPC_TIMEOUT_S", float, 0.0,
        "Per-request control-plane timeout with capped exponential "
        "backoff retry: a blocking client request that gets no response "
        "within this window treats the connection as suspect, re-dials, "
        "and re-sends (submit handlers are idempotent by task/actor id, "
        "so blind re-sends never double-execute). 0 (default) disables — "
        "requests wait indefinitely, as before; enable on partition- or "
        "loss-prone networks (chaos tests set it).")

# -- node drain / preemption -------------------------------------------------
_define("RTPU_DRAIN_DEADLINE_S", float, 30.0,
        "Default grace window a draining node gives its running tasks "
        "before they are killed and re-queued (the DrainNode deadline; "
        "reference autoscaler.proto DrainNode deadline_timestamp_ms). "
        "Callers of drain_node may override per drain.")
_define("RTPU_PREEMPTION_WATCHER", bool, False,
        "Host agent polls the cloud metadata preemption endpoint and "
        "self-drains (reason='preemption') when an imminent-preemption "
        "notice appears, so a spot/preemptible TPU VM migrates its work "
        "instead of crashing. Off by default: only meaningful on "
        "preemptible capacity.")
_define("RTPU_PREEMPTION_URL", str,
        "http://metadata.google.internal/computeMetadata/v1/instance/"
        "preempted",
        "Metadata endpoint the preemption watcher polls. A body of "
        "TRUE/FALSE (the GCE contract) — any other non-empty truthy body "
        "also counts as a notice. Tests point this at a "
        "testing.PreemptionInjector fake.")
_define("RTPU_PREEMPTION_POLL_S", float, 1.0,
        "Preemption watcher polling period.")

# -- actor checkpoints / exactly-once replay ---------------------------------
_define("RTPU_ACTOR_CHECKPOINT", bool, True,
        "Durable actor checkpoints: actors created with "
        "checkpoint_interval_s / checkpoint_every_n periodically "
        "serialize their state (plus the exactly-once call journal) to a "
        "host-local file and ship an async copy to the controller, so a "
        "crash restart restores the newest reachable checkpoint instead "
        "of re-running the constructor (reference: gcs_actor_manager "
        "restart + the Ray paper's actor checkpointing story). 0 "
        "disables the subsystem entirely: no checkpoint threads exist "
        "and the per-call path pays one flag check at actor creation.")
_define("RTPU_CHECKPOINT_DIR", str, None,
        "Directory for host-local actor checkpoint files (default: a "
        "per-host dir under the system temp root). Shared by every "
        "worker on the host so a restarted actor placed on the same "
        "host can restore a checkpoint newer than the controller's "
        "shipped copy.")
_define("RTPU_CHECKPOINT_TICK_S", float, 0.25,
        "Worker-side sweep period for interval-based actor checkpoints "
        "(the timer thread only exists while a checkpointing actor with "
        "checkpoint_interval_s is hosted).")

# -- object transfer (inter-node pulls / broadcast) --------------------------
_define("RTPU_PULL_STREAM", bool, True,
        "Streamed inter-node object pulls: one pull_stream request ships "
        "every chunk back-to-back under a credit window instead of one "
        "request/response round trip per chunk (reference: the object "
        "manager's chunked Push/Pull, object_manager.proto). 0 reverts to "
        "the serial per-chunk loop; the pull path then pays one flag check.")
_define("RTPU_PULL_CHUNK", int, 4 * 1024 * 1024,
        "Chunk size in bytes for inter-node object transfer (streamed and "
        "serial pulls, broadcast chains).")
_define("RTPU_PULL_WINDOW", int, 8,
        "Credit window for streamed pulls / broadcast chains: how many "
        "chunks may be in flight before the sender waits for the "
        "receiver's consumption credits.")
_define("RTPU_PULL_PARALLEL", int, 2,
        "Max concurrent source hosts one pull fans across when the "
        "controller knows replica locations (broadcast copies). 1 "
        "disables range-splitting.")
_define("RTPU_WORKER_SERVE", bool, True,
        "Producing processes serve their own objects' bytes over their "
        "existing direct-call/ref server (Ray's plasma + pull-manager "
        "split: the controller keeps location metadata only). Consumers "
        "fall back to the host agent when the producer is gone. 0 routes "
        "every cross-host pull through the host agent.")

# -- compiled DAG channels ---------------------------------------------------
_define("RTPU_DAG_CHANNELS", bool, True,
        "Compiled DAGs execute over reusable mutable channels: one shm "
        "slot ring (same-host edges) or persistent raw-tail stream "
        "(cross-host edges) per DAG edge, with a resident per-actor loop "
        "on the worker, so steady-state execute() is a header write + one "
        "doorbell with zero controller involvement (reference: aDAG's "
        "MutableObjectManager channels, SURVEY.md §2.2). 0 falls back to "
        "per-execute task submission through the normal submit path.")
_define("RTPU_DAG_SLOT_BYTES", int, 128 * 1024,
        "Payload capacity of one shm channel slot. A value that pickles "
        "larger than this ships via a one-off sidecar shm segment named "
        "in the slot (still zero controller involvement); size it to the "
        "common per-edge payload so the sidecar path stays cold.")
_define("RTPU_DAG_SPIN_US", int, 200,
        "How long a channel reader/writer spins on the seqno header "
        "before arming its doorbell and blocking — spinning covers the "
        "common back-to-back case without syscalls; 0 blocks immediately "
        "(right for oversubscribed 1-core hosts).")
_define("RTPU_DAG_STALL_S", float, 2.0,
        "How long a compiled-DAG get() tolerates zero channel progress "
        "before probing participant liveness (direct dag_status pings, "
        "then resolve_actor). Probes run only when stalled, so the "
        "steady state stays controller-free; a dead/restarted "
        "participant tears the DAG down with DAGTeardownError.")
_define("RTPU_DAG_RECOVERY", bool, True,
        "Compiled DAGs heal in place: when the stall probe finds a dead "
        "restartable participant, the driver quiesces the pipeline, waits "
        "for the controller's actor-restart path (restoring the stage's "
        "durable checkpoint when one is configured), rebuilds only the "
        "affected edges under a bumped ring epoch, and replays retained "
        "items from the last seqno each reader applied, so every "
        "microbatch is delivered exactly once. Non-restartable stages "
        "(max_restarts=0) and an exhausted restart budget still raise "
        "DAGTeardownError; 0 restores the PR 10 fail-fast semantics.")
_define("RTPU_DAG_RECOVERY_TIMEOUT_S", float, 60.0,
        "How long a recovering DAG waits for a dead stage actor to come "
        "back alive (restart scheduling + checkpoint restore) before "
        "giving up and tearing down with DAGTeardownError.")
_define("RTPU_DAG_METER", bool, True,
        "Channel-fabric telemetry: every shm slot ring carries per-writer/"
        "per-reader counter blocks (items, bytes, blocked/starved ns) and "
        "every resident stage loop accounts recv/compute/send phase time, "
        "sampled out-of-band on the worker's metrics-flush heartbeat into "
        "rtpu_dag_edge_*/rtpu_dag_stage_* TSDB families (`rtpu dag "
        "stats`, `rtpu top`, state.dag_timeline()). The hot path adds "
        "only plain cache-line counter stores plus a few amortized "
        "monotonic clock reads; 0 removes even those (perf-guarded in "
        "test_perf_regression.py).")

# -- streaming data plane fault tolerance ------------------------------------
_define("RTPU_DATA_FT", bool, True,
        "Fault-tolerant streaming data plane: actor-pool stages detect "
        "dead/preempted pool actors on the in-flight ref, replace the "
        "actor in place and resubmit the affected batch (bounded by "
        "RTPU_DATA_FT_RETRIES; preempted deaths never burn the budget), "
        "pools proactively migrate off draining nodes, and all-to-all "
        "shards lost to node death re-derive from their recorded "
        "producing call (riding the controller's lineage path first). "
        "0 reproduces the legacy fail-fast plane byte-for-byte; every "
        "stage then pays one flag check at stage start.")
_define("RTPU_DATA_FT_RETRIES", int, 3,
        "Per-batch retry budget of a self-healing actor-pool stage: how "
        "many times one input block may be resubmitted after its pool "
        "actor CRASHED before the stage surfaces the error. Preempted "
        "deaths (drain/spot reclamation) re-submit without consuming "
        "the budget — planned departures are not failures (the PR 4 "
        "drain semantics applied to the data plane).")
_define("RTPU_DATA_DRAIN_POLL_S", float, 1.0,
        "How often (at most) an actor-pool stage refreshes the cluster's "
        "draining-node set while submitting work. A pool actor observed "
        "on a draining node is proactively replaced (new actor placed by "
        "the scheduler, which already excludes draining nodes) instead "
        "of waiting for the drain deadline to kill it mid-batch. 0 "
        "disables the poll; pools then heal only reactively.")

# -- object store / spilling -------------------------------------------------
_define("RTPU_NATIVE_STORE", bool, True,
        "Use the C++ shm arena when available (0 forces pickle fallback).")
_define("RTPU_STORE_LIB", str, None,
        "Alternate librtpu_store build to load (sanitizer variants).")
_define("RTPU_ARENA", str, None,
        "Name of the shm arena segment (internal, set by the creator).")
_define("RTPU_ARENA_SIZE", int, 1 << 30,
        "Arena segment size in bytes.")
_define("RTPU_FORCE_INLINE", bool, False,
        "Force inline (in-band) object payloads; chaos/multinode tests.")
_define("RTPU_SPILL_DIR", str, None,
        "Directory for spilled objects (enables arena spilling).")
_define("RTPU_SPILL_HIGH", float, 0.8,
        "Arena fill fraction that triggers spilling.")
_define("RTPU_SPILL_LOW", float, 0.6,
        "Arena fill fraction spilling drains down to.")
_define("RTPU_SPILL_DELETE_GRACE_S", float, 10.0,
        "Grace period before spilled files of freed objects are deleted.")

# -- runtime env -------------------------------------------------------------
_define("RTPU_RUNTIME_ENV", str, None,
        "Serialized runtime env JSON applied inside a worker (internal).")
_define("RTPU_RUNTIME_ENV_CACHE", str, None,
        "Cache dir for working_dir zips and pip venvs "
        "(default ~/.ray_tpu/runtime_env).")
_define("RTPU_WORKING_DIR_MAX_BYTES", int, 100 * 1024 * 1024,
        "Refuse to package working_dirs larger than this "
        "(reference default cap).")

# -- accelerators / jax ------------------------------------------------------
_define("RTPU_NUM_TPUS", int, None,
        "Override detected local TPU chip count.")
_define("RTPU_TPU_GENERATION", str, None,
        "Override detected TPU generation (v4/v5e/v5p/v6e).")
_define("RTPU_WORKFLOW_STORAGE", str, None,
        "Workflow durability root (default ~/.ray_tpu/workflows).")

_define("RTPU_SP_MODE", str, "ring",
        "Context-parallel attention scheme over the seq mesh axis: "
        "ring | ulysses | auto (ulysses when head counts divide the axis).")

# -- observability -----------------------------------------------------------
_define("RTPU_METRICS_FLUSH_S", float, 1.0,
        "Flush period for app metrics (util/metrics.py) to the controller.")
_define("RTPU_TASK_EVENTS", bool, True,
        "Worker-side task flight recorder: per-task phase timestamps "
        "(scheduling delay, queue wait, arg fetch, execute, result store) "
        "buffered and shipped to the controller in batches together with "
        "finished tracing spans (reference: TaskEventBuffer -> "
        "GcsTaskManager, task_event_buffer.h:206). 0 disables recording "
        "entirely; the hot path then pays one flag check.")
_define("RTPU_TASK_EVENTS_FLUSH_S", float, 0.5,
        "Flight-recorder flush period: how often a worker ships its "
        "buffered phase events + spans to the controller.")
_define("RTPU_TASK_EVENTS_BUF", int, 4096,
        "Per-worker flight-recorder buffer (bounded deque): oldest phase "
        "events drop first when the controller is unreachable longer than "
        "the buffer covers.")
_define("RTPU_SPANS_MAX", int, 20000,
        "Controller-side ring of finished tracing spans shipped by worker "
        "flight recorders (serves get_cluster_spans()).")
_define("RTPU_LOG_TO_DRIVER", bool, True,
        "Tee worker stdout/stderr to connected drivers' consoles.")
_define("RTPU_WORKER_LOG_MAX", int, 16 * 1024 * 1024,
        "Rotate a worker's log file to a .1 backup when it exceeds this "
        "on (re)open (the sidecar attribution index rotates with it).")
_define("RTPU_LOG_ATTRIBUTION", bool, True,
        "Stamp worker log files with task/actor attribution markers and "
        "maintain a per-file task->byte-range index so `rtpu logs "
        "--task-id` fetches one task's output without scanning "
        "(reference: the log_monitor magic-line protocol). 0 disables; "
        "the write path then pays one flag check per write.")
_define("RTPU_EVENTS", bool, True,
        "Cluster event subsystem (core/events.py): structured node/actor/"
        "task/placement-group/autoscaler lifecycle events in a bounded "
        "controller ring, persisted as JSONL alongside --state-path and "
        "served by `rtpu events` / state.list_events (reference: `ray "
        "list cluster-events` + the dashboard event feed). 0 disables; "
        "emit sites then pay one flag check.")
_define("RTPU_EVENTS_MAX", int, 10000,
        "Controller-side cluster-event ring size (and the number of "
        "persisted JSONL lines reloaded after a controller bounce).")
_define("RTPU_EVENTS_FLUSH_S", float, 0.5,
        "Flush period for worker/driver-side cluster events shipped to "
        "the controller in batches.")
_define("RTPU_EVENTS_BUF", int, 2048,
        "Per-process bounded buffer of unshipped cluster events: oldest "
        "drop first when the controller is unreachable longer than the "
        "buffer covers.")
_define("RTPU_JOBS_FT", bool, True,
        "Durable job plane (core/job_manager.py + jobs.py): the controller "
        "owns a persisted job table, the per-job supervisor is a "
        "restartable checkpointed actor whose attempts survive worker "
        "SIGKILL / node death / drain preemption under a capped-"
        "exponential retry budget, job output streams into the worker-log "
        "plane, and wait_job becomes a controller long-poll (reference: "
        "GcsJobManager + dashboard/modules/job JobSupervisor semantics). "
        "0 keeps the legacy fail-fast supervisor: job dies with its "
        "worker, in-memory logs, busy-poll waits.")
_define("RTPU_JOB_MAX_ATTEMPTS", int, 3,
        "Default entrypoint attempt budget per job (submit_job "
        "max_attempts overrides). Crashed/failed attempts consume budget; "
        "attempts lost to a draining/preempted node never do (the "
        "PR 4/16 planned-departure convention).")
_define("RTPU_JOB_BACKOFF_BASE_S", float, 0.5,
        "Base delay of the capped-exponential backoff between billed job "
        "attempts (retry n sleeps min(base * 2^(n-1), RTPU_JOB_BACKOFF_"
        "MAX_S)); preemption-driven restarts relaunch immediately.")
_define("RTPU_JOB_BACKOFF_MAX_S", float, 30.0,
        "Upper bound on the exponential backoff between job attempts.")
_define("RTPU_JOB_STOP_GRACE_S", float, 3.0,
        "stop_job escalation grace: SIGTERM the entrypoint's whole "
        "process group, wait this long, then SIGKILL whatever survives "
        "(shell=True children included) and reap before returning.")
_define("RTPU_JOB_SUP_CHECKPOINT_S", float, 5.0,
        "checkpoint_interval_s applied to FT job supervisor actors: the "
        "hosting worker durably snapshots the supervisor (attempt number "
        "+ child-pid state) this often, so a restore resumes attempt "
        "accounting instead of starting cold. 0 disables supervisor "
        "checkpoints (the controller job table still survives).")
_define("RTPU_JOBS_MAX", int, 1000,
        "Bound on the controller job table: once exceeded, the oldest "
        "TERMINAL job records are evicted (running jobs are never "
        "dropped).")
_define("RTPU_JOB_ID", str, None,
        "Set by the job supervisor in every entrypoint's environment: the "
        "submission id of the job this driver belongs to. Resumable "
        "drivers key their checkpoints/DataIterator resume_key off it.",
        external=True)
_define("RTPU_JOB_ATTEMPT", str, None,
        "Set by the job supervisor in every entrypoint's environment: "
        "1-based attempt number of this launch. Attempt 1 starts cold; "
        "attempt >1 should restore from RTPU_JOB_ID-keyed state instead "
        "of restarting from scratch.",
        external=True)
_define("RTPU_HANG_WATCHDOG", bool, True,
        "Controller watchdog sweeping running tasks/actor calls for hangs "
        "and stragglers: a task older than max(RTPU_HANG_MIN_S, "
        "RTPU_HANG_P99_FACTOR x its label's exec-latency p99) emits a "
        "TASK_HUNG/TASK_STRAGGLER cluster event with an automatic "
        "all-thread stack capture from the executing worker (reference: "
        "the LlamaRL silent-hang failure mode; `ray stack` made "
        "automatic). 0 disables the sweep entirely.")
_define("RTPU_HANG_MIN_S", float, 300.0,
        "Hard floor before the hang watchdog flags any task — no label "
        "history can lower the threshold below this.")
_define("RTPU_HANG_P99_FACTOR", float, 10.0,
        "Straggler threshold multiplier over the label's observed "
        "exec-latency p99 (from the rtpu_task_exec_s histogram).")
_define("RTPU_HANG_POLL_S", float, 2.0,
        "Hang-watchdog sweep period.")
_define("RTPU_EXIT_DETAIL_BYTES", int, 2048,
        "On worker death, quote up to this many bytes of the crashed "
        "process's log tail in the task/actor error surfaced to the "
        "driver (reference: RayTaskError exit_detail); 0 disables the "
        "post-mortem fetch.")
_define("RTPU_TSDB", bool, True,
        "In-controller metrics history (core/telemetry.py): every "
        "registered metric family (core rtpu_* gauges/counters/histograms "
        "plus util/metrics.py app metrics) is sampled into a fixed-step "
        "ring buffer served by the query_metrics RPC and `rtpu top` / the "
        "dashboard sparklines (reference: the Ray dashboard's built-in "
        "time-series view). 0 disables the sampler loop entirely; "
        "query_metrics then reports disabled.")
_define("RTPU_TSDB_STEP_S", float, 5.0,
        "Telemetry sampling step: one point per series per step.")
_define("RTPU_TSDB_RETAIN", int, 720,
        "Points retained per series (ring buffer length); with the "
        "default 5s step this holds one hour of history.")
_define("RTPU_TSDB_PERSIST_S", float, 15.0,
        "How often the telemetry ring (and alert state) is persisted "
        "beside --state-path so history survives a controller bounce. "
        "0 persists only on clean shutdown.")
_define("RTPU_ALERT_RULES", str, None,
        "JSON list of alert rules evaluated over the telemetry ring each "
        "sampling step, merged by name over the built-in defaults "
        "(telemetry.DEFAULT_ALERT_RULES). Rule: {name, metric, stat?, "
        "tags?, op, threshold, for_s, severity?, message?, disabled?}. "
        "Firing/resolving rules emit ALERT_FIRING/ALERT_RESOLVED cluster "
        "events (rtpu events --kind ALERT_FIRING).")
_define("RTPU_PROFILER", bool, True,
        "Cluster flamegraph profiler (core/profiler.py): the profile RPC "
        "fans a pure-Python sys._current_frames() wall-clock sampler out "
        "to workers and merges collapsed stacks (reference: py-spy-based "
        "`ray stack` / dashboard flamegraphs, without the py-spy "
        "dependency). 0 rejects profile requests; workers never sample.")
_define("RTPU_PROFILER_HZ", float, 67.0,
        "Default sampling frequency of the wall-clock profiler.")
_define("RTPU_CALLSITE", bool, False,
        "Record the creating Python callsite (file:line) of every owned "
        "object ref in the ownership census (reference: "
        "RAY_record_ref_creation_sites). Adds a stack walk per put/task "
        "submission, so it is off by default and perf-guarded; enable "
        "when hunting a leak so `rtpu memory --group-by callsite` can "
        "name the allocating line.")
_define("RTPU_CENSUS", bool, True,
        "Cluster object census (`rtpu memory`, state.summarize_objects, "
        "the dashboard /objects page): each process's ownership table "
        "records owner/size/tier/pins per ref and answers the "
        "controller's object_census fan-out. 0 skips all per-ref census "
        "bookkeeping (the ref hot path pays one flag check) and census "
        "RPCs report disabled.")
_define("RTPU_CENSUS_TIMEOUT_S", float, 2.0,
        "Deadline for the object_census worker fan-out; shards that miss "
        "it (dead or wedged processes) are reported as per-shard error "
        "strings while survivors' totals still aggregate.")
_define("RTPU_LEAK_WATCHDOG", bool, True,
        "Leak watchdog (needs RTPU_EVENTS): periodically flags directory "
        "objects older than RTPU_LEAK_AGE_S whose owning process is dead "
        "or unreachable with an OBJECT_LEAK_SUSPECT event (once per "
        "object). 0 disables the sweep entirely.")
_define("RTPU_LEAK_AGE_S", float, 300.0,
        "Minimum age before an object with a dead/unreachable owner is "
        "flagged as OBJECT_LEAK_SUSPECT.")
_define("RTPU_LEAK_POLL_S", float, 10.0,
        "Leak-watchdog sweep period.")
_define("RTPU_DATA_PROGRESS", bool, False,
        "Per-operator progress lines from the streaming data executor "
        "(one stderr line per operator every RTPU_DATA_PROGRESS_S while "
        "a stage runs, reference: Ray Data's ProgressBar rows). Off by "
        "default: interactive use only.")
_define("RTPU_DATA_PROGRESS_S", float, 5.0,
        "Seconds between data-executor progress lines when "
        "RTPU_DATA_PROGRESS is on.")
_define("RTPU_DATA_STATS_ROWS", int, 256,
        "Per-operator bound on retained per-batch stat rows in the "
        "streaming executor (bounded deque + running aggregates keep "
        "Dataset.stats() O(1) memory on long streams).")

# -- serve: deadlines, admission control, circuit breaking -------------------
_define("RTPU_SERVE_ADMISSION", bool, True,
        "Overload protection in the serve router: bounded per-deployment "
        "queues (shed with BackPressureError -> HTTP 503 + Retry-After), "
        "per-replica circuit breakers the power-of-two picker skips, and "
        "a retry budget capped as a fraction of admitted traffic. 0 "
        "restores the legacy unbounded-queue router; the request path "
        "then pays exactly one flag check.")
_define("RTPU_SERVE_MAX_QUEUED", int, 100,
        "Default per-deployment queued-request bound (queued = accepted "
        "by routers beyond the replicas' max_ongoing_requests capacity) "
        "when the deployment does not set max_queued_requests. -1 means "
        "unbounded.")
_define("RTPU_SERVE_REQUEST_TIMEOUT_S", float, 60.0,
        "Default end-to-end deadline for serve requests that do not carry "
        "an explicit one (HTTP X-Request-Timeout-S header, gRPC envelope "
        "timeout_s, or handle .options(deadline_s=...)). Expired work is "
        "dropped with DeadlineExceededError at every queue boundary "
        "instead of executing. <=0 means no default deadline.")
_define("RTPU_SERVE_READY_TIMEOUT_S", float, 600.0,
        "How long a serve replica may take to start — worker process "
        "start, accelerator runtime start and its constructor (model load, "
        "program warm-up) — before the controller gives up on it, and how "
        "long serve.run() waits for the ingress deployment's replicas to "
        "have started. A hang backstop, not a failure detector: a "
        "constructor that raises or a worker that dies surfaces at once, "
        "and serve.run() gives up after three failed constructions. Sized "
        "for a cold model start (bench_350m on a v5e with an empty compile "
        "cache: 75s, chip run PR 21); until PR 21 the wait covered only "
        "the creation of a replica handle, which is instant.")
_define("RTPU_SERVE_BREAKER_THRESHOLD", int, 5,
        "Consecutive failures/timeouts on one replica before its circuit "
        "breaker opens and the router routes around it.")
_define("RTPU_SERVE_BREAKER_COOLDOWN_S", float, 5.0,
        "How long an open replica breaker waits before letting one "
        "half-open probe request through.")
_define("RTPU_SERVE_RETRY_BUDGET", float, 0.2,
        "Retry budget as a fraction of admitted traffic: each admitted "
        "request earns this many retry tokens (bucket capped at 10x), "
        "each retry spends one. Prevents retry amplification during an "
        "outage.")

# -- serve: disaggregated LLM plane (prefill/decode pools, prefix cache) -----
_define("RTPU_SERVE_DISAGG", bool, True,
        "Disaggregated LLM serving: build_disagg_llm_deployment splits "
        "prefill and decode into separately-scaled replica pools with a "
        "streamed K/V handoff between them. 0 collapses the builder to "
        "the unified continuous-batching deployment (identical request/"
        "response behavior, one pool).")
_define("RTPU_SERVE_DISAGG_RETRIES", int, 3,
        "How many times the disagg ingress re-dispatches a token stream "
        "to another decode replica after a mid-stream replica failure "
        "before surfacing the error to the client.")
_define("RTPU_PREFIX_CACHE", bool, True,
        "Decode-replica prefix cache: prefilled K/V keyed by token-prefix "
        "hash stays resident (LRU by KV bytes), so repeated prompts skip "
        "prefill entirely. 0 disables lookup, insert, and the "
        "controller-side cluster index.")
_define("RTPU_PREFIX_CACHE_MAX_MB", float, 256.0,
        "Per-replica prefix-cache budget in MiB of cached K/V (+logits) "
        "bytes; least-recently-used entries evict past it.")
_define("RTPU_PREFIX_CACHE_PROMOTE_HITS", int, 3,
        "Cluster-index promotion threshold: once a prefix accumulates "
        "this many cluster-wide hits, the serve controller broadcasts it "
        "to decode replicas that don't hold it yet. <=0 disables "
        "promotion.")
_define("RTPU_SERVE_AUTOSCALE", bool, True,
        "Signal-driven serve autoscaler: pool replica counts follow TTFT "
        "p99 / slot occupancy / queue depth through the AlertEngine's "
        "threshold+for-duration machinery for deployments that set a "
        "scaling_policy. 0 freezes pools at their deployed size (the "
        "legacy queue-length autoscaling_config path is unaffected).")
_define("RTPU_SERVE_DRAIN_DEADLINE_S", float, 30.0,
        "Scale-down grace: a draining replica stops receiving new "
        "requests immediately (routers drop it on version bump) but is "
        "only killed once idle or after this many seconds, so in-flight "
        "streams finish across a resize.")
_define("RTPU_SERVE_SCALE_COOLDOWN_S", float, 5.0,
        "Minimum seconds between two autoscaler actions on the same "
        "deployment, bounding resize churn.")
_define("RTPU_SERVE_TRACE", bool, True,
        "Per-request serving trace plane: every hop (proxy, router "
        "assign, replica, batch seal, engine slot wait, prefill, KV "
        "handoff, token stream) emits a span on its host's monotonic "
        "clock, finished requests ship to the controller's request "
        "ledger (`rtpu serve requests` / `rtpu serve trace ID`), and the "
        "engine records per-token timelines into rtpu_serve_itl_s. 0 "
        "reduces the whole plane to one flag check per hop.")
_define("RTPU_SERVE_STALL_S", float, 30.0,
        "Stream-stall detector threshold: a live generation slot that "
        "emits no token for this many seconds raises one STREAM_STALLED "
        "event (per request) with the replica's all-thread stack capture "
        "attached. <=0 disables the detector.")
_define("RTPU_SERVE_LEDGER_MAX", int, 2048,
        "Controller request-ledger capacity (finished serve request "
        "records with their spans). Past it, LRU rows evict — except "
        "SLO-miss / shed / deadline-exceeded rows, which are only "
        "reclaimed once every unflagged row is gone.")
_define("RTPU_SERVE_SLO_MS", float, 0.0,
        "Serving latency SLO in milliseconds: finished requests slower "
        "than this count into rtpu_serve_slo_miss_total, are retained "
        "ahead of LRU eviction in the request ledger, and feed the "
        "serve_slo_miss_rate_high alert rule. <=0 means no latency SLO "
        "(shed / deadline-exceeded outcomes still count as misses).")

# -- external (documented, not owned) ----------------------------------------
_define("JAX_PLATFORMS", str, None,
        "JAX platform list. Spawned workers get 'tpu' when started for a "
        "TPU request and default to 'cpu' otherwise (core/worker_env.py); "
        "cpu_mesh_env sets 'cpu' for virtual-mesh tests.", external=True)
_define("JAX_COMPILATION_CACHE_DIR", str, None,
        "Where JAX keeps its persistent compilation cache. Set from "
        "outside, it is used as given and no other path is set in code; "
        "unset, util/jaxenv.py enable_compile_cache() uses <checkout>/"
        ".jax_cache.", external=True)
_define("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", float, None,
        "JAX persistent-cache threshold; enable_compile_cache() sets 0 so "
        "sub-second programs are cached too.", external=True)
_define("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", int, None,
        "JAX persistent-cache threshold; enable_compile_cache() sets -1 "
        "(no minimum).", external=True)
_define("XLA_FLAGS", str, None,
        "XLA flags; cpu_mesh_env appends "
        "--xla_force_host_platform_device_count.", external=True)
_define("TPU_ACCELERATOR_TYPE", str, None,
        "GCE metadata accelerator type (e.g. v5litepod-16); used for "
        "generation detection.", external=True)
_define("TPU_NAME", str, None,
        "TPU pod/slice name from GCE/GKE metadata; when set, the node "
        "advertises the per-pod custom resource {TPU_NAME: 1} "
        "(reference tpu.py:335-398 scheme).", external=True)
_define("TPU_WORKER_ID", str, None,
        "Worker index within a TPU pod; worker 0 additionally advertises "
        "TPU-<type>-head: 1.", external=True)
_define("TPU_VISIBLE_CHIPS", str, None,
        "Comma-separated chip ids visible to this process (the TPU analog "
        "of CUDA_VISIBLE_DEVICES; reference tpu.py TPU_VISIBLE_CHIPS).",
        external=True)
_define("TPU_CHIPS_PER_HOST_BOUNDS", str, None,
        "libtpu: x,y,z shape of the chips this process drives (a TPU VM "
        "sets it for the whole host, e.g. 2,2,1). The worker spawner "
        "overrides it, with TPU_HOST_BOUNDS, for a grant smaller than the "
        "host (1 chip: 1,1,1; 2 chips: 1,2,1).", external=True)
_define("TPU_HOST_BOUNDS", str, None,
        "libtpu: x,y,z grid of cooperating processes; 1,1,1 for a worker "
        "that owns its chips alone.", external=True)


# Hot-path environment access: os.environ.get pays encodekey + a decoded
# copy on every call (~2us), and flag reads sit on the per-call submit and
# execute paths. os._Environ keeps the real mapping in ``_data`` keyed by
# ENCODED names; reading it directly with a precomputed key skips both
# costs while staying write-coherent (os.environ.__setitem__/__delitem__ —
# including monkeypatch.setenv — mutate the same dict). Fallback to the
# public API when the implementation detail is absent.
_env_data = getattr(os.environ, "_data", None)
try:
    _encode_key = os.environ.encodekey  # type: ignore[attr-defined]
except AttributeError:
    _env_data = None
    _encode_key = None
_keyb: Dict[str, Any] = {}


def _env_raw(name: str) -> Optional[str]:
    if _env_data is None:
        return os.environ.get(name)
    kb = _keyb.get(name)
    if kb is None:
        kb = _keyb[name] = _encode_key(name)
    raw = _env_data.get(kb)
    if raw is None:
        return None
    return os.fsdecode(raw)


def get(name: str, default: Any = None) -> Any:
    """Read a registered flag from the environment (call-time).

    ``default`` overrides the registered default when the flag is unset
    (for call sites with contextual fallbacks).
    """
    f = REGISTRY[name]
    raw = _env_raw(name)
    if raw is None:
        return default if default is not None else f.default
    if f.type is bool:
        return raw.strip().lower() not in ("0", "", "false", "no")
    if f.type in (int, float):
        return f.type(raw)
    return raw


def is_set(name: str) -> bool:
    REGISTRY[name]  # typo guard
    return name in os.environ


def raw(name: str) -> Optional[str]:
    """Uncoerced environment value — for error paths that must not re-raise
    on a malformed value."""
    REGISTRY[name]
    return os.environ.get(name)


def set_env(name: str, value: Any) -> None:
    """Set a registered flag in this process's environment (the sanctioned
    write path for process-tree plumbing flags)."""
    REGISTRY[name]  # typo guard
    os.environ[name] = str(value)


def unset_env(name: str) -> None:
    REGISTRY[name]
    os.environ.pop(name, None)


def set_raw(name: str, value: str) -> None:
    """Set an UNregistered environment variable (user runtime_env env_vars —
    arbitrary names the registry cannot enumerate)."""
    os.environ[name] = value


def child_env(**overrides: str) -> Dict[str, str]:
    """Snapshot of the current environment for spawning subprocesses."""
    env = dict(os.environ)
    env.update({k: str(v) for k, v in overrides.items()})
    return env


def describe() -> str:
    lines = []
    for f in sorted(REGISTRY.values(), key=lambda f: (f.external, f.name)):
        tag = " (external)" if f.external else ""
        lines.append(f"{f.name}{tag} [{f.type.__name__}, "
                     f"default={f.default!r}]\n    {f.doc}")
    return "\n".join(lines)


if __name__ == "__main__":
    print(describe())
