"""Public core API: init/shutdown, @remote tasks, actors, get/put/wait.

Parity surface with the reference's L2 API (ray: python/ray/_private/worker.py
init:1214 get:2523 put:2655 wait:2720, remote_function.py:266, actor.py:566),
implemented over the asyncio controller instead of a C++ CoreWorker. See
SURVEY.md §2.1 mapping note for why the Python control plane is acceptable on
TPU: per-step data movement belongs to XLA programs, not to this layer.
"""
from __future__ import annotations

from ray_tpu import flags

import atexit
import functools
import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import cloudpickle

from . import context as ctx
from . import ownership
from ..util import tracing
from .client import CoreClient, EventLoopThread
from .controller import Controller, GetTimeoutError, TaskError
from .ids import ActorID, NodeID, ObjectID, TaskID
from .object_store import get_bytes, get_bytes_with_refresh, put_bytes
from .serialization import ObjectRef, pack_args

_init_lock = threading.RLock()
_owned_controller: Optional[Controller] = None
_controller_io: Optional[EventLoopThread] = None


# ------------------------------------------------------------------ lifecycle


def init(
    address: Optional[str] = None,
    *,
    num_cpus: Optional[int] = None,
    num_tpus: Optional[int] = None,
    resources: Optional[Dict[str, float]] = None,
    namespace: str = "default",
    ignore_reinit_error: bool = False,
    runtime_env: Optional[Dict[str, Any]] = None,
) -> "ClusterHandle":
    """Start (or connect to) a cluster and bind this process as the driver.

    With no ``address`` a local controller is started in-process and one
    virtual node is registered with the host's resources (reference:
    ray.init starting GCS+raylet, _private/node.py:1342).
    """
    global _owned_controller, _controller_io
    with _init_lock:
        if ctx.is_initialized():
            if ignore_reinit_error:
                return ClusterHandle(ctx.get_worker_context())
            raise RuntimeError("ray_tpu.init() called twice; pass ignore_reinit_error=True")

        if address is None:
            # Job entrypoints / `rtpu` CLI processes inherit the cluster
            # address via env (reference: RAY_ADDRESS).
            address = flags.get("RTPU_ADDRESS") or None

        owned = False
        if address is None:
            owned = True
            io = EventLoopThread(name="rtpu-controller")
            controller = Controller()
            host, port = io.call(controller.start(), timeout=10)
            node_res: Dict[str, float] = {
                "CPU": float(num_cpus if num_cpus is not None else os.cpu_count() or 1),
            }
            # Vendor-agnostic autodetection over the registered accelerator
            # managers (util/accelerators.py plugin layer); on a TPU host
            # this adds {"TPU": chips} plus the pod-scoped custom resources
            # when GCE metadata env is present. An explicit num_tpus
            # overrides the detected chip count but must NOT silence the
            # pod resources — the pod-leader scheduling scheme has to work
            # whether or not the user pinned the count.
            from ray_tpu.util.accelerators import (
                detect_node_accelerator_resources,
            )

            node_res.update(detect_node_accelerator_resources())
            if num_tpus is not None:
                node_res.pop("TPU", None)
                if num_tpus:
                    node_res["TPU"] = float(num_tpus)
                    # Detection may have found 0 chips (container without
                    # /dev/accel*) and thus skipped the TPU manager's
                    # additional resources — an explicit chip count says
                    # this IS a TPU host, so advertise them.
                    from ray_tpu.util.accelerators import (
                        TPUAcceleratorManager,
                    )

                    try:
                        for k, v in \
                                TPUAcceleratorManager.additional_resources() \
                                .items():
                            node_res.setdefault(k, v)
                    except Exception:
                        pass
            if resources:
                node_res.update(resources)
            # ensure_head_node: a state-path restore brings back the prior
            # head node — reuse its identity instead of adding a duplicate.
            node_id = controller.ensure_head_node(node_res,
                                                  labels={"head": "1"})
            _owned_controller = controller
            _controller_io = io
            address = f"{host}:{port}"
        else:
            node_id = ""

        host, port_s = address.rsplit(":", 1)
        # Drivers of a REMOTE controller survive a controller bounce: the
        # client reconnects with capped backoff, re-registers, and resubmits
        # in-flight plain tasks (an embedded controller dies with this
        # process, so reconnect would only mask real shutdown races).
        client = CoreClient(host, int(port_s), handler=_driver_handler,
                            reconnect=not owned,
                            on_reconnect=_driver_on_reconnect)
        reg = client.request({"kind": "register", "role": "driver"})
        # A driver on a host with no pull server (neither the controller's
        # host nor an agent's) cannot serve its shm objects to workers: its
        # puts must travel inline on the control plane.
        from .object_store import current_host_id

        ctrl_host = (reg or {}).get("controller_host_id")
        if ctrl_host is not None and ctrl_host != current_host_id():
            flags.set_env("RTPU_FORCE_INLINE", "1")
        if not node_id:
            state = client.request({"kind": "cluster_state"})
            node_id = state["nodes"][0]["node_id"] if state["nodes"] else ""
        wc = ctx.WorkerContext(client=client, node_id=node_id, role="driver", namespace=namespace)
        wc.extra["address"] = address
        if runtime_env:
            # Job-level default env (reference: ray.init(runtime_env=...));
            # applied to every task/actor unless overridden per-call.
            wc.extra["default_runtime_env"] = dict(runtime_env)
        ctx.set_worker_context(wc)
        atexit.register(_atexit_shutdown)
        return ClusterHandle(wc)


async def _driver_handler(conn, msg):
    kind = msg.get("kind")
    if kind == "pubsub":
        ctx.deliver_pubsub(msg["channel"], msg["data"])
    elif kind == "pubsub_batch":
        for item in msg["items"]:
            ctx.deliver_pubsub(item["channel"], item["data"])
    elif kind == "lease_reclaim":
        # The controller has queued work it cannot place while we hold
        # task leases: release every named lease with no in-flight pushes.
        ids = set(msg.get("lease_ids") or ())
        threading.Thread(target=_reclaim_leases, args=(ids,),
                         daemon=True, name="lease-reclaim").start()
    elif kind == "log":
        # A worker's stdout/stderr line, prefixed like the reference's
        # driver-side log tailing ("(pid=...) ...").
        import sys

        stream = sys.stderr if msg.get("stream") == "stderr" else sys.stdout
        try:
            stream.write(f"(worker pid={msg.get('pid')}) {msg['line']}\n")
            stream.flush()
        except Exception:
            pass
    return None


def _driver_on_reconnect(client: CoreClient) -> None:
    """Runs on the fresh connection after a controller bounce, before any
    retried request goes out: re-register as a driver, drop task-lease
    pools the restarted controller knows nothing about, and resubmit
    in-flight plain tasks so blocked get()s complete without a driver
    restart (at-least-once for retryable work; actor routes stay — live
    actor workers keep serving direct calls through the bounce)."""
    # Bounded handshake when the partition-hardening RPC timeout is on: a
    # re-dial into a still-blackholed network must fail fast and keep
    # retrying from ensure_connected, not camp on a 30s wait.
    _t = float(flags.get("RTPU_RPC_TIMEOUT_S") or 0.0)
    client.io.call(
        client.conn.request({"kind": "register", "role": "driver"},
                            timeout=_t * 2 if _t else None),
        timeout=(_t * 2 if _t else 30) + 5)
    # Rotate the client token: per-session caches keyed on it (function
    # registrations, actor routes) re-validate against the restarted
    # controller instead of trusting state it may not have. (Functions of
    # ALREADY in-flight specs come from the --state-path function table.)
    import secrets

    client.token = secrets.token_hex(8)
    # The restarted controller has no lease ledger: forget leased routes so
    # fresh leases are negotiated (the workers themselves re-register as
    # idle). Idle routes close now; routes with pushes IN FLIGHT are
    # retired instead — the hosting workers survive the bounce, so their
    # batches complete on the live direct connections (results publish to
    # the restarted controller once the workers re-register) and the done
    # callback closes each drained route. Closing them here would turn a
    # controller bounce into spurious WorkerCrashedErrors on retry-less
    # directly-pushed tasks.
    for pool in list(_task_pools.values()):
        with pool.lock:
            routes, pool.routes = pool.routes, []
            busy = [r for r in routes if r.inflight > 0]
            for r in busy:
                r.retired = True
        for r in routes:
            if r.inflight > 0:
                continue
            try:
                client.io.call_nowait(r.conn.close())
            except Exception:
                pass
    _task_pools.clear()
    with _inflight_lock:
        specs = [dict(s) for s in _inflight_specs.values()]
    for spec in specs:
        # A spec whose direct push is still in flight on a surviving route
        # must NOT be resubmitted — the live worker will run it; a
        # duplicate through the queue would double-execute it.
        if any(oid in _inflight_direct
               for oid in (spec.get("return_ids") or ())):
            continue
        # Stale placement/dispatch residue must not ride the resubmit.
        for k in ("loc_hints", "sched_node", "blocked", "state"):
            spec.pop(k, None)
        try:
            client.io.call(
                client.conn.request({"kind": "submit_task", "spec": spec}),
                timeout=30)
        except Exception:
            pass


# In-flight plain-task specs for controller-bounce resubmission: task_id ->
# spec, retired when any return location is observed (get()/direct reply),
# bounded so fire-and-forget callers can't grow it without limit.
from collections import OrderedDict as _OrderedDict

_inflight_lock = threading.Lock()
_INFLIGHT_MAX = 4096
_inflight_specs: "_OrderedDict[str, Dict[str, Any]]" = _OrderedDict()
_inflight_oid2task: Dict[str, str] = {}


def _track_inflight(spec: Dict[str, Any]) -> None:
    if spec.get("actor_id") or spec.get("is_actor_creation") \
            or spec.get("streaming") or not spec.get("return_ids"):
        return
    with _inflight_lock:
        _inflight_specs[spec["task_id"]] = spec
        for oid in spec["return_ids"]:
            _inflight_oid2task[oid] = spec["task_id"]
        while len(_inflight_specs) > _INFLIGHT_MAX:
            _, old = _inflight_specs.popitem(last=False)
            for oid in old.get("return_ids") or ():
                _inflight_oid2task.pop(oid, None)


def _untrack_inflight(object_id: str) -> None:
    if object_id not in _inflight_oid2task:
        return
    with _inflight_lock:
        tid = _inflight_oid2task.pop(object_id, None)
        spec = _inflight_specs.pop(tid, None) if tid else None
        if spec:
            for oid in spec.get("return_ids") or ():
                _inflight_oid2task.pop(oid, None)


def _untrack_inflight_many(object_ids) -> None:
    hits = [oid for oid in object_ids if oid in _inflight_oid2task]
    if not hits:
        return
    with _inflight_lock:
        for object_id in hits:
            tid = _inflight_oid2task.pop(object_id, None)
            spec = _inflight_specs.pop(tid, None) if tid else None
            if spec:
                for oid in spec.get("return_ids") or ():
                    _inflight_oid2task.pop(oid, None)


def _atexit_shutdown() -> None:
    try:
        shutdown()
    except Exception:
        pass


def shutdown() -> None:
    global _owned_controller, _controller_io
    with _init_lock:
        if not ctx.is_initialized():
            return
        wc = ctx.get_worker_context()
        ownership.shutdown()
        _reset_direct_state(wc)
        if _owned_controller is not None and _controller_io is not None:
            try:
                # 5s of teardown plus the controller's bounded wait (a
                # minute) for chip-owning workers to exit.
                _controller_io.call(_owned_controller.shutdown(), timeout=70)
            except Exception:
                pass
        try:
            wc.client.close()
        except Exception:
            pass
        if _controller_io is not None:
            _controller_io.stop()
        _owned_controller = None
        _controller_io = None
        ctx.set_worker_context(None)
        flags.unset_env("RTPU_FORCE_INLINE")
        from .object_store import close_process_segments
        from .transfer import reset_transfer_caches

        close_process_segments()
        reset_transfer_caches()


def is_initialized() -> bool:
    return ctx.is_initialized()


@dataclass
class ClusterHandle:
    wc: ctx.WorkerContext

    @property
    def address(self) -> str:
        return self.wc.extra.get("address", "")


# ------------------------------------------------------------------- get/put


def put(value: Any) -> ObjectRef:
    wc = ctx.get_worker_context()
    oid = ObjectID.generate()
    loc = put_bytes(value, oid, wc.node_id)
    # The producer knows the location — cache it so get() of own puts never
    # asks the controller; the directory registration is pipelined (same
    # connection, so any subsequent submit referencing this ref is ordered
    # after it, and remote consumers block in get_locations until it lands).
    _cache_loc(loc)
    _pipelined_submit(wc, {"kind": "put_location", "loc": loc}, (oid,))
    ownership.claim_ownership(oid, loc)
    return ObjectRef(oid, ownership.self_addr())


def _with_block_notify(fn: Callable[[], Any]) -> Any:
    """Release this task's CPU while blocked in get/wait (reference:
    NotifyDirectCallTaskBlocked, src/ray/raylet_client/raylet_client.h:380)."""
    wc = ctx.get_worker_context()
    task_id = ctx.current_task_id()
    if task_id is None or wc.role != "worker":
        return fn()
    wc.client.request({"kind": "task_blocked", "task_id": task_id})
    try:
        return fn()
    finally:
        try:
            wc.client.request({"kind": "task_unblocked", "task_id": task_id})
        except Exception:
            pass


def get(refs: Union[ObjectRef, Sequence[ObjectRef]], *, timeout: Optional[float] = None) -> Any:
    wc = ctx.get_worker_context()
    single = isinstance(refs, ObjectRef)
    ref_list: List[ObjectRef] = [refs] if single else list(refs)
    for r in ref_list:
        if not isinstance(r, ObjectRef):
            raise TypeError(f"get() expects ObjectRef(s), got {type(r)}")
    ids = [r.object_id for r in ref_list]
    # Direct-call results are cached locally — only unknown ids hit the
    # controller (and skip the blocked-notify round trips entirely when
    # everything is local). In-flight direct replies are awaited here
    # rather than asking the controller for locations that are already on
    # the wire. The controller deadline is reduced by the time spent
    # waiting so the overall budget stays `timeout`.
    t_start = time.monotonic()
    if _inflight_direct:
        _await_inflight(ids, timeout)
    missing = [oid for oid in ids if oid not in _local_locs]
    remaining_timeout = (None if timeout is None else
                         max(0.0, timeout - (time.monotonic() - t_start)))

    owners = {r.object_id: r.owner for r in ref_list
              if r.owner and r.object_id in missing}

    def fetch():
        # node_id: the controller resolves replica-aware (consumer-local
        # copies of broadcast objects beat cross-host pulls).
        return wc.client.request(
            {"kind": "get_locations", "object_ids": missing,
             "timeout": remaining_timeout, "owners": owners,
             "node_id": wc.node_id}
        )

    locs = _with_block_notify(fetch) if missing else {}
    for loc in locs.values():
        # Cache controller-fetched locations: later submits that depend on
        # these objects stay eligible for direct dispatch (the lease path
        # requires locally-known dep locations), and repeat gets skip the
        # directory. get_bytes_with_refresh re-resolves stale entries.
        # (_cache_loc also releases this process's submit holds for
        # observed task returns — the single load-bearing hook.)
        _cache_loc(loc)
    out = []
    for oid in ids:
        loc = locs.get(oid) or _local_locs.get(oid)
        if loc is None:
            # Cached entry evicted/freed between the missing-computation
            # and here (LRU bound or concurrent free): the controller is
            # the authority.
            loc = wc.client.request(
                {"kind": "get_locations", "object_ids": [oid],
                 "timeout": remaining_timeout, "node_id": wc.node_id})[oid]
        val, loc = get_bytes_with_refresh(loc, oid, wc.client.request)
        if loc.is_error:
            if isinstance(val, BaseException):
                raise val
            raise RuntimeError(str(val))
        out.append(val)
    return out[0] if single else out


def broadcast(ref: ObjectRef, node_ids: Optional[Sequence[str]] = None,
              *, timeout: float = 120.0) -> Dict[str, Any]:
    """Replicate one object's bytes onto N nodes in a single pass.

    The bytes move source -> N over a pipelined chain of hosts (each hop
    stores a full local copy while forwarding downstream), so the producer
    ships each byte ~once regardless of fan-out — the weight-distribution
    primitive for async-RL topologies (reference: Ray's object-manager
    Push + ray.experimental.channel broadcast). Afterwards, ``get()`` (and
    task argument resolution) on a target node reads the local replica
    over shared memory.

    ``node_ids=None`` targets every alive node that doesn't already hold
    the bytes. Returns ``{ok, replicas: {node_id: "ok"}, skipped: {...},
    stats: {source_bytes}, rounds}``; nodes that die or drain mid-flight
    are re-routed onto a fresh chain and reported in ``skipped``.
    """
    if not isinstance(ref, ObjectRef):
        raise TypeError(f"broadcast() expects an ObjectRef, got {type(ref)}")
    wc = ctx.get_worker_context()
    return wc.client.request(
        {"kind": "broadcast_object", "object_id": ref.object_id,
         "node_ids": list(node_ids) if node_ids is not None else None,
         "timeout": timeout},
        timeout=timeout + 10)


def wait(
    refs: Sequence[ObjectRef],
    *,
    num_returns: int = 1,
    timeout: Optional[float] = None,
    fetch_local: bool = True,
) -> Tuple[List[ObjectRef], List[ObjectRef]]:
    wc = ctx.get_worker_context()
    ids = [r.object_id for r in refs]
    if num_returns > len(ids):
        raise ValueError("num_returns exceeds number of refs")
    local_ready = [oid for oid in ids if oid in _local_locs]
    if len(local_ready) >= num_returns:
        ready_ids = set(local_ready[:num_returns])
        ready = [r for r in refs if r.object_id in ready_ids]
        return ready, [r for r in refs if r.object_id not in ready_ids]

    def do():
        return wc.client.request(
            {"kind": "wait", "object_ids": ids, "num_returns": num_returns, "timeout": timeout}
        )

    ready_ids = set(_with_block_notify(do))
    ready = [r for r in refs if r.object_id in ready_ids]
    not_ready = [r for r in refs if r.object_id not in ready_ids]
    return ready, not_ready


def error_of(ref: ObjectRef, *,
             timeout: Optional[float] = 30.0) -> Optional[BaseException]:
    """The exception a READY object holds, or None for a data object.

    A location-metadata probe, not a fetch: callers that stream large
    blocks by reference (the data plane's executor) use this to classify
    a completed task/actor-call ref as success vs typed system failure
    (ActorDiedError / WorkerCrashedError / NodePreemptedError / ...)
    without ever pulling the payload bytes of a healthy block to this
    process. Direct-dispatch results answer from the local location
    cache (one dict lookup); otherwise one get_locations round trip.
    Only error payloads — which are small — are materialized."""
    wc = ctx.get_worker_context()
    oid = ref.object_id
    loc = _local_locs.get(oid)
    if loc is None:
        locs = wc.client.request(
            {"kind": "get_locations", "object_ids": [oid],
             "timeout": timeout, "node_id": wc.node_id})
        loc = locs[oid]
        _cache_loc(loc)
    if not loc.is_error:
        return None
    val, _ = get_bytes_with_refresh(loc, oid, wc.client.request)
    if isinstance(val, BaseException):
        return val
    return RuntimeError(str(val))


def free(refs: Sequence[ObjectRef]) -> None:
    wc = ctx.get_worker_context()
    for r in refs:
        _local_locs.pop(r.object_id, None)
    wc.client.request({"kind": "free_objects", "object_ids": [r.object_id for r in refs]})


# ------------------------------------------------------------------- tasks


def _validate_accel_quantity(resource: str, quantity: Any) -> float:
    """Validate an accelerator request against its registered manager
    (reference: option validation via accelerator.validate_resource_request_
    quantity in _private/ray_option_utils.py)."""
    from ray_tpu.util.accelerators import manager_for_resource

    mgr = manager_for_resource(resource)
    if mgr is not None:
        ok, err = mgr.validate_request(float(quantity))
        if not ok:
            raise ValueError(err)
    return float(quantity)


def _validate_accel_resources(resources: Dict[str, float]) -> Dict[str, float]:
    """Validate every accelerator-managed entry of a resources dict — the
    resources={"TPU": n} spelling must hit the same validation as
    num_tpus=n."""
    for name, q in resources.items():
        _validate_accel_quantity(name, q)
    return resources


def _normalize_strategy(scheduling_strategy: Any) -> Tuple[Dict[str, Any], Optional[Tuple[str, int]]]:
    """Returns (strategy dict, pg tuple)."""
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
        NodeLabelSchedulingStrategy,
        PlacementGroupSchedulingStrategy,
    )

    if scheduling_strategy is None or scheduling_strategy == "DEFAULT":
        return {"type": "DEFAULT"}, None
    if scheduling_strategy == "SPREAD":
        return {"type": "SPREAD"}, None
    if isinstance(scheduling_strategy, NodeAffinitySchedulingStrategy):
        return (
            {"type": "NODE_AFFINITY", "node_id": scheduling_strategy.node_id,
             "soft": scheduling_strategy.soft},
            None,
        )
    if isinstance(scheduling_strategy, NodeLabelSchedulingStrategy):
        return {"type": "NODE_LABEL", "labels": scheduling_strategy.hard}, None
    if isinstance(scheduling_strategy, PlacementGroupSchedulingStrategy):
        pg = scheduling_strategy.placement_group
        idx = scheduling_strategy.placement_group_bundle_index
        if idx is None or idx < 0:
            idx = -1  # reference semantics: any bundle in the group
        return {"type": "DEFAULT"}, (pg.id, idx)
    raise ValueError(f"unknown scheduling strategy {scheduling_strategy!r}")


class ObjectRefGenerator:
    """Iterator over the refs of a streaming task's yields (reference:
    StreamingObjectRefGenerator, python/ray/_raylet.pyx:273). Each __next__
    blocks until the producer reports the item — the consumer can hold item
    0 while the producer is still running."""

    def __init__(self, task_id: str):
        self._task_id = task_id
        self._index = 0
        self._exhausted = False

    def __iter__(self) -> "ObjectRefGenerator":
        return self

    def __next__(self) -> ObjectRef:
        if self._exhausted:
            # The controller pops generator state at exhaustion; re-asking
            # for the task would error instead of honoring the protocol.
            raise StopIteration
        wc = ctx.get_worker_context()
        # Held until the producer reports the item: a wait, not a stall.
        with tracing.phase("stream.next", slow=False):
            r = wc.client.request(
                {"kind": "generator_next", "task_id": self._task_id,
                 "index": self._index}
            )
        if r.get("done"):
            self._exhausted = True
            raise StopIteration
        self._index += 1
        return ObjectRef(r["object_id"])

    def close(self) -> None:
        """Tell the controller this consumer is gone so a producer stalled
        in the backpressure window is released and state is reclaimed.

        MUST be fire-and-forget: __del__ can run on any thread during GC —
        including an event-loop thread — where a blocking request deadlocks
        the loop against itself (observed: GC inside a controller handler
        collecting a stale generator wedged the whole control plane)."""
        if self._exhausted:
            return
        self._exhausted = True
        try:
            wc = ctx.get_worker_context()
            wc.client.request_async(
                {"kind": "generator_close", "task_id": self._task_id}
            )
        except Exception:
            pass

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def __reduce__(self):
        # Pickling hands ownership to the receiver: disarm close-on-del in
        # this copy so its destruction doesn't cancel the remote consumer.
        self._exhausted = True
        return (ObjectRefGenerator, (self._task_id,))


def _streaming_spec_opts(opts: Dict[str, Any], spec: Dict[str, Any]) -> None:
    spec["streaming"] = True
    spec["backpressure"] = int(
        opts.get("_generator_backpressure_num_objects", 16) or 16
    )


def _attach_runtime_env(wc: ctx.WorkerContext, opts: Dict[str, Any],
                        spec: Dict[str, Any]) -> None:
    """Resolve the effective runtime env (call option > job default) into
    the spec. Normalization (zip + KV upload) is cached per raw-env content
    so repeated calls don't re-zip."""
    raw = opts.get("runtime_env") or wc.extra.get("default_runtime_env")
    if not raw:
        return
    import json as _json

    from . import runtime_env as renv

    cache = wc.extra.setdefault("_renv_cache", {})
    key = _json.dumps(raw, sort_keys=True, default=str)
    if raw.get("working_dir"):
        # Editing files between submissions must ship the new content: key
        # the cache by a cheap directory fingerprint, not the path string.
        key += "|" + renv.working_dir_fingerprint(raw["working_dir"])
    norm = cache.get(key)
    if norm is None:
        norm = renv.normalize(raw, wc.client)
        cache[key] = norm
    if norm:
        spec["runtime_env"] = norm
        spec["env_hash"] = norm["hash"]


class RemoteFunction:
    """Handle produced by @remote on a function (reference:
    python/ray/remote_function.py:266 RemoteFunction._remote)."""

    def __init__(self, fn: Callable, options: Optional[Dict[str, Any]] = None):
        self._fn = fn
        self._options = options or {}
        self._func_id: Optional[str] = None
        self._registered_with: Optional[str] = None
        # Amortized submission: the spec's static fields (closure id,
        # validated resources, normalized strategy, retry options) are
        # computed once per session and shared by every call's spec — each
        # .remote() builds only its ids and args, and batched pushes pickle
        # the shared sub-objects once per frame (pickle memo), not per call.
        self._tmpl: Optional[Tuple[Dict[str, Any], bool, Any]] = None
        self._tmpl_key: Optional[str] = None
        functools.update_wrapper(self, fn)

    def options(self, **opts) -> "RemoteFunction":
        new = RemoteFunction(self._fn, {**self._options, **opts})
        new._func_id = self._func_id
        new._registered_with = self._registered_with
        return new

    def _ensure_registered(self, wc: ctx.WorkerContext) -> str:
        key = wc.client.token
        if self._func_id is None or self._registered_with != key:
            # Assign the id BEFORE pickling: if the function's closure
            # references this handle (recursive remote fn / workflow
            # continuation), the nested __reduce__ must see a settled id
            # instead of re-entering registration forever.
            func_id = TaskID.generate()
            self._func_id = func_id
            self._registered_with = key
            try:
                blob = cloudpickle.dumps(self._fn)
                wc.client.request({"kind": "register_function",
                                   "func_id": func_id, "blob": blob})
            except BaseException:
                self._func_id = None
                self._registered_with = None
                raise
        return self._func_id

    def _ensure_template(self, wc: ctx.WorkerContext):
        key = wc.client.token
        if self._tmpl is not None and self._tmpl_key == key:
            return self._tmpl
        func_id = self._ensure_registered(wc)
        opts = self._options
        num_returns = opts.get("num_returns", 1)
        streaming = num_returns == "streaming"
        resources = dict(opts.get("resources", {}) or {})
        resources["CPU"] = float(opts.get("num_cpus", 1 if "num_tpus" not in opts else 0))
        if opts.get("num_tpus"):
            resources["TPU"] = float(opts["num_tpus"])
        _validate_accel_resources(resources)
        strategy, pg = _normalize_strategy(opts.get("scheduling_strategy"))
        tmpl = {
            "func_id": func_id,
            "resources": {k: v for k, v in resources.items() if v},
            "scheduling": strategy,
            "pg": pg,
            "label": getattr(self._fn, "__name__", "task"),
            "max_retries": int(opts.get("max_retries", 0)),
            # True retries APPLICATION errors too (reference
            # retry_exceptions; bool form — per-exception-class lists are
            # not supported).
            "retry_exceptions": bool(opts.get("retry_exceptions", False)),
        }
        self._tmpl = (tmpl, streaming, num_returns)
        self._tmpl_key = key
        return self._tmpl

    def remote(self, *args, **kwargs):
        wc = ctx.get_worker_context()
        tmpl, streaming, num_returns = self._ensure_template(wc)
        opts = self._options
        args_blob, deps, nested_refs = pack_args(args, kwargs)
        n_rets = 0 if streaming else max(num_returns, 0)
        return_ids = [ObjectID.generate() for _ in range(n_rets)]
        # Static fields come as shared references from the template; only
        # ids and args are per-call.
        spec = dict(tmpl)
        spec["task_id"] = TaskID.generate()
        spec["args_blob"] = args_blob
        spec["deps"] = deps
        spec["return_ids"] = return_ids
        if opts.get("deadline_s") is not None:
            # Absolute end-to-end deadline: every queue boundary (scheduler
            # pop, worker dequeue) drops the spec once it passes.
            spec["deadline_ts"] = time.time() + float(opts["deadline_s"])
        ptid = ctx.current_task_id()
        if ptid:
            # Ownership edge for rtpu.cancel(recursive=True).
            spec["parent_task_id"] = ptid
        _attach_runtime_env(wc, opts, spec)
        if streaming:
            _streaming_spec_opts(opts, spec)
        if deps or nested_refs:
            _register_dep_holds(spec, nested_refs)
        tracing.inject_submit_span(spec, spec["label"])
        if flags.get("RTPU_TASK_EVENTS"):
            # Flight-recorder anchor: the executing worker derives
            # scheduling delay (submit -> dispatch arrival) from this.
            spec["submit_ts"] = time.time()
        # Lease-then-push direct path first; the controller queue is the
        # fallback (and the only path for pg/affinity/streaming tasks).
        # Only controller-path specs enter the bounce-resubmission buffer:
        # a direct push has its own recovery (the batch done callback),
        # and a bounce must not double-schedule work a live worker still
        # holds.
        if not _try_direct_task(wc, spec, opts):
            _track_inflight(spec)
            _pipelined_submit(wc, {"kind": "submit_task", "spec": spec},
                              spec["return_ids"])
        elif "parent_task_id" in spec:
            # Direct push: the controller never sees the submission, so the
            # ownership edge for recursive cancel ships as a fire-and-forget
            # note (only paid when running INSIDE a task — driver submits
            # carry no parent and skip this entirely).
            _note_task_lineage(wc, spec)
        if streaming:
            return ObjectRefGenerator(spec["task_id"])
        refs = _claim_return_refs(return_ids)
        if num_returns == 1:
            return refs[0]
        if num_returns == 0:
            return None
        return refs

    def bind(self, *args, **kwargs):
        """Author a lazy DAG node instead of submitting (reference
        python/ray/dag/function_node.py; used by ray_tpu.workflow and
        ray_tpu.dag.compiled_dag)."""
        from ray_tpu.dag.dag_node import FunctionNode

        return FunctionNode(self, args, kwargs)

    def __reduce__(self):
        # RemoteFunction handles travel inside task results (workflow
        # continuations return DAG nodes holding one). Pickling the wrapped
        # fn by value recurses when its closure references the handle itself
        # (e.g. a recursive continuation), so ship it *by function-table id*
        # — the blob is already exported via register_function. Without a
        # live session (plain copy.deepcopy of a config holding a handle)
        # fall back to by-value, the pre-session behavior.
        if not is_initialized():
            # Re-entrancy guard mirroring the session path: a recursive
            # handle (fn's closure → this object) would otherwise nest
            # cloudpickle.dumps forever. First entry dumps the fn under a
            # token; nested entries reduce to a by-token backreference that
            # the (equally nested) load resolves to the same object.
            state = _value_pickle_state()
            token = state["dumping"].get(id(self))
            if token is not None:
                return (_rebuild_value_backref, (token,))
            token = f"rf-{id(self):x}-{len(state['dumping'])}"
            state["dumping"][id(self)] = token
            try:
                blob = cloudpickle.dumps(self._fn)
            finally:
                del state["dumping"][id(self)]
            return (_rebuild_remote_function_value,
                    (token, blob, self._options))
        wc = ctx.get_worker_context()
        func_id = self._ensure_registered(wc)
        return (_rebuild_remote_function, (func_id, self._options))

    def __call__(self, *args, **kwargs):
        raise TypeError(
            f"remote function {self.__name__!r} cannot be called directly; "
            f"use .remote() or access the underlying function via ._fn"
        )


# Rebuild bookkeeping for by-table-id function handles. ``_rebuilding`` is
# keyed per-thread: a function whose closure references its own handle
# re-enters _rebuild_remote_function while its blob loads and must get the
# same placeholder back, but another thread must NOT observe the partially
# initialized object — it performs its own fetch instead. ``_fn_cache``
# memoizes completed loads so repeat deserializations of the same func_id
# (deep workflow continuations) skip the fetch RPC + unpickle.
_rebuilding: Dict[Any, "RemoteFunction"] = {}
_fn_cache: Dict[Any, Callable] = {}


def _rebuild_remote_function(func_id: str, options) -> "RemoteFunction":
    wc = ctx.get_worker_context()
    cache_key = (wc.client.token, func_id)
    local_key = (threading.get_ident(),) + cache_key
    if local_key in _rebuilding:
        return _rebuilding[local_key]
    fn = _fn_cache.get(cache_key)
    if fn is not None:
        rf = RemoteFunction(fn, options)
    else:
        rf = RemoteFunction.__new__(RemoteFunction)
        _rebuilding[local_key] = rf
        try:
            blob = wc.client.request(
                {"kind": "fetch_function", "func_id": func_id})
            rf.__init__(cloudpickle.loads(blob), options)
            _fn_cache[cache_key] = rf._fn
        finally:
            del _rebuilding[local_key]
    rf._func_id = func_id
    rf._registered_with = wc.client.token
    return rf


_value_tl = threading.local()


def _value_pickle_state() -> Dict[str, Dict]:
    if not hasattr(_value_tl, "state"):
        _value_tl.state = {"dumping": {}, "loading": {}}
    return _value_tl.state


def _rebuild_remote_function_value(token: str, fn_blob: bytes,
                                   options) -> "RemoteFunction":
    state = _value_pickle_state()
    rf = RemoteFunction.__new__(RemoteFunction)
    state["loading"][token] = rf
    try:
        rf.__init__(cloudpickle.loads(fn_blob), options)
    finally:
        del state["loading"][token]
    return rf


def _rebuild_value_backref(token: str) -> "RemoteFunction":
    return _value_pickle_state()["loading"][token]


# ------------------------------------------------------------------- actors

# ---- direct dispatch (lease-then-push) -------------------------------------
# Reference: src/ray/core_worker/transport/direct_task_transport.h:222 and
# direct_actor_task_submitter.h:74 — resolve the actor's worker address once
# via the controller, then push calls peer-to-peer. The controller keeps the
# directory/health/GC roles; it is no longer on the per-call path. Result
# locations return inline on the direct reply and are cached process-locally,
# so the subsequent get() usually needs no controller round trip either (the
# executing worker still fire-and-forget-reports task_done so third-party
# consumers and the state API converge).

from collections import OrderedDict

_routes_lock = threading.Lock()
_routes: Dict[Tuple[str, str], "_ActorRoute"] = {}
_local_locs: "OrderedDict[str, Any]" = OrderedDict()
_LOCAL_LOCS_MAX = 65536


class _ActorRoute:
    """Cached direct path to one actor (per client session)."""

    def __init__(self) -> None:
        self.conn = None  # protocol.Connection on the client's io loop
        self.worker_id: Optional[str] = None
        self.lock = threading.Lock()
        self.batcher: Optional["_PushBatcher"] = None


# ---- submit batching --------------------------------------------------------
# Every spec appended during one event-loop beat rides ONE framed
# direct_task_batch / direct_actor_task_batch message (one pickle, one
# syscall) and ONE aggregated reply. Specs built from a shared template
# (RemoteFunction._submit_template) reference the same static sub-objects,
# so pickle's memo serializes the closure/option template once per batch —
# each additional call costs its args and ids on the wire, nothing else.


class _PushBatch:
    __slots__ = ("specs", "fut", "maxn", "settled")

    def __init__(self) -> None:
        import concurrent.futures

        self.specs: List[Dict[str, Any]] = []
        self.fut: "Any" = concurrent.futures.Future()
        # Seal bound, read once at batch open (not one flag read per add).
        self.maxn = flags.get("RTPU_SUBMIT_BATCH_MAX")
        # One settle per batch: the partition-hardening timeout watchdog
        # and the (late) real reply race onto the same io thread; whichever
        # fires first wins, the other is a no-op.
        self.settled = False


class _PushBatcher:
    """Per-connection micro-batcher for direct pushes.

    ``add`` appends a spec to the open batch and (once per batch) schedules
    a flush on the io loop — the flush runs within the same loop beat, so a
    lone call's latency is unchanged while a burst coalesces into one frame.
    The ``on_done(batch, result, exc)`` callback fires once per batch with
    the aggregated reply (or the transport error)."""

    __slots__ = ("kind", "conn", "io", "on_done", "lock", "cur", "closed",
                 "scheduled")

    def __init__(self, kind: str, conn, io, on_done) -> None:
        self.kind = kind
        self.conn = conn
        self.io = io
        self.on_done = on_done
        self.lock = threading.Lock()
        self.cur: Optional[_PushBatch] = None
        self.closed: List[_PushBatch] = []
        self.scheduled = False

    def add(self, spec: Dict[str, Any], return_ids, meta=None) -> Any:
        """Append one spec; registers its return ids in the in-flight maps
        under the batcher lock (so the batch's done callback, which pops
        them, can never run before they are registered). Returns the
        batch's shared future."""
        with self.lock:
            b = self.cur
            if b is None:
                b = self.cur = _PushBatch()
            b.specs.append(spec)
            for oid in return_ids:
                _inflight_direct[oid] = b.fut
                if meta is not None:
                    _direct_task_meta[oid] = meta
            if len(b.specs) >= b.maxn:
                self.closed.append(b)
                self.cur = None
            if self.scheduled:
                return b.fut
            self.scheduled = True
        try:
            self.io.loop.call_soon_threadsafe(self._flush)
        except RuntimeError as e:  # io loop gone (shutdown race)
            self._fail_open_batches(ConnectionError(str(e)))
        return b.fut

    def _settle(self, b: _PushBatch, res, exc) -> None:
        """Run the batch's bookkeeping callback, then resolve the shared
        future (in that order: by the time a waiter in _await_inflight
        wakes, the aggregated locations are cached and the in-flight maps
        are settled)."""
        if b.settled:
            return
        b.settled = True
        try:
            self.on_done(b, res, exc)
        finally:
            if exc is not None:
                if not b.fut.done():
                    b.fut.set_exception(exc)
            elif not b.fut.done():
                b.fut.set_result(res)

    def _fail_open_batches(self, exc: BaseException) -> None:
        with self.lock:
            batches, self.closed = self.closed, []
            if self.cur is not None:
                batches.append(self.cur)
                self.cur = None
            self.scheduled = False
        for b in batches:
            self._settle(b, None, exc)

    def _flush(self) -> None:
        """Runs on the io loop: seal and send every pending batch, in
        append order (FIFO scheduling keeps cross-batch submission order,
        which the actor mailbox's seqno reordering relies on only as a
        fallback)."""
        with self.lock:
            batches, self.closed = self.closed, []
            if self.cur is not None:
                batches.append(self.cur)
                self.cur = None
            self.scheduled = False
        try:
            rpc_t = float(flags.get("RTPU_RPC_TIMEOUT_S") or 0.0)
        except Exception:
            rpc_t = 0.0
        for b in batches:
            try:
                rfut = self.conn.request_threadsafe(
                    {"kind": self.kind, "specs": b.specs})
            except Exception as e:  # noqa: BLE001
                self._settle(b, None, e)
                continue

            def _chain(f, b=b):
                exc = f.exception() if not f.cancelled() else \
                    ConnectionError("request cancelled")
                if exc is not None:
                    self._settle(b, None, exc)
                else:
                    self._settle(b, f.result() or {}, None)

            rfut.add_done_callback(_chain)
            if rpc_t:
                # Partition hardening: a push into a blackholed-but-open
                # connection never answers — after a generous multiple of
                # the RPC timeout, fail the batch into the normal recovery
                # path (replayable actors resubmit safely; plain tasks run
                # the published-vs-unacked probe). 4x the control-plane
                # timeout so genuinely slow calls don't trip it; 0
                # (default) arms nothing.
                def _expire(b=b, rfut=rfut):
                    if not b.settled:
                        self._settle(b, None, ConnectionError(
                            f"direct push unanswered after "
                            f"{rpc_t * 4:.1f}s (suspected partition)"))

                try:
                    self.io.loop.call_later(rpc_t * 4, _expire)
                except RuntimeError:
                    pass


def _cache_loc(loc) -> None:
    _local_locs[loc.object_id] = loc
    while len(_local_locs) > _LOCAL_LOCS_MAX:
        _local_locs.popitem(last=False)
    # A visible location/error for a task return means the spec is no longer
    # in flight — the submitter's dep holds can go (ownership protocol;
    # no-op for oids this process didn't submit), and the spec leaves the
    # controller-bounce resubmission buffer.
    ownership.on_return_location(loc.object_id)
    _untrack_inflight(loc.object_id)


def _cache_locs(locs) -> None:
    """Batch form of _cache_loc for aggregated direct replies: one lock
    round per batch for the ownership release and the in-flight buffer
    instead of one per location (this runs on the io thread — its GIL
    share comes straight out of the submitting thread's budget)."""
    if not locs:
        return
    oids = []
    for loc in locs:
        _local_locs[loc.object_id] = loc
        oids.append(loc.object_id)
    while len(_local_locs) > _LOCAL_LOCS_MAX:
        _local_locs.popitem(last=False)
    ownership.on_return_locations(oids)
    _untrack_inflight_many(oids)


_actor_seqnos: Dict[str, int] = {}
_actor_seqnos_lock = threading.Lock()


def _next_actor_seqno(actor_id: str) -> int:
    with _actor_seqnos_lock:
        n = _actor_seqnos.get(actor_id, 0)
        _actor_seqnos[actor_id] = n + 1
        return n


def _register_dep_holds(spec: Dict[str, Any], nested_refs=()) -> None:
    """Pin the spec's deps AND refs nested in its args at their owners for
    the life of the submission (reference: reference_count.h counts every id
    serialized into a task spec, top-level or nested)."""
    held = list(spec.get("deps") or [])
    for r in nested_refs:
        if r.object_id not in held:
            held.append(r.object_id)
    dep_owners = ownership.register_submit_holds(
        spec["task_id"], held, spec.get("return_ids") or [])
    if dep_owners:
        spec["dep_owners"] = dep_owners


def _claim_return_refs(return_ids) -> List[ObjectRef]:
    """Task returns are owned by the calling process (reference semantics:
    the caller, not the executing worker, owns task results). One locked
    pass claims + counts every id; the handles are built via __new__ so
    __init__ doesn't take the ref lock a second time per id."""
    addr = ownership.claim_return_refs(return_ids)
    refs = []
    for oid in return_ids:
        r = ObjectRef.__new__(ObjectRef)
        r.object_id = oid
        r.owner = addr
        refs.append(r)
    return refs


def _get_route(wc, actor_id: str) -> "_ActorRoute":
    key = (wc.client.token, actor_id)
    with _routes_lock:
        route = _routes.get(key)
        if route is None:
            route = _routes[key] = _ActorRoute()
        return route


def _invalidate_route(wc, route: "_ActorRoute") -> None:
    with route.lock:
        conn, route.conn = route.conn, None
        route.worker_id = None
    if conn is not None:
        try:
            wc.client.io.call_nowait(conn.close())
        except Exception:
            pass


def _resolve_route(wc, route: "_ActorRoute", actor_id: str) -> bool:
    """Resolve + connect the direct path; False -> use the controller path."""
    from . import protocol

    with route.lock:
        if route.conn is not None:
            return True
        try:
            info = wc.client.request(
                {"kind": "resolve_actor", "actor_id": actor_id})
        except Exception:
            return False
        d = info.get("direct")
        if info.get("state") != "alive" or not d:
            return False
        try:
            route.conn = wc.client.io.call(
                protocol.connect(d["host"], d["port"],
                                 name=f"direct->{actor_id[:8]}"),
                timeout=5)
        except Exception:
            route.conn = None
            return False
        route.worker_id = d["worker_id"]
        route.batcher = _PushBatcher(
            "direct_actor_task_batch", route.conn, wc.client.io,
            _make_actor_batch_done(wc, route))
        return True


def _make_actor_batch_done(wc, route: "_ActorRoute"):
    """Done-callback for one actor route's call batches (io thread)."""

    def done(batch: _PushBatch, res, exc) -> None:
        if exc is None:
            if not getattr(batch.fut, "_rtpu_cached", False):
                batch.fut._rtpu_cached = True
                _cache_locs(res.get("locations"))
                _cache_locs(res.get("error_locations"))
            for spec in batch.specs:
                for oid in spec.get("return_ids", ()):
                    _inflight_direct.pop(oid, None)
                    _direct_task_meta.pop(oid, None)
        else:
            for spec in batch.specs:
                for oid in spec.get("return_ids", ()):
                    _inflight_direct.pop(oid, None)
                    _direct_task_meta.pop(oid, None)
            # Runs on the io thread — hand recovery to a plain thread (it
            # issues blocking controller RPCs).
            threading.Thread(
                target=_direct_failure_specs,
                args=(wc, route, list(batch.specs), exc),
                daemon=True, name="direct-recover").start()

    return done


# In-flight direct calls by return id: get() awaits these instead of asking
# the controller for locations the reply will carry any moment.
_inflight_direct: Dict[str, Any] = {}
# return oid -> (task_id, route conn): lets ray_tpu.cancel reach tasks the
# controller never saw (direct lease pushes).
_direct_task_meta: Dict[str, Any] = {}


def _note_task_lineage(wc, spec: Dict[str, Any]) -> None:
    """Ship the parent->child ownership edge for a directly-pushed spec so
    rtpu.cancel(recursive=True) can find it (fire-and-forget; only emitted
    when submitting from INSIDE a task)."""
    try:
        wc.client.send_nowait(
            {"kind": "task_lineage",
             "edges": [(spec["parent_task_id"], spec["task_id"])]})
    except Exception:
        pass


def _direct_submit(wc, route: "_ActorRoute", spec: Dict[str, Any]) -> bool:
    conn = route.conn
    if conn is None:
        return False
    if conn.closed.is_set():
        # Stale route: the actor's old worker died (e.g. its node drained
        # and the actor migrated). Nothing was sent — drop the route and
        # let the caller take the controller path / re-resolve.
        _invalidate_route(wc, route)
        return False
    batcher = route.batcher
    if batcher is not None and flags.get("RTPU_SUBMIT_BATCH"):
        # Batched push: calls appended in one loop beat ride one frame;
        # per-batch bookkeeping lives in _make_actor_batch_done.
        for oid in spec.get("return_ids", ()):
            _direct_task_meta[oid] = (spec["task_id"], conn)
        batcher.add(spec, spec.get("return_ids", ()))
        return True
    try:
        fut = conn.request_threadsafe(
            {"kind": "direct_actor_task", "spec": spec})
    except Exception:
        _invalidate_route(wc, route)
        return False
    for oid in spec.get("return_ids", ()):
        _inflight_direct[oid] = fut
        # Cancel routing: rtpu.cancel(ref) on a direct-pushed actor call
        # rides this same connection straight to the hosting worker — the
        # controller never saw the spec, so it could not help.
        _direct_task_meta[oid] = (spec["task_id"], conn)

    def done(f, wc=wc, route=route, spec=spec):
        for oid in spec.get("return_ids", ()):
            _inflight_direct.pop(oid, None)
            _direct_task_meta.pop(oid, None)
        exc = f.exception()
        if exc is None:
            res = f.result() or {}
            for loc in (res.get("locations") or ()):
                _cache_loc(loc)
            for loc in (res.get("error_locations") or ()):
                _cache_loc(loc)
        else:
            # Runs on the io thread — hand recovery to a plain thread (it
            # issues blocking controller RPCs).
            threading.Thread(
                target=_direct_failure, args=(wc, route, spec, exc),
                daemon=True, name="direct-recover").start()

    fut.add_done_callback(done)
    return True


def _direct_failure(wc, route: "_ActorRoute", spec: Dict[str, Any],
                    exc: BaseException) -> None:
    _direct_failure_specs(wc, route, [spec], exc)


def _direct_failure_specs(wc, route: "_ActorRoute",
                          specs: List[Dict[str, Any]],
                          exc: BaseException) -> None:
    """Direct actor call(s) failed — one push or a whole batch; the same
    decision applies per spec. Resubmit through the controller ONLY when
    the call provably never executed:

    - NeverSentError: the route's connection was already closed at submit —
      the bytes never left this process.
    - ActorNotHostedError: the worker REFUSED the call before any user code
      ran (the actor migrated off a draining node, or died there).
    - A dead connection where the controller says the actor has MOVED off
      the route's worker (drain migration): migration snapshots the
      instance after every queued call completes AND publishes those
      results before the old worker exits, so a call with no published
      results never ran. Results already published mean the call DID
      complete — cache them instead of resubmitting.

    Anything else fails with ActorDiedError — the reference's default
    actor-task semantics: the worker may have executed the call before the
    connection dropped, and silently re-running a non-idempotent method
    would corrupt actor state.

    The error publication is if_absent: the worker's own fire-and-forget
    task_done may have carried real result locations before it died — a
    completed call must stay completed for third-party consumers.
    """
    from . import protocol
    from .controller import ActorNotHostedError

    old_worker = route.worker_id
    _invalidate_route(wc, route)
    resubmit = isinstance(exc, (protocol.NeverSentError, ActorNotHostedError))
    if not resubmit and specs and specs[0].get("replay"):
        # Exactly-once replay actor (max_task_retries): resubmission needs
        # no never-ran proof — calls that DID execute short-circuit on the
        # restored journal, so re-sending can never double-apply them.
        resubmit = True
    done_ids: set = set()
    moved = False
    if not resubmit and isinstance(exc, (ConnectionError, OSError, EOFError)):
        try:
            info = wc.client.request(
                {"kind": "resolve_actor", "actor_id": specs[0]["actor_id"]})
        except Exception:
            info = None
        d = (info or {}).get("direct") or {}
        moved = info is not None and (
            info.get("state") in ("pending", "restarting")
            or (info.get("state") == "alive"
                and d.get("worker_id") not in (None, old_worker)))
        if moved:
            # Which calls completed before the worker left? Migration
            # publishes completed results before the old worker exits, so
            # one wait probe splits the batch: published ⇒ completed
            # (cache, never re-run), unpublished ⇒ never ran (resubmit).
            all_ids = [oid for s in specs
                       for oid in (s.get("return_ids") or ())]
            try:
                ready = wc.client.request(
                    {"kind": "wait", "object_ids": all_ids,
                     "num_returns": len(all_ids), "timeout": 0})
                done_ids = set(ready or ())
            except Exception:
                done_ids = set()
            if done_ids:
                try:
                    locs = wc.client.request(
                        {"kind": "get_locations",
                         "object_ids": sorted(done_ids), "timeout": 1})
                    for loc in locs.values():
                        _cache_loc(loc)
                except Exception:
                    pass
            resubmit = True
    for spec in specs:
        rids = spec.get("return_ids") or ()
        if moved and (not rids
                      or all(oid in done_ids for oid in rids)):
            continue  # the call completed before the worker left
        _finish_failed_actor_call(wc, spec, exc, resubmit)


def _finish_failed_actor_call(wc, spec: Dict[str, Any], exc: BaseException,
                              resubmit: bool) -> None:
    import pickle as _p

    from .controller import ActorDiedError
    from .object_store import ObjectLocation

    if resubmit:
        try:
            wc.client.request({"kind": "submit_actor_task", "spec": spec})
            return
        except Exception:
            pass  # controller unreachable too: fail the call below
    err = ActorDiedError(
        f"actor {spec['actor_id'][:8]} died during a direct call "
        f"({type(exc).__name__}: {exc})")
    data = _p.dumps(err)
    for oid in spec.get("return_ids", ()):
        loc = ObjectLocation(object_id=oid, size=len(data), inline=data,
                             is_error=True)
        if oid not in _local_locs:
            _cache_loc(loc)
        try:
            wc.client.request(
                {"kind": "put_location", "loc": loc, "if_absent": True})
        except Exception:
            pass


def _reset_direct_state(wc=None) -> None:
    if wc is not None:
        for route in list(_routes.values()):
            _invalidate_route(wc, route)  # closes the direct sockets
        for pool in list(_task_pools.values()):
            pool.shutdown(wc)
    _routes.clear()
    _task_pools.clear()
    _local_locs.clear()
    _inflight_direct.clear()
    _direct_task_meta.clear()
    with _inflight_lock:
        _inflight_specs.clear()
        _inflight_oid2task.clear()


# ---- task leases (direct stateless-task dispatch) --------------------------
# Reference: direct_task_transport.h:75 — the owner leases a worker from the
# raylet and pushes tasks to it peer-to-peer; the lease pins the worker's
# resources. The pool below keeps up to _LEASE_MAX leased workers per
# (resources, env) signature, grows while every route is saturated, and
# releases leases that sit idle. Streaming / placement-group / affinity
# tasks stay on the controller path.

_LEASE_PIPELINE = 1         # grow the pool when every route is busy
_LEASE_IDLE_S = 2.0         # release a lease unused this long
_LEASE_BACKOFF_S = 0.5      # after an EMPTY grant, don't retry sooner
_LEASE_GROW_THROTTLE_S = 0.1  # min spacing between growth RPCs otherwise


def _reclaim_leases(lease_ids) -> None:
    """Release every idle route whose lease the controller asked back."""
    try:
        wc = ctx.get_worker_context()
    except Exception:
        return
    for pool in list(_task_pools.values()):
        with pool.lock:
            victims = [r for r in pool.routes
                       if r.lease_id in lease_ids and r.inflight == 0]
            # Out of the pool BEFORE releasing, or a concurrent pick() can
            # hand a mid-release route to a new submit (double-booked
            # worker + spurious WorkerCrashedError on a retry-less task).
            pool.routes = [r for r in pool.routes if r not in victims]
        if victims:
            pool._release_many(wc, victims)


class _TaskRoute:
    __slots__ = ("conn", "lease_id", "worker_id", "node_id", "inflight",
                 "last_used", "batcher", "retired")

    def __init__(self, conn, lease_id: str, worker_id: str,
                 node_id: str = "") -> None:
        self.conn = conn
        self.lease_id = lease_id
        self.worker_id = worker_id
        self.node_id = node_id
        self.inflight = 0
        self.last_used = time.monotonic()
        self.batcher: Optional[_PushBatcher] = None
        # A retired route (controller bounced: its lease ledger is gone)
        # serves its in-flight pushes to completion but takes no new work;
        # the batch done-callback closes the conn once inflight drains.
        self.retired = False


class _TaskRoutePool:
    def __init__(self) -> None:
        self.routes: List[_TaskRoute] = []
        self.lock = threading.Lock()
        self.next_try = 0.0    # monotonic; backoff after failed lease
        self.acquiring = 0     # in-flight _acquire calls (caps pool growth)

    def _acquire_block(self, wc, resources, env_hash, runtime_env,
                       arg_bytes=None, count: int = 1
                       ) -> Optional[_TaskRoute]:
        """ONE lease_block round trip grants up to ``count`` workers; every
        grant becomes a live route, so the wave fans across the block with
        no further controller involvement. Returns the first route born
        checked-out (the caller's task rides it); extra routes join the
        pool idle."""
        from . import protocol

        try:
            got = wc.client.request({
                "kind": "lease_block", "count": max(1, count),
                "resources": resources,
                "env_hash": env_hash, "runtime_env": runtime_env,
                "arg_bytes": arg_bytes or {}})
        except Exception:
            got = None
        grants = (got or {}).get("grants") or []
        if not grants:
            # Empty grant: the cluster has nothing leasable for this
            # signature right now — back off the full window. A PARTIAL
            # grant only keeps the shorter pick()-side growth throttle:
            # punitive backoff there serialized genuinely-parallel work
            # onto one route for the whole window.
            with self.lock:
                self.next_try = time.monotonic() + _LEASE_BACKOFF_S
            return None
        first: Optional[_TaskRoute] = None
        stranded: List[str] = []
        for g in grants:
            try:
                conn = wc.client.io.call(
                    protocol.connect(g["host"], g["port"],
                                     name=f"lease->{g['worker_id'][:8]}"),
                    timeout=5)
            except Exception:
                stranded.append(g["lease_id"])
                continue
            route = _TaskRoute(conn, g["lease_id"], g["worker_id"],
                               g.get("node_id") or "")
            route.batcher = _PushBatcher(
                "direct_task_batch", conn, wc.client.io,
                self._make_batch_done(wc, route))
            if first is None:
                # Born checked-out (inflight=1): a freshly acquired route
                # must never be visible to _reclaim_leases / the idle
                # reaper with inflight==0 while its first submit is still
                # in flight (advisor r4: that window releases the lease
                # under the push and fabricates a WorkerCrashedError on a
                # retry-less task).
                route.inflight = 1
                first = route
            with self.lock:
                self.routes.append(route)
        if stranded:
            try:
                wc.client.conn.request_threadsafe(
                    {"kind": "release_lease", "lease_ids": stranded})
            except Exception:
                pass
        return first

    def _make_batch_done(self, wc, route: "_TaskRoute"):
        """Done-callback for one route's push batches (io thread): settle
        bookkeeping for every spec in the batch, cache the aggregated
        result locations once, and hand transport failures to a recovery
        thread that distinguishes completed entries from never-ran ones."""

        def done(batch: _PushBatch, res, exc) -> None:
            specs = batch.specs
            with self.lock:
                route.inflight -= len(specs)
                route.last_used = time.monotonic()
                close_retired = route.retired and route.inflight <= 0
            if exc is None:
                # Same mark _await_inflight uses: whichever side processes
                # the aggregated payload first spares the other the walk.
                if not getattr(batch.fut, "_rtpu_cached", False):
                    batch.fut._rtpu_cached = True
                    _cache_locs(res.get("locations"))
                    _cache_locs(res.get("error_locations"))
                for spec in specs:
                    for oid in spec.get("return_ids", ()):
                        _inflight_direct.pop(oid, None)
                        _direct_task_meta.pop(oid, None)
                if close_retired:
                    try:
                        wc.client.io.call_nowait(route.conn.close())
                    except Exception:
                        pass
            else:
                for spec in specs:
                    for oid in spec.get("return_ids", ()):
                        _inflight_direct.pop(oid, None)
                        _direct_task_meta.pop(oid, None)
                threading.Thread(
                    target=_direct_batch_task_failure,
                    args=(wc, self, route, list(specs)),
                    daemon=True, name="lease-recover").start()

        return done

    def _release_many(self, wc, routes: List["_TaskRoute"]) -> None:
        """Hand back several leases in ONE framed message + close conns."""
        with self.lock:
            self.routes = [r for r in self.routes if r not in routes]
        ids = [r.lease_id for r in routes]
        if ids:
            try:
                wc.client.conn.request_threadsafe(
                    {"kind": "release_lease", "lease_ids": ids})
            except Exception:
                pass
        for r in routes:
            try:
                wc.client.io.call_nowait(r.conn.close())
            except Exception:
                pass

    def _release(self, wc, route: _TaskRoute) -> None:
        with self.lock:
            if route in self.routes:
                self.routes.remove(route)
        try:
            wc.client.conn.request_threadsafe(
                {"kind": "release_lease", "lease_id": route.lease_id})
        except Exception:
            pass
        try:
            wc.client.io.call_nowait(route.conn.close())
        except Exception:
            pass

    def pick(self, wc, resources, env_hash, runtime_env,
             arg_bytes=None, lease_max: Optional[int] = None
             ) -> Optional[_TaskRoute]:
        """Least-loaded live route; grows the pool synchronously whenever
        every route is busy (one leased worker per concurrent task, the
        reference's lease-per-pending-task shape — async growth would
        serialize two parallel tasks onto one worker) and reaps idle
        leases. `arg_bytes` ({node_id: bytes of this task's args there})
        prefers an unsaturated route on the data node and rides to the
        controller on pool growth so new leases land there too."""
        now = time.monotonic()
        with self.lock:
            # One pass: drop dead routes, reap idle leases, find the
            # least-loaded survivor (this runs per submit — list-building
            # per call showed up in submission profiles). Reap every idle
            # lease: a held lease pins a CPU the scheduler can't use for
            # queued tasks or actor creation. Reaped routes leave the pool
            # BEFORE selection so this submit can't ride a lease being
            # handed back.
            live: List[_TaskRoute] = []
            reap: List[_TaskRoute] = []
            best = None
            for r in self.routes:
                if r.conn.closed.is_set():
                    continue
                if r.inflight == 0 and now - r.last_used > _LEASE_IDLE_S:
                    reap.append(r)
                    continue
                live.append(r)
                if best is None or r.inflight < best.inflight:
                    best = r
            self.routes = live
            for r in reap:
                threading.Thread(target=self._release, args=(wc, r),
                                 daemon=True).start()
            want_local = False
            if arg_bytes and live:
                # Locality preference: an unsaturated route on the node
                # holding the most argument bytes beats the globally
                # least-loaded route (the bytes don't move; the task can).
                data_node = max(arg_bytes, key=arg_bytes.get)
                local = [r for r in live if r.node_id == data_node
                         and r.inflight < _LEASE_PIPELINE]
                if local:
                    best = min(local, key=lambda r: r.inflight)
                else:
                    # No route on the data node: grow toward it (the new
                    # lease request carries arg_bytes, so the controller
                    # grants there) instead of shipping the bytes over the
                    # network forever through an idle wrong-node route.
                    want_local = True
            if lease_max is None:
                lease_max = flags.get("RTPU_TASK_LEASE_MAX")
            # acquiring counts toward the cap: N threads deciding to grow
            # simultaneously must not overshoot lease_max between them.
            need_grow = ((best is None
                          or best.inflight >= _LEASE_PIPELINE
                          or want_local)
                         and len(live) + self.acquiring < lease_max
                         and now >= self.next_try)
            # Bulk negotiation: ask for a whole block up front (the first
            # grow of a wave fills the pool in one RPC; later grows top it
            # up), never past the per-signature lease cap.
            block_n = min(max(1, flags.get("RTPU_LEASE_BLOCK")),
                          lease_max - len(live) - self.acquiring) \
                if need_grow else 0
            if best is not None:
                # Checkout under THIS lock acquisition (advisor r4): the
                # route leaves pick() already counted busy, so the reclaim
                # and idle-reap inflight==0 tests can never select it
                # between pick() returning and the submit landing. The
                # caller decrements on submit failure.
                best.inflight += 1
                best.last_used = now
            if need_grow:
                self.acquiring += block_n
                # Rolling growth throttle: at most one negotiation RPC per
                # window while saturated (a wave would otherwise pay one
                # per submit); an EMPTY grant extends this to the full
                # backoff in _acquire_block.
                self.next_try = now + _LEASE_GROW_THROTTLE_S
        if need_grow:
            try:
                got = self._acquire_block(wc, resources, env_hash,
                                          runtime_env, arg_bytes=arg_bytes,
                                          count=block_n)
            finally:
                with self.lock:
                    self.acquiring -= block_n
            if want_local and got is not None and arg_bytes and \
                    got.node_id != max(arg_bytes, key=arg_bytes.get):
                # Grew FOR locality but the grant landed off the data node
                # (no capacity there): back off further locality growth so
                # a stream of submits doesn't inflate the pool with
                # off-node leases, one lease RPC per task. The off-node
                # route still serves this task.
                with self.lock:
                    self.next_try = time.monotonic() + _LEASE_BACKOFF_S
            if got is not None:
                # The new route is born checked-out; hand back the
                # speculative reservation on the old best.
                if best is not None:
                    with self.lock:
                        best.inflight -= 1
                        best.last_used = time.monotonic()
                best = got
            elif best is not None and not want_local:
                # Growth was ATTEMPTED because every route was saturated,
                # and the grant came back empty: the cluster has no idle
                # worker for this signature right now. Spill THIS submit to
                # the controller queue (which spawns workers / dispatches
                # when one frees) instead of deepening a busy route's
                # serial queue — two long concurrent tasks must not
                # serialize behind one lease while CPUs sit free. Bounded:
                # only the submit that performed the (throttled+backed-off)
                # negotiation spills; the wave keeps riding the pool.
                with self.lock:
                    best.inflight -= 1
                    best.last_used = time.monotonic()
                return None
        return best

    def shutdown(self, wc) -> None:
        self._release_many(wc, list(self.routes))


_task_pools: Dict[Tuple, _TaskRoutePool] = {}
_task_pools_lock = threading.Lock()


def _try_direct_task(wc, spec: Dict[str, Any], opts: Dict[str, Any]) -> bool:
    """Push a plain task to a leased worker; False -> controller path."""
    lease_max = flags.get("RTPU_TASK_LEASE_MAX")
    if (spec.get("pg") is not None
            or spec.get("scheduling", {}).get("type") != "DEFAULT"
            or spec.get("retry_exceptions")  # app-error retry is a
            # controller-queue feature: the direct path reports errors
            # straight back to the caller
            or spec.get("streaming")
            or not lease_max
            or not flags.get("RTPU_DIRECT_DISPATCH")):
        return False
    # Deps guard: a leased worker BLOCKS in get_locations for unresolved
    # deps while its lease pins a CPU — if the producer is still queued at
    # the controller, that pin can starve it forever (the controller path
    # waits for deps BEFORE dispatch, so it can't deadlock this way). Only
    # push when every dep's location is already known locally; ship those
    # as hints so the worker skips the controller lookup entirely.
    deps = spec.get("deps") or ()
    hints = {}
    for d in deps:
        loc = _local_locs.get(d)
        if loc is None:
            return False
        hints[d] = loc
    resources = spec.get("resources") or {"CPU": 1.0}
    env_hash = spec.get("env_hash") or ""
    key = (wc.client.token, env_hash,
           tuple(sorted(resources.items())))
    with _task_pools_lock:
        pool = _task_pools.get(key)
        if pool is None:
            pool = _task_pools[key] = _TaskRoutePool()
    # pick() returns the route already checked out (inflight counted under
    # the pool lock) — decrement on any failure to submit.
    arg_bytes: Dict[str, int] = {}
    for loc in hints.values():
        if loc.node_id and loc.inline is None:
            arg_bytes[loc.node_id] = arg_bytes.get(loc.node_id, 0) + loc.size
    route = pool.pick(wc, resources, env_hash, spec.get("runtime_env"),
                      arg_bytes=arg_bytes, lease_max=lease_max)
    if route is None:
        return False
    if hints:
        # Only the secured direct route carries cached-location hints: the
        # controller fallback re-resolves locations itself, and a hint that
        # went stale while queued there would turn a recoverable miss into
        # a task read failure (advisor r4).
        spec["loc_hints"] = hints
    if flags.get("RTPU_SUBMIT_BATCH"):
        # Batched push: the spec rides the route's open multi-spec frame;
        # bookkeeping (inflight maps, location caching, failure recovery)
        # is settled per batch by the route's done callback.
        route.batcher.add(spec, spec.get("return_ids", ()),
                          meta=(spec["task_id"], route.conn))
        return True
    try:
        fut = route.conn.request_threadsafe(
            {"kind": "direct_task", "spec": spec})
    except Exception:
        spec.pop("loc_hints", None)  # controller fallback re-resolves
        with pool.lock:
            route.inflight -= 1
        return False
    for oid in spec.get("return_ids", ()):
        _inflight_direct[oid] = fut
        _direct_task_meta[oid] = (spec["task_id"], route.conn)

    def done(f, wc=wc, pool=pool, route=route, spec=spec):
        with pool.lock:
            route.inflight -= 1
            route.last_used = time.monotonic()
            close_retired = route.retired and route.inflight <= 0
        if close_retired:
            try:
                wc.client.io.call_nowait(route.conn.close())
            except Exception:
                pass
        for oid in spec.get("return_ids", ()):
            _inflight_direct.pop(oid, None)
            _direct_task_meta.pop(oid, None)
        exc = f.exception()
        if exc is None:
            res = f.result() or {}
            for loc in (res.get("locations") or ()):
                _cache_loc(loc)
            for loc in (res.get("error_locations") or ()):
                _cache_loc(loc)
        else:
            # Worker/connection died mid-push. The direct attempt counts
            # against max_retries exactly like a controller-tracked attempt
            # (the task may have partially executed — re-running a
            # max_retries=0 task would violate its at-most-once contract).
            # Off the io thread: recovery issues blocking RPCs.
            threading.Thread(
                target=_direct_task_failure, args=(wc, pool, route, spec),
                daemon=True, name="lease-recover").start()

    fut.add_done_callback(done)
    return True


def _direct_task_failure(wc, pool: "_TaskRoutePool", route: "_TaskRoute",
                         spec: Dict[str, Any]) -> None:
    pool._release(wc, route)
    _requeue_or_fail_direct_task(wc, route, spec)


def _requeue_or_fail_direct_task(wc, route: "_TaskRoute",
                                 spec: Dict[str, Any]) -> None:
    """The push failed and the task did NOT provably complete. The direct
    attempt counts against max_retries exactly like a controller-tracked
    attempt; with no budget left the at-most-once contract stands and the
    task fails with WorkerCrashedError."""
    retries = int(spec.get("max_retries", 0))
    if retries > 0:
        spec = dict(spec, max_retries=retries - 1)
        # The hints plausibly point at objects hosted on the worker that
        # just crashed — the controller path must re-resolve fresh.
        spec.pop("loc_hints", None)
        try:
            _track_inflight(spec)  # it now rides the controller queue
            _pipelined_submit(wc, {"kind": "submit_task", "spec": spec},
                              spec.get("return_ids", ()))
        except Exception:
            pass
        return
    import pickle as _p

    from .controller import WorkerCrashedError
    from .object_store import ObjectLocation

    err = WorkerCrashedError(
        f"worker {route.worker_id[:8]} died while running directly-pushed "
        f"task {spec.get('label', '')} (no retries left)")
    data = _p.dumps(err)
    for oid in spec.get("return_ids", ()):
        loc = ObjectLocation(object_id=oid, size=len(data), inline=data,
                             is_error=True)
        if oid not in _local_locs:
            _cache_loc(loc)
        try:
            wc.client.request(
                {"kind": "put_location", "loc": loc, "if_absent": True})
        except Exception:
            pass


def _direct_batch_task_failure(wc, pool: "_TaskRoutePool",
                               route: "_TaskRoute",
                               specs: List[Dict[str, Any]]) -> None:
    """A batched push failed mid-flight (worker death / dead connection).
    Entries that already completed published their result locations to the
    controller through the worker's completion batcher — ONE wait probe
    sorts the batch into completed (cache, never re-run: no duplication)
    and unacked (re-route through the controller: no loss)."""
    pool._release(wc, route)
    all_ids = [oid for s in specs for oid in (s.get("return_ids") or ())]
    done_ids: set = set()
    if all_ids:
        try:
            ready = wc.client.request(
                {"kind": "wait", "object_ids": all_ids,
                 "num_returns": len(all_ids), "timeout": 0})
            done_ids = set(ready or ())
        except Exception:
            done_ids = set()
        if done_ids:
            try:
                locs = wc.client.request(
                    {"kind": "get_locations",
                     "object_ids": sorted(done_ids), "timeout": 1})
                for loc in locs.values():
                    _cache_loc(loc)
            except Exception:
                pass  # get() re-asks the controller; completion stands
    for spec in specs:
        rids = spec.get("return_ids") or ()
        if rids and all(oid in done_ids for oid in rids):
            continue  # completed and published before the route died
        _requeue_or_fail_direct_task(wc, route, spec)


def _pipelined_submit(wc, msg: Dict[str, Any], return_ids) -> None:
    """Submit without waiting for the controller's ack (the reply is
    pipelined on the connection, so ordering with later requests holds).
    A connection drop retries through the client's reconnect path (the
    controller may just be bouncing — puts/submits in flight survive);
    a real submission failure surfaces as error locations on the return
    ids — the same channel task-execution errors use."""
    fut = wc.client.conn.request_threadsafe(msg)

    def fail(exc, return_ids):
        import pickle as _p
        import sys as _sys

        from .object_store import ObjectLocation

        # Fire-and-forget callers never get() these refs — make sure the
        # failure is at least visible somewhere.
        _sys.stderr.write(f"[ray_tpu] pipelined submit failed: {exc!r}\n")
        data = _p.dumps(exc if isinstance(exc, Exception)
                        else RuntimeError(repr(exc)))
        for oid in return_ids:
            loc = ObjectLocation(object_id=oid, size=len(data), inline=data,
                                 is_error=True)
            _cache_loc(loc)
            try:
                wc.client.send_nowait({"kind": "put_location", "loc": loc})
            except Exception:
                pass

    def done(f, wc=wc, msg=msg, return_ids=tuple(return_ids)):
        exc = f.exception()
        if exc is None:
            return
        if (isinstance(exc, ConnectionError)
                and wc.client.reconnect_enabled
                and not wc.client._closed):
            # Controller bounce mid-flight: re-issue through the blocking
            # client (it reconnects with backoff) off the io thread.
            def _retry():
                try:
                    wc.client.request(msg)
                except Exception as e2:  # noqa: BLE001
                    fail(e2, return_ids)

            threading.Thread(target=_retry, daemon=True,
                             name="submit-retry").start()
            return
        fail(exc, return_ids)

    fut.add_done_callback(done)


def _await_inflight(ids, timeout: Optional[float]) -> None:
    """Wait for in-flight direct replies covering `ids` (their locations
    land in _local_locs via the completion callback). Batched pushes share
    one future across many return ids — each distinct future is awaited
    and its aggregated payload processed once, not once per id."""
    deadline = None if timeout is None else time.monotonic() + timeout
    seen: set = set()
    for oid in ids:
        fut = _inflight_direct.get(oid)
        if fut is None or id(fut) in seen:
            continue
        seen.add(id(fut))
        try:
            res = fut.result(None if deadline is None
                             else max(0.0, deadline - time.monotonic()))
        except Exception:
            # Failure recovery (error locations) happens in the done
            # callback / recovery thread; fall through to the controller.
            continue
        # Cache here too: the done-callback runs on the io thread and may
        # not have fired yet when result() unblocks (idempotent with it;
        # the _rtpu_cached mark keeps a 500-entry batch from being
        # re-walked for every one of its ids).
        if getattr(fut, "_rtpu_cached", False):
            continue
        fut._rtpu_cached = True
        _cache_locs((res or {}).get("locations"))
        _cache_locs((res or {}).get("error_locations"))


def exit_actor() -> None:
    """Reference: ray.actor.exit_actor — terminate the hosting actor after
    the current call (implemented in core.worker; re-exported here for the
    package root)."""
    from .worker import exit_actor as _exit_actor

    _exit_actor()


def method(*, num_returns: int = 1):
    """Per-method defaults (reference: @ray.method(num_returns=N)) —
    consumed when the actor class registers, carried on every handle."""
    def deco(fn):
        fn.__rtpu_method_opts__ = {"num_returns": num_returns}
        return fn

    return deco


class ActorMethod:
    def __init__(self, handle: "ActorHandle", name: str, num_returns=1,
                 deadline_s=None):
        self._handle = handle
        self._name = name
        self._num_returns = num_returns
        self._deadline_s = deadline_s

    def options(self, num_returns=1, deadline_s=None) -> "ActorMethod":
        return ActorMethod(self._handle, self._name, num_returns, deadline_s)

    def remote(self, *args, **kwargs):
        return self._handle._submit(self._name, args, kwargs,
                                    self._num_returns,
                                    deadline_s=self._deadline_s)

    def bind(self, *args, **kwargs):
        """Lazy DAG node for this method on an existing actor handle."""
        from ray_tpu.dag.dag_node import ClassMethodNode

        return ClassMethodNode(self._handle, self._name, args, kwargs)


class ActorHandle:
    """Client-side handle to an actor (reference: actor.py ActorHandle)."""

    def __init__(self, actor_id: str, method_names: Sequence[str],
                 method_defaults: Optional[Dict[str, Dict[str, Any]]] = None,
                 replayable: bool = False):
        self._actor_id = actor_id
        self._method_names = list(method_names)
        self._method_defaults = dict(method_defaults or {})
        # max_task_retries actor: calls carry the replay flag, so a failed
        # path may resubmit them without a never-ran proof (the actor's
        # exactly-once journal dedups any that actually executed).
        self._replayable = bool(replayable)
        # Per-method static spec template (see RemoteFunction._tmpl): a
        # call serializes only its args, ids and seqno; batched pushes
        # pickle the shared fields once per frame.
        self._tmpls: Dict[str, Dict[str, Any]] = {}

    def __getattr__(self, name: str) -> ActorMethod:
        if name.startswith("_"):
            raise AttributeError(name)
        if self._method_names and name not in self._method_names:
            raise AttributeError(f"actor has no method {name!r}")
        return ActorMethod(self, name,
                           self._method_defaults.get(name, {}).get(
                               "num_returns", 1))

    def _submit(self, method: str, args, kwargs, num_returns,
                deadline_s=None):
        wc = ctx.get_worker_context()
        streaming = num_returns == "streaming"
        args_blob, deps, nested_refs = pack_args(args, kwargs)
        n_rets = 0 if streaming else max(num_returns, 0)
        return_ids = [ObjectID.generate() for _ in range(n_rets)]
        tmpl = self._tmpls.get(method)
        if tmpl is None:
            tmpl = self._tmpls[method] = {
                "actor_id": self._actor_id,
                "method_name": method,
                "resources": {},
                "label": f"actor.{method}",
                **({"replay": True} if self._replayable else {}),
                # "caller" anchors the per-(caller, actor) sequence
                # numbers: calls from one caller can ride different paths
                # (direct socket vs controller fallback) and overtake each
                # other; the mailbox restores submission order (reference:
                # direct_actor_task_submitter's per-caller sequence_no).
                "caller": ownership.process_token(),
            }
        spec = dict(tmpl)
        spec["task_id"] = TaskID.generate()
        spec["args_blob"] = args_blob
        spec["deps"] = deps
        spec["return_ids"] = return_ids
        spec["seqno"] = _next_actor_seqno(self._actor_id)
        if deadline_s is not None:
            # Absolute deadline: mailbox dequeue drops the call once it
            # passes instead of executing dead work.
            spec["deadline_ts"] = time.time() + float(deadline_s)
        ptid = ctx.current_task_id()
        if ptid:
            spec["parent_task_id"] = ptid
        if streaming:
            _streaming_spec_opts({}, spec)
        if deps or nested_refs:
            _register_dep_holds(spec, nested_refs)
        tracing.inject_submit_span(spec, spec["label"])
        if flags.get("RTPU_TASK_EVENTS"):
            spec["submit_ts"] = time.time()
        submitted = False
        if not streaming and flags.get("RTPU_DIRECT_DISPATCH"):
            route = _get_route(wc, self._actor_id)
            if route.conn is not None or _resolve_route(
                    wc, route, self._actor_id):
                hints = {d: _local_locs[d] for d in deps if d in _local_locs}
                if hints:
                    spec["loc_hints"] = hints
                submitted = _direct_submit(wc, route, spec)
        if not submitted:
            wc.client.request({"kind": "submit_actor_task", "spec": spec})
        elif "parent_task_id" in spec:
            _note_task_lineage(wc, spec)
        if streaming:
            return ObjectRefGenerator(spec["task_id"])
        refs = _claim_return_refs(return_ids)
        if num_returns == 1:
            return refs[0]
        if num_returns == 0:
            return None
        return refs

    def __reduce__(self):
        return (ActorHandle, (self._actor_id, self._method_names,
                              self._method_defaults, self._replayable))

    def __repr__(self) -> str:
        return f"ActorHandle({self._actor_id[:16]})"


class ActorClass:
    def __init__(self, cls: type, options: Optional[Dict[str, Any]] = None):
        self._cls = cls
        self._options = options or {}
        self._func_id: Optional[str] = None
        self._registered_with: Optional[str] = None

    def options(self, **opts) -> "ActorClass":
        new = ActorClass(self._cls, {**self._options, **opts})
        new._func_id = self._func_id
        new._registered_with = self._registered_with
        return new

    def _ensure_registered(self, wc: ctx.WorkerContext) -> str:
        key = wc.client.token
        if self._func_id is None or self._registered_with != key:
            blob = cloudpickle.dumps(self._cls)
            func_id = TaskID.generate()
            wc.client.request({"kind": "register_function", "func_id": func_id, "blob": blob})
            self._func_id = func_id
            self._registered_with = key
        return self._func_id

    def remote(self, *args, **kwargs) -> ActorHandle:
        wc = ctx.get_worker_context()
        func_id = self._ensure_registered(wc)
        opts = self._options
        resources = dict(opts.get("resources", {}) or {})
        # Actors default to 0 CPU while alive (reference semantics — this is
        # what lets 40k actors coexist on a node; ray actor.py default).
        resources["CPU"] = float(opts.get("num_cpus", 0))
        if opts.get("num_tpus"):
            resources["TPU"] = float(opts["num_tpus"])
        _validate_accel_resources(resources)
        strategy, pg = _normalize_strategy(opts.get("scheduling_strategy"))
        args_blob, deps, nested_refs = pack_args(args, kwargs)
        actor_id = ActorID.generate()
        method_names = [
            n for n in dir(self._cls)
            if not n.startswith("_") and callable(getattr(self._cls, n, None))
        ]
        # Crash-consistent fault tolerance (reference: ray actor options
        # max_restarts/max_task_retries + the Ray paper's actor
        # checkpointing): checkpoint_interval_s / checkpoint_every_n make
        # the hosting worker durably checkpoint the instance (plus the
        # exactly-once call journal); max_task_retries != 0 (-1 = always)
        # opts method calls into replay-on-failure — retried calls dedup
        # against the journal, so replay is exactly-once, not at-least.
        max_task_retries = int(opts.get("max_task_retries", 0))
        spec = {
            "task_id": TaskID.generate(),
            "actor_id": actor_id,
            "func_id": func_id,
            "args_blob": args_blob,
            "deps": deps,
            "return_ids": [],
            "resources": {k: v for k, v in resources.items() if v},
            "scheduling": strategy,
            "pg": pg,
            "name": opts.get("name"),
            "namespace": wc.namespace,
            "detached": opts.get("lifetime") == "detached",
            "max_concurrency": opts.get("max_concurrency", 1),
            "max_restarts": int(opts.get("max_restarts", 0)),
            "max_task_retries": max_task_retries,
            "checkpoint_interval_s": float(
                opts.get("checkpoint_interval_s") or 0.0),
            "checkpoint_every_n": int(opts.get("checkpoint_every_n") or 0),
            "label": f"{self._cls.__name__}.__init__",
        }
        _attach_runtime_env(wc, opts, spec)
        _register_dep_holds(spec, nested_refs)
        tracing.inject_submit_span(spec, spec["label"])
        wc.client.request({"kind": "create_actor", "spec": spec})
        method_defaults = {
            n: getattr(getattr(self._cls, n), "__rtpu_method_opts__")
            for n in method_names
            if hasattr(getattr(self._cls, n, None), "__rtpu_method_opts__")
        }
        wc.client.request(
            {"kind": "kv_put", "ns": "__actor_methods__", "key": actor_id,
             "value": cloudpickle.dumps(
                 (method_names, method_defaults,
                  {"replayable": bool(max_task_retries)}))}
        )
        return ActorHandle(actor_id, method_names, method_defaults,
                           replayable=bool(max_task_retries))

    def bind(self, *args, **kwargs):
        """Lazy actor construction node (reference python/ray/dag/class_node.py)."""
        from ray_tpu.dag.dag_node import ClassNode

        return ClassNode(self, args, kwargs)


def remote(*args, **kwargs):
    """``@remote`` decorator for functions and classes, with option form
    ``@remote(num_cpus=..., num_tpus=..., resources=..., ...)``."""

    def wrap(target):
        if isinstance(target, type):
            return ActorClass(target, kwargs)
        return RemoteFunction(target, kwargs)

    if len(args) == 1 and not kwargs and (callable(args[0]) or isinstance(args[0], type)):
        return wrap(args[0])
    if args:
        raise TypeError("use @remote or @remote(**options)")
    return wrap


def cancel(ref: ObjectRef, *, force: bool = False,
           recursive: bool = False) -> None:
    """Cancel the task producing ``ref`` (reference: ray.cancel). Queued
    tasks fail immediately with TaskCancelledError — no worker round-trip;
    running tasks get the exception raised in their executing thread
    (force=True kills the hosting worker instead, for code that swallows
    exceptions). recursive=True also cancels every live descendant task
    via the controller's ownership tree. Cancelling a finished ref (or
    cancelling twice) is a no-op."""
    wc = ctx.get_worker_context()
    meta = _direct_task_meta.get(ref.object_id)
    if meta is not None and not force and not recursive:
        # Directly-pushed task: the controller never saw the spec — the
        # cancel rides the same lease connection the push did.
        task_id, conn = meta
        try:
            wc.client.io.call_nowait(conn.send(
                {"kind": "cancel_task", "task_id": task_id}))
            return
        except Exception:
            pass  # route died: the crash path fails the task anyway
    msg = {"kind": "cancel_task", "object_id": ref.object_id,
           "force": force, "recursive": recursive}
    tid = _inflight_oid2task.get(ref.object_id)
    if tid is not None:
        # Controller-routed task: name it outright so a recursive cancel
        # of an already-FINISHED parent can still walk the ownership tree
        # (the return-oid scan only finds live specs).
        msg["task_id"] = tid
    if meta is not None:
        # Direct push + recursive: the controller holds only the lineage
        # note, keyed by task id — send it so the walk can start there,
        # and reach the task itself through the lease route as usual.
        msg["task_id"] = meta[0]
        task_id, conn = meta
        try:
            wc.client.io.call_nowait(conn.send(
                {"kind": "cancel_task", "task_id": task_id}))
        except Exception:
            pass
    wc.client.request(msg)


def kill(actor: ActorHandle, *, no_restart: bool = True) -> None:
    wc = ctx.get_worker_context()
    wc.client.request({"kind": "kill_actor", "actor_id": actor._actor_id})


def get_actor(name: str, namespace: Optional[str] = None) -> ActorHandle:
    wc = ctx.get_worker_context()
    info = wc.client.request(
        {"kind": "get_named_actor", "name": name, "namespace": namespace or wc.namespace}
    )
    methods_blob = wc.client.request(
        {"kind": "kv_get", "ns": "__actor_methods__", "key": info["actor_id"]}
    )
    blob = cloudpickle.loads(methods_blob) if methods_blob else []
    meta: Dict[str, Any] = {}
    if isinstance(blob, tuple):
        if len(blob) >= 3:
            methods, defaults, meta = blob[0], blob[1], blob[2] or {}
        else:
            methods, defaults = blob
    else:  # pre-@method registrations stored a bare name list
        methods, defaults = blob, {}
    return ActorHandle(info["actor_id"], methods, defaults,
                       replayable=bool(meta.get("replayable")))


# --------------------------------------------------------------- cluster info


def cluster_resources() -> Dict[str, float]:
    wc = ctx.get_worker_context()
    state = wc.client.request({"kind": "cluster_state"})
    out: Dict[str, float] = {}
    for n in state["nodes"]:
        for k, v in n["resources"].items():
            out[k] = out.get(k, 0.0) + v
    return out


def available_resources() -> Dict[str, float]:
    wc = ctx.get_worker_context()
    state = wc.client.request({"kind": "cluster_state"})
    out: Dict[str, float] = {}
    for n in state["nodes"]:
        if not n.get("alive", True):
            # A dead node's snapshot freezes at its last report; counting
            # it advertises capacity the scheduler can no longer place on.
            continue
        for k, v in n["available"].items():
            out[k] = out.get(k, 0.0) + v
    return out


def nodes() -> List[Dict[str, Any]]:
    wc = ctx.get_worker_context()
    return ctx.get_worker_context().client.request({"kind": "cluster_state"})["nodes"]


@dataclass
class RuntimeContext:
    node_id: str
    namespace: str
    task_id: Optional[str]
    actor_id: Optional[str]

    def get_node_id(self) -> str:
        return self.node_id

    def get_accelerator_ids(self) -> Dict[str, List[str]]:
        """Accelerator ids assigned to this worker process, per resource
        name (reference: worker.py:932 get_accelerator_ids_for_accelerator_
        resource over CUDA_VISIBLE_DEVICES/TPU_VISIBLE_CHIPS). Workers
        spawned for a TPU request see the chip ids the spawner granted;
        an empty list means no assignment (unrestricted visibility)."""
        from ray_tpu.util.accelerators import accelerator_managers

        out: Dict[str, List[str]] = {}
        for mgr in accelerator_managers():
            out[mgr.resource_name] = mgr.get_visible_ids() or []
        return out


def get_runtime_context() -> RuntimeContext:
    wc = ctx.get_worker_context()
    return RuntimeContext(
        node_id=wc.node_id,
        namespace=wc.namespace,
        task_id=ctx.current_task_id(),
        actor_id=ctx.current_actor_id(),
    )
