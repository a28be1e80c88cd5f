"""Worker process runtime: task execution loop + actor hosting.

Role-equivalent to the reference's worker-side CoreWorker + the Python worker
shell (ray: src/ray/core_worker/core_worker.cc ExecuteTask path,
python/ray/_private/workers/default_worker.py). One OS process per worker;
plain tasks run on a small thread pool, each actor gets a dedicated mailbox
thread providing ordered execution (max_concurrency>1 widens the mailbox to a
thread pool, mirroring threaded actors / ConcurrencyGroupManager).

Workers import neither jax nor any ML library at startup — a worker stays a
~50ms-spawn control-plane process until user code pulls heavy imports.
"""
from __future__ import annotations

from ray_tpu import flags

import os
import pickle
import queue
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

import cloudpickle

from ..util import tracing
from . import context as ctx
from . import task_events
from .client import CoreClient
from .controller import ActorDiedError, ActorNotHostedError, TaskError
from .ids import WorkerID
from .object_store import (ObjectLocation, get_bytes, get_bytes_with_refresh,
                           put_bytes)
from .serialization import ArgRef, ObjectRef


class ActorExitSignal(BaseException):
    """Raised by ray_tpu.exit_actor inside an actor method: the call
    completes with None and the actor shuts down intentionally (no
    restart, queued calls fail with ActorDiedError)."""


def exit_actor() -> None:
    """Reference: ray.actor.exit_actor — terminate the hosting actor after
    the current call. Only valid inside an actor method."""
    from . import context as _ctx

    if _ctx.current_actor_id() is None:
        raise RuntimeError("exit_actor() called outside an actor method")
    raise ActorExitSignal()


class ActorMailbox:
    """Ordered (or bounded-concurrency) execution context for one actor.

    Actors whose classes define ``async def`` methods additionally get a
    persistent asyncio event loop on its own thread: coroutine methods are
    scheduled there and genuinely interleave while awaiting (reference:
    async actors on a per-actor eventloop, core_worker/fiber.h + ray's
    AsyncioActor; the round-1 per-call asyncio.run() serialized them)."""

    def __init__(self, runtime: "WorkerRuntime", actor_id: str, max_concurrency: int):
        self.runtime = runtime
        self.actor_id = actor_id
        self.instance: Any = None
        self.spec: Optional[Dict[str, Any]] = None  # creation spec (re-claim)
        # SimpleQueue: C-implemented put/get, no per-op lock dance — the
        # mailbox hop is on every actor call's critical path.
        self.q: "queue.SimpleQueue[Optional[Dict[str, Any]]]" = \
            queue.SimpleQueue()
        self.exited = False  # exit_actor ran: refuse everything queued
        # Per-caller sequence reordering state: caller -> {next, held}.
        self._seq: Dict[str, Dict[str, Any]] = {}
        self._seq_lock = threading.Lock()
        # Crash-consistent fault tolerance (core/checkpoint.py): durable
        # checkpoint cadence + the exactly-once replay journal. Configured
        # from the creation spec via configure(); all off by default so a
        # plain actor pays nothing.
        self.ckpt_every_n = 0
        self.ckpt_interval = 0.0
        self.ckpt_enabled = False
        self.replay = False          # journal (caller, seqno) -> result
        self.ckpt_epoch = 0
        self.calls_since_ckpt = 0
        self.last_ckpt = time.monotonic()
        self._ckpt_pending = False
        # caller -> {seqno: result payload} of APPLIED calls; a retried
        # (caller, seqno) short-circuits to its recorded payload instead of
        # re-executing (reference: the dedup the per-handle sequence_no of
        # direct_actor_task_submitter enables). Bounded per caller.
        self.journal: Dict[str, Dict[int, Dict[str, Any]]] = {}
        self._inflight_keys: set = set()   # accepted, not yet journaled
        self._dup_waiters: Dict[tuple, List[Dict[str, Any]]] = {}
        self.aio_loop: Any = None  # created lazily for async actors
        self.aio_sem: Any = None
        self._aio_lock = threading.Lock()
        self.max_concurrency = max(1, max_concurrency)
        self.threads = [
            threading.Thread(target=self._loop, name=f"actor-{actor_id[:8]}-{i}", daemon=True)
            for i in range(self.max_concurrency)
        ]
        for t in self.threads:
            t.start()

    # How long a sequence gap may stall later calls before they flush
    # anyway (the missing call may have failed permanently en route, or
    # this actor restarted and joined the caller's sequence mid-stream).
    _SEQ_GAP_TIMEOUT_S = 1.0

    # Journal entries retained per caller (seqnos are dense per handle, so
    # this bounds dedup memory to the retry horizon, not actor lifetime).
    _JOURNAL_MAX = 1024

    def configure(self, spec: Dict[str, Any]) -> None:
        """Arm checkpointing / exactly-once replay from the creation spec
        (called once, before the creation closure is queued)."""
        self.ckpt_every_n = int(spec.get("checkpoint_every_n") or 0)
        self.ckpt_interval = float(spec.get("checkpoint_interval_s") or 0.0)
        self.ckpt_enabled = bool(
            flags.get("RTPU_ACTOR_CHECKPOINT")
            and (self.ckpt_every_n > 0 or self.ckpt_interval > 0.0))
        self.replay = bool(spec.get("max_task_retries"))

    # ------------------------------------------- exactly-once call replay

    @staticmethod
    def _journal_key(spec: Dict[str, Any]):
        caller = spec.get("caller")
        seq = spec.get("seqno")
        if caller is None or seq is None:
            return None
        return (caller, seq)

    def _journal_lookup(self, key) -> Optional[Dict[str, Any]]:
        with self._seq_lock:
            entries = self.journal.get(key[0])
            return entries.get(key[1]) if entries else None

    def _intercept_replay(self, spec: Dict[str, Any]) -> bool:
        """Dedup a retried call BEFORE it enters the mailbox: an already-
        applied (caller, seqno) short-circuits to its journaled result; one
        still in flight parks as a dup-waiter completed alongside the
        original. Returns True when the spec was consumed here."""
        key = self._journal_key(spec)
        if key is None:
            return False
        with self._seq_lock:
            entries = self.journal.get(key[0])
            hit = entries.get(key[1]) if entries else None
            if hit is None:
                if key in self._inflight_keys:
                    self._dup_waiters.setdefault(key, []).append(spec)
                    return True
                self._inflight_keys.add(key)
                return False
        self.runtime._complete_replayed(spec, hit)
        return True

    def note_result(self, spec: Dict[str, Any],
                    payload: Dict[str, Any]) -> None:
        """Record one applied call's result (journal + dup waiters) and
        advance the checkpoint cadence. Runs on whichever thread completed
        the call; checkpointing itself is enqueued onto the mailbox."""
        key = self._journal_key(spec)
        waiters: List[Dict[str, Any]] = []
        if key is not None and self.replay:
            with self._seq_lock:
                entries = self.journal.setdefault(key[0], {})
                entries[key[1]] = payload
                if len(entries) > self._JOURNAL_MAX:
                    for s in sorted(entries)[:len(entries)
                                             - self._JOURNAL_MAX]:
                        entries.pop(s, None)
                self._inflight_keys.discard(key)
                waiters = self._dup_waiters.pop(key, [])
        for w in waiters:
            self.runtime._complete_replayed(w, payload)
        if self.ckpt_enabled:
            self.calls_since_ckpt += 1
            if self.ckpt_every_n \
                    and self.calls_since_ckpt >= self.ckpt_every_n:
                self.request_checkpoint()

    # ------------------------------------------------ durable checkpoints

    def ckpt_due(self) -> bool:
        return (self.ckpt_enabled and self.ckpt_interval > 0.0
                and self.instance is not None and not self.exited
                and not self._ckpt_pending
                and time.monotonic() - self.last_ckpt >= self.ckpt_interval)

    def request_checkpoint(self) -> None:
        """Enqueue a checkpoint on the mailbox (strictly after every call
        queued before it, so the record reflects results callers saw)."""
        if self._ckpt_pending or self.exited:
            return
        self._ckpt_pending = True
        self.q.put({"__create__": self.do_checkpoint})

    def do_checkpoint(self) -> Optional[bytes]:
        """Serialize instance + journal under the next epoch, write the
        host-local file, ship an async copy to the controller. Mailbox
        thread only (actor state is thread-affine). Best-effort: an
        unpicklable actor keeps running with checkpointing broken, exactly
        like the drain-snapshot fallback."""
        from . import checkpoint

        self._ckpt_pending = False
        if self.exited or self.instance is None:
            return None
        with self._seq_lock:
            journal = {c: dict(e) for c, e in self.journal.items()}
        try:
            blob = checkpoint.encode(self.instance, journal,
                                     self.ckpt_epoch + 1)
        except Exception:
            return None
        self.ckpt_epoch += 1
        self.calls_since_ckpt = 0
        self.last_ckpt = time.monotonic()
        try:
            checkpoint.write_local(self.actor_id, self.ckpt_epoch, blob)
        except OSError:
            pass
        try:
            self.runtime.client.send_nowait(
                {"kind": "actor_checkpoint", "actor_id": self.actor_id,
                 "epoch": self.ckpt_epoch, "blob": blob})
        except Exception:
            pass
        return blob

    def submit(self, spec: Dict[str, Any]) -> None:
        """Enqueue in per-caller SUBMISSION order (reference:
        direct_actor_task_submitter sequence_no). Calls from one caller can
        arrive over two paths (direct socket, controller fallback) and
        overtake; out-of-order arrivals wait in a per-caller hold-back
        buffer until the gap fills — or until a bounded timeout flushes
        them, so a call lost to a path failure stalls ordering, not the
        actor."""
        if "__recv_ts__" not in spec and task_events.enabled():
            # Arrival stamp for the queue-wait phase: covers time spent in
            # the hold-back buffer AND the mailbox queue.
            spec["__recv_ts__"] = time.time()
        if self.replay and self._intercept_replay(spec):
            return  # duplicate of an applied/in-flight call: deduped
        if spec.get("task_id"):
            self.runtime.queued_actor_tasks[spec["task_id"]] = spec
        caller = spec.get("caller")
        seq = spec.get("seqno")
        if caller is None or seq is None:
            self.q.put(spec)
            return
        with self._seq_lock:
            state = self._seq.get(caller)
            if state is None:
                # Fresh caller: sequences start at 0. (A RESTARTED actor
                # joining a caller's stream mid-sequence parks the first
                # arrival in the hold-back buffer until the gap timer
                # flushes it — a one-time bounded hiccup, never a stall.)
                state = self._seq[caller] = {"next": 0, "held": {}}
            if seq < state["next"]:
                self.q.put(spec)  # late duplicate/retry: run, don't stall
                return
            if seq > state["next"]:
                state["held"][seq] = spec
                threading.Timer(self._SEQ_GAP_TIMEOUT_S,
                                self._flush_seq_gap,
                                args=(caller, seq)).start()
                return
            self.q.put(spec)
            state["next"] = seq + 1
            while state["next"] in state["held"]:
                self.q.put(state["held"].pop(state["next"]))
                state["next"] += 1

    def _flush_seq_gap(self, caller: str, seq: int) -> None:
        """Timeout fallback: the call before `seq` never arrived — release
        everything held, in order, and advance the cursor past it."""
        with self._seq_lock:
            state = self._seq.get(caller)
            if state is None or seq not in state["held"]:
                return  # gap filled in time
            for s in sorted(state["held"]):
                if s > seq:
                    break
                self.q.put(state["held"].pop(s))
            state["next"] = max(state["next"], seq + 1)
            while state["next"] in state["held"]:
                self.q.put(state["held"].pop(state["next"]))
                state["next"] += 1

    def stop(self) -> None:
        for _ in self.threads:
            self.q.put(None)
        if self.aio_loop is not None:
            self.aio_loop.call_soon_threadsafe(self.aio_loop.stop)

    def ensure_aio_loop(self):
        """Start the persistent event loop (first async method / creation).
        Locked: with a multi-threaded mailbox, two first-async-calls racing
        here could otherwise each build a loop and strand one's coroutines
        on a loop no thread runs."""
        with self._aio_lock:
            if self.aio_loop is None:
                import asyncio

                loop = asyncio.new_event_loop()
                # Async actors interleave up to max_concurrency coroutines; a
                # plain actor that happens to have one async method still gets
                # real concurrency (ray default for async actors is high).
                n = self.max_concurrency if self.max_concurrency > 1 else 100
                self.aio_sem = asyncio.Semaphore(n)
                self.aio_loop = loop
                t = threading.Thread(
                    target=self._run_aio, name=f"actor-aio-{self.actor_id[:8]}",
                    daemon=True,
                )
                t.start()
            return self.aio_loop

    def _run_aio(self) -> None:
        import asyncio

        # The loop thread belongs to exactly one actor: current_actor_id()
        # (and therefore exit_actor) must work from coroutine methods too.
        ctx.task_local.actor_id = self.actor_id
        asyncio.set_event_loop(self.aio_loop)
        self.aio_loop.run_forever()

    def _loop(self) -> None:
        while True:
            spec = self.q.get()
            if spec is None:
                return
            if "__create__" in spec:
                spec["__create__"]()
                continue
            if self.exited:
                # exit_actor already ran: a queued call must FAIL, not
                # execute on (or double-complete against) a retired actor.
                # The claim pop keeps a racing cancel from also completing.
                tid = spec.get("task_id")
                if not tid or self.runtime.queued_actor_tasks.pop(
                        tid, None) is not None:
                    self.runtime._refuse_exited(spec)
                continue
            self.runtime.run_task(spec, actor_instance=self.instance, mailbox=self)


class _NullSpan:
    """No-op stand-in for tracing.task_span when the spec carries no trace
    context — the per-task fast path pays an attribute check, not a scope."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, et, ev, tb):
        return False

    def detach_context(self) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _BatchReply:
    """Aggregation state for one pushed batch: entries contribute their
    result locations as they finish; the single correlated response
    resolves when the last one lands."""

    __slots__ = ("loop", "fut", "remaining", "locations", "error_locations",
                 "lock")

    def __init__(self, loop, fut, n: int):
        self.loop = loop
        self.fut = fut
        self.remaining = n
        self.locations: List[ObjectLocation] = []
        self.error_locations: List[ObjectLocation] = []
        self.lock = threading.Lock()

    def contribute(self, payload: Dict[str, Any]) -> None:
        with self.lock:
            self.locations.extend(payload.get("locations") or ())
            self.error_locations.extend(payload.get("error_locations") or ())
            self.remaining -= 1
            if self.remaining > 0:
                return
            result = {"locations": self.locations,
                      "error_locations": self.error_locations}

        def _set():
            if not self.fut.done():
                self.fut.set_result(result)

        self.loop.call_soon_threadsafe(_set)


class WorkerRuntime:
    def __init__(self, controller_addr: str, node_id: str):
        host, port = controller_addr.rsplit(":", 1)
        self.worker_id = WorkerID.generate()
        self.node_id = node_id
        from . import ownership as _ownership

        _ownership.set_process_label(f"worker:{self.worker_id[:8]}")
        self.client = CoreClient(host, int(port), handler=self._handle,
                                 reconnect=True,
                                 on_reconnect=self._on_reconnect)
        self.pool = ThreadPoolExecutor(max_workers=32, thread_name_prefix="task")
        # Completion batcher: task_done payloads (acks + result-location
        # publishes) buffered here coalesce into one task_done_batch frame
        # per io-loop beat instead of one loop wakeup + pickle per task.
        self._done_buf: List[Dict[str, Any]] = []
        self._done_lock = threading.Lock()
        self._done_scheduled = False
        self.functions: Dict[str, Any] = {}
        self.actors: Dict[str, ActorMailbox] = {}
        # Installed compiled-DAG plans (dag_id -> dag.resident.WorkerDAG):
        # resident loops + producer rings + stream inboxes on this worker.
        self.dag_channels: Dict[str, Any] = {}
        self.running_threads: Dict[str, int] = {}  # task_id -> thread ident
        self.cancelled_tasks: set = set()  # ray.cancel'd before/while running
        # Actor calls sitting in a mailbox (or its hold-back buffer), not
        # yet executing: task_id -> spec. A cancel that atomically pops an
        # entry owns its completion and fails it IMMEDIATELY — no waiting
        # behind whatever runs ahead of it; run_task's matching pop claims
        # execution, and a miss there means a cancel won the race.
        self.queued_actor_tasks: Dict[str, Dict[str, Any]] = {}
        self.shutdown_event = threading.Event()
        # Direct-dispatch server: peers push actor tasks here without a
        # controller hop (reference: direct task transport,
        # src/ray/core_worker/transport/direct_task_transport.h:222 — the
        # lease-then-push design keeping the control plane off the data
        # path). Advertised to the controller in the register message.
        self.direct_port = self._start_direct_server()
        # Context must be live before registration: the controller may push a
        # task the instant the register request lands.
        ctx.set_worker_context(ctx.WorkerContext(
            client=self.client, node_id=node_id, role="worker",
            extra={"worker_id": self.worker_id}))
        # Apply the runtime env BEFORE registering: the controller may push
        # a task the moment registration lands, and the env (cwd, sys.path,
        # env_vars) must already be in place (the pip venv part was applied
        # by the spawner — this interpreter is the venv's).
        env_hash = ""
        renv_json = flags.get("RTPU_RUNTIME_ENV")
        if renv_json:
            import json as _json

            from . import runtime_env as renv

            norm = _json.loads(renv_json)
            renv.apply_in_worker(norm, self.client)
            env_hash = norm.get("hash", "")
        # Tee stdout/stderr to the driver console via the controller
        # (reference: _private/log_monitor.py tailing worker logs; here the
        # worker pushes its own lines — no per-node tail daemon needed).
        # Installed BEFORE registering: the controller may push a task the
        # instant registration lands, and a print() from that first task
        # must not race the tee install (it would go only to the log file,
        # never to the driver). The same tee stamps task/actor attribution
        # markers + byte-range index entries into the spawn's log file
        # (worker_logs.LogAttributor) so one task's output is remotely
        # retrievable without scanning.
        from . import worker_logs

        self._log_attributor = (
            worker_logs.LogAttributor.create(self.worker_id, node_id)
            if flags.get("RTPU_LOG_ATTRIBUTION") else None)
        if flags.get("RTPU_LOG_TO_DRIVER") \
                or self._log_attributor is not None:
            self._install_log_forwarder()
        self._env_hash = env_hash
        self.client.request(self._register_msg())
        tracing.send_unsent()  # boot.*: slow phases from before the context

        # Controller-connection watch: a dropped connection first enters
        # the client's capped-backoff reconnect loop (the controller may
        # just be bouncing — reference: NotifyGCSRestart re-registration,
        # core_worker.proto:392). Only when the reconnect deadline passes
        # does the worker fate-share and die (an orphaned worker would
        # leak forever).
        async def _watch_conn() -> None:
            import asyncio

            while not self.shutdown_event.is_set():
                conn = self.client.conn
                await conn.closed.wait()
                if self.shutdown_event.is_set():
                    return
                ok = await asyncio.get_running_loop().run_in_executor(
                    None, self._try_reconnect)
                if not ok:
                    self.shutdown_event.set()
                    return

        self.client.io.call_nowait(_watch_conn())

    # ------------------------------------------------- controller reconnect

    def _register_msg(self, reconnect: bool = False) -> Dict[str, Any]:
        msg = {
            "kind": "register",
            "role": "worker",
            "worker_id": self.worker_id,
            "node_id": self.node_id,
            "spawn_token": flags.get("RTPU_SPAWN_TOKEN"),
            "tpu_capable": flags.get("RTPU_TPU_WORKER"),
            # Spawner-assigned chip visibility (agent- or controller-
            # side): reported so the scheduler can match workers to
            # tasks by chip count, not just TPU-capability.
            "chip_ids": [int(x) for x in
                         (flags.get("TPU_VISIBLE_CHIPS") or "").split(",")
                         if x != ""],
            "env_hash": self._env_hash,
            "direct_port": self.direct_port,
            "pid": os.getpid(),
        }
        if reconnect:
            msg["reconnect"] = True
            # Tasks currently executing on this worker: a restarted
            # controller re-claims them so (a) a resubmitted duplicate
            # isn't also scheduled and (b) a node drain's quiesce check
            # keeps waiting for work it would otherwise not see.
            msg["running"] = list(self.running_threads.keys())
            # Re-claim hosted actors: a restarted controller rebuilds its
            # actor directory from these reports, keeping live instances
            # (and their state) over queued re-creations.
            msg["actors"] = [
                self._actor_claim(aid, mb)
                for aid, mb in list(self.actors.items())
                if not mb.exited and mb.instance is not None
            ]
        return msg

    @staticmethod
    def _actor_claim(actor_id: str, mb: "ActorMailbox") -> Dict[str, Any]:
        spec = getattr(mb, "spec", None) or {}
        return {
            "actor_id": actor_id,
            "name": spec.get("name"),
            "namespace": spec.get("namespace", "default"),
            "detached": bool(spec.get("detached")),
            "max_restarts": int(spec.get("max_restarts", 0)),
            "resources": dict(spec.get("resources") or {}),
        }

    def _try_reconnect(self) -> bool:
        try:
            self.client.ensure_connected()
            return True
        except Exception as e:
            import sys as _sys

            print(f"[worker] controller reconnect failed: {e!r}; "
                  f"fate-sharing\n{traceback.format_exc()}",
                  file=_sys.stderr, flush=True)
            return False

    def _on_reconnect(self, client: CoreClient) -> None:
        """Runs on the fresh connection before any retried request:
        re-register under the existing worker id, re-report chips and
        hosted actors, drop actors the controller says were re-created
        elsewhere while we were away."""
        deadline = time.monotonic() + flags.get("RTPU_RECONNECT_MAX_S")
        # Bounded handshake under the partition-hardening RPC timeout: a
        # register into a still-blackholed network fails fast and retries
        # from the client's dial loop instead of camping 30s per attempt.
        rpc_t = float(flags.get("RTPU_RPC_TIMEOUT_S") or 0.0)
        while True:
            reply = client.io.call(
                client.conn.request(self._register_msg(reconnect=True),
                                    timeout=rpc_t * 2 if rpc_t else None),
                timeout=(rpc_t * 2 if rpc_t else 30) + 5)
            if reply and reply.get("ok"):
                break
            if not (reply and reply.get("retry")) \
                    or time.monotonic() >= deadline:
                raise ConnectionError(
                    "controller refused worker re-registration")
            # Our node (host agent) has not re-registered yet: give it a
            # beat and try again.
            time.sleep(0.3)
        for aid in reply.get("drop_actors") or ():
            mb = self.actors.pop(aid, None)
            if mb is not None:
                mb.exited = True
                mb.stop()

    def _install_log_forwarder(self) -> None:
        import sys

        runtime = self

        class _Tee:
            # Forwarded lines cap at 8KB: \r-only writers (progress bars)
            # must not grow the buffer without bound, and a never-ending
            # line is forwarded in chunks rather than buffered forever.
            _MAX_BUF = 8192

            def __init__(self, inner, stream: str):
                self._inner = inner
                self._stream = stream
                self._buf = ""
                self._lock = threading.Lock()

            def _emit(self, line: str) -> None:
                if not line.strip():
                    return
                if not flags.get("RTPU_LOG_TO_DRIVER"):
                    return
                try:
                    runtime.client.send_nowait({
                        "kind": "worker_log", "line": line,
                        "pid": os.getpid(),
                        "worker_id": runtime.worker_id,
                        "stream": self._stream,
                    })
                except Exception:
                    pass

            def write(self, text: str) -> int:
                attr = runtime._log_attributor
                if attr is not None and flags.get("RTPU_LOG_ATTRIBUTION"):
                    # Attribution path: marker stamping + byte-range index
                    # entries keyed by the WRITING thread's execution
                    # context (the task pool / mailbox threads set it).
                    n = attr.write(self._inner, text, self._stream,
                                   ctx.current_task_id(),
                                   ctx.current_actor_id(),
                                   getattr(ctx.task_local, "label", None))
                else:
                    n = self._inner.write(text)
                # The 32-thread task pool writes concurrently; _buf updates
                # must be atomic or lines interleave/vanish.
                with self._lock:
                    self._buf += text
                    self._buf = self._buf.replace("\r\n", "\n")
                    lines = self._buf.replace("\r", "\n").split("\n")
                    self._buf = lines.pop()
                    if len(self._buf) > self._MAX_BUF:
                        lines.append(self._buf)
                        self._buf = ""
                for line in lines:
                    self._emit(line)
                return n

            def flush(self) -> None:
                self._inner.flush()
                # An explicit flush is a visibility request: publish the
                # pending attribution range too, so a live `rtpu logs`
                # follower sees the line now, not at the next context
                # switch or batching threshold.
                attr = runtime._log_attributor
                if attr is not None:
                    attr.flush()

            def __getattr__(self, name):
                return getattr(self._inner, name)

        sys.stdout = _Tee(sys.stdout, "stdout")
        sys.stderr = _Tee(sys.stderr, "stderr")

    # ------------------------------------------------------- direct dispatch

    def _start_direct_server(self) -> int:
        from . import protocol

        # Bind the interface this worker uses to reach the controller —
        # exactly the address the controller advertises to peers (it reads
        # our connection's peername, controller._h_lease_worker). A loopback
        # cluster therefore stays loopback; binding 0.0.0.0 would expose an
        # unauthenticated execute-pickled-callable endpoint on every
        # interface of the host (advisor r4). RTPU_DIRECT_BIND overrides
        # for multi-homed hosts where peers ride a different interface.
        bind_host = flags.get("RTPU_DIRECT_BIND")
        if not bind_host:
            try:
                bind_host = self.client.conn.writer.get_extra_info(
                    "sockname")[0]
            except Exception:
                bind_host = "127.0.0.1"

        async def serve():
            async def on_conn(reader, writer):
                conn = protocol.Connection(
                    reader, writer, handler=self._handle_direct,
                    name="direct")
                conn.start()

            return await __import__("asyncio").start_server(
                on_conn, bind_host, 0)

        self._direct_server = self.client.io.call(serve(), timeout=10)
        port = self._direct_server.sockets[0].getsockname()[1]
        # The direct server doubles as this worker's ownership ref channel
        # (borrow/hold messages land in _handle_direct's ref_* branch).
        from . import ownership

        ownership.set_self_addr(bind_host, port)
        return port

    async def _handle_direct(self, conn, msg):
        """Peer-pushed actor task: enqueue on the mailbox, answer with the
        result locations when it completes. The response rides the same
        connection (request/response correlation), so the caller gets the
        locations with zero controller involvement.

        The *_batch kinds carry many specs in one framed message (one
        unpickle per wave-slice instead of per call); the single response
        aggregates every entry's result locations and resolves when the
        last entry finishes — per-entry results stream to the controller
        via the completion batcher in the meantime, so a mid-batch worker
        death leaves the caller able to distinguish completed entries
        (locations published) from never-ran ones."""
        import asyncio

        kind = msg["kind"]
        if kind.startswith("ref_"):
            from . import ownership

            return ownership.handle_ref_message(msg)
        if kind.startswith("pull_"):
            # Producer-served object plane: this worker serves its own
            # objects' bytes over the direct server (Ray's plasma/pull-
            # manager split — the controller keeps location metadata only;
            # consumers fall back to the host agent when this worker dies).
            from . import transfer

            return await transfer.handle_pull_server_message(conn, msg)
        if kind.startswith("dag_"):
            # Compiled-DAG channel plane: install/teardown/status ride the
            # driver's per-DAG connection; dag_channel_item frames are the
            # cross-host channel legs (raw-tail pushes, no response).
            from ray_tpu.dag import resident

            return resident.handle_direct_message(self, conn, msg)
        if kind == "cancel_task":
            self._cancel_task(msg["task_id"])
            return None
        loop = asyncio.get_running_loop()
        if kind in ("direct_task_batch", "direct_actor_task_batch"):
            specs = msg["specs"]
            fut = loop.create_future()
            state = _BatchReply(loop, fut, len(specs))
            if kind == "direct_actor_task_batch":
                mb = self.actors.get(specs[0]["actor_id"]) if specs else None
                if mb is None:
                    # Typed refusal BEFORE any entry runs: the whole batch
                    # provably never executed, so the caller resubmits it
                    # through the controller.
                    raise ActorNotHostedError(
                        f"actor {(specs[0]['actor_id'][:8]) if specs else '?'}"
                        f" is not hosted on this worker")
            now = time.time() if task_events.enabled() else None
            for spec in specs:
                if now is not None:
                    spec["__recv_ts__"] = now
                spec["__batch__"] = state
                if kind == "direct_task_batch":
                    spec["__leased__"] = True
                    self._lease_submit(spec)
                else:
                    mb.submit(spec)
            return await fut
        spec = msg["spec"]
        if task_events.enabled():
            spec["__recv_ts__"] = time.time()
        if spec.get("streaming"):
            # Generator state lives in the controller; a direct streaming
            # call would hang the caller's future forever.
            raise ValueError("streaming calls must go through the controller")
        # The executing thread POPS "__direct__" when it finishes — bind the
        # future to a local BEFORE handing the spec over, or a fast task
        # completes (and pops) before this coroutine evaluates the
        # subscript and the await raises KeyError.
        fut = loop.create_future()
        if kind == "direct_task":
            # Leased stateless task (reference direct_task_transport.h:222):
            # executes SERIALLY — the lease reserves one CPU, so pushed
            # tasks queue here instead of fanning out over the pool.
            spec["__direct__"] = (fut, loop)
            spec["__leased__"] = True
            self._lease_submit(spec)
            return await fut
        if kind != "direct_actor_task":
            raise ValueError(f"direct server: unknown kind {kind!r}")
        mb = self.actors.get(spec["actor_id"])
        if mb is None:
            # Typed refusal BEFORE any user code runs: the caller knows the
            # call never executed and resubmits through the controller
            # (which has the actor's post-migration address).
            raise ActorNotHostedError(
                f"actor {spec['actor_id'][:8]} is not hosted on this worker "
                f"(died or restarted elsewhere)")
        spec["__direct__"] = (fut, loop)
        mb.submit(spec)
        return await fut

    def _lease_submit(self, spec: Dict[str, Any]) -> None:
        """Queue a leased task for SERIAL execution. A dedicated thread +
        SimpleQueue instead of a ThreadPoolExecutor: submit() there takes
        locks and allocates an unused Future per task — measurable at
        direct-dispatch rates."""
        q = getattr(self, "_lease_q", None)
        if q is None:
            q = self._lease_q = queue.SimpleQueue()

            def _run() -> None:
                while True:
                    s = q.get()
                    self.run_task(s)

            threading.Thread(target=_run, name="lease",
                             daemon=True).start()
        q.put(spec)

    def _finish_direct(self, spec: Dict[str, Any], payload: Dict[str, Any]) -> bool:
        """Resolve a direct caller's future; returns True if this spec came
        through the direct server (single push or batch entry)."""
        st = spec.pop("__batch__", None)
        if st is not None:
            st.contribute(payload)
            return True
        df = spec.pop("__direct__", None)
        if df is None:
            return False
        fut, loop = df

        def _set():
            if not fut.done():
                fut.set_result(payload)

        loop.call_soon_threadsafe(_set)
        return True

    # ----------------------------------------------------------- push handler

    def _refuse_exited(self, spec: Dict[str, Any]) -> None:
        """A call queued behind exit_actor: direct pushes get their reply
        failed; controller-path specs are dropped (the controller already
        stored ActorDiedError for them when it retired the actor —
        completing them here would double-write the return objects)."""
        if "__direct__" in spec:
            self._complete_error(spec, ActorDiedError(
                "actor exited via exit_actor() before this call ran"), "")

    def _handle_actor_exit(self, spec: Dict[str, Any]) -> None:
        """Intentional exit (exit_actor): the triggering call succeeds
        with None (shaped to its num_returns), the controller retires the
        actor WITHOUT restart, the mailbox refuses everything queued."""
        aid = spec.get("actor_id")
        mb = self.actors.get(aid) if aid else None
        if mb is not None:
            mb.exited = True  # BEFORE completing: no queued call may run
        if aid:
            from . import events

            events.emit(
                "INFO", "ACTOR_EXIT",
                f"actor {aid[:8]} exited intentionally via exit_actor()",
                actor_id=aid, worker_id=self.worker_id,
                node_id=self.node_id)
        n = len(spec.get("return_ids") or ())
        self._complete_ok(spec, None if n <= 1 else [None] * n)
        if not aid:
            return
        ok = False
        for _ in range(3):
            try:
                self.client.request({"kind": "actor_exit", "actor_id": aid})
                ok = True
                break
            except Exception:
                time.sleep(0.5)
        if not ok:
            # The control connection is almost certainly gone — fate-share
            # (the watch task would kill us anyway); dying via the normal
            # worker-death path at least fails the actor visibly instead
            # of leaving the controller believing it is alive.
            import sys as _sys

            print("[worker] actor_exit unreachable; fate-sharing",
                  file=_sys.stderr, flush=True)
            self.shutdown_event.set()
        self.actors.pop(aid, None)
        if mb is not None:
            mb.stop()
        from . import checkpoint as _ckpt

        _ckpt.prune_local(aid)  # retired for good: no record may resurrect it

    def _cancel_task(self, task_id: str) -> None:
        """Non-force ray.cancel (reference: TaskCancelledError raised in
        the executing thread via the CPython async-exception hook). A task
        still QUEUED here (lease executor / actor mailbox) is marked and
        refused at run_task start; a RUNNING one sees the exception at its
        next bytecode boundary."""
        queued = self.queued_actor_tasks.pop(task_id, None)
        if queued is not None:
            # Still in an actor mailbox: this pop claims the call — fail
            # it NOW, without waiting behind whatever executes ahead of it
            # (the mailbox dequeue sees the missing claim and skips).
            from .controller import TaskCancelledError

            self._complete_error(queued, TaskCancelledError(
                f"actor call {task_id[:8]} was cancelled while queued"), "")
            return
        if len(self.cancelled_tasks) > 8192:
            # Recursive-cancel broadcasts mark every worker; ids for tasks
            # that never arrive here would otherwise accumulate forever.
            self.cancelled_tasks.pop()
        self.cancelled_tasks.add(task_id)
        ident = self.running_threads.get(task_id)
        if ident is not None:
            import ctypes as _ct

            from .controller import TaskCancelledError

            _ct.pythonapi.PyThreadState_SetAsyncExc(
                _ct.c_ulong(ident), _ct.py_object(TaskCancelledError))

    def _admit(self, spec: Dict[str, Any]) -> bool:
        """Local admission (reference raylet spillback): a host at the edge
        of memory exhaustion rejects the dispatch back to the scheduler
        instead of starting work it will likely be OOM-killed for. Capped
        per task so a cluster-wide pressure wave can't ping-pong a spec
        forever — after two spills it runs wherever it lands."""
        frac_limit = flags.get("RTPU_SPILLBACK_MEM_FRACTION")
        if not frac_limit or spec.get("spillback_count", 0) >= 2:
            return True
        try:
            import psutil

            if psutil.virtual_memory().percent / 100.0 >= frac_limit:
                return False
        except Exception:
            pass
        return True

    async def _handle(self, conn, msg):
        kind = msg["kind"]
        if kind == "execute_task":
            spec = msg["spec"]
            if task_events.enabled():
                spec["__recv_ts__"] = time.time()
            if not self._admit(spec):
                from . import events

                events.emit(
                    "WARNING", "TASK_SPILLBACK",
                    f"worker {self.worker_id[:8]} rejected task "
                    f"{spec.get('label') or spec['task_id'][:8]} under "
                    f"host memory pressure",
                    task_id=spec["task_id"], worker_id=self.worker_id,
                    node_id=self.node_id)
                await conn.send({"kind": "task_spillback",
                                 "task_id": spec["task_id"],
                                 "worker_id": self.worker_id})
                return None
            self.pool.submit(self.run_task, spec)
        elif kind == "instantiate_actor":
            self._instantiate_actor(msg["spec"])
        elif kind == "execute_actor_task":
            spec = msg["spec"]
            mb = self.actors.get(spec["actor_id"])
            if mb is not None:
                mb.submit(spec)
            else:
                # The actor left this worker (killed, or migrated off a
                # draining node) while the dispatch was in flight. The call
                # never ran, so bounce it back to the controller — which
                # routes to the actor's new host, buffers while it
                # re-creates, or stores ActorDiedError if it is truly dead.
                # Bounded so a stale directory can't ping-pong forever; a
                # silent drop would hang the caller.
                spec = dict(spec, __rehost__=spec.get("__rehost__", 0) + 1)

                def _bounce(spec=spec):
                    try:
                        self.client.request(
                            {"kind": "submit_actor_task", "spec": spec})
                    except Exception:
                        self._complete_error(spec, ActorNotHostedError(
                            f"actor {spec['actor_id'][:8]} is no longer "
                            f"hosted on this worker"), "")

                if spec["__rehost__"] <= 3:
                    self.pool.submit(_bounce)
                else:
                    self.pool.submit(
                        self._complete_error, spec,
                        ActorDiedError(
                            f"actor {spec['actor_id'][:8]} is no longer "
                            f"hosted on this worker"), "")
        elif kind == "snapshot_actor":
            # Drain migration: serialize the actor instance ON ITS MAILBOX
            # THREAD (state is thread-affine), after every already-queued
            # call — so the snapshot reflects all calls the caller saw
            # complete. Best-effort: unpicklable/slow actors fall back to a
            # fresh constructor run on the new node.
            return await self._snapshot_actor(msg["actor_id"])
        elif kind == "checkpoint_actor":
            # On-demand durable checkpoint (the memory monitor's final
            # checkpoint before an OOM kill, and tests): the response
            # carries the record so the controller stores it synchronously
            # before the SIGKILL lands.
            return await self._checkpoint_actor(msg["actor_id"])
        elif kind == "drop_actor":
            # The controller moved this actor elsewhere: retire the local
            # instance so post-snapshot mutations cannot be silently lost —
            # and prune this host's checkpoint files, which are stale the
            # moment the actor lives (and checkpoints) somewhere else.
            mb = self.actors.pop(msg["actor_id"], None)
            if mb is not None:
                mb.exited = True
                mb.stop()
            from . import checkpoint as _ckpt

            _ckpt.prune_local(msg["actor_id"])
        elif kind == "cancel_task":
            self._cancel_task(msg["task_id"])
        elif kind == "shutdown":
            self.shutdown_event.set()
        elif kind == "ref_dump":
            # Ownership introspection for `rtpu memory` (reference: the
            # reference-table rows `ray memory` collects per worker); same
            # off-loop reply pattern as stack_dump.
            from . import ownership

            st = ownership.stats()
            threading.Thread(
                target=lambda: self.client.request(
                    {"kind": "profile_result", "req_id": msg["req_id"],
                     "worker_id": self.worker_id, "text": st}),
                daemon=True).start()
        elif kind == "census_dump":
            # Object-census shard for the object_census fan-out: full
            # per-ref rows (owner/size/tier/pins/callsite) vs ref_dump's
            # summary counters; same off-loop reply pattern.
            from . import ownership

            def _census_reply(req_id=msg["req_id"]):
                try:
                    shard = ownership.census_shard()
                except Exception as e:
                    shard = {"error": repr(e), "rows": []}
                try:
                    self.client.request(
                        {"kind": "profile_result", "req_id": req_id,
                         "worker_id": self.worker_id, "text": shard})
                except Exception:
                    pass

            threading.Thread(target=_census_reply, daemon=True).start()
        elif kind == "dag_spans":
            # Channel-meter span ring for `state.dag_timeline()` (the
            # dag_timeline fan-out): recent per-stage step spans with
            # recv/compute/send/blocked phase ns; same off-loop reply
            # pattern as stack_dump.
            def _spans_reply(req_id=msg["req_id"], dag=msg.get("dag")):
                import json as _json

                from ray_tpu.dag import meter as _meter

                try:
                    text = _json.dumps(_meter.spans_snapshot(self, dag))
                except Exception as e:
                    text = _json.dumps({"error": repr(e)})
                try:
                    self.client.request(
                        {"kind": "profile_result", "req_id": req_id,
                         "worker_id": self.worker_id, "text": text})
                except Exception:
                    pass

            threading.Thread(target=_spans_reply, daemon=True).start()
        elif kind == "stack_dump":
            # On-demand profiling (reference: reporter agent py-spy dump):
            # format every thread's current stack and reply off the event
            # loop (client.request blocks).
            text = self._format_stacks()
            threading.Thread(
                target=lambda: self.client.request(
                    {"kind": "profile_result", "req_id": msg["req_id"],
                     "worker_id": self.worker_id, "text": text}),
                daemon=True).start()
        elif kind == "phase_dump":
            # This process's host phases for state.phase_table(); same
            # off-loop reply pattern as stack_dump.
            text = {"table": tracing.phase_table(),
                    "slow": tracing.slow_phases()}
            threading.Thread(
                target=lambda: self.client.request(
                    {"kind": "profile_result", "req_id": msg["req_id"],
                     "worker_id": self.worker_id, "text": text}),
                daemon=True).start()
        elif kind == "profile":
            # Wall-clock sampling profiler (core/profiler.py): sample this
            # process's threads for the requested duration on a daemon
            # thread (the sampler sleeps between ticks — it must not sit
            # on the event loop), then reply via the stack_dump path.
            def _run_profile(duration=float(msg.get("duration", 2.0)),
                             hz=float(msg.get("hz", 67.0)),
                             req_id=msg["req_id"]):
                from . import profiler

                try:
                    if not flags.get("RTPU_PROFILER"):
                        import json as _json

                        text = _json.dumps(
                            {"error": "profiler disabled on worker "
                                      "(RTPU_PROFILER=0)"})
                    else:
                        text = profiler.profile_and_encode(duration, hz)
                except Exception as e:
                    import json as _json

                    text = _json.dumps({"error": repr(e)})
                try:
                    self.client.request(
                        {"kind": "profile_result", "req_id": req_id,
                         "worker_id": self.worker_id, "text": text})
                except Exception:
                    pass

            threading.Thread(target=_run_profile, daemon=True).start()
        elif kind == "pubsub":
            ctx.deliver_pubsub(msg["channel"], msg["data"])
        elif kind == "pubsub_batch":
            for item in msg["items"]:
                ctx.deliver_pubsub(item["channel"], item["data"])
        return None

    async def _snapshot_actor(self, actor_id: str) -> Dict[str, Any]:
        import asyncio

        mb = self.actors.get(actor_id)
        if mb is None or mb.exited or mb.instance is None:
            return {"error": "actor not hosted here"}
        loop = asyncio.get_running_loop()
        fut: "asyncio.Future" = loop.create_future()

        def snap():
            from . import checkpoint

            try:
                # Record format (instance + replay journal + epoch): a
                # migrated replayable actor keeps its dedup journal, and
                # the snapshot supersedes any older durable checkpoint.
                with mb._seq_lock:
                    journal = {c: dict(e) for c, e in mb.journal.items()}
                blob = checkpoint.encode(mb.instance, journal,
                                         mb.ckpt_epoch + 1)
                payload: Dict[str, Any] = {"blob": blob}
            except Exception as e:  # unpicklable state: ctor fallback
                payload = {"error": repr(e)}
            loop.call_soon_threadsafe(
                lambda: fut.done() or fut.set_result(payload))

        # Rides the mailbox's closure lane (same as __init__), so it runs
        # strictly after every call queued before the migration began. A
        # compiled-DAG resident loop owns the mailbox thread and never
        # drains that lane — hand the closure to the loop instead; it runs
        # it between microbatches (a seq-consistent point) and parks.
        routed = False
        for wd in self.dag_channels.values():
            if wd.request_snapshot(actor_id, snap):
                routed = True
                break
        if not routed:
            mb.q.put({"__create__": snap})
        try:
            return await asyncio.wait_for(fut, timeout=8.0)
        except asyncio.TimeoutError:
            return {"error": "snapshot timed out behind queued calls"}

    async def _checkpoint_actor(self, actor_id: str) -> Dict[str, Any]:
        """On-demand durable checkpoint, on the mailbox thread after every
        queued call. Returns {epoch, blob} so the caller (the controller's
        OOM path) can store the record without waiting for the async ship."""
        import asyncio

        mb = self.actors.get(actor_id)
        if mb is None or mb.exited or mb.instance is None:
            return {"error": "actor not hosted here"}
        loop = asyncio.get_running_loop()
        fut: "asyncio.Future" = loop.create_future()

        def run():
            blob = mb.do_checkpoint()
            payload = ({"epoch": mb.ckpt_epoch, "blob": blob}
                       if blob is not None
                       else {"error": "checkpoint failed"})
            loop.call_soon_threadsafe(
                lambda: fut.done() or fut.set_result(payload))

        mb.q.put({"__create__": run})
        try:
            return await asyncio.wait_for(fut, timeout=8.0)
        except asyncio.TimeoutError:
            return {"error": "checkpoint timed out behind queued calls"}

    def _format_stacks(self) -> str:
        return (f"pid={os.getpid()} worker={self.worker_id}\n"
                + tracing.format_stacks())

    # -------------------------------------------------------------- execution

    def _load_function(self, func_id: str) -> Any:
        fn = self.functions.get(func_id)
        if fn is None:
            blob = self.client.request({"kind": "fetch_function", "func_id": func_id})
            fn = cloudpickle.loads(blob)
            self.functions[func_id] = fn
        return fn

    def _resolve_args(self, spec: Dict[str, Any]) -> tuple:
        args, kwargs = pickle.loads(spec["args_blob"])
        hints: Dict[str, ObjectLocation] = spec.get("loc_hints") or {}
        ref_ids = [v.object_id for v in (*args, *kwargs.values())
                   if isinstance(v, ArgRef) and v.object_id not in hints]
        locs: Dict[str, ObjectLocation] = dict(hints)
        if ref_ids:
            # Owners before the directory (reference ownership protocol:
            # the owner is the authority for its objects; the controller
            # keeps a cache). ONE batched round-trip per distinct owner;
            # anything an owner can't resolve (or a dead owner's whole
            # group) falls through to one batched controller
            # get_locations.
            from . import ownership

            dep_owners: Dict[str, str] = spec.get("dep_owners") or {}
            by_owner: Dict[str, List[str]] = {}
            for oid in ref_ids:
                owner = dep_owners.get(oid)
                if owner:
                    by_owner.setdefault(owner, []).append(oid)
            for owner, oids in by_owner.items():
                locs.update(ownership.locate_from_owner_batch(oids, owner))
            still = [oid for oid in ref_ids if oid not in locs]
            if still:
                locs.update(self.client.request(
                    {"kind": "get_locations", "object_ids": still,
                     "node_id": self.node_id}))

        def resolve(v: Any) -> Any:
            if isinstance(v, ArgRef):
                val, loc = get_bytes_with_refresh(
                    locs[v.object_id], v.object_id, self.client.request)
                if loc.is_error:
                    raise val if isinstance(val, BaseException) else RuntimeError(val)
                return val
            return v

        args = tuple(resolve(a) for a in args)
        kwargs = {k: resolve(v) for k, v in kwargs.items()}
        return args, kwargs

    def run_task(
        self,
        spec: Dict[str, Any],
        actor_instance: Any = None,
        mailbox: Optional["ActorMailbox"] = None,
    ) -> None:
        task_id = spec["task_id"]
        if spec.get("__leased__"):
            # Directly-pushed task: the controller never saw a "running"
            # event — the completion report carries the start time so the
            # timeline can synthesize the full span.
            spec["__start_ts__"] = time.time()
        if task_events.enabled():
            # Flight recorder (TaskEventBuffer analog): phase timestamps
            # accumulate in __ph__ and are finalized by the completion
            # paths — which cover sync tasks, actor calls, async actor
            # coroutines (drive()), streaming, and every error path alike.
            now = time.time()
            ph = spec["__ph__"] = {"start_ts": now}
            recv = spec.pop("__recv_ts__", None)
            if recv is not None:
                ph["queue_wait_s"] = max(0.0, now - recv)
                sub = spec.get("submit_ts")
                if sub is not None:
                    ph["scheduling_delay_s"] = max(0.0, recv - sub)
        tls = ctx.task_local
        tls.task_id = task_id
        tls.label = spec.get("label", "")
        if spec.get("actor_id") and actor_instance is not None:
            tls.actor_id = spec["actor_id"]
        if mailbox is not None:
            if (spec.get("actor_id")
                    and self.queued_actor_tasks.pop(task_id, None) is None):
                # A cancel atomically claimed this call while it sat in
                # the mailbox and already completed it with
                # TaskCancelledError — skip without double-completing.
                tls.task_id = None
                return
            if mailbox.replay or mailbox.ckpt_enabled:
                # Completion paths journal the result / advance the
                # checkpoint cadence through this handle (popped exactly
                # once there).
                spec["__mb__"] = mailbox
        if task_id in self.cancelled_tasks:
            from .controller import TaskCancelledError

            self.cancelled_tasks.discard(task_id)
            self._complete_error(spec, TaskCancelledError(
                f"task {task_id[:8]} was cancelled before it started"), "")
            tls.task_id = None
            return
        dl = spec.get("deadline_ts")
        if dl is not None and time.time() > dl:
            # Dequeue-time deadline check (.options(deadline_s=...)): an
            # expired spec — plain-task pool or actor mailbox alike — is
            # refused, never executed.
            from .controller import DeadlineExceededError

            self._complete_error(spec, DeadlineExceededError(
                f"task {task_id[:8]} deadline passed before it started"), "")
            tls.task_id = None
            return
        self.running_threads[task_id] = threading.get_ident()
        # Borrow every dep (ordered before the hold_release on the same
        # owner connection), so the submitter's in-flight holds can retire
        # the moment this worker protects the objects itself. The handles
        # die with this frame — after arg VALUES are materialized the dep
        # bytes are no longer needed here. Guarded on dep_owners so a
        # dep-less task (the direct-dispatch common case) skips the module
        # call and its flag read entirely.
        if spec.get("dep_owners"):
            from . import ownership

            _held = ownership.acquire_spec_refs(spec)  # noqa: F841
        # Manual span scope: the consumer span must cover the ACTUAL body —
        # for async actor methods the user code runs in the awaited
        # coroutine, so span ownership transfers into drive() and closes
        # there (a `with` around the sync call would record a ~0ms success
        # for a 10s coroutine and miss its exceptions). A spec with no
        # carried trace context (tracing off at the submitter — the
        # default) gets the no-op span, skipping scope setup per task.
        if spec.get("trace_ctx"):
            from ray_tpu.util.tracing import task_span

            span = task_span(spec)
        else:
            span = _NULL_SPAN
        span.__enter__()
        span_transferred = False
        try:
            args, kwargs = self._resolve_args(spec)
            ph = spec.get("__ph__")
            if ph is not None:
                t = time.time()
                ph["arg_fetch_s"] = max(0.0, t - ph["start_ts"])
                ph["exec_start"] = t
            if spec.get("actor_id") and actor_instance is not None:
                method = getattr(actor_instance, spec["method_name"])
                result = method(*args, **kwargs)
            else:
                fn = self._load_function(spec["func_id"])
                result = fn(*args, **kwargs)
            if _is_coroutine(result):
                import asyncio

                if spec.get("streaming"):
                    raise TypeError(
                        "num_returns='streaming' requires a (sync or async) "
                        "generator; this method is a plain coroutine"
                    )
                if mailbox is not None:
                    # Async actor method: hand the coroutine to the actor's
                    # persistent loop and release the mailbox thread — the
                    # next call dispatches immediately, so awaits interleave.
                    loop = mailbox.ensure_aio_loop()
                    sem = mailbox.aio_sem
                    span_transferred = True
                    # The mailbox thread moves on to its next call: restore
                    # its current-span slot NOW; the span itself stays open
                    # until the coroutine settles in drive().
                    span.detach_context()

                    async def drive(result=result, spec=spec, span=span):
                        async with sem:
                            try:
                                value = await result
                            except ActorExitSignal:
                                span.__exit__(None, None, None)
                                await asyncio.get_running_loop().run_in_executor(
                                    None,
                                    lambda: self._handle_actor_exit(spec))
                                return
                            except BaseException as e:  # noqa: BLE001
                                tb = traceback.format_exc()
                                span.__exit__(type(e), e, e.__traceback__)
                                await asyncio.get_running_loop().run_in_executor(
                                    None,
                                    lambda: self._complete_error(spec, e, tb),
                                )
                            else:
                                span.__exit__(None, None, None)
                                # Serialization + the controller round-trip
                                # block; keep them off the actor loop so
                                # other in-flight awaits keep interleaving.
                                await asyncio.get_running_loop().run_in_executor(
                                    None, lambda: self._complete_ok(spec, value)
                                )

                    asyncio.run_coroutine_threadsafe(drive(), loop)
                    return
                result = asyncio.run(result)
            if _is_async_gen(result):
                if not spec.get("streaming"):
                    raise TypeError(
                        "async generator methods require "
                        "num_returns='streaming'"
                    )
                if mailbox is not None:
                    self._run_streaming_async(spec, result, mailbox)
                    return
                raise TypeError(
                    "async generators are only supported on actors"
                )
            if spec.get("streaming"):
                self._run_streaming(spec, result)
                return
            self._complete_ok(spec, result)
        except ActorExitSignal:
            self._handle_actor_exit(spec)
        except BaseException as e:  # noqa: BLE001 — every task error is captured
            self._complete_error(spec, e, traceback.format_exc())
        finally:
            if not span_transferred:
                import sys as _sys

                span.__exit__(*_sys.exc_info())
            self.running_threads.pop(task_id, None)
            tls.task_id = None
            if self._log_attributor is not None:
                # Close out the task's pending byte range so its indexed
                # output is complete once the result is observable.
                self._log_attributor.flush()

    def _ship_done(self, msg: Dict[str, Any]) -> None:
        """Fire-and-forget a task_done to the controller, coalesced: every
        payload buffered during one io-loop beat ships as a single framed
        task_done_batch (one wakeup, one pickle, one syscall). Best-effort
        exactly like the per-task send it replaces — a batch in flight when
        the controller bounces is covered by the driver's resubmission and
        the direct caller's recovery probe, not by redelivery here."""
        if not flags.get("RTPU_SUBMIT_BATCH"):
            self.client.send_nowait(msg)
            return
        flush_now = False
        with self._done_lock:
            self._done_buf.append(msg)
            if len(self._done_buf) >= flags.get("RTPU_SUBMIT_BATCH_MAX"):
                flush_now = True
            elif self._done_scheduled:
                return
            self._done_scheduled = True
        try:
            if flush_now:
                self._flush_done_threadsafe()
            else:
                self.client.io.loop.call_soon_threadsafe(
                    self._flush_done_threadsafe)
        except RuntimeError:
            pass  # io loop torn down (shutdown): parity with send_nowait

    def _flush_done_threadsafe(self) -> None:
        with self._done_lock:
            items, self._done_buf = self._done_buf, []
            self._done_scheduled = False
        if not items:
            return
        msg = items[0] if len(items) == 1 else {"kind": "task_done_batch",
                                                "items": items}
        try:
            self.client.send_nowait(msg)
        except Exception:
            pass

    def _record_phases(self, spec: Dict[str, Any], outcome: str) -> None:
        """Finalize + buffer this task's phase event (flight recorder).
        Pops ``__ph__`` so a completion that re-routes (store failure →
        _complete_error) records exactly once."""
        ph = spec.pop("__ph__", None)
        if ph is None:
            return
        end = time.time()
        if "exec_start" in ph and "exec_s" not in ph:
            ph["exec_s"] = max(0.0, end - ph.pop("exec_start"))
        ph.pop("exec_start", None)
        task_events.record({
            "task_id": spec.get("task_id"),
            "label": spec.get("label"),
            "actor_id": spec.get("actor_id"),
            "worker_id": self.worker_id,
            "node_id": self.node_id,
            "start_ts": ph.pop("start_ts"),
            "end_ts": end,
            "outcome": outcome,
            "phases": {k: v for k, v in ph.items()
                       if k in task_events.PHASE_KEYS},
        })

    def _complete_ok(self, spec: Dict[str, Any], result: Any) -> None:
        ph = spec.get("__ph__")
        t_store = 0.0
        if ph is not None:
            t_store = time.time()
            if "exec_start" in ph:
                ph["exec_s"] = max(0.0, t_store - ph.pop("exec_start"))
        try:
            locations = self._store_returns(spec, result)
        except BaseException as e:  # noqa: BLE001
            self._complete_error(spec, e, traceback.format_exc())
            return
        if ph is not None:
            ph["result_store_s"] = max(0.0, time.time() - t_store)
        self._record_phases(spec, "finished")
        mb = spec.pop("__mb__", None)
        if mb is not None:
            # Journal BEFORE the caller can observe the result: a duplicate
            # arriving right after the reply must hit the journal.
            mb.note_result(spec, {"locations": locations})
        msg = {
            "kind": "task_done",
            "task_id": spec["task_id"],
            "worker_id": self.worker_id,
            "locations": locations,
        }
        self._finish_direct(spec, {"locations": locations})
        if spec.pop("__leased__", False):
            # The controller never saw this (directly-pushed) spec; ship it
            # with the completion so lineage + task events stay complete.
            # Fully-inline results need no lineage — the location the
            # controller stores CARRIES the bytes, so the object can never
            # need reconstruction; a slim spec (ids + label) keeps the
            # task-event trail while skipping the args/closure payload and
            # the controller-side lineage write on the hot path.
            if all(loc.inline is not None for loc in locations):
                msg["spec"] = {"task_id": spec["task_id"],
                               "label": spec.get("label"),
                               "return_ids": spec["return_ids"]}
            else:
                msg["spec"] = {k: v for k, v in spec.items()
                               if not k.startswith("__")}
            msg["started_ts"] = spec.get("__start_ts__")
        # Fire-and-forget: nothing consumes the ack, and the worker is not
        # eligible for new work until the controller processes this message
        # anyway (state flips to idle there) — so dropping the round trip
        # costs nothing and saves a response pickle + wakeup per task.
        self._ship_done(msg)

    def _complete_error(self, spec: Dict[str, Any], e: BaseException, tb: str) -> None:
        self._record_phases(spec, "failed")
        label = spec.get("label", spec["task_id"][:8])
        err = TaskError(label, e, tb)
        try:
            data = pickle.dumps(err)
        except Exception:
            # Unpicklable cause (socket, lock, ...): degrade to a string
            # rendition so the error still reaches the caller instead of
            # hanging the task forever.
            err = TaskError(label, RuntimeError(f"{type(e).__name__}: {e}"), tb)
            data = pickle.dumps(err)
        err_ids = list(spec["return_ids"])
        if not err_ids and spec.get("streaming"):
            # Streaming tasks have no pre-allocated return ids; ship the
            # error as a synthetic location so the consumer sees the real
            # exception on next() rather than a generic crash.
            from .ids import ObjectID

            err_ids = [ObjectID.generate()]
        err_locs = [
            ObjectLocation(object_id=oid, size=len(data), inline=data, is_error=True)
            for oid in err_ids
        ]
        mb = spec.pop("__mb__", None)
        if mb is not None:
            # Errors journal too: the call WAS applied (it raised) — a
            # replayed duplicate must observe the same exception, not
            # re-execute the method.
            mb.note_result(spec, {"error_locations": err_locs,
                                  "is_error": True})
        msg = {
            "kind": "task_done",
            "task_id": spec["task_id"],
            "worker_id": self.worker_id,
            "error_locations": err_locs,
            "is_error": True,
        }
        self._finish_direct(spec, {"error_locations": err_locs})
        if spec.pop("__leased__", False):
            msg["spec"] = {k: v for k, v in spec.items()
                           if not k.startswith("__")}
            msg["started_ts"] = spec.get("__start_ts__")
        try:
            self._ship_done(msg)
        except Exception:
            pass

    def _complete_replayed(self, spec: Dict[str, Any],
                           payload: Dict[str, Any]) -> None:
        """A deduped duplicate of an already-applied call: republish the
        journaled outcome — locations or error — without re-executing
        (exactly-once replay). The task_done retires a controller-path
        resubmission of the same task_id; the location store is idempotent,
        so replying twice is safe."""
        self._finish_direct(spec, payload)
        msg = {"kind": "task_done", "task_id": spec["task_id"],
               "worker_id": self.worker_id}
        msg.update(payload)
        spec.pop("__leased__", None)
        try:
            self._ship_done(msg)
        except Exception:
            pass

    def _run_streaming(self, spec: Dict[str, Any], result: Any) -> None:
        """Drive a generator task: each yielded value becomes its own object,
        reported immediately (reference: streaming generator protocol,
        _raylet.pyx:273 execute_streaming_generator). The controller holds
        the report reply while the consumer lags past the backpressure
        window, so this thread self-throttles."""
        import inspect

        from .ids import ObjectID

        task_id = spec["task_id"]
        if not inspect.isgenerator(result):
            raise TypeError(
                f"num_returns='streaming' requires a generator function, "
                f"got {type(result).__name__}"
            )
        for value in result:
            oid = ObjectID.generate()
            with tracing.phase("stream.put"):
                loc = put_bytes(value, oid, self.node_id)
            with tracing.phase("stream.report"):
                ack = self.client.request(
                    {"kind": "generator_item", "task_id": task_id, "loc": loc}
                )
            if isinstance(ack, dict) and ack.get("closed"):
                # Consumer dropped the generator: stop producing.
                result.close()
                break
        self._record_phases(spec, "finished")
        self.client.request(
            {
                "kind": "task_done",
                "task_id": task_id,
                "worker_id": self.worker_id,
                "locations": [],
            }
        )

    def _run_streaming_async(self, spec: Dict[str, Any], agen: Any,
                             mailbox: "ActorMailbox") -> None:
        """Drive an async generator on the actor's persistent loop; item
        reports run in the default executor so awaits keep interleaving."""
        import asyncio

        from .ids import ObjectID

        loop = mailbox.ensure_aio_loop()
        task_id = spec["task_id"]

        def report(loc):  # on an executor thread: a phase nests per thread
            with tracing.phase("stream.report"):
                return self.client.request(
                    {"kind": "generator_item", "task_id": task_id,
                     "loc": loc})

        async def drive():
            try:
                async for value in agen:
                    oid = ObjectID.generate()
                    with tracing.phase("stream.put"):
                        loc = put_bytes(value, oid, self.node_id)
                    ack = await asyncio.get_running_loop().run_in_executor(
                        None, report, loc)
                    if isinstance(ack, dict) and ack.get("closed"):
                        await agen.aclose()
                        break
            except BaseException as e:  # noqa: BLE001
                self._complete_error(spec, e, traceback.format_exc())
                return
            self._record_phases(spec, "finished")
            await asyncio.get_running_loop().run_in_executor(
                None,
                lambda: self.client.request(
                    {"kind": "task_done", "task_id": task_id,
                     "worker_id": self.worker_id, "locations": []}
                ),
            )

        asyncio.run_coroutine_threadsafe(drive(), loop)

    def _store_returns(self, spec: Dict[str, Any], result: Any) -> List[ObjectLocation]:
        return_ids: List[str] = spec["return_ids"]
        if not return_ids:
            return []
        if len(return_ids) == 1:
            values = [result]
        else:
            values = list(result)
            if len(values) != len(return_ids):
                raise ValueError(
                    f"task declared num_returns={len(return_ids)} but returned {len(values)}"
                )
        return [put_bytes(v, oid, self.node_id) for v, oid in zip(values, return_ids)]

    def _restore_record(self, spec: Dict[str, Any],
                        mb: "ActorMailbox") -> Optional[Dict[str, Any]]:
        """Newest reachable checkpoint/snapshot record for this actor: the
        controller-shipped blob riding the spec vs a (possibly newer)
        host-local checkpoint file — epochs are monotonic across hosts, so
        the comparison is one int. None -> run the constructor."""
        from . import checkpoint

        blob = spec.get("state_blob")
        rec: Optional[Dict[str, Any]] = None
        if blob is not None:
            rec = checkpoint.decode(blob)
        if mb.ckpt_enabled:
            local = checkpoint.newest_local(mb.actor_id)
            if local is not None and local[0] > (rec or {}).get("epoch", 0):
                try:
                    rec = checkpoint.decode(local[1])
                except Exception:
                    pass  # torn/stale file: the shipped copy (or ctor) wins
        return rec

    def _instantiate_actor(self, spec: Dict[str, Any]) -> None:
        actor_id = spec["actor_id"]
        mb = ActorMailbox(self, actor_id, spec.get("max_concurrency", 1))
        mb.spec = spec  # kept for re-claiming the actor after a controller bounce
        mb.configure(spec)
        self.actors[actor_id] = mb
        if mb.ckpt_enabled and mb.ckpt_interval > 0.0:
            self._ensure_ckpt_timer()

        def create():
            from . import ownership

            _held = ownership.acquire_spec_refs(spec)  # noqa: F841
            try:
                # Set before instantiating: constructors may legitimately
                # ask for their own id (ray parity: get_runtime_context()
                # works inside __init__), and threads an actor spawns from
                # its constructor inherit it by copying.
                ctx.task_local.actor_id = actor_id
                rec = self._restore_record(spec, mb)
                restored_epoch = None
                if rec is not None:
                    # Drain migration or crash restart: restore the newest
                    # reachable record instead of re-running the
                    # constructor — the actor arrives with state AND its
                    # exactly-once journal intact.
                    mb.instance = rec["instance"]
                    mb.ckpt_epoch = int(rec.get("epoch", 0))
                    if rec.get("journal"):
                        # Call-replay dedup entries only matter when replay
                        # is armed, but __dag__* entries (a compiled DAG's
                        # per-stage seq journal) must survive the restore
                        # regardless — DAG recovery resumes from them.
                        with mb._seq_lock:
                            mb.journal = {
                                c: dict(e)
                                for c, e in rec["journal"].items()
                                if mb.replay or c.startswith("__dag__")}
                    restored_epoch = mb.ckpt_epoch
                else:
                    with tracing.phase("boot.actor_init") as boot:
                        cls = self._load_function(spec["func_id"])
                        boot.attrs["cls"] = getattr(cls, "__name__", "")
                        args, kwargs = self._resolve_args(spec)
                        mb.instance = cls(*args, **kwargs)
                ready: Dict[str, Any] = {"kind": "actor_ready",
                                         "actor_id": actor_id}
                if restored_epoch is not None:
                    ready["restored_epoch"] = restored_epoch
                self.client.request(ready)
            except BaseException as e:  # noqa: BLE001
                tb = traceback.format_exc()
                self.client.request(
                    {
                        "kind": "actor_error",
                        "actor_id": actor_id,
                        "error": ActorDiedError(f"actor constructor failed: {e!r}\n{tb}"),
                    }
                )

        # __init__ runs on the mailbox thread so actor state is thread-affine.
        mb.q.put({"__create__": create})

    def _ensure_ckpt_timer(self) -> None:
        """One daemon sweep thread for interval-based checkpoints, started
        lazily at the first hosted actor with checkpoint_interval_s — a
        worker hosting none never grows the thread."""
        if getattr(self, "_ckpt_timer_started", False):
            return
        self._ckpt_timer_started = True

        def _tick() -> None:
            while not self.shutdown_event.is_set():
                time.sleep(flags.get("RTPU_CHECKPOINT_TICK_S"))
                for mb in list(self.actors.values()):
                    try:
                        if mb.ckpt_due():
                            mb.request_checkpoint()
                    except Exception:
                        pass  # checkpointing must never hurt the actor

        threading.Thread(target=_tick, name="ckpt-timer",
                         daemon=True).start()

    def serve_forever(self) -> None:
        self.shutdown_event.wait()
        if self._log_attributor is not None:
            try:
                self._log_attributor.flush()
            except Exception:
                pass
        try:
            self.client.close()
        except Exception:
            pass
        # Hard-exit: executor threads are non-daemon and user task code may be
        # mid-flight; a worker told to shut down must actually die (the
        # reference's raylet SIGTERMs its workers for the same reason).
        os._exit(0)


def _is_coroutine(x: Any) -> bool:
    import inspect

    return inspect.iscoroutine(x)


def _is_async_gen(x: Any) -> bool:
    import inspect

    return inspect.isasyncgen(x)
