"""DeploymentHandle + Router.

Parity: reference serve/handle.py:711 (DeploymentHandle, .remote :783) →
serve/_private/router.py:312 (Router.assign_request) →
replica_scheduler/pow_2_scheduler.py:49 (PowerOfTwoChoicesReplicaScheduler).
The router keeps a local in-flight counter per replica and picks the less
loaded of two random candidates — queue-length probing without an extra
RPC per request. Replica lists are cached and refreshed from the
controller only when the deployment version bumps or a call fails
(reference LongPollClient config push).
"""
from __future__ import annotations

import random
import threading
import time
from typing import Any, Dict, List, Optional

import ray_tpu
from ray_tpu import flags
from ray_tpu.util import tracing
from ray_tpu.core.controller import (ActorDiedError, DeadlineExceededError,
                                     GetTimeoutError, TaskCancelledError,
                                     TaskError, WorkerCrashedError)

from . import admission
from . import context as serve_context
from . import trace
from .controller import CONTROLLER_NAME


class DeploymentNotFoundError(Exception):
    """The handle's deployment no longer exists on the controller."""


def _unwrap(err: BaseException) -> BaseException:
    """Typed control-flow errors (deadline, cancel) travel wrapped in
    TaskError when they fire inside the worker; callers want the type."""
    if isinstance(err, TaskError) and isinstance(
            err.cause, (DeadlineExceededError, TaskCancelledError)):
        return err.cause
    return err


class DeploymentResponse:
    """Future-like result of handle.remote() (reference DeploymentResponse:
    resolves to the result; .result() blocks; ._to_object_ref for chaining)."""

    def __init__(self, ref, router, replica_key, deadline_ts=None,
                 root=None):
        self._ref = ref
        self._router = router
        self._replica_key = replica_key
        self._deadline_ts = deadline_ts
        self._done = False
        # Trace root when THIS call created the trace (bare driver-side
        # handle call): the terminal outcome here becomes the ledger record.
        self._root = root

    def result(self, timeout: Optional[float] = None) -> Any:
        if timeout is None and self._deadline_ts is not None:
            # Default the wait to the request's remaining budget.
            timeout = max(0.0, self._deadline_ts - time.time())
        try:
            out = ray_tpu.get(self._ref, timeout=timeout)
        except GetTimeoutError as e:
            if (self._deadline_ts is not None
                    and time.time() >= self._deadline_ts):
                # The request's own budget ran out — that is the client's
                # deadline, not a replica fault: no breaker strike.
                admission.deadline_exceeded(self._router.name)
                if self._root is not None:
                    self._root.finish("deadline", error=str(e))
                raise DeadlineExceededError(
                    f"request to {self._router.name} deadline exceeded "
                    f"while awaiting the result") from e
            self._router._note_result(self._replica_key, e)
            if self._root is not None:
                self._root.finish("error", error=str(e))
            raise
        except Exception as e:
            e2 = _unwrap(e)
            self._router._note_result(self._replica_key, e2)
            if self._root is not None:
                status = ("deadline"
                          if isinstance(e2, DeadlineExceededError) else
                          "cancelled"
                          if isinstance(e2, TaskCancelledError) else "error")
                self._root.finish(status, error=str(e2))
            if e2 is not e:
                raise e2 from e
            raise
        else:
            self._router._note_result(self._replica_key, None)
            if self._root is not None:
                self._root.finish("ok")
            return out
        finally:
            self._release()

    def cancel(self) -> None:
        """Cancel the in-flight replica call: a queued mailbox entry is
        refused at dequeue, a running one gets the async-raise."""
        try:
            ray_tpu.cancel(self._ref)
        except Exception:
            pass
        admission.cancelled(self._router.name)
        if self._root is not None:
            self._root.finish("cancelled")
        self._release()

    def _release(self) -> None:
        if not self._done:
            self._done = True
            self._router._on_done(self._replica_key)
            if self._root is not None:
                # Fire-and-forget callers never observe the outcome; close
                # the ledger record as ok at release (first finish wins, so
                # an explicit terminal status above is never overwritten).
                self._root.finish("ok")

    def __del__(self):
        # Fire-and-forget callers never call result(); without this the
        # router's in-flight counter for the replica leaks permanently and
        # power-of-two routing starves it of traffic.
        try:
            self._release()
        except Exception:
            pass

    def _to_object_ref(self):
        return self._ref


class DeploymentStreamingResponse:
    """Iterator over a streaming deployment call's items (reference:
    DeploymentResponseGenerator, serve/handle.py). Yields VALUES; the
    underlying transport is the core streaming-generator protocol."""

    def __init__(self, ref_gen, router, replica_key, deadline_ts=None,
                 root=None):
        self._gen = ref_gen
        self._router = router
        self._replica_key = replica_key
        self._deadline_ts = deadline_ts
        self._done = False
        self._exhausted = False
        self._root = root
        self._items = 0

    def __iter__(self):
        return self

    def __next__(self):
        if (self._deadline_ts is not None
                and time.time() > self._deadline_ts):
            # The consumer's budget ran out mid-stream: stop pulling and
            # close the producer (frees its engine slot).
            admission.deadline_exceeded(self._router.name)
            if self._root is not None:
                self._root.finish("deadline", items=self._items)
            self._release()
            raise DeadlineExceededError(
                f"stream from {self._router.name} deadline exceeded")
        try:
            ref = next(self._gen)
        except StopIteration:
            self._exhausted = True
            self._router._note_result(self._replica_key, None)
            if self._root is not None:
                self._root.finish("ok", items=self._items)
            self._release()
            raise
        except Exception as e:
            e2 = _unwrap(e)
            self._router._note_result(self._replica_key, e2)
            if self._root is not None:
                self._root.finish(
                    "deadline" if isinstance(e2, DeadlineExceededError)
                    else "error", error=str(e2), items=self._items)
            self._release()
            raise
        self._items += 1
        with tracing.phase("stream.get"):
            return ray_tpu.get(ref)

    def close(self) -> None:
        """Client walked away (HTTP disconnect / explicit abort): close the
        producer generator — the replica sees GeneratorExit and aborts its
        engine request, freeing the KV slot immediately."""
        if not self._done and not self._exhausted:
            admission.cancelled(self._router.name)
            if self._root is not None:
                self._root.finish("cancelled", items=self._items)
        elif self._root is not None:
            self._root.finish("ok", items=self._items)
        self._release()

    def _release(self) -> None:
        if not self._done:
            self._done = True
            self._router._on_done(self._replica_key)
            if self._root is not None:
                # Abandoned without an explicit outcome (__del__): a
                # pre-exhaustion drop is a cancellation. First finish wins.
                self._root.finish(
                    "ok" if self._exhausted else "cancelled",
                    items=self._items)
            close = getattr(self._gen, "close", None)
            if close is not None:
                # Frees a producer stalled in the backpressure window when
                # the consumer walks away mid-stream (HTTP client hangup).
                close()

    def __del__(self):
        try:
            self._release()
        except Exception:
            pass


import weakref

_routers: "weakref.WeakSet" = weakref.WeakSet()
_subscribed_tokens: set = set()


def _ensure_push_subscription() -> None:
    """Subscribe this process once to serve's long-poll push channel
    (reference LongPollClient): replica-set changes invalidate router
    caches immediately instead of waiting out the poll period."""
    from ray_tpu.core import context as ctx

    try:
        wc = ctx.get_worker_context()
    except Exception:
        return
    token = wc.client.token
    if token in _subscribed_tokens:
        return
    _subscribed_tokens.add(token)

    def on_update(data) -> None:
        name = (data or {}).get("name")
        for r in list(_routers):
            if r.name == name:
                r._last_refresh = 0.0  # next assign() refreshes

    try:
        ctx.on_pubsub("serve_updates", on_update)
        wc.client.request({"kind": "subscribe", "channel": "serve_updates"})
    except Exception:
        _subscribed_tokens.discard(token)


class Router:
    REFRESH_PERIOD_S = 3.0

    def __init__(self, deployment_name: str):
        self.name = deployment_name
        self._lock = threading.Lock()
        self._version = -1
        self._replicas: List[Any] = []
        self._inflight: Dict[str, int] = {}
        # Replicas hosted on draining/drained nodes: excluded from picks so
        # requests stop landing on a node that is about to vanish
        # (refreshed with the replica list).
        self._avoid: set = set()
        self._controller = None
        self._last_refresh = 0.0
        # Admission control (RTPU_SERVE_ADMISSION): per-replica circuit
        # breakers, the retry token bucket, and the deployment's queue
        # bound (refreshed with the replica list; None until fetched).
        self._board = admission.BreakerBoard(deployment_name)
        self._budget = admission.RetryBudget()
        self._max_ongoing = 16
        self._max_queued: Optional[int] = None
        # Hot-prefix routing table from the controller's PrefixIndex:
        # prefix hash -> replica ids already holding that prefix's K/V.
        self._prefix_routes: Dict[str, List[str]] = {}
        _routers.add(self)
        _ensure_push_subscription()

    def _ctrl(self):
        if self._controller is None:
            self._controller = ray_tpu.get_actor(CONTROLLER_NAME)
        return self._controller

    def _refresh(self, force: bool = False) -> None:
        now = time.time()
        with self._lock:
            fresh = (self._replicas
                     and now - self._last_refresh < self.REFRESH_PERIOD_S)
            if fresh and not force:
                return
        try:
            version, replicas = ray_tpu.get(
                self._ctrl().get_replicas.remote(self.name))
        except Exception as e:
            if "no deployment" in str(e):
                with self._lock:
                    self._replicas = []
                raise DeploymentNotFoundError(self.name) from e
            raise
        rcfg = None
        if (flags.get("RTPU_SERVE_ADMISSION")
                or flags.get("RTPU_PREFIX_CACHE")):
            try:
                rcfg = ray_tpu.get(
                    self._ctrl().get_routing_config.remote(self.name))
            except Exception:
                rcfg = None  # older controller: keep previous bounds
        avoid = self._replicas_on_draining_nodes(replicas)
        with self._lock:
            self._version = version
            self._replicas = replicas
            self._avoid = avoid
            self._inflight = {r._actor_id: self._inflight.get(r._actor_id, 0)
                              for r in replicas}
            self._last_refresh = now
            if rcfg is not None:
                self._max_ongoing = int(rcfg.get("max_ongoing_requests", 16))
                mq = rcfg.get("max_queued_requests")
                self._max_queued = (flags.get("RTPU_SERVE_MAX_QUEUED")
                                    if mq is None else int(mq))
                self._prefix_routes = rcfg.get("prefix_routes", {})
        self._board.prune([r._actor_id for r in replicas])

    @staticmethod
    def _replicas_on_draining_nodes(replicas) -> set:
        """Actor ids of replicas hosted on draining/drained nodes — the
        scheduler already re-creates them elsewhere; routing there just
        buys a request an ActorDiedError when the node goes."""
        if not replicas:
            return set()
        from ray_tpu.core import context as ctx

        try:
            client = ctx.get_worker_context().client
            nodes = client.request({"kind": "cluster_state"})["nodes"]
            bad = {n["node_id"] for n in nodes
                   if n.get("state", "alive") != "alive"}
            if not bad:
                return set()
            actors = client.request(
                {"kind": "list_state", "what": "actors", "limit": 10000})
            want = {r._actor_id for r in replicas}
            return {a["actor_id"] for a in actors
                    if a["actor_id"] in want and a.get("node_id") in bad}
        except Exception:
            return set()

    def _pick(self, use_breaker: bool = False):
        """Power-of-two-choices over local in-flight counts; replicas on
        draining nodes are out of the draw while any alternative exists,
        and (admission on) so are replicas with open circuit breakers."""
        with self._lock:
            reps = [r for r in self._replicas
                    if r._actor_id not in self._avoid] or self._replicas
            if not reps:
                raise RuntimeError(f"no replicas for {self.name}")
            if use_breaker:
                ok = [r for r in reps
                      if self._board.would_allow(r._actor_id)]
                if not ok:
                    admission.shed(self.name, "breaker_open")
                    raise admission.BackPressureError(
                        f"all replicas of {self.name} have open circuit "
                        f"breakers",
                        retry_after_s=flags.get(
                            "RTPU_SERVE_BREAKER_COOLDOWN_S"))
                reps = ok
            if len(reps) == 1:
                r = reps[0]
            else:
                a, b = random.sample(reps, 2)
                r = a if (self._inflight.get(a._actor_id, 0)
                          <= self._inflight.get(b._actor_id, 0)) else b
            self._inflight[r._actor_id] = self._inflight.get(
                r._actor_id, 0) + 1
            return r

    def _on_done(self, key: str) -> None:
        with self._lock:
            if key in self._inflight and self._inflight[key] > 0:
                self._inflight[key] -= 1

    def _pick_affine(self, model_id: str, exclude: Optional[set] = None,
                     use_breaker: bool = False):
        """Model-affine pick: rendezvous hash over replicas, so one model's
        requests land where it is already loaded (reference model-multiplex
        routing). `exclude` holds replicas that already failed this call —
        the deterministic hash would otherwise retry the same dead one.
        Draining-node replicas leave the hash ring the same way (unless
        nothing else remains)."""
        import hashlib

        with self._lock:
            reps = [r for r in self._replicas
                    if not exclude or r._actor_id not in exclude]
            live = [r for r in reps if r._actor_id not in self._avoid]
            reps = live or reps
            if use_breaker and reps:
                # Breaker-open replicas leave the hash ring too (affinity
                # is a preference; a tripped replica is not).
                ok = [r for r in reps
                      if self._board.would_allow(r._actor_id)]
                if not ok:
                    admission.shed(self.name, "breaker_open")
                    raise admission.BackPressureError(
                        f"all replicas of {self.name} have open circuit "
                        f"breakers",
                        retry_after_s=flags.get(
                            "RTPU_SERVE_BREAKER_COOLDOWN_S"))
                reps = ok
            if not reps:
                raise RuntimeError(f"no replicas for {self.name}")
            # Prefix steering: when the controller's cluster index says
            # some live replicas already HOLD this prefix's K/V, restrict
            # the hash ring to them — the request hits their cache and
            # skips prefill. Falls back to plain rendezvous otherwise.
            holders = self._prefix_routes.get(model_id)
            if holders:
                held = [r for r in reps if r._actor_id in holders]
                if held:
                    reps = held
            r = max(
                reps,
                key=lambda rep: hashlib.md5(
                    f"{model_id}|{rep._actor_id}".encode()).digest(),
            )
            self._inflight[r._actor_id] = self._inflight.get(r._actor_id, 0) + 1
            return r

    def _note_result(self, key: str, err: Optional[BaseException]) -> None:
        """Result-side accounting: successes close breakers, replica
        faults strike them; deadline/cancel outcomes go to their counters
        (client decisions, never a replica's fault)."""
        if err is None:
            if flags.get("RTPU_SERVE_ADMISSION"):
                self._board.on_success(key)
            return
        if isinstance(err, DeadlineExceededError):
            admission.deadline_exceeded(self.name)
            return
        if isinstance(err, TaskCancelledError):
            admission.cancelled(self.name)
            return
        if (flags.get("RTPU_SERVE_ADMISSION")
                and isinstance(err, (ActorDiedError, WorkerCrashedError,
                                     TaskError, GetTimeoutError))):
            self._board.on_failure(key)

    def _admit(self) -> None:
        """Bounded-queue admission: total locally-tracked in-flight beyond
        num_replicas*max_ongoing + max_queued sheds with BackPressureError
        (reference: Serve max_queued_requests, handle-side)."""
        with self._lock:
            n = len(self._replicas)
            total = sum(self._inflight.values())
            max_q = self._max_queued
            if max_q is None:
                max_q = flags.get("RTPU_SERVE_MAX_QUEUED")
        if n == 0 or max_q < 0:
            # Cold start (no replicas yet — the pick path retries) or
            # explicitly unbounded.
            self._budget.on_admitted()
            return
        cap = n * self._max_ongoing + max_q
        if total >= cap:
            admission.shed(self.name, "queue_full")
            raise admission.BackPressureError(
                f"deployment {self.name} is at capacity: {total} requests "
                f"in flight >= {n} replicas x {self._max_ongoing} ongoing "
                f"+ {max_q} queued", retry_after_s=1.0)
        self._budget.on_admitted()

    def assign(self, method_name: str, args, kwargs,
               retries: int = 3, stream: bool = False,
               multiplexed_model_id: str = "",
               deadline_ts: Optional[float] = None,
               request_id: str = "",
               trace_ctx: Optional[dict] = None):
        """Route one request. ``trace_ctx`` is the explicit wire trace
        context from an ingress (HTTP/gRPC proxy); without one, a nested
        call inherits the enclosing request's trace from the serve
        context, and a bare driver-side call ROOTS a new trace here (its
        response wrapper then owns the ledger record)."""
        root = hop = None
        if trace.enabled():
            wire = trace_ctx or trace.current_trace_ctx()
            if wire is None:
                root = trace.start_request(
                    request_id=request_id, deployment=self.name,
                    proto="python", method=method_name)
                wire = root.trace_ctx
            hop = trace.start_hop("serve.assign", kind="router",
                                  trace_ctx=wire, deployment=self.name)
            # Downstream spans parent under the CALLER (root / enclosing
            # replica), not the assign hop: assign ends at dispatch, so
            # execution dwell nested under it would double-count when the
            # waterfall attributes exclusive time.
            trace_ctx = wire
        else:
            trace_ctx = None
        try:
            resp = self._assign(method_name, args, kwargs, retries, stream,
                                multiplexed_model_id, deadline_ts,
                                trace_ctx, hop)
        except BaseException as e:
            if hop is not None:
                hop.end(error=type(e).__name__)
            if root is not None:
                status = ("shed"
                          if isinstance(e, admission.BackPressureError) else
                          "deadline"
                          if isinstance(e, DeadlineExceededError) else
                          "error")
                root.finish(status, error=str(e))
            raise
        if hop is not None:
            hop.end()
        if root is not None:
            resp._root = root
        return resp

    def _assign(self, method_name: str, args, kwargs,
                retries: int = 3, stream: bool = False,
                multiplexed_model_id: str = "",
                deadline_ts: Optional[float] = None,
                trace_ctx: Optional[dict] = None,
                hop=None):
        if deadline_ts is None:
            # Nested composition: a handle call made INSIDE a serve
            # request inherits the enclosing request's budget.
            deadline_ts = serve_context.get_request_deadline()
        # Arrival stamp: set once at the outermost hop, inherited by nested
        # calls — TTFT downstream measures from HERE, queue wait included.
        # The wait itself is forwarded as a per-host monotonic DELTA
        # (upstream accumulation + local dwell), never as an epoch
        # difference across machines, so wall-clock skew can't bias it.
        start_ts = serve_context.get_request_start()
        assign_mono = time.monotonic()
        if start_ts is None:
            start_ts = time.time()
        if deadline_ts is not None and time.time() > deadline_ts:
            admission.deadline_exceeded(self.name)
            raise DeadlineExceededError(
                f"request to {self.name} expired before assignment")
        self._refresh()
        admit = bool(flags.get("RTPU_SERVE_ADMISSION"))
        if admit:
            self._admit()
        last_err: Optional[Exception] = None
        failed: set = set()
        for attempt in range(retries):
            if attempt > 0:
                if admit and not self._budget.try_spend():
                    # Retry budget exhausted: surfacing the error beats
                    # amplifying an outage with retry traffic.
                    break
                # Jittered exponential backoff, never past the deadline.
                delay = min(0.1 * (2 ** (attempt - 1)), 2.0)
                delay *= 0.5 + random.random()
                if deadline_ts is not None:
                    delay = min(delay, max(0.0, deadline_ts - time.time()))
                time.sleep(delay)
                self._refresh(force=True)
                if deadline_ts is not None and time.time() > deadline_ts:
                    admission.deadline_exceeded(self.name)
                    raise DeadlineExceededError(
                        f"request to {self.name} expired while retrying")
            try:
                if multiplexed_model_id:
                    replica = self._pick_affine(multiplexed_model_id, failed,
                                                use_breaker=admit)
                else:
                    replica = self._pick(use_breaker=admit)
            except RuntimeError as e:
                last_err = e
                continue
            rid = replica._actor_id
            if admit and not self._board.admit(rid):
                # Lost the half-open probe race: count as a failed attempt.
                self._on_done(rid)
                last_err = RuntimeError(f"replica {rid[:8]} breaker open")
                continue
            remaining = (None if deadline_ts is None
                         else max(0.0, deadline_ts - time.time()))
            # Queue wait accumulated so far, measured at dispatch time on
            # THIS host's monotonic clock: the enclosing request's elapsed
            # when nested, or the local assign dwell at the outermost hop.
            queue_wait = serve_context.elapsed_s()
            if queue_wait is None:
                queue_wait = time.monotonic() - assign_mono
            if hop is not None:
                hop.attributes.update(attempts=attempt + 1,
                                      replica=rid[:12],
                                      queue_wait_s=round(queue_wait, 6))
            try:
                if stream:
                    ref_gen = replica.handle_request_streaming.options(
                        num_returns="streaming", deadline_s=remaining,
                    ).remote(method_name, args, kwargs,
                             multiplexed_model_id, deadline_ts, start_ts,
                             queue_wait, trace_ctx)
                    return DeploymentStreamingResponse(
                        ref_gen, self, rid, deadline_ts)
                ref = replica.handle_request.options(
                    deadline_s=remaining,
                ).remote(method_name, args, kwargs, multiplexed_model_id,
                         deadline_ts, start_ts, queue_wait, trace_ctx)
                return DeploymentResponse(ref, self, rid, deadline_ts)
            except Exception as e:  # dead replica: drop + refresh
                last_err = e
                failed.add(rid)
                self._on_done(rid)
                if admit:
                    self._board.on_failure(rid)
                self._refresh(force=True)
        raise RuntimeError(
            f"could not assign request to {self.name}: {last_err}")


class DeploymentHandle:
    def __init__(self, deployment_name: str, method_name: str = "__call__",
                 stream: bool = False, multiplexed_model_id: str = "",
                 deadline_s: Optional[float] = None,
                 request_id: str = "",
                 trace_ctx: Optional[dict] = None):
        self.deployment_name = deployment_name
        self._method_name = method_name
        self._stream = stream
        self._multiplexed_model_id = multiplexed_model_id
        self._deadline_s = deadline_s
        self._request_id = request_id
        self._trace_ctx = trace_ctx
        self._router: Optional[Router] = None

    # Routers hold runtime state; rebuild lazily after pickling (handles are
    # injected into replica constructors for composition).
    def __getstate__(self):
        return {"deployment_name": self.deployment_name,
                "_method_name": self._method_name,
                "_stream": self._stream,
                "_multiplexed_model_id": self._multiplexed_model_id,
                "_deadline_s": self._deadline_s}

    def __setstate__(self, state):
        self.deployment_name = state["deployment_name"]
        self._method_name = state["_method_name"]
        self._stream = state.get("_stream", False)
        self._multiplexed_model_id = state.get("_multiplexed_model_id", "")
        self._deadline_s = state.get("_deadline_s")
        self._request_id = ""
        self._trace_ctx = None
        self._router = None

    def options(self, *, method_name: Optional[str] = None,
                stream: Optional[bool] = None,
                multiplexed_model_id: Optional[str] = None,
                deadline_s: Optional[float] = None,
                request_id: Optional[str] = None,
                trace_ctx: Optional[dict] = None) -> "DeploymentHandle":
        """``request_id`` names the trace this call roots (an ingress's
        stamped id); ``trace_ctx`` hands over an already-rooted trace
        (the proxies' own root span), making the proxy — not the response
        wrapper — the owner of the ledger record."""
        h = DeploymentHandle(
            self.deployment_name,
            method_name if method_name is not None else self._method_name,
            stream if stream is not None else self._stream,
            (multiplexed_model_id if multiplexed_model_id is not None
             else self._multiplexed_model_id),
            deadline_s if deadline_s is not None else self._deadline_s,
            request_id if request_id is not None else self._request_id,
            trace_ctx if trace_ctx is not None else self._trace_ctx,
        )
        h._router = self._ensure_router()
        return h

    @property
    def method(self):
        return self._method_name

    def _ensure_router(self) -> Router:
        if self._router is None:
            self._router = Router(self.deployment_name)
        return self._router

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        # Cache method-handles and share THIS handle's router: a fresh
        # router per attribute access would cold-RPC the controller on every
        # call and lose the in-flight counts pow-2 routing depends on.
        cache = self.__dict__.setdefault("_method_cache", {})
        h = cache.get(name)
        if h is None:
            h = DeploymentHandle(self.deployment_name, name)
            h._router = self._ensure_router()
            cache[name] = h
        return h

    def remote(self, *args, **kwargs):
        deadline_ts = (None if self._deadline_s is None
                       else time.time() + self._deadline_s)
        return self._ensure_router().assign(
            self._method_name, args, kwargs, stream=self._stream,
            multiplexed_model_id=self._multiplexed_model_id,
            deadline_ts=deadline_ts, request_id=self._request_id,
            trace_ctx=self._trace_ctx)
