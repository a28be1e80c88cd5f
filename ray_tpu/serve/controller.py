"""ServeController: the control-plane actor.

Parity: reference serve/_private/controller.py:86 (ServeController) +
deployment_state.py:1226 (DeploymentState reconciliation): holds target
state per deployment, reconciles actual replica actors toward it, restarts
dead replicas, runs queue-metric autoscaling
(autoscaling_state.py:82 / replica_queue_length_autoscaling_policy), and
answers routing queries (replica handle lists, versioned so routers can
long-poll-style refresh cheaply).
"""
from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import cloudpickle

import ray_tpu
from ray_tpu import flags

from .autoscaler import ServeAutoscaler
from .prefix_cache import PrefixIndex
from .replica import ReplicaActor

logger = logging.getLogger(__name__)

CONTROLLER_NAME = "SERVE_CONTROLLER"

_plane_metrics_cache = None


def _plane_metrics():
    """Controller-exported serve-plane gauges: the autoscaler's inputs and
    `rtpu top`'s SERVE section read these off the shared metrics plane."""
    global _plane_metrics_cache
    if _plane_metrics_cache is None:
        from ray_tpu.util.metrics import Gauge

        _plane_metrics_cache = {
            "queue": Gauge(
                "rtpu_serve_queue_depth",
                description="Requests queued for a generation slot across "
                            "a deployment's replicas (serve controller "
                            "stats poll)",
                tag_keys=("model",)),
            "replicas": Gauge(
                "rtpu_serve_replicas",
                description="Live replica count per serve deployment "
                            "(pool label: prefill | decode | main)",
                tag_keys=("deployment", "pool")),
            "occupancy": Gauge(
                "rtpu_serve_slot_occupancy",
                description="Continuous-batching slot occupancy in [0,1] "
                            "across a deployment's replicas",
                tag_keys=("model",)),
        }
    return _plane_metrics_cache


class _DeploymentInfo:
    def __init__(self, name: str, serialized_callable: bytes, init_args,
                 init_kwargs, config: Dict[str, Any]):
        self.name = name
        self.serialized_callable = serialized_callable
        self.init_args = init_args
        self.init_kwargs = init_kwargs
        self.config = config
        self.target_replicas: int = config["num_replicas"]
        self.replicas: List[Any] = []  # ActorHandles
        # Scale-down victims mid-drain: (handle, drain_start_ts). Out of
        # the routed set (version bump) but alive until idle or the drain
        # deadline — in-flight streams finish across a resize.
        self.draining: List[Tuple[Any, float]] = []
        # Replicas whose constructor is still running: actor id ->
        # monotonic creation time.
        self.starting: Dict[str, float] = {}
        # Constructor failures in a row; serve.run() gives up at 3.
        self.start_failures = 0
        self.version = 0
        self.last_error: Optional[str] = None
        # Latest aggregated serving signals from the stats poll.
        self.signals: Dict[str, float] = {}
        # autoscaling bookkeeping: when the metric FIRST crossed the
        # threshold (None = currently below it) — delays require sustained
        # load, not merely time-since-last-event.
        self.above_since: Optional[float] = None
        self.below_since: Optional[float] = None


class ServeController:
    def __init__(self):
        self._deployments: Dict[str, _DeploymentInfo] = {}
        self._route_prefixes: Dict[str, str] = {}  # prefix -> deployment
        self._lock = threading.Lock()
        self._stop = threading.Event()
        # Signal-driven pool scaling + cluster prefix index (per
        # deployment), both fed by the per-tick replica stats poll.
        self._autoscaler = ServeAutoscaler()
        self._prefix_index: Dict[str, PrefixIndex] = {}
        self._loop = threading.Thread(target=self._control_loop, daemon=True)
        self._loop.start()

    # ------------------------------------------------------------ deploy API

    def deploy(self, name: str, serialized_callable: bytes, init_args,
               init_kwargs, config: Dict[str, Any],
               route_prefix: Optional[str] = None) -> None:
        with self._lock:
            info = self._deployments.get(name)
            if info is None:
                info = _DeploymentInfo(name, serialized_callable, init_args,
                                       init_kwargs, config)
                self._deployments[name] = info
            else:
                info.serialized_callable = serialized_callable
                info.init_args = init_args
                info.init_kwargs = init_kwargs
                info.config = config
                info.target_replicas = config["num_replicas"]
                # In-place redeploy: drop old replicas; reconcile restarts.
                for r in info.replicas:
                    self._kill_replica(r)
                info.replicas = []
                info.starting = {}
                info.version += 1
                self._publish_update(name, info.version)
            if route_prefix:
                self._route_prefixes[route_prefix] = name
            self._autoscaler.configure(name, config.get("scaling_policy"))
        self._reconcile()

    def _publish_update(self, name: str, version: int) -> None:
        """Push-based config propagation (reference: serve LongPollHost
        notifying handles on replica-set changes, long_poll.py:173) — the
        core pubsub replaces per-call version polling in routers."""
        try:
            from ray_tpu.core import context as ctx

            ctx.get_worker_context().client.request(
                {"kind": "publish", "channel": "serve_updates",
                 "data": {"name": name, "version": version}})
        except Exception:
            pass  # routers still have the periodic refresh as backstop

    def delete_deployment(self, name: str) -> None:
        with self._lock:
            info = self._deployments.pop(name, None)
            self._route_prefixes = {
                p: d for p, d in self._route_prefixes.items() if d != name}
            self._autoscaler.forget(name)
            self._prefix_index.pop(name, None)
        if info:
            for r in info.replicas:
                self._kill_replica(r)
            for r, _ in info.draining:
                self._kill_replica(r)

    def shutdown(self) -> None:
        self._stop.set()
        with self._lock:
            names = list(self._deployments)
        for n in names:
            self.delete_deployment(n)

    # -------------------------------------------------------------- routing

    def get_replicas(self, name: str) -> Tuple[int, List[Any]]:
        """(version, replica handles) — routers cache until version bumps."""
        info = self._deployments.get(name)
        if info is None:
            raise KeyError(f"no deployment {name!r}")
        return info.version, list(info.replicas)

    def get_routing_config(self, name: str) -> Dict[str, Any]:
        """Admission-relevant config subset, fetched by routers alongside
        the replica list: replica concurrency bound + queued-request bound
        (None max_queued_requests defers to the RTPU_SERVE_MAX_QUEUED
        flag default; -1 means unbounded)."""
        info = self._deployments.get(name)
        if info is None:
            raise KeyError(f"no deployment {name!r}")
        out = {
            "max_ongoing_requests": int(
                info.config.get("max_ongoing_requests", 16) or 16),
            "max_queued_requests": info.config.get("max_queued_requests"),
        }
        idx = self._prefix_index.get(name)
        if idx is not None:
            # Hot-prefix steering table: hash -> holder replica ids, so
            # routers send a request where its K/V already lives.
            out["prefix_routes"] = idx.routes()
        return out

    def get_deployment_names(self) -> List[str]:
        return list(self._deployments)

    def get_route_table(self) -> Dict[str, str]:
        return dict(self._route_prefixes)

    def get_route_info(self) -> Dict[str, Dict[str, Any]]:
        """Route table with per-deployment metadata the proxy needs (stream
        flag for chunked responses)."""
        out: Dict[str, Dict[str, Any]] = {}
        for prefix, name in self._route_prefixes.items():
            info = self._deployments.get(name)
            out[prefix] = {
                "name": name,
                "stream": bool(info and info.config.get("stream")),
            }
        return out

    def get_last_error(self, name: str) -> Optional[str]:
        info = self._deployments.get(name)
        return info.last_error if info else None

    def get_start_progress(self, name: str) -> Dict[str, Any]:
        """What serve.run() waits on: replicas whose constructor finished
        vs the target, and constructor failures in a row."""
        info = self._deployments.get(name)
        if info is None:
            raise KeyError(f"no deployment {name!r}")
        with self._lock:
            started = sum(r._actor_id not in info.starting
                          for r in info.replicas)
            return {"started": started, "target": info.target_replicas,
                    "start_failures": info.start_failures,
                    "last_error": info.last_error}

    # ---------------------------------------------------------- reconcile

    def _make_replica(self, info: _DeploymentInfo):
        opts = dict(info.config.get("ray_actor_options") or {})
        opts.setdefault("num_cpus", 0.1)
        # Replicas serve concurrently up to max_ongoing_requests (mailbox
        # thread pool) — required for @serve.batch to ever see a batch.
        opts.setdefault("max_concurrency",
                        info.config.get("max_ongoing_requests", 16))
        cls = ray_tpu.remote(ReplicaActor).options(**opts)
        handle = cls.remote(info.serialized_callable, info.init_args,
                            info.init_kwargs, info.config.get("user_config"))
        info.starting[handle._actor_id] = time.monotonic()
        return handle

    def _poll_starting(self, info: _DeploymentInfo, r) -> bool:
        """True while replica ``r`` is constructing or once it has started;
        False (with info.last_error set) when its constructor failed or
        outlived RTPU_SERVE_READY_TIMEOUT_S.

        A starting replica is not held to the 30s health window: process
        start, accelerator runtime start, model load and program warm-up
        are start-up work, and a replica killed in the middle of them can
        never become ready."""
        from ray_tpu.core import context as ctx

        born = info.starting.get(r._actor_id)
        if born is None:  # a concurrent pass already saw it start
            return True
        state = ctx.get_worker_context().client.request(
            {"kind": "resolve_actor", "actor_id": r._actor_id,
             "wait": 0})["state"]
        if state == "alive":  # constructor returned
            info.starting.pop(r._actor_id, None)
            info.start_failures = 0
            return True
        limit = flags.get("RTPU_SERVE_READY_TIMEOUT_S")
        if state == "pending":
            if time.monotonic() - born <= limit:
                return True
            info.last_error = (f"replica still constructing after "
                               f"{limit:g}s (RTPU_SERVE_READY_TIMEOUT_S)")
        else:  # the constructor raised or its worker died: ask it why
            try:
                ray_tpu.get(r.check_health.remote(), timeout=5.0)
                info.last_error = f"replica actor is {state}"
            except Exception as e:
                info.last_error = repr(e)
        info.starting.pop(r._actor_id, None)
        info.start_failures += 1
        return False

    def _kill_replica(self, handle) -> None:
        try:
            ray_tpu.get(handle.prepare_shutdown.remote(), timeout=2.0)
        except Exception:
            pass
        try:
            ray_tpu.kill(handle)
        except Exception:
            pass

    def _reconcile(self) -> None:
        # Snapshot under _lock, health-check OUTSIDE it (hung replicas cost
        # up to the 30s health window; holding the lock through that would
        # stall every deploy/delete), then re-acquire and commit only if the
        # deployment wasn't concurrently redeployed — otherwise a stale pass
        # could resurrect just-killed old-version replicas.
        with self._lock:
            snapshot = [(info, list(info.replicas)) for info in
                        self._deployments.values()]
        # ONE deadline for the whole pass (probes are fired concurrently
        # per deployment): hung replicas across many deployments must not
        # stack 30s each before replacements start.
        deadline = time.monotonic() + 30.0
        for info, replicas in snapshot:
            alive = []
            dead = []
            # Fire every probe first, then gather against the shared pass
            # deadline (30s — the reference serve default,
            # health_check_timeout_s=30: a replica blocking its loop on a
            # long model compile/load must not read as dead). Serial waits
            # would stall a pass 30s PER hung replica.
            probes = []
            for r in replicas:
                try:
                    if r._actor_id in info.starting:
                        (alive if self._poll_starting(info, r)
                         else dead).append(r)
                    else:
                        probes.append((r, r.check_health.remote()))
                except Exception as e:
                    info.last_error = repr(e)
                    dead.append(r)
            for r, ref in probes:
                try:
                    ray_tpu.get(ref, timeout=max(
                        0.5, deadline - time.monotonic()))
                    alive.append(r)
                except Exception as e:
                    logger.warning("replica of %s failed health check",
                                   info.name)
                    info.last_error = repr(e)
                    dead.append(r)
            with self._lock:
                if (self._deployments.get(info.name) is not info
                        or info.replicas != replicas):
                    continue  # redeployed/deleted meanwhile: skip this pass
                for r in dead:
                    try:
                        ray_tpu.kill(r)
                    except Exception:
                        pass
                changed = len(alive) != len(replicas)
                while len(alive) < info.target_replicas:
                    alive.append(self._make_replica(info))
                    changed = True
                now = time.time()
                while len(alive) > info.target_replicas:
                    # Scale-down DRAINS instead of killing: the victim
                    # leaves the routed set on this version bump (routers
                    # stop picking it) and _reap_draining() kills it only
                    # once idle or past RTPU_SERVE_DRAIN_DEADLINE_S — a
                    # resize never cuts an in-flight stream.
                    info.draining.append((alive.pop(), now))
                    changed = True
                if changed:
                    info.replicas = alive
                    info.version += 1
                    self._publish_update(info.name, info.version)
                    live = {r._actor_id for r in alive}
                    info.starting = {a: v for a, v in info.starting.items()
                                     if a in live}

    def _reap_draining(self) -> None:
        """Kill draining replicas that went idle (or overstayed the drain
        deadline). Probes run OUTSIDE the lock — a hung drain victim must
        not stall deploys."""
        with self._lock:
            snapshot = [(info, list(info.draining))
                        for info in self._deployments.values()
                        if info.draining]
        if not snapshot:
            return
        grace = flags.get("RTPU_SERVE_DRAIN_DEADLINE_S")
        now = time.time()
        for info, entries in snapshot:
            reaped = []
            for r, ts in entries:
                kill = now - ts >= grace
                if not kill:
                    try:
                        kill = ray_tpu.get(r.queue_len.remote(),
                                           timeout=2.0) == 0
                    except (ray_tpu.ActorDiedError,
                            ray_tpu.WorkerCrashedError):
                        kill = True  # actually dead: nothing to drain
                    except Exception:
                        # Probe timed out / transient failure: a LIVE
                        # replica can be briefly unresponsive (JIT
                        # compile holding the GIL, busy engine tick).
                        # Killing it now would cut in-flight streams —
                        # keep draining; the grace deadline decides.
                        kill = False
                if kill:
                    self._kill_replica(r)
                    reaped.append(r)
            if reaped:
                with self._lock:
                    info.draining = [(r, ts) for r, ts in info.draining
                                     if r not in reaped]

    # ------------------------------------------------------- signal plane

    def _poll_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-deployment serving signals from replica stats(): queue
        depth (blocked submitters), slot occupancy, prefix-cache holdings
        (folded into the cluster PrefixIndex). Also exports the
        controller-side gauges the autoscaler and `rtpu top` read."""
        with self._lock:
            snapshot = [(info, list(info.replicas))
                        for info in self._deployments.values()]
        signals: Dict[str, Dict[str, float]] = {}
        try:
            m = _plane_metrics()
        except Exception:
            m = None
        for info, replicas in snapshot:
            refs = []
            for r in replicas:
                try:
                    refs.append((r, r.stats.remote()))
                except Exception:
                    pass
            deadline = time.monotonic() + 2.0
            polled = []
            saturated = 0
            for r, ref in refs:
                try:
                    polled.append((r._actor_id, ray_tpu.get(
                        ref, timeout=max(0.1,
                                         deadline - time.monotonic()))))
                except ray_tpu.GetTimeoutError:
                    # The replica is alive but its mailbox is so full the
                    # stats probe couldn't get a thread — which IS the
                    # overload signal. Count it as fully busy with a
                    # waiting queue rather than dropping it, or the
                    # autoscaler would read peak saturation as idle.
                    saturated += 1
                except Exception:
                    pass
            queue = float(saturated)
            busy = total = float(saturated)
            idx = self._prefix_index.get(info.name)
            for rid, s in polled:
                serve = (s or {}).get("serve") or {}
                queue += float(serve.get("queued", 0.0))
                if serve.get("slots_total"):
                    busy += float(serve.get("slots_busy", 0.0))
                    total += float(serve["slots_total"])
                pref = serve.get("prefix")
                if pref:
                    if idx is None:
                        idx = PrefixIndex()
                        self._prefix_index[info.name] = idx
                    idx.update_replica(rid, pref.get("holders") or [],
                                       pref.get("hot") or {})
            if idx is not None:
                live = {r._actor_id for r in replicas}
                for rid in idx.replica_ids():
                    if rid not in live:
                        idx.drop_replica(rid)
            sig = {"queue_depth": queue,
                   "occupancy": (busy / total) if total else 0.0}
            ttft = self._ttft_p99(info.name)
            if ttft is not None:
                sig["ttft_p99_s"] = ttft
            info.signals = sig
            signals[info.name] = sig
            if m is not None:
                pool = info.config.get("pool") or "main"
                try:
                    m["queue"].set(queue, tags={"model": info.name})
                    m["replicas"].set(float(len(replicas)),
                                      tags={"deployment": info.name,
                                            "pool": pool})
                    if total:
                        m["occupancy"].set(sig["occupancy"],
                                           tags={"model": info.name})
                except Exception:
                    pass
        return signals

    def _ttft_p99(self, name: str) -> Optional[float]:
        """Latest per-model TTFT p99 from the telemetry plane — only
        fetched when the deployment's policy actually triggers on it
        (telemetry may be disabled; the signal is best-effort)."""
        p = self._autoscaler.policy(name)
        if p is None or p.ttft_p99_high_s <= 0:
            return None
        try:
            from ray_tpu.util import state as util_state

            res = util_state.query_metrics(
                name="rtpu_serve_ttft_s", tags={"model": name},
                stat="p99", window_s=30.0)
            for ser in (res or {}).get("series") or []:
                pts = ser.get("points") or []
                if pts:
                    return float(pts[-1][1])
        except Exception:
            pass
        return None

    def _autoscale_signals(self, now: float,
                           signals: Dict[str, Dict[str, float]]) -> None:
        """Apply the signal-driven autoscaler's ±1 steps (clamped to the
        policy's replica range); reconcile realizes them — up through the
        deployment path, down through the drain path."""
        deltas = self._autoscaler.step(now, signals)
        if not deltas:
            return
        with self._lock:
            for name, d in deltas.items():
                info = self._deployments.get(name)
                p = self._autoscaler.policy(name)
                if info is None or p is None:
                    continue
                new = max(p.min_replicas,
                          min(p.max_replicas, info.target_replicas + d))
                if new != info.target_replicas:
                    logger.info("serve autoscaler: %s %d -> %d replicas",
                                name, info.target_replicas, new)
                    info.target_replicas = new

    def _promote_prefixes(self) -> None:
        """Broadcast cluster-hot prefixes: replicas missing one pull the
        blob straight from a holder replica (fire-and-forget; bytes move
        worker<->worker, never through the controller)."""
        if not flags.get("RTPU_PREFIX_CACHE"):
            return
        with self._lock:
            snapshot = [(info, list(info.replicas))
                        for info in self._deployments.values()]
        for info, replicas in snapshot:
            idx = self._prefix_index.get(info.name)
            if idx is None or len(replicas) < 2:
                continue
            by_rid = {r._actor_id: r for r in replicas}
            for h, holder_rid, target_rid in idx.promotions(list(by_rid)):
                holder = by_rid.get(holder_rid)
                target = by_rid.get(target_rid)
                if holder is None or target is None:
                    continue
                try:
                    target.handle_request.remote(
                        "pull_prefix", (h, holder), {})
                except Exception:
                    pass

    def get_serve_stats(self) -> Dict[str, Any]:
        """Per-deployment serving snapshot for `rtpu top` / dashboards:
        replica counts (live/target/draining), pool label, and the latest
        polled signals."""
        out: Dict[str, Any] = {}
        with self._lock:
            for name, info in self._deployments.items():
                d = {"replicas": len(info.replicas),
                     "target": info.target_replicas,
                     "draining": len(info.draining),
                     "pool": info.config.get("pool") or "main"}
                d.update(info.signals or {})
                out[name] = d
        return out

    # --------------------------------------------------------- autoscaling

    def _autoscale(self) -> None:
        # Metric: per-replica EXECUTING requests (queue_len). Backlog queued
        # in the actor mailbox beyond max_concurrency is not visible; it
        # surfaces as sustained max-concurrency execution, which still
        # drives upscale.
        now = time.time()
        with self._lock:
            infos = list(self._deployments.values())
        for info in infos:
            ac = info.config.get("autoscaling_config")
            if not ac:
                continue
            ongoing = 0
            for r in list(info.replicas):
                try:
                    ongoing += ray_tpu.get(r.queue_len.remote(), timeout=5.0)
                except Exception:
                    pass
            n = max(1, len(info.replicas))
            per = ongoing / n
            target = info.target_replicas
            if per > ac["target_ongoing_requests"]:
                info.below_since = None
                if info.above_since is None:
                    info.above_since = now
                if now - info.above_since >= ac["upscale_delay_s"]:
                    target = min(ac["max_replicas"],
                                 info.target_replicas + 1)
                    info.above_since = now  # next step needs a fresh window
            elif per < ac["target_ongoing_requests"] * 0.5:
                info.above_since = None
                if info.below_since is None:
                    info.below_since = now
                if now - info.below_since >= ac["downscale_delay_s"]:
                    target = max(ac["min_replicas"],
                                 info.target_replicas - 1)
                    info.below_since = now
            else:
                info.above_since = None
                info.below_since = None
            info.target_replicas = target

    # ------------------------------------------------------------ the loop

    def _control_loop(self) -> None:
        while not self._stop.is_set():
            try:
                now = time.time()
                signals = self._poll_stats()
                self._autoscale()
                self._autoscale_signals(now, signals)
                self._reconcile()
                self._reap_draining()
                self._promote_prefixes()
            except Exception:
                logger.exception("serve control loop error")
            self._stop.wait(1.0)

    def ping(self) -> str:
        return "pong"
