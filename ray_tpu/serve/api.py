"""Public serve API: run/shutdown/get_handle + HTTP ingress.

Parity: reference serve/api.py (serve.run :545, serve.start, serve.delete,
serve.get_app_handle/get_deployment_handle). serve.run deploys an
Application graph: bound child nodes become DeploymentHandles injected into
parent constructors (deployment_graph_build.py), the controller reconciles
replicas, and (optionally) an HTTP proxy exposes the ingress deployment.
"""
from __future__ import annotations

import atexit
import logging
import time
from typing import Any, Dict, Optional

import cloudpickle

import ray_tpu

from .controller import CONTROLLER_NAME, ServeController
from .deployment import Application, Deployment
from .handle import DeploymentHandle
from .proxy import HTTPProxy

logger = logging.getLogger(__name__)

_proxy: Optional[HTTPProxy] = None
_grpc_proxy = None


def _get_controller_if_exists():
    """The running controller actor, or None — never creates one and never
    boots a cluster (read-only probes must stay side-effect free)."""
    if not ray_tpu.is_initialized():
        return None
    try:
        return ray_tpu.get_actor(CONTROLLER_NAME)
    except Exception:
        return None


def _get_or_create_controller():
    if not ray_tpu.is_initialized():
        ray_tpu.init(ignore_reinit_error=True)
    ctrl = _get_controller_if_exists()
    if ctrl is not None:
        return ctrl
    ctrl = ray_tpu.remote(ServeController).options(
        name=CONTROLLER_NAME, num_cpus=0.1, max_concurrency=8).remote()
    ray_tpu.get(ctrl.ping.remote())
    atexit.register(shutdown)
    return ctrl


def start(*, http_host: str = "127.0.0.1", http_port: int = 8000,
          detached: bool = False) -> None:
    """Start serve (controller + HTTP proxy) without deploying anything."""
    global _proxy
    _get_or_create_controller()
    if _proxy is None:
        _proxy = HTTPProxy(http_host, http_port)
        _proxy.start()


def run(target: Application, *, name: str = "default",
        route_prefix: Optional[str] = "/", blocking: bool = False,
        _http: bool = False, http_port: int = 8000,
        _grpc: bool = False, grpc_port: int = 9000) -> DeploymentHandle:
    """Deploy an application graph; returns a handle to the ingress
    deployment. `_http=True` also starts the HTTP proxy on http_port;
    `_grpc=True` starts the gRPC ingress (JSON-envelope generic service,
    grpc_proxy.py) on grpc_port."""
    if not isinstance(target, Application):
        raise TypeError("serve.run expects Deployment.bind(...)")
    ctrl = _get_or_create_controller()

    nodes = target._flatten()
    for node in nodes:
        dep = node.deployment
        # Replace bound child nodes with handles to their deployments.
        args = tuple(
            DeploymentHandle(a.deployment.name) if isinstance(a, Application)
            else a
            for a in node.args)
        kwargs = {
            k: (DeploymentHandle(v.deployment.name)
                if isinstance(v, Application) else v)
            for k, v in node.kwargs.items()}
        cfg = {
            "num_replicas": dep.config.num_replicas,
            "max_ongoing_requests": dep.config.max_ongoing_requests,
            "max_queued_requests": dep.config.max_queued_requests,
            "ray_actor_options": dep.config.ray_actor_options,
            "user_config": dep.config.user_config,
            "autoscaling_config": (
                vars(dep.config.autoscaling_config)
                if dep.config.autoscaling_config else None),
            "stream": dep.config.stream,
            "scaling_policy": dep.config.scaling_policy,
            "pool": dep.config.pool,
        }
        prefix = route_prefix if node is target else None
        ray_tpu.get(ctrl.deploy.remote(
            dep.name, cloudpickle.dumps(dep.func_or_class),
            args, kwargs, cfg, prefix))

    # Wait until every replica of the ingress deployment has finished its
    # constructor, so no request (and no request deadline) ever queues
    # behind model load or program warm-up. A deployment whose constructor
    # keeps failing raises with the real error, not a handle that can
    # never route.
    from ray_tpu import flags

    name = target.deployment.name
    ready_timeout = flags.get("RTPU_SERVE_READY_TIMEOUT_S")
    deadline = time.monotonic() + ready_timeout
    while True:
        p = ray_tpu.get(ctrl.get_start_progress.remote(name))
        if p["started"] >= p["target"]:
            break
        if p["start_failures"] >= 3 or time.monotonic() > deadline:
            raise RuntimeError(
                f"deployment {name!r} has {p['started']} of {p['target']} "
                f"replicas started after {p['start_failures']} failed "
                f"constructions and "
                f"{ready_timeout - (deadline - time.monotonic()):.0f}s; "
                f"last replica error: {p['last_error']}")
        time.sleep(0.1)
    if _http:
        start(http_port=http_port)
    if _grpc:
        global _grpc_proxy
        if _grpc_proxy is None:
            from .grpc_proxy import GRPCProxy

            _grpc_proxy = GRPCProxy(port=grpc_port)
            _grpc_proxy.start()
    handle = DeploymentHandle(target.deployment.name)
    if blocking:
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            pass
    return handle


def get_app_handle(name: str) -> DeploymentHandle:
    return DeploymentHandle(name)


get_deployment_handle = get_app_handle


def delete(name: str) -> None:
    ctrl = _get_or_create_controller()
    ray_tpu.get(ctrl.delete_deployment.remote(name))


def status() -> Optional[Dict[str, Any]]:
    """Read-only: never creates a controller or boots a cluster. Returns
    None when serve is not running (or no cluster is attached), {} when
    serve runs with zero deployments — callers can tell the two apart
    (reference `serve status` draws the same distinction)."""
    ctrl = _get_controller_if_exists()
    if ctrl is None:
        return None
    names = ray_tpu.get(ctrl.get_deployment_names.remote())
    out: Dict[str, Any] = {}
    for n in names:
        version, reps = ray_tpu.get(ctrl.get_replicas.remote(n))
        out[n] = {"version": version, "num_replicas": len(reps)}
    return out


def shutdown() -> None:
    global _proxy, _grpc_proxy
    if _proxy is not None:
        try:
            _proxy.stop()
        except Exception:
            pass
        _proxy = None
    if _grpc_proxy is not None:
        try:
            _grpc_proxy.stop()
        except Exception:
            pass
        _grpc_proxy = None
    ctrl = _get_controller_if_exists()
    if ctrl is None:
        return
    try:
        ray_tpu.get(ctrl.shutdown.remote(), timeout=15)
        ray_tpu.kill(ctrl)
    except Exception:
        pass
