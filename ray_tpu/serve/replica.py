"""ReplicaActor: hosts one copy of the user's deployment callable.

Parity: reference serve/_private/replica.py:231 (ReplicaActor,
UserCallableWrapper :737): constructs the user class (or wraps the
function), executes requests, tracks ongoing-request count for the
power-of-two router and the autoscaler, and exposes health checks +
user_config reconfiguration.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import cloudpickle


class ReplicaActor:
    def __init__(self, serialized_callable: bytes, init_args: Tuple,
                 init_kwargs: Dict, user_config: Optional[Dict] = None):
        func_or_class = cloudpickle.loads(serialized_callable)
        if isinstance(func_or_class, type):
            self._callable = func_or_class(*init_args, **init_kwargs)
            self._is_function = False
        else:
            self._callable = func_or_class
            self._is_function = True
        self._ongoing = 0
        self._total = 0
        self._lock = threading.Lock()
        if user_config is not None:
            self.reconfigure(user_config)

    # ---------------------------------------------------------------- serving

    @staticmethod
    def _check_deadline(deadline_ts, where: str):
        """Pre-execution expiry gate: an expired request is dropped with
        the typed error instead of burning replica capacity."""
        if deadline_ts is not None and time.time() > deadline_ts:
            from ray_tpu.core.controller import DeadlineExceededError

            raise DeadlineExceededError(
                f"request deadline passed {where}")

    def handle_request(self, method_name: str, args: Tuple, kwargs: Dict,
                       multiplexed_model_id: str = "",
                       deadline_ts: Optional[float] = None,
                       start_ts: Optional[float] = None,
                       queue_wait_s: float = 0.0,
                       trace_ctx: Optional[Dict] = None):
        from . import context as serve_context
        from . import trace
        from .multiplex import _set_model_id

        self._check_deadline(deadline_ts, "before replica execution")
        with self._lock:
            self._ongoing += 1
            self._total += 1
        token = _set_model_id(multiplexed_model_id)
        ctx_token = serve_context.set_request_context(
            deadline_ts=deadline_ts, start_ts=start_ts,
            queue_wait_s=queue_wait_s, trace_ctx=trace_ctx)
        # The replica hop measures execution on THIS host's clock; the
        # upstream queue accumulation it rode in on (router dwell + the
        # mailbox) is attached so the waterfall can attribute the gap
        # between the router's dispatch and this span's start.
        hop = trace.start_hop(
            "serve.replica", kind="replica",
            attributes={"method": method_name,
                        "queue_wait_s": round(queue_wait_s or 0.0, 6)})
        try:
            if self._is_function:
                return self._callable(*args, **kwargs)
            if method_name == "__call__":
                return self._callable(*args, **kwargs)
            return getattr(self._callable, method_name)(*args, **kwargs)
        except BaseException as e:
            if hop is not None:
                hop.end(error=type(e).__name__)
                hop = None
            raise
        finally:
            from .multiplex import _model_id_ctx

            if hop is not None:
                hop.end()
            serve_context.reset_request_context(ctx_token)
            _model_id_ctx.reset(token)
            with self._lock:
                self._ongoing -= 1

    def handle_request_streaming(self, method_name: str, args: Tuple,
                                 kwargs: Dict,
                                 multiplexed_model_id: str = "",
                                 deadline_ts: Optional[float] = None,
                                 start_ts: Optional[float] = None,
                                 queue_wait_s: float = 0.0,
                                 trace_ctx: Optional[Dict] = None):
        """Generator variant: the user handler returns a generator/iterable
        whose items stream to the caller one object at a time (reference:
        serve streaming responses over streaming generator returns,
        serve/_private/replica.py handle_request_streaming)."""
        from . import context as serve_context
        from . import trace
        from .multiplex import _set_model_id

        self._check_deadline(deadline_ts, "before replica execution")
        with self._lock:
            self._ongoing += 1
            self._total += 1
        _set_model_id(multiplexed_model_id)
        ctx_token = serve_context.set_request_context(
            deadline_ts=deadline_ts, start_ts=start_ts,
            queue_wait_s=queue_wait_s, trace_ctx=trace_ctx)
        # Covers the stream's whole replica-side life: opened before the
        # user generator starts, ended when it exhausts or the consumer
        # walks away (GeneratorExit lands in the finally).
        hop = trace.start_hop(
            "serve.replica", kind="replica",
            attributes={"method": method_name, "stream": True,
                        "queue_wait_s": round(queue_wait_s or 0.0, 6)})
        items = 0
        try:
            if self._is_function:
                result = self._callable(*args, **kwargs)
            elif method_name == "__call__":
                result = self._callable(*args, **kwargs)
            else:
                result = getattr(self._callable, method_name)(*args, **kwargs)
            for item in result:
                items += 1
                yield item
        except BaseException as e:
            if hop is not None:
                hop.end(error=type(e).__name__, items=items)
                hop = None
            raise
        finally:
            if hop is not None:
                hop.end(items=items)
            serve_context.reset_request_context(ctx_token)
            with self._lock:
                self._ongoing -= 1

    # ----------------------------------------------------------------- state

    def queue_len(self) -> int:
        return self._ongoing

    def stats(self) -> Dict[str, Any]:
        """Replica load snapshot. When the user callable exposes a
        ``serve_stats()`` protocol (the LLM engine deployments do: slot
        occupancy, blocked submitters, prefix-cache hit rates), its dict
        is merged in under ``serve`` — the controller's signal poll and
        the autoscaler read it from here."""
        out: Dict[str, Any] = {"ongoing": self._ongoing,
                               "total": self._total}
        fn = getattr(self._callable, "serve_stats", None)
        if callable(fn):
            try:
                out["serve"] = fn()
            except Exception:
                pass
        return out

    def profile(self, seconds: float, logdir: str) -> Dict[str, Any]:
        """Run jax.profiler for ``seconds`` in this process, the one that
        owns the replica's chips (`rtpu serve profile`; the train-worker
        analog is session.profile). Requests keep being served meanwhile;
        the trace holds the device ops beside the host phases of
        util/tracing.py (engine.tick, stream.report, ...) and one
        clock_anchor. Returns the xplane files written under ``logdir``."""
        import glob
        import os

        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # the phases are the host's story
        opts.host_tracer_level = 2
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            time.sleep(max(0.0, float(seconds)))
        finally:
            jax.profiler.stop_trace()
        return {"pid": os.getpid(), "logdir": logdir,
                "seconds": float(seconds),
                "xplane": sorted(glob.glob(os.path.join(
                    logdir, "**", "*.xplane.pb"), recursive=True))}

    def check_health(self) -> bool:
        user_check = getattr(self._callable, "check_health", None)
        if callable(user_check):
            user_check()
        return True

    def reconfigure(self, user_config: Dict) -> None:
        fn = getattr(self._callable, "reconfigure", None)
        if callable(fn):
            fn(user_config)

    def prepare_shutdown(self) -> None:
        fn = getattr(self._callable, "__del__", None)
        if callable(fn):
            try:
                fn()
            except Exception:
                pass
