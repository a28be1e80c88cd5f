"""Task-span tracing with OpenTelemetry-compatible context propagation.

Parity: reference python/ray/util/tracing/tracing_helper.py — the
submitter's active trace context is injected into every task/actor-call
spec and the executing worker opens a child span around the user function,
so one trace follows a request across processes and nodes.

The wire format is W3C ``traceparent`` (the OTel default propagator), and
when the ``opentelemetry-sdk`` package is importable ``setup_tracing``
registers a real TracerProvider and spans flow through the user's
exporters. This image ships only ``opentelemetry-api`` (no-op tracers that
cannot carry context), so a built-in tracer provides the same surface:
thread-local current-span context, child spans, per-process finished-span
records queryable via ``get_finished_spans()`` — and, with the flight
recorder on (``RTPU_TASK_EVENTS``), cluster-wide via
``get_cluster_spans()``: workers ship their finished spans to the
controller alongside task phase events.

Everything above is gated on ``RTPU_TRACING`` (set by ``setup_tracing``;
worker processes inherit it through the spawn env): when off, submission
pays one flag check and nothing else.

Host phases (``phase`` / ``observe`` / ``steps``, always on) are the other
half: named stretches of host work stamped on CLOCK_MONOTONIC, folded into a
per-process table and, in a process that has imported jax, emitted as
``jax.profiler.TraceAnnotation`` so a profiler session shows them beside the
device ops. See the section at the end of this module.
"""
from __future__ import annotations

import collections
import os
import secrets
import sys
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ray_tpu import flags

_local = threading.local()
_finished: List["Span"] = []
_finished_lock = threading.Lock()
_otel_sdk = None  # resolved once by setup_tracing


def enabled() -> bool:
    return bool(flags.get("RTPU_TRACING"))


@dataclass
class SpanContext:
    trace_id: str  # 32 hex chars
    span_id: str   # 16 hex chars

    @property
    def is_valid(self) -> bool:
        return bool(int(self.trace_id, 16))

    def to_traceparent(self) -> str:
        return f"00-{self.trace_id}-{self.span_id}-01"

    @classmethod
    def from_traceparent(cls, tp: str) -> Optional["SpanContext"]:
        parts = tp.split("-")
        if len(parts) != 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
            return None
        return cls(trace_id=parts[1], span_id=parts[2])


@dataclass
class Span:
    name: str
    context: SpanContext
    parent_span_id: str = ""
    kind: str = "internal"
    attributes: Dict[str, Any] = field(default_factory=dict)
    start_time: float = field(default_factory=time.time)
    end_time: float = 0.0

    def end(self) -> None:
        self.end_time = time.time()
        with _finished_lock:
            _finished.append(Span(**{f: getattr(self, f) for f in (
                "name", "context", "parent_span_id", "kind", "attributes",
                "start_time", "end_time")}))
            del _finished[:-4096]  # bounded per-process record


def current_span_context() -> Optional[SpanContext]:
    return getattr(_local, "ctx", None)


def current_trace_id() -> str:
    ctx = current_span_context()
    return ctx.trace_id if ctx is not None else ""


def get_finished_spans() -> List[Span]:
    with _finished_lock:
        return list(_finished)


def drain_finished_spans() -> List[Span]:
    """Pop (and clear) this process's finished-span records. Used by the
    worker flight recorder (core/task_events.py) to ship spans to the
    controller's cluster-wide collection — after a drain,
    ``get_finished_spans()`` in THIS process no longer returns them."""
    with _finished_lock:
        spans, _finished[:] = list(_finished), []
    return spans


def span_to_dict(s: Span) -> Dict[str, Any]:
    """Wire/JSON form of a span (what get_cluster_spans returns)."""
    return {
        "name": s.name,
        "trace_id": s.context.trace_id,
        "span_id": s.context.span_id,
        "parent_span_id": s.parent_span_id,
        "kind": s.kind,
        "attributes": dict(s.attributes),
        "start_time": s.start_time,
        "end_time": s.end_time,
    }


def get_cluster_spans(trace_id: Optional[str] = None,
                      timeout: float = 10.0) -> List[Dict[str, Any]]:
    """Cluster-wide finished spans, as dicts sorted by start time.

    Merges this process's records (e.g. the driver's PRODUCER submit
    spans, which are never shipped) with the controller's collection of
    spans shipped by every worker's flight recorder (CONSUMER run spans) —
    so one trace_id yields the submitter AND executor sides of a task even
    though they finished in different processes. Filter with ``trace_id``;
    without a live session only local spans are returned.
    """
    from ray_tpu.core import context as ctx

    by_id: Dict[str, Dict[str, Any]] = {
        d["span_id"]: d for d in (span_to_dict(s)
                                  for s in get_finished_spans())}
    if ctx.is_initialized():
        try:
            for d in ctx.get_worker_context().client.request(
                    {"kind": "get_spans", "trace_id": trace_id},
                    timeout=timeout):
                by_id.setdefault(d["span_id"], d)
        except Exception:
            pass  # controller unreachable: local records still answer
    spans = list(by_id.values())
    if trace_id:
        spans = [d for d in spans if d["trace_id"] == trace_id]
    spans.sort(key=lambda d: d["start_time"])
    return spans


class _SpanScope:
    """start span -> set thread-local context -> restore + record."""

    def __init__(self, name: str, kind: str,
                 attributes: Optional[Dict[str, Any]] = None,
                 parent: Optional[SpanContext] = None):
        self.name = name
        self.kind = kind
        self.attributes = dict(attributes or {})
        self.parent = parent
        self.span: Optional[Span] = None
        self._prev: Optional[SpanContext] = None

    def __enter__(self) -> Span:
        parent = self.parent or current_span_context()
        trace_id = parent.trace_id if parent else secrets.token_hex(16)
        ctx = SpanContext(trace_id=trace_id, span_id=secrets.token_hex(8))
        self.span = Span(name=self.name, context=ctx, kind=self.kind,
                         parent_span_id=parent.span_id if parent else "",
                         attributes=self.attributes)
        self._prev = current_span_context()
        _local.ctx = ctx
        return self.span

    def detach_context(self) -> None:
        """Restore THIS thread's current-span slot without ending the span
        — for ownership transfers to another thread/loop (async actor
        methods): the origin thread must not leak the context into its
        next task while the span stays open to record the real duration."""
        _local.ctx = self._prev
        self._prev = None

    def __exit__(self, et, ev, tb):
        if getattr(_local, "ctx", None) is (
                self.span.context if self.span else None):
            _local.ctx = self._prev
        if self.span is not None:
            if et is not None:
                self.span.attributes["error"] = repr(ev)
            self.span.end()
        return False


def start_span(name: str, kind: str = "internal",
               attributes: Optional[Dict[str, Any]] = None) -> _SpanScope:
    """Application-facing span context manager (the reference exposes the
    raw OTel API; this is the built-in analog that also feeds it)."""
    return _SpanScope(name, kind, attributes)


def setup_tracing(span_processor: Optional[Any] = None) -> None:
    """Enable tracing for this session (workers inherit via env).

    With ``opentelemetry-sdk`` importable, a TracerProvider is installed
    (if the global one is still the no-op default) and ``span_processor``
    registered — real OTel spans flow alongside the built-in records. With
    api-only installs the built-in tracer carries everything."""
    global _otel_sdk
    try:
        from opentelemetry import trace as otel_trace
        from opentelemetry.sdk.trace import TracerProvider

        provider = otel_trace.get_tracer_provider()
        if not isinstance(provider, TracerProvider):
            provider = TracerProvider()
            otel_trace.set_tracer_provider(provider)
        if span_processor is not None:
            provider.add_span_processor(span_processor)
        _otel_sdk = otel_trace
    except ImportError:
        _otel_sdk = None  # api-only image: built-in tracer carries spans
    flags.set_env("RTPU_TRACING", "1")


def inject_submit_span(spec: Dict[str, Any], label: str) -> None:
    """Submitter side: record a PRODUCER span for the submission and carry
    its context in the spec as a W3C traceparent (reference:
    _inject_tracing_into_function + the .remote() wrapper span)."""
    if not enabled():
        return
    try:
        with _SpanScope(f"submit {label}", "producer",
                        {"rtpu.task_id": spec.get("task_id", ""),
                         "rtpu.label": label}) as span:
            spec["trace_ctx"] = {
                "traceparent": span.context.to_traceparent()}
    except Exception:
        pass  # tracing must never break submission


class task_span:
    """Worker side: CONSUMER span around the user function, child of the
    submitter's context extracted from the spec."""

    def __init__(self, spec: Dict[str, Any]):
        self._spec = spec
        self._scope: Optional[_SpanScope] = None

    def __enter__(self):
        tp = (self._spec.get("trace_ctx") or {}).get("traceparent", "")
        if not enabled() or not tp:
            return None
        try:
            parent = SpanContext.from_traceparent(tp)
            label = (self._spec.get("label")
                     or self._spec.get("method_name", "task"))
            self._scope = _SpanScope(
                f"run {label}", "consumer",
                {"rtpu.task_id": self._spec.get("task_id", ""),
                 "rtpu.actor_id": self._spec.get("actor_id") or ""},
                parent=parent)
            return self._scope.__enter__()
        except Exception:
            self._scope = None
            return None

    def detach_context(self) -> None:
        if self._scope is not None:
            try:
                self._scope.detach_context()
            except Exception:
                pass

    def __exit__(self, et, ev, tb):
        if self._scope is not None:
            try:
                self._scope.__exit__(et, ev, tb)
            except Exception:
                pass
        return False


# ---------------------------------------------------------------- host phases
# One primitive for "what was the host doing": the engine loop, the token
# relay, the controller's handlers and periodic bodies, the train step's host
# side. No flag and no shipping: a phase costs two clock reads, a TraceMe
# (a no-op outside a profiler session) and one table update.

SLOW_NS = 50_000_000      # phases at least this long go to the slow ring
SLOW_RING = 1024          # entries kept
_N_BUCKETS = 40           # log2(ns) buckets: bucket b holds [2^(b-1), 2^b)
_SLOW_EVENTS_PER_10S = 32  # cluster events a process may emit for slow phases

_phase_lock = threading.Lock()   # guards the registry below, not the folding
_phase_local = threading.local()  # .table: this thread's name -> row
_tables: List[tuple] = []         # (weakref to thread, its table), live threads
_retired: Dict[str, list] = {}    # rows of threads that have ended
_slow: "collections.deque" = collections.deque(maxlen=SLOW_RING)
_anchored = False         # a clock_anchor was emitted in the current session
# tokens, refilled at, events the limit has dropped since the last one sent
_slow_event_budget = [float(_SLOW_EVENTS_PER_10S), 0.0, 0]
_UNSENT_MAX = 64
# Slow phases from before this process had a worker context (a worker's
# boot): held until send_unsent(), which sets this to None.
_unsent: Optional[List[tuple]] = []


def _annotation():
    """jax.profiler.TraceAnnotation if this process has imported jax, else
    None. Never imports it: a runner, controller or proxy process must stay
    without a JAX backend."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        return jax.profiler.TraceAnnotation
    except AttributeError:  # jax is mid-import on another thread
        return None


def _anchor(ann) -> bool:
    """Whether a profiler session is on; once per session it gets an
    annotation carrying this instant on CLOCK_MONOTONIC and on the wall
    clock, so a reader can lay stamps taken by other processes of the
    machine on the trace's clock."""
    global _anchored
    if not ann.is_enabled():
        _anchored = False
        return False
    if not _anchored:
        _anchored = True
        with ann("clock_anchor", monotonic_ns=time.monotonic_ns(),
                 time_ns=time.time_ns()):
            pass
    return True


def _merge(into: Dict[str, list], table: Dict[str, list]) -> None:
    for name, row in list(table.items()):
        acc = into.get(name)
        if acc is None:
            acc = into[name] = [0, 0, 0, [0] * _N_BUCKETS]
        acc[0] += row[0]
        acc[1] += row[1]
        acc[2] = max(acc[2], row[2])
        acc[3] = [a + b for a, b in zip(acc[3], row[3])]


def _thread_table() -> Dict[str, list]:
    """This thread's own table, so folding takes no lock (seventeen threads
    of a replica fold a few thousand phases a second). A new thread's first
    phase registers it and retires the rows of threads that have ended."""
    table: Dict[str, list] = {}
    _phase_local.table = table
    with _phase_lock:
        live = []
        for ref, t in _tables:
            th = ref()
            if th is not None and th.is_alive():
                live.append((ref, t))
            else:
                _merge(_retired, t)
        live.append((weakref.ref(threading.current_thread()), table))
        _tables[:] = live
    return table


def _fold(name: str, start_ns: int, dur_ns: int,
          attrs: Optional[Dict[str, Any]], slow: bool = True) -> None:
    dur_ns = max(0, int(dur_ns))
    try:
        table = _phase_local.table
    except AttributeError:
        table = _thread_table()
    row = table.get(name)
    if row is None:
        row = table[name] = [0, 0, 0, [0] * _N_BUCKETS]
    row[0] += 1
    row[1] += dur_ns
    if dur_ns > row[2]:
        row[2] = dur_ns
    row[3][min(dur_ns.bit_length(), _N_BUCKETS - 1)] += 1
    if dur_ns >= SLOW_NS and slow:
        _slow.append((name, int(start_ns), dur_ns, dict(attrs or {})))
        _slow_event(name, int(start_ns), dur_ns, attrs)


def _slow_event(name: str, start_ns: int, dur_ns: int, attrs) -> None:
    """A slow phase of a worker process also becomes a cluster event (rare by
    construction, and rate-limited here), which is how `rtpu events` and the
    controller's process see a worker's stalls. What the limit drops is
    counted, and the next event that passes carries the count
    (``dropped_before``), so a reader knows whether a sum is whole. The
    controller's own process already holds its phases in this table."""
    if name.startswith("ctrl."):
        return
    try:
        from ray_tpu.core import context as ctx
        from ray_tpu.core import events

        if not ctx.is_initialized():
            # a worker that has not registered yet: kept for send_unsent()
            if _unsent is not None and len(_unsent) < _UNSENT_MAX:
                _unsent.append((name, start_ns, dur_ns, attrs))
            return
        if not (events.enabled()
                and ctx.get_worker_context().role == "worker"):
            return  # a driver's phases stay in its own table
        now = time.monotonic()
        with _phase_lock:
            tokens, at, dropped = _slow_event_budget
            tokens = min(float(_SLOW_EVENTS_PER_10S), tokens + (now - at)
                         * _SLOW_EVENTS_PER_10S / 10.0)
            if tokens < 1.0:
                _slow_event_budget[:] = [tokens, now, dropped + 1]
                return
            _slow_event_budget[:] = [tokens - 1.0, now, 0]
        data = {"name": name, "start_monotonic_ns": start_ns,
                "dur_ns": dur_ns, "pid": os.getpid(),
                "attrs": {k: v for k, v in (attrs or {}).items()
                          if isinstance(v, (int, float, str, bool))}}
        if dropped:
            data["dropped_before"] = dropped
        events.emit(
            "INFO", "SLOW_PHASE",
            f"{name} took {dur_ns / 1e6:.1f} ms", source="tracing",
            worker_id=ctx.get_worker_context().extra.get("worker_id"),
            data=data)
    except Exception:
        pass  # a phase must never break the work it times


def send_unsent() -> None:
    """Once, right after a worker has registered: the slow phases that ended
    before the process had a worker context (``boot.interpreter``,
    ``boot.imports``) go out as the cluster events they could not be then,
    oldest first, through the same rate limit."""
    global _unsent
    held, _unsent = _unsent or [], None
    for entry in held:
        _slow_event(*entry)


def ingest_slow_event(ev: Dict[str, Any]) -> None:
    """Controller side: a SLOW_PHASE cluster event from another process is
    copied into this process's slow ring (tagged with its pid), so a reader
    in the controller's process sees the cluster's stalls after shutdown."""
    d = ev.get("data") or {}
    if d.get("pid") == os.getpid() or "name" not in d:
        return
    attrs = dict(d.get("attrs") or {}, pid=d.get("pid"),
                 worker_id=ev.get("worker_id"))
    if d.get("dropped_before"):
        attrs["dropped_before"] = int(d["dropped_before"])
    _slow.append((d["name"], int(d.get("start_monotonic_ns", 0)),
                  int(d.get("dur_ns", 0)), attrs))


class phase:
    """``with tracing.phase("engine.tick", live=3): ...`` — one named stretch
    of host work on the calling thread. Must exit on the thread it entered
    (a profiler annotation nests per thread); for time measured across
    threads or awaits use ``observe``."""

    __slots__ = ("name", "attrs", "slow", "_t0", "_ann")

    def __init__(self, name: str, slow: bool = True, **attrs: Any):
        self.name = name
        self.attrs = attrs
        self.slow = slow  # False: a wait by design, kept out of the slow ring

    def __enter__(self) -> "phase":
        ann = _annotation()
        if ann is not None:
            _anchor(ann)
            self._ann = ann(self.name, **self.attrs)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        dur = time.monotonic_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        _fold(self.name, self._t0, dur, self.attrs, self.slow)
        return False


def observe(name: str, ns: int, start_ns: Optional[int] = None,
            slow: bool = True, **attrs: Any) -> None:
    """Record a duration measured elsewhere (``ns``, ending now unless
    ``start_ns`` on CLOCK_MONOTONIC is given). In a profiler session it
    appears as an instant annotation carrying ``dur_ns`` and
    ``start_monotonic_ns``, from which a reader rebuilds the interval.
    ``slow=False`` keeps it out of the slow ring (a request-long hop is
    long by nature, not a stall)."""
    ns = max(0, int(ns))
    if start_ns is None:
        start_ns = time.monotonic_ns() - ns
    ann = _annotation()
    if ann is not None and _anchor(ann):
        with ann(name, dur_ns=ns, start_monotonic_ns=int(start_ns), **attrs):
            pass
    _fold(name, start_ns, ns, attrs, slow)


class steps:
    """``await tracing.steps(name, coro)``: run a coroutine on its event loop
    as usual and observe every stretch it holds the loop (resumption to next
    suspension) under ``name``. Awaited time is not counted: a long-poll
    handler is many short steps, a handler that blocks the loop is one long
    one."""

    __slots__ = ("name", "coro")

    def __init__(self, name: str, coro):
        self.name = name
        self.coro = coro

    def __await__(self):
        send, throw, name = self.coro.send, self.coro.throw, self.name
        val: Any = None
        exc: Optional[BaseException] = None
        while True:
            t0 = time.monotonic_ns()
            try:
                out = send(val) if exc is None else throw(exc)
            except StopIteration as stop:
                return stop.value
            finally:  # a step that returns, raises or suspends: all counted
                _fold(name, t0, time.monotonic_ns() - t0, None)
            try:
                val, exc = (yield out), None
            except GeneratorExit:
                self.coro.close()
                raise
            except BaseException as e:
                val, exc = None, e


def phase_table() -> Dict[str, Dict[str, Any]]:
    """This process's phases since it started (``ray_tpu.shutdown()`` does
    not clear them): name -> count, total_ns, max_ns and ``buckets``, where
    bucket b counts durations in [2^(b-1), 2^b) ns."""
    merged: Dict[str, list] = {}
    with _phase_lock:
        _merge(merged, _retired)
        for _, table in _tables:
            _merge(merged, table)
    return {n: {"count": r[0], "total_ns": r[1], "max_ns": r[2],
                "buckets": r[3]} for n, r in merged.items()}


def slow_phases() -> List[Dict[str, Any]]:
    """The last ``SLOW_RING`` phases of ``SLOW_NS`` or more, oldest first:
    this process's, and in the controller's process also those other
    processes reported (``attrs`` then carries their ``pid``)."""
    rows = list(_slow)
    return [{"name": n, "start_monotonic_ns": s, "dur_ns": d, "attrs": a}
            for n, s, d, a in rows]


def bucket_quantile(buckets: List[int], q: float) -> Optional[float]:
    """Approximate quantile (ns) of a ``phase_table`` row's buckets: the
    geometric middle of the bucket the q-th observation falls in."""
    total = sum(buckets)
    if not total:
        return None
    want, seen = q * total, 0
    for b, c in enumerate(buckets):
        seen += c
        if c and seen >= want:
            return 0.0 if b == 0 else 2.0 ** (b - 0.5)
    return 2.0 ** (len(buckets) - 1.5)
