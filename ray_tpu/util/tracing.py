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

The slow ring says why. Every stretch of 50 ms or more that ``phase`` or
``steps`` timed carries what its thread did with the time, as deltas of
``getrusage(RUSAGE_THREAD)``: ``cpu_ns`` (user + system), ``majflt``,
``inblock``, ``nvcsw``, ``nivcsw``, over ``over_ns`` (the stretch and at most
10 ms before it). ``cpu_ns`` near ``dur_ns``: it computed; far under it with
``nvcsw``: it waited (a lock, the GIL, a device, a sleep); with ``nivcsw``:
it was descheduled; ``majflt`` / ``inblock``: it read the disk. ``dur_ns - cpu_ns``
is time off the CPU whatever the reason: the stretch's own blocking calls (a
synchronous RPC, a child it waits for, a read) count as much as a thread
that was starved, and only the two switch counts tell them apart. The TPU
machines' kernel (4.4.0, a sandbox's) reports those and ``majflt``,
``inblock`` as 0 for every thread: there ``cpu_ns`` alone reads, in 10 ms
steps. A collection of generation 1 or 2 is the phase ``host.gc``.

Reading a stall record (``<name>.stall``, made by ``beat``: a loop's period
that exceeded the median of its last 16 by a quarter and 250 ms; ``dur_ns``
is the excess). ``shard_ms`` / ``enqueue_ms`` / ``report_ms`` near the excess:
the program's own phase of that name was long, and its own slow record says
why. ``gc_ms``: the collector ran (any thread: it holds the GIL).
``outside_ms``: the caller's part of the loop, its wait for the device or its
data; ``stack`` (taken by the watchdog thread while the period was open,
the beating thread first, innermost frame first) names the line. ``cpu_ns``
near the period: the thread computed; near none with ``majflt`` or
``inblock``: the disk. No ``stack`` and ``watchdog_late_ms`` near the excess:
the watchdog could not run either, so the GIL was held, or the process was
stopped or starved by its machine. ``profiler`` 1: a profiler session
started or stopped inside the period, which stalls any loop.
"""
from __future__ import annotations

import collections
import gc
import os
import resource
import secrets
import sys
import threading
import time
import traceback
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
STAMP_NS = 10_000_000     # a thread's rusage stamp is good for this long
BEAT_KEPT = 16            # periods a beat keeps; the rule's median is theirs
BEAT_MIN = 8              # periods known before a long one is a stall
STALL_MIN_NS = 250_000_000  # a stall: an excess over the median of this ...
STALL_SHARE = 4           # ... and of the median over this, at least
WATCHDOG_S = 0.25         # the watchdog's wake-ups
STACK_CHARS = 2048        # of the stacks the watchdog took, kept on a record
_N_BUCKETS = 40           # log2(ns) buckets: bucket b holds [2^(b-1), 2^b)
_SLOW_EVENTS_PER_10S = 32  # cluster events a process may emit for slow phases

# Guards the registry below, not the folding. Re-entrant: a collection can
# start under it, and its callback folds `host.gc` on the same thread.
_phase_lock = threading.RLock()
_phase_local = threading.local()  # .table: this thread's _Table
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
# Slow `host.gc` entries whose cluster event waits for a caller that holds no
# lock of its own: a collector's callback must not take the event buffer's.
_deferred: "collections.deque" = collections.deque(maxlen=_UNSENT_MAX)


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


class _Table(dict):
    """A thread's name -> row; its ``stamp``, what a slow stretch takes its
    rusage deltas from: (good until, taken at: monotonic ns, the rusage then,
    this table); and its loops that beat, by name."""

    __slots__ = ("stamp", "beats")

    def restamp(self, now_ns: int, usage: tuple) -> tuple:
        st = self.stamp = (now_ns + STAMP_NS, now_ns, usage, self)
        return st


def _thread_table() -> _Table:
    """This thread's own table, so folding takes no lock (seventeen threads
    of a replica fold a few thousand phases a second). A new thread's first
    phase registers it and retires the rows of threads that have ended."""
    table = _Table()
    table.beats = {}
    table.restamp(time.monotonic_ns(), _usage())
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
          attrs: Optional[Dict[str, Any]], slow: bool = True,
          stamp: Optional[tuple] = None) -> None:
    """One stretch into the calling thread's table and, where it is slow,
    into the ring and out as a cluster event; with the ``stamp`` the thread
    held when the stretch began (it names the table too), a slow one carries
    what the thread did with the time."""
    dur_ns = max(0, int(dur_ns))
    table = _table() if stamp is None else stamp[3]
    row = table.get(name)
    if row is None:
        row = table[name] = [0, 0, 0, [0] * _N_BUCKETS]
    row[0] += 1
    row[1] += dur_ns
    if dur_ns > row[2]:
        row[2] = dur_ns
    row[3][min(dur_ns.bit_length(), _N_BUCKETS - 1)] += 1
    if dur_ns >= SLOW_NS and slow:
        attrs = dict(attrs or {})
        if stamp is not None:
            attrs.update(_spent(stamp, start_ns + dur_ns))
        _slow.append((name, int(start_ns), dur_ns, attrs))
        _slow_event(name, int(start_ns), dur_ns, attrs)
        _send_deferred()


def _send_deferred() -> None:
    while _deferred:
        _slow_event(*_deferred.popleft())


def _usage() -> tuple:
    """The calling thread's rusage, in the order of ``_SPENT``."""
    r = resource.getrusage(resource.RUSAGE_THREAD)
    return (int((r.ru_utime + r.ru_stime) * 1e9), r.ru_majflt, r.ru_inblock,
            r.ru_nvcsw, r.ru_nivcsw)


_SPENT = ("cpu_ns", "majflt", "inblock", "nvcsw", "nivcsw")


def _table() -> _Table:
    try:
        return _phase_local.table
    except AttributeError:
        return _thread_table()


def _stamp(table: _Table, now_ns: int) -> tuple:
    """The thread's stamp, taken anew when it is older than ``STAMP_NS``: a
    stretch that ends short of ``SLOW_NS`` pays one read of it, and a thread
    one ``getrusage`` in 10 ms."""
    st = table.stamp
    return table.restamp(now_ns, _usage()) if now_ns > st[0] else st


def _spent(st: tuple, now_ns: int) -> Dict[str, int]:
    """What the thread did since its stamp ``st``, for a slow record: the
    five deltas and ``over_ns``, the interval they cover (a stretch and at
    most ``STAMP_NS`` before it)."""
    use = _usage()
    st[3].restamp(now_ns, use)
    out = {k: b - a for k, a, b in zip(_SPENT, st[2], use)}
    out["over_ns"] = now_ns - st[1]
    return out


def _slow_event(name: str, start_ns: int, dur_ns: int, attrs,
                limited: bool = True) -> None:
    """A slow phase of a worker process also becomes a cluster event (rare by
    construction, and rate-limited here), which is how `rtpu events` and the
    controller's process see a worker's stalls. What the limit drops is
    counted, and the next event that passes carries the count
    (``dropped_before``), so a reader knows whether a sum is whole. A stall
    record (``limited=False``: four a second at most by its rule) is never
    dropped. The controller's own process already holds its phases in this
    table."""
    if name.startswith("ctrl."):
        return
    try:
        from ray_tpu.core import context as ctx
        from ray_tpu.core import events

        if not ctx.is_initialized():
            # a worker that has not registered yet: kept for send_unsent()
            if _unsent is not None and len(_unsent) < _UNSENT_MAX:
                _unsent.append((name, start_ns, dur_ns, attrs, limited))
            return
        if not (events.enabled()
                and ctx.get_worker_context().role == "worker"):
            return  # a driver's phases stay in its own table
        now = time.monotonic()
        with _phase_lock:
            tokens, at, dropped = _slow_event_budget
            tokens = min(float(_SLOW_EVENTS_PER_10S), tokens + (now - at)
                         * _SLOW_EVENTS_PER_10S / 10.0)
            if tokens < 1.0 and limited:
                _slow_event_budget[:] = [tokens, now, dropped + 1]
                return
            _slow_event_budget[:] = [max(0.0, tokens - 1.0), now, 0]
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

    __slots__ = ("name", "attrs", "slow", "_t0", "_ann", "_st")

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
        try:  # here, so that the exit's fold need not look it up
            table = _phase_local.table
        except AttributeError:
            table = _thread_table()
        self._t0 = t0 = time.monotonic_ns()
        st = table.stamp
        if t0 > st[0]:  # _stamp()'s common case, without the call
            st = _stamp(table, t0)
        self._st = st
        return self

    def __exit__(self, et, ev, tb) -> bool:
        dur = time.monotonic_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        _fold(self.name, self._t0, dur, self.attrs, self.slow, self._st)
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
        table = _table()  # the loop's thread: a coroutine does not migrate
        val: Any = None
        exc: Optional[BaseException] = None
        while True:
            t0 = time.monotonic_ns()
            st = _stamp(table, t0)
            try:
                out = send(val) if exc is None else throw(exc)
            except StopIteration as stop:
                return stop.value
            finally:  # a step that returns, raises or suspends: all counted
                _fold(name, t0, time.monotonic_ns() - t0, None, True, st)
            try:
                val, exc = (yield out), None
            except GeneratorExit:
                self.coro.close()
                raise
            except BaseException as e:
                val, exc = None, e


# ------------------------------------------------------------- the collector

_gc_open: list = [0, None]   # the collection under way: its start, its stamp
_gc_total_ns = [0]           # generations 1 and 2, this process, all threads


def _on_gc(when: str, info: Dict[str, int]) -> None:
    """``gc.callbacks`` entry: a collection of generation 1 or 2 is the phase
    ``host.gc`` (attrs ``generation``, ``collected``) on the thread it ran
    on; a young pass (hundreds a second, microseconds each) returns at once.
    It runs wherever an allocation tripped the collector, under whatever lock
    that thread holds: it folds, and leaves a slow entry's cluster event to
    the next slow phase or the watchdog (``_deferred``)."""
    try:
        gen = info["generation"]
        if not gen:
            return
        now = time.monotonic_ns()
        if when == "start":
            _gc_open[:] = now, _stamp(_table(), now)
            return
        t0, st = _gc_open
        if st is None:
            return  # installed while this collection ran
        _gc_open[1] = None
        dur = now - t0
        _gc_total_ns[0] += dur
        attrs = {"generation": gen, "collected": info["collected"]}
        _fold("host.gc", t0, dur, None, slow=False)
        if dur >= SLOW_NS:
            attrs.update(_spent(st, now))
            _slow.append(("host.gc", t0, dur, attrs))
            _deferred.append(("host.gc", t0, dur, attrs))
    except Exception:
        pass  # never into the collector (the interpreter may be going down)


gc.callbacks.append(_on_gc)


# ------------------------------------------------------------ a loop that beats

def format_stacks(first: Optional[int] = None,
                  innermost_first: bool = False) -> str:
    """Every thread's current stack, the thread ``first`` (an ident) leading:
    what `stack_dump`, the serve plane's stall events and a stall record
    attach. ``innermost_first`` turns each stack over, so that a cut keeps
    the frame a thread stands in."""
    names = {t.ident: t.name for t in threading.enumerate()}
    frames = sys._current_frames()
    parts = []
    for tid in sorted(frames, key=lambda t: t != first):
        lines = traceback.format_stack(frames[tid])
        if innermost_first:
            lines.reverse()
        parts.append(f"--- thread {names.get(tid, '?')} ({tid}) ---")
        parts.append("".join(lines))
    return "\n".join(parts)


_beats: List["_Beat"] = []    # every live thread's, for the watchdog
_watchdog_woke = [0]          # its last wake-up, 0 while it does not run
_stall_said = [0.0]           # the last stderr line, on time.monotonic()


def _stall_floor(median_ns: int) -> int:
    return max(STALL_MIN_NS, median_ns // STALL_SHARE)


class _Beat:
    """One loop on one thread: its periods, and the stall rule over them."""

    __slots__ = ("name", "parts", "thread", "index", "last_ns", "periods",
                 "median_ns", "snap", "sample", "late_ns")

    def __init__(self, name: str, parts: Optional[Dict[str, str]] = None):
        self.name = name
        self.parts = dict(parts or {})   # attribute -> phase of this thread
        self.thread = weakref.ref(threading.current_thread())
        self.index = 0
        self.last_ns: Optional[int] = None
        self.periods: "collections.deque" = collections.deque(
            maxlen=BEAT_KEPT)
        self.median_ns = 0               # 0 until BEAT_MIN periods are known
        # at the last beat: the thread's rusage, its totals under `parts`,
        # the process's collector time, whether a profiler session was on
        self.snap: Optional[tuple] = None
        # the watchdog's, for the open period: (its start, the stacks), and
        # the latest of its wake-ups since the last beat
        self.sample: Optional[tuple] = None
        self.late_ns = 0

    def tick(self, now_ns: int, profiling: bool = False
             ) -> Optional[Dict[str, Any]]:
        """The beat at ``now_ns``: keeps the period since the last one for
        the rule's median (no row in the table: nothing would read it), and
        returns the attributes of the ``<name>.stall`` record it made, if the
        rule made one."""
        prev, self.last_ns = self.last_ns, now_ns
        self.index += 1
        was, sample, late_ns = self.snap, self.sample, self.late_ns
        table, usage = _table(), _usage()
        self.snap = (usage, [row[1] if row else 0 for row in map(
            table.get, self.parts.values())], _gc_total_ns[0], profiling)
        self.sample, self.late_ns = None, 0
        table.restamp(now_ns, usage)
        if prev is None:
            return None
        period = now_ns - prev
        median = self.median_ns          # of the periods before this one
        self.periods.append(period)
        n = len(self.periods)
        if n >= BEAT_MIN:
            s = sorted(self.periods)
            self.median_ns = (s[(n - 1) // 2] + s[n // 2]) // 2
        if not median or period - median < _stall_floor(median):
            return None
        return self._stall(prev, period, median, was, sample, late_ns)

    def _stall(self, prev, period, median, was, sample, late_ns
               ) -> Dict[str, Any]:
        ms = lambda ns: round(ns / 1e6, 3)  # noqa: E731
        now_ns, excess = prev + period, period - median
        attrs: Dict[str, Any] = {"index": self.index, "period_ms": ms(period),
                                 "median_ms": ms(median)}
        (usage0, totals0, gc0, prof0), (usage, totals, gc, prof) = (
            was, self.snap)
        named = gc - gc0
        for key, a, b in zip(self.parts, totals0, totals):
            attrs[key] = ms(b - a)
            named += b - a
        attrs["gc_ms"] = ms(gc - gc0)
        attrs["outside_ms"] = ms(max(0, period - named))
        attrs.update(zip(_SPENT, (b - a for a, b in zip(usage0, usage))))
        attrs["profiler"] = int(prof0 != prof)
        woke = _watchdog_woke[0]
        if woke:  # a wake-up overdue now counts: it could not run either
            late_ns = max(late_ns, now_ns - woke - int(WATCHDOG_S * 1e9))
            attrs["watchdog_late_ms"] = ms(late_ns)
        if sample and sample[0] == prev:
            attrs["stack"] = sample[1]
        name, start = self.name + ".stall", prev + median
        if attrs["profiler"]:  # the session's own doing: not laid on a trace
            _fold(name, start, excess, None, slow=False)
        else:
            observe(name, excess, start, slow=False, **{
                k: v for k, v in attrs.items() if k != "stack"})
        _slow.append((name, start, excess, dict(attrs)))
        _slow_event(name, start, excess, attrs, limited=False)
        _say_stall(name, excess, self.parts, attrs)
        return attrs


def _say_stall(name: str, excess_ns: int, parts, attrs) -> None:
    """One line on stderr, one a second at most: an untraced run's log then
    tells a stalled run from a slow program."""
    now = time.monotonic()
    if now - _stall_said[0] < 1.0:
        return
    _stall_said[0] = now
    said = [f"outside {attrs['outside_ms']:.0f}"]
    said += [f"{k[:-3]} {attrs[k]:.0f}" for k in parts]
    said += [f"gc {attrs['gc_ms']:.0f}", f"cpu {attrs['cpu_ns'] / 1e6:.0f} ms",
             f"majflt {attrs['majflt']}"]
    if attrs["profiler"]:
        said.append("profiler 1")
    try:
        sys.stderr.write(
            f"[tracing] {name} {excess_ns / 1e6:.0f} ms at beat "
            f"{attrs['index']}: {', '.join(said)}\n")
        sys.stderr.flush()
    except (OSError, ValueError):
        pass  # a closed stderr


def _watchdog() -> None:
    """The one daemon thread of a process that beats. Every ``WATCHDOG_S``:
    notes how late it woke (where the GIL is held through a stall it cannot
    run either, and its lateness is the evidence), takes every thread's
    stack once for a beat whose open period already exceeds the rule, and
    sends what the collector left."""
    woke = time.monotonic_ns()
    while True:
        time.sleep(WATCHDOG_S)
        now = time.monotonic_ns()
        late, woke = max(0, now - woke - int(WATCHDOG_S * 1e9)), now
        _watchdog_woke[0] = now
        try:
            for b in list(_beats):
                if late > b.late_ns:
                    b.late_ns = late
                last, median = b.last_ns, b.median_ns
                if (median and b.sample is None and last is not None
                        and now - last - median >= _stall_floor(median)):
                    th = b.thread()
                    b.sample = (last, format_stacks(
                        th.ident if th else None, True)[:STACK_CHARS])
            _send_deferred()
        except Exception:
            pass  # the watchdog outlives what it watches


def beat(name: str, parts: Optional[Dict[str, str]] = None) -> None:
    """``tracing.beat("train", {"enqueue_ms": "train.step"})`` at the top of a
    loop's body: the time since this thread's last beat of that name is the
    loop's period (kept for the rule, the last ``BEAT_KEPT``; no phase of its
    own), and a period that exceeds their median (``BEAT_MIN`` known at
    least) by ``STALL_MIN_NS`` and by a quarter of that median makes one slow
    record ``<name>.stall``: it starts a median after the last beat and lasts
    the excess. Its attributes (the module's docstring says how to read
    them): ``index``, ``period_ms``, ``median_ms``; the period's time under
    each phase of ``parts`` on this thread, ``gc_ms`` and the rest as
    ``outside_ms``; the five rusage deltas over the period; ``profiler``;
    ``stack`` and ``watchdog_late_ms`` from the watchdog thread, which the
    process's first beat starts. The record passes the cluster events' rate
    limit and writes one line to stderr."""
    now = time.monotonic_ns()
    b = _table().beats.get(name) or _new_beat(name, parts)
    ann = _annotation()
    b.tick(now, ann is not None and ann.is_enabled())


def _new_beat(name: str, parts: Optional[Dict[str, str]]) -> _Beat:
    b = _table().beats[name] = _Beat(name, parts)
    with _phase_lock:
        _beats[:] = [o for o in _beats
                     if (th := o.thread()) is not None and th.is_alive()]
        _beats.append(b)
        if not _watchdog_woke[0]:
            _watchdog_woke[0] = time.monotonic_ns()
            threading.Thread(target=_watchdog, name="rtpu-tracing-watchdog",
                             daemon=True).start()
    return b


def phase_table() -> Dict[str, Dict[str, Any]]:
    """This process's phases since it started (``ray_tpu.shutdown()`` does
    not clear them): name -> count, total_ns, max_ns and ``buckets``, where
    bucket b counts durations in [2^(b-1), 2^b) ns."""
    merged: Dict[str, list] = {}
    with _phase_lock:
        _merge(merged, _retired)
        for _, table in list(_tables):
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
