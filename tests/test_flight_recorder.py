"""Cluster-wide task flight recorder.

Reference surfaces matched: TaskEventBuffer -> GcsTaskManager
(src/ray/core_worker/task_event_buffer.h:206) feeding `ray timeline` and
`ray summary` with per-phase latency accounting. Worker-side phase events
(scheduling delay, queue wait, arg fetch, execute, result store) batch to
the controller, derive Prometheus histograms, nest as chrome-trace
sub-slices with submit->run flow arrows, and carry finished tracing spans
cluster-wide.
"""
import json
import os
import re
import socket
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.util import state, tracing


def _poll(fn, timeout=30.0, interval=0.3):
    """Poll fn() until it returns a truthy value (the recorder flushes on
    RTPU_TASK_EVENTS_FLUSH_S cadence, so assertions must wait for a ship)."""
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        last = fn()
        if last:
            return last
        time.sleep(interval)
    return last


def test_timeline_phase_subslices_and_flow_arrows(tmp_path):
    """state.timeline() nests per-task phase sub-slices under each task
    slice, links the driver's submit event to the worker's run slice with
    chrome-trace flow arrows (ph s/f) across pid rows, and phase durations
    sum to <= the task's wall time."""
    os.environ["RTPU_TASK_LEASE_MAX"] = "0"  # queue path -> submitted events
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def traced(x):
            time.sleep(0.05)
            return x + 1

        assert ray_tpu.get([traced.remote(i) for i in range(4)],
                           timeout=60) == [1, 2, 3, 4]

        def ready():
            tr = state.timeline()
            execs = [e for e in tr if e.get("cat") == "phase"
                     and e["name"] == "exec"]
            return tr if len(execs) >= 4 else None

        trace = _poll(ready)
        assert trace, "phase sub-slices never reached the controller"

        # Main task slices with the phase breakdown in args.
        slices = [e for e in trace if e["ph"] == "X"
                  and e["name"] == "traced"]
        assert len(slices) >= 4
        with_phases = [e for e in slices if "exec_s" in e["args"]]
        assert with_phases, slices
        for e in with_phases:
            ph_sum = sum(e["args"].get(k, 0.0) for k in
                         ("arg_fetch_s", "exec_s", "result_store_s"))
            assert e["args"]["exec_s"] >= 0.04  # the sleep is visible
            assert ph_sum * 1e6 <= e["dur"] + 1e3, \
                f"phases {ph_sum * 1e6}us exceed wall {e['dur']}us"

        # Sub-slices nest inside their parent slice's row and extent.
        for name in ("arg_fetch", "exec", "result_store"):
            subs = [e for e in trace
                    if e.get("cat") == "phase" and e["name"] == name]
            assert subs, f"no {name} sub-slices"
            for s in subs:
                parent = next(p for p in with_phases
                              if p["args"]["task_id"]
                              == s["args"]["task_id"])
                assert s["pid"] == parent["pid"]
                assert s["tid"] == parent["tid"]

        # Flow arrows: well-formed s/f pairs crossing pid rows.
        s_evs = {e["id"]: e for e in trace
                 if e.get("ph") == "s" and e.get("cat") == "flow"}
        f_evs = {e["id"]: e for e in trace
                 if e.get("ph") == "f" and e.get("cat") == "flow"}
        assert s_evs and f_evs
        paired = set(s_evs) & set(f_evs)
        assert paired, (s_evs, f_evs)
        assert any(s_evs[i]["pid"] != f_evs[i]["pid"] for i in paired), \
            "no flow arrow crosses process rows"
        for i in paired:
            assert f_evs[i]["ts"] >= s_evs[i]["ts"]
            assert f_evs[i].get("bp") == "e"

        # The export is valid JSON (perfetto/chrome://tracing loadable).
        path = str(tmp_path / "trace.json")
        state.timeline(path)
        with open(path) as f:
            loaded = json.load(f)
        assert isinstance(loaded, list) and loaded
    finally:
        os.environ.pop("RTPU_TASK_LEASE_MAX", None)
        ray_tpu.shutdown()


def test_phase_histograms_on_metrics_scrape():
    """All five derived rtpu_task_* phase histograms appear on the
    controller's /metrics endpoint with non-zero counts after a workload."""
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def work(x):
            return x * 2

        dep = ray_tpu.put(21)
        assert ray_tpu.get(work.remote(dep), timeout=60) == 42
        assert ray_tpu.get([work.remote(i) for i in range(4)],
                           timeout=60) == [0, 2, 4, 6]

        addr = state.metrics_address()
        assert addr, "metrics endpoint not advertised"
        names = ["rtpu_task_scheduling_delay_s", "rtpu_task_queue_wait_s",
                 "rtpu_task_arg_fetch_s", "rtpu_task_exec_s",
                 "rtpu_task_result_store_s"]

        def scraped():
            with urllib.request.urlopen(f"http://{addr}/metrics",
                                        timeout=10) as r:
                text = r.read().decode()
            for name in names:
                m = re.search(rf'{name}_count\{{[^}}]*\}} (\d+)', text)
                if m is None or int(m.group(1)) == 0:
                    return None
            return text

        text = _poll(scraped)
        assert text, "phase histograms never appeared on /metrics"
        # Histogram plumbing is complete: buckets + sum + TYPE metadata,
        # so grafana generation derives quantile panels from these.
        assert "# TYPE rtpu_task_exec_s histogram" in text
        assert re.search(r'rtpu_task_exec_s_bucket\{[^}]*le="\+Inf"[^}]*\}',
                         text), text[-2000:]
        assert 'label="work"' in text
        # RPC handler accounting rides the same scrape.
        assert "rtpu_rpc_handled_total" in text

        # The breakdown summary derives p50/p99 from the same histograms.
        rows = state.summarize_tasks(breakdown=True)
        assert "work" in rows, rows
        st = rows["work"]["exec_s"]
        assert st["count"] >= 5
        assert 0.0 <= st["p50"] <= st["p99"] <= 60.0
    finally:
        ray_tpu.shutdown()


def test_get_cluster_spans():
    """Submitter (producer) and executor (consumer) spans of one trace are
    both visible cluster-wide: the worker ships its finished spans with
    phase batches; the driver's stay local and merge at query time."""
    tracing.setup_tracing()
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def span_task():
            return 1

        with tracing.start_span("driver-root") as root:
            trace_id = root.context.trace_id
            assert ray_tpu.get(span_task.remote(), timeout=60) == 1

        def both_sides():
            spans = tracing.get_cluster_spans(trace_id)
            kinds = {s["kind"] for s in spans}
            return spans if {"producer", "consumer"} <= kinds else None

        spans = _poll(both_sides)
        assert spans, "executor span never reached the controller"
        assert all(s["trace_id"] == trace_id for s in spans)
        submits = [s for s in spans if s["name"] == "submit span_task"]
        runs = [s for s in spans if s["name"] == "run span_task"]
        assert submits and runs
        # The consumer span is the submit span's child (context propagated
        # through the spec as W3C traceparent).
        assert runs[0]["parent_span_id"] == submits[0]["span_id"]
        assert runs[0]["end_time"] >= runs[0]["start_time"]
    finally:
        os.environ.pop("RTPU_TRACING", None)
        ray_tpu.shutdown()


def test_failed_before_running_instant_event():
    """A task that dies before ever running (dependency failure -> never
    dispatched) is visible in the timeline as an instant event (ph: "i")
    instead of silently vanishing."""
    os.environ["RTPU_TASK_LEASE_MAX"] = "0"
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def boom():
            raise ValueError("upstream failure")

        @ray_tpu.remote
        def child(x):
            return x

        ref = child.remote(boom.remote())
        with pytest.raises(Exception):
            ray_tpu.get(ref, timeout=60)

        def has_instant():
            tr = state.timeline()
            return [e for e in tr if e.get("ph") == "i"
                    and "child" in e["name"]] or None

        instants = _poll(has_instant, timeout=15)
        assert instants, "failed-before-running task absent from timeline"
        ev = instants[0]
        assert ev["s"] == "p" and ev["name"].endswith("failed")
        assert ev["args"]["task_id"]
    finally:
        os.environ.pop("RTPU_TASK_LEASE_MAX", None)
        ray_tpu.shutdown()


# ------------------------------------------------ controller-bounce survival


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_phase_events_survive_controller_bounce(tmp_path):
    """Events recorded while the controller is DOWN (direct actor call
    served worker-to-worker during the outage) are buffered by the
    recorder and land on the restarted controller once the worker
    re-registers — the reconnect-safety the ControllerKiller harness
    exists to prove."""
    import test_controller_reconnect as tcr

    port = _free_port()
    state_path = str(tmp_path / "state.pkl")
    head = tcr._start_head(port, state_path,
                           log_path=str(tmp_path / "head1.log"))
    killed = []
    try:
        ray_tpu.init(address=f"127.0.0.1:{port}")
        from ray_tpu.core import context as ctx

        client = ctx.get_worker_context().client

        @ray_tpu.remote
        class Ping:
            def ping(self, x):
                return x

        a = Ping.remote()
        assert ray_tpu.get(a.ping.remote(1), timeout=60) == 1
        # The outage call below must ride the direct route (worker-to-
        # worker dispatch). A first call submitted while the actor was
        # still pending went through the controller and left the route
        # unresolved (`_resolve_route`: state != "alive"); the call during
        # the outage would then wait out two 20 s reconnect deadlines on a
        # controller that is down by design. The actor is alive now, so the
        # next call resolves it: wait for that, with a bound of its own.
        from ray_tpu.core import api

        def warm():
            assert ray_tpu.get(a.ping.remote(1), timeout=60) == 1
            return api._get_route(ctx.get_worker_context(),
                                  a._actor_id).conn

        assert _poll(warm, timeout=10), \
            "the direct route to the actor never came up"
        tcr._wait_snapshot(state_path, lambda s: s.get("nodes"))

        killed.extend(tcr._worker_pids(client))
        tcr._kill9(head)
        # Served entirely during the outage over the direct route; the
        # worker buffers this call's phase event (its flush blocks in the
        # reconnect loop).
        r = a.ping.remote(42)
        head = tcr._start_head(port, state_path,
                               log_path=str(tmp_path / "head2.log"))
        assert ray_tpu.get(r, timeout=90) == 42

        def landed():
            evs = client.request({"kind": "task_events"})
            return [e for e in evs if e.get("event") == "phases"
                    and e.get("label") == "actor.ping"] or None

        phases = _poll(landed, timeout=60)
        assert phases, \
            "phase events recorded across the bounce never landed"
        assert all("exec_s" in (e.get("phases") or {}) for e in phases)
    finally:
        killed.extend(tcr._worker_pids(client) if "client" in dir() else [])
        tcr._cleanup(head, killed)
