"""State observability API.

Parity surface with the reference's state API + timeline export:
- list_tasks/actors/nodes/workers/objects/placement_groups + summarize
  (ray: python/ray/util/state/api.py:110, state_manager queries),
- timeline() chrome-trace export (ray: GlobalState.chrome_tracing_dump,
  python/ray/_private/state.py:434) — open the file in chrome://tracing or
  Perfetto,
- metrics_address() for the controller's Prometheus scrape endpoint
  (ray: _private/metrics_agent.py role, collapsed to a controller-local
  /metrics listener).
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional

from ray_tpu.core import context as ctx


def _req(msg: Dict[str, Any]) -> Any:
    return ctx.get_worker_context().client.request(msg)


def list_tasks(limit: int = 1000) -> List[Dict[str, Any]]:
    return _req({"kind": "list_state", "what": "tasks", "limit": limit})


def list_actors(limit: int = 1000) -> List[Dict[str, Any]]:
    return _req({"kind": "list_state", "what": "actors", "limit": limit})


def list_nodes(limit: int = 1000) -> List[Dict[str, Any]]:
    return _req({"kind": "list_state", "what": "nodes", "limit": limit})


def list_workers(limit: int = 1000) -> List[Dict[str, Any]]:
    return _req({"kind": "list_state", "what": "workers", "limit": limit})


def list_objects(limit: int = 1000) -> List[Dict[str, Any]]:
    return _req({"kind": "list_state", "what": "objects", "limit": limit})


def list_compiled_dags(limit: int = 1000) -> List[Dict[str, Any]]:
    """Compiled DAGs with live channel plans: stages (actor + method per
    pipeline position), per-edge transport (shm ring vs raw-tail stream),
    the in-flight window depth, and self-healing counters (``recoveries``
    completed in place, ``recovering`` when a heal is in flight,
    ``last_recovery_s``/``last_cause`` for the most recent one). The
    controller only sees compile, teardown, and recovery phase
    transitions, so this is the registry of pipelines whose steady-state
    dispatch bypasses it entirely."""
    return _req({"kind": "list_state", "what": "dags", "limit": limit})


def list_placement_groups(limit: int = 1000) -> List[Dict[str, Any]]:
    """Reference: `ray list placement-groups` (util/state/api.py) — id,
    name, state, strategy, and per-bundle resources/placement."""
    return _req({"kind": "list_state", "what": "placement_groups",
                 "limit": limit})


def summarize_objects(*, min_size: int = 0, limit: int = 1000,
                      timeout: Optional[float] = None) -> Dict[str, Any]:
    """Cluster object census (reference: `ray summary objects` /
    `ray memory`'s grouped views). Returns the aggregated census dict:

    - ``objects``: per-object rows (size/tier/node/owner/pins/age,
      callsite when RTPU_CALLSITE is on), largest first, ``min_size``
      filtered and capped at ``limit``;
    - ``groups``: {owner|tier|node|callsite: {key: {bytes, count,
      tiers}}} computed over ALL rows before truncation;
    - ``errors``: one string per shard that never answered (dead or
      unreachable workers) — partial totals from survivors are still
      returned;
    - ``arenas``/``spill``: per-node ground truth for cross-checking
      attribution.

    The calling process's own ownership shard ships with the request so
    driver-owned refs are attributed too."""
    from ray_tpu.core import ownership

    return _req({"kind": "object_census", "min_size": min_size,
                 "limit": limit, "timeout": timeout,
                 "shard": ownership.census_shard()})


def profile_workers(timeout: float = 2.0) -> Dict[str, Any]:
    """On-demand all-thread stack dump from every live worker (reference:
    dashboard reporter's py-spy stack capture, `ray stack`). Returns
    {"requested": N, "workers": {worker_id: stack text}} — workers stuck
    in native code miss the window and are simply absent."""
    return _req({"kind": "profile_workers", "timeout": timeout})


def phase_table(workers: bool = True,
                timeout: float = 2.0) -> Dict[str, Any]:
    """Host phases (util/tracing.py): {"controller": {"table", "slow"},
    "workers": {worker_id: {"table", "slow"}}}. ``table`` is
    ``tracing.phase_table()`` of that process (name -> count, total_ns,
    max_ns, log2 buckets), ``slow`` its ``tracing.slow_phases()`` (phases of
    50 ms or more with their start on CLOCK_MONOTONIC). The controller's
    slow list also holds what workers reported as SLOW_PHASE events."""
    return _req({"kind": "phase_table", "workers": workers,
                 "timeout": timeout})


def slow_phases(timeout: float = 2.0) -> List[Dict[str, Any]]:
    """Every process's slow phases, oldest first, each with its
    ``process`` ("controller" or a worker id)."""
    r = phase_table(True, timeout)
    rows = [dict(p, process=wid) for wid, w in r["workers"].items()
            for p in w.get("slow", ())]
    seen = {(p["name"], p["start_monotonic_ns"]) for p in rows}
    # the controller's ring also holds what workers reported: once is enough
    rows += [dict(p, process="controller") for p in r["controller"]["slow"]
             if (p["name"], p["start_monotonic_ns"]) not in seen]
    return sorted(rows, key=lambda p: p["start_monotonic_ns"])


def profile(duration: float = 2.0, *,
            task_id: Optional[str] = None,
            actor_id: Optional[str] = None,
            node_id: Optional[str] = None,
            worker_id: Optional[str] = None,
            hz: Optional[float] = None) -> Dict[str, Any]:
    """Cluster flamegraph profile (reference: the dashboard's py-spy
    flamegraph button / `ray stack --native`, without py-spy): every
    targeted worker samples its threads' wall-clock stacks for
    ``duration`` seconds; the controller merges them into collapsed-stack
    format. Entity ids scope the fan-out and match on prefix; with no
    filter every live worker participates. Returns {"stacks":
    {collapsed: count}, "samples", "workers", "requested"} or {"error"}
    when RTPU_PROFILER=0. Render with core/profiler.save_flamegraph or
    `rtpu profile --out prof.html`."""
    return ctx.get_worker_context().client.request(
        {"kind": "profile", "duration": duration, "task_id": task_id,
         "actor_id": actor_id, "node_id": node_id, "worker_id": worker_id,
         "hz": hz},
        # The fan-out itself takes >= duration; the session default RPC
        # timeout may be shorter.
        timeout=duration + 30.0)


def query_metrics(name: Optional[str] = None, *,
                  prefix: Optional[str] = None,
                  tags: Optional[Dict[str, str]] = None,
                  since: Optional[float] = None,
                  stat: Optional[str] = None,
                  window_s: float = 60.0,
                  limit_series: int = 64) -> Dict[str, Any]:
    """Metrics history from the controller's telemetry ring (reference:
    the dashboard's built-in time-series view; no Prometheus server
    needed). Filter by exact ``name`` or ``prefix`` and a tags subset;
    ``since`` is a wall-clock lower bound. Counters come back as
    per-second rates, histograms as derived series (``stat`` in
    p50/p99/mean/rate; default both quantiles). Returns {"enabled",
    "series": [{name, tags, type, stat, points: [[t, v], ...]}],
    "now", "step_s", "retain"}."""
    return _req({"kind": "query_metrics", "name": name, "prefix": prefix,
                 "tags": tags, "since": since, "stat": stat,
                 "window_s": window_s, "limit_series": limit_series})


def list_alerts() -> Dict[str, Any]:
    """Alert rules (telemetry.DEFAULT_ALERT_RULES merged with
    RTPU_ALERT_RULES) and which are currently firing. Firing/resolving
    transitions also land in the event log as ALERT_FIRING /
    ALERT_RESOLVED (`rtpu events --kind ALERT_FIRING`)."""
    return _req({"kind": "list_alerts"})


def summarize_tasks(breakdown: bool = False) -> Dict[str, Dict[str, Any]]:
    """Per-function counts of task events (reference: `ray summary tasks`).

    With ``breakdown=True``, returns per-label per-phase latency stats
    instead — ``{label: {phase: {count, mean, p50, p99}}}`` over the
    flight-recorder histograms (scheduling_delay_s, queue_wait_s,
    arg_fetch_s, exec_s, result_store_s), the `ray summary` timing-column
    analog.
    """
    if breakdown:
        return _req({"kind": "list_state", "what": "summary_breakdown"})
    return _req({"kind": "list_state", "what": "summary"})


def drain_node(node_id: str, reason: str = "manual",
               deadline_s: Optional[float] = None) -> Dict[str, Any]:
    """Gracefully drain a node out of the cluster (reference: the DrainNode
    protocol / `ray drain-node`): scheduling stops there immediately,
    hosted restartable actors migrate with their state, running tasks get
    ``deadline_s`` (default RTPU_DRAIN_DEADLINE_S) to finish before they
    re-queue with the preempted flag, and sole-copy objects re-replicate
    before the node's chips leave the pool. ``reason`` is one of
    manual / preemption / idle_scale_down (exported as
    rtpu_node_drains_total{reason}). Returns {ok, node_id, state}."""
    return _req({"kind": "drain_node", "node_id": node_id,
                 "reason": reason, "deadline_s": deadline_s})


def list_jobs() -> List[Dict[str, Any]]:
    """Jobs from the controller's durable job table (reference: `ray list
    jobs`): id, status, entrypoint, returncode, attempt accounting
    (``attempt`` counts every launch, ``attempts_used`` only launches
    that billed the retry budget — preempted/drained attempts are free),
    placement, and a bounded status history. Terminal jobs keep their
    real status/entrypoint/returncode; the table itself rides
    --state-path, so listings survive a controller bounce."""
    return _req({"kind": "job_list"})["jobs"]


def get_job(job_id: str) -> Dict[str, Any]:
    """One job's record from the durable job table (see list_jobs)."""
    resp = _req({"kind": "job_status", "job_id": job_id})
    if resp.get("error"):
        raise ValueError(resp["error"])
    return resp["record"]


def wait_job(job_id: str, after_seq: int = 0,
             wait_s: float = 10.0) -> Dict[str, Any]:
    """Long-poll one job's status cursor (the get_events ``after_seq``
    shape): returns {"record", "seq"} as soon as the record changed past
    ``after_seq``, immediately for terminal jobs, else when ``wait_s``
    expires. Feed ``seq`` back in to follow a job without polling."""
    resp = _req({"kind": "job_wait", "job_id": job_id,
                 "after_seq": after_seq, "wait_s": wait_s})
    if resp.get("error"):
        raise ValueError(resp["error"])
    return resp


def list_events(severity: Optional[str] = None,
                kind: Optional[Any] = None,
                task_id: Optional[str] = None,
                actor_id: Optional[str] = None,
                node_id: Optional[str] = None,
                worker_id: Optional[str] = None,
                since: Optional[float] = None,
                limit: int = 1000) -> List[Dict[str, Any]]:
    """Cluster events (reference: `ray list cluster-events`): structured
    node/actor/task/placement-group/autoscaler lifecycle records — plus
    the hang watchdog's TASK_HUNG / TASK_STRAGGLER findings with their
    captured stacks in ``data["stack"]``. ``severity`` is a minimum level
    (DEBUG/INFO/WARNING/ERROR), ``kind`` one kind or a list, entity ids
    match on prefix, ``since`` is a wall-clock lower bound."""
    return _req({"kind": "get_events", "severity": severity,
                 "kinds": kind, "task_id": task_id, "actor_id": actor_id,
                 "node_id": node_id, "worker_id": worker_id,
                 "since": since, "limit": limit})["events"]


def follow_events(severity: Optional[str] = None,
                  kind: Optional[Any] = None,
                  task_id: Optional[str] = None,
                  actor_id: Optional[str] = None,
                  node_id: Optional[str] = None,
                  worker_id: Optional[str] = None,
                  wait_s: float = 2.0):
    """Generator of cluster events as they happen (the `rtpu events
    --follow` backend). Each poll is an independent long-poll request on
    the session's reconnecting client; the seq cursor survives a
    controller bounce because the event log (and its seq counter) is
    persisted alongside ``--state-path``."""
    import time as _time

    after_seq = None
    while True:
        try:
            r = _req({"kind": "get_events", "severity": severity,
                      "kinds": kind, "task_id": task_id,
                      "actor_id": actor_id, "node_id": node_id,
                      "worker_id": worker_id, "after_seq": after_seq,
                      "wait_s": wait_s if after_seq is not None else 0,
                      "limit": 1000})
        except Exception:
            _time.sleep(min(wait_s, 2.0) or 0.5)
            continue
        if after_seq is None:
            # First poll establishes the cursor: only NEW events stream.
            after_seq = r.get("seq", 0)
            continue
        after_seq = max(after_seq, r.get("seq", after_seq))
        for ev in r.get("events", ()):
            yield ev


def broadcast(object_id: str, node_ids: Optional[List[str]] = None,
              timeout: float = 120.0) -> Dict[str, Any]:
    """Replicate an object's bytes onto N nodes over a pipelined chain
    (the ``ray_tpu.broadcast`` backend, addressable by raw object id from
    operational tooling). The source ships each byte ~once regardless of
    fan-out; consumer-local ``get_locations`` then resolves to the replica
    on the consumer's own host. Returns {ok, replicas, skipped, stats}."""
    return _req({"kind": "broadcast_object", "object_id": object_id,
                 "node_ids": node_ids, "timeout": timeout})


def metrics_address() -> Optional[str]:
    """host:port of the controller's Prometheus /metrics endpoint."""
    state = _req({"kind": "cluster_state"})
    port = state.get("metrics_port")
    if not port:
        return None
    host = ctx.get_worker_context().client.host
    return f"{host}:{port}"


# ------------------------------------------------------- cluster log fetching
# Reference: `ray logs` (python/ray/scripts) + the dashboard log API — any
# worker log on any node is listable, fetchable, and followable through
# the head, with task/actor attribution selecting one task's output.

_LOG_CHUNK = 65536


def list_logs() -> Dict[str, List[Dict[str, Any]]]:
    """Cluster log index: node_id -> [{name, size, mtime}]."""
    return _req({"kind": "list_logs"})


def resolve_log(task_id: Optional[str] = None, actor_id: Optional[str] = None,
                worker_id: Optional[str] = None) -> Dict[str, Any]:
    """Which node/file holds this id's output: {found, node_id, name}."""
    return _req({"kind": "resolve_log", "task_id": task_id,
                 "actor_id": actor_id, "worker_id": worker_id})


def get_log(name: Optional[str] = None, node_id: Optional[str] = None,
            task_id: Optional[str] = None, actor_id: Optional[str] = None,
            worker_id: Optional[str] = None, offset: int = 0,
            max_bytes: int = _LOG_CHUNK,
            wait_s: float = 0.0) -> Dict[str, Any]:
    """One chunk of a worker log: {data, offset, size, eof} (offset is the
    resume cursor). With task_id/actor_id, only that id's attributed
    output is returned (index-backed — no file scan); negative offsets
    count back from the end."""
    return _req({"kind": "get_log", "name": name, "node_id": node_id or "",
                 "task_id": task_id, "actor_id": actor_id,
                 "worker_id": worker_id, "offset": offset,
                 "max_bytes": max_bytes, "wait_s": wait_s})


def get_log_text(name: Optional[str] = None, node_id: Optional[str] = None,
                 task_id: Optional[str] = None,
                 actor_id: Optional[str] = None,
                 worker_id: Optional[str] = None, tail_lines: int = 0,
                 max_bytes: int = 1 << 20) -> str:
    """Convenience fetch (the `rtpu logs` one-shot body): the id's full
    attributed output, or the file's last ``max_bytes`` — optionally cut
    to the final ``tail_lines`` lines."""
    filtered = bool(task_id or actor_id)
    r = get_log(name=name, node_id=node_id, task_id=task_id,
                actor_id=actor_id, worker_id=worker_id,
                offset=0 if filtered else -max_bytes, max_bytes=max_bytes)
    if r.get("error"):
        raise RuntimeError(f"log fetch failed: {r['error']}")
    text = r.get("data", "")
    if tail_lines and tail_lines > 0:
        text = "\n".join(text.splitlines()[-tail_lines:])
        if text:
            text += "\n"
    return text


def follow_log(name: Optional[str] = None, node_id: Optional[str] = None,
               task_id: Optional[str] = None, actor_id: Optional[str] = None,
               worker_id: Optional[str] = None, wait_s: float = 2.0,
               from_start: Optional[bool] = None):
    """Generator of new log chunks (the `rtpu logs --follow` backend).

    Each poll is an independent long-poll request on the session's
    reconnecting client, and ids re-resolve server-side per call — so a
    controller bounce pauses the stream and it resumes once the client
    re-registers and workers re-report their log files.
    """
    import time as _time

    filtered = bool(task_id or actor_id)
    if from_start is None:
        from_start = filtered
    offset = 0 if from_start else -2048
    while True:
        r = get_log(name=name, node_id=node_id, task_id=task_id,
                    actor_id=actor_id, worker_id=worker_id, offset=offset,
                    max_bytes=_LOG_CHUNK, wait_s=wait_s)
        if r.get("error"):
            # File not written yet / agent flapping: keep polling.
            _time.sleep(min(wait_s, 2.0) or 0.5)
            continue
        offset = r.get("offset", offset)
        if r.get("data"):
            yield r["data"]


def _phase_subslices(pev: Dict[str, Any], pid: str, tid: str,
                     task_id: str) -> List[Dict[str, Any]]:
    """Flight-recorder phases -> nested sub-slices on the task's row:
    queue_wait before the worker-side start, then arg_fetch / exec /
    result_store laid end to end from it."""
    out: List[Dict[str, Any]] = []
    phases = pev.get("phases") or {}
    start = pev.get("start_ts")
    if start is None:
        return out

    def sub(name: str, ts: float, dur_s: float) -> None:
        out.append({
            "name": name, "cat": "phase", "ph": "X",
            "ts": ts * 1e6, "dur": max(0.5, dur_s * 1e6),
            "pid": pid, "tid": tid,
            "args": {"task_id": task_id, f"{name}_s": dur_s},
        })

    qw = phases.get("queue_wait_s")
    if qw:
        sub("queue_wait", start - qw, qw)
    cursor = start
    for key, name in (("arg_fetch_s", "arg_fetch"), ("exec_s", "exec"),
                      ("result_store_s", "result_store")):
        d = phases.get(key)
        if d is None:
            continue
        sub(name, cursor, d)
        cursor += d
    return out


def timeline(filename: Optional[str] = None) -> Any:
    """Export task events as a chrome-trace JSON (trace-event format).

    Pairs each task's "running" event with its terminal event into one
    complete ("ph": "X") slice; rows are (node, worker). With the flight
    recorder on (RTPU_TASK_EVENTS), each task slice additionally carries
    nested phase sub-slices (queue_wait / arg_fetch / exec / result_store)
    and a flow arrow ("ph": "s"/"f") linking the driver's submit event to
    the worker's run slice across pid rows; tasks that failed before ever
    running show as instant events ("ph": "i") on their owning node's row.
    Load the file in chrome://tracing or https://ui.perfetto.dev.
    """
    events = _req({"kind": "task_events"})
    starts: Dict[str, Dict[str, Any]] = {}
    submitted: Dict[str, Dict[str, Any]] = {}
    phase_evs: Dict[str, Dict[str, Any]] = {}
    done: List[tuple] = []  # (start_ev, terminal_ev)
    ran: set = set()
    trace: List[Dict[str, Any]] = []
    for ev in events:
        tid = ev["task_id"]
        if ev["event"] == "submitted":
            submitted[tid] = ev
        elif ev["event"] == "running":
            starts[tid] = ev
            ran.add(tid)
        elif ev["event"] == "phases":
            phase_evs[tid] = ev
        elif ev["event"] in ("finished", "failed"):
            if tid in starts:
                done.append((starts.pop(tid), ev))
            elif ev["event"] == "failed" and tid not in ran:
                # Failed before ever running (scheduling/spawn/dependency
                # failure): an instant event on the owning node row, so the
                # failure is visible in the trace at all.
                trace.append({
                    "name": f"{ev.get('label') or tid[:8]} failed",
                    "cat": "task", "ph": "i", "s": "p",
                    "ts": ev["ts"] * 1e6,
                    "pid": (ev.get("node_id") or "driver")[:12],
                    "tid": "failures",
                    "args": {"task_id": tid},
                })
    flow_id = 0
    for s, ev in done:
        tid = s["task_id"]
        pid = (s.get("node_id") or "node")[:12]
        row = (s.get("worker_id") or "worker")[:12]
        pev = phase_evs.get(tid)
        args: Dict[str, Any] = {"task_id": tid, "outcome": ev["event"]}
        if pev is not None:
            args.update(pev.get("phases") or {})
        trace.append(
            {
                "name": s.get("label") or tid[:8],
                "cat": "actor_task" if s.get("actor_id") else "task",
                "ph": "X",
                "ts": s["ts"] * 1e6,
                "dur": max(1.0, (ev["ts"] - s["ts"]) * 1e6),
                "pid": pid,
                "tid": row,
                "args": args,
            }
        )
        if pev is not None:
            trace.extend(_phase_subslices(pev, pid, row, tid))
        sub = submitted.get(tid)
        if sub is not None:
            # The driver's submit slice (its duration IS the scheduling
            # delay) + a flow arrow landing on the worker's run slice.
            flow_id += 1
            sub_ts = sub["ts"] * 1e6
            run_ts = s["ts"] * 1e6
            label = s.get("label") or tid[:8]
            trace.append({
                "name": f"submit {label}", "cat": "task_submit", "ph": "X",
                "ts": sub_ts, "dur": max(1.0, run_ts - sub_ts),
                "pid": "driver", "tid": "submit",
                "args": {"task_id": tid},
            })
            trace.append({"name": "task", "cat": "flow", "ph": "s",
                          "id": flow_id, "ts": sub_ts,
                          "pid": "driver", "tid": "submit"})
            trace.append({"name": "task", "cat": "flow", "ph": "f",
                          "bp": "e", "id": flow_id,
                          "ts": run_ts, "pid": pid, "tid": row})
    # Still-running tasks appear as begin events so they show in the view.
    for tid, s in starts.items():
        trace.append(
            {
                "name": s.get("label") or tid[:8],
                "cat": "task",
                "ph": "B",
                "ts": s["ts"] * 1e6,
                "pid": (s.get("node_id") or "node")[:12],
                "tid": (s.get("worker_id") or "worker")[:12],
            }
        )
    # Serve request spans (the per-request trace plane) share the same
    # clock: each hop becomes a complete slice on the "serve" pid, one
    # row per deployment, so a request's waterfall lines up against the
    # tasks that ran under it.
    try:
        srows = _req({"kind": "serve_requests", "with_spans": True,
                      "limit": 200})
    except Exception:
        srows = []
    for row in srows:
        for sp in row.get("spans") or ():
            try:
                trace.append({
                    "name": sp["name"], "cat": "serve", "ph": "X",
                    "ts": float(sp["start_ts"]) * 1e6,
                    "dur": max(1.0, float(sp.get("dwell_s") or 0) * 1e6),
                    "pid": "serve",
                    "tid": (sp.get("deployment")
                            or row.get("deployment") or "serve"),
                    "args": dict(sp.get("attributes") or {},
                                 request_id=row.get("request_id"),
                                 trace_id=row.get("trace_id"),
                                 status=row.get("status")),
                })
            except Exception:
                continue
    if filename is not None:
        with open(filename, "w") as f:
            json.dump(trace, f)
        return filename
    return trace


def list_serve_requests(*, model: Optional[str] = None,
                        status: Optional[str] = None,
                        min_latency_s: Optional[float] = None,
                        since: Optional[float] = None,
                        limit: int = 100) -> List[Dict[str, Any]]:
    """Finished (and in-flight) serve requests from the controller's
    request ledger (serve/trace.py), newest first. ``model`` filters by
    deployment-name prefix; ``status`` by terminal status (ok / error /
    shed / deadline / cancelled / inflight); ``min_latency_s`` keeps only
    slower requests; ``since`` is a start_ts lower bound. Rows carry the
    terminal record + token stats; fetch one request's hop spans with
    serve_trace()."""
    return _req({"kind": "serve_requests", "model": model,
                 "status": status, "min_latency_s": min_latency_s,
                 "since": since, "limit": limit})


def serve_trace(request_id: str) -> Dict[str, Any]:
    """One request's full trace: the ledger row plus a per-hop
    ``waterfall`` — spans ordered depth-first with ``depth`` for
    indentation and ``self_s`` (the span's dwell minus its children's,
    clamped at zero) so the exclusive times sum to the end-to-end wall.
    ``request_id`` may be a unique prefix. Raises KeyError when the
    ledger has no such request."""
    rows = _req({"kind": "serve_requests", "request_id": request_id,
                 "limit": 1})
    if not rows:
        raise KeyError(f"no serve request {request_id!r} in the ledger")
    row = dict(rows[0])
    spans = sorted(row.get("spans") or (),
                   key=lambda s: s.get("start_ts") or 0)
    by_id = {s.get("span_id"): s for s in spans}
    kids: Dict[str, List[Dict[str, Any]]] = {}
    roots: List[Dict[str, Any]] = []
    for s in spans:
        p = s.get("parent_span_id") or ""
        if p and p in by_id:
            kids.setdefault(p, []).append(s)
        else:
            roots.append(s)
    waterfall: List[Dict[str, Any]] = []

    def walk(s: Dict[str, Any], depth: int) -> None:
        ch = kids.get(s.get("span_id"), ())
        dwell = float(s.get("dwell_s") or 0.0)
        child_sum = sum(float(c.get("dwell_s") or 0.0) for c in ch)
        waterfall.append({
            "name": s.get("name"), "kind": s.get("kind"),
            "span_id": s.get("span_id"),
            "parent_span_id": s.get("parent_span_id") or "",
            "deployment": s.get("deployment") or "",
            "depth": depth, "start_ts": s.get("start_ts"),
            "dwell_s": dwell,
            "self_s": max(0.0, dwell - child_sum),
            "attributes": dict(s.get("attributes") or {}),
        })
        for c in ch:
            walk(c, depth + 1)

    for s in roots:
        walk(s, 0)
    row["waterfall"] = waterfall
    return row


def dag_timeline(filename: Optional[str] = None, *,
                 dag: Optional[str] = None,
                 include_tasks: bool = True,
                 timeout: float = 5.0) -> Any:
    """Chrome-trace export of compiled-DAG stage execution (the channel
    meter's span rings, gathered from every hosting worker).

    Rows are (``dag <id>``, stage): each finished microbatch is one
    complete ("ph": "X") slice whose nested sub-slices split the step
    into recv (waiting on inputs), compute (the user method), blocked
    (writer waiting for ring space — downstream backpressure) and send
    (publishing). With ``include_tasks`` (default) the regular
    ``timeline()`` task trace is merged in, so the dispatch-path tasks
    that fed the pipeline and the channel-plane steps that bypassed the
    controller share one clock in chrome://tracing / Perfetto. Requires
    RTPU_DAG_METER (the default); with the meter off the DAG rows are
    simply empty. ``dag`` filters by dag-id prefix."""
    r = _req({"kind": "dag_timeline", "dag": dag, "timeout": timeout})
    trace: List[Dict[str, Any]] = (
        list(timeline()) if include_tasks else [])
    for sp in r.get("spans", ()):
        try:
            recv = int(sp.get("recv_ns", 0))
            comp = int(sp.get("compute_ns", 0))
            send = int(sp.get("send_ns", 0))
            blocked = int(sp.get("blocked_ns", 0))
            total_ns = recv + comp + send + blocked
            end_us = float(sp["end_s"]) * 1e6
            pid = f"dag {sp['dag']}"
            row = f"{sp['stage']} {sp.get('method') or ''}".strip()
            start_us = end_us - total_ns / 1e3
        except Exception:
            continue
        trace.append({
            "name": f"step {sp.get('seq')}", "cat": "dag_step",
            "ph": "X", "ts": start_us,
            "dur": max(1.0, total_ns / 1e3), "pid": pid, "tid": row,
            "args": {"seq": sp.get("seq"), "recv_ns": recv,
                     "compute_ns": comp, "send_ns": send,
                     "blocked_ns": blocked,
                     "worker_id": sp.get("worker_id")},
        })
        cursor = start_us
        # Phase order mirrors the stage loop: wait for inputs, run the
        # method, wait out backpressure, publish.
        for ns, nm in ((recv, "recv"), (comp, "compute"),
                       (blocked, "blocked"), (send, "send")):
            if ns <= 0:
                continue
            trace.append({
                "name": nm, "cat": "dag_phase", "ph": "X",
                "ts": cursor, "dur": max(0.5, ns / 1e3),
                "pid": pid, "tid": row,
                "args": {"seq": sp.get("seq")},
            })
            cursor += ns / 1e3
    if filename is not None:
        with open(filename, "w") as f:
            json.dump(trace, f)
        return filename
    return trace
