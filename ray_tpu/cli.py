"""Operator CLI: start/stop nodes, inspect state, submit jobs.

Parity: reference python/ray/scripts/scripts.py (`ray start --head`,
`ray start --address`, `ray stop`, `ray status`, `ray summary`, `ray
timeline`) + `ray job submit/status/logs/list/stop` (dashboard job CLI).

Usage (no console-script install needed):

    python -m ray_tpu.cli start --head [--port 6380] [--num-cpus N]
    python -m ray_tpu.cli start --address HOST:PORT [--num-cpus N]
    python -m ray_tpu.cli status  [--address HOST:PORT]
    python -m ray_tpu.cli summary [--address HOST:PORT]
    python -m ray_tpu.cli logs [NAME] [--task-id ID] [--follow|--tail N]
    python -m ray_tpu.cli timeline --out trace.json
    python -m ray_tpu.cli job submit -- python my_script.py
    python -m ray_tpu.cli job logs <job_id>
    python -m ray_tpu.cli stop
"""
from __future__ import annotations

from ray_tpu import flags

import argparse
import json
import os
import signal
import sys
import tempfile
import time

_PIDFILE = os.path.join(tempfile.gettempdir(), "rtpu_head.pid")
_ADDRFILE = os.path.join(tempfile.gettempdir(), "rtpu_head.addr")


def _resolve_address(args) -> str:
    addr = getattr(args, "address", None) or flags.get("RTPU_ADDRESS")
    if not addr and os.path.exists(_ADDRFILE):
        addr = open(_ADDRFILE).read().strip()
    if not addr:
        sys.exit("no cluster address: pass --address, set RTPU_ADDRESS, or "
                 "start a head with `python -m ray_tpu.cli start --head`")
    return addr


def cmd_start(args) -> int:
    if args.head:
        import asyncio

        if getattr(args, "state_path", None):
            flags.set_env("RTPU_STATE_PATH", args.state_path)

        from ray_tpu.core.controller import Controller

        async def run_head():
            controller = Controller(port=args.port)
            host, port = await controller.start()
            from ray_tpu.util.accelerators import (
                detect_node_accelerator_resources,
            )

            res = {"CPU": float(args.num_cpus or os.cpu_count() or 1)}
            # Same vendor-agnostic autodetection as api.init(): accelerator
            # counts plus pod-scoped custom resources — a CLI-started head
            # must schedule identically to an init()-started one.
            res.update(detect_node_accelerator_resources())
            if args.resources:
                res.update(json.loads(args.resources))
            # ensure_head_node: a restart with --state-path reuses the
            # persisted head-node identity so surviving workers of the
            # previous controller can reconnect under their node id.
            controller.ensure_head_node(res, labels={"head": "1"})
            addr = f"{host}:{port}"
            with open(_ADDRFILE, "w") as f:
                f.write(addr)
            with open(_PIDFILE, "w") as f:
                f.write(str(os.getpid()))
            print(f"rtpu head started at {addr}")
            print(f"  connect with: ray_tpu.init(address={addr!r})")
            print(f"  metrics:      http://{host}:{controller.metrics_port}/metrics")
            stop = asyncio.Event()
            loop = asyncio.get_running_loop()
            for s in (signal.SIGTERM, signal.SIGINT):
                try:
                    loop.add_signal_handler(s, stop.set)
                except NotImplementedError:
                    pass
            await stop.wait()
            await controller.shutdown()
            # Only OUR files: a newer head may have overwritten them, and
            # removing its address would strand its clients (compare
            # content before unlink, reference `ray stop` semantics).
            for path, mine in ((_ADDRFILE, addr),
                               (_PIDFILE, str(os.getpid()))):
                try:
                    if open(path).read().strip() == mine:
                        os.unlink(path)
                except OSError:
                    pass

        asyncio.run(run_head())
        return 0
    # worker node: join an existing cluster as a host agent
    address = _resolve_address(args)
    from ray_tpu.core.host_agent import _amain

    class A:
        controller = address
        resources = json.dumps(
            {"CPU": float(args.num_cpus or os.cpu_count() or 1)})
        labels = ""
        host_id = ""
        port = 0

    import asyncio

    return asyncio.run(_amain(A()))


def cmd_stop(args) -> int:
    if not os.path.exists(_PIDFILE):
        print("no head pidfile; nothing to stop")
        return 0
    pid = int(open(_PIDFILE).read().strip() or 0)
    try:
        os.kill(pid, signal.SIGTERM)
        print(f"sent SIGTERM to head (pid {pid})")
    except ProcessLookupError:
        print("head already gone")
    for f in (_PIDFILE, _ADDRFILE):
        try:
            os.unlink(f)
        except OSError:
            pass
    return 0


def _connect(args):
    import ray_tpu

    ray_tpu.init(address=_resolve_address(args))
    return ray_tpu


def cmd_status(args) -> int:
    rt = _connect(args)
    from ray_tpu.core import context as ctx

    state = ctx.get_worker_context().client.request({"kind": "cluster_state"})
    # Per-node utilization table (reference: the `ray status` node
    # report): the controller already holds host CPU%/mem% from agent
    # heartbeats — surface them instead of burying them in the JSON.
    # Human output goes to stderr: stdout stays pure JSON so
    # `rtpu status | jq` keeps working.
    nodes = state.get("nodes") or []
    if nodes:
        print(f"{'NODE':14} {'STATE':10} {'CPU%':>6} {'MEM%':>6} "
              f"{'WORKERS':>8} {'STORE':>13} {'SPILL':>9}  RESOURCES",
              file=sys.stderr)
        for n in sorted(nodes, key=lambda n: n.get("index", 0)):
            st = n.get("state", "alive" if n.get("alive") else "dead")
            if st in ("draining", "drained") and n.get("drain_reason"):
                st = f"{st[:4]}:{n['drain_reason'][:5]}"
            # Object-store occupancy: arena used/capacity + spilled bytes
            # on disk (the census tiers, per node).
            arena = n.get("arena") or {}
            store = (f"{_fmt_bytes(arena.get('used', 0))}"
                     f"/{_fmt_bytes(arena.get('capacity', 0))}"
                     if arena.get("capacity") else "-")
            spill = n.get("spill") or {}
            spill_s = (_fmt_bytes(spill.get("bytes", 0))
                       if spill.get("bytes") else "-")
            print(f"{n['node_id'][:12]:14} {st:10} "
                  f"{n.get('cpu_percent') or 0.0:>6.1f} "
                  f"{(n.get('mem_fraction') or 0.0) * 100:>6.1f} "
                  f"{n.get('num_workers', 0):>8} {store:>13} "
                  f"{spill_s:>9}  "
                  f"{json.dumps(n.get('resources', {}))}", file=sys.stderr)
        print(file=sys.stderr)
    # Compiled DAGs with live channel plans: their steady-state dispatch
    # bypasses the controller, so this registry is the only place an
    # operator can see which pipelines hold resident actor loops.
    dags = state.get("compiled_dags") or {}
    if dags:
        print(f"{'COMPILED DAG':14} {'STAGES':>6} {'DEPTH':>6} "
              f"{'RECOV':>6}  EDGES", file=sys.stderr)
        for did, d in sorted(dags.items()):
            kinds = d.get("edges") or {}
            summary = ",".join(
                f"{eid}:{kind}" for eid, kind in sorted(kinds.items()))
            recov = str(d.get("recoveries", 0))
            if d.get("recovering"):
                recov += "*"  # a recovery is in flight right now
            print(f"{did[:12]:14} {d.get('stages', 0):>6} "
                  f"{d.get('depth', 0):>6} {recov:>6}  {summary}",
                  file=sys.stderr)
        print(file=sys.stderr)
    print(json.dumps(state, indent=1, default=str))
    # Quote recent hang/straggler findings: the watchdog's whole point is
    # that a silently hung step shows up where operators already look.
    try:
        from ray_tpu.util import state as state_api

        hangs = state_api.list_events(
            kind=["TASK_HUNG", "TASK_STRAGGLER"], limit=5)
        if hangs:
            print("\nrecent hang/straggler events "
                  "(`rtpu events --kind TASK_HUNG` for stacks):",
                  file=sys.stderr)
            for ev in hangs:
                print(f"  {_fmt_event(ev)}", file=sys.stderr)
    except Exception:
        pass
    rt.shutdown()
    return 0


def _fmt_event(ev, stacks: bool = False) -> str:
    """One human line per cluster event (the `rtpu events` row shape)."""
    t = time.strftime("%H:%M:%S", time.localtime(ev.get("ts", 0)))
    ids = " ".join(
        f"{k.split('_')[0]}={ev[k][:12]}"
        for k in ("task_id", "actor_id", "worker_id", "node_id")
        if ev.get(k))
    line = (f"[{t}] {ev.get('severity', 'INFO'):7} "
            f"{ev.get('kind', '?'):22} {ev.get('message', '')}"
            + (f"  ({ids})" if ids else ""))
    # DAG recoveries carry the structured cause (`rtpu events --kind
    # DAG_RECOVERED` answers "what killed it last time" directly).
    cause = (ev.get("data") or {}).get("cause")
    if cause:
        line += f"  cause={cause}"
    stack = (ev.get("data") or {}).get("stack")
    if stacks and stack:
        indented = "\n".join("    " + ln for ln in stack.splitlines())
        line += f"\n{indented}"
    return line


def cmd_events(args) -> int:
    """`rtpu events` (reference: `ray list cluster-events`): the cluster
    event feed — node/actor/task lifecycle, autoscaler decisions, and the
    hang watchdog's TASK_HUNG/TASK_STRAGGLER findings (--stacks prints
    their captured all-thread stacks). --follow streams new events."""
    rt = _connect(args)
    from ray_tpu.util import state

    sel = dict(severity=args.severity, kind=args.kind or None,
               task_id=args.task_id, actor_id=args.actor_id,
               node_id=args.node, worker_id=args.worker_id)
    # With an id filter the stacks are usually what you came for.
    stacks = args.stacks or bool(args.task_id or args.actor_id)
    try:
        if args.follow:
            try:
                for ev in state.follow_events(**sel):
                    print(_fmt_event(ev, stacks=stacks), flush=True)
            except KeyboardInterrupt:
                pass
            return 0
        since = time.time() - args.since if args.since else None
        events = state.list_events(**sel, since=since, limit=args.limit)
        for ev in events:
            print(_fmt_event(ev, stacks=stacks))
        if not events:
            print("no matching events")
        return 0
    finally:
        rt.shutdown()


def cmd_stack(args) -> int:
    """`rtpu stack` (reference: `ray stack`): on-demand all-thread stack
    dump from live workers, over the same profile_workers fan-out the
    dashboard and the hang watchdog use. Filter with --worker-id / --node
    (id prefixes)."""
    rt = _connect(args)
    from ray_tpu.util import state

    try:
        res = state.profile_workers(timeout=args.timeout)
        workers = res.get("workers", {})
        if args.node:
            rows = state.list_workers()
            on_node = {w["worker_id"] for w in rows
                       if (w.get("node_id") or "").startswith(args.node)}
            workers = {w: t for w, t in workers.items() if w in on_node}
        if args.worker_id:
            workers = {w: t for w, t in workers.items()
                       if w.startswith(args.worker_id)}
        for wid, text in sorted(workers.items()):
            print(f"=== worker {wid} ===")
            print(text)
        print(f"{len(workers)} worker(s) answered "
              f"({res.get('requested', 0)} asked; busy-in-native-code "
              f"workers miss the window)")
        return 0
    finally:
        rt.shutdown()


_SPARK_BARS = "▁▂▃▄▅▆▇█"


def _spark(vals) -> str:
    """Unicode sparkline over a value series (the `rtpu top` history
    cells)."""
    vals = list(vals)
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    span = (hi - lo) or 1.0
    return "".join(
        _SPARK_BARS[int((v - lo) / span * (len(_SPARK_BARS) - 1))]
        for v in vals)


def _fmt_bytes(n) -> str:
    n = float(n or 0)
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if n < 1024 or unit == "TB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}TB"


def _top_frame(window: float = 120.0, spark_points: int = 30) -> str:
    """One `rtpu top` frame: cluster header, node table, per-label task
    rates + exec p99 with history sparklines, object-store bytes, firing
    alerts, event tail — all from cluster_state + the telemetry ring
    (query_metrics), zero external services."""
    from ray_tpu.core import context as ctx
    from ray_tpu.util import state as state_api

    cs = ctx.get_worker_context().client.request({"kind": "cluster_state"})
    since = time.time() - window
    lines = []
    nodes = cs.get("nodes") or []
    alive = sum(1 for n in nodes if n.get("alive"))
    lines.append(
        f"ray_tpu top — uptime {cs.get('uptime_s', 0):.0f}s · "
        f"nodes {alive}/{len(nodes)} alive · "
        f"workers {cs.get('num_workers', 0)} · "
        f"actors {len(cs.get('actors') or {})} · "
        f"pending {cs.get('pending_tasks', 0)}")
    try:
        firing = state_api.list_alerts().get("firing") or []
    except Exception:
        firing = []
    for a in firing:
        tags = ",".join(f"{k}={v}" for k, v in sorted(a["tags"].items()))
        lines.append(f"!! ALERT FIRING: {a['alert']}"
                     + (f" {{{tags}}}" if tags else "")
                     + f" value={a.get('value', 0):.4g}")
    lines.append("")
    lines.append(f"{'NODE':14} {'STATE':10} {'CPU%':>6} {'MEM%':>6} "
                 f"{'WORKERS':>8} {'TPU':>5}")
    for n in sorted(nodes, key=lambda n: n.get("index", 0)):
        st = n.get("state", "alive" if n.get("alive") else "dead")
        tpu = (n.get("resources") or {}).get("TPU", 0)
        lines.append(
            f"{n['node_id'][:12]:14} {st:10} "
            f"{n.get('cpu_percent') or 0.0:>6.1f} "
            f"{(n.get('mem_fraction') or 0.0) * 100:>6.1f} "
            f"{n.get('num_workers', 0):>8} {tpu:>5.0f}")

    def q(**kw):
        try:
            resp = state_api.query_metrics(since=since, **kw)
            return resp.get("series", []) if resp.get("enabled") else None
        except Exception:
            return None

    rate = q(name="rtpu_task_exec_s", stat="rate", window_s=30.0)
    p99 = q(name="rtpu_task_exec_s", stat="p99", window_s=window)
    if rate is None:
        lines.append("")
        lines.append("telemetry disabled (RTPU_TSDB=0) — task-rate and "
                     "history views need the controller TSDB")
    else:
        p99_by_tags = {tuple(sorted(s["tags"].items())): s for s in p99 or []}
        lines.append("")
        lines.append(f"{'TASK LABEL':24} {'RATE/S':>8} {'EXEC P99':>10}  "
                     f"HISTORY (rate, {window:.0f}s)")
        for ser in sorted(rate, key=lambda s: str(s["tags"])):
            label = ser["tags"].get("label", "?")
            pts = [v for _, v in ser["points"]]
            cur = pts[-1] if pts else 0.0
            pser = p99_by_tags.get(tuple(sorted(ser["tags"].items())))
            pv = (pser["points"][-1][1]
                  if pser and pser["points"] else 0.0)
            lines.append(f"{label[:24]:24} {cur:>8.1f} {pv:>9.4f}s  "
                         f"{_spark(pts[-spark_points:])}")
        if not rate:
            lines.append("  (no task history yet)")
        arena = q(name="rtpu_arena_used_bytes") or []
        for ser in arena:
            pts = [v for _, v in ser["points"]]
            if pts:
                lines.append("")
                lines.append(
                    f"object store  used {_fmt_bytes(pts[-1]):>10}  "
                    f"{_spark(pts[-spark_points:])}")
    # Serve plane: per-deployment pools with the controller's polled
    # signals (queue depth, occupancy) + telemetry TTFT/token rates.
    try:
        import ray_tpu as _rt

        _ctrl = _rt.get_actor("SERVE_CONTROLLER")
        sstats = _rt.get(_ctrl.get_serve_stats.remote(), timeout=2.0)
    except Exception:
        sstats = None
    if sstats:
        ttft = {s["tags"].get("model"): s["points"][-1][1]
                for s in (q(name="rtpu_serve_ttft_s", stat="p99",
                            window_s=60.0) or []) if s["points"]}
        toks = {s["tags"].get("model"): s["points"][-1][1]
                for s in (q(name="rtpu_serve_decode_tokens_total") or [])
                if s["points"]}
        itl = {s["tags"].get("model"): s["points"][-1][1]
               for s in (q(name="rtpu_serve_itl_s", stat="p99",
                           window_s=60.0) or []) if s["points"]}
        # SLO miss rate = misses/s over finished-requests/s (both rate
        # stats over the same window), per deployment.
        reqr = {}
        for s in (q(name="rtpu_serve_requests_total", stat="rate",
                    window_s=60.0) or []):
            if s["points"]:
                dep = s["tags"].get("deployment")
                reqr[dep] = reqr.get(dep, 0.0) + s["points"][-1][1]
        missr = {s["tags"].get("deployment"): s["points"][-1][1]
                 for s in (q(name="rtpu_serve_slo_miss_total",
                             stat="rate", window_s=60.0) or [])
                 if s["points"]}
        lines.append("")
        lines.append(f"{'SERVE DEPLOYMENT':22} {'POOL':8} {'REPL':>5} "
                     f"{'DRAIN':>6} {'QUEUE':>6} {'OCC%':>6} "
                     f"{'TTFT P99':>9} {'ITL P99':>9} {'TOK/S':>7} "
                     f"{'SLO-MISS%':>9}")
        for dname in sorted(sstats):
            d = sstats[dname]
            base = dname.split("-")[0]
            tv = ttft.get(dname, ttft.get(base))
            kv = toks.get(dname, toks.get(base))
            iv = itl.get(dname, itl.get(base))
            rr = reqr.get(dname, reqr.get(base))
            mr = missr.get(dname, missr.get(base, 0.0))
            miss_pct = (min(100.0, mr / rr * 100.0)
                        if rr else (100.0 if mr else None))
            repl = f"{d.get('replicas', 0)}/{d.get('target', 0)}"
            lines.append(
                f"{dname[:22]:22} {str(d.get('pool', 'main'))[:8]:8} "
                f"{repl:>5} {d.get('draining', 0):>6} "
                f"{d.get('queue_depth', 0.0):>6.0f} "
                f"{d.get('occupancy', 0.0) * 100:>6.1f} "
                + (f"{tv:>8.3f}s" if tv is not None else f"{'-':>9}")
                + (f" {iv * 1e3:>6.1f}ms" if iv is not None
                   else f" {'-':>9}")
                + (f" {kv:>7.1f}" if kv is not None else f" {'-':>7}")
                + (f" {miss_pct:>9.1f}" if miss_pct is not None
                   else f" {'-':>9}"))
    # Data plane: per-operator throughput from the streaming executor's
    # live rtpu_data_operator_* families (Dataset.stats() is the
    # per-run report; this is the cluster-wide cumulative view).
    dblocks = q(name="rtpu_data_operator_blocks_total") or []
    if dblocks:
        def _last_by(name, **want):
            out = {}
            for s2 in q(name=name) or []:
                tg = s2["tags"]
                if s2["points"] and all(tg.get(k) == v
                                        for k, v in want.items()):
                    out[tg.get("operator", "?")] = s2["points"][-1][1]
            return out

        wall = _last_by("rtpu_data_operator_seconds_total", phase="wall")
        udf = _last_by("rtpu_data_operator_seconds_total", phase="udf")
        bp = _last_by("rtpu_data_operator_seconds_total",
                      phase="backpressure")
        byt = _last_by("rtpu_data_operator_bytes_total", dir="out")
        rws = _last_by("rtpu_data_operator_rows_total", dir="out")
        lines.append("")
        lines.append(f"{'DATA OPERATOR':24} {'BLOCKS':>8} "
                     f"{'ROWS OUT':>10} {'BYTES OUT':>10} {'WALL':>8} "
                     f"{'UDF':>8} {'BP WAIT':>8}")
        for ser in sorted(dblocks, key=lambda s: str(s["tags"])):
            op = ser["tags"].get("operator", "?")
            pts = [v for _, v in ser["points"]]
            lines.append(
                f"{op[:24]:24} {pts[-1] if pts else 0:>8.0f} "
                f"{rws.get(op, 0):>10.0f} "
                f"{_fmt_bytes(byt.get(op, 0)):>10} "
                f"{wall.get(op, 0):>7.1f}s {udf.get(op, 0):>7.1f}s "
                f"{bp.get(op, 0):>7.1f}s")
    # Channel plane: compiled DAGs whose steady-state dispatch bypasses
    # the controller entirely — steps/s, recovery state and the
    # bottleneck verdict come from the channel meter's rollup
    # (`rtpu dag stats` has the full stages×edges view).
    try:
        dag_rows = ctx.get_worker_context().client.request(
            {"kind": "list_state", "what": "dags", "limit": 100})
    except Exception:
        dag_rows = []
    if dag_rows:
        lines.append("")
        lines.append(f"{'COMPILED DAG':14} {'STAGES':>6} {'DEPTH':>6} "
                     f"{'STEPS/S':>8} {'RECOV':>6}  BOTTLENECK")
        for d in sorted(dag_rows, key=lambda d: d["dag_id"]):
            methods = {f"s{s.get('idx')}": s.get("method", "")
                       for s in d.get("stages") or ()}
            bn = d.get("bottleneck")
            verdict = (f"{bn} {methods.get(bn, '')}".strip()
                       if bn else "-")
            recov = str(d.get("recoveries", 0))
            if d.get("recovering"):
                recov += "*"
                verdict = "(recovering)"
            sps = d.get("steps_per_s")
            lines.append(
                f"{d['dag_id'][:12]:14} "
                f"{len(d.get('stages') or ()):>6} "
                f"{d.get('depth', 0):>6} "
                + (f"{sps:>8.1f}" if sps is not None else f"{'-':>8}")
                + f" {recov:>6}  {verdict}")
    lines.append("")
    try:
        events = state_api.list_events(limit=6)
    except Exception:
        events = []
    lines.append("EVENTS")
    for ev in events[-6:]:
        lines.append("  " + _fmt_event(ev))
    if not events:
        lines.append("  (none)")
    return "\n".join(lines)


def _bar(frac, width: int = 10) -> str:
    """Fixed-width busy bar for the `rtpu dag stats` phase cells."""
    frac = max(0.0, min(1.0, float(frac or 0.0)))
    n = int(round(frac * width))
    return "█" * n + "·" * (width - n)


def _render_dag_stats(rows, state_api) -> str:
    """One `rtpu dag stats` frame: per compiled DAG, a stages×phases busy
    table (recv=starved / compute / send bars from the channel meter's
    busy-fraction gauges), a per-edge ring table (items/bytes/occupancy/
    lag/writer-blocked), and THE bottleneck verdict
    (dag.meter.attribute_bottleneck, computed controller-side)."""
    if not rows:
        return ("no compiled DAGs registered "
                "(compile a pipeline with ray_tpu.dag.compile first)")
    # Per-stage steps/s from the telemetry ring; one query covers every
    # DAG (tags carry dag+stage).
    stage_rate = {}
    try:
        resp = state_api.query_metrics(name="rtpu_dag_stage_steps_total")
        for ser in (resp.get("series") or ()) if resp.get("enabled") else ():
            pts = ser.get("points") or ()
            if pts:
                tg = ser["tags"]
                stage_rate[(tg.get("dag"), tg.get("stage"))] = pts[-1][1]
    except Exception:
        pass
    lines = []
    for d in rows:
        short = d["dag_id"][:12]
        busy = d.get("stage_busy") or {}
        edges = d.get("edge_stats") or {}
        bn = d.get("bottleneck")
        methods = {f"s{s.get('idx')}": s.get("method", "")
                   for s in d.get("stages") or ()}
        recov = str(d.get("recoveries", 0))
        if d.get("recovering"):
            recov += "*"
        sps = d.get("steps_per_s")
        lines.append(
            f"DAG {short}  stages {len(d.get('stages') or ())}  "
            f"depth {d.get('depth', 0)}  recoveries {recov}  "
            + (f"steps/s {sps:.1f}" if sps is not None else "steps/s -"))
        if bn is not None:
            b = busy.get(bn) or {}
            score = b.get("compute", 0.0) + b.get("send", 0.0)
            lines.append(
                f"  bottleneck: {bn} {methods.get(bn, '')} "
                f"(compute+send {score * 100:.0f}% of wall — this stage "
                f"bounds throughput; starved stages are its victims)")
        else:
            lines.append(
                "  (no meter samples yet — RTPU_DAG_METER=0, or the "
                "pipeline has not stepped since the last metrics flush)")
        if busy:
            lines.append(f"  {'STAGE':6} {'METHOD':16} {'STEPS/S':>8}  "
                         f"{'RECV(STARVED)':16} {'COMPUTE':16} "
                         f"{'SEND':16}")
            for stage in sorted(busy):
                ph = busy[stage]
                r = stage_rate.get((short, stage))
                cells = " ".join(
                    f"{_bar(ph.get(p, 0.0))} {ph.get(p, 0.0) * 100:>3.0f}%"
                    for p in ("recv", "compute", "send"))
                mark = "  << bottleneck" if stage == bn else ""
                lines.append(
                    f"  {stage:6} {methods.get(stage, '?')[:16]:16} "
                    + (f"{r:>8.1f}" if r is not None else f"{'-':>8}")
                    + f"  {cells}{mark}")
        if edges:
            kinds = d.get("edges") or {}
            lines.append(f"  {'EDGE':6} {'KIND':7} {'ITEMS':>10} "
                         f"{'BYTES':>10} {'OCC':>5} {'LAG':>5}  "
                         f"WRITER-BLOCKED")
            for eid in sorted(edges):
                e = edges[eid]
                bf = e.get("blocked_fraction", 0.0)
                lines.append(
                    f"  {eid:6} {str(kinds.get(eid, '?'))[:7]:7} "
                    f"{e.get('items', 0):>10.0f} "
                    f"{_fmt_bytes(e.get('bytes', 0)):>10} "
                    f"{e.get('occupancy', 0):>5.0f} "
                    f"{e.get('lag', 0):>5.0f}  {_bar(bf)} {bf * 100:.0f}%")
        lines.append("")
    return "\n".join(lines).rstrip()


def cmd_dag(args) -> int:
    """`rtpu dag stats [DAG] [--watch]` / `rtpu dag timeline`: the
    channel-meter consumers. Stats renders the stages×edges busy view
    with the bottleneck verdict; timeline writes the per-step chrome
    trace (state.dag_timeline) for chrome://tracing / Perfetto."""
    rt = _connect(args)
    from ray_tpu.util import state as state_api

    try:
        if args.dag_cmd == "timeline":
            state_api.dag_timeline(args.out, dag=args.dag)
            print(f"wrote {args.out} (open in chrome://tracing or "
                  f"ui.perfetto.dev)")
            return 0

        def frame() -> str:
            rows = state_api.list_compiled_dags()
            if args.dag:
                rows = [r for r in rows
                        if r["dag_id"].startswith(args.dag)]
                if not rows:
                    return f"no compiled DAG matches {args.dag!r}"
            return _render_dag_stats(rows, state_api)

        if args.watch:
            while True:
                sys.stdout.write("\x1b[2J\x1b[H" + frame() + "\n")
                sys.stdout.flush()
                time.sleep(args.interval)
        print(frame())
        return 0
    except KeyboardInterrupt:
        return 0
    finally:
        rt.shutdown()


def cmd_top(args) -> int:
    """`rtpu top` (reference: the dashboard's live cluster view / `htop`
    for the cluster): a refreshing terminal view of nodes, per-label task
    rates + exec p99 with sparkline history, object-store bytes, firing
    alerts, and the event tail — served entirely from the controller's
    in-process telemetry ring."""
    rt = _connect(args)
    try:
        if args.once:
            print(_top_frame(window=args.window))
            return 0
        while True:
            frame = _top_frame(window=args.window)
            # Clear + home; one write so the frame never tears.
            sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
            sys.stdout.flush()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0
    finally:
        rt.shutdown()


def cmd_profile(args) -> int:
    """`rtpu profile` (reference: py-spy flamegraphs via the dashboard /
    `ray stack --native`): sample wall-clock stacks across the targeted
    workers for --duration seconds, merge into one cluster-wide profile,
    write a self-contained flamegraph HTML."""
    rt = _connect(args)
    from ray_tpu.util import state

    try:
        res = state.profile(
            duration=args.duration, task_id=args.task_id,
            actor_id=args.actor_id, node_id=args.node,
            worker_id=args.worker_id, hz=args.hz)
        if res.get("error"):
            print(f"profile failed: {res['error']}", file=sys.stderr)
            return 1
        stacks = res.get("stacks") or {}
        from ray_tpu.core import profiler

        meta = (f"{res.get('samples', 0)} samples over "
                f"{res.get('duration', 0):.1f}s at {res.get('hz', 0):.0f}Hz "
                f"from {len(res.get('workers') or {})} worker(s)")
        profiler.save_flamegraph(args.out, stacks,
                                 title="rtpu cluster profile", meta=meta)
        if args.collapsed_out:
            with open(args.collapsed_out, "w") as f:
                f.write(profiler.to_collapsed_text(stacks))
        print(f"{meta} -> {args.out}", file=sys.stderr)
        # The terminal gets the hot leaves (self-heavy stacks), the HTML
        # the full picture.
        top = sorted(stacks.items(), key=lambda kv: -kv[1])[:5]
        for key, n in top:
            leaf = key.rsplit(";", 1)[-1]
            print(f"  {n:>6}  {leaf}", file=sys.stderr)
        return 0
    finally:
        rt.shutdown()


def cmd_summary(args) -> int:
    rt = _connect(args)
    from ray_tpu.util import state

    if getattr(args, "breakdown", False):
        rows = state.summarize_tasks(breakdown=True)
        if not rows:
            print("no phase events recorded yet "
                  "(is RTPU_TASK_EVENTS enabled?)")
        else:
            print(f"{'LABEL':28} {'PHASE':20} {'COUNT':>7} "
                  f"{'MEAN_MS':>9} {'P50_MS':>9} {'P99_MS':>9}")
            for label in sorted(rows):
                for phase, st in rows[label].items():
                    print(f"{label[:28]:28} {phase:20} {st['count']:>7} "
                          f"{st['mean'] * 1e3:>9.2f} "
                          f"{st['p50'] * 1e3:>9.2f} "
                          f"{st['p99'] * 1e3:>9.2f}")
    else:
        print(json.dumps(state.summarize_tasks(), indent=1))
    rt.shutdown()
    return 0


def cmd_drain(args) -> int:
    """`rtpu drain NODE` (reference: `ray drain-node`): graceful node
    departure — stop scheduling, migrate actors with state, give running
    tasks the deadline, re-replicate sole-copy objects, then release the
    node. NODE may be a unique node-id prefix from `rtpu status`."""
    rt = _connect(args)
    from ray_tpu.util import state

    try:
        res = state.drain_node(args.node, reason=args.reason,
                               deadline_s=args.deadline)
        if not res.get("ok"):
            print(f"drain failed: {res.get('error', 'unknown error')}")
            return 1
        print(f"node {res['node_id']} -> {res['state']} "
              f"(reason={args.reason})")
        if args.wait:
            deadline = time.monotonic() + args.wait
            from ray_tpu.core import context as ctx

            while time.monotonic() < deadline:
                nodes = ctx.get_worker_context().client.request(
                    {"kind": "cluster_state"})["nodes"]
                row = next((n for n in nodes
                            if n["node_id"] == res["node_id"]), None)
                if row is None or row.get("state") in ("drained", "dead"):
                    print(f"node {res['node_id']} drained")
                    return 0
                time.sleep(0.3)
            print("drain still in progress (deadline not reached)")
        return 0
    finally:
        rt.shutdown()


def cmd_memory(args) -> int:
    """Cluster object census (reference: `ray memory` /
    `ray summary objects`): the object directory joined with every live
    process's ownership shard, grouped by owner/tier/node/callsite with a
    per-tier byte breakdown inside each group. Dead shards are reported
    as error lines; survivors' totals still aggregate."""
    rt = _connect(args)
    from ray_tpu.util import state as state_api

    s = state_api.summarize_objects(min_size=args.min_size,
                                    limit=args.limit)
    if not s.get("enabled", True):
        for err in s.get("errors") or ():
            print(err, file=sys.stderr)
        rt.shutdown()
        return 1
    print(f"objects: {s['num_objects']}  "
          f"total: {_fmt_bytes(s['total_bytes'])}  "
          f"shards: {s.get('shards', 0)}/{s.get('requested', 0)}")
    for err in s.get("errors") or ():
        print(f"shard error: {err}", file=sys.stderr)
    # Ground truth next to attribution: census bytes vs what the arenas
    # and spill dirs actually hold — a big gap means unattributed memory.
    for nid, st in sorted((s.get("arenas") or {}).items()):
        used, cap = st.get("used", 0), st.get("capacity", 0)
        print(f"arena {nid[:8]}: {_fmt_bytes(used)}/{_fmt_bytes(cap)} "
              f"({st.get('objects', 0)} objects)")
    for nid, st in sorted((s.get("spill") or {}).items()):
        if st and st.get("bytes"):
            print(f"spill {nid[:8]}: {_fmt_bytes(st['bytes'])} "
                  f"({st.get('files', 0)} files)")
    groups = (s.get("groups") or {}).get(args.group_by) or {}
    if groups:
        print()
        print(f"{args.group_by.upper():28} {'BYTES':>12} {'COUNT':>7}  "
              f"TIERS")
        for key, g in sorted(groups.items(),
                             key=lambda kv: -kv[1]["bytes"]):
            tiers = " ".join(
                f"{t}={_fmt_bytes(b)}"
                for t, b in sorted(g["tiers"].items(),
                                   key=lambda kv: -kv[1]))
            print(f"{str(key)[:28]:28} {_fmt_bytes(g['bytes']):>12} "
                  f"{g['count']:>7}  {tiers}")
    rows = s.get("objects") or []  # server-ranked largest-first
    if rows:
        print()
        print(f"{'OBJECT':34} {'SIZE':>10} {'TIER':8} {'NODE':10} "
              f"{'OWNER':16} {'AGE':>7}  CALLSITE")
        for o in rows:
            cs = o.get("callsite") or ""
            print(f"{o['object_id'][:32]:34} "
                  f"{_fmt_bytes(o['size']):>10} "
                  f"{(o.get('tier') or '?'):8} "
                  f"{(o.get('node_id') or '')[:8]:10} "
                  f"{(o.get('owner') or '?')[:16]:16} "
                  f"{o.get('age_s', 0):>6.0f}s  {cs[-40:]}")
    rt.shutdown()
    return 0


def cmd_logs(args) -> int:
    """`rtpu logs` (reference: the `ray logs` CLI + dashboard log API):
    list worker log files cluster-wide, fetch one file (or one task's /
    actor's attributed output) from whichever node holds it, or --follow
    a live stream of new lines."""
    rt = _connect(args)
    from ray_tpu.util import state

    try:
        sel = {"name": args.name, "node_id": args.node,
               "task_id": args.task_id, "actor_id": args.actor_id,
               "worker_id": args.worker_id}
        if not any(sel.values()):
            listing = state.list_logs()
            for nid in sorted(listing):
                print(f"node {nid}")
                for f in listing[nid]:
                    print(f"  {f['name']:<32} {f['size']:>12} bytes")
            return 0
        if args.follow:
            try:
                for chunk in state.follow_log(**sel):
                    sys.stdout.write(chunk)
                    sys.stdout.flush()
            except KeyboardInterrupt:
                pass
            return 0
        text = state.get_log_text(**sel, tail_lines=args.tail)
        sys.stdout.write(text)
        if text and not text.endswith("\n"):
            sys.stdout.write("\n")
        return 0
    finally:
        rt.shutdown()


def cmd_serve(args) -> int:
    """`rtpu serve run|status|shutdown` (reference: the `serve` CLI,
    python/ray/serve/scripts.py — run imports `module:app`, deploys it
    with the HTTP proxy, and blocks)."""
    rt = _connect(args)
    from ray_tpu import serve

    try:
        if args.serve_cmd == "run":
            import importlib

            mod_name, _, attr = args.target.partition(":")
            sys.path.insert(0, os.getcwd())
            app = getattr(importlib.import_module(mod_name), attr or "app")
            print(f"serving {args.target} on :{args.port} (ctrl-c to stop)")
            serve.run(app, _http=True, http_port=args.port, blocking=True)
            return 0
        if args.serve_cmd == "status":
            st = serve.status()
            if st is None:
                print("serve is not running")
            elif not st:
                print("serve is running with no deployments")
            else:
                print(json.dumps(st, indent=1, default=str))
            return 0
        if args.serve_cmd == "shutdown":
            serve.shutdown()
            print("serve shut down")
            return 0
        if args.serve_cmd == "requests":
            from ray_tpu.util import state

            since = (time.time() - args.since_s) if args.since_s else None
            rows = state.list_serve_requests(
                model=args.model, status=args.status,
                min_latency_s=args.min_latency_s, since=since,
                limit=args.limit)
            if not rows:
                print("no matching requests in the ledger")
                return 0
            print(f"{'REQUEST':18} {'DEPLOYMENT':16} {'PROTO':6} "
                  f"{'STATUS':9} {'WALL':>9} {'TOKENS':>6} "
                  f"{'ITL P99':>9} {'SLO':>4}  ERROR")
            for r in rows:
                wall = r.get("wall_s")
                itl = r.get("itl_p99_s")
                print(
                    f"{r['request_id'][:18]:18} "
                    f"{(r.get('deployment') or '-')[:16]:16} "
                    f"{(r.get('proto') or '-')[:6]:6} "
                    f"{(r.get('status') or '?')[:9]:9} "
                    + (f"{wall * 1e3:>8.1f}m" if wall is not None
                       else f"{'-':>9}")
                    + f" {r.get('tokens', '-'):>6}"
                    + (f" {itl * 1e3:>8.2f}m" if itl is not None
                       else f" {'-':>9}")
                    + f" {'MISS' if r.get('slo_miss') else '-':>4}"
                    + f"  {(r.get('error') or '')[:40]}")
            return 0
        if args.serve_cmd == "trace":
            from ray_tpu.util import state

            row = state.serve_trace(args.request_id)
            wall = row.get("wall_s")
            print(f"request {row['request_id']}  "
                  f"trace {row.get('trace_id') or '?'}")
            print(f"  deployment={row.get('deployment') or '-'} "
                  f"proto={row.get('proto') or '-'} "
                  f"method={row.get('method') or '-'} "
                  f"status={row.get('status')}"
                  + (f" wall={wall * 1e3:.1f}ms" if wall is not None
                     else "")
                  + (" SLO-MISS" if row.get("slo_miss") else ""))
            if row.get("tokens") is not None:
                itl50, itl99 = row.get("itl_p50_s"), row.get("itl_p99_s")
                print(f"  tokens={row['tokens']}"
                      + (f" ttft={row['ttft_s'] * 1e3:.1f}ms"
                         if row.get("ttft_s") is not None else "")
                      + (f" itl p50/p99={itl50 * 1e3:.2f}/"
                         f"{itl99 * 1e3:.2f}ms"
                         if itl50 is not None and itl99 is not None
                         else "")
                      + (f" abort={row['abort_cause']}"
                         if row.get("abort_cause") else ""))
            if row.get("error"):
                print(f"  error: {row['error']}")
            wf = row.get("waterfall") or []
            if not wf:
                print("  (no hop spans shipped yet — replicas flush on "
                      "the task-events cadence)")
                return 0
            t0 = min(e["start_ts"] for e in wf if e.get("start_ts"))
            print()
            print(f"{'HOP':44} {'START':>9} {'DWELL':>10} {'SELF':>10}"
                  f"  DETAIL")
            attributed = 0.0
            for e in wf:
                attributed += e["self_s"]
                a = e.get("attributes") or {}
                detail = " ".join(
                    f"{k}={a[k]}" for k in sorted(a)
                    if k not in ("stack",))[:48]
                nm = ("  " * e["depth"] + e["name"])[:44]
                off = ((e["start_ts"] - t0) * 1e3
                       if e.get("start_ts") else 0.0)
                print(f"{nm:44} {off:>7.1f}ms "
                      f"{e['dwell_s'] * 1e3:>8.2f}ms "
                      f"{e['self_s'] * 1e3:>8.2f}ms  {detail}")
            line = (f"hop dwell (self) total {attributed * 1e3:.2f}ms")
            if wall is not None:
                line += (f" of {wall * 1e3:.2f}ms wall "
                         f"({attributed / wall * 100:.1f}% attributed)"
                         if wall > 0 else "")
            print()
            print(line)
            return 0
        if args.serve_cmd == "profile":
            import ray_tpu
            from ray_tpu.serve.controller import CONTROLLER_NAME

            ctrl = ray_tpu.get_actor(CONTROLLER_NAME)
            _, replicas = ray_tpu.get(ctrl.get_replicas.remote(
                args.deployment))
            logdir = os.path.abspath(args.logdir)
            outs = ray_tpu.get(
                [r.profile.remote(args.seconds, os.path.join(
                    logdir, f"replica-{i}"))
                 for i, r in enumerate(replicas)],
                timeout=args.seconds + 120)
            for i, o in enumerate(outs):
                print(f"replica {i} pid {o['pid']}: {args.seconds:g}s -> "
                      + (", ".join(o["xplane"]) or "no xplane file written"))
            print(f"view: tensorboard --logdir {logdir} (the host phases "
                  "are on the replica's threads beside the TPU planes)")
            return 0
        raise SystemExit(f"unknown serve subcommand {args.serve_cmd!r}")
    finally:
        if args.serve_cmd != "run":
            rt.shutdown()


def cmd_timeline(args) -> int:
    rt = _connect(args)
    from ray_tpu.util import state

    state.timeline(args.out)
    print(f"wrote {args.out} (open in chrome://tracing or ui.perfetto.dev)")
    rt.shutdown()
    return 0


def cmd_dashboard(args) -> int:
    """Serve the web dashboard against a running cluster (reference:
    the dashboard head process, dashboard/head.py)."""
    import time

    import ray_tpu
    from ray_tpu.dashboard import start_dashboard

    ray_tpu.init(address=_resolve_address(args))
    if getattr(args, "grafana_out", None):
        # Generate importable Grafana JSON from the live metric surface
        # and exit (reference: grafana_dashboard_factory.py).
        import urllib.request

        from ray_tpu.util import state as state_api
        from ray_tpu.util.grafana import write_dashboard

        addr = state_api.metrics_address()
        if not addr:
            sys.exit("controller metrics endpoint is disabled")
        with urllib.request.urlopen(f"http://{addr}/metrics",
                                    timeout=5) as resp:
            prom_text = resp.read().decode()
        dash = write_dashboard(args.grafana_out, prom_text)
        print(f"wrote {len(dash['panels'])} panels to {args.grafana_out}")
        ray_tpu.shutdown()
        return 0
    dash = start_dashboard(host=args.host, port=args.dash_port)
    print(f"dashboard at http://{args.host}:{dash.port}")
    print(f"  task timeline: http://{args.host}:{dash.port}/timeline")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        dash.stop()
    return 0


def cmd_job(args) -> int:
    rt = _connect(args)
    from ray_tpu.jobs import JobSubmissionClient

    client = JobSubmissionClient()
    if args.job_cmd == "submit":
        entrypoint = " ".join(args.entrypoint)
        renv = {}
        if args.working_dir:
            renv["working_dir"] = args.working_dir
        job_id = client.submit_job(entrypoint=entrypoint,
                                   runtime_env=renv or None,
                                   max_attempts=args.max_attempts)
        print(job_id)
        if args.wait:
            status = client.wait_until_finished(job_id, timeout=args.timeout)
            print(client.get_job_logs(job_id), end="")
            print(f"job {job_id}: {status}")
            rt.shutdown()
            return 0 if status == "SUCCEEDED" else 1
    elif args.job_cmd == "status":
        print(client.get_job_status(args.job_id))
    elif args.job_cmd == "logs":
        if getattr(args, "follow", False):
            # Durable follow: the stream rides the controller's job-log
            # walker, so it rolls across supervisor failovers and keeps
            # tailing the replacement attempt mid-flight.
            try:
                for chunk in client.tail_job_logs(args.job_id,
                                                  follow=True):
                    print(chunk, end="", flush=True)
            except KeyboardInterrupt:
                pass
        else:
            print(client.get_job_logs(args.job_id), end="")
    elif args.job_cmd == "stop":
        client.stop_job(args.job_id)
        print("stopped")
    elif args.job_cmd == "list":
        for d in client.list_jobs():
            attempts = (f"{d.attempts_used}/{d.max_attempts}"
                        if d.max_attempts else "-")
            rc = "-" if d.returncode is None else str(d.returncode)
            print(f"{d.job_id}\t{d.status}\tattempts={attempts}\t"
                  f"rc={rc}\t{d.entrypoint}")
    rt.shutdown()
    return 0


def cmd_up(args) -> int:
    from ray_tpu.launcher import ClusterConfig, ClusterLauncher

    cfg = ClusterConfig.load(args.config)
    state = ClusterLauncher(cfg).up()
    print(f"cluster {cfg.cluster_name!r} is up at {state['address']} "
          f"({1 + len(state['workers'])} nodes)")
    print(f"  attach: python -m ray_tpu.cli attach {args.config}")
    print(f"  tear down: python -m ray_tpu.cli down {args.config}")
    return 0


def cmd_down(args) -> int:
    from ray_tpu.launcher import ClusterConfig, ClusterLauncher

    cfg = ClusterConfig.load(args.config)
    ClusterLauncher(cfg).down()
    print(f"cluster {cfg.cluster_name!r} is down")
    return 0


def cmd_exec(args) -> int:
    import shlex

    from ray_tpu.launcher import ClusterConfig, ClusterLauncher

    cfg = ClusterConfig.load(args.config)
    # shlex.join: the remote shell re-parses the string — plain " ".join
    # would destroy the operator's quoting (`-c 'print("a b")'`).
    out = ClusterLauncher(cfg).exec(shlex.join(args.command),
                                    timeout=args.timeout)
    sys.stdout.write(out)
    return 0


def cmd_attach(args) -> int:
    from ray_tpu.launcher import ClusterConfig, ClusterLauncher

    cfg = ClusterConfig.load(args.config)
    cmd = ClusterLauncher(cfg).attach_command()
    os.execvp(cmd[0], cmd)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="rtpu", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("up", help="launch a cluster from a YAML config")
    p.add_argument("config")
    p.set_defaults(fn=cmd_up)

    p = sub.add_parser("down", help="tear down a launched cluster")
    p.add_argument("config")
    p.set_defaults(fn=cmd_down)

    p = sub.add_parser("exec", help="run a command on the cluster head")
    p.add_argument("config")
    p.add_argument("--timeout", type=float, default=600.0)
    p.add_argument("command", nargs=argparse.REMAINDER)
    p.set_defaults(fn=cmd_exec)

    p = sub.add_parser("attach", help="open a shell bound to the cluster")
    p.add_argument("config")
    p.set_defaults(fn=cmd_attach)

    p = sub.add_parser("start", help="start a head or worker node")
    p.add_argument("--head", action="store_true")
    p.add_argument("--address", default=None, help="join an existing head")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--num-cpus", type=int, default=None)
    p.add_argument("--resources", default=None,
                   help='extra head-node resources, JSON (e.g. {"TPU": 4})')
    p.add_argument("--state-path", default=None,
                   help="persist controller state (KV, detached actors, "
                        "node table) across head restarts")
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("stop", help="stop the head started on this machine")
    p.set_defaults(fn=cmd_stop)

    p = sub.add_parser("status")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_status)

    p = sub.add_parser("summary", help="per-function task-event counts")
    p.add_argument("--address", default=None)
    p.add_argument("--breakdown", action="store_true",
                   help="per-label per-phase latency breakdown "
                        "(p50/p99/mean over the flight-recorder histograms: "
                        "scheduling delay, queue wait, arg fetch, execute, "
                        "result store)")
    p.set_defaults(fn=cmd_summary)

    p = sub.add_parser("timeline")
    p.add_argument("--address", default=None)
    p.add_argument("--out", default="timeline.json")
    p.set_defaults(fn=cmd_timeline)

    p = sub.add_parser("logs", help="list / fetch / follow cluster worker "
                                    "logs")
    p.add_argument("name", nargs="?", default=None,
                   help="log file name (from the no-argument listing)")
    p.add_argument("--address", default=None)
    p.add_argument("--node", default=None, help="node id owning the file")
    p.add_argument("--task-id", default=None,
                   help="fetch only this task's attributed output")
    p.add_argument("--actor-id", default=None,
                   help="fetch only this actor's attributed output")
    p.add_argument("--worker-id", default=None,
                   help="resolve the file by worker id")
    p.add_argument("--follow", "-f", action="store_true",
                   help="stream new lines live (ctrl-c to stop)")
    p.add_argument("--tail", type=int, default=0,
                   help="only the last N lines")
    p.set_defaults(fn=cmd_logs)

    p = sub.add_parser("events", help="cluster event feed (lifecycle + "
                                      "hang-watchdog findings)")
    p.add_argument("--address", default=None)
    p.add_argument("--severity", default=None,
                   choices=["DEBUG", "INFO", "WARNING", "ERROR"],
                   help="minimum severity to show")
    p.add_argument("--kind", action="append", default=None,
                   help="event kind filter (repeatable), e.g. TASK_HUNG, "
                        "NODE_DIED, ACTOR_RESTARTING")
    p.add_argument("--task-id", default=None,
                   help="events for this task id (prefix ok)")
    p.add_argument("--actor-id", default=None,
                   help="events for this actor id (prefix ok)")
    p.add_argument("--node", default=None,
                   help="events for this node id (prefix ok)")
    p.add_argument("--worker-id", default=None,
                   help="events for this worker id (prefix ok)")
    p.add_argument("--since", type=float, default=0.0, metavar="S",
                   help="only events from the last S seconds")
    p.add_argument("--limit", type=int, default=100)
    p.add_argument("--follow", "-f", action="store_true",
                   help="stream new events live (ctrl-c to stop)")
    p.add_argument("--stacks", action="store_true",
                   help="print captured stacks attached to hang events "
                        "(implied by --task-id/--actor-id)")
    p.set_defaults(fn=cmd_events)

    p = sub.add_parser("stack", help="all-thread stack dump from live "
                                     "workers (`ray stack` analog)")
    p.add_argument("--address", default=None)
    p.add_argument("--worker-id", default=None,
                   help="only this worker (id prefix)")
    p.add_argument("--node", default=None,
                   help="only workers on this node (id prefix)")
    p.add_argument("--timeout", type=float, default=2.0,
                   help="seconds to wait for worker replies")
    p.set_defaults(fn=cmd_stack)

    p = sub.add_parser("dag", help="compiled-DAG observability: per-edge "
                                   "ring telemetry, stage phase "
                                   "accounting, bottleneck attribution")
    dsub = p.add_subparsers(dest="dag_cmd", required=True)
    ds = dsub.add_parser("stats", help="stages×edges busy/starved/blocked "
                                       "view + bottleneck verdict")
    ds.add_argument("dag", nargs="?", default=None,
                    help="dag id (or prefix); default: every compiled DAG")
    ds.add_argument("--address", default=None)
    ds.add_argument("--watch", "-w", action="store_true",
                    help="refresh in place (ctrl-c to stop)")
    ds.add_argument("--interval", type=float, default=2.0,
                    help="refresh period seconds with --watch")
    ds.set_defaults(fn=cmd_dag)
    dt = dsub.add_parser("timeline",
                         help="chrome-trace of per-stage steps with "
                              "recv/compute/send/blocked sub-slices")
    dt.add_argument("dag", nargs="?", default=None,
                    help="dag id (or prefix); default: every compiled DAG")
    dt.add_argument("--address", default=None)
    dt.add_argument("--out", default="dag_timeline.json")
    dt.set_defaults(fn=cmd_dag)

    p = sub.add_parser("top", help="live cluster view: nodes, task "
                                   "rates/p99 with sparkline history, "
                                   "firing alerts, event tail")
    p.add_argument("--address", default=None)
    p.add_argument("--interval", type=float, default=2.0,
                   help="refresh period seconds")
    p.add_argument("--window", type=float, default=120.0,
                   help="history window seconds for rates/sparklines")
    p.add_argument("--once", action="store_true",
                   help="print one frame and exit (no screen clearing)")
    p.set_defaults(fn=cmd_top)

    p = sub.add_parser("profile", help="cluster-wide wall-clock "
                                       "flamegraph (sampling profiler "
                                       "across workers)")
    p.add_argument("--address", default=None)
    p.add_argument("--duration", type=float, default=2.0,
                   help="seconds each targeted worker samples")
    p.add_argument("--hz", type=float, default=None,
                   help="sampling frequency (default RTPU_PROFILER_HZ)")
    p.add_argument("--task-id", default=None,
                   help="only the worker executing this task (prefix ok)")
    p.add_argument("--actor-id", default=None,
                   help="only the worker hosting this actor (prefix ok)")
    p.add_argument("--node", default=None,
                   help="only workers on this node (prefix ok)")
    p.add_argument("--worker-id", default=None,
                   help="only this worker (prefix ok)")
    p.add_argument("-o", "--out", default="profile.html",
                   help="flamegraph HTML output path")
    p.add_argument("--collapsed-out", default=None, metavar="FILE",
                   help="also write collapsed-stack text "
                        "(flamegraph.pl/speedscope format)")
    p.set_defaults(fn=cmd_profile)

    p = sub.add_parser("drain", help="gracefully drain a node "
                                     "(migrate actors, re-queue tasks, "
                                     "then remove it)")
    p.add_argument("node", help="node id (or unique prefix) to drain")
    p.add_argument("--address", default=None)
    p.add_argument("--reason", default="manual",
                   choices=["manual", "preemption", "idle_scale_down"],
                   help="drain reason (rtpu_node_drains_total label)")
    p.add_argument("--deadline", type=float, default=None,
                   help="grace seconds for running tasks "
                        "(default RTPU_DRAIN_DEADLINE_S)")
    p.add_argument("--wait", type=float, default=0.0, metavar="S",
                   help="block up to S seconds until the node is drained")
    p.set_defaults(fn=cmd_drain)

    p = sub.add_parser("memory", help="cluster object census: who owns "
                                      "which bytes, in which tier")
    p.add_argument("--address", default=None)
    p.add_argument("--limit", type=int, default=50)
    p.add_argument("--group-by", default="owner", dest="group_by",
                   choices=["owner", "tier", "node", "callsite"],
                   help="grouped byte/count summary (callsite needs "
                        "RTPU_CALLSITE=1 on the producing processes)")
    p.add_argument("--min-size", type=int, default=0, dest="min_size",
                   help="hide per-object rows smaller than this many "
                        "bytes (group totals still count everything)")
    p.set_defaults(fn=cmd_memory)

    p = sub.add_parser("serve", help="deploy/inspect Serve applications")
    ssub = p.add_subparsers(dest="serve_cmd", required=True)
    sr = ssub.add_parser("run", help="import module:app and serve it")
    sr.add_argument("target")
    sr.add_argument("--address", default=None)
    sr.add_argument("--port", type=int, default=8000)
    sr.set_defaults(fn=cmd_serve)
    for name in ("status", "shutdown"):
        sp = ssub.add_parser(name)
        sp.add_argument("--address", default=None)
        sp.set_defaults(fn=cmd_serve)
    sq = ssub.add_parser("requests",
                         help="the cluster request ledger: finished serve "
                              "requests with status, latency, token stats")
    sq.add_argument("--address", default=None)
    sq.add_argument("--model", default=None,
                    help="filter by deployment-name prefix")
    sq.add_argument("--status", default=None,
                    choices=["ok", "error", "shed", "deadline",
                             "cancelled", "inflight"])
    sq.add_argument("--min-latency-s", type=float, default=None,
                    dest="min_latency_s",
                    help="only requests slower than this many seconds")
    sq.add_argument("--since-s", type=float, default=None, dest="since_s",
                    help="only requests that started in the last N seconds")
    sq.add_argument("--limit", type=int, default=50)
    sq.set_defaults(fn=cmd_serve)
    st_ = ssub.add_parser("trace",
                          help="per-hop waterfall of one request "
                               "(request id may be a unique prefix)")
    st_.add_argument("request_id")
    st_.add_argument("--address", default=None)
    st_.set_defaults(fn=cmd_serve)
    spf = ssub.add_parser("profile",
                          help="jax.profiler trace of every replica of a "
                               "deployment, taken in the chip-owning process")
    spf.add_argument("deployment")
    spf.add_argument("--seconds", type=float, default=3.0)
    spf.add_argument("--logdir", default="serve_profile")
    spf.add_argument("--address", default=None)
    spf.set_defaults(fn=cmd_serve)

    p = sub.add_parser("dashboard", help="serve the web dashboard")
    p.add_argument("--address", default=None)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--dash-port", type=int, default=8265)
    p.add_argument("--grafana-out", default=None, metavar="FILE",
                   help="write importable Grafana dashboard JSON generated "
                        "from the live metric registry, then exit")
    p.set_defaults(fn=cmd_dashboard)

    p = sub.add_parser("job")
    p.add_argument("--address", default=None)
    jsub = p.add_subparsers(dest="job_cmd", required=True)
    j = jsub.add_parser("submit")
    j.add_argument("--working-dir", default=None)
    j.add_argument("--wait", action="store_true")
    j.add_argument("--timeout", type=float, default=600.0)
    j.add_argument("--max-attempts", type=int, default=None,
                   help="entrypoint retry budget (default "
                        "RTPU_JOB_MAX_ATTEMPTS; preempted attempts are "
                        "free)")
    j.add_argument("entrypoint", nargs=argparse.REMAINDER,
                   help="command after --")
    for name in ("status", "logs", "stop"):
        j = jsub.add_parser(name)
        j.add_argument("job_id")
        if name == "logs":
            j.add_argument("--follow", "-f", action="store_true",
                           help="stream until the job is terminal "
                                "(rides the controller long-poll; "
                                "survives supervisor failover)")
    jsub.add_parser("list")
    p.set_defaults(fn=cmd_job)

    args = ap.parse_args(argv)
    if args.cmd == "job":
        # strip a leading "--" in the remainder
        ep = getattr(args, "entrypoint", None)
        if ep and ep[0] == "--":
            args.entrypoint = ep[1:]
    if args.cmd == "exec":
        cl = getattr(args, "command", None)
        if cl and cl[0] == "--":
            args.command = cl[1:]
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
