"""The system's set-up, told by the program's own host phases.

Since PR 35 the program stamps what happens before the window as phases of
its one primitive (ray_tpu/util/tracing.py): a worker's boot (`boot.*`), the
start of the runtime (`runtime.*`), and every program's trace, lowering,
cache read and compile (`xla.*`, with `fun_name`). Those of 50 ms or more
reach the runner's slow ring as SLOW_PHASE events, with the worker's pid and
their start on CLOCK_MONOTONIC, beside the controller's own `ctrl.*`
stretches; `host_spans.runner_phases()` reads that ring after shutdown.

`split(slow, marks)` is the whole reduction, on plain data, so that it can
be checked on a recorded ring (reduce/recorded_setup_ring.json, a CPU
rehearsal's; chipbench/tests/test_setup_spans.py):

- the chip-owning worker is the pid with the most `runtime.*` + `xla.*`
  time (plain workers start no backend); no such phase (an older commit, an
  empty ring): None, and every metric is left out of the line;
- only entries that start before the window's start count;
- `setup_boot_s`: the owner's `boot.*`, each less what lies under its own
  `runtime.*` / `xla.*` phases (a serve replica's constructor holds them);
- `setup_runtime_init_s`: its `runtime.*`;
- `setup_trace_s`, `_lower_s`, `_cache_read_s`, `_compile_s`: sums of
  `self_ns` (the program subtracts nested long spans itself; an entry
  without it counts whole), each entry's share outside the check's stretch:
  the reference comparison is the benchmark's own work, and its sums go to
  `notes` (`in_check_s`);
- `setup_named_pct`: of the runner's start .. the window's start less the
  check's stretch, the share under any phase above or under a `ctrl.*`
  stretch of the runner, as a union of intervals: overlaps count once.

`marks_of(ctx)` lays the benchmark's wall stamps (`ctx["phases"]`, seconds
in time order from the runner's process start) on CLOCK_MONOTONIC: one
machine, both clocks read here."""
from __future__ import annotations

import time
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Tuple

from chipbench.reduce import host_spans

BOOT = ("boot.interpreter", "boot.imports", "boot.connect",
        "boot.actor_init")
RUNTIME = ("runtime.import_jax", "runtime.backend_init", "runtime.mesh")
XLA = {"xla.trace": "setup_trace_s", "xla.lower": "setup_lower_s",
       "xla.cache_read": "setup_cache_read_s",
       "xla.compile": "setup_compile_s"}
STAMPED = ("cluster_s", "backend_s", "weights_s", "warm_s", "check_s")
METRICS = ("setup_boot_s", "setup_runtime_init_s") + tuple(XLA.values()) + (
    "setup_named_pct",)
NAMED = frozenset(BOOT + RUNTIME + tuple(XLA))

Iv = Tuple[int, int]


def _attrs(p: Dict[str, Any]) -> Dict[str, Any]:
    return p.get("attrs") or {}


def _iv(p: Dict[str, Any]) -> Iv:
    return p["start_monotonic_ns"], p["start_monotonic_ns"] + p["dur_ns"]


def _clip(spans: Iterable[Iv], a: int, b: int) -> List[Iv]:
    return [(max(s, a), min(e, b)) for s, e in spans if s < b and e > a]


def _length(spans: Iterable[Iv]) -> int:
    return sum(e - s for s, e in host_spans._union(spans))


def _outside(spans: Iterable[Iv], hole: Optional[Iv]) -> List[Iv]:
    """The spans with `hole` cut out of each."""
    if hole is None:
        return list(spans)
    out = []
    for s, e in spans:
        out += [(s, min(e, hole[0])), (max(s, hole[1]), e)]
    return [(s, e) for s, e in out if e > s]


def marks_of(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """CLOCK_MONOTONIC ns of the runner's process start, the check's stretch
    and the window's start."""
    from chipbench import common

    shift = time.monotonic_ns() - time.time_ns()
    ns = lambda wall: int(wall * 1e9) + shift  # noqa: E731
    ph = ctx["phases"]
    t0 = t = common.proc_start_wall()
    check = None
    for key, secs in ph.items():  # the stamped phases, in time order
        if key not in STAMPED:
            break
        if key == "check_s":
            check = [ns(t), ns(t + secs)]
        t += secs
    return {"proc_ns": ns(t0), "check_ns": check,
            "window_ns": ns(t0 + ph["setup_s"])}


def owner_pid(slow: List[Dict[str, Any]], before: int) -> Optional[int]:
    by_pid: Dict[int, int] = defaultdict(int)
    for p in slow:
        pid = _attrs(p).get("pid")
        if pid is not None and p["start_monotonic_ns"] < before and \
                p["name"].startswith(("runtime.", "xla.")):
            by_pid[pid] += p["dur_ns"]
    return max(by_pid, key=by_pid.get) if by_pid else None


def split(slow: List[Dict[str, Any]],
          marks: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The seven metrics (seconds, and a share) and the notes, from a slow
    ring and the three marks; None when the ring holds no `runtime.*` or
    `xla.*` phase of a worker."""
    proc, window = marks["proc_ns"], marks["window_ns"]
    check: Optional[Iv] = tuple(marks["check_ns"]) if marks.get(
        "check_ns") else None
    pid = owner_pid(slow, window)
    if pid is None:
        return None
    early = [p for p in slow if p["start_monotonic_ns"] < window]
    mine = [p for p in early if _attrs(p).get("pid") == pid
            and not p["name"].startswith("ctrl.")]
    ctrl = [p for p in early if p["name"].startswith("ctrl.")]
    inner = [_iv(p) for p in mine
             if p["name"].startswith(("runtime.", "xla."))]

    out: Dict[str, Any] = {m: 0.0 for m in METRICS}
    for p in mine:
        if p["name"] in BOOT:
            a, b = _iv(p)
            out["setup_boot_s"] += (b - a - _length(_clip(inner, a, b))) / 1e9
        elif p["name"] in RUNTIME:
            out["setup_runtime_init_s"] += p["dur_ns"] / 1e9
    in_check = {m: 0.0 for m in XLA.values()}
    programs: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {m: 0.0 for m in XLA.values()})
    for p in mine:
        metric = XLA.get(p["name"])
        if metric is None or not p["dur_ns"]:
            continue
        attrs = _attrs(p)
        own = attrs.get("self_ns", p["dur_ns"]) / 1e9
        outside = _length(_outside([_iv(p)], check)) / p["dur_ns"]
        out[metric] += own * outside
        in_check[metric] += own * (1.0 - outside)
        fun = str(attrs.get("fun_name", "?"))
        if fun.startswith("jit(") and fun.endswith(")"):
            fun = fun[4:-1]
        programs[fun][metric] += own

    span = _outside([(proc, window)], check)
    total = _length(span)
    named = [_iv(p) for p in mine if p["name"] in NAMED] + [
        _iv(p) for p in ctrl]
    painted = host_spans._union(
        iv for a, b in span for iv in _clip(named, a, b))
    covered = sum(e - s for s, e in painted)
    out["setup_named_pct"] = 100.0 * covered / total if total else None

    gaps = []  # what no phase covers: where the next phase would go
    for a, b in span:
        at = a
        for s, e in painted + [(b, b)]:
            if a <= s <= b:
                if s > at:
                    gaps.append([(at - proc) / 1e9, (s - at) / 1e9])
                at = max(at, e)
    blocks = [p for p in ctrl
              if p["name"].startswith(("ctrl.rpc.", "ctrl.periodic."))]
    longest = max(blocks, key=lambda p: p["dur_ns"], default=None)
    seen = defaultdict(int)
    for p in mine:
        seen[p["name"]] += 1
    out["notes"] = {
        "owner_pid": pid,
        "system_setup_s": total / 1e9,
        "named_s": covered / 1e9,
        "in_check_s": in_check,
        "phases_seen": dict(seen),
        # beside counts.cache_hits + cache_misses of the run's first line:
        # they differ by the programs under 50 ms and by what was dropped
        "cache_read_plus_compile_seen": seen["xla.cache_read"]
                                        + seen["xla.compile"],
        "dropped_before": sum(int(_attrs(p).get(
            "dropped_before", 0)) for p in slow
            if _attrs(p).get("pid") == pid),
        "top_programs_trace_lower_read_compile_ms": [
            [fun] + [round(1e3 * v[m], 1) for m in XLA.values()]
            for fun, v in sorted(programs.items(),
                                 key=lambda kv: -sum(kv[1].values()))[:5]],
        "largest_unnamed_at_s_dur_s": sorted(
            gaps, key=lambda g: -g[1])[:5],
        "ctrl_spawns_pid_ms": [
            [_attrs(p).get("pid"), p["dur_ns"] / 1e6]
            for p in ctrl if p["name"] == "ctrl.worker_spawn"],
    }
    if longest is not None:
        st = tuple(t / 1e9 for t in _iv(longest))
        out["notes"]["longest_ctrl_block"] = {
            "name": longest["name"], "dur_ms": longest["dur_ns"] / 1e6,
            "at_s": (longest["start_monotonic_ns"] - proc) / 1e9,
            "worker_phases": [
                [o["name"], o["pid"], o["dur_ms"], o["overlap_ms"]]
                for o in host_spans.overlapping(st, [
                    p for p in slow if not p["name"].startswith("ctrl.")])
            ][:6]}
    return out


def picture(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """The split of one run, computed once, kept in `ctx["setup_spans"]`,
    its notes in `ctx["notes"]["setup_spans"]`; empty when the program has
    no such phases."""
    if "setup_spans" in ctx:
        return ctx["setup_spans"]
    ctx["setup_spans"] = pic = {}
    runner = host_spans.runner_phases()
    if not runner or not runner["slow"]:
        return pic
    marks = marks_of(ctx)
    got = split(runner["slow"], marks)
    if got is None:
        return pic
    notes = got.pop("notes")
    pic.update(got)
    # the benchmark's own counts of the same run, beside what the reader saw
    # (cache_hits + cache_misses are on the run's `counts` line)
    ctx.setdefault("notes", {})["setup_spans"] = dict(
        notes, marks=marks, counts=ctx.get("counts"))
    return pic
