"""Host phases of a profiler trace -> what the host did while the device
was idle, and what overlapped each stall of the token streams.

The program stamps named host phases (ray_tpu/util/tracing.py `phase` /
`observe`): in the chip-owning process they are profiler annotations on the
thread that did the work, in the same .xplane.pb as the device planes, with
one `clock_anchor` event that maps the trace's clock onto CLOCK_MONOTONIC.
Every process also keeps a table of its phases and a ring of the slow ones
(50 ms or more); the runner hosts the controller and the proxy, so its own
table and ring hold `ctrl.*`, `stream.next`, `stream.get`, `proxy.write` and
the slow phases workers reported. A program without these (an older commit)
gives a picture with `has_phases` false, and the metrics return nothing.

Steps, so that the second can be checked on a small recorded trace
(reduce/recorded_host_trace.json; `python3 chipbench/reduce/host_spans.py`
checks it, and chipbench/tests/test_host_spans.py):

1. `load(path)`: an .xplane.pb -> `{"device": [[plane, line, name, start_ns,
   dur_ns, flow]], "host": [[thread, name, start_ns, dur_ns, stats]]}`.
   Device: the "XLA Modules" and "XLA Ops" lines, as reduce/xplane.py keeps
   them, a program with the flow it consumes. Host: the program's phases,
   the anchor, and the events that launch a program (`PjitFunction(<fn>)`
   and every event that produces or consumes a flow).
2. `idle_by_phase(raw)`: every device idle gap of xplane.reduce's definition
   (same window, gaps of 2 us or more) is split over the innermost program
   phase open, instant by instant, on the thread that launched the next
   program on that device; time under no phase is `unattributed`.
3. `stalls(recs, w0, w1)` and `overlapping(stall, slow)`: stretches of the
   window in which no stream got a token though one was live, and the slow
   phases that overlap each.
4. `picture(ctx)`: all of it for one run, parsed once and kept in `ctx`,
   with the summary written to `ctx["notes"]["host_spans"]`."""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Tuple

PROGRAM = re.compile(r"^(engine|serve|stream|train|ctrl|proxy)\.")
ANCHOR = "clock_anchor"
LAUNCH = "PjitFunction("
SMALL_GAP_NS = 2000          # xplane.reduce's small_gap_ns
STALL_S = 0.2
#: Phases that wait for someone else by design: they overlap any stall
#: without being its cause, so they do not count as its attribution.
WAITS = ("stream.next", "engine.idle")
RELAY = ("stream.poll_lag", "stream.put", "stream.report", "stream.next",
         "stream.get", "proxy.write", "ctrl.rpc.generator_item",
         "ctrl.rpc.generator_next")

Span = Tuple[int, int, str]  # start_ns, end_ns, name


# ------------------------------------------------------------------- load


_FLOW = ("_pt", "_p", "_ct", "_c")


def load(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    device, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name not in ("XLA Modules", "XLA Ops"):
                    continue
                mods = line.name == "XLA Modules"
                for ev in line.events:
                    flow = ""
                    if mods:
                        st = {k: str(v) for k, v in ev.stats if k in _FLOW}
                        if "_c" in st:
                            flow = f"{st.get('_ct', '')}:{st['_c']}"
                    device.append([plane.name, line.name,
                                   ev.name.split(" ")[0] if mods else "",
                                   int(ev.start_ns), int(ev.duration_ns),
                                   flow])
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                thread = f"{line.name}#{i}"
                for ev in line.events:
                    name = ev.name
                    if PROGRAM.match(name) or name == ANCHOR:
                        stats = {k: str(v) for k, v in ev.stats}
                    else:
                        stats = {k: str(v) for k, v in ev.stats
                                 if k in _FLOW}
                        if not stats and not name.startswith(LAUNCH):
                            continue
                    host.append([thread, name, int(ev.start_ns),
                                 int(ev.duration_ns), stats])
    return {"device": device, "host": host}


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[0] if files else None


# ----------------------------------------------------------------- phases


def clock_shift(raw: Dict[str, Any]) -> Optional[int]:
    """Nanoseconds to add to a trace time to get CLOCK_MONOTONIC, from the
    trace's first `clock_anchor`; None when the program emitted none."""
    for _, name, start, _, stats in raw["host"]:
        if name == ANCHOR and "monotonic_ns" in stats:
            return int(stats["monotonic_ns"]) - start
    return None


def phases_by_thread(raw: Dict[str, Any]) -> Dict[str, List[Span]]:
    """The program's phases on each thread, in trace time, sorted by start.
    A `phase` is the annotation's own interval; an `observe` is an instant
    annotation carrying `dur_ns`: its interval ends where the annotation
    stands."""
    out: Dict[str, List[Span]] = defaultdict(list)
    for thread, name, start, dur, stats in raw["host"]:
        if not PROGRAM.match(name):
            continue
        if "dur_ns" in stats:
            out[thread].append((start - int(stats["dur_ns"]), start, name))
        else:
            out[thread].append((start, start + dur, name))
    for spans in out.values():
        spans.sort()
    return dict(out)


class Launches:
    """Which host thread launched a device program.

    The profiler links events across threads by flows: a producer event
    carries `_pt`/`_p` (type, id) and its consumer `_ct`/`_c`. A program on
    the device consumes the flow of the runtime's enqueue, which runs inside
    the consumer of the flow from the runtime call, which runs inside ...
    back to the `PJRT_LoadedExecutable_Execute` inside `PjitFunction(<fn>)`
    on the Python thread that called the jitted function. `thread_of` walks
    that chain upstream until it stands on a thread with program phases.
    Where the chain breaks (a launch before the trace began), it falls back
    to the last `PjitFunction(<fn>)` of the program's name that started
    before the program did."""

    def __init__(self, raw: Dict[str, Any], phase_threads: Iterable[str]):
        self.phase_threads = set(phase_threads)
        self.producer: Dict[str, Tuple[str, int]] = {}
        consumers: Dict[str, List[Tuple[int, int, str]]] = defaultdict(list)
        self.pjit: Dict[str, List[Tuple[int, str]]] = defaultdict(list)
        for thread, name, start, dur, stats in raw["host"]:
            if "_p" in stats:
                self.producer[f"{stats.get('_pt', '')}:{stats['_p']}"] = (
                    thread, start)
            if "_c" in stats:
                consumers[thread].append(
                    (start, start + dur, f"{stats.get('_ct', '')}:"
                                         f"{stats['_c']}"))
            if name.startswith(LAUNCH):
                self.pjit[name[len(LAUNCH):].rstrip(")")].append(
                    (start, thread))
        self.consumers = {t: sorted(v) for t, v in consumers.items()}
        for v in self.pjit.values():
            v.sort()

    def _enclosing_flow(self, thread: str, t: int) -> Optional[str]:
        """Flow consumed by the innermost consumer event open at t there."""
        best = None
        for s, e, flow in self.consumers.get(thread, ()):
            if s > t:
                break
            if e >= t and (best is None or s >= best[0]):
                best = (s, flow)
        return best[1] if best else None

    def thread_of(self, name: str, start: int, flow: str) -> str:
        for _ in range(8):  # the chain is three links long on this runtime
            at = self.producer.get(flow)
            if at is None:
                break
            thread, t = at
            if thread in self.phase_threads:
                return thread
            flow = self._enclosing_flow(thread, t)
            if flow is None:
                break
        fn = re.sub(r"\(\d+\)$", "", name)
        fn = fn[4:] if fn.startswith("jit_") else fn
        calls = self.pjit.get(fn, [])
        i = bisect.bisect_right(calls, (start, "\uffff")) - 1
        return calls[i][1] if i >= 0 else ""


def split_over_phases(a: int, b: int, spans: List[Span]) -> Dict[str, int]:
    """[a, b) split over the innermost phase open at each instant (the one
    that started last among those covering it); the rest is unattributed."""
    cover = [s for s in spans if s[0] < b and s[1] > a]
    cuts = sorted({a, b} | {t for s in cover for t in (s[0], s[1])
                            if a < t < b})
    out: Dict[str, int] = defaultdict(int)
    for lo, hi in zip(cuts, cuts[1:]):
        open_ = [s for s in cover if s[0] <= lo and s[1] >= hi]
        name = max(open_, key=lambda s: (s[0], -s[1]))[2] if open_ \
            else "unattributed"
        out[name] += hi - lo
    return dict(out)


# ------------------------------------------------------------- idle gaps


def _union(spans: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(spans):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def device_window(raw: Dict[str, Any]) -> Optional[Tuple[int, int]]:
    ops = [e for e in raw["device"] if e[1] == "XLA Ops"] or raw["device"]
    if not ops:
        return None
    return min(e[3] for e in ops), max(e[3] + e[4] for e in ops)


def idle_gaps(raw: Dict[str, Any]) -> List[Tuple[str, int, int, Any]]:
    """(plane, start, end, the program that runs next on that device as
    (name, start, flow) or None) for every idle gap of SMALL_GAP_NS or more in the window first
    op start .. last op end over all devices: reduce/xplane.py's gaps."""
    win = device_window(raw)
    if win is None:
        return []
    w0, w1 = win
    out = []
    for plane in sorted({e[0] for e in raw["device"]}):
        evs = [e for e in raw["device"] if e[0] == plane]
        ops = [e for e in evs if e[1] == "XLA Ops"] or evs
        mods = sorted((e[3], e[3] + e[4], e[2], e[5]) for e in evs
                      if e[1] == "XLA Modules")
        starts = [m[0] for m in mods]
        busy = _union((max(e[3], w0), min(e[3] + e[4], w1)) for e in ops
                      if e[3] < w1 and e[3] + e[4] > w0)
        edges = [(w0, w0)] + busy + [(w1, w1)]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b - a < SMALL_GAP_NS:
                continue
            # the program that holds b, else the next one to start
            i = bisect.bisect_right(starts, b + 1) - 1
            if not (i >= 0 and mods[i][1] >= b):
                i = bisect.bisect_left(starts, b)
            nxt = ((mods[i][2], mods[i][0], mods[i][3])
                   if 0 <= i < len(mods) else None)
            out.append((plane, a, b, nxt))
    return out


def idle_by_phase(raw: Dict[str, Any]) -> Dict[str, Any]:
    """Idle nanoseconds by phase name (devices summed), with the total and
    the window. `has_phases` is false when the trace holds no annotation of
    the program at all."""
    by_thread = phases_by_thread(raw)
    launches = Launches(raw, by_thread)
    by_phase: Dict[str, int] = defaultdict(int)
    total = 0
    for _, a, b, nxt in idle_gaps(raw):
        total += b - a
        spans = by_thread.get(launches.thread_of(*nxt), []) if nxt else []
        for name, ns in split_over_phases(a, b, spans).items():
            by_phase[name] += ns
    win = device_window(raw)
    return {"has_phases": bool(by_thread), "idle_ns": total,
            "by_phase_ns": dict(by_phase),
            "devices": len({e[0] for e in raw["device"]}),
            "window_ns": (win[1] - win[0]) if win else 0}


def phase_stats(raw: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """count, p50, p90, p99, max, total (ms) of every program phase in the
    trace."""
    from chipbench.common import pct

    durs: Dict[str, List[int]] = defaultdict(list)
    for spans in phases_by_thread(raw).values():
        for s, e, name in spans:
            durs[name].append(e - s)
    return {n: {"count": len(d), "p50_ms": pct(d, 50) / 1e6,
                "p90_ms": pct(d, 90) / 1e6,
                "p99_ms": pct(d, 99) / 1e6, "max_ms": max(d) / 1e6,
                "total_ms": sum(d) / 1e6} for n, d in sorted(durs.items())}


# ----------------------------------------------------------------- stalls


def stalls(recs: List[Dict[str, Any]], w0: float, w1: float,
           min_s: float = STALL_S) -> List[Tuple[float, float]]:
    """Stretches of [w0, w1) (CLOCK_MONOTONIC seconds, the load generator's
    stamps) of `min_s` or more in which no stream received a token although
    at least one was live throughout: sent before the stretch, and its last
    token (or its end) after it."""
    stamps = sorted(s for r in recs for s in r.get("stamps", ())
                    if w0 <= s < w1)
    lives = [(r["sent"], max([r.get("done", w1)] + list(r.get("stamps", ()))))
             for r in recs if "sent" in r]
    out = []
    for a, b in zip([w0] + stamps, stamps + [w1]):
        if b - a >= min_s and any(s <= a and e >= b for s, e in lives):
            out.append((a, b))
    return out


def overlapping(stall: Tuple[float, float],
                slow: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The slow phases (tracing.slow_phases() rows, CLOCK_MONOTONIC ns) that
    overlap a stall, longest overlap first."""
    a, b = int(stall[0] * 1e9), int(stall[1] * 1e9)
    out = []
    for p in slow:
        s, e = p["start_monotonic_ns"], p["start_monotonic_ns"] + p["dur_ns"]
        ov = min(b, e) - max(a, s)
        if ov > 0:
            out.append({"name": p["name"], "dur_ms": p["dur_ns"] / 1e6,
                        "overlap_ms": ov / 1e6,
                        "pid": (p.get("attrs") or {}).get("pid", "runner"),
                        "starts_ms_after_stall": (s - a) / 1e6})
    return sorted(out, key=lambda o: -o["overlap_ms"])


def covered_ns(stall: Tuple[float, float], slow: List[Dict[str, Any]]) -> int:
    """Nanoseconds of a stall under at least one slow phase that is not a
    wait by design (WAITS)."""
    a, b = int(stall[0] * 1e9), int(stall[1] * 1e9)
    spans = _union(
        (max(a, p["start_monotonic_ns"]),
         min(b, p["start_monotonic_ns"] + p["dur_ns"])) for p in slow
        if p["name"] not in WAITS and p["start_monotonic_ns"] < b
        and p["start_monotonic_ns"] + p["dur_ns"] > a)
    return sum(e - s for s, e in spans)


# ---------------------------------------------------------------- picture


def _table_stats(table: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    from ray_tpu.util import tracing

    out = {}
    for name, row in sorted(table.items()):
        q = {k: tracing.bucket_quantile(row["buckets"], v)
             for k, v in (("p50_ms", 0.5), ("p99_ms", 0.99))}
        out[name] = {"count": row["count"],
                     "mean_ms": row["total_ns"] / row["count"] / 1e6,
                     "max_ms": row["max_ns"] / 1e6,
                     **{k: None if v is None else v / 1e6
                        for k, v in q.items()}}
    return out


def runner_phases() -> Optional[Dict[str, Any]]:
    """This (runner) process's phase table and slow ring, or None when the
    program has none (an older commit)."""
    try:
        from ray_tpu.util import tracing

        return {"table": tracing.phase_table(), "slow": tracing.slow_phases()}
    except (ImportError, AttributeError):
        return None


def picture(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """Everything this module can say of one run; parsed once, kept in
    `ctx["host_spans"]`, summarised into `ctx["notes"]["host_spans"]`."""
    if "host_spans" in ctx:
        return ctx["host_spans"]
    from chipbench import inworker

    pic: Dict[str, Any] = {"has_phases": False}
    ctx["host_spans"] = pic
    note: Dict[str, Any] = {}
    path = find_xplane(inworker.TRACE_DIR) if ctx.get("trace") else None
    if path:
        _trace_into(pic, note, load(path))
    runner = runner_phases()
    rstats = _table_stats(runner["table"]) if runner else {}
    if runner:
        pic["has_phases"] = pic["has_phases"] or bool(runner["table"])
        _runner_into(pic, note, rstats)
    # The relay's hops, a token's way out: mean and p99 of each, from the
    # trace for the replica's side and the runner's table for the rest.
    hops = {}
    for name in RELAY:
        tr = pic.get("trace_phases", {}).get(name)
        if tr:
            hops[name] = [tr["total_ms"] / tr["count"], tr["p99_ms"]]
        elif name in rstats:
            hops[name] = [rstats[name]["mean_ms"], rstats[name]["p99_ms"]]
    if hops:
        note["relay_hops_mean_p99_ms"] = hops
    _stalls_into(pic, note, runner)
    if note:
        ctx.setdefault("notes", {})["host_spans"] = note
    return pic


def _trace_into(pic, note, raw) -> None:
    """The trace's part: idle time by phase, every phase's times, and the
    serve-only numbers `tick_host_ms`, `attach_wait_p50/p90_ms`,
    `relay_hop_p50/p99_ms` (the `stream.report` round trip)."""
    idle = idle_by_phase(raw)
    pic.update(idle)
    pic["trace_phases"] = stats = phase_stats(raw)
    if idle["has_phases"] and idle["idle_ns"]:
        n = idle["devices"]
        named = idle["idle_ns"] - idle["by_phase_ns"].get("unattributed", 0)
        pic["idle_attributed_pct"] = 100.0 * named / idle["idle_ns"]
        note.update(
            idle_s=idle["idle_ns"] / n / 1e9,
            window_s=idle["window_ns"] / 1e9,
            idle_attributed_pct=pic["idle_attributed_pct"],
            idle_s_by_phase=[[k, v / n / 1e9] for k, v in sorted(
                idle["by_phase_ns"].items(), key=lambda kv: -kv[1])[:10]])
    if not stats:
        return
    note["trace_phases_count_p50_p99_max_ms"] = {
        k: [v["count"], v["p50_ms"], v["p99_ms"], v["max_ms"]]
        for k, v in stats.items()}
    if "engine.tick" in stats:
        tick = stats["engine.tick"]
        note["tick_host_ms"] = tick["total_ms"] / tick["count"]
    if "stream.report" in stats:
        note["relay_hop_p50_ms"] = stats["stream.report"]["p50_ms"]
        note["relay_hop_p99_ms"] = stats["stream.report"]["p99_ms"]
    if "engine.attach.wait" in stats:
        note["attach_wait_p50_ms"] = stats["engine.attach.wait"]["p50_ms"]
        note["attach_wait_p90_ms"] = stats["engine.attach.wait"]["p90_ms"]


def _runner_into(pic, note, rstats) -> None:
    """The runner's own table: what held the controller's loop longest, the
    loop's lag, and every phase that is frequent or was ever long."""
    blocks = {k: v["max_ms"] for k, v in rstats.items()
              if k.startswith(("ctrl.rpc.", "ctrl.periodic."))}
    if blocks:
        worst = max(blocks, key=blocks.get)
        pic["ctrl_loop_block_max_ms"] = blocks[worst]
        note["ctrl_loop_block_max"] = [worst, blocks[worst]]
        note["ctrl_blocks_over_10_ms"] = sorted(
            ([k, v] for k, v in blocks.items() if v >= 10.0),
            key=lambda kv: -kv[1])[:10]
    if "ctrl.loop_lag" in rstats:
        lag = rstats["ctrl.loop_lag"]
        note["ctrl_loop_lag_p99_max_ms"] = [lag["p99_ms"], lag["max_ms"]]
    note["runner_phases_count_mean_p99_max_ms"] = {
        k: [v["count"], v["mean_ms"], v["p99_ms"], v["max_ms"]]
        for k, v in rstats.items() if v["count"] >= 20 or v["max_ms"] >= 10.0}


def _stalls_into(pic, note, runner) -> None:
    from chipbench import common

    try:
        job = load_json(os.path.join(common.RUN_DIR, "client_job.json"))
        recs = load_json(os.path.join(common.RUN_DIR,
                                      "client_out.json"))["recs"]
    except (OSError, ValueError, KeyError):
        return  # not a serve cell
    w0, w1 = job["t0"], job["t0"] + job["seconds"]
    found = stalls(recs, w0, w1)
    slow = runner["slow"] if runner else []
    rows, covered, total = [], 0, 0
    for st in found:
        total += int((st[1] - st[0]) * 1e9)
        covered += covered_ns(st, slow)
        rows.append({"at_s": st[0] - w0, "dur_ms": (st[1] - st[0]) * 1e3,
                     "phases": [[o["name"], o["pid"], o["dur_ms"],
                                 o["overlap_ms"]]
                                for o in overlapping(st, slow)[:6]]})
    pic["stalls"] = rows
    note["stalls"] = rows
    note["stall_s"] = total / 1e9
    if total:
        note["stall_attributed_pct"] = 100.0 * covered / total


# ------------------------------------------------------------------ check


def check_recorded(path: Optional[str] = None) -> Dict[str, Any]:
    """The reduction on the recorded trace against what was worked out by
    hand beside it (`expect`)."""
    path = path or os.path.join(os.path.dirname(__file__),
                                "recorded_host_trace.json")
    rec = load_json(path)
    exp = rec["expect"]
    idle = idle_by_phase(rec)
    assert idle["idle_ns"] == exp["idle_ns"], (idle["idle_ns"], exp["idle_ns"])
    assert idle["by_phase_ns"] == exp["by_phase_ns"], idle["by_phase_ns"]
    assert sum(idle["by_phase_ns"].values()) == idle["idle_ns"]
    assert clock_shift(rec) == exp["clock_shift_ns"]
    found = stalls(rec["client"]["recs"], *rec["client"]["window"],
                   min_s=exp["stall_min_s"])
    assert [[round(a, 6), round(b, 6)] for a, b in found] == exp["stalls"]
    for st, want in zip(found, exp["stall_phases"]):
        got = [o["name"] for o in overlapping(st, rec["slow"])]
        assert got == want, (st, got, want)
    assert [covered_ns(st, rec["slow"]) for st in found] == exp["covered_ns"]
    return idle


if __name__ == "__main__":
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    if len(sys.argv) > 1:  # an .xplane.pb: what the reduction makes of it
        raw_ = load(sys.argv[1])
        print(json.dumps({"idle": idle_by_phase(raw_),
                          "phases": phase_stats(raw_)}, indent=1))
    else:
        got_ = check_recorded()
        print("host spans: ok, idle", got_["idle_ns"], "ns:",
              got_["by_phase_ns"])
