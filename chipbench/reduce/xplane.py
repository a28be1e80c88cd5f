"""Profiler trace -> the numbers the per-layer metrics read.

Two steps, so that the second can be checked on a small recorded trace
(reduce/recorded_trace.json, selfcheck.py):

1. `load(path)`: an .xplane.pb -> a flat list of device events
   `[plane, line, name, start_ns, dur_ns, shape]` (jax.profiler.ProfileData;
   parsing starts no backend). Only the device planes' "XLA Modules" and
   "XLA Ops" lines are kept.
2. `reduce(events)`: per device, the union of the intervals in which an op
   ran (busy), each op's self time (a `while` or `call` event spans its
   body's events on the same line; its own time is what they leave), the
   programs ("XLA Modules") with their durations, and the idle gaps named by
   the programs on either side. Devices are averaged.

Names are made stable where the compiler allows: a program is its jit name
without the run id, an op is its HLO name plus its result shape (the fusion
number still moves when the program changes; a Pallas kernel carries its own
name)."""
from __future__ import annotations

import json
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

Event = Tuple[str, str, str, int, int, str]  # plane line name start dur shape

_SHAPE = re.compile(r"\b(pred|[subf]\d+|bf16|f8\w*)\[([\d,]*)\]")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.I)


def _kernels() -> Dict[str, str]:
    """reduce/kernels/<kernel>.json: a Pallas kernel's name by its operand
    and result counts. The profiler's op events carry a Pallas call's HLO
    text but not its name (kernel_metadata is empty)."""
    import glob
    import os

    out = {}
    for path in glob.glob(os.path.join(os.path.dirname(__file__), "kernels",
                                       "*.json")):
        with open(path) as f:
            k = json.load(f)
        out[k["signature"]] = k["kernel"]
    return out


def _shape_of(text: str, kernels: Dict[str, str]) -> str:
    """From an op event's HLO text: its first result shape, and for a Pallas
    call (custom_call_target="tpu_custom_call"; the trace carries no kernel
    name) the kernel that reduce/kernels/ gives for its operand/result counts."""
    head, _, rest = text.partition(" = ")
    result, _, call = rest.partition(" custom-call(")
    m = _SHAPE.search(rest)
    shape = f"{m.group(1)}_{m.group(2).replace(',', '_')}" if m else ""
    if 'custom_call_target="tpu_custom_call"' in call:
        args = call.split("), custom_call_target")[0]
        sig = f"{args.count('%')}in_{len(_SHAPE.findall(result))}out"
        shape += "__" + kernels.get(sig, "pallas_" + sig)
    return shape


def load(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    kernels = _kernels()
    events: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name not in ("XLA Modules", "XLA Ops"):
                continue
            ops = line.name == "XLA Ops"
            for ev in line.events:
                name = ev.name
                shape = _shape_of(name, kernels) if ops else ""
                events.append((plane.name, line.name, name.split(" ")[0],
                               int(ev.start_ns), int(ev.duration_ns), shape))
    return events


def load_json(path: str) -> List[Event]:
    with open(path) as f:
        return [tuple(e) for e in json.load(f)["events"]]


def module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name).strip()


def op_label(name: str, shape: str) -> str:
    name = name.lstrip("%").split(" ")[0]
    return f"{name}_{shape}" if shape else name


def _self_times(ops: List[Tuple[int, int, str]]):
    """[(start, end, label)] on one line -> [(label, self_ns)], a parent's
    self time being its span less its children's."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    out, stack = [], []  # stack of [end, label, self]
    for s, e, label in ops:
        while stack and stack[-1][0] <= s:
            top = stack.pop()
            out.append((top[1], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, label, e - s])
    out.extend((t[1], t[2]) for t in stack)
    return out


def _union(spans: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(spans):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def reduce(events: List[Event], window: Optional[Tuple[int, int]] = None,
           small_gap_ns: int = 2000, edge_ns: int = 10000) -> Dict[str, Any]:
    """See the module docstring. `window` (ns, the trace's clock) defaults
    to first op start .. last op end over all devices."""
    planes = sorted({e[0] for e in events})
    if not planes:
        return {"devices": 0}
    op_events = [e for e in events if e[1] == "XLA Ops"]
    if not op_events:  # no op line: the programs themselves are the ops
        op_events = [e for e in events if e[1] == "XLA Modules"]
    if window is None:
        window = (min(e[3] for e in op_events),
                  max(e[3] + e[4] for e in op_events))
    w0, w1 = window
    busy_ns = 0
    op_self: Dict[str, int] = defaultdict(int)
    op_count: Dict[str, int] = defaultdict(int)
    modules: Dict[str, List[float]] = defaultdict(list)
    gaps: Dict[str, int] = defaultdict(int)
    for plane in planes:
        mods = sorted((e[3], e[3] + e[4], module_name(e[2])) for e in events
                      if e[0] == plane and e[1] == "XLA Modules")
        for s, e, name in mods:  # whole runs only: the trace cuts the
            if s > w0 + edge_ns and e < w1 - edge_ns:  # first and last short
                modules[name].append((e - s) / 1e6)
        ops = [(max(e[3], w0), min(e[3] + e[4], w1), op_label(e[2], e[5]))
               for e in op_events if e[0] == plane
               and e[3] < w1 and e[3] + e[4] > w0]
        for label, ns in _self_times(ops):
            op_self[label] += ns
            op_count[label] += 1
        spans = _union([(s, e) for s, e, _ in ops])
        busy_ns += sum(e - s for s, e in spans)

        def module_at(t: int) -> Optional[Tuple[int, str]]:
            for i, (s, e, name) in enumerate(mods):
                if s <= t <= e:
                    return i, name
            return None

        edges = [(w0, w0)] + spans + [(w1, w1)]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b <= a:
                continue
            if b - a < small_gap_ns:
                gaps[f"gaps_under_{small_gap_ns // 1000}_us_between_ops"] \
                    += b - a
                continue
            before = module_at(a - 1) if a > w0 else None
            after = module_at(b + 1) if b < w1 else None
            if before and after and before[0] == after[0]:
                gaps[f"inside_{before[1]}"] += b - a
            else:
                left = before[1] if before else (
                    "window_start" if a == w0 else "no_program")
                right = after[1] if after else (
                    "window_end" if b == w1 else "no_program")
                gaps[f"{left}_-_{right}"] += b - a
    n = len(planes)
    coll = sum(ns for label, ns in op_self.items()
               if _COLLECTIVE.search(label))
    return {
        "devices": n,
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "collective_s": coll / n / 1e9,
        "op_self_s": {k: v / n / 1e9 for k, v in op_self.items()},
        "op_count": {k: v / n for k, v in op_count.items()},
        "module_ms": dict(modules),
        "gap_s": {k: v / n / 1e9 for k, v in gaps.items()},
    }


def breakdown(red: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    def rank(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": rank(red.get("op_self_s", {})),
            "idle_gaps": rank(red.get("gap_s", {}))}


def describe(path: str, per_line: int = 6) -> str:
    """What a trace file holds, for reading one by hand: planes, lines,
    event counts and a few events of each line with their stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:per_line]:
                stats = {k: str(v)[:80] for k, v in ev.stats}
                out.append(f"    {ev.name[:100]!r} start={ev.start_ns} "
                           f"dur={ev.duration_ns} {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1]))
