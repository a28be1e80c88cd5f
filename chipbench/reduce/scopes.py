"""Device time by named scope, from the same .xplane.pb the other readers use.

reduce/xplane.py keeps an op's HLO name and shape; the `jax.named_scope`s an
op was traced under (`kda`, `kda.core`, `mla`, `moe.route`, `moe.experts`)
are not in an event's own stats but in its *metadata* (`tf_op`, the op's
name stack: `jit(_step)/transpose(jvp(...))/checkpoint/kda/kda.core/dot_general:`;
found with a probe on the chip, PR 27), which jax.profiler.ProfileData does
not expose. So this file reads the protobuf's wire format itself (XSpace >
XPlane > event_metadata / stat_metadata / XLine > XEvent; field numbers of
tsl/profiler/protobuf/xplane.proto), nothing imported but the stdlib.

1. `load(path)`: [plane, op name, start_ns, dur_ns, tf_op] of every event
   on a device plane's "XLA Ops" line.
2. `by_scope(events, scopes)`: each op's self time (a `while` spans its
   body's ops; xplane._self_times) under the first of `scopes` that is a
   component of its name stack, else "other"; and the busy time. Devices
   are averaged. Forward, backward (`transpose(jvp(kda))` keeps the scope
   as a component) and rematerialised ops all count.

A fusion carries the name stack of one of the ops fused into it, so an
elementwise op fused across a scope's edge is counted on one side of it."""
from __future__ import annotations

import json
import os
import sys
from collections import defaultdict
from typing import Any, Dict, Iterator, List, Sequence, Tuple

if __name__ == "__main__":  # by hand: python3 chipbench/reduce/scopes.py F
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from chipbench.reduce import xplane  # noqa: E402

Event = Tuple[str, str, int, int, str]  # plane, op, start_ns, dur_ns, tf_op


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one message: ints for varints, bytes for
    length-delimited and fixed fields."""
    i, n = 0, len(b)
    while i < n:
        tag, i = _varint(b, i)
        wt = tag & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt in (1, 5):
            ln = 8 if wt == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"wire type {wt}")
        yield tag >> 3, v


def _map_entry(b: bytes) -> Tuple[int, bytes]:
    kv = dict(_fields(b))
    return kv.get(1, 0), kv.get(2, b"")


def load(path: str) -> List[Event]:
    with open(path, "rb") as f:
        space = f.read()
    events: List[Event] = []
    for fn, plane in _fields(space):
        if fn != 1:
            continue
        name, lines, emeta, smeta = "", [], {}, {}
        for f2, v in _fields(plane):
            if f2 == 2:
                name = v.decode()
            elif f2 == 3:
                lines.append(v)
            elif f2 == 4:
                k, m = _map_entry(v)
                emeta[k] = m
            elif f2 == 5:
                k, m = _map_entry(v)
                smeta[k] = dict(_fields(m)).get(2, b"").decode()
        if not name.startswith("/device:TPU"):
            continue
        tf_op_ids = {k for k, n in smeta.items() if n == "tf_op"}
        ops: Dict[int, Tuple[str, str]] = {}
        for mid, m in emeta.items():
            op, scope = "", ""
            for f3, v in _fields(m):
                if f3 == 2:
                    op = v.decode(errors="replace").split(" ")[0]
                elif f3 == 5:
                    st = dict(_fields(v))
                    if st.get(1) in tf_op_ids:
                        if 7 in st:  # ref_value: the string is a stat name
                            scope = smeta.get(st[7], "")
                        else:        # str_value or bytes_value
                            scope = st.get(5, st.get(6, b"")).decode(
                                errors="replace")
            ops[mid] = (op, scope)
        for line in lines:
            lf = defaultdict(list)
            for f3, v in _fields(line):
                lf[f3].append(v)
            if (lf.get(2) or [b""])[0].decode() != "XLA Ops":
                continue
            t0 = (lf.get(3) or [0])[0]
            for ev in lf.get(4, []):
                e = dict(_fields(ev))
                op, scope = ops.get(e.get(1, 0), ("", ""))
                events.append((name, op, t0 + e.get(2, 0) // 1000,
                               e.get(3, 0) // 1000, scope))
    return events


def load_json(path: str) -> List[Event]:
    with open(path) as f:
        return [tuple(e) for e in json.load(f)["events"]]


def scope_of(tf_op: str, scopes: Sequence[str]) -> str:
    parts = tf_op.rstrip(":").split("/")
    for s in scopes:
        if any(p == s or f"({s})" in p for p in parts):
            return s
    return "other"


def by_scope(events: List[Event], scopes: Sequence[str]) -> Dict[str, Any]:
    """{"busy_s", "scope_s": {scope or "other": self seconds}}, a device."""
    planes = sorted({e[0] for e in events})
    if not planes:
        return {"devices": 0, "busy_s": 0.0, "scope_s": {}}
    out: Dict[str, int] = defaultdict(int)
    busy = 0
    for plane in planes:
        ops = [(e[2], e[2] + e[3], scope_of(e[4], scopes))
               for e in events if e[0] == plane]
        for label, ns in xplane._self_times(ops):
            out[label] += ns
        busy += sum(e - s for s, e in xplane._union([(s, e)
                                                     for s, e, _ in ops]))
    n = len(planes)
    return {"devices": n, "busy_s": busy / n / 1e9,
            "scope_s": {k: v / n / 1e9 for k, v in out.items()}}


SCOPES = ("kda.core", "kda", "mla", "moe.route", "moe.experts")


def picture(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """by_scope of the run's trace file, computed once a run (ctx["scopes"]).
    {} when the run was not traced or the file is gone."""
    if "scopes" not in ctx:
        import glob

        from chipbench import inworker

        files = glob.glob(os.path.join(inworker.TRACE_DIR, "**",
                                       "*.xplane.pb"), recursive=True)
        ctx["scopes"] = by_scope(load(files[0]), SCOPES) if files and \
            ctx.get("trace") else {}
        if ctx["scopes"]:
            ctx.setdefault("notes", {})["scope_s"] = ctx["scopes"]["scope_s"]
    return ctx["scopes"]


if __name__ == "__main__":
    print(json.dumps(by_scope(load(sys.argv[1]), SCOPES), indent=1))
