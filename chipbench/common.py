"""Shared by the runner, the drivers and the in-worker code: where files
live, how a cell is loaded by name, process clocks and waits.

Nothing here imports jax: the runner process never starts a backend (one
process per chip; the TPU workers own the chips)."""
from __future__ import annotations

import json
import os
import select
import time
from typing import Any, Dict, Iterable, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RUN_DIR = os.path.join(HERE, ".run")  # scratch of one run, wiped at start


def load_json(*parts: str) -> Any:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_manifest() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str) -> Dict[str, Any]:
    """One entry of BENCHMARK.json `workloads` with its configuration, its
    traffic mix and the metrics it reports, all found by name."""
    man = load_manifest()
    cand = load_json("candidates.json")  # built and run, not yet admitted
    cells = {w["name"]: w for w in cand["workloads"] + man["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                         f"or chipbench/candidates.json (have {sorted(cells)})")
    w = cells[name]
    man = {k: man[k] + cand[k] for k in ("end_to_end", "per_layer")}

    def reported(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "name": name, "chips": w["chips"], "why": w["why"],
        "config_name": w["config"], "mix_name": w["traffic"],
        "config": load_json("configs", w["config"] + ".json"),
        "mix": load_json("traffic", w["traffic"] + ".json"),
        "end_to_end": [m for m in man["end_to_end"] if reported(m)],
        "per_layer": [m for m in man["per_layer"] if reported(m)],
    }


def peaks_for(device_kind: str) -> Dict[str, Any]:
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(
            f"chipbench: device_kind {device_kind!r} is not in "
            f"chipbench/peaks.json (have {sorted(table)}); refusing to "
            "report against a peak that is not the device's")
    return table[device_kind]


def proc_start_wall(pid: Optional[int] = None) -> float:
    """Wall-clock time at which process `pid` (default: this one) was
    created, from /proc (10 ms resolution): set-up is counted from here, so
    the interpreter's start and the imports are inside it."""
    with open(f"/proc/{pid or os.getpid()}/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))


def child_pids() -> List[int]:
    """Live (non-zombie) direct children of this process."""
    me, out = os.getpid(), []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                state, ppid = f.read().rsplit(")", 1)[1].split()[:2]
        except (OSError, ValueError):
            continue
        if int(ppid) == me and state != "Z":
            out.append(int(pid))
    return out


def wait_gone(pids: Iterable[int], timeout: float) -> List[int]:
    """Block (event-driven, on pidfds) until every pid has exited; returns
    those still alive at the timeout."""
    deadline = time.monotonic() + timeout
    left = []
    for pid in pids:
        try:
            fd = os.pidfd_open(pid)
        except (ProcessLookupError, OSError):
            continue
        try:
            r, _, _ = select.select(
                [fd], [], [], max(0.0, deadline - time.monotonic()))
            if not r:
                left.append(pid)
            else:
                try:
                    os.waitpid(pid, os.WNOHANG)
                except ChildProcessError:
                    pass
        finally:
            os.close(fd)
    return left


def pct(values, q: float) -> Optional[float]:
    """Percentile by linear interpolation between order statistics."""
    vs = sorted(values)
    if not vs:
        return None
    k = (len(vs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(vs) - 1)
    return vs[lo] + (vs[hi] - vs[lo]) * (k - lo)


def mean(values) -> Optional[float]:
    vs = list(values)
    return sum(vs) / len(vs) if vs else None
