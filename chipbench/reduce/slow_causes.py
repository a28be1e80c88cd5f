"""Why a slow stretch was slow, from the runner's slow ring.

Since PR 51 every entry that the program's `phase` / `steps` put into a slow
ring (50 ms or more; ray_tpu/util/tracing.py) carries what its thread did
with the time, as `getrusage(RUSAGE_THREAD)` deltas: `cpu_ns`, `majflt`,
`inblock`, `nvcsw`, `nivcsw` over `over_ns` (the stretch and at most 10 ms
before it). A train loop that beats (`ShardedTrainStep.step`) records a late
step as `train.stall` (its excess over the median period, where the thread
spent the period, the machine's pressure, the watchdog's stack), and a
collection of generation 1 or 2 is `host.gc`. A worker's reach the runner's
ring as SLOW_PHASE events with its pid; `host_spans.runner_phases()` reads
that ring after shutdown.

`split(slow, marks)` is the whole reduction, on plain data, as
`setup_spans.split` is (the same ring, the same marks), so that it can be
checked on a recorded ring (reduce/recorded_stall_ring.json, a CPU
rehearsal's; chipbench/tests/test_slow_causes.py):

- a ring on which no entry carries `cpu_ns` is an older program's: None, and
  the three metrics are left out of the line;
- the chip-owning worker is `setup_spans.owner_pid`'s;
- `step_stall_ms`: the `dur_ns` (the excess) of its `train.stall` entries
  that start at or after the window's start and whose `profiler` is 0 (a
  stall that holds the start or the stop of a profiler session is the traced
  run's own doing), summed; 0.0 when there is none;
- `setup_runtime_wait_s`: over its `runtime.*` entries that start before the
  window, `dur_ns - cpu_ns` (the seconds its thread was off the CPU; an
  entry's share never below 0), summed; their `inblock` and `majflt` go to
  the notes;
- `ctrl_loop_wait_ms`: the same difference over the runner's own
  `ctrl.rpc.*` / `ctrl.periodic.*` entries of the whole run; 0.0 when the
  ring holds none.

Off the CPU is not yet starved: `dur_ns - cpu_ns` counts a stretch's own
blocking calls (a synchronous RPC, a child it waits for, a read) as it
counts a thread the machine did not run. `nvcsw` / `nivcsw` tell the two
apart where the kernel fills them; the chip machines' (4.4.0) reads them,
`majflt` and `inblock` as 0, and `cpu_ns` in 10 ms steps over a stretch and
up to 10 ms before it. So the notes carry what else the ring holds of the
same seconds: the runner's `ctrl.loop_lag` (how late the loop's 50 ms timer
fired) that overlaps the longest stretch, the longest lag of the run, and
for both whether they fall inside the owner's `runtime.backend_init`:
another process's start, which no handler waits for.

The notes hold every stall record whole, the longest controller stretch and
every `runtime.*` phase with its deltas, and the owner's slow collections."""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from chipbench.reduce import host_spans, setup_spans

SPENT = ("cpu_ns", "majflt", "inblock", "nvcsw", "nivcsw", "over_ns")
STALL = "train.stall"
CTRL = ("ctrl.rpc.", "ctrl.periodic.")
LAG = "ctrl.loop_lag"
BACKEND = "runtime.backend_init"
METRICS = ("step_stall_ms", "setup_runtime_wait_s", "ctrl_loop_wait_ms")

_attrs = setup_spans._attrs


def waited_ns(p: Dict[str, Any]) -> int:
    """The part of a stretch in which its thread was off the CPU: starved,
    or in a blocking call of its own."""
    return max(0, p["dur_ns"] - _attrs(p)["cpu_ns"])


def _meet(p: Dict[str, Any], others: List[Dict[str, Any]]
          ) -> List[Dict[str, Any]]:
    """Those of `others` that share some time with `p`."""
    a, b = setup_spans._iv(p)
    return [q for q in others if q["start_monotonic_ns"] < b
            and q["start_monotonic_ns"] + q["dur_ns"] > a]


def _told(p: Dict[str, Any], proc_ns: int) -> Dict[str, Any]:
    """One entry for the notes: where, how long, what its thread did."""
    a = _attrs(p)
    return dict({"name": p["name"], "dur_ms": p["dur_ns"] / 1e6,
                 "at_s": (p["start_monotonic_ns"] - proc_ns) / 1e9},
                **{k: a[k] for k in SPENT if k in a})


def split(slow: List[Dict[str, Any]],
          marks: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The three metrics and the notes from a slow ring and the marks; None
    when no entry of the ring says what its thread did (an older commit)."""
    timed = [p for p in slow if "cpu_ns" in _attrs(p)]
    if not timed:
        return None
    proc, window = marks["proc_ns"], marks["window_ns"]
    pid = setup_spans.owner_pid(slow, window)
    stalls = [p for p in slow if p["name"] == STALL
              and (pid is None or _attrs(p).get("pid") == pid)]
    counted = [p for p in stalls if p["start_monotonic_ns"] >= window
               and not _attrs(p).get("profiler")]
    runtime = [p for p in timed if p["name"] in setup_spans.RUNTIME
               and _attrs(p).get("pid") == pid
               and p["start_monotonic_ns"] < window]
    ctrl = [p for p in timed if p["name"].startswith(CTRL)]
    gcs = [p for p in slow if p["name"] == "host.gc"
           and _attrs(p).get("pid") == pid]
    lags = [p for p in slow if p["name"] == LAG and "pid" not in _attrs(p)]
    backend = [p for p in slow if p["name"] == BACKEND
               and _attrs(p).get("pid") == pid]
    out: Dict[str, Any] = {
        "step_stall_ms": sum(p["dur_ns"] for p in counted) / 1e6,
        "setup_runtime_wait_s": sum(map(waited_ns, runtime)) / 1e9,
        "ctrl_loop_wait_ms": sum(map(waited_ns, ctrl)) / 1e6,
    }
    notes: Dict[str, Any] = {
        "owner_pid": pid,
        "stalls_counted": len(counted),
        # every record whole: the stack and the pressure are the evidence
        "stalls": [dict(_attrs(p), dur_ms=p["dur_ns"] / 1e6, at_window_s=(
            p["start_monotonic_ns"] - window) / 1e9) for p in stalls],
        "runtime": [_told(p, proc) for p in runtime],
        "runtime_inblock": sum(_attrs(p)["inblock"] for p in runtime),
        "runtime_majflt": sum(_attrs(p)["majflt"] for p in runtime),
        "ctrl_slow_stretches": len(ctrl),
        "owner_gc_count_total_max_ms": [
            len(gcs), sum(p["dur_ns"] for p in gcs) / 1e6,
            max((p["dur_ns"] for p in gcs), default=0) / 1e6],
    }
    longest = max(ctrl, key=lambda p: p["dur_ns"], default=None)
    if longest is not None:
        notes["longest_ctrl"] = dict(
            _told(longest, proc), waited_ms=waited_ns(longest) / 1e6,
            loop_lag_ms=max((q["dur_ns"] for q in _meet(longest, lags)),
                            default=0) / 1e6,
            in_backend_init=bool(_meet(longest, backend)))
    lag = max(lags, key=lambda p: p["dur_ns"], default=None)
    if lag is not None:  # the loop's longest freeze, a body on it or none
        notes["longest_loop_lag"] = dict(
            _told(lag, proc), in_backend_init=bool(_meet(lag, backend)),
            under=[q["name"] for q in _meet(lag, ctrl)])
    out["notes"] = notes
    return out


def picture(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """The split of one run, computed once, kept in `ctx["slow_causes"]`,
    its notes in `ctx["notes"]["slow_causes"]`; empty when the program says
    nothing of what its threads did."""
    if "slow_causes" in ctx:
        return ctx["slow_causes"]
    ctx["slow_causes"] = pic = {}
    runner = host_spans.runner_phases()
    if not runner or not runner["slow"]:
        return pic
    got = split(runner["slow"], setup_spans.marks_of(ctx))
    if got is None:
        return pic
    ctx.setdefault("notes", {})["slow_causes"] = got.pop("notes")
    pic.update(got)
    return pic
