#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that never starts a JAX backend itself (one process per chip:
the TPU workers own the chips). It finds the cell's configuration, traffic
mix, driver and metric readers by name, drives the system through its public
entry points (`ray_tpu.init`, `serve.run`, `JaxTrainer.fit`), measures for
`--seconds`, shuts down, waits until every worker it started is gone, and
prints, last, one JSON line: correct, attempted, failed, metrics, device
(and breakdown in a traced run). Earlier lines carry the set-up phases and
every number compared with the reference beside its limit. Off the chip it
prints no result and exits non-zero. `--rehearse` (not part of the contract)
walks the same control flow on the CPU at a tiny preset and refuses to print
a result line.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import signal
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench import common  # noqa: E402

TIME_LIMIT_S = 1150  # a checkout's first run compiles; the contract allows 1200


def say(**facts) -> None:
    print(json.dumps(facts), flush=True)


def die(msg: str) -> "NoReturn":  # noqa: F821
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    for pid in common.child_pids():
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    os._exit(1)


def phases_of(t_proc: float, ph: dict, stamps: dict) -> dict:
    """Seconds of each set-up phase, from the wall-clock stamps of the runner
    and of the chip-owning worker (one machine, one clock)."""
    order = sorted((stamps[k], k) for k in
                   ("worker_proc", "backend", "weights", "warm", "check")
                   if k in stamps)
    names = {"worker_proc": "cluster_s", "backend": "backend_s",
             "weights": "weights_s", "warm": "warm_s", "check": "check_s"}
    out, prev = {}, t_proc
    for t, k in order:
        out[names[k]] = t - prev
        prev = t
    out["ready_s"] = ph["ready"] - t_proc
    out["setup_s"] = ph["window_start"] - t_proc
    out["other_s"] = out["setup_s"] - sum(out[n] for n in names.values()
                                          if n in out)
    return out


def main() -> int:
    t_proc = common.proc_start_wall()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    args.seed %= 2 ** 31  # numpy and jax.random.key both take it

    signal.signal(signal.SIGALRM,
                  lambda *_: die(f"exceeded {TIME_LIMIT_S}s"))
    signal.alarm(TIME_LIMIT_S)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ["CHIPBENCH_REHEARSE"] = str(int(args.rehearse))
    shutil.rmtree(common.RUN_DIR, ignore_errors=True)
    os.makedirs(common.RUN_DIR)
    cell = common.load_cell(args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
            f"device_count={cell['chips']}")
    try:
        from ray_tpu.util.accelerators import detect_tpu_chips
    except ImportError as e:
        die(f"the system under test is not in this checkout: {e}")
    if not args.rehearse:
        plat = os.environ.get("JAX_PLATFORMS")
        if plat and "tpu" not in plat.split(","):
            die(f"JAX_PLATFORMS={plat!r} excludes the tpu platform; "
                "refusing to measure the host")
        chips = detect_tpu_chips()
        if chips < cell["chips"]:
            die(f"{chips} TPU chips found, cell {cell['name']} needs "
                f"{cell['chips']}; refusing to measure the host")

    driver = importlib.import_module(
        "chipbench.drivers." + cell["mix"]["kind"])
    ph = {}
    try:
        out = driver.run(cell, args, ph)
    except BaseException:
        import traceback

        traceback.print_exc()
        die(f"cell {cell['name']} failed")

    from chipbench import inworker
    from chipbench.metrics import readers
    from chipbench.reduce import xplane

    phases = phases_of(t_proc, ph, out["setup"]["stamps"])
    phases["teardown_s"] = out["teardown"]["teardown_s"]
    end = out["worker"]["end"]
    device = dict(end["device"])
    counts = {"setup_cache_misses": out["setup"]["cache_entries_added"],
              "compiles_in_window": end["compiles_in_window"]}
    t_w = out["setup"]["stamps"]["worker_proc"]
    say(phases=phases, counts=dict(counts, **out["setup"]["counts"]),
        fine_s_from_worker_start=[[n, round(t - t_w, 3)]
                                  for n, t in out["setup"].get("fine", [])],
        stats=out["stats"])

    limits = cell["config"]["limits"]
    compared = {k: {"value": v, "limit": limits[k]}
                for k, v in out["check"].items() if k in limits}
    say(compared=compared, check=out["check"])
    correct = bool(compared) and all(
        c["value"] == c["value"] and c["value"] <= c["limit"]
        for c in compared.values())
    correct = correct and out["stats"].get("loss_finite", True)

    ctx = {"phases": phases, "counts": counts, "series": out["series"],
           "stats": out["stats"], "e2e": dict(out["e2e"], setup_s=phases[
               "setup_s"]), "cell": cell, "mix": cell["mix"],
           "sizes": inworker.sizes(cell["config"], args.rehearse),
           "peaks": None if args.rehearse else common.peaks_for(
               device["kind"]), "trace": None}
    breakdown = None
    if args.trace:
        tr = out["worker"].get("trace")
        if not tr or not tr.get("xplane"):
            die("the traced run wrote no xplane file")
        ctx["trace"] = red = xplane.reduce(xplane.load(tr["xplane"]))
        if not red.get("busy_s"):
            die("no operation ran on the device in the traced window")
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        breakdown = xplane.breakdown(red)
        wanted = cell["per_layer"]
        values = {m["name"]: readers.read(m["name"], ctx) for m in wanted}
        if ctx.get("notes"):
            say(notes=ctx["notes"])
    else:
        wanted = cell["end_to_end"]
        values = {m["name"]: ctx["e2e"].get(m["name"]) for m in wanted}
        missing = [k for k, v in values.items() if v is None or v != v]
        if missing:
            die(f"end-to-end metrics not measured: {missing}")
    units = {m["name"]: m["unit"] for m in wanted}
    result = {
        "correct": correct, "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items() if v is not None and v == v},
        "device": device,
    }
    if breakdown:
        result["breakdown"] = breakdown
    if args.rehearse:
        print("chipbench: rehearsal on the host; these are not device "
              "numbers and no result line is printed:\n" +
              json.dumps(result)[:2000], file=sys.stderr)
        return 3
    if not os.environ.get("CHIPBENCH_KEEP_RUN"):  # keeps the xplane file
        shutil.rmtree(common.RUN_DIR, ignore_errors=True)
    say(**result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
