#!/usr/bin/env python3
"""Find the knee of an open-loop serve cell once, on the chip: one
deployment, one fixed-length run at each rate, with a drain between rates.

    python3 chipbench/sweep.py --workload internlm2_1_8b.chat_open \\
        --rates 1.5,2,2.5,3,3.5,4 --seconds 25 --out chiprun_out/sweep.json

For each rate: requests sent and completed in the window, time to first token
and token-gap percentiles (from when each request was due), the requests in
flight at each quarter of the window, those beyond the slots at its end
(queued), and slot occupancy. The knee is the highest rate at which no queue
grows; the cell runs at 4/5 of it. The table is kept beside the mix file
(traffic/<mix>.sweep.json) with the rate chosen and why."""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import common  # noqa: E402
from chipbench.common import mean, pct  # noqa: E402
from chipbench.drivers import serve_common as sc  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25)
    ap.add_argument("--seed", type=int, default=424242)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    args.trace = 0
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(common.ROOT, ".jax_cache"))
    os.environ["CHIPBENCH_REHEARSE"] = str(int(args.rehearse))
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.makedirs(common.RUN_DIR, exist_ok=True)
    cell = common.load_cell(args.workload)
    b = sc.Bench(cell, args, {})
    b.up()
    rows = []

    def one_rate(rate: float):
        out = b.traffic("open", drain=True,
                        mix={"rate_rps": rate, "ramp_s": 0})
        recs, t0, t_end = out["recs"], out["t0"], out["t_end"]

        def in_flight(t):
            return sum(r["sent"] <= t < r["done"] for r in recs)

        flight = [in_flight(t0 + args.seconds * q)
                  for q in (0.25, 0.5, 0.75)] + [in_flight(t_end)]
        ttft = [r["stamps"][0] - (t0 + r["due"]) for r in recs if r["stamps"]]
        gaps = sc.gaps_in(recs, t0, t_end)
        done = [r for r in recs if r["done"] <= t_end]
        return {
            "rate_rps": rate, "sent": len(recs), "completed": len(done),
            "failed": sum(not r["ok"] for r in recs),
            "in_flight_at_quarters": flight,
            "queued_at_end": max(0, flight[-1] - b.mix["slots"]),
            "slot_occupancy_pct": 100.0 * sc.slot_seconds(recs, t0, t_end)
            / (args.seconds * b.mix["slots"]),
            "drain_s": out["drain_s"],
            "ttft_p50_ms": 1e3 * pct(ttft, 50), "ttft_p90_ms": 1e3 * pct(ttft, 90),
            "itl_p50_ms": 1e3 * pct(gaps, 50), "itl_p95_ms": 1e3 * pct(gaps, 95),
            "latency_per_tok_ms": 1e3 * mean(
                (r["stamps"][-1] - (t0 + r["due"])) / r["n_out"]
                for r in done if r["n_out"]),
            "tokens_per_s": sum(r["n_out"] for r in done) / args.seconds,
        }

    try:
        for rate in map(float, args.rates.split(",")):
            row = one_rate(rate)
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        b.down()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "seconds": args.seconds,
                   "seed": args.seed, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
