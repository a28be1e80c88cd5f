"""Open loop: requests are sent on the seed's Poisson schedule whether or not
earlier ones have finished, from `ramp_s` before the window (the ramp fills
the slots and counts as set-up) to its end. Each request is timed from when
it was due, and the generator's own lateness is reported."""
from __future__ import annotations

from typing import Any, Dict

from chipbench.common import mean, pct
from chipbench.drivers import serve_common as sc


def run(cell: Dict[str, Any], args, phases: Dict[str, float]) -> Dict[str, Any]:
    b = sc.Bench(cell, args, phases)
    b.up()
    out = b.traffic("open")
    recs, t0, side = out["recs"], out["t0"], out["side"]
    phases["window_start"] = side["window_wall"]
    down = b.down()
    w0, w1 = t0, t0 + args.seconds
    done, ok, first = sc.in_window(recs, w0, w1)
    series = {
        "latency_per_tok_s": [(r["stamps"][-1] - (t0 + r["due"])) / r["n_out"]
                              for r in ok],
        "itl_s": sc.gaps_in(recs, w0, w1),
        "ttft_s": [r["stamps"][0] - (t0 + r["due"]) for r in first],
        "ttft_from_send_s": [r["stamps"][0] - r["sent"] for r in first],
        "tpot_s": [(r["stamps"][-1] - r["stamps"][0]) / (r["n_out"] - 1)
                   for r in ok if r["n_out"] > 1],
        "gen_lag_s": [r["sent"] - (t0 + r["due"]) for r in recs
                      if "sent" in r and r["due"] >= 0],
        "engine_ttft_s": side["end"]["engine_ttft_s"],
        "engine_tpot_s": side["end"]["engine_itl_mean_s"],
    }
    stats = {
        "slot_occupancy_pct": 100.0 * sc.slot_seconds(recs, w0, w1)
        / (args.seconds * b.mix["slots"]),
        "completed": len(ok),
        "in_flight_at_end": sum("sent" in r and "done" not in r
                                for r in recs),
        "tokens_streamed": sum(w0 <= s < w1 for r in recs
                               for s in r.get("stamps", [])),
        "itl_p50_ms": 1e3 * pct(series["itl_s"], 50),
        "itl_p95_ms": 1e3 * pct(series["itl_s"], 95),
        "itl_p99_ms": 1e3 * pct(series["itl_s"], 99),
        "engine_tpot_mean_ms": 1e3 * mean(series["engine_tpot_s"]),
    }
    return {
        "e2e": {"latency_per_tok_ms": 1e3 * mean(series["latency_per_tok_s"]),
                "tpot_mean_ms": 1e3 * mean(series["tpot_s"])},
        "series": series, "stats": stats, "check": b.check, "setup": b.setup,
        "attempted": len(done), "failed": len(done) - len(ok),
        "worker": side, "teardown": down,
    }
