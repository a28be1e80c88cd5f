"""Closed loop: `clients` callers each send their next request when the last
one has finished, from `ramp_s` before the window to its end. A slow system
receives less load, so throughput is what is judged: prompt plus output
tokens of the requests completed in the window, per second."""
from __future__ import annotations

from typing import Any, Dict

from chipbench.drivers import serve_common as sc


def run(cell: Dict[str, Any], args, phases: Dict[str, float]) -> Dict[str, Any]:
    b = sc.Bench(cell, args, phases)
    b.up()
    out = b.traffic("closed")
    recs, t0, side = out["recs"], out["t0"], out["side"]
    phases["window_start"] = side["window_wall"]
    down = b.down()
    w0, w1 = t0, t0 + args.seconds
    done, ok, first = sc.in_window(recs, w0, w1)
    series = {
        "ttft_s": [r["stamps"][0] - r["sent"] for r in first],
        "itl_s": sc.gaps_in(recs, w0, w1),
        "engine_ttft_s": side["end"]["engine_ttft_s"],
        "engine_tpot_s": side["end"]["engine_itl_mean_s"],
    }
    stats = {
        "slot_occupancy_pct": 100.0 * sc.slot_seconds(recs, w0, w1)
        / (args.seconds * b.mix["slots"]),
        "completed": len(ok),
    }
    tokens = sum(r["prompt_len"] + r["n_out"] for r in ok)
    return {
        "e2e": {"serve_tok_s": tokens / args.seconds},
        "series": series, "stats": stats, "check": b.check, "setup": b.setup,
        "attempted": len(done), "failed": len(done) - len(ok),
        "worker": side, "teardown": down,
    }
