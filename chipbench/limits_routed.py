#!/usr/bin/env python3
"""limits_stack.py's method for a `train_stack_routed` cell
(drivers/train_stack_routed.py): the same readings (the program's comparison
with the reference over several seeds, and the float8 control's, which has to
come out as not correct), each with one number a group of gradient leaves
(`config["stack"]["groups"]`), since each group has a limit of its own.

    python3 chipbench/limits_routed.py --workload <cell> --seeds 4 --control-seeds 2

Prints one JSON line a reading and a summary; `--rehearse` runs the tiny
preset on the CPU."""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import common, inworker as iw, limits_stack  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2000000000)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(common.ROOT, ".jax_cache"))
    cell = common.load_cell(args.workload)
    from chipbench.drivers.train_stack_routed import grouped
    from ray_tpu.util.jaxenv import enable_compile_cache

    enable_compile_cache()
    iw.enter(args.rehearse)
    mix = dict(cell["mix"])
    if args.rehearse:
        mix.update(mix.get("rehearsal", {}))
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    control = [args.first_seed + 104729 * (i + 1)
               for i in range(args.control_seeds)]
    limits = cell["config"]["limits"]
    by = {"program": {}, "control": {}}
    for kind, seed, out in limits_stack.readings(cell, mix, seeds, control,
                                                 args.rehearse):
        out = grouped(cell["config"], out)
        print(json.dumps({"kind": kind, "seed": seed, **out}), flush=True)
        for k in limits:
            if k in out:
                by[kind].setdefault(k, []).append(out[k])
    nan = [float("nan")]
    summary = {k: {"program_max": max(by["program"].get(k, nan)),
                   "control_min": min(by["control"].get(k, nan)),
                   "limit": limits[k]} for k in limits}
    print(json.dumps({"workload": args.workload, "summary": summary,
                      "device": iw.device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
