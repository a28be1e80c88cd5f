#!/usr/bin/env python3
"""limits.py's method for a `train_stack` cell (drivers/train_stack.py): the
two numbers a limit is set from, in one process on the chip. The program's
comparison with the reference its configuration names over several seeds,
and the control's (the reference in the program's place with float8_e4m3fn
matmul operands), which has to come out as not correct.

    python3 chipbench/limits_stack.py --workload <cell> --seeds 4 --control-seeds 2

Prints one JSON line a reading and a summary; `--rehearse` runs the tiny
preset on the CPU."""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import common, inworker as iw  # noqa: E402


def readings(cell, mix, seeds, control_seeds, rehearse):
    import jax

    from chipbench.drivers import train_stack as drv
    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.parallel import sharding as shd

    cfg = iw.transformer_config(cell["config"], rehearse, remat=mix["remat"],
                                remat_policy=mix["remat_policy"])
    weights = drv.pieces(cell["config"])[0]
    sz = weights.sizes_of(cell["config"], rehearse)
    b, seq = mix["check"]["batch"], mix["seq"]
    if seeds:
        mesh = make_mesh(MeshSpec(**(mix["mesh"] or {})),
                         devices=jax.devices())
        loss_fn = lambda p, b: tfm.loss_fn(p, b, cfg, shift_inputs=True)
        make = jax.jit(lambda key: weights.program_params(key, sz, cfg),
                       out_shardings=shd.tree_shardings(
                           mesh, tfm.param_logical_specs(cfg)))
    for seed in seeds:
        params = jax.block_until_ready(make(jax.random.key(seed)))
        yield "program", seed, drv.train_check(
            cell["config"], loss_fn, cfg, params, mesh, sz, seed, b, seq)
        del params
    for seed in control_seeds:
        yield "control", seed, drv.train_control(cell["config"], sz, seed, b,
                                                 seq)


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
    for kind, seed, out in readings(cell, mix, seeds, control, args.rehearse):
        print(json.dumps({"kind": kind, "seed": seed, **out}), flush=True)
        for k in limits:
            if k in out:
                by[kind].setdefault(k, []).append(out[k])
    summary = {k: {"program_max": max(by["program"].get(k, [float("nan")])),
                   "control_min": min(by["control"].get(k, [float("nan")])),
                   "limit": limits[k]}
               for k in limits if k in by["program"] or k in by["control"]}
    print(json.dumps({"workload": args.workload, "summary": summary,
                      "device": iw.device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
