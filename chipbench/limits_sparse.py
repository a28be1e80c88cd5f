#!/usr/bin/env python3
"""limits_routed.py's method for a `train_stack_sparse` cell
(drivers/train_stack_sparse.py): the readings every limit of its comparison
is set from, in one process on the chip.

- `program`: the program's comparison with the reference given the program's
  selection, over several seeds (`train_check`).
- `control`: the reference in the program's place with float8_e4m3fn matmul
  operands, one precision step down (its own selection, held to the float32
  reference's top-k; its loss and gradients against the float32 reference
  given that selection), which has to come out as not correct.
- `bf16_index`: the reference in the program's place with L_I formed in
  bfloat16 (the scores as the loss reads them, their logsumexp, the target
  and the KL: one step down in what the configuration states as float32 for
  them; every matmul and the selection float32), read the same way: it too
  has to come out as not correct.
- `bf16_params`: the PROGRAM with its parameters rounded to bfloat16 (what
  `param_dtype: bfloat16` would hold), against the float32 reference made
  from the seed. It reads inside the sound seeds' spread (every product
  already takes its weights as bfloat16 operands, and one gradient does not
  see an update lost under a bfloat16 ulp): printed for the record, no limit
  is set from it.

    python3 chipbench/limits_sparse.py --workload <cell> --seeds 4 --control-seeds 2

Prints one JSON line a reading, each with the verdict chipbench/run.py's
comparison gives it under the configuration's limits (`correct`, and
`over`: the numbers past their limit), and a summary; `--rehearse` runs the
tiny preset on the CPU."""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import common, inworker as iw  # noqa: E402


def verdict(out, limits):
    """run.py's comparison: every number that has a limit is within it."""
    over = [k for k in limits if k in out
            and not (out[k] == out[k] and out[k] <= limits[k])]
    return {"correct": not over and any(k in out for k in limits),
            "over": over}


def readings(cell, mix, seeds, control_seeds, index_seeds, rounded_seeds,
             rehearse):
    import jax
    import jax.numpy as jnp

    from chipbench.drivers import train_stack_sparse as drv
    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.parallel import sharding as shd

    cfg = iw.transformer_config(
        cell["config"], rehearse, remat=mix["remat"],
        remat_policy=mix["remat_policy"], fused_ce=mix.get("fused_ce"))
    weights = drv.pieces(cell["config"])[0]
    sz = weights.sizes_of(cell["config"], rehearse)
    mesh = make_mesh(MeshSpec(**(mix["mesh"] or {})), devices=jax.devices())
    make = jax.jit(lambda key: weights.program_params(key, sz, cfg),
                   out_shardings=shd.tree_shardings(
                       mesh, tfm.param_logical_specs(cfg)))
    rounded = jax.jit(lambda p: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype), p))
    for kind, some in (("program", seeds), ("bf16_params", rounded_seeds)):
        for seed in some:
            params = jax.block_until_ready(make(jax.random.key(seed)))
            if kind == "bf16_params":
                params = jax.block_until_ready(rounded(params))
            yield kind, seed, drv.train_check(cell["config"], cfg, params,
                                              mesh, sz, seed, mix)
            del params
    for kind, lowered, some in (("control", "matmuls", control_seeds),
                                ("bf16_index", "index", index_seeds)):
        for seed in some:
            yield kind, seed, drv.train_control(cell["config"], sz, seed, mix,
                                                lowered)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--index-seeds", type=int, default=1)
    ap.add_argument("--rounded-seeds", type=int, default=1)
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
    index = [args.first_seed + 32452843 * (i + 1)
             for i in range(args.index_seeds)]
    rounded = [args.first_seed + 15485863 * (i + 1)
               for i in range(args.rounded_seeds)]
    limits = cell["config"]["limits"]
    by = {"program": {}, "control": {}, "bf16_index": {}, "bf16_params": {}}
    verdicts = {k: [] for k in by}
    for kind, seed, out in readings(cell, mix, seeds, control, index, rounded,
                                    args.rehearse):
        v = verdict(out, limits)
        verdicts[kind].append(v["correct"])
        print(json.dumps({"kind": kind, "seed": seed, **v, **out}),
              flush=True)
        for k in list(limits) + ["dsa_selection_margin_mean"]:
            if k in out:
                by[kind].setdefault(k, []).append(out[k])
    nan = [float("nan")]
    summary = {k: {"program_max": max(by["program"].get(k, nan)),
                   "control_min": min(by["control"].get(k, nan)),
                   "bf16_index_min": min(by["bf16_index"].get(k, nan)),
                   "bf16_params_min": min(by["bf16_params"].get(k, nan)),
                   "limit": limits.get(k)}
               for k in list(limits) + ["dsa_selection_margin_mean"]}
    print(json.dumps({"workload": args.workload, "summary": summary,
                      "correct": verdicts, "device": iw.device_info()}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
