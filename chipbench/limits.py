#!/usr/bin/env python3
"""Read the two numbers a limit is set from, in one process on the chip:
the program's comparison with the reference over several seeds, and the
control's, which has to come out as not correct.

    python3 chipbench/limits.py --workload <cell> --seeds 8 --control-seeds 3

The control is the nearest precision below the one the configuration states
(bfloat16 matmuls): for a serve cell the program itself with its
`quantize_int8` path on (weight-only int8); for a train cell, where the
program has no such path, the reference in the program's place with
float8_e4m3fn matmul operands. This process owns the chip itself (no
cluster): it builds the engine or the loss exactly as the deployment and the
training loop do, and swaps the weights seed by seed.
Prints one JSON line per reading and a summary; `--rehearse` runs a tiny
preset on the CPU (tests/test_control.py)."""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import common, inworker as iw  # noqa: E402


def serve_readings(cell, mix, seeds, control_seeds, rehearse):
    import jax

    from ray_tpu.models.quantize import quantize_params_int8
    from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

    cfg = iw.transformer_config(cell["config"], rehearse,
                                param_dtype=mix.get("param_dtype"))
    sz = iw.sizes(cell["config"], rehearse)
    eng = None
    for kind, seed in ([("program", s) for s in seeds] +
                       [("control", s) for s in control_seeds]):
        if eng is not None:
            eng.params = None
        params = iw.make_params(seed, cell["config"], rehearse,
                                mix.get("param_dtype"))
        if kind == "control":
            f32, params = params, None
            params = jax.block_until_ready(quantize_params_int8(f32))
            del f32
        if eng is None:
            eng = ContinuousBatchingEngine(
                cfg, params, num_slots=mix["slots"],
                max_prompt_len=mix["max_prompt_len"],
                max_new_tokens=mix["max_new_tokens"], seed=0)
        eng.params = params
        del params
        yield kind, seed, iw.serve_check(eng, sz, seed, mix["check"])


def train_readings(cell, mix, seeds, control_seeds, rehearse):
    import jax

    from chipbench import weights
    from chipbench.reference import dense_decoder as ref
    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.parallel import sharding as shd

    cfg = iw.transformer_config(cell["config"], rehearse, remat=mix["remat"],
                                remat_policy=mix["remat_policy"])
    sz = iw.sizes(cell["config"], rehearse)
    b, seq = mix["check"]["batch"], mix["seq"]
    if seeds:  # the control alone needs one device, not the cell's mesh
        mesh = make_mesh(MeshSpec(**(mix["mesh"] or {})),
                         devices=jax.devices())
        loss_fn = lambda p, b: tfm.loss_fn(p, b, cfg, shift_inputs=True)
        make = jax.jit(lambda key: weights.program_params(key, sz),
                       out_shardings=shd.tree_shardings(
                           mesh, tfm.param_logical_specs(cfg)))
    for seed in seeds:
        params = jax.block_until_ready(make(jax.random.key(seed)))
        yield "program", seed, iw.train_check(loss_fn, params, mesh, sz,
                                              seed, b, seq)
        del params
    for seed in control_seeds:
        yield "control", seed, iw.train_control(sz, seed, b, seq, ref.mm_fp8)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2000000000)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(common.ROOT, ".jax_cache"))
    cell = common.load_cell(args.workload)
    if args.rehearse:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
            f"device_count={cell['chips']}")
    from ray_tpu.util.jaxenv import enable_compile_cache

    enable_compile_cache()
    iw.enter(args.rehearse)
    mix = dict(cell["mix"])
    if args.rehearse:
        mix.update(mix.get("rehearsal", {}))
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    control = [args.first_seed + 104729 * (i + 1)
               for i in range(args.control_seeds)]
    fn = train_readings if mix["kind"] == "train" else serve_readings
    limits = cell["config"]["limits"]
    by = {"program": {}, "control": {}}
    for kind, seed, out in fn(cell, mix, seeds, control, args.rehearse):
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
