"""Training a stack of several layer kinds with routed experts whose
configuration file names its own pieces: the loop of drivers/train_stack.py
for a `stack` section that also has

    "counters": the names of the device scalars the step returns beside the
                loss (`ShardedTrainStep(has_aux=True)`,
                `tfm.loss_fn(with_counters=True)`): the moe_* five,
    "groups":   {limit name: [compared leaves]}: each group of gradient
                leaves is held to a limit of its own, because a leaf behind
                a top-k selection moves by tens of percent on a rounding
                (drivers/train_hybrid.py `numbers`) and the others must not
                get that room.

`pieces`, `reference_grads` and `train_control` are train_stack.py's; the
stamps and returned keys are the other train drivers', the moe_* stats
train_hybrid.py's (a lost assignment fails the run). This is the driver the
fold of README_stack.md needs (README_routed.md)."""
from __future__ import annotations

import gc
import os
import time
from typing import Any, Dict

from chipbench import common
from chipbench.drivers.train_stack import (pieces, reference_grads,  # noqa: F401
                                           train_control as _control)


def grouped(config: Dict[str, Any], out: Dict[str, Any]) -> Dict[str, Any]:
    """inworker._train_numbers' result with one number a group of leaves
    (`config["stack"]["groups"]`) in place of the one over all of them."""
    by = out["grad_rel_err_by_leaf"]
    out = {k: v for k, v in out.items() if k != "train_grad_rel_err"}
    for limit, leaves in config["stack"]["groups"].items():
        found = [by[n] for n in leaves if n in by]
        if found:
            out[limit] = max(found)
    return out


def train_control(config, sz, seed: int, batch: int, seq: int
                  ) -> Dict[str, Any]:
    return grouped(config, _control(config, sz, seed, batch, seq))


def train_check(config, loss_fn, cfg, params, mesh, sz, seed: int,
                batch: int, seq: int) -> Dict[str, Any]:
    from chipbench.drivers import train_stack

    return grouped(config, train_stack.train_check(
        config, loss_fn, cfg, params, mesh, sz, seed, batch, seq))


def flash_plans(cfg, seq: int) -> Dict[str, Any]:
    """What a `flash.plan` observation of each attention kind's forward call
    carries at the cell's length (the kernels' own `tile_sizes` and
    `tile_plan`; the observation itself is made where a call is traced, in
    the set-up): {"swa": ..., "attn": ...} for the kinds the stack has."""
    from ray_tpu.ops import flash_attention as fa

    out, hd = {}, cfg.head_dim
    for mixer in sorted({m for m, _ in cfg.layer_kinds()} & {"swa", "attn"}):
        window = cfg.sliding_window if mixer == "swa" else None
        if window is not None and window >= seq:
            window = None
        bq, bk, sub = fa.tile_sizes(seq, hd, hd, cfg.dtype)
        out[mixer] = dict(fa.tile_plan(seq, bq, bk, sub, True,
                                       window)._asdict(), window=window or 0)
    return out


# -------------------------------------------------------------------- loop


def loop(c: Dict[str, Any]) -> None:
    from chipbench import inworker as iw

    iw.enter(c["rehearse"])
    import jax
    import optax

    from chipbench import traffic_gen
    from ray_tpu import train
    from ray_tpu.models import transformer as tfm
    from ray_tpu.train.step import ShardedTrainStep

    mix, seed = c["mix"], c["seed"]
    mesh = train.get_mesh()
    cfg = iw.transformer_config(c["config"], c["rehearse"], remat=mix["remat"],
                                remat_policy=mix["remat_policy"])
    weights = pieces(c["config"])[0]
    sz = weights.sizes_of(c["config"], c["rehearse"])
    names = c["config"]["stack"]["counters"]
    ts = ShardedTrainStep(
        init_params_fn=None,  # the weights are the benchmark's, see below
        loss_fn=lambda p, b: tfm.loss_fn(p, b, cfg, shift_inputs=True,
                                         with_counters=True),
        logical_specs=tfm.param_logical_specs(cfg), mesh=mesh, has_aux=True,
        optimizer=optax.adamw(mix["lr"], weight_decay=0.0))

    # As drivers/train.py: every AdamW moment gets its parameter's sharding.
    def init(key):
        params = weights.program_params(key, sz, cfg)
        return params, ts.optimizer.init(params)

    flat = jax.tree.leaves(ts.param_shardings)
    replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    moments = iter(flat * 2)  # optax.adamw: count, mu, nu (parameter order)
    opt_sh = jax.tree.map(
        lambda a: next(moments) if a.ndim else replicated,
        jax.eval_shape(init, jax.random.key(0))[1])
    params, opt = jax.block_until_ready(jax.jit(
        init, out_shardings=(ts.param_shardings, opt_sh))(
        jax.random.key(seed)))
    iw.mark("weights_made")
    iw.stamp("weights")
    check = train_check(
        c["config"], lambda p, b: tfm.loss_fn(p, b, cfg, shift_inputs=True),
        cfg, params, mesh, sz, seed, mix["check"]["batch"], mix["seq"])
    iw.stamp("check")
    pool = traffic_gen.train_tokens(mix, seed, sz.V)
    # One compile for the loop and for the step's memory.
    ma = ts.compile_step(params, opt, ts.shard_batch(
        {"tokens": pool[0]})).memory_analysis()
    step_bytes = int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    losses, counters = [], []
    for i in range(mix["warm_steps"]):
        params, opt, loss, aux = ts.step(
            params, opt, ts.shard_batch({"tokens": pool[i % len(pool)]}))
        losses.append(float(loss))
    iw.stamp("warm")
    setup = iw.setup_report()
    gc.collect()  # the set-up's garbage, now and not inside the window

    compiles0 = iw.COUNTS["compiles"]
    window_wall, t0 = time.time(), time.monotonic()
    ends, waiting, trace, t_trace = [], None, None, None
    i = mix["warm_steps"]
    while True:
        params, opt, loss, aux = ts.step(
            params, opt, ts.shard_batch({"tokens": pool[i % len(pool)]}))
        i += 1
        if waiting is not None:
            losses.append(float(waiting[0]))  # waits for the step before
            seen = ts.observe_counters(waiting[1])
            counters.append({n: seen[n] for n in names})
            ends.append(time.monotonic() - t0)
            if ends[-1] >= c["seconds"]:
                break
        waiting = (loss, aux)
        if c["trace"] and trace is None and ends:
            # A traced run reports no rate: the profiler's start and the
            # writing of its file stall the loop.
            if t_trace is None and ends[-1] >= mix["trace"]["start_s"]:
                iw.trace_start()
                t_trace = ends[-1]
            elif t_trace is not None and (
                    ends[-1] - t_trace >= mix["trace"]["seconds"]):
                jax.block_until_ready(loss)
                trace = iw.trace_stop()
    jax.block_until_ready(loss)
    from ray_tpu.util import tracing

    table = tracing.phase_table()
    train.report({
        "check": check, "setup": setup, "losses": losses,
        "window_wall": window_wall, "step_ends": ends,
        "tokens_per_step": mix["batch"] * mix["seq"],
        "compiles_in_window": iw.COUNTS["compiles"] - compiles0,
        "device": iw.device_info(step_bytes), "trace": trace,
        "counters": counters, "flash_plans": flash_plans(cfg, mix["seq"]),
        "phases": {k: v["count"] for k, v in table.items()
                   if k.startswith(("train.moe_", "flash.plan"))},
    })


def run(cell: Dict[str, Any], args, phases: Dict[str, float]) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    mix = dict(cell["mix"])
    if args.rehearse:
        mix.update(mix.get("rehearsal", {}))
    ray_tpu.init(**({"num_cpus": 4} if args.rehearse else {}))
    if not args.rehearse:
        found = int(ray_tpu.cluster_resources().get("TPU", 0))
        if found < cell["chips"]:
            raise SystemExit(
                f"chipbench: {found} chips, cell needs {cell['chips']}")
    trainer = JaxTrainer(
        loop,
        train_loop_config={"config": cell["config"], "mix": mix,
                           "seed": args.seed, "seconds": args.seconds,
                           "trace": bool(args.trace),
                           "rehearse": args.rehearse},
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=not args.rehearse,
            tpus_per_worker=cell["chips"]),
        run_config=RunConfig(name="chipbench", storage_path=os.path.join(
            common.RUN_DIR, "train")),
        mesh_shape=mix["mesh"])
    m = trainer.fit().metrics
    phases["ready"] = m["setup"]["stamps"]["warm"]
    phases["window_start"] = m["window_wall"]
    t = time.time()
    owners = common.child_pids()
    ray_tpu.shutdown()
    left = common.wait_gone(owners, 120)
    if left:
        raise SystemExit(f"chipbench: workers still alive: {left}")
    ends = m["step_ends"]  # every step whose end was seen, the last one
    elapsed = ends[-1]     # closing the window
    tokens = len(ends) * m["tokens_per_step"]
    finite = all(x == x and abs(x) != float("inf") for x in m["losses"])
    cs = m["counters"]  # one entry a counted step
    assigned = sum(c["moe_assigned"] for c in cs)
    dropped = sum(c["moe_dropped"] for c in cs)
    return {
        "e2e": {"train_tok_s_chip": tokens / elapsed / cell["chips"]},
        "series": {"step_s": [b - a for a, b in zip([0.0] + ends, ends)]},
        "stats": {"steps": len(ends), "elapsed_s": elapsed,
                  "tokens_per_step": m["tokens_per_step"],
                  "seq": mix["seq"], "batch": mix["batch"],
                  "loss_first": m["losses"][0], "loss_last": m["losses"][-1],
                  "loss_finite": finite,
                  "moe_assigned_a_step": assigned / len(cs),
                  "moe_dropped": dropped,
                  "moe_past_buffer": sum(c["moe_past_buffer"] for c in cs),
                  "moe_dropped_pct": 100.0 * dropped / assigned,
                  "moe_assigned_first": cs[0]["moe_assigned"],
                  "moe_assigned_last": cs[-1]["moe_assigned"],
                  "moe_load_max": max(c["moe_load_max"] for c in cs),
                  "moe_load_mean": sum(c["moe_load_mean"] for c in cs)
                  / len(cs),
                  "phases": m["phases"], "flash_plans": m["flash_plans"]},
        "check": m["check"], "setup": m["setup"],
        "attempted": len(ends),  # a lost assignment fails the run
        "failed": 0 if finite and not dropped else len(ends),
        "worker": {"end": {"compiles_in_window": m["compiles_in_window"],
                           "device": m["device"]}, "trace": m["trace"]},
        "teardown": {"teardown_s": time.time() - t},
    }
