"""Training: `JaxTrainer.fit()` with one worker that owns every chip of the
cell; everything is measured from inside its loop (only the chip's owner can
time or trace it). The loop is the benchmark's: weights from the seed through
`ShardedTrainStep(init_params_fn=...)`, the comparison with the reference,
warm-up steps, then AdamW steps for the window, each batch taken from a
seeded pool through the trainer's `shard_batch`. A step is counted when its
loss has been waited for; the next one is already queued, so the device
never waits for the host."""
from __future__ import annotations

import gc
import os
import time
from typing import Any, Dict

from chipbench import common


def loop(c: Dict[str, Any]) -> None:
    from chipbench import inworker as iw

    iw.enter(c["rehearse"])
    import jax

    from chipbench import traffic_gen, weights
    from ray_tpu import train
    from ray_tpu.models import transformer as tfm
    from ray_tpu.train.step import ShardedTrainStep

    mix, seed = c["mix"], c["seed"]
    mesh = train.get_mesh()
    cfg = iw.transformer_config(c["config"], c["rehearse"], remat=mix["remat"],
                                remat_policy=mix["remat_policy"])
    sz = iw.sizes(c["config"], c["rehearse"])
    loss_fn = lambda p, b: tfm.loss_fn(p, b, cfg, shift_inputs=True)
    ts = ShardedTrainStep(
        init_params_fn=None,  # the weights are the benchmark's, see below
        loss_fn=loss_fn, logical_specs=tfm.param_logical_specs(cfg),
        mesh=mesh)
    # Not ts.init(): its jitted optimizer.init leaves AdamW's moments
    # replicated (zeros_like has no data dependence for GSPMD to follow, and
    # their out_shardings is None): 15 GB a chip for this model on the 2x2
    # mesh. Here every moment gets its parameter's sharding.
    def init(key):
        params = weights.program_params(key, sz)
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
    check = iw.train_check(loss_fn, params, mesh, sz, seed,
                           mix["check"]["batch"], mix["seq"])
    iw.stamp("check")
    pool = traffic_gen.train_tokens(mix, seed, sz.V)
    losses = []
    for i in range(mix["warm_steps"]):
        params, opt, loss = ts.step(
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
        params, opt, loss = ts.step(
            params, opt, ts.shard_batch({"tokens": pool[i % len(pool)]}))
        i += 1
        if waiting is not None:
            losses.append(float(waiting))  # waits for the step before
            ends.append(time.monotonic() - t0)
            if ends[-1] >= c["seconds"]:
                break
        waiting = loss
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
    # After the window: what the step program holds on a chip while it runs.
    ma = ts.lower_step(params, opt, ts.shard_batch(
        {"tokens": pool[0]})).compile().memory_analysis()
    step_bytes = int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    train.report({
        "check": check, "setup": setup, "losses": losses,
        "window_wall": window_wall, "step_ends": ends,
        "tokens_per_step": mix["batch"] * mix["seq"],
        "compiles_in_window": iw.COUNTS["compiles"] - compiles0,
        "device": iw.device_info(step_bytes), "trace": trace,
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
    return {
        "e2e": {"train_tok_s_chip": tokens / elapsed / cell["chips"]},
        "series": {"step_s": [b - a for a, b in zip([0.0] + ends, ends)]},
        "stats": {"steps": len(ends), "elapsed_s": elapsed,
                  "tokens_per_step": m["tokens_per_step"],
                  "seq": mix["seq"], "batch": mix["batch"],
                  "loss_first": m["losses"][0], "loss_last": m["losses"][-1],
                  "loss_finite": finite},
        "check": m["check"], "setup": m["setup"],
        "attempted": len(ends), "failed": 0 if finite else len(ends),
        "worker": {"end": {"compiles_in_window": m["compiles_in_window"],
                           "device": m["device"]}, "trace": m["trace"]},
        "teardown": {"teardown_s": time.time() - t},
    }
