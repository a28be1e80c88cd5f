"""Training a stack of several layer kinds whose configuration file names
its own pieces: the loop of drivers/train_hybrid.py with the weight maker,
the plain reference and the compared gradient leaves taken from
`config["stack"]` (module paths), not from imports written here:

    "stack": {"weights":   module with sizes_of(config, rehearse),
                           program_params(key, sz, cfg),
                           program_leaves(cfg, sz, grads) -> {leaf: array},
              "reference": module with loss_and_grads(key, tokens, sz, mm),
                           mm_f32, mm_fp8,
              "counts":    module with stack_flops_per_token(sz, seq), read
                           by metrics/train_mfu_stack_pct.py}

Same stamps and returned keys as the other two train drivers. One limit,
`train_grad_rel_err`, over every compared leaf (no leaf here is behind a
top-k). The step returns no counters. See README_stack.md: a `benchmark`
issue that folds the train drivers keeps this one."""
from __future__ import annotations

import gc
import importlib
import os
import time
from typing import Any, Dict

from chipbench import common


def pieces(config: Dict[str, Any]):
    """(weights module, reference module) the configuration names."""
    st = config["stack"]
    return (importlib.import_module(st["weights"]),
            importlib.import_module(st["reference"]))


# ------------------------------------------------------------------- check


def reference_grads(ref, sz, key, toks, mm=None):
    import jax
    import jax.numpy as jnp

    from chipbench import inworker as iw

    dev0 = jax.local_devices()[0]
    args = (jax.device_put(key, dev0), jax.device_put(jnp.asarray(toks), dev0))
    compiled = jax.jit(lambda k, t: ref.loss_and_grads(
        k, t, sz, mm or ref.mm_f32)).lower(*args).compile()
    iw.mark("ref_loaded")
    loss, g = compiled(*args)
    loss = float(loss)
    iw.mark("ref_ran")
    return loss, g


def train_control(config, sz, seed: int, batch: int, seq: int
                  ) -> Dict[str, Any]:
    """The control: the reference in the program's place with float8 matmul
    operands, against the reference."""
    from chipbench import inworker as iw

    ref = pieces(config)[1]
    key, toks = iw._sample(sz, seed, batch, seq)
    return iw._train_numbers(*reference_grads(ref, sz, key, toks, ref.mm_fp8),
                             *reference_grads(ref, sz, key, toks))


def train_check(config, loss_fn, cfg, params, mesh, sz, seed: int,
                batch: int, seq: int) -> Dict[str, Any]:
    """The program's loss and compared gradient leaves on a seeded sample of
    sequences at the cell's length, against the reference's."""
    import jax

    from chipbench import inworker as iw
    from ray_tpu.parallel import sharding as shd

    weights, ref = pieces(config)
    key, toks = iw._sample(sz, seed, batch, seq)

    def pick(p, b):
        with shd.sharding_ctx(mesh, shd.DEFAULT_RULES):
            loss, g = jax.value_and_grad(loss_fn)(p, b)
        return loss, weights.program_leaves(cfg, sz, g)

    loss_p, g_p = jax.jit(pick)(params, shd.shard_batch(mesh, {"tokens": toks}))
    loss_p = float(loss_p)
    iw.mark("check_program")
    out = reference_grads(ref, sz, key, toks)
    iw.mark("check_reference")
    return iw._train_numbers(loss_p, g_p, *out)


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
    loss_fn = lambda p, b: tfm.loss_fn(p, b, cfg, shift_inputs=True)
    ts = ShardedTrainStep(
        init_params_fn=None,  # the weights are the benchmark's, see below
        loss_fn=loss_fn, logical_specs=tfm.param_logical_specs(cfg),
        mesh=mesh, optimizer=optax.adamw(mix["lr"], weight_decay=0.0))

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
    check = train_check(c["config"], loss_fn, cfg, params, mesh, sz, seed,
                        mix["check"]["batch"], mix["seq"])
    iw.stamp("check")
    pool = traffic_gen.train_tokens(mix, seed, sz.V)
    # One compile for the loop and for the step's memory.
    ma = ts.compile_step(params, opt, ts.shard_batch(
        {"tokens": pool[0]})).memory_analysis()
    step_bytes = int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes)
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
    from ray_tpu.util import tracing

    core = {k: v["count"] for k, v in tracing.phase_table().items()
            if k.startswith(("ssd.core", "kda.core", "flash.plan"))}
    train.report({
        "check": check, "setup": setup, "losses": losses,
        "window_wall": window_wall, "step_ends": ends,
        "tokens_per_step": mix["batch"] * mix["seq"],
        "compiles_in_window": iw.COUNTS["compiles"] - compiles0,
        "device": iw.device_info(step_bytes), "trace": trace,
        "core_phases": core,
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
                  "loss_finite": finite,
                  "core_phases": m["core_phases"]},
        "check": m["check"], "setup": m["setup"],
        "attempted": len(ends),
        "failed": 0 if finite else len(ends),
        "worker": {"end": {"compiles_in_window": m["compiles_in_window"],
                           "device": m["device"]}, "trace": m["trace"]},
        "teardown": {"teardown_s": time.time() - t},
    }
