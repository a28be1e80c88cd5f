"""Training a hybrid stack (configs/kimi_linear_48b_a3b.json: KDA and MLA
mixers, a dense lead layer, sigmoid-routed experts of which this chip holds
a share): the loop of drivers/train.py with this model's weights
(weights_kimi_linear.py), reference (reference/kimi_linear.py) and check, the
same stamps and the same returned keys. New against train.py: the step
returns the routing counters beside the loss (`ShardedTrainStep(has_aux)`),
read where the loop waits for the loss and folded into the phase table and
the report. See README_hybrid.md: a `benchmark` issue may fold the two
drivers into one."""
from __future__ import annotations

import gc
import os
import time
from typing import Any, Dict

from chipbench import common


def hybrid_sizes(config: Dict[str, Any], rehearse: bool):
    from chipbench.weights_kimi_linear import sizes_of  # imports jax

    return sizes_of(config, rehearse)


# ------------------------------------------------------------------- check


def reference_grads(sz, key, toks, mm=None):
    import jax
    import jax.numpy as jnp

    from chipbench import inworker as iw
    from chipbench.reference import kimi_linear as ref

    dev0 = jax.local_devices()[0]
    args = (jax.device_put(key, dev0), jax.device_put(jnp.asarray(toks), dev0))
    compiled = jax.jit(lambda k, t: ref.loss_and_grads(
        k, t, sz, mm or ref.mm_f32)).lower(*args).compile()
    iw.mark("ref_loaded")
    loss, g = compiled(*args)
    loss = float(loss)
    iw.mark("ref_ran")
    return loss, g


def program_leaves(cfg, sz, g):
    """The five compared gradient leaves, from the program's gradient tree,
    in the reference's plain layout."""
    from ray_tpu.models.transformer import layer_params

    moe = layer_params(g, cfg, sz.l_moe)
    return {
        "final_norm": g["final_norm"],
        "kda_wo": layer_params(g, cfg, sz.l_kda)["kda_wo"].reshape(-1, sz.d),
        "mla_wkvb": layer_params(g, cfg, sz.l_mla)["mla_wkvb"].reshape(
            sz.lat, -1),
        "expert_down": moe["moe_w_down"][sz.e_pick],
        "router": moe["router"],
    }


ROUTED = ("expert_down", "router")


def numbers(loss_p, g_p, loss_r, g_r) -> Dict[str, Any]:
    """inworker._train_numbers, with the gradient leaves in two groups, each
    with a limit of its own: `train_grad_rel_err` over the leaves every
    token reaches (final norm, KDA W_o, MLA W_kvb) and
    `train_grad_rel_err_routed` over the two behind the top-8 selection (a
    held expert's down projection, the router), where a token whose 8th and
    9th scores are a rounding apart lands on another expert: bfloat16
    activations move those leaves by tens of percent with nothing wrong."""
    from chipbench import inworker as iw

    out = iw._train_numbers(loss_p, g_p, loss_r, g_r)
    by = out["grad_rel_err_by_leaf"]
    out["train_grad_rel_err"] = max(v for k, v in by.items()
                                    if k not in ROUTED)
    out["train_grad_rel_err_routed"] = max(by[k] for k in ROUTED)
    return out


def train_control(sz, seed: int, batch: int, seq: int, mm) -> Dict[str, Any]:
    from chipbench import inworker as iw

    key, toks = iw._sample(sz, seed, batch, seq)
    return numbers(*reference_grads(sz, key, toks, mm),
                   *reference_grads(sz, key, toks))


def train_check(loss_fn, cfg, params, mesh, sz, seed: int, batch: int,
                seq: int) -> Dict[str, Any]:
    """The program's loss and five gradient leaves (final norm, the last KDA
    layer's W_o, the MLA layer's W_kvb, one held expert's down projection
    and the router of the first expert layer) on a seeded sample of
    sequences at the cell's length, against the reference's."""
    import jax

    from chipbench import inworker as iw
    from ray_tpu.parallel import sharding as shd

    key, toks = iw._sample(sz, seed, batch, seq)

    def pick(p, b):
        with shd.sharding_ctx(mesh, shd.DEFAULT_RULES):
            loss, g = jax.value_and_grad(loss_fn)(p, b)
        return loss, program_leaves(cfg, sz, g)

    loss_p, g_p = jax.jit(pick)(params, shd.shard_batch(mesh, {"tokens": toks}))
    loss_p = float(loss_p)
    iw.mark("check_program")
    ref = reference_grads(sz, key, toks)
    iw.mark("check_reference")
    return numbers(loss_p, g_p, *ref)


# -------------------------------------------------------------------- loop


def loop(c: Dict[str, Any]) -> None:
    from chipbench import inworker as iw

    iw.enter(c["rehearse"])
    import jax
    import optax

    from chipbench import traffic_gen, weights_kimi_linear as weights
    from ray_tpu import train
    from ray_tpu.models import transformer as tfm
    from ray_tpu.train.step import ShardedTrainStep

    mix, seed = c["mix"], c["seed"]
    mesh = train.get_mesh()
    cfg = iw.transformer_config(c["config"], c["rehearse"], remat=mix["remat"],
                                remat_policy=mix["remat_policy"])
    sz = hybrid_sizes(c["config"], c["rehearse"])
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
        lambda p, b: tfm.loss_fn(p, b, cfg, shift_inputs=True), cfg, params,
        mesh, sz, seed, mix["check"]["batch"], mix["seq"])
    iw.stamp("check")
    pool = traffic_gen.train_tokens(mix, seed, sz.V)
    # One compile for the loop and for the step's memory (train.py compiles
    # the step again after the window to read it: a minute here).
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
            counters.append(ts.observe_counters(waiting[1]))
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
        "counters": counters,
        "counter_phases": {k: v["count"] for k, v in table.items()
                           if k.startswith("train.moe_")},
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
                  "counter_phases": m["counter_phases"]},
        "check": m["check"], "setup": m["setup"],
        "attempted": len(ends),  # a lost assignment fails the run
        "failed": 0 if finite and not dropped else len(ends),
        "worker": {"end": {"compiles_in_window": m["compiles_in_window"],
                           "device": m["device"]}, "trace": m["trace"]},
        "teardown": {"teardown_s": time.time() - t},
    }
