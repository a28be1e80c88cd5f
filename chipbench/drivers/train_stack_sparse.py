"""Training a stack whose attention keeps a learned selection of keys, on
batches that carry positions and a mask: the loop of
drivers/train_stack_routed.py (its `pieces`, `grouped`, stamps, returned keys
and moe_* stats) for a `stack` section whose `counters` also name
`dsa_selected` and `dsa_index_loss`, with what that loop cannot be given
without an edit:

- the batch. `batches(mix, seed, vocab)` makes, for every sequence, `tokens`
  [seq + 1], `positions` [3, seq + 1] and `mask` [seq + 1]: `mix["images"]`
  images of `mix["image_side"]` x `mix["image_side"]` merged patches, image i
  starting at a seeded offset inside the i-th of as many equal parts of the
  sequence, text between; positions by Qwen2-VL's rule (text counts up in all
  three streams alike; an image at counter c has p = (c, c + row, c + col) and
  the text after it resumes at c + side); `mask` 0 over image positions, 1
  over text; ids uniform over the vocabulary slice at every position (no
  tower: an image position's id is an id like any other).
- the comparison (`train_check`). The program's loss, gradient leaves AND
  selection (`loss_fn(with_selection=True)`: every layer's kept keys as bits,
  from the cell's own configuration) on a seeded sample at the cell's length;
  the reference
  (`config["stack"]["reference"]`.loss_and_grads) run GIVEN that selection, so
  that a neighbour swapped at a threshold by a bfloat16 rounding does not
  loosen every other limit; and the selection held to the reference's own
  float32 top-k apart: `dsa_selection_mismatch` (the share of the program's
  kept pairs the reference's top-k does not hold) and `dsa_selection_margin`
  (how far under the reference's threshold the furthest of them scores, in
  units of its row's score spread; `dsa_selection_margin_mean`, the same
  distance averaged over them, is printed). `dsa_index_loss_rel_err` beside
  the loss's. Groups of leaves as `train_stack_routed.grouped`.
- the run's exactness: `dsa_selected` must be layers x batch x
  sum_t min(t + 1, topk) in EVERY counted step; a step where it is not fails
  the run as a lost assignment does (`stats.dsa_selected_exact`).

See README_sparse.md."""
from __future__ import annotations

import gc
import os
import time
from typing import Any, Dict

import numpy as np

from chipbench import common
from chipbench.drivers.train_stack import pieces
from chipbench.drivers.train_stack_routed import grouped


# ------------------------------------------------------------------ batches


def layout(rng: np.random.Generator, n: int, images: int, side: int):
    """(positions [3, n], mask [n]) of one sequence of n positions."""
    part, area = n // images, side * side
    if area > part:
        raise ValueError(f"an image of {area} positions does not fit a part "
                         f"of {part}")
    starts = [i * part + int(rng.integers(0, part - area + 1))
              for i in range(images)]
    pos = np.zeros((3, n), np.int64)
    mask = np.ones(n, np.int32)
    patch = np.arange(area)
    at = c = 0  # where in the sequence, the position counter
    for s in starts + [n]:
        text = np.arange(s - at)
        pos[:, at:s] = c + text
        c += s - at
        if s == n:
            break
        pos[0, s:s + area] = c
        pos[1, s:s + area] = c + patch // side
        pos[2, s:s + area] = c + patch % side
        mask[s:s + area] = 0
        at, c = s + area, c + side
    return pos.astype(np.int32), mask


def batches(mix: Dict[str, Any], seed: int, vocab: int, pool=None,
            batch=None) -> Dict[str, np.ndarray]:
    """{"tokens" [pool, batch, seq + 1], "positions" [pool, 3, batch,
    seq + 1], "mask" [pool, batch, seq + 1]} a run cycles through."""
    rng = np.random.default_rng(seed)
    P, B, n = pool or mix["pool"], batch or mix["batch"], mix["seq"] + 1
    tokens = rng.integers(0, vocab, (P, B, n), dtype=np.int32)
    made = [[layout(rng, n, mix["images"], mix["image_side"])
             for _ in range(B)] for _ in range(P)]
    return {"tokens": tokens,
            "positions": np.stack([np.stack([m[0] for m in row], axis=1)
                                   for row in made]),
            "mask": np.stack([np.stack([m[1] for m in row]) for row in made])}


def one(pool: Dict[str, np.ndarray], i: int) -> Dict[str, np.ndarray]:
    return {k: v[i % len(v)] for k, v in pool.items()}


def selected_a_step(cfg, batch: int, seq: int) -> float:
    """What `dsa_selected` must read every step."""
    k = min(cfg.dsa_topk, seq)
    layers = sum(m == "dsa" for m, _ in cfg.layer_kinds())
    return float(layers * batch * (k * (k + 1) // 2 + (seq - k) * k))


# ------------------------------------------------------------------- check


def _numbers(config, loss_p, g_p, index_p, loss_r, g_r, aux) -> Dict[str, Any]:
    from chipbench import inworker as iw

    out = grouped(config, iw._train_numbers(loss_p, g_p, loss_r, g_r))
    kept, index_r = float(aux["kept"]), float(aux["index"])
    out.update(
        dsa_selection_mismatch=float(aux["missed"]) / kept,
        dsa_selection_margin=float(aux["margin"]),
        dsa_selection_margin_mean=float(aux["margin_sum"]) / max(
            float(aux["missed"]), 1.0),
        dsa_index_loss_rel_err=abs(index_p - index_r) / abs(index_r),
        index_loss_program=index_p, index_loss_reference=index_r,
        selection_kept=kept, selection_missed=float(aux["missed"]))
    return out


def reference_run(ref, sz, key, batch, mm=None, selection=None,
                  index_dtype="float32"):
    """(loss, gradient leaves, aux) of the reference on the first device,
    given a selection or making its own."""
    import jax
    import jax.numpy as jnp

    from chipbench import inworker as iw

    dev0 = jax.local_devices()[0]
    put = lambda a: jax.device_put(jnp.asarray(a), dev0)
    args = (jax.device_put(key, dev0), {k: put(v) for k, v in batch.items()})
    if selection is not None:
        args += (put(selection),)
    compiled = jax.jit(lambda k, b, s=None: ref.loss_and_grads(
        k, b, sz, mm or ref.mm_f32, s, jnp.dtype(index_dtype))).lower(
        *args).compile()
    iw.mark("ref_loaded")
    loss, g, aux = compiled(*args)
    loss = float(loss)
    iw.mark("ref_ran")
    return loss, g, aux


def train_control(config, sz, seed: int, mix, lowered: str = "matmuls"
                  ) -> Dict[str, Any]:
    """A control: the reference in the program's place, one precision step
    down in `lowered` ("matmuls": float8 operands in every matmul; "index":
    L_I formed in bfloat16, the scores as it reads them, their logsumexp and
    the target with it, every matmul float32), its own selection, its loss and
    gradients under it, against the float32 reference given that selection."""
    import jax

    ref = pieces(config)[1]
    batch = one(batches(mix, seed, sz.V, 1, mix["check"]["batch"]), 0)
    key = jax.random.key(seed)
    how = {"matmuls": dict(mm=ref.mm_fp8),
           "index": dict(index_dtype="bfloat16")}[lowered]
    loss_c, g_c, aux_c = reference_run(ref, sz, key, batch, **how)
    loss_r, g_r, aux_r = reference_run(ref, sz, key, batch,
                                       selection=aux_c["bits"])
    return _numbers(config, loss_c, g_c, float(aux_c["index"]), loss_r, g_r,
                    aux_r)


def train_check(config, cfg, params, mesh, sz, seed: int, mix
                ) -> Dict[str, Any]:
    """The program's loss, compared gradient leaves and selection on a seeded
    sample at the cell's length, against the reference given that selection."""
    import jax

    from chipbench import inworker as iw
    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel import sharding as shd

    weights, ref = pieces(config)
    batch = one(batches(mix, seed, sz.V, 1, mix["check"]["batch"]), 0)

    def pick(p, b):
        with shd.sharding_ctx(mesh, shd.DEFAULT_RULES):
            (loss, c), g = jax.value_and_grad(lambda p: tfm.loss_fn(
                p, b, cfg, shift_inputs=True, with_counters=True,
                with_selection=True), has_aux=True)(p)
        return (loss, weights.program_leaves(cfg, sz, g), c["dsa_selection"],
                c["dsa_index_loss"], c["dsa_selected"])

    loss_p, g_p, bits, index_p, selected = jax.jit(pick)(
        params, shd.shard_batch(mesh, batch))
    loss_p = float(loss_p)
    iw.mark("check_program")
    out = _numbers(config, loss_p, g_p, float(index_p), *reference_run(
        ref, sz, jax.random.key(seed), batch, selection=bits))
    iw.mark("check_reference")
    out["dsa_selected_exact"] = float(selected) == selected_a_step(
        cfg, mix["check"]["batch"], mix["seq"]) == out["selection_kept"]
    return out


# -------------------------------------------------------------------- loop


def loop(c: Dict[str, Any]) -> None:
    from chipbench import inworker as iw

    iw.enter(c["rehearse"])
    import jax
    import optax

    from ray_tpu import train
    from ray_tpu.models import transformer as tfm
    from ray_tpu.train.step import ShardedTrainStep

    mix, seed = c["mix"], c["seed"]
    mesh = train.get_mesh()
    cfg = iw.transformer_config(
        c["config"], c["rehearse"], remat=mix["remat"],
        remat_policy=mix["remat_policy"], fused_ce=mix.get("fused_ce"))
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
    # The moments are made AFTER the comparison: its program holds the
    # gradients, the layers' residuals and every layer's selection beside
    # the weights, and 4.5 GB of moments beside those do not fit the chip
    # (compiled for a v5e: 13.9 GB by memory_analysis).
    make = lambda key: weights.program_params(key, sz, cfg)
    flat = jax.tree.leaves(ts.param_shardings)
    replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    moments = iter(flat * 2)  # optax.adamw: count, mu, nu (parameter order)
    opt_sh = jax.tree.map(
        lambda a: next(moments) if a.ndim else replicated,
        jax.eval_shape(lambda k: ts.optimizer.init(make(k)),
                       jax.random.key(0)))
    params = jax.block_until_ready(jax.jit(
        make, out_shardings=ts.param_shardings)(jax.random.key(seed)))
    iw.mark("weights_made")
    iw.stamp("weights")
    check = train_check(c["config"], cfg, params, mesh, sz, seed, mix)
    iw.stamp("check")
    opt = jax.block_until_ready(jax.jit(
        ts.optimizer.init, out_shardings=opt_sh)(params))
    pool = batches(mix, seed, sz.V)
    feed = lambda i: ts.shard_batch(one(pool, i))
    # One compile for the loop and for the step's memory.
    ma = ts.compile_step(params, opt, feed(0)).memory_analysis()
    step_bytes = int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    losses, counters = [], []
    for i in range(mix["warm_steps"]):
        params, opt, loss, aux = ts.step(params, opt, feed(i))
        losses.append(float(loss))
    iw.stamp("warm")
    setup = iw.setup_report()
    gc.collect()  # the set-up's garbage, now and not inside the window

    compiles0 = iw.COUNTS["compiles"]
    window_wall, t0 = time.time(), time.monotonic()
    ends, waiting, trace, t_trace = [], None, None, None
    i = mix["warm_steps"]
    while True:
        params, opt, loss, aux = ts.step(params, opt, feed(i))
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

    from ray_tpu.ops import sparse_attention

    table = tracing.phase_table()
    train.report({
        "check": check, "setup": setup, "losses": losses,
        "window_wall": window_wall, "step_ends": ends,
        "tokens_per_step": mix["batch"] * mix["seq"],
        "compiles_in_window": iw.COUNTS["compiles"] - compiles0,
        "device": iw.device_info(step_bytes), "trace": trace,
        "counters": counters,
        "selected_a_step": selected_a_step(cfg, mix["batch"], mix["seq"]),
        "triangle_a_step": float(
            sum(m == "dsa" for m, _ in cfg.layer_kinds()) * mix["batch"]
            * mix["seq"] * (mix["seq"] + 1) // 2),
        "phases": {k: v["count"] for k, v in table.items()
                   if k.startswith(("train.moe_", "train.dsa_", "dsa.plan"))},
        "plan": dict(sparse_attention.plan(mix["seq"])._asdict(),
                     topk=cfg.dsa_topk, impl="threshold"),
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
    inexact = sum(c["dsa_selected"] != m["selected_a_step"] for c in cs)
    exact = not inexact and m["check"].pop("dsa_selected_exact")
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
                  "dsa_selected_a_step": sum(c["dsa_selected"] for c in cs)
                  / len(cs),
                  "dsa_selected_exact": bool(exact),
                  "dsa_selected_pct": 100.0 * sum(
                      c["dsa_selected"] for c in cs) / len(cs)
                  / m["triangle_a_step"],
                  "dsa_index_loss_first": cs[0]["dsa_index_loss"],
                  "dsa_index_loss_last": cs[-1]["dsa_index_loss"],
                  "phases": m["phases"], "dsa_plan": m["plan"]},
        "check": m["check"], "setup": m["setup"],
        "attempted": len(ends),  # a lost assignment or key fails the run
        "failed": 0 if finite and not dropped and exact else len(ends),
        "worker": {"end": {"compiles_in_window": m["compiles_in_window"],
                           "device": m["device"]}, "trace": m["trace"]},
        "teardown": {"teardown_s": time.time() - t},
    }
