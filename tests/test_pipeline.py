"""Pipeline parallelism (GPipe over the `pipe` mesh axis) on the virtual CPU
mesh. Numeric ground truth is the plain single-mesh forward/backward on the
same params (SURVEY §5.7 done bar: pipe=2 matches single-device numerics)."""
import jax
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import llama_tiny, gpt2_tiny
from ray_tpu.parallel import MeshSpec, RULES_TP, make_mesh
from ray_tpu.parallel.pipeline import pipeline_loss_fn
from ray_tpu.train.step import transformer_train_step


def _tokens(cfg, batch=4, seq=32, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)


@pytest.mark.parametrize("cfgname", ["llama", "gpt2"])
def test_pipeline_matches_single_device(cfgname):
    cfg = llama_tiny(n_layers=4) if cfgname == "llama" else gpt2_tiny(n_layers=4)
    params = tfm.init_params(jax.random.key(0), cfg)
    batch = {"tokens": _tokens(cfg, batch=8)}

    # (jitted: op by op every scan and small op compiles on its own)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: tfm.loss_fn(p, batch, cfg)))(params)

    mesh = make_mesh(MeshSpec(pipe=2, data=2), devices=jax.devices()[:4])
    loss_fn = pipeline_loss_fn(cfg, mesh, rules=RULES_TP, num_microbatches=4)
    pl, pl_grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch)))(params)
    ref_loss, pl = float(ref_loss), float(pl)
    assert abs(pl - ref_loss) < 2e-3, (pl, ref_loss)

    for a, b in zip(jax.tree.leaves(ref_grads), jax.tree.leaves(pl_grads)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-3, rtol=2e-2)


def test_pipeline_train_step_runs(tmp_path):
    cfg = llama_tiny(n_layers=4)
    mesh = make_mesh(MeshSpec(pipe=2, data=2), devices=jax.devices()[:4])
    ts = transformer_train_step(cfg, mesh, rules=RULES_TP,
                                pipeline_microbatches=4)
    params, opt = ts.init(jax.random.key(0))
    b = ts.shard_batch({"tokens": _tokens(cfg, batch=8)})
    losses = []
    for _ in range(4):
        params, opt, loss = ts.step(params, opt, b)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses  # it learns on a fixed batch


@pytest.mark.parametrize("axes", [
    {"pipe": 2, "tensor": 2, "data": 2},
    {"pipe": 2, "fsdp": 2, "data": 2},
    {"pipe": 2, "fsdp": 2, "tensor": 2},
])
def test_pipeline_composes_with_tensor_fsdp(axes):
    """pipe x tensor / pipe x fsdp: the GSPMD pipeline leaves stage-internal
    sharding to the rule table, so layer params stay tensor/fsdp-sharded and
    the loss matches the unpipelined model (round-3 verdict item 5)."""
    cfg = llama_tiny(n_layers=4)
    n = 1
    for v in axes.values():
        n *= v
    mesh = make_mesh(MeshSpec(**axes), devices=jax.devices()[:n])
    params = tfm.init_params(jax.random.key(0), cfg)
    batch = {"tokens": _tokens(cfg, batch=8)}
    ref_loss = float(tfm.loss_fn(params, batch, cfg))
    loss_fn = pipeline_loss_fn(cfg, mesh, rules=RULES_TP, num_microbatches=4)
    pl = float(jax.jit(loss_fn)(params, batch))
    assert abs(pl - ref_loss) < 2e-3, (axes, pl, ref_loss)


def test_moe_under_pipe_matches_and_threads_aux():
    """MoE under pipeline parallelism: the cross-entropy matches the
    unpipelined MoE model, and the aux loss threads through the stage
    schedule (bubbles masked) as the mean of the microbatches' own."""
    import dataclasses

    from ray_tpu.models.configs import moe_tiny

    # capacity_factor high enough that NO tokens drop: capacity-based MoE
    # drops per-chunk, so a microbatched pipeline legitimately drops a
    # different token set than the full-batch forward — parity is only
    # well-defined in the drop-free regime.
    cfg = moe_tiny(n_layers=4, moe_capacity_factor=8.0)
    cfg0 = dataclasses.replace(cfg, moe_aux_coef=0.0)
    params = tfm.init_params(jax.random.key(0), cfg)
    batch = {"tokens": _tokens(cfg, batch=8)}
    mesh = make_mesh(MeshSpec(pipe=2, expert=2, data=2),
                     devices=jax.devices()[:8])
    piped = lambda c: float(jax.jit(pipeline_loss_fn(
        c, mesh, rules=RULES_TP, num_microbatches=4))(params, batch))
    pl, pl0 = piped(cfg), piped(cfg0)
    ref0 = float(jax.jit(lambda p, b: tfm.loss_fn(p, b, cfg0))(params, batch))
    assert abs(pl0 - ref0) < 2e-3, (pl0, ref0)  # the dense parity bound

    # The balancing loss is a product of token means, so a microbatch's is
    # not the whole batch's (it reads 10-25% higher here): what the schedule
    # owes is the mean over the four microbatches, no bubble tick counted.
    aux = jax.jit(lambda p, t: tfm.forward_with_aux(p, t, cfg)[1])
    want = np.mean([float(aux(params, batch["tokens"][m:m + 2]))
                    for m in range(0, 8, 2)])
    assert want > 1.0  # four layers' worth: not lost in the schedule
    assert abs((pl - pl0) - cfg.moe_aux_coef * want) < 2e-4, (pl - pl0, want)
