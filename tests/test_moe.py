"""Mixture-of-Experts layer + expert parallelism (SURVEY §5.7; ops/moe.py
GShard capacity-based dispatch)."""
import jax
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import moe_tiny
from ray_tpu.parallel import MeshSpec, RULES_TP, make_mesh
from ray_tpu.train.step import transformer_train_step


def _tokens(cfg, batch=4, seq=32, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)


def test_moe_forward_and_grads():
    cfg = moe_tiny()
    params = tfm.init_params(jax.random.key(0), cfg)
    batch = {"tokens": _tokens(cfg)}
    loss = float(tfm.loss_fn(params, batch, cfg))
    assert np.isfinite(loss)
    grads = jax.grad(lambda p: tfm.loss_fn(p, batch, cfg))(params)
    # Routed experts receive gradient (capacity>0 ensures some dispatch).
    g = np.asarray(grads["layers"]["moe_w_gate_up"])
    assert np.abs(g).sum() > 0
    # Router learns too.
    assert np.abs(np.asarray(grads["layers"]["router"])).sum() > 0


def test_moe_aux_loss_nonzero():
    cfg = moe_tiny()
    params = tfm.init_params(jax.random.key(0), cfg)
    _, aux = tfm.forward_with_aux(params, _tokens(cfg), cfg)
    # Switch aux is ~1.0 at uniform routing; 0 would mean it's disconnected.
    assert 0.1 < float(aux) / cfg.n_layers < 10.0


def test_moe_trains():
    cfg = moe_tiny()
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    ts = transformer_train_step(cfg, mesh, rules=RULES_TP)
    params, opt = ts.init(jax.random.key(0))
    b = ts.shard_batch({"tokens": _tokens(cfg, batch=8)})
    losses = []
    for _ in range(5):
        params, opt, loss = ts.step(params, opt, b)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_expert_parallel_matches_single_device():
    """expert=2 mesh (all-to-all dispatch emitted by GSPMD) matches the
    single-device numerics."""
    cfg = moe_tiny()
    params = tfm.init_params(jax.random.key(0), cfg)
    batch = {"tokens": _tokens(cfg, batch=8)}
    ref = float(tfm.loss_fn(params, batch, cfg))

    mesh = make_mesh(MeshSpec(expert=2, data=2), devices=jax.devices()[:4])
    from ray_tpu.parallel import sharding as shd

    with shd.sharding_ctx(mesh, RULES_TP):
        ep = float(jax.jit(
            lambda p, b: tfm.loss_fn(p, b, cfg))(params, batch))
    assert abs(ep - ref) < 2e-3, (ep, ref)


@pytest.mark.parametrize("k", [1, 2, 8])
def test_softmax_route_is_top_k_of_the_softmax(k):
    """`softmax_route`: softmax over every expert's score, the top k of the
    probabilities, renormalised: weights sum to 1, ids and weights are
    `jax.lax.top_k`'s of the softmax, and no bias or scale takes part."""
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    x = jax.random.normal(jax.random.key(0), (96, 32))
    w = jax.random.normal(jax.random.key(1), (32, 16)) * 0.3
    idx, wts = moe.softmax_route(x, w, experts_per_token=k)
    assert idx.shape == wts.shape == (96, k) and idx.dtype == jnp.int32
    np.testing.assert_allclose(wts.sum(-1), 1.0, atol=1e-6)
    p = jax.nn.softmax(jnp.dot(x, w, precision="highest"), -1)
    top, want = jax.lax.top_k(p, k)
    np.testing.assert_array_equal(idx, want)
    np.testing.assert_allclose(wts, top / top.sum(-1, keepdims=True),
                               atol=1e-6)


def test_held_layer_takes_the_routing_it_is_given():
    """`moe_ffn_held` with `softmax_route` bound: every expert held, the
    layer is the plain sum over each token's top k; the GShard path's
    capacity plays no part (nothing dropped at any skew)."""
    import functools

    import jax.numpy as jnp

    from ray_tpu.ops import moe

    ks = jax.random.split(jax.random.key(2), 4)
    x = jax.random.normal(ks[0], (2, 24, 16))
    rw = jax.random.normal(ks[1], (16, 8))
    wgu = jax.random.normal(ks[2], (8, 16, 2, 12)) * 0.2
    wd = jax.random.normal(ks[3], (8, 12, 16)) * 0.2
    route = functools.partial(moe.softmax_route, experts_per_token=2)
    with jax.default_matmul_precision("highest"):
        y, cnt = moe.moe_ffn_held(x, rw, wgu, wd, route=route,
                                  dtype=jnp.float32)
        xf = x.reshape(-1, 16)
        idx, wts = route(xf, rw)
        want = jnp.zeros_like(xf)
        for e in range(8):
            we = jnp.sum(jnp.where(idx == e, wts, 0.0), -1)
            h = jax.nn.silu(xf @ wgu[e, :, 0]) * (xf @ wgu[e, :, 1])
            want = want + we[:, None] * (h @ wd[e])
    np.testing.assert_allclose(y.reshape(-1, 16), want, atol=2e-5)
    assert float(cnt["dropped"]) == 0.0
    assert float(cnt["assigned"]) == 48 * 2
