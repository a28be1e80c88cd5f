"""Model + sharded train-step tests (8-device CPU mesh)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import gpt2_tiny, llama_tiny
from ray_tpu.parallel import MeshSpec, RULES_DP, RULES_TP, make_mesh
from ray_tpu.train.step import transformer_train_step


@pytest.mark.parametrize("cfg_fn", [llama_tiny, gpt2_tiny])
def test_forward_shapes(cfg_fn):
    cfg = cfg_fn()
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = np.zeros((2, 16), np.int32)
    logits = tfm.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_param_specs_match_params():
    cfg = llama_tiny()
    params = tfm.init_params(jax.random.key(0), cfg)
    specs = tfm.param_logical_specs(cfg)
    pt = jax.tree.structure(params)
    st = jax.tree.structure(
        specs,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )
    assert pt == st
    # Each spec has one entry per array dim.
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(
        specs,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )
    for p, s in zip(flat_p, flat_s):
        assert p.ndim == len(s), (p.shape, s)


def test_causality():
    """Future tokens must not affect earlier logits."""
    cfg = llama_tiny()
    params = tfm.init_params(jax.random.key(0), cfg)
    rng = np.random.RandomState(0)
    t1 = rng.randint(0, cfg.vocab_size, (1, 16)).astype(np.int32)
    t2 = t1.copy()
    t2[0, -1] = (t2[0, -1] + 7) % cfg.vocab_size  # perturb last token
    l1 = np.asarray(tfm.forward(params, t1, cfg))
    l2 = np.asarray(tfm.forward(params, t2, cfg))
    np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], atol=2e-2)
    assert np.abs(l1[0, -1] - l2[0, -1]).max() > 1e-3


def test_num_params_accounting():
    cfg = llama_tiny()
    params = tfm.init_params(jax.random.key(0), cfg)
    actual = sum(p.size for p in jax.tree.leaves(params))
    assert actual == cfg.num_params()


@pytest.mark.parametrize(
    "spec,rules",
    [
        (MeshSpec(data=8), RULES_DP),
        (MeshSpec(fsdp=4, tensor=2), RULES_TP),
        (MeshSpec(data=2, fsdp=2, tensor=2), RULES_TP),
    ],
    ids=["dp8", "fsdp4xtp2", "dp2xfsdp2xtp2"],
)
def test_sharded_training_decreases_loss(spec, rules):
    mesh = make_mesh(spec)
    cfg = llama_tiny()
    ts = transformer_train_step(cfg, mesh, rules=rules)
    params, opt_state = ts.init(jax.random.key(0))
    tokens = np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 64)).astype(np.int32)
    batch = ts.shard_batch({"tokens": tokens})
    losses = []
    for _ in range(3):
        params, opt_state, loss = ts.step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert all(np.isfinite(losses))


def test_sharded_matches_single_device():
    """Same seed, same batch: DP-8 loss == single-device loss."""
    cfg = llama_tiny()
    tokens = np.random.RandomState(1).randint(0, cfg.vocab_size, (8, 32)).astype(np.int32)

    mesh8 = make_mesh(MeshSpec(data=8))
    ts8 = transformer_train_step(cfg, mesh8, rules=RULES_DP)
    p8, o8 = ts8.init(jax.random.key(0))
    l8 = float(ts8.eval_loss(p8, ts8.shard_batch({"tokens": tokens})))

    mesh1 = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    ts1 = transformer_train_step(cfg, mesh1, rules=RULES_DP)
    p1, o1 = ts1.init(jax.random.key(0))
    l1 = float(ts1.eval_loss(p1, ts1.shard_batch({"tokens": tokens})))

    assert abs(l8 - l1) < 1e-2, (l8, l1)


def _flash_kernel_calls(jaxpr, times=1, out=None):
    """Flash pallas_calls of a jaxpr by operand signature (the forward
    kernel is 3 in / 2 out, dq 6 / 1, dkv 6 / 2), a call inside a scan
    counted once per iteration."""
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            sig = f"{len(eqn.invars)}in_{len(eqn.outvars)}out"
            out[sig] = out.get(sig, 0) + times
        inner = times * (eqn.params["length"]
                         if eqn.primitive.name == "scan" else 1)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _flash_kernel_calls(sub, inner, out)
    return out


def _tiny_batch(cfg, shape=(2, 32)):
    tokens = np.random.RandomState(2).randint(
        0, cfg.vocab_size, shape).astype(np.int32)
    return {"tokens": tokens}


@pytest.mark.parametrize("impl,remat_kw,fwd_calls_per_layer", [
    ("xla", dict(remat=True, remat_policy="full"), 0),
    ("xla", dict(remat=True, remat_policy="dots"), 0),
    ("flash", dict(remat=False), 1),
    ("flash", dict(remat=True, remat_policy="dots"), 1),
    ("flash", dict(remat=True, remat_policy="full"), 2),
])
def test_remat_matches_no_remat(monkeypatch, impl, remat_kw,
                                fwd_calls_per_layer):
    """Both remat policies give the gradients of no remat, and only "full"
    runs the flash forward kernel a second time in the backward: "dots"
    keeps the kernel's own residuals (o, lse), which no dot produces."""
    monkeypatch.setenv("RTPU_ATTN_IMPL", impl)
    cfg = llama_tiny()
    params = tfm.init_params(jax.random.key(0), cfg)
    batch = _tiny_batch(cfg)
    g1 = jax.grad(lambda p: tfm.loss_fn(p, batch, cfg))(params)
    # Remat recomputes the layer body in the backward; XLA fuses the remat
    # and no-remat programs differently, so individual bf16 activations can
    # round one ulp apart (observed: 1 element in 65536 at 2^-11). Gradients
    # must agree to bf16 resolution, not bitwise. With the kernel the
    # largest differences seen are 1 and 1.5 ulp of the largest gradients
    # (embed, 0.25: ulp 2^-10) under "dots" and "full", so two ulps there.
    atol = 1e-3 if impl == "xla" else 2e-3
    cfg_r = llama_tiny(**remat_kw)
    grad_r = jax.grad(lambda p: tfm.loss_fn(p, batch, cfg_r))
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(grad_r(params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)
    calls = _flash_kernel_calls(jax.make_jaxpr(grad_r)(params).jaxpr)
    per_layer = {k: v / cfg.n_layers for k, v in calls.items()}
    assert per_layer == ({"3in_2out": fwd_calls_per_layer, "6in_1out": 1,
                          "6in_2out": 1} if fwd_calls_per_layer else {})


def test_remat_dots_keeps_flash_residuals_through_shard_map(monkeypatch):
    """The mesh path (ops/attention.py wraps the kernel in shard_map on a
    multi-device mesh): still one forward call a layer under "dots"."""
    from ray_tpu.parallel.sharding import DEFAULT_RULES, sharding_ctx

    monkeypatch.setenv("RTPU_ATTN_IMPL", "flash")
    mesh = make_mesh(MeshSpec(fsdp=2, tensor=2), devices=jax.devices()[:4])
    cfg = llama_tiny(remat=True, remat_policy="dots")
    params = tfm.init_params(jax.random.key(0), cfg)
    batch = _tiny_batch(cfg, shape=(4, 32))

    def loss(p):
        with sharding_ctx(mesh, DEFAULT_RULES):
            return tfm.loss_fn(p, batch, cfg)

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    assert "shard_map" in str(jaxpr)
    assert _flash_kernel_calls(jaxpr.jaxpr) == {
        "3in_2out": cfg.n_layers, "6in_1out": cfg.n_layers,
        "6in_2out": cfg.n_layers}


def test_remat_dots_saved_residuals(monkeypatch):
    """What one checkpointed layer keeps for the backward under "dots": the
    kernel's o once, as [B,H,S,hd], and lse as lane-dense [B,H,S] float32;
    no second attention output in the model's [B,S,H,hd] layout."""
    from jax._src.ad_checkpoint import saved_residuals

    from ray_tpu.ops.flash_attention import RESIDUAL_NAMES

    monkeypatch.setenv("RTPU_ATTN_IMPL", "flash")
    cfg = llama_tiny(remat=True, remat_policy="dots")
    B, S, H, hd = 2, 32, cfg.n_heads, cfg.head_dim
    params = tfm.init_params(jax.random.key(0), cfg)
    layer = tfm.layer_params(params, cfg, 0)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    body = tfm.layer_scan_body(cfg, cfg.layer_kinds()[0], positions)
    saved = [
        (aval.shape, str(aval.dtype), why) for aval, why in saved_residuals(
            lambda x, l: body(x, l)[0].astype(jnp.float32).sum(),
            jnp.zeros((B, S, cfg.d_model), cfg.dtype), layer)
        if "argument" not in why]
    lse_name = RESIDUAL_NAMES[1]
    assert [(sh, dt) for sh, dt, why in saved if f"'{lse_name}'" in why] == [
        ((B, H, S), "float32")]
    # jax puts a reduce_precision behind a residual that the forward pass
    # also uses, which hides o's name here: o is found by where it was made.
    from_kernel = [(sh, dt) for sh, dt, why in saved
                   if "flash_attention" in why and f"'{lse_name}'" not in why]
    assert from_kernel == [((B, H, S, hd), "bfloat16")]
    assert not [why for sh, dt, why in saved
                if sh == (B, S, H, hd) and "_qkv_proj" not in why]


@pytest.mark.parametrize("policy", ["dots_attn", "min", "half_dots",
                                    "half_full"])
def test_remat_policy_that_is_gone_raises_at_construction(policy):
    """`remat_policy` is "dots" or "full"; a name that once meant something
    is refused where the configuration is made, not at trace time."""
    with pytest.raises(ValueError, match=f"unknown remat_policy '{policy}'"):
        llama_tiny(remat=True, remat_policy=policy)
