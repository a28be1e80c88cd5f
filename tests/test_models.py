"""Model + sharded train-step tests (8-device CPU mesh)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import transformer as tfm
from ray_tpu.models import configs
from ray_tpu.models.configs import gpt2_tiny, kanana2_tiny, llama_tiny
from ray_tpu.parallel import MeshSpec, RULES_DP, RULES_TP, make_mesh
from ray_tpu.train.step import transformer_train_step
from ray_tpu.util import tracing


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
    kernel is 3 in / 2 out, the one backward kernel 6 / 3; past its VMEM
    budget dq 6 / 1 and dkv 6 / 2), a call inside a scan counted once per
    iteration."""
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


def _layer_saves(cfg, index, B=2, S=32):
    """[(shape, dtype, why)] of what layer `index`'s checkpointed body keeps
    for the backward besides its arguments and closed-over constants (the
    positions, a rotation's frequencies)."""
    from jax._src.ad_checkpoint import saved_residuals

    params = tfm.init_params(jax.random.key(0), cfg)
    layer = tfm.layer_params(params, cfg, index)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    body = tfm.layer_scan_body(cfg, cfg.layer_kinds()[index], positions)
    return [(aval.shape, str(aval.dtype), why) for aval, why in
            saved_residuals(
                lambda x, l: body(x, l)[0].astype(jnp.float32).sum(),
                jnp.zeros((B, S, cfg.d_model), cfg.dtype), layer)
            if "argument" not in why and "constant" not in why]


def _kanana2_two_layers(**kw):
    """The dense lead layer and one expert layer: as many latent layers as
    "a layer" needs."""
    return kanana2_tiny(n_layers=2, **kw)


@pytest.mark.parametrize("impl,preset,remat_kw,fwd_calls_per_layer", [
    ("xla", llama_tiny, dict(remat=True, remat_policy="full"), 0),
    ("xla", llama_tiny, dict(remat=True, remat_policy="dots"), 0),
    ("flash", llama_tiny, dict(remat=False), 1),
    ("flash", llama_tiny, dict(remat=True, remat_policy="dots"), 1),
    ("flash", llama_tiny, dict(remat=True, remat_policy="full"), 1),
    ("flash", _kanana2_two_layers, dict(remat=True, remat_policy="full"), 1),
])
def test_remat_matches_no_remat(request, impl, preset, remat_kw,
                                fwd_calls_per_layer):
    """Both remat policies give the gradients of no remat, and neither runs
    the flash forward kernel a second time in the backward: both keep the
    kernel's own residuals (o, lse), which no dot produces. The latent
    layers' keys (24 wide) and values (16) go through the same rule."""
    if impl == "flash":  # ("xla" is the rule's own answer on the CPU)
        request.getfixturevalue("flash_kernels")
    cfg = preset()
    params = tfm.init_params(jax.random.key(0), cfg)
    batch = _tiny_batch(cfg)
    g1 = jax.jit(jax.grad(lambda p: tfm.loss_fn(p, batch, cfg)))(params)
    # Remat recomputes the layer body in the backward; XLA fuses the remat
    # and no-remat programs differently, so individual bf16 activations can
    # round one ulp apart (observed: 1 element in 65536 at 2^-11). Gradients
    # must agree to bf16 resolution, not bitwise. With the kernel the
    # largest differences seen are 1 and 1.5 ulp of the largest gradients
    # (embed, 0.25: ulp 2^-10) under "dots" and "full", so two ulps there.
    atol = 1e-3 if impl == "xla" else 2e-3
    cfg_r = preset(**remat_kw)
    grad_r = jax.grad(lambda p: tfm.loss_fn(p, batch, cfg_r))
    for a, b in zip(jax.tree.leaves(g1),
                    jax.tree.leaves(jax.jit(grad_r)(params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)
    calls = _flash_kernel_calls(jax.make_jaxpr(grad_r)(params).jaxpr)
    per_layer = {k: v / cfg.n_layers for k, v in calls.items()}
    assert per_layer == ({"3in_2out": fwd_calls_per_layer, "6in_3out": 1}
                         if fwd_calls_per_layer else {})


def test_remat_full_is_the_work_of_saving_nothing(monkeypatch,
                                                  flash_kernels):
    """The saved o and lse are the arrays the second forward call would have
    written: the loss and every gradient element under "full" are bit-equal
    to a body checkpointed with nothing saved (here, where XLA compiles the
    two programs alike; on the chip they differ by roundings, PERF.md
    section 6, PR 40)."""
    cfg = llama_tiny(remat=True, remat_policy="full")
    params = tfm.init_params(jax.random.key(0), cfg)
    batch = _tiny_batch(cfg)
    run = lambda: jax.jit(jax.value_and_grad(
        lambda p: tfm.loss_fn(p, batch, cfg)))(params)
    got = run()
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: jax.checkpoint_policies.nothing_saveable)
    want = run()
    calls = _flash_kernel_calls(jax.make_jaxpr(jax.grad(
        lambda p: tfm.loss_fn(p, batch, cfg)))(params).jaxpr)
    assert calls["3in_2out"] == 2 * cfg.n_layers  # the reference ran it twice
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("policy", ["dots", "full"])
def test_remat_keeps_flash_residuals_through_shard_map(flash_kernels,
                                                       policy):
    """The mesh path (ops/attention.py wraps the kernel in shard_map on a
    multi-device mesh): still one forward call a layer under either
    policy."""
    from ray_tpu.parallel.sharding import DEFAULT_RULES, sharding_ctx

    mesh = make_mesh(MeshSpec(fsdp=2, tensor=2), devices=jax.devices()[:4])
    cfg = llama_tiny(remat=True, remat_policy=policy)
    params = tfm.init_params(jax.random.key(0), cfg)
    batch = _tiny_batch(cfg, shape=(4, 32))

    def loss(p):
        with sharding_ctx(mesh, DEFAULT_RULES):
            return tfm.loss_fn(p, batch, cfg)

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    assert "shard_map" in str(jaxpr)
    assert _flash_kernel_calls(jaxpr.jaxpr) == {
        "3in_2out": cfg.n_layers, "6in_3out": cfg.n_layers}


# The module whose forward rule names a mixer kind's kernel residuals.
_MIXER_KERNEL = {"attn": "flash_attention", "swa": "flash_attention",
                 "mla": "flash_attention", "kda": "kda", "gdn": "kda",
                 "mamba2": "ssd"}


@pytest.mark.parametrize("preset", [
    "llama_tiny", "moe_tiny", "kimi_linear_tiny", "kanana2_tiny",
    "granite_hybrid_tiny", "mellum2_tiny", "qwen3_next_tiny"])
def test_remat_full_keeps_what_a_layers_kernels_name(
        monkeypatch, flash_kernels, preset):
    """What "full" keeps a layer kind: its input and what a hand-written
    kernel's forward rule names, so the backward re-runs no such kernel. An
    `attn` / `swa` / `mla` layer keeps o [B,H,S,hd] and lse [B,H,S]; a `kda`
    / `gdn` layer the three `kda_*` residuals, a `mamba2` layer the two
    `ssd_*`, a layer with held experts two `moe_*` and its routing beside its
    mixer's;
    no layer keeps a dot's output (what still makes "full" the small
    policy), and "dots" keeps strictly more."""
    from ray_tpu.ops import flash_attention, kda, moe, ssd

    ops = dict(flash_attention=flash_attention, kda=kda, ssd=ssd, moe=moe)
    for core in (kda, ssd):  # the cores' kernels, where the names are
        monkeypatch.setattr(core, "use_kernels", lambda *a, **kw: True)
    # jax puts a reduce_precision behind a residual that the forward pass
    # also uses, which hides the first name (o, y): a save is placed by
    # where it was made, as in test_remat_dots_saved_residuals.
    made_in = lambda why: {n for n in ops if f"/ops/{n}.py:" in why}
    cfg = getattr(configs, preset)(remat=True, remat_policy="full")
    dots = getattr(configs, preset)(remat=True, remat_policy="dots")
    kinds = cfg.layer_kinds()
    B, S = 2, 32
    for index in sorted({kinds.index(k) for k in kinds}):
        mixer, ffn = kinds[index]
        want = {_MIXER_KERNEL[mixer]}
        if ffn == "moe" and cfg.moe_holds_range:
            want.add("moe")
        saved = _layer_saves(cfg, index, B, S)
        assert all(made_in(why) for _, _, why in saved), (kinds[index], saved)
        assert not [why for _, _, why in saved if "dot_general" in why]
        for n in ops:
            rows = [r for r in saved if made_in(r[2]) == {n}]
            # (the experts' third names the window's token order, made
            # where the grouped products are kernels, not here, and since
            # PR 59 the routing the windows go by: the sorted list, the
            # runs' ends, the weights, kept so that the backward works the
            # rows the forward filled)
            names = ops[n].RESIDUAL_NAMES[:2 if n == "moe" else None]
            routing = 3 if n == "moe" and n in want else 0
            assert len(rows) == (len(names) + routing if n in want else 0), (
                kinds[index], n, rows)
            # (the weights' name is hidden as o's is: the forward uses them)
            assert len([r for r in rows if f"'{moe.RESIDUAL_NAMES[2]}'"
                        in r[2] and r[1] == "int32"]) == (2 if routing else 0)
            if n in want:  # the first (o, y) may be the hidden one
                assert all(any(f"'{name}'" in r[2] for r in rows)
                           for name in names[1:]), rows
        if "flash_attention" in want:
            lse, o = sorted((r for r in saved if "flash_attention" in r[2]),
                            key=lambda r: len(r[0]))
            H = cfg.attn_heads(mixer)
            assert f"'{flash_attention.RESIDUAL_NAMES[1]}'" in lse[2]
            assert lse[:2] == ((B, H, S), "float32")
            assert o[0][:3] == (B, H, S) and o[1] == "bfloat16"
        assert len(_layer_saves(dots, index, B, S)) > len(saved)


def test_remat_full_saves_no_ring_product(flash_kernels):
    """Under a `tensor` axis the decomposed products name their outputs
    (tp.RESIDUAL_NAMES) for "dots"; "full" keeps none of them. (A save that
    the forward pass also uses loses its name in the listing: the ring's are
    found by where they were made.)"""
    from ray_tpu.parallel.sharding import sharding_ctx

    mesh = make_mesh(MeshSpec(fsdp=2, tensor=2), devices=jax.devices()[:4])
    saves = {}
    for policy in ("dots", "full"):
        with sharding_ctx(mesh, RULES_TP):
            saves[policy] = _layer_saves(
                llama_tiny(remat=True, remat_policy=policy), 0, B=4)
    ring = lambda rows: [w for _, _, w in rows if "tensor_overlap.py" in w]
    assert ring(saves["dots"]) and not ring(saves["full"])
    assert len(saves["full"]) == 2, saves["full"]


def test_remat_counter_is_one_a_checkpointed_body(monkeypatch):
    """`train.remat`: one observation a checkpointed layer body built, with
    the policy and the names it keeps ("full": every kernel's; "dots": those
    and a ring's products, beside every dot); none without remat."""
    seen = []
    real = tracing.observe
    monkeypatch.setattr(tracing, "observe", lambda name, ns, **kw: (
        seen.append((name, kw)), real(name, ns, **kw))[1])
    positions = jnp.zeros((2, 32), jnp.int32)
    count = lambda: tracing.phase_table().get("train.remat", {}).get(
        "count", 0)
    before = count()
    for kw in (dict(remat=False), dict(remat=True, remat_policy="full"),
               dict(remat=True, remat_policy="dots")):
        cfg = llama_tiny(**kw)
        tfm.layer_scan_body(cfg, cfg.layer_kinds()[0], positions)
    rows = [kw for name, kw in seen if name == "train.remat"]
    assert count() == before + 2
    kernels = ("flash_o,flash_lse,kda_o,kda_states,kda_tinv,ssd_y,ssd_states,"
               "moe_gate_up,moe_down,moe_token_order,dsa_bits,dsa_lse_i,dsa_o,"
               "dsa_lse,dsa_kl,dsa_dqi,dsa_dki,dsa_dw,sscan_y,sscan_states")
    assert rows[0] == dict(slow=False, policy="full", kept=kernels)
    assert rows[1] == dict(slow=False, policy="dots",
                           kept=kernels + ",tp.gathered,tp.scattered")


def test_remat_dots_saved_residuals(flash_kernels):
    """What one checkpointed layer keeps for the backward under "dots": the
    kernel's o once, as [B,H,S,hd], and lse as lane-dense [B,H,S] float32;
    no second attention output in the model's [B,S,H,hd] layout."""
    from ray_tpu.ops.flash_attention import RESIDUAL_NAMES

    cfg = llama_tiny(remat=True, remat_policy="dots")
    B, S, H, hd = 2, 32, cfg.n_heads, cfg.head_dim
    saved = _layer_saves(cfg, 0, B, S)
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
