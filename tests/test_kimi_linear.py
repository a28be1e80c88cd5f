"""The mixed stack (KDA and MLA mixers, a dense lead layer, sigmoid-routed
experts of which a program holds a share) on the CPU at small sizes: the
chunked KDA against the recurrence, the program against the plain reference
(chipbench/reference/kimi_linear.py) for each kind of layer and for the
27-layer pattern, the shares of an expert layer against the uncut layer,
the counters, the counts and the configuration file."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import kimi_linear_tiny
from ray_tpu.ops import kda, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _qkvgb(B, S, H, dk, dv, seed=0, decay=1.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = kda.l2_normalize(jax.random.normal(ks[0], (B, S, H, dk)))
    k = kda.l2_normalize(jax.random.normal(ks[1], (B, S, H, dk)))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (B, S, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("S,chunk,sub", [(64, 16, 16), (100, 32, 16),
                                         (96, 64, 32), (256, 128, 32),
                                         (130, 128, 32)])
def test_kda_chunked_matches_recurrence(S, chunk, sub):
    """Outputs, final state and every input's gradient, across chunk sizes
    and lengths that are not a multiple of the chunk."""
    args = _qkvgb(2, S, 2, 16, 24, seed=S)
    chunked = jax.jit(lambda *a: kda.kda_chunked(*a, chunk=chunk, sub=sub))
    o1, s1 = jax.jit(kda.kda_recurrent)(*args)
    o2, s2 = chunked(*args)
    np.testing.assert_allclose(o2, o1, atol=2e-5 * float(jnp.abs(o1).max()))
    np.testing.assert_allclose(s2, s1, atol=2e-5 * float(jnp.abs(s1).max()))
    loss = lambda f: lambda *a: jnp.sum(f(*a)[0] ** 2)
    g1 = jax.jit(jax.grad(loss(kda.kda_recurrent),
                          argnums=(0, 1, 2, 3, 4)))(*args)
    g2 = jax.jit(jax.grad(loss(chunked), argnums=(0, 1, 2, 3, 4)))(*args)
    for a, b in zip(g2, g1):
        np.testing.assert_allclose(a, b, atol=5e-5 * float(jnp.abs(b).max()))


def test_kda_chunked_carries_state_and_strong_decay():
    """A sequence in two calls equals one call; a decay of e^-60 inside one
    chunk (past float32's range for e^G * e^-G from the chunk's start)
    stays exact thanks to the sub-block references."""
    q, k, v, g, beta = _qkvgb(1, 128, 2, 16, 16, seed=3, decay=0.5)
    g = g.at[:, 40:60].set(-3.0)  # 20 tokens of e^-3 each in a 128-chunk
    o, s = kda.kda_recurrent(q, k, v, g, beta)
    a = [x[:, :64] for x in (q, k, v, g, beta)]
    b = [x[:, 64:] for x in (q, k, v, g, beta)]
    o_a, s_a = kda.kda_chunked(*a, chunk=32)
    o_b, s_b = kda.kda_chunked(*b, chunk=32, initial_state=s_a)
    np.testing.assert_allclose(jnp.concatenate([o_a, o_b], 1), o, atol=1e-5)
    np.testing.assert_allclose(s_b, s, atol=1e-5)
    o128, _ = kda.kda_chunked(q, k, v, g, beta, chunk=128, sub=32)
    np.testing.assert_allclose(o128, o, atol=1e-5)


@pytest.mark.parametrize("S,chunk,sub,dk,dv,init,strong", [
    (256, 128, 32, 128, 128, False, False),   # the chip's tile sizes
    (256, 128, 32, 128, 128, True, True),     # e^-60 inside a chunk
    (300, 128, 32, 16, 24, True, False),      # S not a multiple of the chunk
    (96, 32, 16, 16, 16, False, True),
    (64, 16, 16, 16, 24, True, False),        # one sub-block, no merge
])
def test_kda_kernels_match_recurrence_and_xla(S, chunk, sub, dk, dv, init,
                                              strong):
    """The Pallas kernels (interpret mode here) against the recurrence and
    the XLA body: outputs, final state, all five gradients and the initial
    state's, with a cotangent on the final state too."""
    q, k, v, g, beta = _qkvgb(1, S, 2, dk, dv, seed=S + dk,
                              decay=0.5 if strong else 1.0)
    if strong:  # 20 tokens of e^-3 each inside one chunk
        g = g.at[:, 40:60].set(-3.0)
    s0 = (jax.random.normal(jax.random.key(9), (1, 2, dk, dv)) if init
          else jnp.zeros((1, 2, dk, dv)))

    def run(f, **kw):
        def loss(q, k, v, g, beta, s0):
            o, s = f(q, k, v, g, beta, initial_state=s0, **kw)
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(s)), (o, s)
        (_, (o, s)), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4, 5), has_aux=True))(
                q, k, v, g, beta, s0)
        return (o, s) + grads

    want = run(kda.kda_recurrent)
    xla = run(kda.kda_chunked_xla, chunk=chunk, sub=sub)
    got = run(kda.kda_chunked_pallas, chunk=chunk, sub=sub)
    for i, (a, b, c) in enumerate(zip(got, want, xla)):
        atol = (2e-5 if i < 2 else 5e-5) * float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, atol=atol, err_msg=f"output {i}")
        np.testing.assert_allclose(a, c, atol=atol, err_msg=f"output {i}")


def test_kda_dispatch_rule():
    """`use_kernels` is a pure function of platform and shapes; on the CPU
    `kda_chunked` is the XLA body, and says so in the phase table."""
    from ray_tpu.util import tracing

    assert kda.use_kernels("tpu", 128, 128, 128, on_mesh=False)
    assert kda.use_kernels("tpu", 256, 128, 256, on_mesh=False)
    assert not kda.use_kernels("cpu", 128, 128, 128, on_mesh=False)
    assert not kda.use_kernels("tpu", 16, 128, 128, on_mesh=False)
    assert not kda.use_kernels("tpu", 128, 16, 128, on_mesh=False)
    assert not kda.use_kernels("tpu", 128, 128, 32, on_mesh=False)
    assert not kda.use_kernels("tpu", 128, 128, 128, on_mesh=True)
    count = lambda n: tracing.phase_table().get(n, {"count": 0})["count"]
    before = count("kda.core.xla"), count("kda.core.pallas")
    args = _qkvgb(1, 128, 1, 128, 128)
    np.testing.assert_array_equal(
        kda.kda_chunked(*args)[0], kda.kda_chunked_xla(*args)[0])
    assert (count("kda.core.xla"), count("kda.core.pallas")) == (
        before[0] + 1, before[1])


def _kernel_calls(jaxpr, times=1, out=None):
    """pallas_calls of a jaxpr by operand signature, a call inside a scan
    counted once per iteration (tests/test_models.py does it for flash)."""
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            sig = f"{len(eqn.invars)}in_{len(eqn.outvars)}out"
            out[sig] = out.get(sig, 0) + times
        inner = times * (eqn.params["length"]
                         if eqn.primitive.name == "scan" else 1)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, inner, out)
    return out


@pytest.mark.parametrize("policy,fwd_calls_per_layer",
                         [("dots", 1), ("full", 2)])
def test_remat_dots_keeps_the_kda_kernel_residuals(monkeypatch, policy,
                                                   fwd_calls_per_layer):
    """The traced gradient of a KDA stack through the kernels: under "dots"
    the forward kernel (6 in / 4 out) runs once a layer, its o, states and
    inverses being named residuals; under "full" twice. The backward kernel
    (9 in / 6 out) once. Neither has a flash kernel's signature
    (chipbench/reduce/xplane.py names kernels by it). Gradients are those
    of the XLA body."""
    cfg = kimi_linear_tiny(n_layers=3, moe_held=(0, 16), remat=True,
                           remat_policy=policy, dtype=jnp.float32)
    params = tfm.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 33), 0, cfg.vocab_size)
    # A new function each time: jax caches a trace by the function's identity.
    grad = lambda: jax.grad(lambda p: tfm.loss_fn(
        p, {"tokens": toks}, cfg, shift_inputs=True))
    assert _kernel_calls(jax.make_jaxpr(grad())(params).jaxpr) == {}
    g_xla = jax.jit(grad())(params)
    monkeypatch.setattr(kda, "use_kernels", lambda *a, **kw: True)
    calls = _kernel_calls(jax.make_jaxpr(grad())(params).jaxpr)
    assert calls == {"6in_4out": 3 * fwd_calls_per_layer, "9in_6out": 3}
    if policy == "dots":
        for a, b in zip(jax.tree.leaves(jax.jit(grad())(params)),
                        jax.tree.leaves(g_xla)):
            np.testing.assert_allclose(a, b, atol=1e-5 + 1e-4 * float(
                jnp.abs(b).max()))


def _reference_parts(cfg, seed=5):
    from chipbench import weights_kimi_linear as WK

    tc = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    sz = WK.HybridSizes(tc, cfg.norm_eps)
    key = jax.random.key(seed)
    params = jax.jit(lambda k: WK.program_params(k, sz, cfg))(key)
    return sz, key, params


@pytest.mark.parametrize("name,over", [
    ("kda_dense", dict(n_layers=1)),
    ("kda_moe", dict(n_layers=1, moe_first_dense=0)),
    ("mla_moe", dict(n_layers=1, moe_first_dense=0, kda_layers=(),
                     mla_layers=(1,))),
    ("stack5_remat", dict(remat=True, remat_policy="dots")),
    ("pattern27", dict(n_layers=27)),
])
def test_program_matches_reference(name, over):
    """Logits, loss and the compared gradient leaves, program against the
    plain reference, weights from one seed: each layer kind alone and the
    published 27-layer pattern (irregular tail included) at tiny widths."""
    from chipbench.drivers import train_hybrid as drv
    from chipbench.reference import kimi_linear as ref

    cfg = kimi_linear_tiny(dtype=jnp.float32, moe_held=(4, 4), **over)
    sz, key, params = _reference_parts(cfg)
    if cfg.n_layers == 27:
        kinds = [p for p, _ in cfg.stack_plan()]
        assert [len(p) for p in kinds] == [1, 4, 1, 1], cfg.stack_plan()
        assert cfg.stack_plan()[1][1] == 6
    deep = cfg.n_layers == 27  # the reference unrolls: keep its compile short
    toks = jax.random.randint(jax.random.key(1), (1, 17) if deep else (2, 41),
                              0, cfg.vocab_size)
    if not deep:
        np.testing.assert_allclose(
            jax.jit(lambda p: tfm.forward(p, toks[:, :-1], cfg))(params),
            ref.forward(key, toks[:, :-1], sz), atol=2e-4)
    loss_p, g = jax.jit(jax.value_and_grad(lambda p: tfm.loss_fn(
        p, {"tokens": toks}, cfg, shift_inputs=True)))(params)
    # Eagerly when deep: 27 unrolled layers compile as one program for
    # minutes, op by op the layers share their compiled pieces.
    ref_grads = lambda k, t: ref.loss_and_grads(k, t, sz)
    loss_r, g_r = (ref_grads if deep else jax.jit(ref_grads))(key, toks)
    assert abs(float(loss_p) - float(loss_r)) < 1e-5 * float(loss_r)
    # Leaves of layer kinds this stack lacks are absent from the program.
    has = {m for m, _ in sz.kinds} | {f for _, f in sz.kinds}
    want = {"final_norm": True, "kda_wo": "kda" in has,
            "mla_wkvb": "mla" in has, "expert_down": "moe" in has,
            "router": "moe" in has}
    lay = lambda l: tfm.layer_params(g, cfg, l)
    got = {"final_norm": g["final_norm"]}
    if want["kda_wo"]:
        got["kda_wo"] = lay(sz.l_kda)["kda_wo"].reshape(-1, sz.d)
    if want["mla_wkvb"]:
        got["mla_wkvb"] = lay(sz.l_mla)["mla_wkvb"].reshape(sz.lat, -1)
    if want["expert_down"]:
        got["expert_down"] = lay(sz.l_moe)["moe_w_down"][sz.e_pick]
        got["router"] = lay(sz.l_moe)["router"]
    for n, a in got.items():
        err = float(jnp.linalg.norm(a - g_r[n]) / jnp.linalg.norm(g_r[n]))
        assert err < 2e-4, (name, n, err)
    if cfg.n_layers == 27:
        assert set(drv.program_leaves(cfg, sz, g)) == set(g_r)


def _layer_case(seed=0, B=2, S=32, d=32, E=16, F=24, k=4):
    ks = jax.random.split(jax.random.key(seed), 8)
    return dict(
        x=jax.random.normal(ks[0], (B, S, d)),
        rw=jax.random.normal(ks[1], (d, E)) * 0.3,
        b=jax.random.normal(ks[2], (E,)) * 0.1,
        wgu=jax.random.normal(ks[3], (E, d, 2, F)) * 0.2,
        wd=jax.random.normal(ks[4], (E, F, d)) * 0.2,
        sgu=jax.random.normal(ks[5], (d, 2, F)) * 0.2,
        sd=jax.random.normal(ks[6], (F, d)) * 0.2, k=k, E=E)


def _uncut_layer(c, scale=2.446):
    """The whole layer by the published equations, a loop over all experts."""
    x = c["x"].reshape(-1, c["x"].shape[-1])
    s = jax.nn.sigmoid(x @ c["rw"])
    _, idx = jax.lax.top_k(s + c["b"], c["k"])
    w = jnp.take_along_axis(s, idx, -1)
    w = w / w.sum(-1, keepdims=True) * scale
    y = (jax.nn.silu(x @ c["sgu"][:, 0]) * (x @ c["sgu"][:, 1])) @ c["sd"]
    for e in range(c["E"]):
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        h = jax.nn.silu(x @ c["wgu"][e, :, 0]) * (x @ c["wgu"][e, :, 1])
        y = y + we[:, None] * (h @ c["wd"][e])
    return y.reshape(c["x"].shape)


@pytest.mark.parametrize("shares,factor", [(1, 4.0), (4, 4.0), (16, 4.0),
                                           (4, 0.5)])
def test_expert_shares_add_up_to_the_uncut_layer(shares, factor, monkeypatch):
    """The parts all the shares give, the shared expert counted once, add
    up to the uncut layer's output; no assignment is dropped or counted
    twice (the shares' `assigned` add up to tokens x k). At factor 0.5 a
    share's window is half its even load: two or three trips of the loop,
    with experts' runs that straddle the windows."""
    monkeypatch.setattr(moe, "HELD_WINDOW_FACTOR", factor)
    c = _layer_case(S=128)
    per = c["E"] // shares
    total, assigned, past = 0.0, 0.0, 0.0
    for r in range(shares):
        y, cnt = moe.moe_ffn_held(
            c["x"], c["rw"], c["b"], c["wgu"][r * per:(r + 1) * per],
            c["wd"][r * per:(r + 1) * per], held_first=r * per,
            experts_per_token=c["k"], routed_scale=2.446, dtype=jnp.float32)
        assert float(cnt["dropped"]) == 0.0
        total, assigned = total + y, assigned + float(cnt["assigned"])
        past += float(cnt["past_buffer"])
    shared = tfm._swiglu(c["x"], c["sgu"], c["sd"])
    np.testing.assert_allclose(total + shared, _uncut_layer(c), atol=2e-5)
    assert assigned == c["x"].shape[0] * c["x"].shape[1] * c["k"]
    assert (past > 0) == (factor < 1)


def test_no_assignment_dropped_under_a_skewed_router(monkeypatch):
    """A router that sends every token to the same four experts. With every
    expert held one window holds all tokens x k assignments. A share that
    holds those four with a window an eighth of their load works them in
    eight trips of the loop: nothing is dropped, `past_buffer` counts what
    went beyond the first window, and output and gradients are those of a
    window that holds everything."""
    c = _layer_case(seed=1, S=128)
    c["b"] = c["b"].at[:4].add(10.0)  # experts 0-3 win every selection
    kw = dict(experts_per_token=c["k"], routed_scale=2.446,
              dtype=jnp.float32)
    T = c["x"].shape[0] * c["x"].shape[1]
    shared = tfm._swiglu(c["x"], c["sgu"], c["sd"])
    y, cnt = moe.moe_ffn_held(c["x"], c["rw"], c["b"], c["wgu"], c["wd"],
                              **kw)
    assert float(cnt["dropped"]) == 0.0 == float(cnt["past_buffer"])
    assert float(cnt["load_max"]) == T and float(cnt["assigned"]) == T * 4
    np.testing.assert_allclose(y + shared, _uncut_layer(c), atol=2e-5)

    def share(x, wgu, factor):
        monkeypatch.setattr(moe, "HELD_WINDOW_FACTOR", factor)
        return moe.moe_ffn_held(x, c["rw"], c["b"], wgu, c["wd"][:4], **kw)

    monkeypatch.setattr(moe, "HELD_WINDOW_FACTOR", 0.5)
    rows = moe.held_window_rows(T, 4, 16, 4)
    assert rows == 128 and T * 4 == 1024  # eight trips
    y, cnt = share(c["x"], c["wgu"][:4], 0.5)
    assert float(cnt["assigned"]) == T * 4
    assert float(cnt["past_buffer"]) == T * 4 - rows
    assert float(cnt["dropped"]) == 0.0
    np.testing.assert_allclose(y + shared, _uncut_layer(c), atol=2e-5)
    loss = lambda f: lambda x, w: jnp.sum(jnp.sin(share(x, w, f)[0]))
    for got, want in zip(
            jax.grad(loss(0.5), (0, 1))(c["x"], c["wgu"][:4]),
            jax.grad(loss(8.0), (0, 1))(c["x"], c["wgu"][:4])):
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_dropped_counts_what_the_loop_did_not_work(monkeypatch):
    """`dropped` is read from the loop (each trip's own count of valid rows),
    not reckoned from the sizes: a loop that stops a trip short says so."""
    c = _layer_case(seed=1, S=128)
    c["b"] = c["b"].at[:4].add(10.0)
    monkeypatch.setattr(moe, "HELD_WINDOW_FACTOR", 0.5)
    monkeypatch.setattr(moe, "_trips", lambda held, rows, windows: 7)
    _, cnt = moe.moe_ffn_held(c["x"], c["rw"], c["b"], c["wgu"][:4],
                              c["wd"][:4], experts_per_token=c["k"],
                              dtype=jnp.float32)
    assert float(cnt["assigned"]) == 1024 and float(cnt["dropped"]) == 128


def test_counts_match_the_cut():
    """num_params / num_active_params / flops_per_token of the published
    widths at the chip's share, against ISSUE 27's table: KDA 39.5 M, MLA
    29.1 M, dense 63.7 M, expert layer 64.3 M, embedding + head 94.4 M,
    602.4 M in all; 336 M matmul parameters a token touches."""
    with open(os.path.join(
            ROOT, "chipbench", "configs", "kimi_linear_48b_a3b.json")) as f:
        conf = json.load(f)
    tc = dict(conf["transformer_config"])
    tc["dtype"], tc["param_dtype"] = jnp.bfloat16, jnp.float32
    cfg = tfm.TransformerConfig(**tc)
    M = 1e6
    assert round(cfg._mixer_params("kda") / M, 1) == 39.5
    assert round(cfg._mixer_params("mla") / M, 1) == 29.1
    assert round(cfg._ffn_params("dense") / M, 1) == 63.7
    assert round(cfg._ffn_params("moe") / M, 1) == 64.3
    assert round(2 * cfg.vocab_size * cfg.d_model / M, 1) == 94.4
    n = cfg.num_params()
    print(f"kimi_linear_48b_a3b at the chip's share: {n:,} parameters, "
          f"{16 * n / 1e9:.2f} GB at 16 B a parameter")
    assert round(n / M, 1) == 602.4
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == n
    touched = cfg.num_active_params() - cfg.vocab_size * cfg.d_model
    assert 335 < touched / M < 337, touched
    assert cfg.layer_kinds() == (("kda", "dense"), ("kda", "moe"),
                                 ("kda", "moe"), ("mla", "moe"),
                                 ("kda", "moe"))
    # 6 a touched parameter, plus attention and the KDA core.
    f = cfg.flops_per_token(8192)
    assert 6 * touched < f < 6 * touched + 0.5e9
    # The published widths are in the file unchanged.
    for key, val in {"hidden_size": 2304, "intermediate_size": 9216,
                     "moe_intermediate_size": 1024, "kv_lora_rank": 512,
                     "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                     "v_head_dim": 128, "num_attention_heads": 32,
                     "num_experts_per_token": 8, "num_shared_experts": 1,
                     "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
                     "first_k_dense_replace": 1, "head_dim": 72}.items():
        assert conf[key] == val, key
    assert conf["linear_attn_config"]["head_dim"] == 128
    assert conf["linear_attn_config"]["short_conv_kernel_size"] == 4
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (5, 8, 20480)
    assert (tc["d_model"], tc["d_ff"], tc["moe_d_ff"], tc["moe_num_experts"],
            tc["moe_held"][1]) == (2304, 9216, 1024, 256, 8)
    assert "32 chips" in conf["deployment"] and conf["assumed"]


def test_classic_stacks_are_what_they_were():
    """A configuration with no kda / mla layer and the GShard router keeps
    its one stacked tree, its counts and `stack_plan` of one segment."""
    from ray_tpu.models.configs import gpt2_125m, llama_tiny

    for cfg in (llama_tiny(), llama_tiny(moe_num_experts=4), gpt2_125m()):
        assert not cfg.mixed
        assert cfg.stack_plan() == (((cfg.layer_kinds()[0],), cfg.n_layers),)
    assert gpt2_125m().num_params() == 124_439_808 - 82_944  # no lin. biases
    p = tfm.init_params(jax.random.key(0), llama_tiny())
    assert isinstance(p["layers"], dict)
    assert tfm.layer_params(p, llama_tiny(), 1)["wo"].shape == (128, 128)
    with pytest.raises(ValueError):
        kimi_linear_tiny(moe_router="softmax_capacity")


def test_train_step_returns_counters_and_folds_them():
    """transformer_train_step(with_counters=True): the step returns the
    routing counters beside the loss, the loss falls, and observe_counters
    puts them into the phase table."""
    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.train.step import transformer_train_step
    from ray_tpu.util import tracing

    cfg = kimi_linear_tiny(n_layers=2, moe_held=(8, 8), remat=True,
                           remat_policy="full")
    mesh = make_mesh(MeshSpec(data=2), devices=jax.devices()[:2])
    ts = transformer_train_step(cfg, mesh, shift_inputs=True,
                                with_counters=True)
    params, opt = ts.init(jax.random.key(0))
    toks = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 33)).astype(np.int32)
    before = tracing.phase_table().get("train.moe_assigned", {"count": 0})
    losses = []
    for _ in range(3):
        params, opt, loss, aux = ts.step(params, opt,
                                         ts.shard_batch({"tokens": toks}))
        losses.append(float(loss))
        seen = ts.observe_counters(aux)
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert seen["moe_dropped"] == 0.0
    # One expert layer x 128 tokens x 4 a token, half the experts held.
    assert 0.3 * 512 < seen["moe_assigned"] < 0.7 * 512
    assert seen["moe_load_max"] >= seen["moe_load_mean"] > 0
    row = tracing.phase_table()["train.moe_assigned"]
    assert row["count"] == before["count"] + 3
    # compile_step: one executable for the loop and for memory_analysis().
    batch = ts.shard_batch({"tokens": toks})
    exe = ts.compile_step(params, opt, batch)
    assert exe.memory_analysis().temp_size_in_bytes > 0
    params, opt, loss, aux = ts.step(params, opt, batch)
    assert float(loss) < losses[-1] and ts._compiled_step is exe


@pytest.mark.parametrize("D,Dv,kvh", [(24, 16, 4), (16, 16, 2), (8, 16, 4)])
def test_flash_value_width_of_its_own(D, Dv, kvh):
    """The flash kernels with values narrower or wider than keys (MLA: 192
    / 128), forward and all three gradients, against dense attention."""
    from ray_tpu.ops.attention import reference_attention
    from ray_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.key(D), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, D))
    k = jax.random.normal(ks[1], (2, 64, kvh, D))
    v = jax.random.normal(ks[2], (2, 64, kvh, Dv))
    f = lambda q, k, v: flash_attention(q, k, v, block_q=32, block_k=32)
    assert f(q, k, v).shape == (2, 64, 4, Dv)
    np.testing.assert_allclose(f(q, k, v), reference_attention(q, k, v),
                               atol=2e-5)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    for a, b in zip(jax.grad(loss(f), argnums=(0, 1, 2))(q, k, v),
                    jax.grad(loss(reference_attention),
                             argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, atol=5e-5)
