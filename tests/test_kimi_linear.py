"""The layer stack (models/transformer.py) where its layers are of several
kinds, on the CPU at small sizes: a plan of several segments scanned against
the same layers applied one by one, the shares of an expert layer against
the uncut layer, the counters, the counts, the configuration file and the
two stored formats. The KDA core is tests/test_kda.py, the program against
the plain reference tests/test_kimi_linear_reference.py."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import kimi_linear_tiny
from ray_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.usefixtures("exact_matmuls")


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


def _shared_expert(c):
    """The always-on expert as the program computes it: the dense SwiGLU."""
    return tfm._mlp_block(
        kimi_linear_tiny(dtype=jnp.float32), "dense", c["x"],
        {"w_gate_up": c["sgu"], "w_down": c["sd"]})[0]


def _sigmoid(c, scale=2.446):
    """`moe_ffn_held`'s `route` for the case: sigmoid scores, its bias."""
    import functools

    return functools.partial(moe.sigmoid_route, bias=c["b"],
                             experts_per_token=c["k"], routed_scale=scale)


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


def _window_factor(monkeypatch, factor):
    """The first window as `factor` times the even share alone, without the
    module's row a token under it."""
    monkeypatch.setattr(moe, "HELD_WINDOW_FACTOR", factor)
    monkeypatch.setattr(moe, "HELD_WINDOW_MIN_TOKENS", 0.0)


@pytest.mark.parametrize("shares,factor", [(1, None), (4, None), (16, None),
                                           (4, 0.5), (4, 4.0)])
def test_expert_shares_add_up_to_the_uncut_layer(shares, factor, monkeypatch):
    """The parts all the shares give, the shared expert counted once, add
    up to the uncut layer's output; no assignment is dropped or counted
    twice (the shares' `assigned` add up to tokens x k). A share's window
    is its even load times the module's factor (None), 2.5, and a
    row a token at least (the sixteenth shares: 256 rows for an even load of
    64): what a share's routing puts past it takes further, smaller windows
    and is counted. At factor 0.5 the window is half the even load: two or three
    trips of the loop, with experts' runs that straddle the windows; at 4.0
    (the rule of PRs 27-33) a quarter share's window is every assignment."""
    if factor:
        _window_factor(monkeypatch, factor)
    c = _layer_case(S=128)
    per, every = c["E"] // shares, c["x"].shape[0] * c["x"].shape[1] * c["k"]
    rows = moe.held_window_rows(every // c["k"], c["k"], c["E"], per)
    assert rows == {(1, None): every, (4, None): 640, (16, None): 256,
                    (4, 0.5): 128, (4, 4.0): every}[shares, factor]
    total, assigned = 0.0, 0.0
    for r in range(shares):
        y, cnt = moe.moe_ffn_held(
            c["x"], c["rw"], c["wgu"][r * per:(r + 1) * per],
            c["wd"][r * per:(r + 1) * per], route=_sigmoid(c),
            held_first=r * per, dtype=jnp.float32)
        assert float(cnt["dropped"]) == 0.0
        held = float(cnt["assigned"])
        assert float(cnt["window_rows"]) == rows
        assert float(cnt["trips"]) == 1 + max(
            -(-(held - rows) // moe.further_window_rows(rows)), 0)
        assert float(cnt["past_buffer"]) == max(held - rows, 0)
        assert (factor != 0.5) or float(cnt["trips"]) > 1
        total, assigned = total + y, assigned + held
    shared = _shared_expert(c)
    np.testing.assert_allclose(total + shared, _uncut_layer(c), atol=2e-5)
    assert assigned == every


def test_no_assignment_dropped_under_a_skewed_router(monkeypatch):
    """A router that sends every token to the same four experts. With every
    expert held one window holds all tokens x k assignments: one trip. A
    share that holds those four works them in two trips at the module's
    windows (2.5 x its even quarter, 640 rows, then one of 384, half of it
    to a multiple of 128) and in eight at a window an eighth of their load:
    nothing is dropped, `past_buffer` counts what went beyond the first
    window, and output and gradients are those of a window that holds
    everything."""
    c = _layer_case(seed=1, S=128)
    c["b"] = c["b"].at[:4].add(10.0)  # experts 0-3 win every selection
    kw = dict(route=_sigmoid(c), dtype=jnp.float32)
    T = c["x"].shape[0] * c["x"].shape[1]
    shared = _shared_expert(c)
    y, cnt = moe.moe_ffn_held(c["x"], c["rw"], c["wgu"], c["wd"], **kw)
    assert float(cnt["dropped"]) == 0.0 == float(cnt["past_buffer"])
    assert float(cnt["trips"]) == 1.0 and float(cnt["window_rows"]) == T * 4
    assert float(cnt["load_max"]) == T and float(cnt["assigned"]) == T * 4
    np.testing.assert_allclose(y + shared, _uncut_layer(c), atol=2e-5)
    y, cnt = moe.moe_ffn_held(c["x"], c["rw"], c["wgu"][:4], c["wd"][:4],
                              **kw)
    assert moe.held_window_rows(T, 4, 16, 4) == 640 == float(
        cnt["window_rows"])
    assert moe.further_window_rows(640) == 384
    assert float(cnt["trips"]) == 2.0 and float(cnt["dropped"]) == 0.0
    assert float(cnt["past_buffer"]) == T * 4 - 640
    np.testing.assert_allclose(y + shared, _uncut_layer(c), atol=2e-5)

    def share(x, wgu, factor):
        _window_factor(monkeypatch, factor)
        return moe.moe_ffn_held(x, c["rw"], wgu, c["wd"][:4], **kw)

    _window_factor(monkeypatch, 0.5)
    rows = moe.held_window_rows(T, 4, 16, 4)
    assert rows == 128 and T * 4 == 1024  # eight trips
    y, cnt = share(c["x"], c["wgu"][:4], 0.5)
    assert float(cnt["assigned"]) == T * 4
    assert float(cnt["past_buffer"]) == T * 4 - rows
    assert float(cnt["dropped"]) == 0.0 and float(cnt["trips"]) == 8.0
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
    _window_factor(monkeypatch, 0.5)
    monkeypatch.setattr(moe, "_trips", lambda held, rows, more, further: 7)
    _, cnt = moe.moe_ffn_held(c["x"], c["rw"], c["wgu"][:4], c["wd"][:4],
                              route=_sigmoid(c, 1.0), dtype=jnp.float32)
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


def _layer_by_layer(params, toks, cfg):
    """The stack as a Python loop over its layers, each taken out of the
    stored tree by `layer_params`: no plan, no scan, no remat."""
    x = tfm.embed_tokens(params, toks, cfg)
    positions = jnp.broadcast_to(jnp.arange(toks.shape[1], dtype=jnp.int32),
                                 toks.shape)
    for l, kind in enumerate(cfg.layer_kinds()):
        x, _ = tfm._layer_body(cfg, kind, x, tfm.layer_params(params, cfg, l),
                               positions)
    return tfm.lm_head(params, x, cfg)


@pytest.mark.parametrize("name,over,plan", [
    # kda | mla | attn: one layer of each mixer, the last in neither list
    ("attn_tail", dict(n_layers=3, kda_layers=(1,), mla_layers=(2,)),
     [(1, 1), (1, 1), (1, 1)]),
    # (kda, attn) twice, then kda: a pattern of two positions with repeats,
    # under the remat policy
    ("kda_attn_pairs", dict(n_layers=5, kda_layers=(1, 3, 5), mla_layers=(),
                            moe_first_dense=0, remat=True,
                            remat_policy="dots"),
     [(2, 2), (1, 1)]),
    # grouped-query attention between mla layers, every layer with experts
    ("gqa_among_mla", dict(n_layers=4, kda_layers=(), mla_layers=(1, 2, 4),
                           n_kv_heads=2, moe_first_dense=0),
     [(1, 2), (1, 1), (1, 1)]),
])
def test_scanned_segments_match_layer_by_layer(name, over, plan):
    """A softmax-attention layer among kda / mla ones (rope on it alone), in
    a plan of several segments: `_backbone`'s scans against the same layers
    applied one by one. Logits, and the gradient of every leaf of every
    layer, found through `stack_segments` / `layer_slot` on both sides."""
    cfg = kimi_linear_tiny(dtype=jnp.float32, positional="rope",
                           moe_held=(4, 8), **over)
    assert [(len(p), r) for p, r in cfg.stack_plan()] == plan
    mixers = {m for m, _ in cfg.layer_kinds()}
    assert "attn" in mixers and len(mixers) >= 2
    params = tfm.init_params(jax.random.key(2), cfg)
    toks = jax.random.randint(jax.random.key(3), (2, 24), 0, cfg.vocab_size)

    def logits_and_grads(forward):  # one compile a side
        def loss(p):
            logits = forward(p, toks, cfg)
            return jnp.mean(jnp.sin(logits)), logits
        (_, logits), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
        return logits, g

    logits, g_scan = logits_and_grads(tfm.forward)
    want_logits, g_loop = logits_and_grads(_layer_by_layer)
    np.testing.assert_allclose(logits, want_logits, atol=1e-5)
    for l, (mixer, ffn) in enumerate(cfg.layer_kinds()):
        got = tfm.layer_params(g_scan, cfg, l)
        want = tfm.layer_params(g_loop, cfg, l)
        assert ({"attn": "wo", "mla": "mla_wo", "kda": "kda_wo"}[mixer] in got
                and {"dense": "w_down", "moe": "moe_w_down"}[ffn] in got)
        for n in want:
            if n == "router_bias":
                continue  # a buffer
            np.testing.assert_allclose(
                got[n], want[n], err_msg=f"{name} layer {l} {n}",
                atol=1e-6 + 1e-4 * float(jnp.abs(want[n]).max()))


def test_classic_stacks_are_what_they_were():
    """A configuration with no kda / mla layer and the GShard router keeps
    its one stacked tree (a dict of leaves [L, ...], also where
    `param_logical_specs` describes it), its counts and `stack_plan` of one
    segment; any other stack is a list of segments, a one-layer one too."""
    from ray_tpu.models.configs import gpt2_125m, llama_tiny

    for cfg in (llama_tiny(), llama_tiny(moe_num_experts=4), gpt2_125m()):
        specs = tfm.param_logical_specs(cfg)
        assert isinstance(specs["layers"], dict)
        assert tfm.stack_segments(specs, cfg) == [[specs["layers"]]]
        assert cfg.stack_plan() == (((cfg.layer_kinds()[0],), cfg.n_layers),)
    assert gpt2_125m().num_params() == 124_439_808 - 82_944  # no lin. biases
    p = tfm.init_params(jax.random.key(0), llama_tiny())
    assert isinstance(p["layers"], dict)
    assert p["layers"]["wo"].shape == (2, 128, 128)
    assert tfm.layer_params(p, llama_tiny(), 1)["wo"].shape == (128, 128)
    one = kimi_linear_tiny(n_layers=1)  # one KDA layer, a dense feed-forward
    assert one.stack_plan() == (((("kda", "dense"),), 1),)
    specs = tfm.param_logical_specs(one)
    assert isinstance(specs["layers"], list)
    assert tfm.stack_segments(specs, one) is specs["layers"]
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
    assert seen["moe_window_rows"] == moe.held_window_rows(128, 4, 16, 8)
    assert (seen["moe_trips"] > 1.0) == (seen["moe_past_buffer"] > 0)
    table = tracing.phase_table()
    assert table["train.moe_assigned"]["count"] == before["count"] + 3
    assert {"train.moe_trips", "train.moe_window_rows",
            "train.moe_rows_worked"} <= set(table)
    # The row passes work whole blocks, as far as the held rows reach.
    block = moe.block_rows(int(seen["moe_window_rows"]))
    assert seen["moe_rows_worked"] % block == 0 and (
        seen["moe_assigned"] <= seen["moe_rows_worked"]
        < seen["moe_assigned"] + seen["moe_trips"] * block)
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
