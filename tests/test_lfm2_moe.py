"""The gated-short-convolution / GQA expert stack (LFM2-MoE, `lfm2_moe_tiny`):
the convolution's core against a ten-line loop, value and all four
gradients, for the XLA body and the interpreted kernel pair; the preset
through `loss_fn` held to chipbench/reference/lfm2_moe.py on seeded weights
in float32, loss and EVERY gradient leaf; eleven mechanisms got wrong, each
of which must read apart; the dispatch rule. What the families share
(digests, plans, counts, scopes, the expert shares, the configuration file)
is in tests/test_model_table.py and tests/test_expert_shares.py."""
import dataclasses
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from chipbench import weights_lfm2_moe as W
from chipbench.reference import lfm2_moe as ref
from chipbench.weights import layer_key
from ray_tpu.models import configs, transformer as tfm
from ray_tpu.ops import kda, moe, shortconv as sc

pytestmark = pytest.mark.usefixtures("exact_matmuls")

LIMIT = 1e-4   # float32 against float32: rounding reads 1e-6 to 3e-6
APART = 3e-3   # a wrong mechanism: thirty times the limit at the least
S = 40


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


# ------------------------------------------------------------------ the core


def _core_loop(bg, cg, x, w):
    """y_t = Cg_t * sum_j w[j] (Bg * x)_{t-K+1+j}, a token and a tap at a
    time, nothing before a sequence's start."""
    z, K = bg * x, w.shape[0]
    c = jnp.zeros_like(z)
    for t in range(z.shape[1]):
        for j in range(K):
            if t - (K - 1) + j >= 0:
                c = c.at[:, t].add(w[j] * z[:, t - (K - 1) + j])
    return cg * c


@pytest.mark.parametrize("body", ["xla", "pallas"])
def test_the_core_is_the_loop(body, monkeypatch):
    """Batch 2 x 40 rows in row blocks of 16: a block boundary inside each
    sequence (the halo is read), a sequence's first block (the halo is
    zeroed, the second sequence's too) and a last block that ends past the
    sequence (its rows are masked); value, dBg, dCg, dx and dw."""
    monkeypatch.setattr(sc, "ROWS", (16, 16))
    C = 128
    ks = jax.random.split(jax.random.key(0), 3)
    p = jax.random.normal(ks[0], (2, 40, 3 * C), jnp.float32)
    w = jax.random.normal(ks[1], (3, C), jnp.float32)
    dy = jax.random.normal(ks[2], (2, 40, C), jnp.float32)
    fn = {"xla": sc.gated_conv_xla, "pallas": sc.gated_conv_pallas}[body]

    def pair(f):
        y, vjp = jax.vjp(f, p, w)
        return (y,) + vjp(dy)

    got = jax.jit(lambda: pair(fn))()
    want = jax.jit(lambda: pair(lambda p, w: _core_loop(
        p[..., :C], p[..., C:2 * C], p[..., 2 * C:], w)))()
    for g, r in zip(got, want):  # dp is d[Bg ; Cg ; x] as one tensor
        assert g.shape == r.shape and _rel(g, r) < 1e-5
    for k in range(3):
        assert _rel(got[1][..., k * C:(k + 1) * C],
                    want[1][..., k * C:(k + 1) * C]) < 1e-5


def test_the_rule_is_a_pure_function():
    assert sc.use_kernels("tpu", 2048, False)
    assert not sc.use_kernels("tpu", 2048, True)    # a mesh: the XLA body
    assert not sc.use_kernels("cpu", 2048, False)
    assert not sc.use_kernels("tpu", 2048 + 64, False)  # no whole lane tiles


# ----------------------------------------------------- the stack, every leaf


def _sizes(cfg):
    tc = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return W.Lfm2Sizes(tc, cfg.norm_eps)


@pytest.fixture(scope="module")
def case():
    """cfg, tokens, the seeded weights as the program holds them, and the
    reference's loss and gradient of EVERY leaf, layer by layer in the
    program's layout."""
    cfg = configs.lfm2_moe_tiny(dtype=jnp.float32)
    sz, key = _sizes(cfg), jax.random.key(3)
    toks = jax.random.randint(jax.random.key(1), (2, S + 1), 0, sz.V)

    @jax.jit
    def make(key):
        given = (W.top(key, sz), [W.layer(layer_key(key, l), sz, kind)
                                  for l, kind in enumerate(sz.kinds)])
        loss, g = jax.value_and_grad(
            lambda g: ref.loss(key, toks, sz, given=g))(given)
        return (W.program_params(key, sz, cfg), loss, g[0],
                [W.to_program(w, sz, kind) for w, kind in zip(g[1], sz.kinds)])

    with jax.default_matmul_precision("highest"):
        params, loss, top, layers = make(key)
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(lambda: tfm.init_params(key, cfg)))
    return cfg, toks, params, float(loss), top, layers


def _errors(cfg, toks, params, top, layers):
    """(the program's loss, {leaf: relative error of its gradient})."""
    loss, g = jax.jit(jax.value_and_grad(lambda p: tfm.loss_fn(
        p, {"tokens": toks}, cfg, shift_inputs=True)))(params)
    errs = {n: _rel(g[n], top[n]) for n in top}
    for l, want in enumerate(layers):
        got = tfm.layer_params(g, cfg, l)
        errs.update({f"{l}.{n}": _rel(got[n], want[n]) for n in want
                     if n != "router_bias"})  # a buffer: no gradient
    return float(loss), errs


def test_the_stack_is_the_reference(case):
    """Under remat `full`, the cell's policy (on the CPU the core is the XLA
    body: nothing of it is kept under either policy)."""
    cfg, toks, params, want_loss, top, layers = case
    cfg = dataclasses.replace(cfg, remat=True, remat_policy="full")
    loss, errs = _errors(cfg, toks, params, top, layers)
    assert abs(loss - want_loss) < 1e-5 * want_loss
    assert len(errs) == 2 + 7 + 10 + 2 * 8   # every leaf with a gradient
    assert max(errs.values()) < LIMIT, max(errs.items(), key=lambda e: e[1])


# ------------------------------------------------- mechanisms got wrong


def _wrong_conv(how):
    """`_shortconv_mixer` with one thing wrong, in plain jax.numpy."""
    def apply(cfg, kind, h, layer, positions, overlap):
        d = cfg.d_model
        p = h @ tfm._w(layer, "shortconv_win", cfg).reshape(d, -1)
        bg, cg, x = p[..., :d], p[..., d:2 * d], p[..., 2 * d:]
        w = tfm._w(layer, "shortconv_conv", cfg)
        z = x if how == "no_bg" else bg * x
        if how == "reversed":
            w = w[::-1]
        if how == "four_taps":  # one more tap, on the token three back
            w = jnp.concatenate([w[:1], w])
        if how == "halo":  # the batch's rows as one sequence
            c = kda.short_conv(z.reshape(1, -1, d), w).reshape(z.shape)
        else:
            c = kda.short_conv(z, w)
        if how == "silu":  # `mixer_conv`'s function
            c = jax.nn.silu(c)
        y = c if how == "no_cg" else cg * c
        return y @ tfm._w(layer, "shortconv_wout", cfg), None, None
    return mock.patch.dict(tfm.MIXERS, {"shortconv": dataclasses.replace(
        tfm.MIXERS["shortconv"], apply=apply)})


def _norm_after_rotation():
    plain = tfm._qkv_proj

    def proj(cfg, h, layer, positions, mixer="attn", overlap=None):
        q, k, v = plain(dataclasses.replace(cfg, attn_qk_norm=False), h,
                        layer, positions, mixer, overlap)
        q, k = (tfm._norm(x, layer[n], None, "rmsnorm", cfg.norm_eps)
                for x, n in ((q, "q_norm"), (k, "k_norm")))
        return q, k, v
    return mock.patch.object(tfm, "_qkv_proj", proj)


def _route(how):
    def route(x, router_w, bias, *, experts_per_token, routed_scale):
        s = jax.nn.sigmoid(x @ router_w)
        _, idx = jax.lax.top_k(s + bias, experts_per_token)
        w = jnp.take_along_axis(s + bias if how == "bias" else s, idx, -1)
        if how != "unnormed":
            w = w / jnp.sum(w, -1, keepdims=True)
        return idx.astype(jnp.int32), w * routed_scale
    return mock.patch.object(moe, "sigmoid_route", route)


WRONG = {
    "no_bg_gate": functools.partial(_wrong_conv, "no_bg"),
    "no_cg_gate": functools.partial(_wrong_conv, "no_cg"),
    "silu_after_the_taps": functools.partial(_wrong_conv, "silu"),
    "taps_reversed": functools.partial(_wrong_conv, "reversed"),
    "four_taps": functools.partial(_wrong_conv, "four_taps"),
    "halo_over_a_sequence_boundary": functools.partial(_wrong_conv, "halo"),
    "no_qk_norm": dict(attn_qk_norm=False),
    "norm_after_the_rotation": _norm_after_rotation,
    "bias_inside_the_gates": functools.partial(_route, "bias"),
    "softmax_for_sigmoid": dict(moe_router="softmax"),
    "gates_not_renormalised": functools.partial(_route, "unnormed"),
}


@pytest.mark.parametrize("how", sorted(WRONG))
def test_a_wrong_mechanism_reads_apart(case, how):
    """Each of the eleven, in the program's place, misses the reference by
    more than `APART` in some gradient leaf (the sound program reads under
    `LIMIT`, thirty times less)."""
    cfg, toks, params, _, top, layers = case
    wrong = WRONG[how]
    if isinstance(wrong, dict):
        loss, errs = _errors(dataclasses.replace(cfg, **wrong), toks, params,
                             top, layers)
    else:
        with wrong():
            loss, errs = _errors(cfg, toks, params, top, layers)
    assert max(errs.values()) > APART, (how, max(errs.values()))
