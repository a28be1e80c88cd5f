"""The Gated DeltaNet / gated-attention expert stack (models/transformer.py
`gdn` mixer on ops/kda.py at a decay a head with shared key heads; the
`attn` kind with q / k norms, a rotation of a quarter of a head and a
sigmoid gate on its output; zero-centred norm weights; softmax-routed
experts beside a shared expert behind a sigmoid gate) on the CPU at the tiny
preset: the program against the plain reference
(chipbench/reference/qwen3_next.py: nothing from ray_tpu, the delta rule
token by token, full softmax rows, a loop over the held experts) on seeded
weights, each new mechanism got wrong one way, the sixteen shares of an
expert layer, the core at a scalar decay against the recurrence, the other
configurations' programs against what they were, the plan, the counts, the
configuration file, and what decoding refuses."""
import contextlib
import dataclasses
import functools
import hashlib
import json
import os
import re
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, transformer as tfm
from ray_tpu.models.configs import qwen3_next_tiny
from ray_tpu.ops import kda

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

pytestmark = pytest.mark.usefixtures("exact_matmuls")

CONFIG = os.path.join(ROOT, "chipbench", "configs", "qwen3_next_80b_a3b.json")
CATALOG = {  # the catalog's `config` of Qwen3-Next-80B-A3B-Instruct
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
FIRST = ("final_norm", "gdn_wo", "gdn_wqkvz", "gdn_A_log", "gdn_dt_bias",
         "gdn_conv", "attn_wq", "attn_wo", "attn_q_norm")
ROUTED = ("expert_down", "router", "shared_gate")
WRONG = ("decay_a_step_late", "no_beta", "gate_before_norm",
         "w_for_one_plus_w", "half_a_head_rotated", "gate_on_the_query",
         "value_heads_i_and_i_plus_half", "no_shared_gate")
# sha256[:16] of the StableHLO each preset's gradient program lowered to at
# the parent commit (PR 40's tree, this container's JAX, the CPU, tokens
# [2, 33], `shift_inputs`, float32 matmuls as float32: this file's
# `exact_matmuls`), remat off and on: the text of
# `jax.jit(value_and_grad(loss_fn)).lower(...).as_text()`. (The three routed
# presets' were taken again at PR 43's tree, whose held experts' row passes
# run in blocks: `ops/moe.py`; the three others' are PR 40's still.)
PARENT_HLO = {
    "llama_tiny": ("477b60d37afe307a", "fb0a0ec778730463"),
    "gpt2_tiny": ("4f2ae9e07027bc87", "4a9578c67d387a25"),
    "kimi_linear_tiny": ("1351b6f8a51ed658", "595571e2024d4fe8"),
    "granite_hybrid_tiny": ("ff3c8acbca76f994", "51b8226e0760232d"),
    "mellum2_tiny": ("7c8f75ed566a2912", "80fc62d0b1aaf755"),
    "kanana2_tiny": ("ad659bdff892a231", "4ee529c508a5e1e7"),
}


def _wrong_gdn_mixer(kind, cfg, h, layer):
    """`tfm._gdn_mixer` written out with one thing got wrong."""
    f32 = jnp.float32
    w = functools.partial(tfm._w, layer, cfg=cfg)
    qk = jnp.einsum("bsd,dcnh->bscnh", h, w("gdn_wqk"))
    vz = jnp.einsum("bsd,dcnh->bscnh", h, w("gdn_wvz"))
    ba = jnp.einsum("bsd,dcn->bscn", h, w("gdn_wba"))
    qk = jax.nn.silu(kda.short_conv(qk, layer["gdn_conv_qk"]))
    v = jax.nn.silu(kda.short_conv(vz[:, :, 0], layer["gdn_conv_v"]))
    q, k = kda.l2_normalize(qk[:, :, 0]), kda.l2_normalize(qk[:, :, 1])
    beta = jax.nn.sigmoid(ba[:, :, 0].astype(f32))
    g = -jnp.exp(layer["gdn_A_log"].astype(f32)) * jax.nn.softplus(
        ba[:, :, 1].astype(f32) + layer["gdn_dt_bias"].astype(f32))
    if kind == "decay_a_step_late":
        g = jnp.pad(g, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    if kind == "no_beta":
        beta = jnp.ones_like(beta)
    if kind == "value_heads_i_and_i_plus_half":
        r = cfg.gdn_v_heads // cfg.gdn_k_heads
        q, k = jnp.tile(q, (1, 1, r, 1)), jnp.tile(k, (1, 1, r, 1))
    o, _ = kda.kda_chunked(q, k, v, g, beta, chunk=cfg.gdn_chunk)
    z = jax.nn.silu(vz[:, :, 1].astype(f32))
    if kind == "gate_before_norm":
        o = tfm._norm(o.astype(f32) * z, layer["gdn_o_norm"], None,
                      "rmsnorm", cfg.norm_eps)
    else:
        o = tfm._norm(o.astype(f32), layer["gdn_o_norm"], None, "rmsnorm",
                      cfg.norm_eps) * z
    return jnp.einsum("bsnh,nhd->bsd", o.astype(h.dtype), w("gdn_wo"))


def _gate_on_the_query(cfg, kind, h, layer, positions, overlap):
    """`tfm._attn_mixer` with sigmoid(gate) on the query, not the output."""
    B, S, _ = h.shape
    q, k, v = tfm._qkv_proj(cfg, h, layer, positions, kind[0], overlap)
    gate = jnp.einsum("bsd,dnh->bsnh", h, tfm._w(layer, "wq_gate", cfg))
    o = tfm.attention(q * jax.nn.sigmoid(gate), k, v, causal=True)
    return o.reshape(B, S, -1) @ tfm._w(layer, "wo", cfg), k, v


@contextlib.contextmanager
def wrong(kind: str, cfg):
    """-> the configuration to run, inside patches on models/transformer.py
    that get one new mechanism wrong (no option of the program but the three
    that are configuration fields): the chip run at the timed sizes
    (PERF.md section 6) uses the same."""
    fields = {"w_for_one_plus_w": dict(norm_offset=0.0),
              "half_a_head_rotated": dict(rope_fraction=0.5),
              "no_shared_gate": dict(moe_shared_gate=False)}
    if kind in fields:
        yield dataclasses.replace(cfg, **fields[kind])
    elif kind == "gate_on_the_query":
        with mock.patch.object(tfm, "_attn_mixer", _gate_on_the_query):
            yield cfg
    else:
        with mock.patch.object(tfm, "_gdn_mixer",
                               functools.partial(_wrong_gdn_mixer, kind)):
            yield cfg


def _sizes(cfg, **changes):
    from chipbench import weights_qwen3_next as W

    tc = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return W.QwenNextSizes(dict(tc, **changes), cfg.norm_eps)


def _program(cfg, sz, params, toks):
    """(loss, compared gradient leaves) of the program."""
    from chipbench import weights_qwen3_next as W

    loss, g = jax.jit(jax.value_and_grad(lambda p: tfm.loss_fn(
        p, {"tokens": toks}, cfg, shift_inputs=True)))(params)
    return float(loss), W.program_leaves(cfg, sz, g)


@pytest.fixture(scope="module")
def conf():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def case():
    """The tiny preset in float32, seeded weights in both layouts' terms,
    and the program's and the reference's logits, loss and gradients. The
    decay's input `a` is drawn at its full fan-in scale here (the cell's
    maker quarters it to stay under the chunked form's e^80 at chunks of
    128; chunks of 16 have the room): a decay then differs by a factor of
    e between neighbouring tokens, and one taken a step late shows."""
    from chipbench import weights_qwen3_next as W
    from chipbench.reference import qwen3_next as ref

    cfg = qwen3_next_tiny(dtype=jnp.float32)
    sz, key = _sizes(cfg), jax.random.key(21)
    toks = jax.random.randint(jax.random.key(22), (2, 49), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"), \
            mock.patch.object(W, "A_SCALE", 1.0):
        params = W.program_params(key, sz, cfg)
        loss_p, got = _program(cfg, sz, params, toks)
        loss_r, want = jax.jit(lambda k, t: ref.loss_and_grads(k, t, sz))(
            key, toks)
        logits_p = jax.jit(lambda p, t: tfm.forward(p, t, cfg))(
            params, toks[:, :-1])
        logits_r = jax.jit(lambda k, t: ref.forward(k, t, sz))(
            key, toks[:, :-1])
    return dict(cfg=cfg, sz=sz, key=key, params=params, toks=toks,
                loss=(loss_p, float(loss_r)), logits=(logits_p, logits_r),
                grads=(got, want))


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_logits_and_loss_match_the_reference(case):
    got, want = case["logits"]
    assert got.shape == (2, 48, 256)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert abs(case["loss"][0] - case["loss"][1]) < 1e-5
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0),
                                                    case["cfg"]))
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(
        lambda a: a.shape, case["params"])  # the maker's layout is init's


@pytest.mark.parametrize("leaf", FIRST + ROUTED)
def test_gradient_leaf_matches_the_reference(case, conf, leaf):
    """Both groups of the cell's compared leaves: the nine every token
    reaches and the three of the expert layer; the fused leaves' gradients
    come back in the plain layout."""
    groups = conf["stack"]["groups"]
    assert tuple(groups["train_grad_rel_err"]) == FIRST
    assert tuple(groups["train_grad_rel_err_routed"]) == ROUTED
    got, want = case["grads"]
    assert got[leaf].shape == want[leaf].shape
    assert float(jnp.linalg.norm(want[leaf])) > 0
    assert _rel(got[leaf], want[leaf]) < 2e-5, leaf


@pytest.mark.parametrize("kind", WRONG)
def test_a_mechanism_got_wrong_fails_the_first_limit(case, conf, kind):
    """Each new mechanism made wrong puts the first group's error over the
    cell's limit (the sound program reads 1e-6 here and a few percent in
    bfloat16 on the chip): a decay a step late, beta left out, the gate
    before the norm, 1 + w taken as w, half a head rotated for a quarter,
    the gate on the query, key head i serving value heads i and i + H_k,
    the shared expert's gate left out."""
    with wrong(kind, case["cfg"]) as cfg, \
            jax.default_matmul_precision("highest"):
        _, got = _program(cfg, case["sz"], case["params"], case["toks"])
    want = case["grads"][1]
    worst = max(_rel(got[n], want[n]) for n in FIRST)
    assert worst > conf["limits"]["train_grad_rel_err"], worst


def test_the_shares_add_up():
    """One expert layer at 32 experts: the routed parts the sixteen held
    ranges give (the program's `moe_ffn_held` under `softmax_route`, each
    rank's weights made from the seed by the benchmark's maker) plus the
    gated shared expert, counted once, sum to the uncut reference's layer,
    a loop over all 32 experts; no assignment is dropped or counted twice."""
    from chipbench import weights_qwen3_next as W
    from chipbench.reference import qwen3_next as ref
    from chipbench.weights import layer_key
    from ray_tpu.ops import moe

    cfg = qwen3_next_tiny(dtype=jnp.float32)
    kind = ("gdn", "moe")
    key = layer_key(jax.random.key(31), 1)
    x = jax.random.normal(jax.random.key(32), (2, 40, cfg.d_model))
    whole = _sizes(cfg, moe_held=None)
    w_all = W.layer(key, whole, kind)
    want = ref._experts(x, w_all, whole, ref.mm_f32)
    shared = jax.nn.sigmoid(x @ w_all["shared_gate"])[..., None] * (
        ref._swiglu(x, w_all["s_gate"], w_all["s_up"], w_all["s_down"],
                    ref.mm_f32))
    total, assigned = shared, 0.0
    route = functools.partial(moe.softmax_route,
                              experts_per_token=cfg.moe_experts_per_token)
    for first in range(0, 32, 2):
        sz = _sizes(cfg, moe_held=(first, 2))
        w = W.to_program(W.layer(key, sz, kind), sz, kind)
        np.testing.assert_array_equal(  # a rank's experts are the model's
            w["moe_w_down"], w_all["e_down"][first:first + 2])
        np.testing.assert_array_equal(w["shared_gate"], w_all["shared_gate"])
        y, cnt = moe.moe_ffn_held(
            x, w["router"], w["moe_w_gate_up"], w["moe_w_down"], route=route,
            held_first=first, dtype=jnp.float32)
        assert float(cnt["dropped"]) == 0.0
        total, assigned = total + y, assigned + float(cnt["assigned"])
        # the layer a rank runs: its routed part plus the shared expert whole
        rank_cfg = dataclasses.replace(cfg, moe_held=(first, 2))
        part, _ = tfm._mlp_block(rank_cfg, "moe", x, w)
        np.testing.assert_allclose(part, y + shared, atol=2e-5)
    np.testing.assert_allclose(total, want, atol=3e-5)
    assert assigned == 2 * 40 * cfg.moe_experts_per_token


def _core_inputs(S, Hk, Hv, d, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = kda.l2_normalize(jax.random.normal(ks[0], (2, S, Hk, d)))
    k = kda.l2_normalize(jax.random.normal(ks[1], (2, S, Hk, d)))
    v = jax.random.normal(ks[2], (2, S, Hv, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (2, S, Hv), minval=-6.0,
                                    maxval=0.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, S, Hv)))
    return q, k, v, g, beta


@pytest.mark.parametrize("body,S,init,strong", [
    ("xla", 80, False, False),
    ("pallas", 256, False, False),
    ("pallas", 300, True, False),   # a state to start from, a ragged end
    ("pallas", 256, True, True),    # a head that forgets inside a few tokens
])
@pytest.mark.parametrize("Hk,Hv", [(2, 2), (2, 4)])
def test_the_scalar_decay_core_is_the_recurrence(body, S, init, strong, Hk,
                                                 Hv):
    """The chunked core at one decay a head, for H_v = H_k and H_v = 2 H_k
    (key head i serving value heads 2i and 2i + 1), is `kda_recurrent`:
    outputs, final state and the gradients of all five inputs and of the
    initial state, through the XLA body and through the scalar-decay kernels
    (interpret mode), which are held to the XLA body too. `strong`: value
    head 1 decays by e^-4 a token, e^-128 inside one sub-block of 32, past
    the per-channel bodies' cap of e^80 (their pairs late in the sub-block
    come out e^-7 for e^-4, so that case is held to the recurrence alone);
    the scalar body's e^(G_t - G_s) has nothing to cap."""
    d, chunk = (16, 16) if body == "xla" else (128, 128)
    fn = kda.kda_chunked_xla if body == "xla" else kda.kda_chunked_pallas
    q, k, v, g, beta = _core_inputs(S, Hk, Hv, d)
    if strong:
        g = g.at[:, :, 1].set(-4.0)
    s0 = (jax.random.normal(jax.random.key(9), (2, Hv, d, d)) if init
          else jnp.zeros((2, Hv, d, d)))
    args = (q, k, v, g, beta, s0)

    def run(f, **kw):
        def loss(q, k, v, g, beta, s0):
            o, s = f(q, k, v, g, beta, initial_state=s0, **kw)
            return jnp.sum(jnp.sin(o)) + jnp.sum(s * s), (o, s)
        return jax.jit(jax.value_and_grad(loss, argnums=range(6),
                                          has_aux=True))(*args)

    (_, (o, s)), grads = run(fn, chunk=chunk)
    (_, (o_r, s_r)), grads_r = run(kda.kda_recurrent)
    assert o.shape == (2, S, Hv, d) and s.shape == (2, Hv, d, d)
    np.testing.assert_allclose(o, o_r, atol=2e-5)
    np.testing.assert_allclose(s, s_r, atol=2e-5)
    for got, want in zip(grads, grads_r):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=5e-5 * max(
            1.0, float(jnp.abs(want).max())))
    if body == "pallas" and not strong:
        (_, (o_x, s_x)), grads_x = run(kda.kda_chunked_xla, chunk=chunk)
        for got, want in zip((o, s) + grads, (o_x, s_x) + grads_x):
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=5e-5 * max(
                1.0, float(jnp.abs(want).max())))
    # Value heads 0 and 1 read key head 0: head i + H_k would differ.
    if Hv > Hk:
        alone, _ = kda.kda_recurrent(
            q[:, :, :1], k[:, :, :1], v[:, :, 1:2], g[:, :, 1:2],
            beta[:, :, 1:2], initial_state=s0[:, 1:2])
        np.testing.assert_allclose(o[:, :, 1:2], alone, atol=2e-5)


def _outer_avals(jaxpr, out=None):
    """(shape, dtype) of every variable an equation outside the kernels
    makes (a `pallas_call`'s own results counted, its body not), and the
    `pallas_call` equations themselves."""
    out = ([], []) if out is None else out
    for eqn in jaxpr.eqns:
        out[0].extend((tuple(v.aval.shape), str(v.aval.dtype))
                      for v in eqn.outvars)
        if eqn.primitive.name == "pallas_call":
            out[1].append(eqn)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _outer_avals(sub, out)
    return out


def test_a_scalar_decay_call_takes_its_operands_as_they_lie():
    """A rank-3 call's program, forward and gradient: nothing [B,S,H_v,d_k]
    in float32 (g broadcast over the channels, or its gradient before the
    sum), no q or k repeated over the value heads (nothing [B,S,H_v,d_k] or
    [B,S,H_v d_k] at all: d_v differs here), and two `pallas_call`s that
    read g as [B,S,H_v] and write dg as dbeta, [B,H_v,1,S]."""
    B, S, Hk, Hv, dk, dv = 1, 256, 2, 4, 128, 256
    sd = jax.ShapeDtypeStruct
    q, v = sd((B, S, Hk, dk), jnp.bfloat16), sd((B, S, Hv, dv), jnp.bfloat16)
    g = sd((B, S, Hv), jnp.float32)

    def loss(q, k, v, g, beta):
        o, _ = kda.kda_chunked_pallas(q, k, v, g, beta, chunk=128)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=range(5)))(q, q, v, g, g)
    avals, calls = _outer_avals(jaxpr.jaxpr)
    shapes = {sh for sh, _ in avals}
    assert (B, S, Hv, dk) not in shapes and (B, S, Hv * dk) not in shapes
    assert [len(c.invars) for c in calls] == [6, 9]
    for call in calls:
        ins = [tuple(x.aval.shape) for x in call.invars]
        assert ins[:5] == [(B, S, Hk * dk)] * 2 + [(B, S, Hv * dv)] + [
            (B, S, Hv)] * 2
    outs = [tuple(x.aval.shape) for x in calls[1].outvars]
    assert outs[:2] == [(B, S, Hk * dk)] * 2          # dq, dk a KEY head
    assert outs[3:5] == [(B, Hv, 1, S)] * 2           # dg as dbeta: rows
    # The broadcast it replaces, for scale: a per-channel call on the same
    # rule has both.
    def broadcast(q, k, v, g, beta):
        q, k, g = kda._per_channel(q, k, v, g)
        return loss(q, k, v, g, beta)

    old = jax.make_jaxpr(jax.grad(broadcast, argnums=range(5)))(q, q, v, g, g)
    assert ((B, S, Hv, dk), "float32") in _outer_avals(old.jaxpr)[0]


def test_the_stack_takes_the_scalar_kernels_under_full_remat(monkeypatch):
    """The tiny stack's gradient through the kernels (interpret mode) under
    the cell's remat policy: the three DeltaNet layers run the scalar
    forward kernel twice each (6 in / 4 out; `full` keeps no residual of
    the core: ROADMAP D3) and the backward once (9 in / 6 out), every call
    reading g as [B, S, H_v]; the gradients are the XLA body's."""
    from test_kda import _kernel_calls

    cfg = qwen3_next_tiny(remat=True, remat_policy="full", dtype=jnp.float32)
    params = tfm.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 33), 0, cfg.vocab_size)
    # A new function each time: jax caches a trace by the function's identity.
    grad = lambda: jax.grad(lambda p: tfm.loss_fn(
        p, {"tokens": toks}, cfg, shift_inputs=True))
    g_xla = jax.jit(grad())(params)
    monkeypatch.setattr(kda, "use_kernels", lambda *a, **kw: True)
    jaxpr = jax.make_jaxpr(grad())(params).jaxpr
    calls = _kernel_calls(jaxpr)
    assert calls["6in_4out"] == 6 and calls["9in_6out"] == 3, calls
    for call in _outer_avals(jaxpr)[1]:
        if len(call.invars) in (6, 9):  # the core's, not flash's
            assert call.invars[3].aval.shape == (2, 32, cfg.gdn_v_heads)
    for a, b in zip(jax.tree.leaves(jax.jit(grad())(params)),
                    jax.tree.leaves(g_xla)):
        np.testing.assert_allclose(a, b, atol=1e-5 + 1e-4 * float(
            jnp.abs(b).max()))


def test_a_decay_constant_over_channels_is_the_scalar_decay():
    """A rank-4 g that is the same number in every channel gives what the
    rank-3 g gives, bit for bit (the scalar call IS that broadcast), and a
    per-channel call traces what it traced: `_per_channel` hands its
    operands back as they are."""
    q, k, v, g, beta = _core_inputs(64, 2, 2, 16)
    g4 = jnp.broadcast_to(g[..., None], g.shape + (16,))
    for fn in (kda.kda_chunked_xla, kda.kda_recurrent):
        o3, s3 = fn(q, k, v, g, beta)
        o4, s4 = fn(q, k, v, g4, beta)
        np.testing.assert_array_equal(o3, o4)
        np.testing.assert_array_equal(s3, s4)
    same = kda._per_channel(q, k, v, g4)
    assert same[0] is q and same[1] is k and same[2] is g4


@pytest.mark.parametrize("preset", sorted(PARENT_HLO))
def test_the_other_configurations_lower_to_what_they_did(preset):
    """The six accepted presets' gradient programs, remat off and on, lower
    to the StableHLO they lowered to at the parent commit, byte for byte:
    at their defaults the four new `attn` properties, `norm_offset`, the
    shared gate and the scalar-decay path trace nothing."""
    for remat, want in zip((False, True), PARENT_HLO[preset]):
        cfg = getattr(configs, preset)(remat=remat)
        p = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
        toks = jax.ShapeDtypeStruct((2, 33), jnp.int32)
        text = jax.jit(lambda p, t: jax.value_and_grad(
            lambda p: tfm.loss_fn(p, {"tokens": t}, cfg, shift_inputs=True))(
                p)).lower(p, toks).as_text()
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == want, (
            preset, remat)


def test_defaults_spelled_out_trace_nothing_and_each_property_counts(case):
    """A configuration that spells the five defaults out has the jaxpr of
    one that does not; and each property alone moves the tiny preset's
    logits."""
    base = configs.llama_tiny(dtype=jnp.float32)
    spelled = dataclasses.replace(base, attn_qk_norm=False, rope_fraction=1.0,
                                  attn_out_gate=False, norm_offset=0.0,
                                  moe_shared_gate=False)
    params = tfm.init_params(jax.random.key(0), base)
    toks = case["toks"][:, :24]
    jaxpr = lambda cfg: str(jax.make_jaxpr(
        lambda: tfm.forward(params, toks, cfg))())
    assert jaxpr(spelled) == jaxpr(base)
    cfg, (_, want) = case["cfg"], case["logits"]
    for change in (dict(attn_qk_norm=False), dict(rope_fraction=1.0),
                   dict(attn_out_gate=False), dict(norm_offset=0.0),
                   dict(moe_shared_gate=False)):
        got = tfm.forward(case["params"], case["toks"][:, :-1],
                          dataclasses.replace(cfg, **change))
        assert float(jnp.max(jnp.abs(got - want))) > 1e-3, change


def test_scopes_and_observations(case):
    """The program's text carries the device scopes the metrics read (`gdn`,
    `gdn.core`, `gattn`, `gattn.gate`, `moe.shared`), a stack without the
    gated kind none of `gattn`, and a traced scalar-decay core call leaves
    one `gdn.core.xla` observation with what it saw: `body` says whether
    the rule ran as itself (the kernels: "scalar") or as a broadcast into
    the per-channel body (the XLA fallback)."""
    from ray_tpu.util import tracing

    cfg = case["cfg"]
    before = tracing.phase_table().get("gdn.core.xla", {"count": 0})["count"]
    kda_before = tracing.phase_table().get("kda.core.xla",
                                           {"count": 0})["count"]
    with mock.patch.object(tracing, "observe", wraps=tracing.observe) as spy:
        text = jax.jit(lambda p, t: tfm.forward(p, t, cfg)).lower(
            case["params"], case["toks"][:, :-1]).as_text(debug_info=True)
        seen = [c for c in spy.call_args_list if c.args[0] == "gdn.core.xla"]
        assert [c.kwargs["body"] for c in seen] == ["per_channel"]
        assert seen[0].kwargs == dict(
            slow=False, chunk=cfg.gdn_chunk, chunks=3, k_heads=2, v_heads=4,
            decay="head", body="per_channel")
        with mock.patch.object(kda, "use_kernels", lambda *a, **k: True):
            jax.eval_shape(lambda *a: kda.kda_chunked(*a, chunk=128),
                           *_core_inputs(256, 2, 4, 128))
        last = spy.call_args_list[-1]
        assert last.args[0] == "gdn.core.pallas"
        assert last.kwargs["body"] == "scalar"
    for scope in ("gdn", "gdn.core", "gattn", "gattn.gate", "moe.shared",
                  "moe.route", "moe.experts"):
        assert re.search(rf'["/]{re.escape(scope)}/', text), scope
    table = tracing.phase_table()
    assert table["gdn.core.xla"]["count"] == before + 1  # one scanned body
    assert table.get("kda.core.xla", {"count": 0})["count"] == kda_before
    plain = configs.mellum2_tiny(dtype=jnp.float32)
    p = tfm.init_params(jax.random.key(0), plain)
    other = jax.jit(lambda p, t: tfm.forward(p, t, plain)).lower(
        p, case["toks"][:, :-1]).as_text(debug_info=True)
    assert "gattn" not in other and "moe.shared" not in other
    assert '"swa/' in other


def test_partial_rotation_and_the_gated_norm(case):
    """`_rope_first` turns the first `rot` columns as `_rope` would turn a
    head of that width and passes the rest; all of a head is `_rope`
    itself. The rotated scores depend on the distance alone."""
    cfg = case["cfg"]
    x = jax.random.normal(jax.random.key(3), (2, 48, 4, 32))
    pos = jnp.broadcast_to(jnp.arange(48, dtype=jnp.int32)[None], (2, 48))
    got = tfm._rope_first(x, 8, pos, cfg.rope_theta)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(
        got[..., :8], tfm._rope(x[..., :8], pos, cfg.rope_theta))
    np.testing.assert_array_equal(tfm._rope_first(x, 32, pos, 1e4),
                                  tfm._rope(x, pos, 1e4))
    assert cfg.rope_rotated == 8 and cfg.head_dim == 32
    layer = tfm.layer_params(case["params"], cfg, 3)
    h = jax.random.normal(jax.random.key(5), (2, 48, cfg.d_model))
    out = tfm._attn_mixer(cfg, ("attn", "moe"), h, layer, pos, None)[0]
    shifted = tfm._attn_mixer(cfg, ("attn", "moe"), h, layer, pos + 1000,
                              None)[0]
    np.testing.assert_allclose(shifted, out, atol=2e-5)
    with pytest.raises(ValueError, match="rope_fraction"):
        qwen3_next_tiny(rope_fraction=0.0)
    with pytest.raises(ValueError, match="norm_offset"):
        qwen3_next_tiny(norm_offset=0.5)
    with pytest.raises(ValueError, match="moe_shared_gate"):
        configs.llama_tiny(moe_shared_gate=True)


def test_stack_plans():
    """The cut is two segments (three DeltaNet layers, then the attention
    layer); the whole 48-layer stack is one segment of the four-layer period,
    twelve repeats; a layer listed twice is refused."""
    g, a = ("gdn", "moe"), ("attn", "moe")
    assert qwen3_next_tiny().stack_plan() == (((g,), 3), ((a,), 1))
    assert qwen3_next_tiny(n_layers=48).stack_plan() == (((g, g, g, a), 12),)
    assert qwen3_next_tiny(n_layers=8).layer_kinds() == (g, g, g, a) * 2
    assert qwen3_next_tiny().layer_slot(3) == (1, 0, 0)
    assert isinstance(tfm.param_logical_specs(qwen3_next_tiny())["layers"],
                      list)
    with pytest.raises(ValueError, match="two of"):
        qwen3_next_tiny(kda_layers=(1,))
    with pytest.raises(ValueError, match="multiple"):
        qwen3_next_tiny(gdn_k_heads=3)


def test_counts_and_the_configuration_file(conf):
    """num_params of the cut is 625,667,136 (ISSUE 41's arithmetic) and of
    the whole model 79.67 G; what the weights module builds has as many; the
    file keeps every key of the catalog's `config` but the three in
    `reduced`; the specs put heads and experts on their axes."""
    tc = dict(conf["transformer_config"])
    tc["dtype"], tc["param_dtype"] = jnp.bfloat16, jnp.float32
    cfg = tfm.TransformerConfig(**tc)
    assert cfg._mixer_params("gdn") == 33_718_464
    assert cfg._mixer_params("attn") == 27_263_488
    assert cfg._ffn_params("moe") == 4_196_352 + 32 * 3_145_728
    assert cfg.num_params() == 625_667_136
    from chipbench import weights_qwen3_next as W

    sz = W.sizes_of(conf, False)
    shapes = jax.eval_shape(lambda k: W.program_params(k, sz, cfg),
                            jax.random.key(0))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(shapes)) == 625_667_136
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(
        lambda a: a.shape, jax.eval_shape(
            lambda k: tfm.init_params(k, cfg), jax.random.key(0)))
    assert cfg.stack_plan() == (((("gdn", "moe"),), 3),
                                ((("attn", "moe"),), 1))
    whole = dataclasses.replace(cfg, n_layers=48, vocab_size=151936,
                                moe_held=None)
    assert round(whole.num_params() / 1e9, 2) == 79.67
    # Of the held experts a token touches k x held / E = 0.625 under even
    # routing.
    assert cfg.num_params() - cfg.num_active_params() == 4 * (
        31.375 * 3_145_728)
    for key, val in CATALOG.items():
        if key not in conf["reduced"]:
            assert conf[key] == val, key
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (4, 32, 18992)
    assert conf["published"] == dict(
        conf["published"], num_hidden_layers=48, num_experts=512,
        vocab_size=151936)
    assert conf["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert "625,667,136" in conf["deployment"] and conf["assumed"]
    assert "16 chips" in conf["deployment"]
    assert "loader owes it" in conf["assumed"]["interleaving"].lower()
    assert (tc["moe_num_experts"], tc["moe_experts_per_token"],
            tc["moe_held"], tc["moe_d_ff"], tc["moe_shared_experts"],
            tc["moe_shared_gate"]) == (512, 10, [224, 32], 512, 1, True)
    assert (tc["gdn_k_heads"], tc["gdn_v_heads"], tc["gdn_head_dim"],
            tc["gdn_conv"]) == (16, 32, 128, 4)
    assert (tc["n_heads"], tc["n_kv_heads"], tc["attn_head_dim"],
            tc["rope_fraction"], tc["rope_theta"]) == (16, 2, 256, 0.25, 1e7)
    assert tc["gdn_layers"] == [l for l in range(1, 49) if l % 4]
    assert sorted(conf["limits"]) == sorted(conf["stack"]["groups"])
    specs = tfm.param_logical_specs(cfg)["layers"][0][0]
    assert specs["gdn_wqk"] == ("layers", "embed", None, "heads", None)
    assert specs["gdn_wo"] == ("layers", "heads", None, "embed")
    assert specs["moe_w_down"] == ("layers", "expert", "mlp", "embed")


def test_hand_count_of_the_cells_operations(conf):
    """reduce/qwen3_next_counts.py at the cell's shape, by hand. A DeltaNet
    layer's matmul parameters: W_qkvz 2048 x 12,288 = 25,165,824, W_ba 2048
    x 64 = 131,072, W_o 4096 x 2048 = 8,388,608: 33,685,504. The attention
    layer's: 16,777,216 + 2 x 1,048,576 + 8,388,608 = 27,262,976. An expert
    layer adds the router 1,048,576, the gate 2,048, the shared expert and
    10 x 32 / 512 = 0.625 of an expert, 1.625 x 3,145,728. The core a token:
    16 key heads x 2 x 128 x 128 and 32 value heads x (6 x 128^2 + 3 x 128^2
    + 128^2 / 3). The flash kernels: 4 x 16 x 256 operations a pair of the
    triangle forward, 10 x backward, K and V bytes once a key head."""
    from chipbench import weights_qwen3_next as W
    from chipbench.reduce import qwen3_next_counts as c

    sz = W.sizes_of(conf, False)
    moe = 1_048_576 + 2_048 + 1.625 * 3_145_728
    assert c.layer_matmul_params(sz, "gdn") == 33_685_504 + moe
    assert c.layer_matmul_params(sz, "attn") == 27_262_976 + moe
    S = 16384
    core = 16 * 2 * 128 * 128 + 32 * (9 * 128 * 128 + 128 * 128 / 3.0)
    assert c.gdn_core_fwd_flops_per_token(16, 32, 128, 128, 128) == core
    n = 18992 * 2048 + 3 * 33_685_504 + 27_262_976 + 4 * moe
    pairs = S * (S + 1) / 2
    assert c.stack_flops_per_token(sz, S) == pytest.approx(
        6.0 * n + 12.0 * 16 * 256 * pairs / S + 9.0 * core, rel=1e-12)
    fwd, bwd = (f(1, 16, 32, S, 128, 128, 128)
                for f in (c.gdn_core_fwd, c.gdn_core_bwd))
    assert fwd["flops"] == S * core and bwd["flops"] == 2 * fwd["flops"]
    ins = (2 * 16 * 128 + 32 * 128) * 2 + 8 * 32  # q k v, g and beta
    assert fwd["bytes"] == S * (ins + 32 * 128 * 2)
    assert bwd["bytes"] == S * (2 * ins + 32 * 128 * 2)
    f = c.flash_fwd(1, 16, 2, S, 256)
    assert f["flops"] == 4.0 * 16 * 256 * pairs
    assert f["bytes"] == 2.0 * S * 256 * 2 * (16 + 2) + 4.0 * 16 * S
    assert c.flash_bwd(1, 16, 2, S, 256)["flops"] == 2.5 * f["flops"]
    # The program's own count has the norms' and the convolution's
    # parameters at 6 each and S^2 / 2 pairs; the benchmark's has neither
    # and the triangle's S (S + 1) / 2.
    tc = dict(conf["transformer_config"])
    tc["dtype"], tc["param_dtype"] = jnp.bfloat16, jnp.float32
    cfg = tfm.TransformerConfig(**tc)
    extra = 6 * (4 * 2 * 2048 + 2048 + 3 * (32_768 + 32 + 32 + 128) + 512)
    diff = cfg.flops_per_token(S) - c.stack_flops_per_token(sz, S)
    assert diff == pytest.approx(extra - 6.0 * 16 * 256, rel=1e-6)


def test_decoding_refuses_the_layer(case):
    from ray_tpu.models.generate import prefill

    with pytest.raises(NotImplementedError, match="R7 / R9"):
        prefill(case["params"], case["toks"][:, :8], case["cfg"], 16)
    gated = configs.llama_tiny(attn_out_gate=True)
    with pytest.raises(NotImplementedError, match="attn_out_gate"):
        prefill(tfm.init_params(jax.random.key(0), gated),
                case["toks"][:, :8], gated, 16)


@pytest.mark.parametrize("policy", ["dots", "full"])
def test_train_step_returns_the_counters_and_folds_them(policy):
    """transformer_train_step(with_counters=True) on the tiny preset under
    both remat policies, the kernels interpreted where they are reached
    (flash at heads of 32): the loss falls, nothing is dropped, four expert
    layers' assignments are counted and folded into the phase table."""
    from ray_tpu.ops import moe
    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.train.step import transformer_train_step
    from ray_tpu.util import tracing

    cfg = qwen3_next_tiny(remat=True, remat_policy=policy)
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    ts = transformer_train_step(cfg, mesh, shift_inputs=True,
                                with_counters=True)
    params, opt = ts.init(jax.random.key(0))
    toks = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 65)).astype(np.int32)
    before = tracing.phase_table().get("train.moe_assigned", {"count": 0})
    losses = []
    for _ in range(3):
        params, opt, loss, aux = ts.step(params, opt,
                                         ts.shard_batch({"tokens": toks}))
        losses.append(float(loss))
        seen = ts.observe_counters(aux)
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert seen["moe_dropped"] == 0.0
    # Four expert layers x 256 tokens x 4 a token, a quarter of the experts
    # held.
    assert 0.15 * 4096 < seen["moe_assigned"] < 0.35 * 4096
    assert seen["moe_window_rows"] == moe.held_window_rows(256, 4, 32, 8)
    assert (seen["moe_trips"] > 4.0) == (seen["moe_past_buffer"] > 0)
    table = tracing.phase_table()
    assert table["train.moe_assigned"]["count"] == before["count"] + 3
