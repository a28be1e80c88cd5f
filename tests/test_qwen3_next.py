"""The Gated DeltaNet / gated-attention expert stack (models/transformer.py
`gdn` mixer on ops/kda.py at a decay a head with shared key heads; the
`attn` kind with q / k norms, a rotation of a quarter of a head and a
sigmoid gate on its output; zero-centred norm weights; softmax-routed
experts beside a shared expert behind a sigmoid gate) on the CPU at the tiny
preset: the program against the plain reference
(chipbench/reference/qwen3_next.py: nothing from ray_tpu, the delta rule
token by token, full softmax rows, a loop over the held experts) on seeded
weights, each new mechanism got wrong one way (a row of the program's table
of mixers replaced), its scopes and observations, the counts and the
configuration file. What it shares with the other families is
tests/test_model_table.py (plan, lowering, decoding), test_preset_programs.py
(the train step) and test_expert_shares.py; the core at a scalar decay is
tests/test_kda_scalar.py."""
import contextlib
import dataclasses
import functools
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
from test_kda_scalar import _core_inputs

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


def _wrong_gdn_mixer(wrong, cfg, kind, h, layer, positions, overlap):
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
    if wrong == "decay_a_step_late":
        g = jnp.pad(g, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    if wrong == "no_beta":
        beta = jnp.ones_like(beta)
    if wrong == "value_heads_i_and_i_plus_half":
        r = cfg.gdn_v_heads // cfg.gdn_k_heads
        q, k = jnp.tile(q, (1, 1, r, 1)), jnp.tile(k, (1, 1, r, 1))
    o, _ = kda.kda_chunked(q, k, v, g, beta, chunk=cfg.gdn_chunk)
    z = jax.nn.silu(vz[:, :, 1].astype(f32))
    if wrong == "gate_before_norm":
        o = tfm._norm(o.astype(f32) * z, layer["gdn_o_norm"], None,
                      "rmsnorm", cfg.norm_eps)
    else:
        o = tfm._norm(o.astype(f32), layer["gdn_o_norm"], None, "rmsnorm",
                      cfg.norm_eps) * z
    return (jnp.einsum("bsnh,nhd->bsd", o.astype(h.dtype), w("gdn_wo")),
            None, None)


def _gate_on_the_query(cfg, kind, h, layer, positions, overlap):
    """`tfm._attn_mixer` with sigmoid(gate) on the query, not the output."""
    B, S, _ = h.shape
    q, k, v = tfm._qkv_proj(cfg, h, layer, positions, kind[0], overlap)
    gate = jnp.einsum("bsd,dnh->bsnh", h, tfm._w(layer, "wq_gate", cfg))
    o = tfm.attention(q * jax.nn.sigmoid(gate), k, v, causal=True)
    return o.reshape(B, S, -1) @ tfm._w(layer, "wo", cfg), k, v


@contextlib.contextmanager
def wrong(kind: str, cfg):
    """-> the configuration to run, a row of `tfm.MIXERS` replaced by one
    whose `apply` gets one new mechanism wrong (no option of the program but
    the three that are configuration fields): the chip run at the timed
    sizes (PERF.md section 6) uses the same."""
    fields = {"w_for_one_plus_w": dict(norm_offset=0.0),
              "half_a_head_rotated": dict(rope_fraction=0.5),
              "no_shared_gate": dict(moe_shared_gate=False)}
    if kind in fields:
        yield dataclasses.replace(cfg, **fields[kind])
        return
    name, apply = (("attn", _gate_on_the_query)
                   if kind == "gate_on_the_query" else
                   ("gdn", functools.partial(_wrong_gdn_mixer, kind)))
    row = dataclasses.replace(tfm.MIXERS[name], apply=apply)
    with mock.patch.dict(tfm.MIXERS, {name: row}):
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
        changed = dataclasses.replace(cfg, **change)  # (a program each)
        got = jax.jit(lambda p, t: tfm.forward(p, t, changed))(
            case["params"], case["toks"][:, :-1])
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
        # (the core's last: a process that listens to jax's own events
        # observes an `xla.trace` after it)
        last = [c for c in spy.call_args_list
                if c.args[0].startswith("gdn.core")][-1]
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
    assert cfg.attn_rope("attn")[1] == 8 and cfg.head_dim == 32
    layer = tfm.layer_params(case["params"], cfg, 3)
    h = jax.random.normal(jax.random.key(5), (2, 48, cfg.d_model))
    attn = tfm.MIXERS["attn"].apply
    out = attn(cfg, ("attn", "moe"), h, layer, pos, None)[0]
    shifted = attn(cfg, ("attn", "moe"), h, layer, pos + 1000, None)[0]
    np.testing.assert_allclose(shifted, out, atol=2e-5)
    with pytest.raises(ValueError, match="rope_fraction"):
        qwen3_next_tiny(rope_fraction=0.0)
    with pytest.raises(ValueError, match="norm_offset"):
        qwen3_next_tiny(norm_offset=0.5)
    with pytest.raises(ValueError, match="moe_shared_gate"):
        configs.llama_tiny(moe_shared_gate=True)


def test_counts_and_the_configuration_file(conf):
    """num_params of the cut is 625,667,136 (ISSUE 41's arithmetic) and of
    the whole model 79.67 G; what the weights module builds has as many; the
    file keeps every key of the catalog's `config` but the three in
    `reduced`; the specs put heads and experts on their axes."""
    tc = dict(conf["transformer_config"])
    tc["dtype"], tc["param_dtype"] = jnp.bfloat16, jnp.float32
    cfg = tfm.TransformerConfig(**tc)
    assert tfm._size(tfm.MIXERS["gdn"].shapes(cfg)) == 33_718_464
    assert tfm._size(tfm.MIXERS["attn"].shapes(cfg)) == 27_263_488
    assert tfm._size(tfm._ffn_shapes(cfg, "moe")) == (
        4_196_352 + 32 * 3_145_728)
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
