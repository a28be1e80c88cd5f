"""The sliding-window / full-attention expert stack (models/transformer.py
`swa` mixer, YaRN on the `attn` layers, an explicit head width,
`moe_router="softmax"` with a held range) on the CPU at the tiny preset: the
program against the plain reference (chipbench/reference/mellum2.py: nothing
from ray_tpu, full softmax rows with the band as a mask, a loop over the held
experts) on seeded weights, a window off by one, the rotation's frequencies,
the counts and the configuration file. What it shares with the other families
is tests/test_model_table.py (plan, lowering, decoding),
test_preset_programs.py (the train step, the flash path) and
test_expert_shares.py; the windowed kernels themselves are
tests/test_flash_attention.py."""
import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import mellum2_tiny

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

pytestmark = pytest.mark.usefixtures("exact_matmuls")

CONFIG = os.path.join(ROOT, "chipbench", "configs", "mellum2_12b_a2_5b.json")
ROUTED = ("expert_down", "router")


def _sizes(cfg, **changes):
    from chipbench import weights_mellum2 as W

    tc = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return W.MellumSizes(dict(tc, **changes), cfg.norm_eps)


def _program(cfg, sz, key, toks):
    """(weights, loss, compared gradient leaves) of the program. The
    reference's are the module's `case`: made once, whatever the program is
    made to get wrong."""
    from chipbench import weights_mellum2 as W

    params = W.program_params(key, sz, cfg)
    loss, g = jax.jit(jax.value_and_grad(lambda p: tfm.loss_fn(
        p, {"tokens": toks}, cfg, shift_inputs=True)))(params)
    return params, float(loss), W.program_leaves(cfg, sz, g)


@pytest.fixture(scope="module")
def case():
    """The tiny preset in float32, seeded weights in both layouts' terms,
    and the program's and the reference's logits, loss and gradients."""
    from chipbench.reference import mellum2 as ref

    cfg = mellum2_tiny(dtype=jnp.float32)
    sz, key = _sizes(cfg), jax.random.key(21)
    toks = jax.random.randint(jax.random.key(22), (2, 49), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        params, loss_p, got = _program(cfg, sz, key, toks)
        loss_r, want = jax.jit(lambda k, t: ref.loss_and_grads(k, t, sz))(
            key, toks)
        loss, grads = (loss_p, float(loss_r)), (got, want)
        logits_p = jax.jit(lambda p, t: tfm.forward(p, t, cfg))(
            params, toks[:, :-1])
        logits_r = jax.jit(lambda k, t: ref.forward(k, t, sz))(
            key, toks[:, :-1])
    return dict(cfg=cfg, sz=sz, key=key, params=params, toks=toks, loss=loss,
                logits=(logits_p, logits_r), grads=grads)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_logits_and_loss_match_the_reference(case):
    got, want = case["logits"]
    assert got.shape == (2, 48, 256)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert abs(case["loss"][0] - case["loss"][1]) < 1e-5


@pytest.mark.parametrize("leaf", ["final_norm", "full_wo", "full_wq",
                                  "swa_wkv", "swa_wo", "expert_down",
                                  "router"])
def test_gradient_leaf_matches_the_reference(case, leaf):
    """Both groups of the cell's compared leaves: the five every token
    reaches and the two behind the top-k."""
    got, want = case["grads"]
    assert got[leaf].shape == want[leaf].shape
    assert float(jnp.linalg.norm(want[leaf])) > 0
    assert _rel(got[leaf], want[leaf]) < 2e-5, leaf


@pytest.mark.parametrize("window", [7, 9])
def test_a_window_off_by_one_fails_the_first_limit(case, window):
    """The program at a window of 7 or 9 against the reference at 8: the
    first group's error is over the cell's limit (the sound program reads
    1e-6 here and a few percent in bfloat16 on the chip)."""
    with open(CONFIG) as f:
        conf = json.load(f)
    cfg = dataclasses.replace(case["cfg"], sliding_window=window)
    _, _, got = _program(cfg, case["sz"], case["key"], case["toks"])
    want = case["grads"][1]
    first = conf["stack"]["groups"]["train_grad_rel_err"]
    assert set(first) | set(ROUTED) == set(want)
    worst = max(_rel(got[n], want[n]) for n in first)
    assert worst > conf["limits"]["train_grad_rel_err"], worst
    assert _rel(got["swa_wkv"], want["swa_wkv"]) > 0.05


def test_yarn_frequencies_against_a_hand_table():
    """Heads of 128 at theta 500000, factor 16 from 8,192 positions, betas
    32 and 1: low = floor(128 ln(8192 / (32 x 2 pi)) / (2 ln theta)) = 18,
    high = ceil(128 ln(8192 / (2 pi)) / (2 ln theta)) = 35. Pair 5 (below
    low) keeps theta^(-5/64), pair 40 (above high) is theta^(-40/64) / 16,
    pair 26 (between: r = 8/17) blends them; factor 1 is plain RoPE; cos
    and sin carry the attention factor."""
    from chipbench import weights_mellum2 as W
    from chipbench.reference import mellum2 as ref

    theta = 500000.0
    assert math.floor(128 * math.log(8192 / (32 * 2 * math.pi))
                      / (2 * math.log(theta))) == 18
    assert math.ceil(128 * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(theta))) == 35
    r = tfm.yarn_ramp(64, theta, 8192, 32.0, 1.0)
    assert r[18] == 0.0 and r[35] == 1.0 and r[5] == 0.0 and r[40] == 1.0
    np.testing.assert_allclose(r[26], 8 / 17, rtol=1e-6)
    f = lambda i: theta ** (-i / 64)
    want = {5: f(5), 40: f(40) / 16,
            26: f(26) * (1 - 8 / 17) + f(26) / 16 * (8 / 17)}
    # The reference's table, made from the formulas on its own.
    with open(CONFIG) as fh:
        sz = W.sizes_of(json.load(fh), False)
    table = np.asarray(ref.inv_freq(sz, True))
    plain = np.asarray(ref.inv_freq(sz, False))
    for i, v in want.items():
        np.testing.assert_allclose(table[i], v, rtol=1e-5)
        np.testing.assert_allclose(plain[i], f(i), rtol=1e-5)
    # The program's rotation: q = e_0 + e_64 of pair 0... every pair at
    # once: x1 = 1, x2 = 0 gives (cos, sin) of the pair's angle.
    x = jnp.concatenate([jnp.ones((1, 4, 1, 64)), jnp.zeros((1, 4, 1, 64))],
                        -1)
    pos = jnp.asarray([[0, 1, 100, 16383]], jnp.int32)
    yarn = (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    out = tfm._rope(x, pos, theta, yarn)[0, :, 0]          # [4, 128]
    ang = np.asarray(pos[0], np.float64)[:, None] * np.float64(table)[None]
    np.testing.assert_allclose(out[:, :64], 1.2772588722239782 * np.cos(ang),
                               atol=2e-3)  # float32 angles up to 16,383
    np.testing.assert_allclose(out[:, 64:], 1.2772588722239782 * np.sin(ang),
                               atol=2e-3)
    np.testing.assert_allclose(
        tfm._rope(x, pos, theta, (1.0, 8192, 32.0, 1.0, 1.0)),
        tfm._rope(x, pos, theta), atol=1e-4)  # f (1 - r) + f r rounds
    jaxpr = lambda **kw: str(jax.make_jaxpr(
        lambda x: tfm._rope(x, pos, theta, **kw))(x))
    assert jaxpr() == jaxpr(yarn=None)  # nothing traced without it


def test_only_the_full_layers_take_yarn(case):
    """Taking the scaling away changes the output; the windowed layers'
    rotation does not depend on it (a stack of windowed layers alone is the
    same with and without)."""
    toks = case["toks"][:, :-1]
    f = lambda cfg, p=case["params"]: tfm.forward(p, toks, cfg)
    plain = dataclasses.replace(case["cfg"], yarn_factor=None)
    assert float(jnp.max(jnp.abs(f(plain) - f(case["cfg"])))) > 1e-3
    swa = dataclasses.replace(case["cfg"], n_layers=3)
    p3 = dict(case["params"], layers=case["params"]["layers"][:1])
    np.testing.assert_allclose(
        f(swa, p3), f(dataclasses.replace(swa, yarn_factor=None), p3),
        atol=1e-6)


def test_counts_and_the_configuration_file():
    """num_params of the cut is 595,153,152 (ISSUE 33's table) and of the
    whole model 12.15 G; the file keeps every published width, the window
    and the router's 64 outputs and 8 a token; a windowed layer counts its
    band; the specs put heads and experts on their axes."""
    with open(CONFIG) as f:
        conf = json.load(f)
    tc = dict(conf["transformer_config"])
    tc["dtype"], tc["param_dtype"] = jnp.bfloat16, jnp.float32
    cfg = tfm.TransformerConfig(**tc)
    assert cfg.head_dim == 128 != cfg.d_model // cfg.n_heads
    assert tfm._size(tfm.MIXERS["attn"].shapes(cfg)) == tfm._size(
        tfm.MIXERS["swa"].shapes(cfg)) == 21_233_664
    assert tfm._size(tfm._ffn_shapes(cfg, "moe")) == 99_090_432 + 147_456
    assert cfg.num_params() == 595_153_152
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.key(0))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(shapes)) == 595_153_152
    assert "router_bias" not in shapes["layers"][0][0]
    whole = dataclasses.replace(cfg, n_layers=28, vocab_size=98304,
                                moe_held=None)
    assert round(whole.num_params() / 1e9, 2) == 12.15
    assert [(len(p), r) for p, r in whole.stack_plan()] == [(4, 7)]
    # Of the held experts a token touches k x held / E = 2 under even
    # routing: 2 x 6,193,152 of the 99 M.
    assert cfg.num_params() - cfg.num_active_params() == 4 * 14 * 6_193_152
    from chipbench import weights_mellum2 as W
    from chipbench.reduce import mellum2_counts as counts

    sz = W.sizes_of(conf, False)
    S = 16384
    # The program counts the norms' parameters as 6 each too; the
    # benchmark's count does not; both count a windowed layer's band.
    f = counts.stack_flops_per_token(sz, S)
    diff = cfg.flops_per_token(S) - f
    assert abs(diff - (6 * (4 * 2 * 2304 + 2304)
                       - 12 * 32 * 128 * 0.5)) < 1e-3 * S, diff
    band = 3 * counts.band_pairs(S, 1024) + counts.triangle_pairs(S)
    assert 12 * 32 * 128 * band / S < 0.4 * 12 * 32 * 128 * 4 * (S + 1) / 2
    for key, val in {
            "hidden_size": 2304, "head_dim": 128, "num_attention_heads": 32,
            "num_key_value_heads": 4, "intermediate_size": 7168,
            "moe_intermediate_size": 896, "num_experts_per_tok": 8,
            "norm_topk_prob": True, "sliding_window": 1024,
            "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
            "max_position_embeddings": 131072, "model_type": "mellum",
    }.items():
        assert conf[key] == val, key
    assert len(conf["layer_types"]) == len(conf["mlp_layer_types"]) == 28
    assert [i + 1 for i, t in enumerate(conf["layer_types"])
            if t == "sliding_attention"] == list(cfg.swa_layers)
    ya = conf["rope_parameters"]["full_attention"]
    assert cfg.rope_yarn == (ya["factor"], 8192, ya["beta_fast"],
                             ya["beta_slow"], ya["attention_factor"])
    assert conf["rope_parameters"]["sliding_attention"]["rope_theta"] == (
        ya["rope_theta"]) == cfg.rope_theta
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (4, 16, 24576)
    assert conf["published"] == dict(conf["published"], num_hidden_layers=28,
                                     num_experts=64, vocab_size=98304)
    assert "595,153,152" in conf["deployment"] and conf["assumed"]
    assert (tc["moe_num_experts"], tc["moe_experts_per_token"],
            tc["moe_held"], tc["moe_d_ff"]) == (64, 8, [16, 16], 896)
    specs = tfm.param_logical_specs(cfg)["layers"][0][0]
    assert specs["wq"] == ("layers", "embed", "heads", None)
    assert specs["wkv"] == ("layers", "embed", None, "kv_heads", None)
    assert specs["moe_w_down"] == ("layers", "expert", "mlp", "embed")


def test_hand_count_of_the_kernels_operations():
    """reduce/mellum2_counts.py at one shape, by hand: B 1, 32 / 4 heads of
    128, 16,384 positions, window 1,024. Pairs: 16384 x 1024 - 1024 x 1023
    / 2 = 16,253,440. Forward 4 x 32 x 128 a pair = 16,384: 266,296,360,960
    operations; bytes: Q and O 2 x 16384 x 32 x 128 x 2 and K and V 2 x
    16384 x 4 x 128 x 2 = 301,989,888, + float32 statistics 2,097,152."""
    from chipbench.reduce import mellum2_counts as c

    assert c.band_pairs(16384, 1024) == 16_253_440
    assert c.band_pairs(512, 4096) == c.triangle_pairs(512) == 512 * 513 / 2
    fwd = c.swa_flash_fwd(1, 32, 4, 16384, 128, 1024)
    assert fwd == {"flops": 266_296_360_960.0,
                   "bytes": 301_989_888 + 2_097_152}
    bwd = c.swa_flash_bwd(1, 32, 4, 16384, 128, 1024)
    assert bwd["flops"] == 2.5 * fwd["flops"]
    assert bwd["bytes"] == 2 * 301_989_888 + 2_097_152
    full = c.full_flash_fwd(1, 32, 4, 16384, 128)
    assert full["flops"] == 16384 * 16384 * 16385 / 2 and (
        full["bytes"] == fwd["bytes"])
    assert round(fwd["flops"] / full["flops"], 4) == 0.1211
    # A worked row: gate, up, down = 3 x 2304 x 896 multiply-adds, x 6.
    e = c.experts(32768, 16, 2304, 896)
    assert e["flops"] == 32768 * 6 * 3 * 2304 * 896 == 1_217_623_228_416
