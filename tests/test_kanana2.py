"""The all-latent-attention expert stack (models/transformer.py `mla` mixer
under `positional="rope"`: a decoupled rotary part on every query head and
on the shared key part; `moe_router="sigmoid"` with two shared experts and a
held range) on the CPU at the tiny preset: the program against the plain
reference (chipbench/reference/kanana2.py: nothing from ray_tpu, the rotation
on the published interleaved layout, full softmax rows, a loop over the held
experts) on seeded weights, five ways to get the rotation wrong, the layout
turn, the counts and the configuration file. What it shares with the other
families is tests/test_model_table.py (plan, lowering, decoding),
test_preset_programs.py (the train step, the flash path) and
test_expert_shares.py."""
import contextlib
import dataclasses
import functools
import json
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import kanana2_tiny

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

pytestmark = pytest.mark.usefixtures("exact_matmuls")

CONFIG = os.path.join(ROOT, "chipbench", "configs", "kanana_2_30b_a3b.json")
CATALOG = {  # the catalog's `config` of kanana-2-30b-a3b-instruct-2601
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256}
LEAVES = ("final_norm", "mla_wo", "mla_wq", "mla_wkva", "mla_wkvb", "w_down",
          "expert_down", "router")
WRONG = ("no_rotation", "halves_on_interleaved", "key_off_by_one",
         "bf16_angles", "scale_128")


def _bf16_rope(x, positions, theta, yarn=None):
    """`tfm._rope` with its frequencies and angles in bfloat16."""
    half = x.shape[-1] // 2
    bf = jnp.bfloat16
    freqs = (1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                              / half))).astype(bf)
    angles = (positions[:, :, None].astype(bf) * freqs[None, None, :])
    cos = jnp.cos(angles).astype(jnp.float32)[:, :, None, :]
    sin = jnp.sin(angles).astype(jnp.float32)[:, :, None, :]
    x1, x2 = (x[..., :half].astype(jnp.float32),
              x[..., half:].astype(jnp.float32))
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


@contextlib.contextmanager
def wrong_rotation(kind: str, nope: int, shift: int = 0):
    """The program with its latent layers' rotation got wrong in one of five
    ways, by patches on models/transformer.py and the weight maker (no
    option of the program): the chip run at the timed sizes
    (PERF.md section 6) uses the same patches. `shift` moves the positions
    of the bfloat16 angles: an angle's rounding grows with the position, and
    a sound rotation does not see a shift, so 48 positions that end at
    16,383 show here what the cell's last tokens see."""
    from chipbench import weights_kanana2 as W

    rope, attention = tfm._rope, tfm.attention
    patches = {
        "no_rotation": (tfm, "_rope", lambda x, *a, **k: x),
        # The published (interleaved) columns handed to a program that
        # rotates halves: the turn left out.
        "halves_on_interleaved": (W, "turn", lambda x, back=False: x),
        # The shared key part ([B,S,1,rope]) one position late.
        "key_off_by_one": (tfm, "_rope", lambda x, pos, *a, **k: rope(
            x, pos + (x.shape[2] == 1), *a, **k)),
        "bf16_angles": (tfm, "_rope", lambda x, pos, *a, **k: _bf16_rope(
            x, pos + shift, *a, **k)),
        # Scores over the root of the unrotated width alone.
        "scale_128": (tfm, "attention",
                      functools.partial(attention, scale=nope ** -0.5)),
    }
    with mock.patch.object(*patches[kind]):
        yield


def _sizes(cfg, **changes):
    from chipbench import weights_kanana2 as W

    tc = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return W.KananaSizes(dict(tc, **changes), cfg.norm_eps)


def _program(cfg, sz, key, toks):
    """(weights, loss, compared gradient leaves) of the program. The
    reference's are the module's `case`: made once, whatever the program is
    made to get wrong."""
    from chipbench import weights_kanana2 as W

    params = W.program_params(key, sz, cfg)
    loss, g = jax.jit(jax.value_and_grad(lambda p: tfm.loss_fn(
        p, {"tokens": toks}, cfg, shift_inputs=True)))(params)
    return params, float(loss), W.program_leaves(cfg, sz, g)


@pytest.fixture(scope="module")
def conf():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def case():
    """The tiny preset in float32, seeded weights in both layouts' terms,
    and the program's and the reference's logits, loss and gradients."""
    from chipbench.reference import kanana2 as ref

    cfg = kanana2_tiny(dtype=jnp.float32)
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
    """The program on turned weights is the reference on published ones."""
    got, want = case["logits"]
    assert got.shape == (2, 48, 256)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert abs(case["loss"][0] - case["loss"][1]) < 1e-5


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(case, conf, leaf):
    """Both groups of the cell's compared leaves: the six every token
    reaches and the two behind the top-k; the rotated columns' gradients
    come back in the published layout."""
    groups = conf["stack"]["groups"]
    assert sorted(sum(groups.values(), [])) == sorted(LEAVES)
    got, want = case["grads"]
    assert got[leaf].shape == want[leaf].shape
    assert float(jnp.linalg.norm(want[leaf])) > 0
    assert _rel(got[leaf], want[leaf]) < 2e-5, leaf


@pytest.mark.parametrize("kind", WRONG)
def test_a_wrong_rotation_fails_the_first_limit(case, conf, kind):
    """Each way of getting the rotation wrong puts the first group's error
    over the cell's limit (the sound program reads 1e-6 here and about a
    percent in bfloat16 on the chip)."""
    with wrong_rotation(kind, case["cfg"].qk_nope_head_dim, shift=16384 - 48):
        _, _, got = _program(case["cfg"], case["sz"], case["key"],
                             case["toks"])
    want = case["grads"][1]
    first = conf["stack"]["groups"]["train_grad_rel_err"]
    worst = max(_rel(got[n], want[n]) for n in first)
    assert worst > conf["limits"]["train_grad_rel_err"], worst


def test_the_turn_is_a_permutation_and_is_needed(case):
    """`turn` takes pair i = columns (2i, 2i + 1) to (i, n / 2 + i) and back;
    only the rotated columns move; a program handed the published columns
    unturned is another model."""
    from chipbench import weights_kanana2 as W
    from chipbench.weights import layer_key

    x = jnp.arange(8.0)
    np.testing.assert_array_equal(W.turn(x), [0, 2, 4, 6, 1, 3, 5, 7])
    np.testing.assert_array_equal(W.turn(W.turn(x), back=True), x)
    sz = case["sz"]
    w = W.layer(layer_key(case["key"], 0), sz, ("mla", "dense"))
    p = W.to_program(w, sz, ("mla", "dense"))
    q = w["wq"].reshape(sz.d, sz.H, -1)
    np.testing.assert_array_equal(p["mla_wq"][..., :sz.nope], q[..., :sz.nope])
    np.testing.assert_array_equal(p["mla_wq"][..., sz.nope:sz.nope + 4],
                                  q[..., sz.nope::2][..., :4])
    np.testing.assert_array_equal(p["mla_wkva"][:, :sz.lat],
                                  w["wkva"][:, :sz.lat])
    np.testing.assert_array_equal(p["mla_wkva"][:, sz.lat + sz.rope // 2],
                                  w["wkva"][:, sz.lat + 1])
    with wrong_rotation("halves_on_interleaved", sz.nope):
        unturned = W.program_params(case["key"], sz, case["cfg"])
    got = tfm.forward(unturned, case["toks"][:, :-1], case["cfg"])
    assert float(jnp.max(jnp.abs(got - case["logits"][1]))) > 1e-2


def test_rotated_scores_depend_on_the_distance_alone(case):
    """All positions shifted by a constant: the mixer's output is the same
    (a rotation by p on the query and on the key is one by i - j on their
    product), and it is not the unrotated mixer's. The rotation sits under
    `mla.rope`, and the key part is rotated once, on [B,S,1,rope]."""
    cfg = case["cfg"]
    layer = tfm.layer_params(case["params"], cfg, 1)
    h = jax.random.normal(jax.random.key(5), (2, 48, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(48, dtype=jnp.int32)[None], (2, 48))
    mla = lambda cfg, h, pos: tfm.MIXERS["mla"].apply(
        cfg, ("mla", "moe"), h, layer, pos, None)[0]
    out = mla(cfg, h, pos)
    np.testing.assert_allclose(mla(cfg, h, pos + 1000), out, atol=2e-5)
    nope_cfg = dataclasses.replace(cfg, positional="none")
    assert float(jnp.max(jnp.abs(mla(nope_cfg, h, pos) - out))) > 1e-3
    seen = []
    rope = tfm._rope
    with mock.patch.object(tfm, "_rope", lambda x, *a, **k: (
            seen.append(x.shape), rope(x, *a, **k))[1]):
        text = jax.jit(lambda h: mla(cfg, h, pos)).lower(h).as_text(
            debug_info=True)
    assert sorted(seen) == [(2, 48, 1, 8), (2, 48, 4, 8)]
    assert "mla.rope" in text


def test_counts_and_the_configuration_file(conf):
    """num_params of the cut is 575,955,968 (ISSUE 39's table) and of the
    whole model 30.67 G; the file keeps every key of the catalog's `config`
    but the three in `reduced`; the specs put heads and experts on their
    axes; the rotation has no parameter and no counted operation."""
    tc = dict(conf["transformer_config"])
    tc["dtype"], tc["param_dtype"] = jnp.bfloat16, jnp.float32
    cfg = tfm.TransformerConfig(**tc)
    assert tfm._size(tfm.MIXERS["mla"].shapes(cfg)) == 26_345_984
    assert tfm._size(tfm._ffn_shapes(cfg, "dense")) == 37_748_736
    assert tfm._size(tfm._ffn_shapes(cfg, "moe")) == 85_196_928
    assert cfg.num_params() == 575_955_968
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.key(0))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(shapes)) == 575_955_968
    assert cfg.stack_plan() == (((("mla", "dense"),), 1),
                                ((("mla", "moe"),), 4))
    whole = dataclasses.replace(cfg, n_layers=48, vocab_size=128256,
                                moe_held=None)
    assert round(whole.num_params() / 1e9, 2) == 30.67
    # Of the held experts a token touches k x held / E = 0.75 under even
    # routing: 0.75 x 4,718,592 of the 75 M a layer.
    assert cfg.num_params() - cfg.num_active_params() == 4 * (
        15.25 * 4_718_592 + 128)
    nope = dataclasses.replace(cfg, positional="none")
    assert nope.num_params() == cfg.num_params()
    assert nope.flops_per_token(16384) == cfg.flops_per_token(16384)
    from chipbench import weights_kanana2 as W
    from chipbench.reduce import kanana2_counts as counts

    sz = W.sizes_of(conf, False)
    S = 16384
    # The program counts the norms' parameters (and the latent norm's) as 6
    # each too and the triangle as S^2 / 2; the benchmark's count has no
    # norm and S (S + 1) / 2 pairs.
    diff = cfg.flops_per_token(S) - counts.stack_flops_per_token(sz, S)
    assert abs(diff - (6 * (5 * (2 * 2048 + 512) + 2048)
                       - 5 * 3 * 32 * 320)) < 1e-3 * S, diff
    for key, val in CATALOG.items():
        if key not in conf["reduced"]:
            assert conf[key] == val, key
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert (conf["num_hidden_layers"], conf["n_routed_experts"],
            conf["vocab_size"]) == (5, 16, 16032)
    assert conf["published"] == dict(
        conf["published"], num_hidden_layers=48, n_routed_experts=128,
        vocab_size=128256)
    assert conf["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert "575,955,968" in conf["deployment"] and conf["assumed"]
    assert "8 chips" in conf["deployment"]
    assert (tc["moe_num_experts"], tc["moe_experts_per_token"],
            tc["moe_held"], tc["moe_d_ff"], tc["moe_shared_experts"],
            tc["moe_routed_scale"]) == (128, 6, [48, 16], 768, 2, 2.448)
    assert (tc["positional"], tc["rope_theta"], tc["d_ff"],
            tc["max_seq_len"]) == ("rope", 1e6, 6144, 16384)
    assert tc["mla_layers"] == list(range(1, 49))
    specs = tfm.param_logical_specs(cfg)["layers"][1][0]
    assert specs["mla_wq"] == ("layers", "embed", "heads", None)
    assert specs["moe_w_down"] == ("layers", "expert", "mlp", "embed")


def test_hand_count_of_one_layers_operations(conf):
    """reduce/kanana2_counts.py at the cell's shape, by hand. A latent
    layer's matmul parameters: W_q 2048 x 32 x 192 = 12,582,912, W_kva 2048
    x 576 = 1,179,648, W_kvb 512 x 32 x 256 = 4,194,304, W_o 32 x 128 x
    2048 = 8,388,608: 26,345,472 (the latent norm's 512 are no matmul). An
    expert layer adds the router 262,144, the shared experts 9,437,184 and
    6 x 16 / 128 = 0.75 of an expert of 4,718,592. The kernels: forward
    32 x 16384^2 x 320 operations a call, backward 32 x 16384^2 x 832."""
    from chipbench import weights_kanana2 as W
    from chipbench.reduce import kanana2_counts as c

    sz = W.sizes_of(conf, False)
    assert c.layer_matmul_params(sz, "dense") == 26_345_472 + 37_748_736
    assert c.layer_matmul_params(sz, "moe") == (
        26_345_472 + 262_144 + 9_437_184 + 0.75 * 4_718_592)
    S = 16384
    n = 16032 * 2048 + 64_094_208 + 4 * 39_583_744
    assert c.stack_flops_per_token(sz, S) == (
        6.0 * n + 5 * 3.0 * (S + 1) * 32 * 320)
    fwd = c.flash_fwd(1, 32, S, 192, 128)
    assert fwd["flops"] == 32.0 * S * S * 320 == 2_748_779_069_440
    assert fwd["bytes"] == 2 * S * 32 * 640 + 4 * 32 * S
    bwd = c.flash_bwd(1, 32, S, 192, 128)
    assert bwd["flops"] == 32.0 * S * S * 832 == 2.6 * fwd["flops"]
    # A worked row: gate, up, down = 3 x 2048 x 768 multiply-adds, x 6.
    e = c.experts(12288, 16, 2048, 768)
    assert e["flops"] == 12288 * 6 * 3 * 2048 * 768 == 347_892_350_976
