"""The mixed-head window / full attention expert stack (models/transformer.py:
a `swa` kind with its own query heads, theta and rotated share beside the
`attn` kind's YaRN over half a head, a sigmoid gate a head on every attention
output, a dense lead layer, sigmoid-routed experts beside a shared one) on
the CPU at the tiny preset: the program against the plain reference
(chipbench/reference/laguna.py: nothing from ray_tpu, full softmax rows, a
loop over the held experts) on seeded weights, each mechanism got wrong one
way, a chip's share of the heads against the whole layer, mellum2's rotary
angles against the parent's bits, the observation a layer publishes, the
counts and the configuration file. What it shares with the other families is
tests/test_model_table.py (plan, lowering, decoding), test_preset_programs.py
(the train step, the flash path) and test_expert_shares.py (the experts'
shares)."""
import contextlib
import dataclasses
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
from ray_tpu.models.configs import laguna_tiny
from ray_tpu.models.generate import prefill

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

pytestmark = pytest.mark.usefixtures("exact_matmuls")

CONFIG = os.path.join(ROOT, "chipbench", "configs", "laguna_s_2_1.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FIRST = ("final_norm", "full_wq", "full_wo", "full_gate", "swa_wq", "swa_wkv",
         "swa_wo", "swa_gate", "w_down")
ROUTED = ("expert_down", "router")
WRONG = ("no_gate", "gate_on_the_query", "gate_from_the_raw_residual",
         "sliding_at_the_full_theta", "yarn_on_a_sliding_layer",
         "a_whole_head_rotated_on_a_full_layer", "ramp_over_the_whole_head",
         "factor_on_the_scores_too", "window_less_one", "window_plus_one",
         "sliding_heads_in_the_full_groups", "no_routed_scale",
         "no_shared_expert", "top_k_of_unbiased_scores")
# What `_rope` gave at the parent commit of PR 45 (ce1be74) for mellum2's
# cell (heads of 128, theta 5e5, YaRN x 16 from 8,192; positions 0..16,383,
# x = normal(key(0), [1,16384,2,128])): sha256[:16] of the rotated array
# under YaRN, plain, and of `yarn_ramp`'s 64 values.
PARENT_ANGLES = ("1b993d52007ca959", "364ef7d9069c2e45", "32e97a09595f46da")


def _wrong_attn_mixer(wrong, seen, cfg, kind, h, layer, positions, overlap):
    """`tfm._attn_mixer` written out with one thing got wrong."""
    mixer = kind[0]
    B, S, _ = h.shape
    q, k, v = tfm._qkv_proj(cfg, h, layer, positions, mixer, overlap)
    source = seen["x"] if wrong == "gate_from_the_raw_residual" else h
    gate = jax.nn.sigmoid(jnp.einsum(
        "bsd,dn->bsn", source, tfm._w(layer, "w_head_gate", cfg),
        preferred_element_type=jnp.float32))[..., None].astype(q.dtype)
    scale = cfg.head_dim ** -0.5
    if wrong == "factor_on_the_scores_too" and mixer == "attn":
        scale = scale * cfg.yarn_attn_factor
    if wrong == "gate_on_the_query":
        q = q * gate
    if wrong == "sliding_heads_in_the_full_groups" and mixer == "swa":
        group = cfg.attn_heads("attn") // cfg.kv_heads
        serve = (jnp.arange(cfg.attn_heads("swa")) // group) % cfg.kv_heads
        k, v = k[:, :, serve], v[:, :, serve]
    o = tfm.attention(q, k, v, causal=True, scale=scale,
                      window=cfg.sliding_window if mixer == "swa" else None)
    if wrong != "gate_on_the_query":
        o = o * gate
    return o.reshape(B, S, -1) @ tfm._w(layer, "wo", cfg), k, v


@contextlib.contextmanager
def wrong(kind: str, cfg, params):
    """-> (the configuration, the parameters) to run with one mechanism got
    wrong: a configuration field where the mechanism is one, a leaf zeroed
    where leaving a term out is that, else the `attn` and `swa` rows of
    `tfm.MIXERS` replaced or `yarn_ramp` / `attn_rope` patched (no option of
    the program): the chip run at the timed sizes (PERF.md section 6) uses
    the same."""
    fields = {
        "no_gate": dict(attn_head_gate=False),
        "sliding_at_the_full_theta": dict(swa_rope_theta=None),
        "a_whole_head_rotated_on_a_full_layer": dict(rope_fraction=1.0),
        "window_less_one": dict(sliding_window=cfg.sliding_window - 1),
        "window_plus_one": dict(sliding_window=cfg.sliding_window + 1),
        "no_routed_scale": dict(moe_routed_scale=1.0)}
    zeroed = {"no_shared_expert": "shared_w_down",
              "top_k_of_unbiased_scores": "router_bias"}
    if kind in fields:
        yield dataclasses.replace(cfg, **fields[kind]), params
    elif kind in zeroed:
        yield cfg, dict(params, layers=[
            [{n: jnp.zeros_like(a) if n == zeroed[kind] else a
              for n, a in pos.items()} for pos in seg]
            for seg in params["layers"]])
    elif kind == "yarn_on_a_sliding_layer":
        rope = tfm.TransformerConfig.attn_rope
        with mock.patch.object(
                tfm.TransformerConfig, "attn_rope",
                lambda self, m: rope(self, m)[:2] + (self.rope_yarn,)):
            yield cfg, params
    elif kind == "ramp_over_the_whole_head":
        ramp = tfm.yarn_ramp
        with mock.patch.object(
                tfm, "yarn_ramp",
                lambda half, *a: ramp(cfg.head_dim // 2, *a)[:half]):
            yield cfg, params
    else:
        seen, norm = {}, tfm._norm

        def spy(x, *a, **k):  # `_layer_body` norms x, then calls the mixer
            seen["x"] = x
            return norm(x, *a, **k)

        rows = {n: dataclasses.replace(tfm.MIXERS[n], apply=(
            lambda *a: _wrong_attn_mixer(kind, seen, *a)))
            for n in ("attn", "swa")}
        with mock.patch.dict(tfm.MIXERS, rows), \
                mock.patch.object(tfm, "_norm", spy):
            yield cfg, params


def _sizes(cfg, heads=None, **changes):
    from chipbench import weights_laguna as W

    tc = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return W.LagunaSizes(dict(tc, **changes), cfg.norm_eps, heads)


def _program(cfg, sz, params, toks):
    """(loss, compared gradient leaves) of the program."""
    from chipbench import weights_laguna as W

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
    selection bias is drawn at ten times the cell's scale here (N(0, 0.1)):
    among 16 experts a top-3 of unbiased scores then differs on enough of
    the 96 tokens to show."""
    from chipbench import weights_laguna as W
    from chipbench.reference import laguna as ref

    cfg = laguna_tiny(dtype=jnp.float32)
    sz, key = _sizes(cfg), jax.random.key(21)
    toks = jax.random.randint(jax.random.key(22), (2, 49), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"), \
            mock.patch.object(W, "BIAS_STD", 0.1):
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
    # the two kinds' leaves differ in shape: 4 query heads and 6
    full, swa = (tfm.layer_params(case["params"], case["cfg"], l)
                 for l in (4, 1))
    assert (full["wq"].shape, swa["wq"].shape) == ((64, 4, 16), (64, 6, 16))
    assert (full["w_head_gate"].shape, swa["w_head_gate"].shape) == (
        (64, 4), (64, 6))
    assert full["wkv"].shape == swa["wkv"].shape == (64, 2, 2, 16)


@pytest.mark.parametrize("leaf", FIRST + ROUTED)
def test_gradient_leaf_matches_the_reference(case, conf, leaf):
    """Both groups of the cell's compared leaves: the nine every token
    reaches and the two of the expert layer; the fused leaves' gradients
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
    """Each mechanism made wrong puts the first group's error over the
    cell's limit (the sound program reads 1e-6 here and a few percent in
    bfloat16 on the chip): the gate left out, on the query, or from the raw
    residual; the sliding layers at the full layers' theta or under YaRN; a
    full layer's whole head rotated, its ramp taken over the head's columns
    for the rotated ones, the attention factor on the scores as well; a
    window of one less and one more; a sliding layer's query heads grouped
    six to a key head as a full layer's; the routed scale, the shared expert
    or the selection bias left out."""
    with wrong(kind, case["cfg"], case["params"]) as (cfg, params), \
            jax.default_matmul_precision("highest"):
        _, got = _program(cfg, case["sz"], params, case["toks"])
    want = case["grads"][1]
    worst = max(_rel(got[n], want[n]) for n in FIRST)
    assert worst > conf["limits"]["train_grad_rel_err"], worst


@pytest.mark.parametrize("mixer", ["attn", "swa"])
def test_the_head_shares_add_up(case, mixer):
    """One attention layer of each kind cut two ways over its heads (query
    heads 0-1 | 2-3 of the full layer's 4, 0-2 | 3-5 of the sliding layer's
    6, key head 0 | 1: whole groups): a chip's weights made from the seed by
    the benchmark's maker are the whole layer's for its heads, its partial
    `W_o` sum by the reference is what the program's mixer gives on the
    chip's numbers of heads, and the two chips' sums add up to the uncut
    reference's attention output."""
    from chipbench import weights_laguna as W
    from chipbench.reference import laguna as ref
    from chipbench.weights import layer_key

    cfg, kind = case["cfg"], (mixer, "moe")
    key = layer_key(jax.random.key(31), 1)
    h = jax.random.normal(jax.random.key(32), (2, 40, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32)[None], (2, 40))
    whole = _sizes(cfg)
    w_all = W.layer(key, whole, kind)
    want = ref._attention(h, w_all, whole, mixer, ref.mm_f32)
    H, hd = whole.H[mixer], whole.hd
    half = dataclasses.replace(cfg, n_heads=2, swa_heads=3, n_kv_heads=1)
    total = 0.0
    for rank in (0, 1):
        sz = _sizes(half, heads={"rank": rank, "ways": 2})
        assert sz.H[mixer] * 2 == H and sz.KVH * 2 == whole.KVH
        w = W.layer(key, sz, kind)
        mine = slice(rank * H // 2 * hd, (rank + 1) * H // 2 * hd)
        np.testing.assert_array_equal(w["wq"], w_all["wq"][:, mine])
        np.testing.assert_array_equal(w["wo"], w_all["wo"][mine])
        np.testing.assert_array_equal(
            w["wg"], w_all["wg"][:, rank * H // 2:(rank + 1) * H // 2])
        np.testing.assert_array_equal(
            w["wk"], w_all["wk"][:, rank * hd:(rank + 1) * hd])
        part = ref._attention(h, w, sz, mixer, ref.mm_f32)
        got = tfm.MIXERS[mixer].apply(half, kind, h, W.to_program(w, sz, kind),
                                      pos, None)[0]
        np.testing.assert_allclose(got, part, atol=2e-5)
        total = total + part
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.max(jnp.abs(total - part))) > 1e-2  # a half is no whole


def test_defaults_spelled_out_trace_nothing_and_each_property_counts(case):
    """A configuration that spells the new fields' defaults out (the `attn`
    layers' head count, theta and rotated share on the `swa` layers, no gate
    a head) has the jaxpr of one that does not; and each property alone
    moves the tiny preset's logits."""
    base = configs.mellum2_tiny(dtype=jnp.float32)
    spelled = dataclasses.replace(
        base, swa_heads=base.n_heads, swa_rope_theta=base.rope_theta,
        swa_rope_fraction=base.rope_fraction, attn_head_gate=False)
    params = tfm.init_params(jax.random.key(0), base)
    toks = case["toks"][:, :24]
    jaxpr = lambda cfg: str(jax.make_jaxpr(
        lambda: tfm.forward(params, toks, cfg))())
    assert jaxpr(spelled) == jaxpr(base)
    cfg, (_, want) = case["cfg"], case["logits"]
    for change in (dict(swa_rope_theta=None), dict(swa_rope_fraction=None),
                   dict(rope_fraction=1.0), dict(attn_head_gate=False),
                   dict(yarn_factor=None), dict(moe_routed_scale=1.0)):
        changed = dataclasses.replace(cfg, **change)  # (a program each)
        got = jax.jit(lambda p, t: tfm.forward(p, t, changed))(
            case["params"], case["toks"][:, :-1])
        assert float(jnp.max(jnp.abs(got - want))) > 1e-3, change
    with pytest.raises(ValueError, match="swa_heads"):
        laguna_tiny(swa_heads=5)
    with pytest.raises(ValueError, match="swa_rope_fraction"):
        laguna_tiny(swa_rope_fraction=0.2)
    with pytest.raises(ValueError, match="not both"):
        laguna_tiny(attn_out_gate=True)


def test_mellum2s_angles_are_the_parents():
    """The ramp runs over the rotated columns: at a rotated share of 1.0
    that is the whole head, and `_rope` on mellum2's cell's sizes gives the
    parent's bits, under YaRN and plain. Half a head's ramp is the one of a
    head of that width (low 9, high 18 at the published sizes), not the
    first half of the whole head's."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "mellum2_12b_a2_5b.json")) as f:
        tc = dict(json.load(f)["transformer_config"])
    tc["dtype"], tc["param_dtype"] = jnp.bfloat16, jnp.float32
    cfg = tfm.TransformerConfig(**tc)
    assert cfg.attn_rope("attn") == (5e5, 128, cfg.rope_yarn)
    assert cfg.attn_rope("swa") == (5e5, 128, None)
    x = jax.random.normal(jax.random.key(0), (1, 16384, 2, 128), jnp.float32)
    pos = jnp.arange(16384, dtype=jnp.int32)[None]
    digest = lambda a: hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]
    ramp = tfm.yarn_ramp(64, 5e5, *cfg.rope_yarn[1:4])
    assert (digest(tfm._rope(x, pos, 5e5, cfg.rope_yarn)),
            digest(tfm._rope(x, pos, 5e5)), digest(ramp)) == PARENT_ANGLES
    np.testing.assert_array_equal(
        tfm._rope_first(x, 128, pos, 5e5, cfg.rope_yarn),
        tfm._rope(x, pos, 5e5, cfg.rope_yarn))
    # Laguna's full layers: 64 rotated columns, theta 5e5, from 8,192
    half = tfm.yarn_ramp(32, 5e5, 8192, 32.0, 1.0)
    want = np.clip((np.arange(32) - 9) / 9.0, 0, 1).astype(np.float32)
    np.testing.assert_array_equal(half, want)
    assert not np.array_equal(half, ramp[:32])


def test_scopes_and_the_plan_a_layer_publishes(case):
    """Both kinds of layer open `gattn` (a gate is on) and their products,
    rotations and gates run under `gattn.gate`; a sliding layer's attention
    stays under `swa` inside it. One `attn.plan` observation a traced layer
    body says what the layer is; a stack without a gate opens no `gattn`."""
    from ray_tpu.util import tracing

    cfg = case["cfg"]
    before = tracing.phase_table().get("attn.plan", {"count": 0})["count"]
    with mock.patch.object(tracing, "observe", wraps=tracing.observe) as spy:
        text = jax.jit(lambda p, t: tfm.forward(p, t, cfg)).lower(
            case["params"], case["toks"][:, :-1]).as_text(debug_info=True)
        seen = [c.kwargs for c in spy.call_args_list
                if c.args[0] == "attn.plan"]
    assert seen == [  # one a segment's body: the plan has three
        dict(slow=False, kind="attn", heads=4, kv_heads=2, window=0,
             rotated=8, theta=1e4, yarn=16.0, gate="head"),
        dict(slow=False, kind="swa", heads=6, kv_heads=2, window=8,
             rotated=16, theta=100.0, yarn=0, gate="head"),
        dict(slow=False, kind="attn", heads=4, kv_heads=2, window=0,
             rotated=8, theta=1e4, yarn=16.0, gate="head")]
    assert tracing.phase_table()["attn.plan"]["count"] == before + 3
    for scope in ("gattn/gattn.gate", "gattn/swa", "moe.route",
                  "moe.experts"):
        assert re.search(rf'["/]{re.escape(scope)}/', text), scope
    assert not re.search(r'gattn\.gate/[^"]*swa/', text)
    plain = configs.mellum2_tiny(dtype=jnp.float32)
    p = tfm.init_params(jax.random.key(0), plain)
    with mock.patch.object(tracing, "observe", wraps=tracing.observe) as spy:
        other = jax.jit(lambda p, t: tfm.forward(p, t, plain)).lower(
            p, case["toks"][:, :-1]).as_text(debug_info=True)
        gates = {c.kwargs["gate"] for c in spy.call_args_list
                 if c.args[0] == "attn.plan"}
    assert "gattn" not in other and '"swa/' in other and gates == {"none"}


def test_decoding_refuses_the_gate_a_head_in_words(case):
    cfg = configs.llama_tiny(attn_head_gate=True)
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    with pytest.raises(NotImplementedError,
                       match="attn_head_gate.*a column or a head"):
        prefill(params, jnp.zeros((2, 8), jnp.int32), cfg, 16)
    with pytest.raises(NotImplementedError, match="windowed"):
        prefill(case["params"], jnp.zeros((2, 8), jnp.int32), case["cfg"],
                16)


def test_counts_and_the_configuration_file(conf):
    """num_params of the cut is ISSUE 45's 672,125,952 and the four
    selection biases' 1,024; the bytes the file states are that times 16;
    what the weights module builds has as many; the file keeps every key of
    the catalog's `config` but those in `reduced`, and the whole model by
    the same keys is 117.6 G."""
    tc = dict(conf["transformer_config"])
    tc["dtype"], tc["param_dtype"] = jnp.bfloat16, jnp.float32
    cfg = tfm.TransformerConfig(**tc)
    assert tfm._size(tfm.MIXERS["attn"].shapes(cfg)) == 22_093_824
    assert tfm._size(tfm.MIXERS["swa"].shapes(cfg)) == 31_567_872
    assert tfm._size(tfm._ffn_shapes(cfg, "dense")) == 113_246_208
    assert tfm._size(tfm._ffn_shapes(cfg, "moe")) == 85_721_088 + 256
    assert cfg.num_params() == 672_125_952 + 4 * 256 == 672_126_976
    assert "672,126,976" in conf["deployment"]
    assert "10.75 GB" in conf["deployment"]
    assert round(cfg.num_params() * 16 / 1e9, 2) == 10.75
    from chipbench import weights_laguna as W

    sz = W.sizes_of(conf, False)
    assert (sz.rank, sz.ways, sz.H, sz.KVH) == (
        0, 2, {"attn": 24, "swa": 36}, 4)
    assert (sz.rot, sz.theta) == ({"attn": 64, "swa": 128},
                                  {"attn": 5e5, "swa": 1e4})
    shapes = jax.eval_shape(lambda k: W.program_params(k, sz, cfg),
                            jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(
        lambda a: a.shape, jax.eval_shape(
            lambda k: tfm.init_params(k, cfg), jax.random.key(0)))
    assert cfg.stack_plan() == (((("attn", "dense"),), 1),
                                ((("swa", "moe"),), 3),
                                ((("attn", "moe"),), 1))
    whole = dataclasses.replace(cfg, n_layers=48, vocab_size=100352,
                                moe_held=None, n_heads=48, swa_heads=72,
                                n_kv_heads=8)
    assert round(whole.num_params() / 1e9, 1) == 117.6
    assert cfg.attn_rope("attn") == (5e5, 64, (128.0, 8192, 32.0, 1.0,
                                               1.4852030263919618))
    assert cfg.attn_rope("swa") == (1e4, 128, None)
    # the catalog's row
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-S-2.1")
    assert conf["source"] == row["source_url"]
    for key, val in row["config"].items():
        if key not in conf["reduced"]:
            assert conf[key] == val, key
    assert conf["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_attention_heads", "num_attention_heads_per_layer",
        "num_key_value_heads"]
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"]) == (5, 8, 12544, 24, 4)
    assert conf["num_attention_heads_per_layer"] == [
        h // 2 for h in row["config"]["num_attention_heads_per_layer"]]
    assert conf["published"] == dict(
        conf["published"], num_hidden_layers=48, num_experts=256,
        vocab_size=100352, num_attention_heads=48, num_key_value_heads=8)
    assert conf["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert tc["swa_layers"] == [
        l + 1 for l, t in enumerate(row["config"]["layer_types"])
        if t == "sliding_attention"]
    assert "32 chips" in conf["deployment"] and conf["assumed"]
    assert sorted(conf["limits"]) == sorted(conf["stack"]["groups"])
    assert set(conf["assumed"]) >= {"gate", "router", "rotation"}
    specs = tfm.param_logical_specs(cfg)["layers"][1][0]
    assert specs["w_head_gate"] == ("layers", "embed", "heads")
    assert specs["wq"] == ("layers", "embed", "heads", None)


def test_hand_count_of_the_cells_operations(conf):
    """reduce/laguna_counts.py at the cell's shape, by hand. A full layer's
    matmul parameters: W_q and W_o 3072 x 3072 each, W_k and W_v 3072 x 512,
    W_g 3072 x 24; a sliding layer's at 36 heads: 3072 x 4608 twice, the same
    W_k and W_v, W_g 3072 x 36. Layer 1 adds 3 x 3072 x 12,288; an expert
    layer the router 786,432, the shared expert and 10 x 8 / 256 of an
    expert: 1.3125 x 9,437,184. The band's pairs at 8,192 x 512: 4,063,488
    (8192 x 512 - 512 x 511 / 2), the triangle's 33,558,528."""
    from chipbench import weights_laguna as W
    from chipbench.reduce import laguna_counts as c

    sz = W.sizes_of(conf, False)
    S = 8192
    full = 2 * 3072 * 3072 + 2 * 3072 * 512 + 3072 * 24
    swa = 2 * 3072 * 4608 + 2 * 3072 * 512 + 3072 * 36
    moe = 786_432 + 1.3125 * 9_437_184
    assert c.layer_matmul_params(sz, ("attn", "dense")) == (
        full + 3 * 3072 * 12288)
    assert c.layer_matmul_params(sz, ("swa", "moe")) == swa + moe
    assert c.layer_matmul_params(sz, ("attn", "moe")) == full + moe
    band, tri = c.band_pairs(S, 512), c.triangle_pairs(S)
    assert (band, tri) == (4_063_488, 33_558_528)
    n = 12544 * 3072 + 2 * full + 3 * swa + 3 * 3072 * 12288 + 4 * moe
    assert c.stack_flops_per_token(sz, S) == pytest.approx(
        6.0 * n + 12.0 * 128 * (2 * 24 * tri + 3 * 36 * band) / S, rel=1e-12)
    f = c.band_flash_fwd(1, 36, 4, S, 128, 512)
    assert f["flops"] == 4.0 * 36 * 128 * band
    assert f["bytes"] == 2.0 * S * 128 * 2 * (36 + 4) + 4.0 * 36 * S
    assert c.band_flash_bwd(1, 36, 4, S, 128, 512)["flops"] == 2.5 * f["flops"]
    g = c.full_flash_fwd(1, 24, 4, S, 128)
    assert g["flops"] == 4.0 * 24 * 128 * tri
    assert g["bytes"] == 2.0 * S * 128 * 2 * (24 + 4) + 4.0 * 24 * S
    assert c.full_flash_bwd(1, 24, 4, S, 128)["flops"] == 2.5 * g["flops"]
    # The program's own count has the norms' parameters at 6 each, S^2 / 2
    # pairs a full layer and the band's pairs as the benchmark has them.
    tc = dict(conf["transformer_config"])
    tc["dtype"], tc["param_dtype"] = jnp.bfloat16, jnp.float32
    cfg = tfm.TransformerConfig(**tc)
    diff = cfg.flops_per_token(S) - c.stack_flops_per_token(sz, S)
    assert diff == pytest.approx(6 * (5 * 2 * 3072 + 3072)
                                 - 2 * 6.0 * 24 * 128, rel=1e-6)
