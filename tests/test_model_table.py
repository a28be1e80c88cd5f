"""The table of mixer kinds (models/transformer.py `MIXERS`) and what every
preset's program is, read from it: each preset's gradient program against the
text it lowered to at the parent commit (THE guard of a change that should
change nothing), its counts against the parent's numbers, its plan, what
decoding refuses of it, its device scopes, and docs/model_layers.md against
the table. What only one family has (its reference, its wrong mechanisms,
its configuration file) is in that family's file."""
import collections
import dataclasses
import functools
import glob
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import configs, transformer as tfm
from ray_tpu.models.generate import _kv_stack, prefill

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.usefixtures("exact_matmuls")

PRESETS = ("llama_tiny", "gpt2_tiny", "moe_tiny", "kimi_linear_tiny",
           "granite_hybrid_tiny", "mellum2_tiny", "kanana2_tiny",
           "qwen3_next_tiny", "laguna_tiny", "ouro_tiny", "keye_vl2_tiny",
           "phi4_flash_tiny", "lfm2_moe_tiny")
REMAT = ("off", "dots", "full")
# "<sha256[:16] of the StableHLO>:<sha256[:16] of its operations' name
# stacks>" of each preset's gradient program, remat off and under either
# policy, as it lowered at the parent commit of PR 44 (154c181, before the
# table; this container's JAX, the CPU, tokens [2, 33], `shift_inputs`,
# float32 matmuls as float32): `_digests` below. The first is the text of
# `jax.jit(value_and_grad(loss_fn)).lower(...).as_text()`; the second is
# over `_scoped_ops` of the text with its locations, which the first drops:
# the device scopes (`kda`, `mla`, `mamba`, `gdn`, `gattn`, `swa`, `*.core`,
# `moe.*`) the per-kind metrics read, each with what runs under it. A change that means
# to alter a program takes the new digests with `python
# tests/test_model_table.py` and says why. PR 46 re-recorded the "full"
# column of the five presets with held experts (kimi_linear, mellum2,
# kanana2, qwen3_next, laguna): "full" keeps `moe.RESIDUAL_NAMES` there (and
# the KDA / SSD kernels' names, which the CPU's XLA bodies do not carry);
# every "off" and "dots" digest and the other presets' "full" are the
# parent's. PR 52 re-recorded the text digests of granite_hybrid and
# qwen3_next: the halves of `mamba_wzx`, `gdn_wqk` and `gdn_wvz` come out of
# their product one after the other (`cbsnh`), so that each tensor lies whole
# as `mixer_conv`'s kernels and the cores read it (on the CPU the convolution
# path is the XLA body, op for op the parent's: kimi_linear's digests, whose
# projections were apart already, did not move); their scope digests and
# every other preset's row are the parent's. PR 59 (`gated_norm`: on the CPU
# the XLA body, each mixer's former expression op for op) moved no digest of
# granite_hybrid or qwen3_next and kimi_linear's text alone (its row). The same
# PR re-recorded the TEXT digests of the six presets with held experts
# (kimi_linear, mellum2, kanana2, qwen3_next, laguna, keye_vl2; scope digests
# unmoved): `moe_ffn_held` names the routing its windows go by (the sorted
# list, the runs' ends, the weights) with the token order, so that a remat
# policy keeps it beside the products' outputs and the backward works the
# rows the forward filled (ops/moe.py; on the chip a recomputed routing
# differed from the forward's at a tie and a layer's expert gradients came
# out wrong, PERF.md section 6, PR 59).
PARENT = {
    "llama_tiny": ("477b60d37afe307a:1204d8d39a7b453e",
                   "fb0a0ec778730463:1204d8d39a7b453e",
                   "24c710c0bd2bd4d3:1204d8d39a7b453e"),
    "gpt2_tiny": ("4f2ae9e07027bc87:076347b6a6ef6975",
                  "4a9578c67d387a25:076347b6a6ef6975",
                  "3ba16a7e98c43668:076347b6a6ef6975"),
    "moe_tiny": ("e7db6dd180684ee6:846b7814e5778ffa",
                 "b1a0b14f2dbc5ccf:846b7814e5778ffa",
                 "1a06e30aefde286e:846b7814e5778ffa"),
    # text re-recorded in PR 59 (beside the routing's names, above: the same
    # operations in another order: the
    # output gate's two low-rank products are an ARGUMENT of `gated_norm`,
    # so they are traced before the norm's lines where `_kda_mixer` wrote
    # them after; the lines of the text, sorted, SSA names erased, are the
    # parent's under "dots" and "full", and under "off" but for the order of
    # one call's residuals; the scope digests unmoved)
    "kimi_linear_tiny": ("9ca3d68216127708:0dec5428f5393512",
                         "9d798a2c59dfb7c8:690c4963985676f7",
                         "4bded50f75fe4ee1:690c4963985676f7"),
    "granite_hybrid_tiny": ("9ed9d48b47d5f78c:1aa0ff837d278860",
                            "67c1cff2033e2b05:9ce01d30262a7b2d",
                            "7276f6af5ccbf46a:9ce01d30262a7b2d"),
    "mellum2_tiny": ("16cca482cb4d30a5:5277c5b9e65d3b63",
                     "5d5695e03fafcb7d:5277c5b9e65d3b63",
                     "856f8c11546a3402:5277c5b9e65d3b63"),
    "kanana2_tiny": ("e18ccae8390ca03d:593d1eba54411321",
                     "595b844a53fbbca8:593d1eba54411321",
                     "de94b9f7f5ca2179:593d1eba54411321"),
    "qwen3_next_tiny": ("7000882c6b79b320:4d7d9eca8c599952",
                        "20afe83c15dcbde8:79df8da7a94dd775",
                        "87dbccbb63dba44c:79df8da7a94dd775"),
    # new in PR 45 (its own tree's: the `swa` kind with its own heads, theta
    # and rotated share, the gate a head); the rows above are the parent's
    "laguna_tiny": ("b158847aa832b0e1:99e87d210fd8ce25",
                    "fb286a5009c7c5fb:99e87d210fd8ce25",
                    "d4f5700cb43107e8:99e87d210fd8ce25"),
    # new in PR 49 (its own tree's: four passes over two layers, the
    # post-norms, a head and an exit gate a pass under `loop.head`); every
    # row above is the parent's
    "ouro_tiny": ("3282a46b53f1b59b:b9265fa8a1143fdc",
                  "2f196b66ea41495d:b9265fa8a1143fdc",
                  "cc6e52c1bdf75295:b9265fa8a1143fdc"),
    # PR 54's own (the "dsa" kind's kernels interpreted); re-recorded in PR
    # 55 (its own tree's: the selection's adaptive search in the text, its
    # two counters under `dsa`; every other preset's program unmoved)
    "keye_vl2_tiny": ("ccc613e59ce2bca9:d02a27497dfe0501",
                      "4e8b7153711fb0aa:d02a27497dfe0501",
                      "0e6b36960d796f29:d02a27497dfe0501"),
    # new in PR 56 (its own tree's: the `mamba1`, `gmu` and `xattn` kinds,
    # differential attention under `diffattn`, what two layers hand on in
    # the stack's carry); every row above is the parent's: a stack without
    # readers carries the residual alone and traces what it always traced
    "phi4_flash_tiny": ("f777c0a4e2142200:bdeabde20eab01bb",
                        "724778c22dc2c088:f7ed437597c6e1d7",
                        "d439e5528bdd388a:f7ed437597c6e1d7"),
    # new in PR 60 (its own tree's: the `shortconv` kind, `shortconv.core`
    # inside it, the XLA body on the CPU); every row above is the parent's
    "lfm2_moe_tiny": ("029b0a8e5e439340:82547843ee104708",
                      "42db50c48a324384:82547843ee104708",
                      "6db2f6b2dfb28e9e:82547843ee104708"),
    # flash's rule set true: the kernels' calls (interpret mode) in the text;
    # re-recorded in PR 47 (its own tree's: one backward kernel where the
    # parent's text held dQ's and dK/dV's; the operations' scopes unmoved)
    "llama_tiny-flash": ("c818439799ab19f7:c7a534cb53ae9dba",
                         "910183df523627d5:c7a534cb53ae9dba",
                         "10557681da416ccb:c7a534cb53ae9dba"),
}
# (num_params, num_active_params, flops_per_token(), flops_per_token(512)) of
# every preset of models/configs.py and of every chipbench/configs/*.json's
# `transformer_config`, as the parent's hand formulas (`_mixer_params`,
# `_ffn_params`) gave them: the leaves' sizes summed give the same.
PARENT_COUNTS = {
    "gpt2_125m": (124356864, 124356864, 798045696.0, 769734144.0),
    "llama3_8b": (8030261248, 8030261248, 51471998976.0, 45432201216.0),
    "llama_tiny": (459392, 459392, 2952960.0, 3542784.0),
    "gpt2_tiny": (476416, 476416, 2956800.0, 3546624.0),
    "bench_350m": (341099520, 341099520, 2197592064.0, 2122094592.0),
    "moe_tiny": (1377920, 788096, 4925184.0, 5515008.0),
    "kimi_linear_tiny": (583280, 288304, 1801504.0, 2016544.0),
    "kanana2_tiny": (239344, 179392, 1100928.0, 1961088.0),
    "granite_hybrid_tiny": (548440, 548440, 3826704.0, 3998736.0),
    "mellum2_tiny": (215616, 141888, 837024.0, 1182852.0),
    "qwen3_next_tiny": (344008, 171976, 1077936.0, 1422000.0),
    "laguna_tiny": (260480, 180544, 1060248.0, 1405635.0),  # PR 45's own
    "ouro_tiny": (115329, 115329, 2571288.0, 3947544.0),  # PR 49's own
    "gpt2_124m.json": (124356864, 124356864, 798045696.0, 769734144.0),
    "granite_4_0_h_micro.json": (772160448, 772160448, 4769113728.0,
                                 4725073536.0),
    "internlm2_1_8b.json": (1889110016, 1889110016, 19861155840.0,
                            10348474368.0),
    "kanana_2_30b_a3b.json": (575955968, 288121344, 4048309248.0,
                              1610370048.0),
    "kimi_linear_48b_a3b.json": (602434432, 383018880, 2337959168.0,
                                 2102029568.0),
    "mellum2_12b_a2_5b.json": (595153152, 248336640, 1699215360.0,
                               1200686592.0),
    "qwen3_next_80b_a3b.json": (625667136, 230878272, 1603307904.0,
                                1213237632.0),
    "laguna_s_2_1.json": (672126976, 381932544, 2444659776.0,  # PR 45's own
                          2121808896.0),
    "ouro_2_6b.json": (612438017, 612438017, 15503818776.0,  # PR 49's own
                       12483919896.0),
    "keye_vl2_tiny": (114592, 74656, 383808.0, 472344.0),  # PR 54's own
    "keye_vl_2_0_30b_a3b.json": (465391104, 182275584, 1653021696.0,
                                 917013504.0),  # PR 54's own
    "phi4_flash_tiny": (271520, 271520, 1729992.0, 2246529.0),  # PR 56's own
    "phi4_mini_flash": (3852562944, 3852562944, 71635590504.0,
                        23317576704.0),
    "phi4_mini_flash_reasoning.json": (697094272, 697094272, 4963714512.0,
                                       4220927232.0),
    "lfm2_moe_tiny": (178872, 123552, 765888.0, 937920.0),  # PR 60's own
    "lfm2_8b_a1b": (8339930560, 1557740288, 18783625728.0, 9384190464.0),
    "lfm2_8b_a1b.json": (507820288, 199538816, 1297896192.0, 1203524352.0),
}


@functools.lru_cache(maxsize=None)
def _lowered(preset, mode="off", impl=None):
    """(sha256[:16] of the text, `_scoped_ops` of the text with locations) of
    a preset's gradient program, remat off or under a policy; lowered once
    a process (`impl`: "flash" where the caller set flash's rule true)."""
    cfg = getattr(configs, preset)(
        remat=mode != "off", remat_policy="dots" if mode == "off" else mode)
    p = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    toks = jax.ShapeDtypeStruct((2, 33), jnp.int32)
    low = jax.jit(lambda p, t: jax.value_and_grad(
        lambda p: tfm.loss_fn(p, {"tokens": t}, cfg, shift_inputs=True))(
            p)).lower(p, toks)
    located = low.as_text(debug_info=True)
    # (third: every component of every location's name, for a scope whose
    # operations are all located relative to a call site, as `gmu`'s are)
    return (hashlib.sha256(low.as_text().encode()).hexdigest()[:16],
            _scoped_ops(located),
            {p for n in re.findall(r'loc\("([^"]+)"', located)
             for p in n.split("/")})


def _scoped_ops(text):
    """{(device scopes, operation)} over the locations of a text lowered with
    `debug_info`: of every `jit(<lambda>)/jvp()/while/body/gdn/gdn.core/dot`
    the scopes a mixer opened (`gdn`, `gdn.core`) and what ran under them,
    the wrappers of transforms and loops dropped; a call of a jitted
    function counts as one operation (its body, traced once and shared, is
    located by whichever call came first in the process)."""
    wrapper = lambda p: "(" in p or p in ("while", "body", "cond", "scan",
                                          "closed_call", "checkpoint")
    out = set()
    for name in re.findall(r'loc\("(jit\(<lambda>\)/[^"]+)"', text):
        parts = name.split("/")[1:]
        calls = [i for i, p in enumerate(parts) if p.startswith("jit(")]
        parts = parts[:calls[0] + 1] if calls else parts
        out.add((tuple(p for p in parts[:-1] if not wrapper(p)), parts[-1]))
    return out


def _digests(preset, mode, impl=None):
    text, ops, _ = _lowered(preset, mode, impl)
    return text + ":" + hashlib.sha256(
        repr(sorted(ops)).encode()).hexdigest()[:16]


@pytest.mark.parametrize("mode", REMAT)
@pytest.mark.parametrize("preset", sorted(PARENT))
def test_lowers_to_the_parents_program(preset, mode, request):
    """Byte for byte, and every operation under the scope it was under."""
    name, _, impl = preset.partition("-")
    if impl:
        request.getfixturevalue("flash_kernels")
    assert _digests(name, mode, impl or None) == PARENT[preset][
        REMAT.index(mode)]


def _file_config(path):
    with open(path) as f:
        tc = dict(json.load(f)["transformer_config"])
    tc["dtype"], tc["param_dtype"] = jnp.bfloat16, jnp.float32
    return tfm.TransformerConfig(**tc)


@pytest.mark.parametrize("name", sorted(PARENT_COUNTS))
def test_counts_are_the_parents(name):
    """The counts from the leaves' shapes are the hand formulas' numbers,
    and what `init_params` makes has as many."""
    cfg = (_file_config(os.path.join(ROOT, "chipbench", "configs", name))
           if name.endswith(".json") else getattr(configs, name)())
    assert (cfg.num_params(), cfg.num_active_params(), cfg.flops_per_token(),
            cfg.flops_per_token(512)) == PARENT_COUNTS[name]
    made = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    assert sum(a.size for a in jax.tree.leaves(made)) == cfg.num_params()


def test_the_lfm2_file_holds_every_published_width():
    """chipbench/configs/lfm2_8b_a1b.json: its `transformer_config` is the
    published model's (`configs.lfm2_8b_a1b`) in every field but the four
    cuts the file lists under `reduced` (depth, the leading dense layers,
    the held experts, the vocabulary slice), the stage's own layer list and
    the cell's length; the catalog's keys stand at the top level, every
    width as published."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "lfm2_8b_a1b.json")) as f:
        file = json.load(f)
    cut = _file_config(os.path.join(ROOT, "chipbench", "configs",
                                    "lfm2_8b_a1b.json"))
    whole = configs.lfm2_8b_a1b(dtype=jnp.bfloat16)
    differ = {f.name for f in dataclasses.fields(cut)
              if getattr(cut, f.name) != getattr(whole, f.name)}
    assert differ == {"n_layers", "moe_first_dense", "moe_held", "vocab_size",
                      "shortconv_layers", "max_seq_len"}
    assert file["reduced"] == ["num_hidden_layers", "num_dense_layers",
                               "num_experts", "vocab_size"]
    assert {k: file[k] for k in file["reduced"]} == {
        "num_hidden_layers": cut.n_layers,
        "num_dense_layers": cut.moe_first_dense,
        "num_experts": cut.moe_held[1], "vocab_size": cut.vocab_size}
    assert {k: file["published"][k] for k in file["reduced"]} == {
        "num_hidden_layers": whole.n_layers,
        "num_dense_layers": whole.moe_first_dense,
        "num_experts": whole.moe_num_experts, "vocab_size": whole.vocab_size}
    assert (file["hidden_size"], file["intermediate_size"],
            file["moe_intermediate_size"], file["num_attention_heads"],
            file["num_key_value_heads"], file["conv_L_cache"],
            file["num_experts_per_tok"], file["norm_eps"],
            file["rope_theta"]) == (
        cut.d_model, cut.ff_dim, cut.moe_ff_dim, cut.n_heads, cut.kv_heads,
        tfm.MIXERS["shortconv"].shapes(cut)["shortconv_conv"][0][0],
        cut.moe_experts_per_token, cut.norm_eps,
        cut.rope_theta) == (2048, 7168, 1792, 32, 8, 3, 4, 1e-5, 1e6)
    assert cut.head_dim == 64 and cut.attn_qk_norm and cut.tie_embeddings
    # the stage is published layers 1-5 (0-based) of the file's own list
    kinds = {"conv": "shortconv", "full_attention": "attn"}
    assert [kinds[t] for t in file["layer_types"]] == [
        m for m, _ in whole.layer_kinds()]
    assert [kinds[t] for t in file["layer_types"][1:6]] == [
        m for m, _ in cut.layer_kinds()]
    assert cut.num_params() == 507820288  # 8.13 GB at 16 bytes


def test_every_configuration_file_and_preset_is_counted():
    files = {os.path.basename(p) for p in glob.glob(
        os.path.join(ROOT, "chipbench", "configs", "*.json"))}
    presets = {n for n, f in vars(configs).items()
               if callable(f) and getattr(f, "__module__", "") ==
               configs.__name__ and not n.startswith("_")}
    assert files | presets == set(PARENT_COUNTS)
    assert set(PRESETS) <= presets


def test_the_table_is_what_the_configuration_lists():
    """One row a kind: the rows' fields are the configuration's `*_layers`
    fields, one row has none (the kind of a layer no list names), every
    row's leaves are its own, and a row that cannot be decoded says why."""
    rows = tfm.MIXERS
    assert list(rows) == [r.name for r in rows.values()] == [
        "attn", "swa", "mla", "kda", "mamba2", "gdn", "dsa", "xattn",
        "mamba1", "gmu", "shortconv"]
    fields = {f.name for f in dataclasses.fields(tfm.TransformerConfig)}
    listed = [r.layers_field for r in rows.values() if r.layers_field]
    assert sorted(listed) == sorted(f for f in fields if f.endswith("_layers")
                                    and f != "n_layers")
    assert [r.name for r in rows.values() if not r.layers_field] == ["attn"]
    cfg = configs.llama_tiny(sliding_window=8)
    # ("swa" and "xattn" hold the "attn" layer's leaves, "xattn" no key /
    # value projection; "dsa" holds them and its own)
    own = lambda r: [n for n in r.shapes(cfg) if r.name != "dsa"
                     or n.startswith("dsa_")]
    leaves = collections.Counter(
        n for r in rows.values() if r.name not in ("swa", "xattn")
        for n in own(r))
    assert max(leaves.values()) == 1
    assert set(rows["attn"].shapes(cfg)) < set(rows["dsa"].shapes(cfg))
    # swa's leaves are attn's until it has a head count of its own
    assert rows["swa"].shapes(cfg) == rows["attn"].shapes(cfg)
    assert set(rows["xattn"].shapes(cfg)) == {"wq", "wo"}
    # what layers hand on: two rows write, two read, by name
    assert {r.name: (r.writes, r.reads) for r in rows.values()
            if r.writes or r.reads} == {
        "attn": (("k", "v"), ()), "mamba1": (("m",), ()),
        "gmu": ((), ("m",)), "xattn": ((), ("k", "v"))}
    wider = configs.llama_tiny(sliding_window=8, swa_heads=8)
    assert (rows["swa"].shapes(wider)["wo"][0], rows["attn"].shapes(wider)[
        "wo"][0]) == ((8 * wider.head_dim, 128), (128, 128))
    assert [r.name for r in rows.values() if r.cut_rows] == ["attn", "swa"]
    assert [r.name for r in rows.values() if not r.no_decode] == ["attn"]
    with pytest.raises(ValueError, match="two of swa_layers, mla_layers, kda"):
        configs.llama_tiny(kda_layers=(1,), mla_layers=(1,))
    with pytest.raises(ValueError, match="swa_layers / mla_layers / kda"):
        configs.moe_tiny(swa_layers=(1,), sliding_window=8)


# What each preset's plan is: `plan` of the preset as it is, `deep` (layers,
# plan) of the published depth, `slot` (layer, its `layer_slot`), whether
# params["layers"] is the list of segments (else one dict of leaves [L, ..]),
# and overrides the configuration refuses.
_a, _s, _m = ("attn", "dense"), ("swa", "moe"), ("mamba2", "dense")
_g, _ga = ("gdn", "moe"), ("attn", "moe")
_ld, _lm = ("mla", "dense"), ("mla", "moe")
_kd, _km = ("kda", "dense"), ("kda", "moe")
_ad, _d = ("attn", "dense"), ("dsa", "moe")
_m1, _sd = ("mamba1", "dense"), ("swa", "dense")
_gm, _x = ("gmu", "dense"), ("xattn", "dense")
_cd, _cm = ("shortconv", "dense"), ("shortconv", "moe")
PLANS = {
    "llama_tiny": dict(plan=(((_a,), 2),), deep=(24, (((_a,), 24),)),
                       slot=(1, (0, 0, 1)), segments=False,
                       refused=[dict(remat_policy="none")]),
    "gpt2_tiny": dict(plan=(((_a,), 2),), deep=(12, (((_a,), 12),)),
                      slot=(0, (0, 0, 0)), segments=False,
                      refused=[dict(norm_offset=1.0)]),
    "moe_tiny": dict(plan=(((_ga,), 2),), deep=(4, (((_ga,), 4),)),
                     slot=(1, (0, 0, 1)), segments=False,
                     refused=[dict(moe_router="top1")]),
    "kimi_linear_tiny": dict(
        plan=(((_kd,), 1), ((_km,), 2), ((_lm,), 1), ((_km,), 1)),
        deep=(27, (((_kd,), 1), ((_km, _km, _lm, _km), 6), ((_km,), 1),
                   ((_lm,), 1))),
        slot=(3, (2, 0, 0)), segments=True,
        refused=[dict(moe_router="softmax_capacity")]),
    "granite_hybrid_tiny": dict(
        plan=(((_m,), 5), ((_a,), 1), ((_m,), 4)),
        deep=(40, (((_m,) * 5 + (_a,) + (_m,) * 4, 4),)),
        slot=(5, (1, 0, 0)), segments=True,
        refused=[dict(kda_layers=(1,))]),
    "mellum2_tiny": dict(
        plan=(((_s,), 3), ((_ga,), 1)), deep=(28, (((_s, _s, _s, _ga), 7),)),
        slot=(3, (1, 0, 0)), segments=True,
        refused=[dict(mla_layers=(1,)), dict(sliding_window=None),
                 dict(moe_router="softmax_capacity"),
                 dict(moe_routed_scale=2.0)]),
    "kanana2_tiny": dict(
        plan=(((_ld,), 1), ((_lm,), 3)), deep=(48, (((_ld,), 1), ((_lm,), 47))),
        slot=(2, (1, 0, 1)), segments=True,
        refused=[dict(yarn_factor=4.0)]),
    "qwen3_next_tiny": dict(
        plan=(((_g,), 3), ((_ga,), 1)), deep=(48, (((_g, _g, _g, _ga), 12),)),
        slot=(3, (1, 0, 0)), segments=True,
        refused=[dict(kda_layers=(1,)), dict(gdn_k_heads=3)]),
    "laguna_tiny": dict(
        plan=(((_ad,), 1), ((_s,), 3), ((_ga,), 1)),
        deep=(48, (((_ad,), 1), ((_s, _s, _s, _ga), 11), ((_s,), 3))),
        slot=(4, (2, 0, 0)), segments=True,
        refused=[dict(swa_heads=5), dict(attn_out_gate=True),
                 dict(swa_rope_fraction=0.2), dict(sliding_window=None)]),
    # the loop is no part of the plan: one segment, run `loop_steps` times
    "ouro_tiny": dict(plan=(((_a,), 2),), deep=(48, (((_a,), 48),)),
                      slot=(1, (0, 0, 1)), segments=False,
                      refused=[dict(loop_steps=1), dict(loop_steps=0),
                               dict(norm="layernorm")]),
    "keye_vl2_tiny": dict(plan=(((_d,), 2),), deep=(48, (((_d,), 48),)),
                          slot=(1, (0, 0, 1)), segments=True,
                          refused=[dict(rope_sections=(2, 3, 4)),
                                   dict(mla_layers=(49,), n_layers=49),
                                   dict(moe_router="softmax_capacity")]),
    # a stage from the middle of the published stack: the lists are the
    # stage's own, so a deeper cut only adds plain layers after it
    "phi4_flash_tiny": dict(
        plan=(((_m1,), 1), ((_sd,), 1), ((_m1,), 1), ((_a,), 1), ((_gm,), 1),
              ((_x,), 1)),
        deep=(8, (((_m1,), 1), ((_sd,), 1), ((_m1,), 1), ((_a,), 1),
                  ((_gm,), 1), ((_x,), 1), ((_a,), 2))),
        slot=(4, (4, 0, 0)), segments=True,
        refused=[dict(n_heads=3), dict(mamba1_layers=()),
                 dict(sliding_window=None), dict(attn_qk_norm=True)]),
    # the cell's cut: published layers 1-5 at four (one convolution expert
    # layer fewer); deeper, the stage's own lists only add plain layers
    "lfm2_moe_tiny": dict(
        plan=(((_cd,), 1), ((_ga,), 1), ((_cm,), 2)),
        deep=(6, (((_cd,), 1), ((_ga,), 1), ((_cm,), 2), ((_ga,), 2))),
        slot=(3, (2, 0, 1)), segments=True,
        refused=[dict(shortconv_layers=(1,), kda_layers=(1,)),
                 dict(moe_router="softmax_capacity")]),
}


@pytest.mark.parametrize("preset", PRESETS)
def test_stack_plan(preset):
    """The cut's segments, the published depth's (a period scanned over its
    repeats: compile time follows the segments, not the depth), where a
    layer lives, how the layers are stored, and what is refused where the
    configuration is made."""
    want, make = PLANS[preset], getattr(configs, preset)
    cfg = make()
    assert cfg.stack_plan() == want["plan"]
    n, deep = want["deep"]
    assert make(n_layers=n).stack_plan() == deep
    assert sum(len(p) * r for p, r in deep) == n
    assert make(n_layers=n).layer_kinds()[:cfg.n_layers] == cfg.layer_kinds()
    l, slot = want["slot"]
    assert cfg.layer_slot(l) == slot
    specs = tfm.param_logical_specs(cfg)
    assert isinstance(specs["layers"], list if want["segments"] else dict)
    segments = tfm.stack_segments(specs, cfg)
    assert [len(seg) for seg in segments] == [len(p) for p, _ in want["plan"]]
    kind = cfg.layer_kinds()[l]
    assert list(segments[slot[0]][slot[1]]) == list(
        tfm._layer_shapes(cfg, kind))
    for override in want["refused"]:
        with pytest.raises(ValueError):
            make(**override)


def test_a_one_layer_stack_of_another_kind_is_a_list_of_segments():
    one = configs.kimi_linear_tiny(n_layers=1)  # one KDA layer, dense
    assert one.stack_plan() == (((("kda", "dense"),), 1),)
    specs = tfm.param_logical_specs(one)
    assert tfm.stack_segments(specs, one) is specs["layers"]
    p = tfm.init_params(jax.random.key(0), configs.llama_tiny())
    assert p["layers"]["wo"].shape == (2, 128, 128)
    assert tfm.layer_params(p, configs.llama_tiny(), 1)["wo"].shape == (
        128, 128)


@pytest.mark.parametrize("preset", PRESETS)
def test_decoding_serves_a_row_or_says_its_sentence(preset):
    """`generate._kv_stack` is a loop over the table: a stack whose rows all
    hold keys and values is served, any other is refused with the sentence
    of its first layer's row that cannot be."""
    cfg = getattr(configs, preset)()
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    toks = jnp.zeros((2, 8), jnp.int32)
    why = [tfm.MIXERS[m].no_decode for m, _ in cfg.layer_kinds()
           if tfm.MIXERS[m].no_decode]
    if cfg.loop_steps > 1:  # every row holds keys and values, ONE slab each
        with pytest.raises(NotImplementedError,
                           match="a cache slab a pass a layer"):
            prefill(params, toks, cfg, 16)
        return
    if not why:
        kind, layers = _kv_stack(params, cfg)
        assert kind == cfg.layer_kinds()[0]
        assert layers["wo"].shape[0] == cfg.n_layers
        return
    with pytest.raises(NotImplementedError, match=re.escape(why[0])):
        prefill(params, toks, cfg, 16)
    word = {"kimi_linear_tiny": "KDA / MLA", "granite_hybrid_tiny": "Mamba-2",
            "mellum2_tiny": "windowed", "kanana2_tiny": "MLA",
            "qwen3_next_tiny": "R7 / R9", "laguna_tiny": "windowed",
            "keye_vl2_tiny": "R22 (a)", "phi4_flash_tiny": "Mamba-1",
            "lfm2_moe_tiny": "taps - 1 = 2 rows"}[preset]
    assert word in why[0]


def test_the_pipeline_refuses_a_looped_stack():
    """`pipeline_loss_fn` visits a stage once a microbatch: a looped stack
    is refused where the loss is built, with what is missing; the post-norms
    alone are the shared layer body's and pass."""
    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.parallel.pipeline import pipeline_loss_fn

    mesh = make_mesh(MeshSpec(pipe=2), devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError,
                       match="a stage visited once a pass"):
        pipeline_loss_fn(configs.ouro_tiny(), mesh)
    pipeline_loss_fn(configs.ouro_tiny(loop_steps=1, exit_gate=False), mesh)


UNAPPLIED = {
    "post_norm": dict(post_norm=True),
    "attn_out_gate": dict(attn_out_gate=True),
    "attn_head_gate": dict(attn_head_gate=True),
    "norm_offset": dict(norm_offset=1.0),
    "logit_scale": dict(logit_scale=8.0),
    "embed_scale": dict(embed_scale=12.0),
    "moe_held": dict(moe_num_experts=4, moe_router="softmax",
                     moe_held=(0, 2), tie_embeddings=False),
}


@pytest.mark.parametrize("field", sorted(UNAPPLIED))
def test_decoding_refuses_what_it_does_not_apply(field):
    cfg = configs.llama_tiny(**UNAPPLIED[field])
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    with pytest.raises(NotImplementedError, match=field):
        prefill(params, jnp.zeros((2, 8), jnp.int32), cfg, 16)


# The device scopes a preset's program must carry, from its rows and from
# where the mixers open their own (`docs/model_layers.md`).
INNER = {"kda": ("kda.core",), "gdn": ("gdn.core",), "mamba2": ("ssd.core",),
         "swa": ("swa",), "mla": (), "attn": (),
         # (`dsa.core` too, but its kernels sit in `custom_vjp` functions
         # that lower apart, their locations relative: tests/test_dsa.py)
         "dsa": ("dsa.index",), "mamba1": ("mamba1.core",), "gmu": (),
         "xattn": (), "shortconv": ("shortconv.core",)}


@pytest.mark.parametrize("preset", PRESETS)
def test_a_presets_program_carries_its_rows_scopes(preset):
    cfg = getattr(configs, preset)()
    under = {s for scopes, _ in _lowered(preset)[1] for s in scopes}
    mixers = {m for m, _ in cfg.layer_kinds()}
    for row in tfm.MIXERS.values():
        scope = row.scope(cfg)
        if row.name in mixers:
            assert scope is None or scope in under | _lowered(preset)[2], scope
            assert set(INNER[row.name]) <= under, row.name
        elif scope is not None and scope not in {
                tfm.MIXERS[m].scope(cfg) for m in mixers}:
            assert scope not in under, scope
    assert ("mla.rope" in under) == ("mla" in mixers and cfg.mla_rotates)
    # (the gate's scopes are the "attn" / "swa" kinds': a "dsa" layer's q / k
    # norms run under `dsa`)
    gated = cfg.attn_gated and bool(mixers & {"attn", "swa"})
    assert ("gattn.gate" in under) == gated == ("gattn" in under)


def test_the_docs_table_is_the_table():
    """docs/model_layers.md's table of kinds names every row, its list's
    field and its scope, in the table's order."""
    with open(os.path.join(ROOT, "docs", "model_layers.md")) as f:
        text = f.read()
    rows = re.findall(r"^\| `(\w+)` \| (?:`(\w+)`|none[^|]*) \| (?:`([\w.]+)`"
                      r"[^|]*|none[^|]*) \|", text, flags=re.M)
    gated = configs.qwen3_next_tiny()
    assert rows == [(r.name, r.layers_field or "", r.scope(gated) or "")
                    for r in tfm.MIXERS.values()], rows
    assert "_mixer_params" not in text


if __name__ == "__main__":  # this tree's digests (JAX_PLATFORMS=cpu), for PARENT
    from ray_tpu.ops import flash_attention as fa

    with jax.default_matmul_precision("highest"):
        for preset in PARENT:
            name, _, impl = preset.partition("-")
            fa.use_kernels = lambda platform: bool(impl)  # the CPU's, or true
            print(repr(preset) + ":",
                  tuple(_digests(name, m, impl or None) for m in REMAT), ",")
