"""metrics/_sparse.py: device time under the `dsa.core`, `dsa.index` and `dsa`
scopes grouped by reduce/scopes.by_scope and the five readers that stand on
them, on the scoped ops and Pallas calls of one step of a traced run of
keye_vl_2_0_30b_a3b.train_sparse_rank8 recorded on the chip
(reduce/recorded_sparse_trace.json, PR 54), on a hand-made picture, and where
there is nothing to read: every OTHER recorded trace, a configuration without
a `stack` section or without these counts, an untraced run.

    python3 -m pytest chipbench/tests/test_sparse_scopes.py
"""
import glob
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench.metrics import _sparse, readers  # noqa: E402
from chipbench.reduce import keye_vl2_counts as counts, scopes  # noqa: E402

REDUCE = os.path.join(os.path.dirname(HERE), "reduce")
RECORDED = os.path.join(REDUCE, "recorded_sparse_trace.json")
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
NEW = ("dsa_share_pct", "dsa_index_share_pct", "dsa_core_roofline",
       "dsa_index_roofline", "dsa_selected_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
S, K = 32768, 2048


def _conf(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def _layers():
    return _conf("keye_vl_2_0_30b_a3b")["transformer_config"]["n_layers"]


@pytest.mark.parametrize("stack,want", [
    ("jit(_step)/jvp()/while/body/closed_call/dsa/dsa.index/dsa_select/"
     "pallas_call:", "dsa.index"),
    ("jit(_step)/jvp()/while/body/closed_call/dsa/dsa.core/dsa_fwd/"
     "pallas_call:", "dsa.core"),
    # A backward rule is traced outside the mixer: the scope
    # ops/sparse_attention.py opens round its own call is what it keeps.
    ("jit(_step)/transpose(jvp())/while/body/closed_call/dsa.core/dsa_bwd/"
     "pallas_call:", "dsa.core"),
    ("jit(_step)/transpose(jvp())/while/body/closed_call/dsa.index/mul:",
     "dsa.index"),
    ("jit(_step)/jvp()/while/body/closed_call/dsa/bsd,dnh->bsnh/"
     "dot_general:", "dsa"),
    ("jit(_step)/jvp()/while/body/closed_call/moe.experts/gmm:", "other"),
    ("jit(_step)/jvp()/gattn/pallas_call:", "other"),
])
def test_scope_of_a_name_stack(stack, want):
    assert scopes.scope_of(stack, _sparse.SCOPES) == want


def _ctx(pic, conf="keye_vl_2_0_30b_a3b", step_ms=2500.0, busy=2.5):
    return {"cell": {"config": _conf(conf), "chips": 1}, "dsa": pic,
            "stats": {"batch": 1, "seq": S, "dsa_selected_pct": 12.109},
            "peaks": PEAKS,
            "trace": {"busy_s": busy, "module_ms": {"jit__step": [step_ms]}}}


def test_counts():
    """12.109% of the triangle is kept at 32,768 and 2,048; a layer's core is
    1.065 T operations forward (5.41 ms at the MXU's peak) and 2.5 times that
    backward; the indexer's 4.93 T a layer and a step."""
    kept, tri = counts.selected_pairs(S, K), counts.triangle_pairs(S)
    assert kept == sum(min(t + 1, K) for t in range(S))
    assert abs(100 * kept / tri - 12.109) < 1e-3
    assert counts.selected_pairs(1024, K) == counts.triangle_pairs(1024)
    fwd = counts.dsa_core_fwd(1, 32, 4, S, 128, K)
    bwd = counts.dsa_core_bwd(1, 32, 4, S, 128, K)
    assert abs(fwd["flops"] / 197e12 - 5.407e-3) < 1e-6
    assert bwd["flops"] == 2.5 * fwd["flops"]
    assert fwd["bytes"] / 819e9 < fwd["flops"] / 197e12
    ix = counts.dsa_index(1, 32, 4, S, 128, 16, 64, K)
    assert abs(ix["flops"] / 1e12 - 4.931) < 1e-3


def test_metrics_from_a_picture():
    """Two steps of 2.5 s traced, the configuration's L layers. The core's
    least time a layer is 5.407 + 13.517 = 18.924 ms, so 2 L x 189.24 ms
    under `dsa.core` is a tenth of its roofline; the indexer's 25.029 ms a
    layer against 2 L x 125.145 ms a fifth."""
    L = _layers()
    core, index = 2 * L * 0.18924, 2 * L * 0.125145
    pic = {"busy_s": 5.0, "scope_s": {"dsa": 0.5, "dsa.core": core,
                                      "dsa.index": index,
                                      "other": 4.5 - core - index}}
    ctx = _ctx(pic, busy=5.0)
    assert abs(readers.read("dsa_share_pct", dict(ctx))
               - 20 * (0.5 + core + index)) < 1e-3
    assert abs(readers.read("dsa_index_share_pct", dict(ctx))
               - 20 * index) < 1e-3
    assert abs(readers.read("dsa_core_roofline", dict(ctx)) - 10.0) < 0.01
    assert abs(readers.read("dsa_index_roofline", dict(ctx)) - 20.0) < 0.01
    assert readers.read("dsa_selected_pct", dict(ctx)) == 12.109
    # under 100 while the scope takes its least time or more
    pic["scope_s"]["dsa.core"] = 2 * L * 18.93e-3
    assert 99.9 < readers.read("dsa_core_roofline", dict(ctx)) < 100


def test_readers_say_nothing_where_there_is_nothing():
    """No trace; a configuration without a `stack` section; every other
    configuration's counts module; a trace with no `dsa` scope (the parent's
    program under this PR's benchmark files): None, never an exception."""
    ctx = {"cell": {"config": {"transformer_config": {}}, "chips": 1},
           "trace": None, "stats": {}, "peaks": {}}
    assert _sparse.picture(ctx) == {}
    for name in NEW:
        assert readers.read(name, dict(ctx)) is None, name
    for path in sorted(glob.glob(os.path.join(CONFIGS, "*.json"))):
        conf = os.path.basename(path)[:-5]
        if conf == "keye_vl_2_0_30b_a3b":
            continue
        for pic in ({}, {"busy_s": 1.0, "scope_s": {"dsa.core": 0.5,
                                                    "dsa.index": 0.2}}):
            ctx = dict(_ctx(pic, conf), stats={"batch": 1, "seq": 16384})
            for name in ("dsa_core_roofline", "dsa_index_roofline",
                         "dsa_selected_pct"):
                assert readers.read(name, dict(ctx)) is None, (conf, name)


@pytest.mark.parametrize("path", sorted(
    p for p in glob.glob(os.path.join(REDUCE, "recorded_*_trace.json"))
    if p != RECORDED))
def test_every_other_recorded_trace_reads_none(path):
    """The other cells' recorded steps carry no `dsa` scope: the picture is
    empty and the four trace readers return None on them."""
    with open(path) as f:
        events = [tuple(e) for e in json.load(f).get("events", [])]
    if not events or len(events[0]) != 5:  # a recording without name stacks:
        events = []                        # by_scope finds no scope in it
    red = scopes.by_scope(events, _sparse.SCOPES)
    assert not any(s in red["scope_s"] for s in _sparse.SCOPES)
    pic = red if any(s in red["scope_s"] for s in _sparse.SCOPES) else {}
    ctx = _ctx(pic)
    for name in NEW[:4]:
        assert readers.read(name, dict(ctx)) is None, name


@pytest.fixture(scope="module")
def rec():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_chip_trace(rec):
    events = scopes.load_json(RECORDED)
    red = scopes.by_scope(events, _sparse.SCOPES)
    assert red["devices"] == rec["expect"]["devices"] == 1
    assert abs(red["busy_s"] - rec["expect"]["busy_s"]) < 1e-12
    for k, v in rec["expect"]["scope_s"].items():
        assert abs(red["scope_s"][k] - v) < 1e-12, k
    for s in _sparse.SCOPES:
        assert red["scope_s"][s] > 0, s
    # One step under remat "full": each of the four kernels ONCE a layer
    # (their outputs are kept), L layers; the selection and the indexer's
    # loss under `dsa.index`, the forward and the one backward under
    # `dsa.core`.
    label = lambda e: rec["labels"].get(e[1], "").rsplit("__", 1)[-1]
    by = lambda scope, sig: sum(
        label(e) == sig and scopes.scope_of(e[4], _sparse.SCOPES) == scope
        for e in events)
    L = _layers()
    assert by("dsa.index", "pallas_3in_3out") == L
    assert by("dsa.index", "pallas_8in_4out") == L
    assert by("dsa.core", "pallas_4in_2out") == L
    assert by("dsa.core", "pallas_7in_3out") == L
    # the mechanism is most of what the recording holds, and its core and
    # its indexer are both larger than the projections round them
    assert red["scope_s"]["dsa.core"] > red["scope_s"]["dsa"]
    assert red["scope_s"]["dsa.index"] > red["scope_s"]["dsa"]
    step_s = (rec["step_ns"][1] - rec["step_ns"][0]) / 1e9
    ctx = _ctx(red, step_ms=1e3 * step_s, busy=step_s)  # one step traced
    share = readers.read("dsa_share_pct", dict(ctx))
    index = readers.read("dsa_index_share_pct", dict(ctx))
    mech = sum(red["scope_s"][s] for s in _sparse.SCOPES)
    assert abs(share - 100 * mech / red["busy_s"]) < 1e-9
    assert 0 < index < share <= 100
    core = readers.read("dsa_core_roofline", dict(ctx))
    want = 100 * L * 18.924e-3 / red["scope_s"]["dsa.core"]
    assert abs(core - want) < 0.01 and 0 < core < 100
    ix = readers.read("dsa_index_roofline", dict(ctx))
    assert 0 < ix < 100
