"""metrics/_shortconv.py: device time under the `shortconv.core` and
`shortconv` scopes grouped by reduce/scopes.by_scope and the two readers that
stand on them, on the scoped ops and Pallas calls of one step of a traced
run of lfm2_8b_a1b.train_rank4_8k recorded on the chip
(reduce/recorded_shortconv_trace.json, PR 60), on a hand-made picture, and
where there is nothing to read: every OTHER recorded trace
(recorded_gdn_trace.json among them), a configuration without a `stack`
section or without these counts, an untraced run.

    python3 -m pytest chipbench/tests/test_shortconv_scopes.py
"""
import glob
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench import weights_lfm2_moe as W  # noqa: E402
from chipbench.metrics import _shortconv, readers  # noqa: E402
from chipbench.reduce import lfm2_moe_counts as counts, scopes  # noqa: E402

REDUCE = os.path.join(os.path.dirname(HERE), "reduce")
RECORDED = os.path.join(REDUCE, "recorded_shortconv_trace.json")
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
NAME = "lfm2_8b_a1b"
NEW = ("shortconv_share_pct", "shortconv_core_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
B, S = 4, 8192


def _conf(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("stack,want", [
    ("jit(_step)/jvp()/while/body/closed_call/shortconv/shortconv.core/"
     "jit(_fwd_call)/shortconv.core/pallas_call:", "shortconv.core"),
    # A backward rule is traced outside the mixer: the scope
    # ops/shortconv.py opens round its own call is what it keeps.
    ("jit(_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "jit(_bwd_call)/shortconv.core/pallas_call:", "shortconv.core"),
    ("jit(_step)/jvp()/while/body/closed_call/shortconv/dot_general:",
     "shortconv"),
    ("jit(_step)/jvp()/while/body/closed_call/gdn/jit(_conv_fwd_call)/gdn/"
     "pallas_call:", "other"),
    ("jit(_step)/jvp()/gattn/pallas_call:", "other"),
])
def test_scope_of_a_name_stack(stack, want):
    assert scopes.scope_of(stack, _shortconv.SCOPES) == want


def _ctx(pic, conf=NAME, step_ms=700.0, busy=0.7):
    return {"cell": {"config": _conf(conf), "chips": 1}, "shortconv": pic,
            "stats": {"batch": B, "seq": S}, "peaks": PEAKS,
            "trace": {"busy_s": busy, "module_ms": {"jit__step": [step_ms]}}}


def test_counts():
    """The core at [4, 8192] tokens x 2,048 channels in bfloat16: 16 KB a
    token forward (12 read, 4 written) and 28 KB backward (16 read, 12
    written): 0.655 and 1.147 ms a layer at 819 GB/s; the whole stack 1.298
    G operations a token, within a thousandth of
    `TransformerConfig.flops_per_token` (which also counts 6 a norm weight,
    tap and bias)."""
    sz = W.sizes_of(_conf(NAME), False)
    cost = counts.shortconv_core(B, S, sz.d, sz.K)
    assert cost["bytes_fwd"] == B * S * 16384
    assert cost["bytes_bwd"] == B * S * 28672
    assert abs(cost["bytes_fwd"] / 819e9 * 1e3 - 0.6555) < 1e-3
    assert abs(counts.roofline_s(cost, PEAKS) * 1e3 - 1.8028) < 1e-3
    flops = counts.stack_flops_per_token(sz, S)
    assert abs(flops / 1e9 - 1.2976) < 1e-3
    assert sz.kinds == [("shortconv", "dense"), ("attn", "moe")] + [
        ("shortconv", "moe")] * 3


def test_metrics_from_a_picture():
    """Two steps of 0.7 s traced, four convolution layers: 2 x 4 x 3.6 ms
    under `shortconv.core` is half its roofline; the share is both scopes'
    seconds over the busy time."""
    core = 2 * 4 * 2 * 1.80282e-3
    pic = {"busy_s": 1.4, "scope_s": {"shortconv.core": core,
                                      "shortconv": 0.35,
                                      "other": 1.05 - core}}
    ctx = _ctx(pic, busy=1.4)
    assert abs(readers.read("shortconv_core_roofline", dict(ctx)) - 50.0
               ) < 0.01
    assert abs(readers.read("shortconv_share_pct", dict(ctx))
               - 100 * (0.35 + core) / 1.4) < 1e-9
    # under 100 while the scope takes its least time or more
    pic["scope_s"]["shortconv.core"] = 2 * 4 * 1.81e-3
    assert 99.5 < readers.read("shortconv_core_roofline", dict(ctx)) < 100


def test_readers_say_nothing_where_there_is_nothing():
    """No trace; a configuration without a `stack` section; every other
    configuration's counts module; a trace without the scopes (the parent's
    program under this PR's benchmark files): None, never an exception."""
    ctx = {"cell": {"config": {"transformer_config": {}}, "chips": 1},
           "trace": None, "stats": {}, "peaks": {}}
    assert _shortconv.picture(ctx) == {}
    for name in NEW:
        assert readers.read(name, dict(ctx)) is None, name
    for path in sorted(glob.glob(os.path.join(CONFIGS, "*.json"))):
        conf = os.path.basename(path)[:-5]
        if conf == NAME:
            continue
        for pic in ({}, {"busy_s": 1.0, "scope_s": {"shortconv.core": 0.5}}):
            ctx = dict(_ctx(pic, conf), stats={"batch": 1, "seq": 4096})
            assert readers.read("shortconv_core_roofline", dict(ctx)
                                ) is None, conf
        for name in NEW:
            assert readers.read(name, dict(_ctx({}, conf))) is None, (
                conf, name)


@pytest.mark.parametrize("path", sorted(
    p for p in glob.glob(os.path.join(REDUCE, "recorded_*_trace.json"))
    if p != RECORDED))
def test_every_other_recorded_trace_reads_none(path):
    """The other cells' recorded steps carry neither scope: the picture is
    empty and both readers return None on them."""
    with open(path) as f:
        events = [tuple(e) for e in json.load(f).get("events", [])]
    if not events or len(events[0]) != 5:  # a recording without name stacks:
        events = []                        # by_scope finds no scope in it
    red = scopes.by_scope(events, _shortconv.SCOPES)
    assert not any(s in red["scope_s"] for s in _shortconv.SCOPES)
    for name in NEW:
        assert readers.read(name, dict(_ctx({}))) is None, name


@pytest.fixture(scope="module")
def rec():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_chip_trace(rec):
    events = scopes.load_json(RECORDED)
    red = scopes.by_scope(events, _shortconv.SCOPES)
    assert red["devices"] == rec["expect"]["devices"] == 1
    assert abs(red["busy_s"] - rec["expect"]["busy_s"]) < 1e-12
    for k, v in rec["expect"]["scope_s"].items():
        assert abs(red["scope_s"][k] - v) < 1e-12, k
    for s in _shortconv.SCOPES:
        assert red["scope_s"][s] > 0, s
    # One step under remat "full": nothing of the core is kept, so a
    # convolution layer's forward kernel runs twice (the forward pass and
    # the backward's recomputation) and its backward kernel once.
    # The pair has no file under reduce/kernels/ (a call is named there by
    # its operand counts): the forward is `pallas_4in_1out` (Bg, Cg, x,
    # taps), the backward's 7in_2out (those, the two halo parts, dy) reads
    # as that table's `sscan_fwd`. The name stack tells them from any other.
    label = lambda e: rec["labels"].get(e[1], "").rsplit("__", 1)[-1]
    core = [label(e) for e in events if "__" in rec["labels"].get(e[1], "")
            and scopes.scope_of(e[4], _shortconv.SCOPES) == "shortconv.core"]
    assert sorted(set(core)) == ["pallas_4in_1out", "sscan_fwd"]
    assert core.count("pallas_4in_1out") == 2 * 4 and len(core) == 3 * 4
    whole = rec["whole_step"]
    step_s = (rec["step_ns"][1] - rec["step_ns"][0]) / 1e9
    assert abs(whole["busy_s"] - step_s) < 1e-3 * step_s
    ctx = _ctx(whole, step_ms=1e3 * step_s, busy=whole["busy_s"])
    got = {n: readers.read(n, dict(ctx)) for n in NEW}
    assert all(0 < v < 100 for v in got.values()), got
    core = whole["scope_s"]["shortconv.core"]
    assert abs(got["shortconv_core_roofline"]
               - 100 * 4 * 1.80282e-3 / core) < 0.01
    assert abs(got["shortconv_share_pct"] - 100 * (
        core + whole["scope_s"]["shortconv"]) / whole["busy_s"]) < 1e-9
