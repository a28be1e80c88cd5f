"""metrics/_gdn.py: device time under the `gdn.core`, `gdn`, `gattn.gate` and
`gattn` scopes grouped by reduce/scopes.by_scope, the flash kernels told to
the gated attention layer by the `gattn` scope (`_routed.kernel_seconds`),
and the five readers that stand on them, on the scoped ops and Pallas calls
of one step of a traced run of qwen3_next_80b_a3b.train_rank16_16k recorded
on the chip (reduce/recorded_gdn_trace.json, PR 41), on a hand-made picture,
and where there is nothing to read.

    python3 -m pytest chipbench/tests/test_gdn_scopes.py
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench.metrics import _gdn, _routed, readers  # noqa: E402
from chipbench.reduce import qwen3_next_counts as counts, scopes  # noqa: E402

RECORDED = os.path.join(os.path.dirname(HERE), "reduce",
                        "recorded_gdn_trace.json")
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
NEW = ("gdn_share_pct", "gdn_outer_share_pct", "gdn_core_roofline",
       "gattn_flash_fwd_roofline", "gattn_flash_bwd_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _conf(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("stack,want", [
    ("jit(_step)/jvp()/while/body/closed_call/gdn/gdn.core/gdn.core/"
     "pallas_call:", "gdn.core"),
    # The backward rule is traced outside the mixer: the scope ops/kda.py
    # opens around its own call is what it keeps.
    ("jit(_step)/transpose(jvp())/while/body/closed_call/gdn.core/"
     "pallas_call:", "gdn.core"),
    ("jit(_step)/transpose(jvp(gdn.core))/reduce_sum:", "gdn.core"),
    ("jit(_step)/jvp()/while/body/closed_call/gdn/bsd,dcnh->bscnh/"
     "dot_general:", "gdn"),
    ("jit(_step)/jvp()/gattn/gattn.gate/logistic:", "gattn.gate"),
    ("jit(_step)/jvp()/gattn/pallas_call:", "gattn"),
    ("jit(_step)/jvp()/checkpoint/moe.shared/logistic:", "other"),
    ("jit(_step)/jvp()/while/body/closed_call/kda/kda.core/pallas_call:",
     "other"),
])
def test_scope_of_a_name_stack(stack, want):
    assert scopes.scope_of(stack, _gdn.SCOPES) == want


def _ctx(pic, busy=1.0):
    return {"cell": {"config": _conf("qwen3_next_80b_a3b"), "chips": 1},
            "gdn": pic, "stats": {"batch": 1, "seq": 16384}, "peaks": PEAKS,
            "trace": {"busy_s": busy, "module_ms": {"jit__step": [500.0]}}}


@pytest.fixture(scope="module")
def rec():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_chip_trace(rec):
    events = scopes.load_json(RECORDED)
    red = scopes.by_scope(events, _gdn.SCOPES)
    assert red["devices"] == rec["expect"]["devices"] == 1
    assert abs(red["busy_s"] - rec["expect"]["busy_s"]) < 1e-12
    for k, v in rec["expect"]["scope_s"].items():
        assert abs(red["scope_s"][k] - v) < 1e-12, k
    for s in _gdn.SCOPES:
        assert red["scope_s"][s] > 0, s
    got = _routed.kernel_seconds(events, rec["labels"], scope="gattn")
    assert got == rec["expect"]["kernels"]
    # One step of the cut under remat "full": the gated attention layer's
    # forward kernel runs ONCE (its o and lse are kept), a dQ and a dK/dV;
    # every flash call of the step is that layer's.
    assert got["out"] == {}
    assert (got["in"]["flash_fwd"][0] == got["in"]["flash_dq"][0]
            == got["in"]["flash_dkv"][0] == 1)
    # The three DeltaNet layers' core: the forward kernel twice a layer (the
    # second in the backward's recomputation: `full` keeps only the flash
    # kernel's outputs), the backward kernel once, all under `gdn.core`.
    core = [e for e in events
            if scopes.scope_of(e[4], _gdn.SCOPES) == "gdn.core"]
    label = lambda e: rec["labels"].get(e[1], "").rsplit("__", 1)[-1]
    assert sum(label(e) == "pallas_6in_4out" for e in core) == 6
    assert sum(label(e) == "pallas_9in_6out" for e in core) == 3
    kernels = sum(e[3] for e in core if label(e).startswith("pallas")) / 1e9
    assert 0.5 * red["scope_s"]["gdn.core"] < kernels < red["scope_s"][
        "gdn.core"]
    # The core is the smaller part of the DeltaNet layers, the gate a small
    # part of the attention layer.
    assert red["scope_s"]["gdn.core"] < red["scope_s"]["gdn"]
    assert red["scope_s"]["gattn.gate"] < red["scope_s"]["gattn"]
    assert _routed.ragged_dot_seconds(events) == 0.0
    moe = scopes.by_scope(events, _routed.SCOPES)["scope_s"]
    assert moe["moe.experts"] > moe["moe.route"] > 0
    # The five readers on the recorded step (shares are of the kept ops'
    # busy time; one step, so `steps_traced` is the busy time over the
    # step's 567 ms).
    ctx = _ctx(dict(red, kernels=got["in"]))
    share = readers.read("gdn_share_pct", dict(ctx))
    outer = readers.read("gdn_outer_share_pct", dict(ctx))
    gdn = red["scope_s"]["gdn"] + red["scope_s"]["gdn.core"]
    assert abs(share - 100 * gdn / red["busy_s"]) < 1e-9
    assert abs(outer - 100 * red["scope_s"]["gdn"] / red["busy_s"]) < 1e-9
    assert 0 < outer < share < 100
    fwd = readers.read("gattn_flash_fwd_roofline", dict(ctx))
    bwd = readers.read("gattn_flash_bwd_roofline", dict(ctx))
    assert 70 < fwd < 85 and 58 < bwd < 72
    ctx["trace"] = {"busy_s": 0.5, "module_ms": {"jit__step": [500.0]}}
    core_pct = readers.read("gdn_core_roofline", ctx)  # one step traced
    want = 100 * 3 * 1.3979050100729005e-3 / red["scope_s"]["gdn.core"]
    assert abs(core_pct - want) < 1e-6 and 2 < core_pct < 8


def test_readers_say_nothing_where_there_is_nothing():
    """A configuration without a `stack` section, a run without a trace, a
    traced run with a `stack` section whose trace has no `gdn` scope (an
    older program, the other routed cells): None, not an exception."""
    ctx = {"cell": {"config": {"transformer_config": {}}, "chips": 1},
           "trace": None, "stats": {}, "peaks": {}}
    assert _gdn.picture(ctx) == {}
    for name in NEW:
        assert readers.read(name, dict(ctx)) is None, name
    for conf in ("qwen3_next_80b_a3b", "kanana_2_30b_a3b",
                 "mellum2_12b_a2_5b", "kimi_linear_48b_a3b"):
        ctx = dict(_ctx({}), cell={"config": _conf(conf), "chips": 1})
        for name in NEW:
            assert readers.read(name, dict(ctx)) is None, name


def test_metrics_from_a_picture():
    """The five readers on a hand-made picture. The core, a layer: forward
    88.76 G operations (0.4506 ms at 197 TFLOP/s) and 0.4069 GB (0.4968 ms at
    819 GB/s: memory-bound by a little), backward twice the operations
    (0.9011 ms; its 0.6795 GB take 0.8297): 1.3979 ms, so three layers and
    two steps in 83.874 ms of `gdn.core` are a tenth of the roofline. One
    flash forward call is 4 x 16 x 256 x 16384 x 16385 / 2 = 2.1992 T
    operations = 11.163 ms, so a call of 22.327 ms is half its roofline; the
    backward pair's 2.5 times as much, 27.908 ms, against 30 + 25.817 ms is
    again a half."""
    S = 16384
    fwd = counts.gdn_core_fwd(1, 16, 32, S, 128, 128, 128)
    bwd = counts.gdn_core_bwd(1, 16, 32, S, 128, 128, 128)
    assert abs(fwd["flops"] / 197e12 - 0.4506e-3) < 1e-7
    assert abs(fwd["bytes"] / 819e9 - 0.4968e-3) < 1e-7
    assert abs(bwd["flops"] / 197e12 - 0.9011e-3) < 1e-7
    assert bwd["bytes"] / 819e9 < bwd["flops"] / 197e12
    pic = {"busy_s": 1.0,
           "scope_s": {"gdn": 0.25, "gdn.core": 83.874e-3, "gattn": 0.1,
                       "gattn.gate": 0.02, "other": 0.5},
           "kernels": {"flash_fwd": [2.0, 2 * 22.327e-3],
                       "flash_dq": [2.0, 2 * 30e-3],
                       "flash_dkv": [2.0, 2 * 25.817e-3]}}
    ctx = _ctx(pic)  # busy 1.0 s over a step of 500 ms: two steps traced
    assert abs(readers.read("gdn_share_pct", dict(ctx)) - 33.3874) < 1e-9
    assert readers.read("gdn_outer_share_pct", dict(ctx)) == 25.0
    assert abs(readers.read("gdn_core_roofline", dict(ctx)) - 10.0) < 0.01
    assert abs(readers.read("gattn_flash_fwd_roofline", dict(ctx))
               - 50.0) < 0.01
    assert abs(readers.read("gattn_flash_bwd_roofline", dict(ctx))
               - 50.0) < 0.01
    # A picture with one of the backward kernels missing: no number.
    del pic["kernels"]["flash_dkv"]
    assert readers.read("gattn_flash_bwd_roofline", dict(ctx)) is None
    # Every roofline share is under 100 while a call takes its least time
    # or more.
    pic["kernels"]["flash_fwd"] = [2.0, 2 * 11.2e-3]
    assert readers.read("gattn_flash_fwd_roofline", dict(ctx)) < 100
