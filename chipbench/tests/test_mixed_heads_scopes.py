"""metrics/_mixed_heads.py: device time under `gattn.gate`, and the flash
kernels told to the sliding layers (under `swa`) or to the full ones (under
`gattn` and not under `swa`), each held to reduce/laguna_counts.py at the
kind's OWN head count; the five readers that stand on them, on the scoped
ops and Pallas calls of one step of a traced run of
laguna_s_2_1.train_rank32_8k recorded on the chip
(reduce/recorded_mixed_heads_trace.json, PR 45), on a hand-made picture, and
where there is nothing to read.

    python3 -m pytest chipbench/tests/test_mixed_heads_scopes.py
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench.metrics import _mixed_heads as mh, _routed, readers  # noqa: E402
from chipbench.reduce import laguna_counts as counts, scopes  # noqa: E402

RECORDED = os.path.join(os.path.dirname(HERE), "reduce",
                        "recorded_mixed_heads_trace.json")
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
NEW = ("band512_flash_fwd_roofline", "band512_flash_bwd_roofline",
       "full_gated_flash_fwd_roofline", "full_gated_flash_bwd_roofline",
       "attn_gate_share_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _conf(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("stack,want", [
    ("jit(_step)/jvp()/while/body/closed_call/gattn/gattn.gate/bsd,dnh->bsnh/"
     "dot_general:", "gattn.gate"),
    ("jit(_step)/jvp()/while/body/closed_call/gattn/swa/pallas_call:", "swa"),
    ("jit(_step)/transpose(jvp())/while/body/closed_call/gattn/swa/"
     "pallas_call:", "swa"),
    ("jit(_step)/jvp()/gattn/pallas_call:", "gattn"),
    ("jit(_step)/transpose(jvp(gattn))/gattn.gate/logistic:", "gattn.gate"),
    ("jit(_step)/jvp()/checkpoint/moe.shared/dot_general:", "other"),
    ("jit(_step)/jvp()/while/body/closed_call/swa/pallas_call:", "swa"),
])
def test_scope_of_a_name_stack(stack, want):
    assert scopes.scope_of(stack, mh.SCOPES) == want


def _ctx(pic, busy=1.0, conf="laguna_s_2_1"):
    return {"cell": {"config": _conf(conf), "chips": 1},
            "mixed_heads": pic, "stats": {"batch": 1, "seq": 8192},
            "peaks": PEAKS,
            "trace": {"busy_s": busy, "module_ms": {"jit__step": [250.0]}}}


@pytest.fixture(scope="module")
def rec():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_chip_trace(rec):
    events = scopes.load_json(RECORDED)
    red = scopes.by_scope(events, mh.SCOPES)
    assert red["devices"] == rec["expect"]["devices"] == 1
    assert abs(red["busy_s"] - rec["expect"]["busy_s"]) < 1e-12
    for k, v in rec["expect"]["scope_s"].items():
        assert abs(red["scope_s"][k] - v) < 1e-12, k
    for s in mh.SCOPES:
        assert red["scope_s"][s] > 0, s
    got = mh.kernels_by_kind(events, rec["labels"])
    assert got == rec["expect"]["kernels"]
    # One step of the cut under remat "dots": every attention layer's
    # forward kernel runs ONCE (its o and lse are kept), a dQ and a dK/dV:
    # three sliding layers under `swa`, two full ones under `gattn` alone.
    for kernel in _routed.KERNELS:
        assert got["swa"][kernel][0] == 3 and got["attn"][kernel][0] == 2
    # every kernel outside `swa` ran under `gattn`: the full layers'
    full = _routed.kernel_seconds(events, rec["labels"], scope="gattn")
    assert full["out"] == {} and all(
        full["in"][k][0] == 5 for k in _routed.KERNELS)
    # a full layer's call (the triangle at 24 heads) takes longer than a
    # sliding layer's (the band at 36)
    for kernel in _routed.KERNELS:
        assert got["attn"][kernel][1] / 2 > got["swa"][kernel][1] / 3
    assert _routed.ragged_dot_seconds(events) == 0.0
    moe = scopes.by_scope(events, _routed.SCOPES)["scope_s"]
    assert moe["moe.experts"] > moe["moe.route"] > 0
    # The five readers on the recorded step (shares are of the kept ops'
    # busy time).
    ctx = _ctx(dict(red, kernels=got))
    gate = readers.read("attn_gate_share_pct", dict(ctx))
    assert abs(gate - 100 * red["scope_s"]["gattn.gate"] / red["busy_s"]
               ) < 1e-9 and 0 < gate < 100
    values = {n: readers.read(n, dict(ctx)) for n in NEW[:4]}
    assert all(0 < v < 100 for v in values.values()), values
    assert values["full_gated_flash_fwd_roofline"] > values[
        "band512_flash_fwd_roofline"]
    # the band at the SLIDING layers' 36 heads: sized by the full layers' 24
    # it would read two thirds of it
    sz = _routed.sizes_and_counts(ctx)[0]
    assert (sz.H["swa"], sz.H["attn"], sz.KVH, sz.window) == (36, 24, 4, 512)
    secs = got["swa"]["flash_fwd"][1] / 3
    want = 100 * counts.band_flash_fwd(1, 36, 4, 8192, 128, 512)[
        "flops"] / 197e12 / secs
    assert abs(values["band512_flash_fwd_roofline"] - want) < 1e-9


def test_readers_say_nothing_where_there_is_nothing():
    """A configuration without a `stack` section, a run without a trace, a
    traced run with a `stack` section whose trace has no `gattn.gate` beside
    `swa` (an older program, the other routed cells, whose sizes carry one
    head count): None, not an exception."""
    ctx = {"cell": {"config": {"transformer_config": {}}, "chips": 1},
           "trace": None, "stats": {}, "peaks": {}}
    assert mh.picture(ctx) == {}
    for name in NEW:
        assert readers.read(name, dict(ctx)) is None, name
    for conf in ("laguna_s_2_1", "qwen3_next_80b_a3b", "kanana_2_30b_a3b",
                 "mellum2_12b_a2_5b", "kimi_linear_48b_a3b"):
        for name in NEW:
            assert readers.read(name, _ctx({}, conf=conf)) is None, name
    # mellum2's own sizes under a picture that has the kernels: its `H` is
    # one number, and these readers leave the cell to `swa_flash_*_roofline`
    pic = {"busy_s": 1.0, "scope_s": {"gattn.gate": 0.1, "swa": 0.1},
           "kernels": {"swa": {"flash_fwd": [3.0, 0.006]}, "attn": {}}}
    ctx = _ctx(pic, conf="mellum2_12b_a2_5b")
    assert readers.read("band512_flash_fwd_roofline", ctx) is None


def test_metrics_from_a_picture():
    """The five readers on a hand-made picture. A sliding layer's forward
    call: 4 x 36 x 128 x 4,063,488 = 74.90 G operations = 0.3802 ms at 197
    TFLOP/s (its 168.9 MB take 0.2063 ms: compute-bound), so a call of 1.5208
    ms is a quarter of its roofline; the backward pair's 2.5 times as much,
    0.9505 ms, against 1.0 + 0.901 ms is a half. A full layer's forward:
    4 x 24 x 128 x 33,558,528 = 412.4 G = 2.0932 ms, so 2.9903 ms is 70%."""
    S = 8192
    f = counts.band_flash_fwd(1, 36, 4, S, 128, 512)
    assert abs(f["flops"] / 197e12 - 0.3802e-3) < 1e-7
    assert abs(f["bytes"] / 819e9 - 0.2063e-3) < 1e-7
    g = counts.full_flash_fwd(1, 24, 4, S, 128)
    assert abs(g["flops"] / 197e12 - 2.0932e-3) < 1e-7
    pic = {"busy_s": 1.0,
           "scope_s": {"gattn.gate": 0.15, "swa": 0.05, "gattn": 0.1,
                       "other": 0.7},
           "kernels": {
               "swa": {"flash_fwd": [12.0, 12 * 1.5208e-3],
                       "flash_dq": [12.0, 12 * 1.0e-3],
                       "flash_dkv": [12.0, 12 * 0.901e-3]},
               "attn": {"flash_fwd": [8.0, 8 * 2.9903e-3],
                        "flash_dq": [8.0, 8 * 5.0e-3],
                        "flash_dkv": [8.0, 8 * 5.466e-3]}}}
    ctx = _ctx(pic)
    assert readers.read("attn_gate_share_pct", dict(ctx)) == 15.0
    assert abs(readers.read("band512_flash_fwd_roofline", dict(ctx))
               - 25.0) < 0.01
    assert abs(readers.read("band512_flash_bwd_roofline", dict(ctx))
               - 50.0) < 0.01
    assert abs(readers.read("full_gated_flash_fwd_roofline", dict(ctx))
               - 70.0) < 0.01
    assert abs(readers.read("full_gated_flash_bwd_roofline", dict(ctx))
               - 50.0) < 0.01
    # A picture with one of the backward kernels missing: no number.
    del pic["kernels"]["swa"]["flash_dkv"]
    assert readers.read("band512_flash_bwd_roofline", dict(ctx)) is None
    # Every roofline share is under 100 while a call takes its least time
    # or more.
    pic["kernels"]["attn"]["flash_fwd"] = [8.0, 8 * 2.1e-3]
    assert readers.read("full_gated_flash_fwd_roofline", dict(ctx)) < 100
