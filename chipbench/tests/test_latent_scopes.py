"""metrics/_latent.py: device time under the `mla.rope` and `mla` scopes
grouped by reduce/scopes.by_scope, the flash kernels told to the latent
mixer by the `mla` scope (`_routed.kernel_seconds`), and the four readers
that stand on them, on the scoped ops and Pallas calls of one step of a
traced run of kanana_2_30b_a3b.train_rank8_16k recorded on the chip
(reduce/recorded_latent_trace.json, PR 39), on a hand-made picture, and
where there is nothing to read.

    python3 -m pytest chipbench/tests/test_latent_scopes.py
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench.metrics import _latent, _routed, readers  # noqa: E402
from chipbench.reduce import scopes  # noqa: E402

RECORDED = os.path.join(os.path.dirname(HERE), "reduce",
                        "recorded_latent_trace.json")
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
NEW = ("mla_share_pct", "mla_outer_share_pct", "mla_stack_flash_fwd_roofline",
       "mla_stack_flash_bwd_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _conf(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("stack,want", [
    ("jit(_step)/jvp()/while/body/closed_call/mla/mla.rope/cos:", "mla.rope"),
    ("jit(_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mla/mla.rope/mul:", "mla.rope"),
    ("jit(_step)/transpose(jvp(mla.rope))/mul:", "mla.rope"),
    ("jit(_step)/jvp()/while/body/closed_call/mla/pallas_call:", "mla"),
    ("jit(_step)/jvp()/while/body/closed_call/mla/bsnh,nhd->bsd/"
     "dot_general:", "mla"),
    ("jit(_step)/jvp()/checkpoint/moe.experts/pallas_call:", "other"),
    ("jit(_step)/jvp()/checkpoint/pallas_call:", "other"),
])
def test_scope_of_a_name_stack(stack, want):
    assert scopes.scope_of(stack, _latent.SCOPES) == want


@pytest.fixture(scope="module")
def rec():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_chip_trace(rec):
    events = scopes.load_json(RECORDED)
    red = scopes.by_scope(events, _latent.SCOPES)
    assert red["devices"] == rec["expect"]["devices"] == 1
    assert abs(red["busy_s"] - rec["expect"]["busy_s"]) < 1e-12
    for k, v in rec["expect"]["scope_s"].items():
        assert abs(red["scope_s"][k] - v) < 1e-12, k
    for s in _latent.SCOPES:
        assert red["scope_s"][s] > 0, s
    got = _routed.kernel_seconds(events, rec["labels"], scope="mla")
    assert got == rec["expect"]["kernels"]
    # One step of the cut under remat "full": five latent layers, two
    # forward calls each (the second in the backward's recomputation), a dQ
    # and a dK/dV each; every flash call of the step is a latent layer's.
    assert got["out"] == {}
    assert got["in"]["flash_fwd"][0] == 10
    assert got["in"]["flash_dq"][0] == got["in"]["flash_dkv"][0] == 5
    kernels = sum(v[1] for v in got["in"].values())
    mla = red["scope_s"]["mla"] + red["scope_s"]["mla.rope"]
    assert 0.5 * mla < kernels < mla
    # The rotation is a small part of what lies around the kernels.
    assert red["scope_s"]["mla.rope"] < 0.5 * (mla - kernels)
    # The held experts' first window goes through the Pallas grouped
    # products, which carry their scope: no `ragged-dot` op in an even step.
    assert _routed.ragged_dot_seconds(events) == 0.0
    moe = scopes.by_scope(events, _routed.SCOPES)["scope_s"]
    assert moe["moe.experts"] > moe["moe.route"] > 0
    # The four readers on the recorded step (shares are of the kept ops'
    # busy time): a forward call of 24.96 ms for a least time of 13.95, the
    # backward pair's 68.41 ms for 36.28.
    ctx = {"cell": {"config": _conf("kanana_2_30b_a3b"), "chips": 1},
           "latent": dict(red, kernels=got["in"]), "trace": {"busy_s": 1.0},
           "stats": {"batch": 1, "seq": 16384}, "peaks": PEAKS}
    share = readers.read("mla_share_pct", dict(ctx))
    outer = readers.read("mla_outer_share_pct", dict(ctx))
    assert abs(share - 100 * mla / red["busy_s"]) < 1e-9
    assert abs(outer - 100 * (mla - kernels) / red["busy_s"]) < 1e-9
    assert 0 < outer < share < 100
    fwd = readers.read("mla_stack_flash_fwd_roofline", dict(ctx))
    bwd = readers.read("mla_stack_flash_bwd_roofline", dict(ctx))
    assert abs(fwd - 55.89) < 0.05 and abs(bwd - 53.03) < 0.05


def test_readers_say_nothing_where_there_is_nothing():
    """A configuration without a `stack` section, a run without a trace, a
    traced run with a `stack` section whose trace has no `mla.rope` scope
    (an older program, the other routed cell): None, not an exception."""
    ctx = {"cell": {"config": {"transformer_config": {}}, "chips": 1},
           "trace": None, "stats": {}, "peaks": {}}
    assert _latent.picture(ctx) == {}
    for name in NEW:
        assert readers.read(name, dict(ctx)) is None, name
    for conf in (_conf("kanana_2_30b_a3b"), _conf("mellum2_12b_a2_5b"),
                 _conf("kimi_linear_48b_a3b")):
        ctx = {"cell": {"config": conf, "chips": 1}, "latent": {},
               "trace": {"busy_s": 1.0, "module_ms": {"jit__step": [500.0]}},
               "stats": {"batch": 1, "seq": 16384}, "peaks": PEAKS}
        for name in NEW:
            assert readers.read(name, dict(ctx)) is None, name


def test_metrics_from_a_picture():
    """The four readers on a hand-made picture. One forward call is 32 x
    16384^2 x 320 = 2.7488 T operations = 13.953 ms at 197 TFLOP/s (its
    bytes, 0.67 GB, take 0.82 ms: compute-bound), so a call of 27.906 ms is
    half its roofline; the backward pair's 832 / 320 = 2.6 times as much,
    36.278 ms, against 50 + 22.556 ms is again a half."""
    pic = {"busy_s": 2.0,
           "scope_s": {"mla": 1.5, "mla.rope": 0.02, "other": 0.48},
           "kernels": {"flash_fwd": [20.0, 20 * 27.906e-3],
                       "flash_dq": [10.0, 10 * 50e-3],
                       "flash_dkv": [10.0, 10 * 22.556e-3]}}
    ctx = {"cell": {"config": _conf("kanana_2_30b_a3b"), "chips": 1},
           "latent": pic, "trace": {"busy_s": 2.0},
           "stats": {"batch": 1, "seq": 16384}, "peaks": PEAKS}
    assert readers.read("mla_share_pct", dict(ctx)) == 76.0
    kernels = 20 * 27.906e-3 + 10 * 72.556e-3
    outer = readers.read("mla_outer_share_pct", dict(ctx))
    assert abs(outer - 100 * (1.52 - kernels) / 2.0) < 1e-9
    fwd = readers.read("mla_stack_flash_fwd_roofline", dict(ctx))
    assert abs(fwd - 50.0) < 0.01
    bwd = readers.read("mla_stack_flash_bwd_roofline", dict(ctx))
    assert abs(bwd - 50.0) < 0.01
    # A picture with one of the backward kernels missing: no number.
    del pic["kernels"]["flash_dkv"]
    assert readers.read("mla_stack_flash_bwd_roofline", dict(ctx)) is None
