"""metrics/_routed.py: the flash kernels told apart by the `swa` scope (a
kernel named from its HLO text by reduce/xplane.py, its mixer from its op's
name stack by reduce/scopes.py, joined on the op's name) and the scopes `swa`,
`moe.route`, `moe.experts` grouped by reduce/scopes.by_scope, on the scoped
ops and Pallas calls of one step of a traced run of
mellum2_12b_a2_5b.train_share_16k recorded on the chip
(reduce/recorded_routed_trace.json, PR 33), and the readers' answers where
there is nothing to read.

    python3 -m pytest chipbench/tests/test_routed_scopes.py
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench.metrics import _routed, readers  # noqa: E402
from chipbench.reduce import scopes  # noqa: E402

RECORDED = os.path.join(os.path.dirname(HERE), "reduce",
                        "recorded_routed_trace.json")
NEW = ("swa_flash_fwd_roofline", "swa_flash_bwd_roofline", "swa_share_pct",
       "moe_experts_roofline")


@pytest.mark.parametrize("stack,want", [
    ("jit(_step)/jvp()/checkpoint/swa/pallas_call:", "swa"),
    ("jit(_step)/transpose(jvp())/checkpoint/rematted_computation/swa/"
     "transpose:", "swa"),
    ("jit(_step)/transpose(jvp(swa))/pallas_call:", "swa"),
    ("jit(_step)/jvp()/checkpoint/moe.experts/ragged_dot_general:",
     "moe.experts"),
    ("jit(_step)/jvp()/checkpoint/moe.route/sort:", "moe.route"),
    ("jit(_step)/jvp()/checkpoint/pallas_call:", "other"),
    ("jit(_step)/swap/add:", "other"),
])
def test_scope_of_a_name_stack(stack, want):
    assert scopes.scope_of(stack, _routed.SCOPES) == want


@pytest.fixture(scope="module")
def rec():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_chip_trace(rec):
    events = scopes.load_json(RECORDED)
    red = scopes.by_scope(events, _routed.SCOPES)
    assert red["devices"] == rec["expect"]["devices"] == 1
    assert abs(red["busy_s"] - rec["expect"]["busy_s"]) < 1e-12
    for k, v in rec["expect"]["scope_s"].items():
        assert abs(red["scope_s"][k] - v) < 1e-12, k
    for s in _routed.SCOPES:
        assert red["scope_s"][s] > 0, s
    got = _routed.kernel_seconds(events, rec["labels"])
    assert got == rec["expect"]["kernels"]
    # One step of the cut: three windowed layers and one full layer, a
    # forward call each (`dots` keeps its residuals), a dQ and a dK/dV each;
    # a windowed call takes a fraction of the full layer's time.
    for kernel in _routed.KERNELS:
        assert got["in"][kernel][0] == 3 and got["out"][kernel][0] == 1
        assert got["in"][kernel][1] / 3 < 0.4 * got["out"][kernel][1]
    # Every windowed kernel's time lies inside the scope's.
    assert sum(v[1] for v in got["in"].values()) < red["scope_s"]["swa"]
    # The grouped products carry no name stack: found by name, all of them
    # outside the `moe.experts` scope (8 calls a layer and 3 of metadata).
    dots = [e for e in events if e[1].lstrip("%").startswith("ragged-dot")]
    assert len(dots) == 4 * (8 + 3)
    assert all(scopes.scope_of(e[4], _routed.SCOPES) == "other" for e in dots)
    assert abs(_routed.ragged_dot_seconds(events)
               - rec["expect"]["ragged_dot_s"]) < 1e-12
    assert 0.05 < rec["expect"]["ragged_dot_s"] < red["scope_s"]["moe.experts"]


def test_kernels_by_hand():
    """Two devices, a forward call under the scope and one outside it on
    each, and an op that is no kernel: counts and seconds a device."""
    ev = [("/device:TPU:0", "%a", 0, 2_000_000, "jit(f)/swa/pallas_call:"),
          ("/device:TPU:0", "%b", 3_000_000, 8_000_000, "jit(f)/pallas_call:"),
          ("/device:TPU:1", "%a", 0, 4_000_000, "jit(f)/swa/pallas_call:"),
          ("/device:TPU:1", "%b", 5_000_000, 8_000_000, "jit(f)/pallas_call:"),
          ("/device:TPU:0", "%c", 12_000_000, 1_000_000, "jit(f)/swa/add:")]
    labels = {"%a": "bf16_1_2__flash_fwd", "%b": "bf16_1_2__flash_fwd",
              "%c": "bf16_4"}
    got = _routed.kernel_seconds(ev, labels)
    assert got == {"in": {"flash_fwd": [1.0, 0.003]},
                   "out": {"flash_fwd": [1.0, 0.008]}}


def test_readers_say_nothing_where_there_is_nothing():
    """A configuration without a `stack` section, a run without a trace, a
    program whose trace has none of the scopes (an older program): None,
    not an exception."""
    ctx = {"cell": {"config": {"transformer_config": {}}, "chips": 1},
           "trace": None, "stats": {}, "peaks": {}}
    assert _routed.picture(ctx) == {}
    assert _routed.scope_share_pct(ctx, "swa") is None
    for name in NEW:
        assert readers.read(name, dict(ctx)) is None, name
    # Traced, with a `stack` section, but no op under the scopes.
    conf = json.load(open(os.path.join(
        os.path.dirname(HERE), "configs", "mellum2_12b_a2_5b.json")))
    ctx = {"cell": {"config": conf, "chips": 1}, "routed": {},
           "trace": {"busy_s": 1.0, "module_ms": {"jit__step": [500.0]}},
           "stats": {"batch": 1, "seq": 16384, "moe_assigned_a_step": 1e5},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    for name in NEW:
        assert readers.read(name, dict(ctx)) is None, name


def test_metrics_from_a_picture():
    """The four readers on a hand-made picture: a windowed forward call of
    2.704 ms is half of its roofline (1.352 ms of operations at 197
    TFLOP/s)."""
    conf = json.load(open(os.path.join(
        os.path.dirname(HERE), "configs", "mellum2_12b_a2_5b.json")))
    pic = {"busy_s": 2.0, "ragged_dot_s": 0.1,
           "scope_s": {"swa": 0.1, "moe.experts": 0.4, "other": 1.4},
           "kernels": {"in": {"flash_fwd": [6.0, 6 * 2.704e-3],
                              "flash_dq": [6.0, 6 * 3.0e-3],
                              "flash_dkv": [6.0, 6 * 3.76e-3]}, "out": {}}}
    ctx = {"cell": {"config": conf, "chips": 1}, "routed": pic,
           "trace": {"busy_s": 2.0, "module_ms": {"jit__step": [500.0]}},
           "stats": {"batch": 1, "seq": 16384,
                     "moe_assigned_a_step": 131072.0},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert readers.read("swa_share_pct", dict(ctx)) == 5.0
    fwd = readers.read("swa_flash_fwd_roofline", dict(ctx))
    assert abs(fwd - 49.99) < 0.05
    bwd = readers.read("swa_flash_bwd_roofline", dict(ctx))
    assert abs(bwd - 100 * (2.5 * 1.3517e-3) / 6.76e-3) < 0.05
    # Four steps traced (2 s busy, 0.5 s a step): 100 ms a step under the
    # scope and 25 in the grouped products' own calls, against 131,072
    # worked rows = 4.87 TFLOP = 24.7 ms.
    moe = readers.read("moe_experts_roofline", dict(ctx))
    assert abs(moe - 100 * (18 * 2304 * 896 * 131072 / 197e12) / 0.125) < 0.01
