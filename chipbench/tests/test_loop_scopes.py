"""metrics/_loop.py: device time under `loop.head` (every pass's final norm,
head, cross-entropy and exit gate of a looped stack; the passes are written
out, so the scope reaches an op's name stack as `jvp(loop.head)` /
`transpose(jvp(loop.head))`) grouped by reduce/scopes.by_scope on the ops of
one step of a traced run of ouro_2_6b.train_loop4_8k recorded on the chip
(reduce/recorded_loop_trace.json, PR 49: the ops under the scope, the
backward Pallas calls and the whiles), the reader on a hand-made picture,
the counts the whole-step share is held against, and the readers' answers
where there is nothing to read.

    python3 -m pytest chipbench/tests/test_loop_scopes.py
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench.metrics import _loop, readers  # noqa: E402
from chipbench.reduce import ouro_counts, scopes  # noqa: E402

RECORDED = os.path.join(os.path.dirname(HERE), "reduce",
                        "recorded_loop_trace.json")
CONFIG = os.path.join(os.path.dirname(HERE), "configs", "ouro_2_6b.json")


@pytest.mark.parametrize("stack,want", [
    ("jit(_step)/jvp(loop.head)/dot_general:", "loop.head"),
    ("jit(_step)/transpose(jvp(loop.head))/while/body/dot_general:",
     "loop.head"),
    ("jit(_step)/transpose(jvp(loop.head))/jvp(loop.head)/checkpoint/"
     "rematted_computation/reduce_max:", "loop.head"),
    ("jit(_step)/jvp()/loop.head/mul:", "loop.head"),
    ("jit(_step)/jvp()/while/body/closed_call/checkpoint/pallas_call:",
     "other"),
    ("jit(_step)/jvp(loop.headroom)/add:", "other"),
    ("", "other"),
])
def test_scope_of_a_name_stack(stack, want):
    assert scopes.scope_of(stack, _loop.SCOPES) == want


def test_recorded_chip_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    red = scopes.by_scope(scopes.load_json(RECORDED), _loop.SCOPES)
    assert red["devices"] == rec["expect"]["devices"] == 1
    assert abs(red["busy_s"] - rec["expect"]["busy_s"]) < 1e-12
    for k, v in rec["expect"]["scope_s"].items():
        assert abs(red["scope_s"][k] - v) < 1e-12, k
    assert abs(sum(red["scope_s"].values()) - red["busy_s"]) < 1e-9
    # the kept ops hold every op under the scope: its seconds are the whole
    # step's, and a share of that step between a tenth and a third
    whole = rec["whole_step"]
    assert abs(red["scope_s"]["loop.head"] - whole["scope_s"]["loop.head"]
               ) < 1e-9
    assert 0.10 < whole["scope_s"]["loop.head"] / whole["busy_s"] < 0.33
    ctx = {"loop": whole}
    assert abs(_loop.head_share_pct(ctx) - 100.0 * whole["scope_s"][
        "loop.head"] / whole["busy_s"]) < 1e-9
    # the passes are written out: the backward flash call is four ops of the
    # program, one a pass, each run once a layer (the forward calls' label,
    # `flash_fwd`, is not among what the recording kept)
    calls = [e for e in rec["events"] if e[4].endswith("pallas_call:")]
    assert len(calls) == 32 and len({e[1] for e in calls}) == 4
    assert all("transpose(jvp())/while/body" in e[4] for e in calls)


def test_share_from_a_picture():
    ctx = {"loop": {"busy_s": 2.0, "scope_s": {"loop.head": 0.5,
                                               "other": 1.5}}}
    assert _loop.head_share_pct(ctx) == 25.0
    assert readers.read("loop_head_share_pct", dict(ctx)) == 25.0
    assert _loop.head_share_pct({"loop": {}}) is None


def test_the_counts_are_the_stacks():
    """6 a matmul parameter a use (32 layer applications, 4 heads) and
    causal attention a layer application: 15.5 GFLOP a token at 8,192, the
    heads 2.4 of them."""
    from chipbench import weights_ouro as W

    with open(CONFIG) as f:
        sz = W.sizes_of(json.load(f), False)
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert ouro_counts.layer_matmul_params(sz) == layer == 51380224
    head = 6.0 * 4 * 49152 * 2048
    assert ouro_counts.head_flops_per_token(sz) == head
    assert ouro_counts.stack_flops_per_token(sz, 8192) == (
        32 * (6.0 * layer + 3.0 * 8192 * 16 * 256) + head) == 15502147584.0


def test_readers_say_nothing_where_there_is_nothing():
    """A run without a trace, or a trace with no op under the scope (another
    cell, an older program): None, not an exception."""
    ctx = {"cell": {"config": {"transformer_config": {}}, "chips": 1},
           "trace": None, "stats": {}, "peaks": {}}
    assert _loop.picture(ctx) == {}
    assert _loop.head_share_pct(ctx) is None
    assert readers.read("loop_head_share_pct", dict(ctx)) is None
