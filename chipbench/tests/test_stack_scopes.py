"""metrics/_stack.py: the Mamba-2 scopes (`ssd.core` inside `mamba`) grouped
by reduce/scopes.by_scope on a slice of a traced run of
granite_4_0_h_micro.train_stage_4k recorded on the chip
(reduce/recorded_stack_scope_trace.json, PR 31), and the readers' answers
where there is nothing to read.

    python3 -m pytest chipbench/tests/test_stack_scopes.py
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench.metrics import _stack  # noqa: E402
from chipbench.reduce import scopes  # noqa: E402

RECORDED = os.path.join(os.path.dirname(HERE), "reduce",
                        "recorded_stack_scope_trace.json")


@pytest.mark.parametrize("stack,want", [
    ("jit(_step)/jvp()/checkpoint/mamba/ssd.core/dot_general:", "ssd.core"),
    ("jit(_step)/transpose(jvp())/checkpoint/rematted_computation/mamba/"
     "ssd.core/exp:", "ssd.core"),
    ("jit(_step)/transpose(jvp(mamba))/ssd.core/mul:", "ssd.core"),
    ("jit(_step)/jvp()/checkpoint/mamba/dot_general:", "mamba"),
    ("jit(_step)/transpose(jvp(mamba))/reduce_sum:", "mamba"),
    ("jit(_step)/jvp()/checkpoint/kda/kda.core/dot_general:", "other"),
    ("jit(_step)/mambax/add:", "other"),
    ("", "other"),
])
def test_inner_scope_listed_first(stack, want):
    assert scopes.scope_of(stack, _stack.SCOPES) == want


def test_recorded_chip_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    red = scopes.by_scope(scopes.load_json(RECORDED), _stack.SCOPES)
    assert red["devices"] == rec["expect"]["devices"] == 1
    assert abs(red["busy_s"] - rec["expect"]["busy_s"]) < 1e-12
    for k, v in rec["expect"]["scope_s"].items():
        assert abs(red["scope_s"][k] - v) < 1e-12, k
    # Self times partition the busy time, and both scopes carry device time.
    assert abs(sum(red["scope_s"].values()) - red["busy_s"]) < 1e-9
    assert red["scope_s"]["ssd.core"] > 0 and red["scope_s"]["mamba"] > 0
    assert red["scope_s"]["other"] > 0


def test_readers_say_nothing_where_there_is_nothing():
    """A configuration without a `stack` section, or a run without a trace:
    None, not an exception (a traced run of another cell, or of an older
    program, leaves the metric out)."""
    ctx = {"cell": {"config": {"transformer_config": {}}, "chips": 1},
           "trace": None, "stats": {}, "peaks": {}}
    assert _stack.sizes_and_counts(ctx) == (None, None)
    assert _stack.picture(ctx) == {}
    assert _stack.scope_share_pct(ctx, "ssd.core") is None
    assert _stack.steps_traced(ctx) is None
    from chipbench.metrics import readers

    for name in ("ssd_share_pct", "mamba_outer_share_pct",
                 "ssd_core_roofline", "train_mfu_stack_pct"):
        assert readers.read(name, dict(ctx)) is None, name


def test_share_from_a_picture():
    ctx = {"stack_scopes": {"busy_s": 2.0, "scope_s": {
        "ssd.core": 0.5, "mamba": 0.25, "other": 1.25}}}
    assert _stack.scope_share_pct(ctx, "ssd.core") == 25.0
    assert _stack.scope_share_pct(ctx, "mamba") == 12.5
    assert _stack.scope_share_pct(ctx, "kda") is None
