"""The reader of what slow stretches did with their time
(reduce/slow_causes.py) on a recorded slow ring (a CPU rehearsal's,
reduce/recorded_stall_ring.json: one induced stall, one stall that holds the
profiler's stop, controller stretches and the runtime's start with their
rusage deltas) against the sums worked out by hand beside it, and on rings
without such entries."""
import copy
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench.reduce import slow_causes as sc  # noqa: E402

HERE = os.path.dirname(sc.__file__)


@pytest.fixture
def rec():
    with open(os.path.join(HERE, "recorded_stall_ring.json")) as f:
        return json.load(f)


def test_the_recorded_ring_reads_as_worked_out_by_hand(rec):
    exp = rec["expect"]
    got = sc.split(rec["slow"], rec["marks"])
    for m in sc.METRICS:
        assert got[m] == pytest.approx(exp[m], abs=1e-9), m
    n = got["notes"]
    assert n["owner_pid"] == exp["owner_pid"] and n["stalls_counted"] == 1
    assert n["runtime_inblock"] == exp["runtime_inblock"]
    assert n["runtime_majflt"] == exp["runtime_majflt"]
    assert [p["name"] for p in n["runtime"]] == ["runtime.import_jax"]
    assert set(sc.SPENT) <= set(n["runtime"][0])
    for k, v in exp["longest_ctrl"].items():
        assert n["longest_ctrl"][k] == pytest.approx(v), k
    # the loop's lag over the same seconds, beside the stretch
    for k, v in exp["longest_loop_lag"].items():
        assert n["longest_loop_lag"][k] == pytest.approx(v), k
    assert n["owner_gc_count_total_max_ms"] == pytest.approx(
        exp["owner_gc_count_total_max_ms"])
    # every stall record whole, the profiler's own among them
    assert [s["profiler"] for s in n["stalls"]] == [1, 0]
    assert n["stalls"][0]["dur_ms"] == pytest.approx(exp["profiler_stall_ms"])
    ind = n["stalls"][1]
    for k in ("index", "watchdog_late_ms", "cpu_ns", "outside_ms"):
        assert ind[k] == exp["induced_stall"][k]
    assert exp["induced_stall"]["stack_names"] in ind["stack"]
    assert ind["at_window_s"] > 0 and {"profiler", "gc_ms"} <= set(ind)


def test_the_sums_are_plain_sums_over_the_entries(rec):
    """The same three numbers by loops written out here."""
    window, pid = rec["marks"]["window_ns"], rec["expect"]["owner_pid"]
    stall = runtime = ctrl = 0
    for p in rec["slow"]:
        a = p["attrs"]
        if (p["name"] == "train.stall" and a["pid"] == pid
                and not a["profiler"] and p["start_monotonic_ns"] >= window):
            stall += p["dur_ns"]
        if (p["name"].startswith("runtime.") and a.get("pid") == pid
                and p["start_monotonic_ns"] < window):
            runtime += p["dur_ns"] - a["cpu_ns"]
        if p["name"].startswith(("ctrl.rpc.", "ctrl.periodic.")):
            ctrl += p["dur_ns"] - a["cpu_ns"]
    got = sc.split(rec["slow"], rec["marks"])
    assert got["step_stall_ms"] == stall / 1e6
    assert got["setup_runtime_wait_s"] == runtime / 1e9
    assert got["ctrl_loop_wait_ms"] == ctrl / 1e6


@pytest.mark.parametrize("change,metric,want", [
    ("no_stall", "step_stall_ms", 0.0),
    ("no_ctrl", "ctrl_loop_wait_ms", 0.0),
    ("profiler_off", "step_stall_ms", 701.438078 + 425.579296),
    ("stall_before_window", "step_stall_ms", 0.0),
    ("stall_of_another_pid", "step_stall_ms", 0.0),
    ("computing_ctrl", "ctrl_loop_wait_ms", 152.608658 - 101.43556),
    ("frozen_in_backend_init", "ctrl_loop_wait_ms", 152.608658),
    ("no_lag", "ctrl_loop_wait_ms", 152.608658),
])
def test_what_counts(rec, change, metric, want):
    slow = copy.deepcopy(rec["slow"])
    stalls = [p for p in slow if p["name"] == "train.stall"]
    if change == "no_stall":
        slow = [p for p in slow if p["name"] != "train.stall"]
    elif change == "no_ctrl":
        slow = [p for p in slow if not p["name"].startswith(sc.CTRL)]
    elif change == "profiler_off":
        stalls[0]["attrs"]["profiler"] = 0
    elif change == "stall_before_window":
        stalls[1]["start_monotonic_ns"] = rec["marks"]["window_ns"] - 1
    elif change == "stall_of_another_pid":
        stalls[1]["attrs"]["pid"] = 1
    elif change == "computing_ctrl":   # cpu beyond the wall never goes below 0
        p = max((p for p in slow if p["name"].startswith(sc.CTRL)),
                key=lambda p: p["dur_ns"])
        p["attrs"]["cpu_ns"] = p["dur_ns"] + 5_000_000
    elif change == "frozen_in_backend_init":   # the owner's backend starts
        slow.append({"name": sc.BACKEND, "dur_ns": 10 ** 9,    # meanwhile
                     "start_monotonic_ns": 106506025694912 - 5 * 10 ** 8,
                     "attrs": dict({k: 0 for k in sc.SPENT}, cpu_ns=10 ** 7,
                                   pid=rec["expect"]["owner_pid"])})
    elif change == "no_lag":
        slow = [p for p in slow if p["name"] != sc.LAG]
    got = sc.split(slow, rec["marks"])
    assert got[metric] == pytest.approx(want, abs=1e-9)
    assert all(isinstance(got[m], float) for m in sc.METRICS)
    notes = got["notes"]
    if change == "no_ctrl":
        assert "longest_ctrl" not in notes
        assert notes["longest_loop_lag"]["under"] == []
    elif change == "frozen_in_backend_init":
        assert notes["longest_ctrl"]["in_backend_init"] is True
        assert notes["longest_loop_lag"]["in_backend_init"] is True
    elif change == "no_lag":
        assert notes["longest_ctrl"]["loop_lag_ms"] == 0.0
        assert "longest_loop_lag" not in notes


def test_an_older_programs_ring_reads_nothing(rec):
    """No entry says what its thread did: None, and `picture` leaves the
    three metrics out (the parent commit under this PR's benchmark files)."""
    slow = copy.deepcopy(rec["slow"])
    for p in slow:
        for k in sc.SPENT:
            p["attrs"].pop(k, None)
    assert sc.split(slow, rec["marks"]) is None
    with open(os.path.join(HERE, "recorded_setup_ring.json")) as f:
        old = json.load(f)
    assert sc.split(old["slow"], old["marks"]) is None
    assert sc.split([], rec["marks"]) is None


def test_picture_reads_this_processes_ring_once(rec, monkeypatch):
    from chipbench.reduce import host_spans, setup_spans

    monkeypatch.setattr(host_spans, "runner_phases",
                        lambda: {"table": {}, "slow": rec["slow"]})
    monkeypatch.setattr(setup_spans, "marks_of", lambda ctx: rec["marks"])
    ctx = {}
    pic = sc.picture(ctx)
    assert pic == {m: pytest.approx(rec["expect"][m]) for m in sc.METRICS}
    assert ctx["notes"]["slow_causes"]["stalls_counted"] == 1
    assert sc.picture(ctx) is pic
    monkeypatch.setattr(host_spans, "runner_phases", lambda: None)
    assert sc.picture({}) == {}


@pytest.mark.parametrize("name", sc.METRICS)
def test_each_metric_has_a_reader_and_a_manifest_entry(name, rec, monkeypatch):
    from chipbench import common
    from chipbench.metrics import readers
    from chipbench.reduce import host_spans, setup_spans

    monkeypatch.setattr(host_spans, "runner_phases",
                        lambda: {"table": {}, "slow": rec["slow"]})
    monkeypatch.setattr(setup_spans, "marks_of", lambda ctx: rec["marks"])
    assert readers.read(name, {}) == pytest.approx(rec["expect"][name])
    monkeypatch.setattr(host_spans, "runner_phases",
                        lambda: {"table": {}, "slow": []})
    assert readers.read(name, {}) is None
    (entry,) = [m for m in common.load_manifest()["per_layer"]
                if m["name"] == name]
    assert entry["source"] == "program_span"
    cells = [w["name"] for w in common.load_manifest()["workloads"]]
    assert entry.get("workloads", cells) == cells
