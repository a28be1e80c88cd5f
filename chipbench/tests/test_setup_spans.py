"""The set-up reader (reduce/setup_spans.py) on a recorded slow ring (a CPU
rehearsal's, reduce/recorded_setup_ring.json) against sums worked out here
by painting every millisecond: the check's stretch cut out, overlaps counted
once, nothing from a ring without the program's phases."""
import copy
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench.reduce import setup_spans as ss  # noqa: E402

MS = 1_000_000


@pytest.fixture
def rec():
    path = os.path.join(os.path.dirname(ss.__file__),
                        "recorded_setup_ring.json")
    with open(path) as f:
        return json.load(f)


def paint(rec):
    """Every millisecond from the runner's start to the window's start, by
    hand: which are the check's, which lie under a phase that counts, and,
    for the chip-owning worker's xla.* phases, the innermost one open."""
    m = rec["marks"]
    proc, window, check = m["proc_ns"], m["window_ns"], m["check_ns"]
    pid = ss.owner_pid(rec["slow"], window)
    early = [p for p in rec["slow"] if p["start_monotonic_ns"] < window]
    mine = [p for p in early if p["attrs"].get("pid") == pid
            and not p["name"].startswith("ctrl.")]
    counted = [p for p in mine if p["name"].split(".")[0] in (
        "boot", "runtime", "xla")] + [
        p for p in early if p["name"].startswith("ctrl.")]
    n_system = n_named = 0
    xla = {name: 0 for name in ss.XLA}
    xla_check = {name: 0 for name in ss.XLA}
    for t in range(proc, window, MS):
        at = t + MS // 2
        in_check = check is not None and check[0] <= at < check[1]
        open_ = [p for p in mine if p["name"] in ss.XLA and
                 p["start_monotonic_ns"] <= at
                 < p["start_monotonic_ns"] + p["dur_ns"]]
        if open_:
            inner = max(open_, key=lambda p: p["start_monotonic_ns"])
            (xla_check if in_check else xla)[inner["name"]] += 1
        if in_check:
            continue
        n_system += 1
        n_named += any(p["start_monotonic_ns"] <= at
                       < p["start_monotonic_ns"] + p["dur_ns"]
                       for p in counted)
    return {"system_ms": n_system, "named_ms": n_named, "xla_ms": xla,
            "xla_check_ms": xla_check, "pid": pid}


def close(got_s, want_ms, entries):
    """Within a millisecond an entry's edge (painting rounds each)."""
    return abs(got_s * 1e3 - want_ms) <= 1.0 * max(1, entries) + 1e-6


def test_recorded_ring_reduces_to_what_painting_gives(rec):
    got = ss.split(rec["slow"], rec["marks"])
    want = paint(rec)
    notes = got["notes"]
    assert notes["owner_pid"] == want["pid"]
    n = len(rec["slow"])
    assert close(notes["system_setup_s"], want["system_ms"], 2)
    assert close(notes["named_s"], want["named_ms"], n)
    assert abs(got["setup_named_pct"]
               - 100.0 * want["named_ms"] / want["system_ms"]) < 0.5
    for name, metric in ss.XLA.items():
        assert close(got[metric], want["xla_ms"][name], n), metric
        assert close(notes["in_check_s"][metric],
                     want["xla_check_ms"][name], n), metric
    # the rehearsal had a warm cache and a reference comparison: both sides
    # of the cut hold something, and nothing was compiled
    assert got["setup_cache_read_s"] > 0 and got["setup_compile_s"] == 0
    assert notes["in_check_s"]["setup_trace_s"] > 0
    assert got["setup_trace_s"] > 0 and got["setup_lower_s"] > 0
    assert 0 < got["setup_named_pct"] <= 100
    assert got["setup_boot_s"] > 0 and got["setup_runtime_init_s"] > 0
    assert notes["dropped_before"] == 0
    assert notes["cache_read_plus_compile_seen"] == sum(
        p["name"] in ("xla.cache_read", "xla.compile")
        and p["attrs"].get("pid") == want["pid"] for p in rec["slow"])
    top = notes["top_programs_trace_lower_read_compile_ms"]
    assert 1 <= len(top) <= 5 and all(len(r) == 5 for r in top)
    assert [sum(r[1:]) for r in top] == sorted(
        (sum(r[1:]) for r in top), reverse=True)


def test_the_checks_stretch_is_cut_out(rec):
    """Without the check's marks its programs count as the system's, and
    the system's set-up is longer by the stretch."""
    with_ = ss.split(rec["slow"], rec["marks"])
    marks = dict(rec["marks"], check_ns=None)
    without = ss.split(rec["slow"], marks)
    a, b = rec["marks"]["check_ns"]
    assert abs(without["notes"]["system_setup_s"]
               - with_["notes"]["system_setup_s"] - (b - a) / 1e9) < 1e-6
    for metric in ss.XLA.values():
        assert abs(without[metric] - with_[metric]
                   - with_["notes"]["in_check_s"][metric]) < 1e-6
        assert without["notes"]["in_check_s"][metric] == 0
    want = paint(dict(rec, marks=marks))
    assert close(without["notes"]["named_s"], want["named_ms"],
                 len(rec["slow"]))


def test_overlaps_are_not_counted_twice(rec):
    """A nested long trace (the program gives its parent `self_ns`), the
    same stretch reported by a second phase, and a controller stretch over
    a worker's phase: sums and the named share still count an instant
    once."""
    rec = copy.deepcopy(rec)
    pid = ss.owner_pid(rec["slow"], rec["marks"]["window_ns"])
    lower = next(p for p in rec["slow"] if p["name"] == "xla.lower"
                 and p["attrs"].get("pid") == pid
                 and p["start_monotonic_ns"] < rec["marks"]["check_ns"][0])
    base = ss.split(rec["slow"], rec["marks"])
    s, d = lower["start_monotonic_ns"], lower["dur_ns"]
    child = {"name": "xla.trace", "start_monotonic_ns": s + d // 4,
             "dur_ns": d // 2, "attrs": {"pid": pid, "fun_name": "rule"}}
    lower["attrs"]["self_ns"] = d - d // 2
    twin = {"name": "ctrl.rpc.kv_get", "start_monotonic_ns": s,
            "dur_ns": d, "attrs": {}}
    rec["slow"] += [child, twin]
    got = ss.split(rec["slow"], rec["marks"])
    assert abs(got["setup_lower_s"] - (base["setup_lower_s"] - d // 2 / 1e9)
               ) < 1e-6
    assert abs(got["setup_trace_s"] - (base["setup_trace_s"] + d // 2 / 1e9)
               ) < 1e-6
    assert got["notes"]["named_s"] == base["notes"]["named_s"]
    want = paint(rec)
    for name, metric in ss.XLA.items():
        assert close(got[metric], want["xla_ms"][name], len(rec["slow"]))
    assert close(got["notes"]["named_s"], want["named_ms"], len(rec["slow"]))


@pytest.mark.parametrize("ring", ["empty", "older_commit", "after_window"])
def test_none_without_the_programs_phases(rec, ring):
    """An empty ring, a parent commit's (controller stretches and a slow
    `train.step`, no boot / runtime / xla phase) and one whose phases all
    start after the window: no metric, and nothing raised."""
    slow = {"empty": [],
            "older_commit": [p for p in rec["slow"] if p["name"].split(
                ".")[0] not in ("boot", "runtime", "xla")
                and p["name"] != "ctrl.worker_spawn"],
            "after_window": [dict(p, start_monotonic_ns=rec["marks"][
                "window_ns"] + 1) for p in rec["slow"]]}[ring]
    if ring == "older_commit":
        assert slow   # it does hold something
    assert ss.split(slow, rec["marks"]) is None


def test_picture_leaves_the_metrics_out_when_the_ring_has_nothing(
        monkeypatch):
    from chipbench.reduce import host_spans

    ctx = {"phases": {"cluster_s": 1.0, "backend_s": 2.0, "weights_s": 1.0,
                      "check_s": 3.0, "warm_s": 1.0, "ready_s": 8.0,
                      "setup_s": 8.5, "other_s": 0.5}}
    monkeypatch.setattr(host_spans, "runner_phases", lambda: None)
    assert ss.picture(ctx) == {} and "notes" not in ctx
    ctx.pop("setup_spans")
    monkeypatch.setattr(host_spans, "runner_phases", lambda: {
        "table": {}, "slow": [{"name": "ctrl.loop_lag", "dur_ns": 6 * 10 ** 7,
                               "start_monotonic_ns": 5, "attrs": {}}]})
    assert ss.picture(ctx) == {} and "notes" not in ctx


def test_marks_follow_the_stamps_in_time_order(monkeypatch):
    from chipbench import common

    monkeypatch.setattr(common, "proc_start_wall", lambda: 1000.0)
    ctx = {"phases": {"cluster_s": 1.0, "backend_s": 2.0, "weights_s": 1.5,
                      "check_s": 3.0, "warm_s": 1.0, "ready_s": 8.5,
                      "setup_s": 9.0, "other_s": 0.5}}
    m = ss.marks_of(ctx)
    rel = lambda ns: round((ns - m["proc_ns"]) / 1e9, 6)  # noqa: E731
    assert [rel(t) for t in m["check_ns"]] == [4.5, 7.5]
    assert rel(m["window_ns"]) == 9.0
    del ctx["phases"]["check_s"]   # a cell without a check stamp
    assert ss.marks_of(ctx)["check_ns"] is None
