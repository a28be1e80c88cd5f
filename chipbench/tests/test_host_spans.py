"""The host-span reduction (reduce/host_spans.py) on the recorded host+device
trace (reduce/recorded_host_trace.json), whose expectations were computed by
another method (every nanosecond painted; see the file's `what`), and on
small made-up cases of each rule.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.reduce import host_spans as hs  # noqa: E402

REC = hs.load_json(os.path.join(ROOT, "chipbench", "reduce",
                                "recorded_host_trace.json"))


def test_recorded_trace_matches_the_hand_computed_expectations():
    idle = hs.check_recorded()
    assert idle["has_phases"] and idle["devices"] == 1


def test_attribution_sums_to_the_idle_time():
    idle = hs.idle_by_phase(REC)
    gaps = hs.idle_gaps(REC)
    assert sum(b - a for _, a, b, _ in gaps) == idle["idle_ns"]
    assert sum(idle["by_phase_ns"].values()) == idle["idle_ns"]
    assert all(b - a >= hs.SMALL_GAP_NS for _, a, b, _ in gaps)
    w0, w1 = hs.device_window(REC)
    assert idle["window_ns"] == w1 - w0 and 0 < idle["idle_ns"] < w1 - w0


def test_the_launching_thread_is_found_through_the_flows():
    phases = hs.phases_by_thread(REC)
    launches = hs.Launches(REC, phases)
    by_prog = {}
    for _, _, _, nxt in hs.idle_gaps(REC):
        by_prog.setdefault(nxt[0].split("(")[0], set()).add(
            launches.thread_of(*nxt))
    engine = {t for t, sp in phases.items()
              if any(n == "engine.tick" for _, _, n in sp)}
    assert len(engine) == 1
    assert by_prog["jit__tick"] == by_prog["jit__threefry_fold_in"] == engine
    (req,) = by_prog["jit__splice"]        # a request's own thread
    assert req not in engine and any(
        n == "engine.attach.splice" for _, _, n in phases[req])


def test_a_gap_with_no_phase_is_unattributed():
    assert hs.idle_by_phase(REC)["by_phase_ns"]["unattributed"] > 0
    # the same trace without the program's phases: everything unattributed,
    # and the metrics' readers are told the program has none
    bare = dict(REC, host=[h for h in REC["host"]
                           if not hs.PROGRAM.match(h[1])])
    idle = hs.idle_by_phase(bare)
    assert not idle["has_phases"]
    assert idle["by_phase_ns"] == {"unattributed": idle["idle_ns"]}


@pytest.mark.parametrize("spans,want", [
    ([], {"unattributed": 100}),
    ([(0, 40, "a")], {"a": 40, "unattributed": 60}),
    ([(0, 100, "a"), (20, 50, "a.b")], {"a": 70, "a.b": 30}),
    # an observed interval laid over a phase: the later start is innermost
    ([(0, 60, "a"), (30, 100, "hop")], {"a": 30, "hop": 70}),
    ([(-50, 10, "a"), (90, 500, "b")],
     {"a": 10, "b": 10, "unattributed": 80}),
])
def test_split_over_phases_takes_the_innermost_open_phase(spans, want):
    assert hs.split_over_phases(0, 100, spans) == want


def test_stalls_need_a_live_stream_and_overlaps_name_the_slow_phases():
    recs = [{"sent": 0.0, "stamps": [0.1, 0.2, 0.9, 1.0], "done": 1.0},
            {"sent": 0.15, "stamps": [0.25, 0.3], "done": 0.3},
            {"sent": 2.0, "stamps": [2.1], "done": 2.1}]
    # 0.3 -> 0.9: a stream is live; 1.0 -> 2.1: nobody is
    assert hs.stalls(recs, 0.0, 3.0) == [(0.3, 0.9)]
    slow = [{"name": "ctrl.periodic.telemetry", "dur_ns": 400_000_000,
             "start_monotonic_ns": 350_000_000, "attrs": {}},
            {"name": "stream.next", "start_monotonic_ns": 300_000_000,
             "dur_ns": 600_000_000, "attrs": {}},
            {"name": "engine.tick", "start_monotonic_ns": 2_000_000_000,
             "dur_ns": 60_000_000, "attrs": {"pid": 7}}]
    over = hs.overlapping((0.3, 0.9), slow)
    assert [o["name"] for o in over] == ["stream.next",
                                         "ctrl.periodic.telemetry"]
    assert over[1]["overlap_ms"] == 400.0 and over[1]["pid"] == "runner"
    # a wait by design overlaps without attributing
    assert hs.covered_ns((0.3, 0.9), slow) == 400_000_000
    assert hs.covered_ns((0.3, 0.9), slow[1:]) == 0


def test_a_recorded_stall_is_listed_with_the_phase_that_overlapped_it():
    exp = REC["expect"]
    found = hs.stalls(REC["client"]["recs"], *REC["client"]["window"],
                      min_s=exp["stall_min_s"])
    named = {round(st[0], 3): [o["name"] for o in hs.overlapping(
        st, REC["slow"])] for st in found}
    assert named[2.451] == ["engine.attach.wait"]
    assert named[3.523] == ["engine.attach.wait"]
    assert named[-0.587] == ["stream.next"]      # only a wait: none did
    assert hs.covered_ns(found[0], REC["slow"]) == 0


def test_the_anchor_maps_trace_time_onto_the_monotonic_clock():
    shift = hs.clock_shift(REC)
    (anchor,) = [h for h in REC["host"] if h[1] == hs.ANCHOR]
    assert anchor[2] + shift == int(anchor[4]["monotonic_ns"])
    assert hs.clock_shift({"host": [], "device": []}) is None


def test_phase_stats_rebuild_an_observed_interval_from_its_stats():
    raw = {"device": [], "host": [
        ["t#0", "stream.poll_lag", 5000, 10,
         {"dur_ns": "3000", "start_monotonic_ns": "1"}],
        ["t#0", "engine.tick", 1000, 2000, {"live": "2"}],
        ["t#0", "PjitFunction(_tick)", 1100, 50, {}]]}
    assert hs.phases_by_thread(raw) == {
        "t#0": [(1000, 3000, "engine.tick"), (2000, 5000, "stream.poll_lag")]}
    st = hs.phase_stats(raw)
    assert st["stream.poll_lag"]["max_ms"] == 0.003
    assert st["engine.tick"]["count"] == 1


def test_the_metric_readers_walk_a_host_only_trace_and_an_older_program(
        tmp_path, monkeypatch):
    """run.py stops a traced rehearsal before any reader (the CPU has no
    /device:TPU plane), so the three readers are walked here: on a CPU trace
    of a program with phases they find no device idle time (None), read the
    train phases and the controller's table; on a program without phases
    (an older commit: no table, no annotations) each returns None."""
    import jax

    from chipbench import common, inworker
    from chipbench.metrics import readers
    from ray_tpu.util import tracing

    monkeypatch.setattr(inworker, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(common, "RUN_DIR", str(tmp_path))  # no client files
    f = jax.jit(lambda x: x * 2)
    f(1.0).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with tracing.phase("train.shard_batch"):
                pass
            with tracing.phase("train.step"):
                f(2.0)
    finally:
        jax.profiler.stop_trace()
    tracing.observe("ctrl.rpc.kv_get", 7_000_000)
    tracing.observe("ctrl.periodic.telemetry", 21_000_000)
    ctx = {"trace": {"devices": 0}}
    assert readers.read("idle_attributed_pct", ctx) is None
    assert 0 < readers.read("step_host_ms", ctx) < 1000
    assert readers.read("ctrl_loop_block_max_ms", ctx) >= 21.0
    note = ctx["notes"]["host_spans"]
    assert note["ctrl_loop_block_max"][0].startswith("ctrl.")
    assert note["trace_phases_count_p50_p99_max_ms"]["train.step"][0] == 3
    assert "stalls" not in note                      # not a serve cell

    monkeypatch.setattr(hs, "runner_phases", lambda: None)
    monkeypatch.setattr(inworker, "TRACE_DIR", str(tmp_path / "none"))
    old = {"trace": {"devices": 1}}
    for name in ("idle_attributed_pct", "step_host_ms",
                 "ctrl_loop_block_max_ms"):
        assert readers.read(name, old) is None
    assert "notes" not in old
