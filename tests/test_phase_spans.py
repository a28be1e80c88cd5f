"""Host phases (util/tracing.py phase / observe / steps): the per-process
table and slow ring, the profiler annotations and their clock anchor, and
the sites that emit them: serve hops, the engine loop, the token relay, the
controller's handlers and its loop-lag probe."""
import asyncio
import glob
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu.util import tracing


def _row(name):
    return tracing.phase_table().get(name) or {
        "count": 0, "total_ns": 0, "max_ns": 0, "buckets": []}


def _slow(name):
    return [p for p in tracing.slow_phases() if p["name"] == name]


# ------------------------------------------------------------- the primitive


@pytest.mark.parametrize("ns,bucket", [
    (0, 0), (1, 1), (1000, 10), (1024, 11), (3_000_000, 22),
    (2 ** 60, 39)])
def test_table_folds_count_total_max_and_log2_buckets(ns, bucket):
    name = f"t.bucket.{ns}"
    tracing.observe(name, ns)
    tracing.observe(name, 0)
    row = _row(name)
    assert row["count"] == 2 and row["total_ns"] == ns and row["max_ns"] == ns
    want = [0] * 40
    want[bucket] += 1
    want[0] += 1
    assert row["buckets"] == want
    if ns:  # a bucket holds [2^(b-1), 2^b)
        assert bucket == 39 or 2 ** (bucket - 1) <= ns < 2 ** bucket


def test_bucket_quantile_reads_the_right_bucket():
    name = "t.quantile"
    for _ in range(98):
        tracing.observe(name, 1000)          # bucket 10
    tracing.observe(name, 40_000_000)        # bucket 26
    tracing.observe(name, 40_000_000)
    b = _row(name)["buckets"]
    assert 512 <= tracing.bucket_quantile(b, 0.5) < 1024
    assert 2 ** 25 <= tracing.bucket_quantile(b, 0.99) < 2 ** 26
    assert tracing.bucket_quantile([0] * 40, 0.5) is None


@pytest.mark.parametrize("ns,kept", [
    (tracing.SLOW_NS - 1, False), (tracing.SLOW_NS, True)])
def test_slow_ring_threshold_is_50_ms(ns, kept):
    assert tracing.SLOW_NS == 50_000_000
    name = f"t.slow.{ns}"
    tracing.observe(name, ns, start_ns=123, why="x")
    got = _slow(name)
    assert bool(got) == kept
    if kept:
        assert got[0] == {"name": name, "start_monotonic_ns": 123,
                          "dur_ns": ns, "attrs": {"why": "x"}}
    tracing.observe(name, ns, slow=False)    # a hop: table only
    assert len(_slow(name)) == int(kept) and _row(name)["count"] == 2


def test_slow_ring_is_bounded_at_1024():
    for i in range(tracing.SLOW_RING + 50):
        tracing.observe("t.ring", tracing.SLOW_NS + i)
    slow = tracing.slow_phases()
    assert len(slow) == tracing.SLOW_RING == 1024
    assert slow[-1]["dur_ns"] == tracing.SLOW_NS + tracing.SLOW_RING + 49
    assert _row("t.ring")["count"] == tracing.SLOW_RING + 50


def test_phase_times_on_the_monotonic_clock_and_keeps_attrs():
    before = time.monotonic_ns()
    with tracing.phase("t.phase", live=3):
        time.sleep(0.06)
    after = time.monotonic_ns()
    (p,) = _slow("t.phase")
    assert before <= p["start_monotonic_ns"] <= after
    assert 55_000_000 <= p["dur_ns"] <= after - before
    assert p["attrs"] == {"live": 3}
    with pytest.raises(KeyError):  # an exception passes through, and counts
        with tracing.phase("t.phase"):
            raise KeyError("x")
    assert _row("t.phase")["count"] == 2


def test_phase_in_a_process_without_jax_never_imports_it():
    code = (
        "import sys\n"
        "import ray_tpu\n"
        "from ray_tpu.util import tracing\n"
        "with tracing.phase('a', k=1):\n"
        "    tracing.observe('b', 5)\n"
        "assert set(tracing.phase_table()) == {'a', 'b'}\n"
        "assert 'jax' not in sys.modules, 'phase() imported jax'\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_steps_counts_loop_occupancy_not_awaited_time():
    async def handler():
        time.sleep(0.06)            # holds the loop: one slow step
        await asyncio.sleep(0.12)   # awaited: not counted
        return 7

    async def cancelled():
        await asyncio.sleep(30)

    async def main():
        assert await tracing.steps("t.steps", handler()) == 7
        t = asyncio.ensure_future(tracing.steps("t.steps.c", cancelled()))
        await asyncio.sleep(0.01)
        t.cancel()
        with pytest.raises(asyncio.CancelledError):
            await t
        with pytest.raises(ZeroDivisionError):
            await tracing.steps("t.steps.e", _boom())

    async def _boom():
        await asyncio.sleep(0)
        1 / 0

    asyncio.run(main())
    row = _row("t.steps")
    assert row["count"] == 2                      # before and after the await
    assert 55_000_000 <= row["total_ns"] < 400_000_000   # not the 120 ms awaited
    assert len(_slow("t.steps")) == 1
    assert _row("t.steps.c")["count"] == 2 and _row("t.steps.e")["count"] == 2


# ------------------------------------------------- profiler session (the CPU)


def test_annotations_and_clock_anchor_land_in_the_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: x + 1)
    f(1).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        m0, w0 = time.monotonic_ns(), time.time_ns()
        with tracing.phase("t.outer", live=2, waiting=1):
            with tracing.phase("t.outer.inner"):
                f(2).block_until_ready()
            tracing.observe("t.observed", 4321, start_ns=m0, request_id="r1")
        m1 = time.monotonic_ns()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("t.") or ev.name == "clock_anchor":
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         {k: str(v) for k, v in ev.stats}))
    (outer,), (inner,) = found["t.outer"], found["t.outer.inner"]
    assert outer[2] == {"live": "2", "waiting": "1"}
    assert outer[0] <= inner[0] and inner[1] <= outer[1]  # nest as entered
    (obs,) = found["t.observed"]
    assert obs[2] == {"dur_ns": "4321", "start_monotonic_ns": str(m0),
                      "request_id": "r1"}
    assert outer[0] <= obs[0] <= outer[1]
    (anchor,) = found["clock_anchor"]            # one a session
    assert m0 <= int(anchor[2]["monotonic_ns"]) <= m1
    assert abs(int(anchor[2]["time_ns"]) - w0) < 60e9
    # the anchor maps the trace's clock onto CLOCK_MONOTONIC: the outer
    # phase's start lies between the stamps taken around it
    shift = int(anchor[2]["monotonic_ns"]) - anchor[0]
    assert m0 - 2e6 <= outer[0] + shift <= m1 + 2e6


# ------------------------------------------------------------ the serve hops


def test_hop_end_feeds_the_table_once(monkeypatch):
    from ray_tpu.serve import trace

    shipped = []
    monkeypatch.setattr(trace._shipper, "add",
                        lambda span=None, record=None: shipped.append(span))
    ctx = {"traceparent": "00-" + "a" * 32 + "-" + "b" * 16 + "-01",
           "request_id": "rid-1"}
    hop = trace.start_hop("serve.t_hop", trace_ctx=ctx)
    time.sleep(0.06)
    hop.end(status="ok")
    hop.end()                                      # idempotent
    row = _row("serve.t_hop")
    assert row["count"] == 1 and row["total_ns"] >= 55_000_000
    assert len(shipped) == 1 and shipped[0]["name"] == "serve.t_hop"
    assert abs(shipped[0]["dwell_s"] * 1e9 - row["total_ns"]) < 1e6
    assert not _slow("serve.t_hop")   # request-long by nature: not a stall
    assert not tracing.get_finished_spans()        # one store, not two


# ---------------------------------------------------------------- the engine


def test_engine_emits_every_engine_phase_in_order(monkeypatch):
    import jax

    from ray_tpu.models import transformer as tfm
    from ray_tpu.models.configs import llama_tiny
    from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

    cfg = llama_tiny(remat=False)
    eng = ContinuousBatchingEngine(
        cfg, tfm.init_params(jax.random.key(0), cfg), num_slots=2,
        max_prompt_len=16, max_new_tokens=3)
    seen = []
    fold = tracing._fold
    monkeypatch.setattr(
        tracing, "_fold", lambda name, *a, **k: (
            seen.append(name) if name.startswith("engine.") else None,
            fold(name, *a, **k))[1])
    # No loop yet: the first token's stamp is the oldest unseen one until a
    # consumer has taken it.
    t0 = time.monotonic()
    req = eng.submit([7, 1])
    toks, stamp = eng.peek_stamped(req)
    assert len(toks) == 1 and t0 <= stamp <= time.monotonic()
    assert eng.peek_stamped(req, sent=1) == (toks, None)
    eng.tick()
    assert eng.peek_stamped(req, sent=1)[1] >= stamp
    while eng.tick():
        pass
    assert eng.peek_stamped(req) == (eng.result(req), None)   # finished
    seen.clear()
    stop = threading.Event()
    th = threading.Thread(target=eng.run_forever, args=(stop,))
    th.start()
    try:
        deadline = time.monotonic() + 10           # nothing live: idles
        while "engine.idle" not in seen and time.monotonic() < deadline:
            time.sleep(0.01)
        req = eng.submit([5, 9, 2])
        assert eng.result(req, timeout=60) == eng.peek(req)
    finally:
        stop.set()
        th.join(30)
    # phases are folded as they end: children before their parent
    first = [seen.index(n) for n in (
        "engine.idle", "engine.prefill.pad", "engine.attach.wait",
        "engine.attach.splice", "engine.tick.lock", "engine.tick.dispatch",
        "engine.tick.readback", "engine.tick.emit", "engine.tick")]
    assert first == sorted(first), seen
    tick = [n for n in seen if n.startswith("engine.tick")]
    assert tick[:4] == ["engine.tick.lock", "engine.tick.dispatch",
                        "engine.tick.readback", "engine.tick.emit"]
    assert tick.count("engine.tick") == 2          # 3 tokens: 1 + 2 ticks
    assert _row("engine.tick")["count"] >= 2


# ------------------------------------------- the relay and the controller


def test_run_streaming_emits_put_and_report_and_state_reads_them(
        ray_start_regular):
    import ray_tpu
    from ray_tpu.util import state

    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i

    before = _row("stream.next")["count"]
    assert [ray_tpu.get(r) for r in gen.remote(5)] == list(range(5))
    assert _row("stream.next")["count"] == before + 6   # 5 items + the end
    out = state.phase_table()
    assert out["controller"]["table"]["ctrl.rpc.generator_item"]["count"] >= 5
    assert out["controller"]["table"]["ctrl.rpc.generator_next"]["count"] >= 6
    tables = [w["table"] for w in out["workers"].values()
              if "stream.put" in w.get("table", {})]
    assert tables, out["workers"].keys()
    assert tables[0]["stream.put"]["count"] == 5
    assert tables[0]["stream.report"]["count"] == 5
    assert isinstance(state.slow_phases(), list)


@pytest.mark.parametrize("how", ["holds_loop", "testing_delay"])
def test_controller_handler_that_holds_the_loop_is_named(
        ray_start_regular, how):
    """ctrl.rpc.<kind> counts what a handler holds the loop for. A handler
    that blocks it for 120 ms is in slow_phases() under its kind, and the
    loop-lag probe saw the same stretch. RTPU_TESTING_RPC_DELAY_MS is an
    awaited sleep in core/protocol.py before the controller's dispatch: the
    request takes as long, the loop stays free, and nothing is named."""
    from ray_tpu.core import api
    from ray_tpu.core import context as ctx
    from ray_tpu.testing.fault_injection import rpc_delays

    client = ctx.get_worker_context().client
    ctrl = api._owned_controller
    n0 = len(_slow("ctrl.rpc.kv_get"))
    lag0 = len(_slow("ctrl.loop_lag"))
    if how == "holds_loop":
        async def blocking(conn, msg):
            time.sleep(0.12)
            return None

        ctrl._h_kv_get = blocking
        try:
            t0 = time.monotonic_ns()
            client.request({"kind": "kv_get", "key": "k"})
            t1 = time.monotonic_ns()
        finally:
            del ctrl._h_kv_get
        (p,) = _slow("ctrl.rpc.kv_get")[n0:]
        assert t0 <= p["start_monotonic_ns"] and p["dur_ns"] >= 115_000_000
        assert p["start_monotonic_ns"] + p["dur_ns"] <= t1
        time.sleep(0.2)       # the 50 ms probe fired at least 70 ms late
        assert len(_slow("ctrl.loop_lag")) > lag0
    else:
        with rpc_delays("kv_get=80"):
            t0 = time.monotonic()
            client.request({"kind": "kv_get", "key": "k"})
            assert time.monotonic() - t0 >= 0.08
        assert len(_slow("ctrl.rpc.kv_get")) == n0
    assert _row("ctrl.rpc.kv_get")["count"] >= 1
    assert _row("ctrl.loop_lag")["count"] >= 1
    assert any(n.startswith("ctrl.periodic.") for n in tracing.phase_table())


def test_worker_slow_phase_reaches_the_controllers_ring(ray_start_regular):
    """A slow phase in a worker is a SLOW_PHASE cluster event, which the
    controller's process copies into its own slow ring (tagged with the
    worker's pid): what a reader there sees after shutdown."""
    import os

    import ray_tpu
    from ray_tpu.core import events
    from ray_tpu.util import state

    @ray_tpu.remote
    def slow_one():
        from ray_tpu.util import tracing as tr

        with tr.phase("t.worker.slow", slot=4):
            time.sleep(0.06)
        events.flush_events()
        return os.getpid()

    pid = ray_tpu.get(slow_one.remote())
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not _slow("t.worker.slow"):
        time.sleep(0.05)
    (p,) = _slow("t.worker.slow")
    assert p["attrs"]["pid"] == pid != os.getpid()
    assert p["attrs"]["slot"] == 4 and p["dur_ns"] >= 55_000_000
    evs = state.list_events(kind="SLOW_PHASE")
    assert any(e["data"]["name"] == "t.worker.slow" for e in evs)
