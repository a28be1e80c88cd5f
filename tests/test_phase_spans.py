"""Host phases (util/tracing.py phase / observe / steps): the per-process
table and slow ring, the profiler annotations and their clock anchor, and
the sites that emit them: serve hops, the engine loop, the token relay, the
controller's handlers and its loop-lag probe."""
import asyncio
import collections
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
    assert p["attrs"]["live"] == 3
    assert set(p["attrs"]) == {"live", "over_ns", *tracing._SPENT}
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
        "assert set(tracing.phase_table()) - {'host.gc'} == {'a', 'b'}\n"
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
        for _ in range(2 * tr._SLOW_EVENTS_PER_10S):   # the limit, run dry
            tr.observe("t.worker.flood", tr.SLOW_NS)
        b, t = tr._Beat("t.worker"), time.monotonic_ns()
        for i in range(10):
            b.tick(t + i * 20_000_000)
        b.tick(t + 800_000_000)    # 9 periods of 20 ms, then one of 620
        events.flush_events()
        return os.getpid()

    pid = ray_tpu.get(slow_one.remote())
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not _slow("t.worker.stall"):
        time.sleep(0.05)
    (p,) = _slow("t.worker.slow")
    assert p["attrs"]["pid"] == pid != os.getpid()
    assert p["attrs"]["slot"] == 4 and p["dur_ns"] >= 55_000_000
    # what the worker's thread did with the time reaches this ring too
    assert all(isinstance(p["attrs"][k], int) for k in tracing._SPENT)
    assert p["attrs"]["cpu_ns"] < p["dur_ns"] / 2
    assert p["attrs"]["nvcsw"] >= _counts_switches()
    evs = state.list_events(kind="SLOW_PHASE")
    assert any(e["data"]["name"] == "t.worker.slow" for e in evs)
    # a stall record is not the rate limit's to drop
    assert len(_slow("t.worker.flood")) < 2 * tracing._SLOW_EVENTS_PER_10S
    (st,) = _slow("t.worker.stall")
    assert st["dur_ns"] == 600_000_000 and st["attrs"]["pid"] == pid
    assert st["attrs"]["index"] == 11 and st["attrs"]["median_ms"] == 20.0
    assert st["attrs"]["dropped_before"] >= 1


# ------------------------------------- set-up: xla.*, runtime.*, boot.* (PR 35)

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_READ = "/jax/compilation_cache/cache_retrieval_time_sec"


def _by_fun(fun):
    """Slow-ring entries of the xla.* phases that name `fun`."""
    return [p for p in tracing.slow_phases() if p["name"].startswith("xla.")
            and p["attrs"].get("fun_name") in (fun, f"jit({fun})")]


@pytest.fixture
def watched(monkeypatch):
    """The listeners on, and every phase long enough for the slow ring, so
    that a test reads names and attributes there."""
    from ray_tpu.util import jaxenv

    jaxenv.watch_compiles()
    monkeypatch.setattr(tracing, "SLOW_NS", 0)
    return jaxenv


def test_first_call_folds_into_trace_lower_and_compile_second_into_nothing(
        watched):
    import jax
    import jax.numpy as jnp

    def p35_first_call(x):
        return jnp.tanh(x) * 3

    f = jax.jit(p35_first_call)
    before = time.monotonic_ns()
    f(jnp.ones((4, 4))).block_until_ready()
    after = time.monotonic_ns()
    got = _by_fun("p35_first_call")
    names = [p["name"] for p in got]
    assert names[:2] == ["xla.trace", "xla.lower"], names
    assert names[2:] in (["xla.compile"], ["xla.cache_read"]), names
    assert got[0]["attrs"]["fun_name"] == "p35_first_call"
    assert got[1]["attrs"]["fun_name"] == "jit(p35_first_call)"
    assert got[2]["attrs"]["cache"] in ("hit", "miss", "off")
    for p in got:   # where the work was, on the one clock
        assert before - 5e6 <= p["start_monotonic_ns"]
        assert p["start_monotonic_ns"] + p["dur_ns"] <= after + 5e6
    starts = [p["start_monotonic_ns"] for p in got]
    assert starts == sorted(starts)
    rows = {n: _row(n)["count"] for n in ("xla.trace", "xla.lower",
                                          "xla.compile", "xla.cache_read")}
    f(jnp.ones((4, 4))).block_until_ready()   # compiled: nothing
    assert len(_by_fun("p35_first_call")) == 3
    assert rows == {n: _row(n)["count"] for n in rows}


@pytest.mark.parametrize("how,want", [
    ("hit", [("xla.cache_read", "hit")]),
    ("miss", [("xla.compile", "miss")]),
    ("off", [("xla.compile", "off")])])
def test_cache_read_is_named_after_its_program_and_a_hit_compiles_nothing(
        watched, how, want):
    """The read carries no name: it arrives on the compiling thread just
    before its program's compile span, which names it. A hit is the read
    alone; a compile says whether a cache was asked at all."""
    import jax.monitoring as mon

    fun = f"jit(p35_cache_{how})"
    t1 = time.time()
    t0 = t1 - 0.5
    if how != "off":
        mon.record_event(_ASKED)
    if how == "hit":
        mon.record_event_duration_secs(_READ, 0.2)
    now = time.monotonic_ns()
    mon.record_event_time_span(_COMPILE, t0, t1, fun_name=fun)
    got = _by_fun(f"p35_cache_{how}")
    assert [(p["name"], p["attrs"]["cache"]) for p in got] == want
    (p,) = got
    if how == "hit":
        assert p["dur_ns"] == 200_000_000
        assert abs(p["start_monotonic_ns"] + p["dur_ns"] - now) < 50e6
        assert abs(p["attrs"]["span_ns"] - 500_000_000) < 1000
    else:
        assert abs(p["dur_ns"] - 500_000_000) < 1000
        assert abs(p["start_monotonic_ns"] + p["dur_ns"] - now) < 50e6
    # the read belonged to that program only: the next compile is its own
    mon.record_event_time_span(_COMPILE, t0, t1,
                               fun_name=f"jit(p35_next_{how})")
    (nxt,) = _by_fun(f"p35_next_{how}")
    assert (nxt["name"], nxt["attrs"]["cache"]) == ("xla.compile", "off")


@pytest.mark.parametrize("bad", [
    lambda m: m.record_event_time_span(_TRACE, "then", None, fun_name=7),
    lambda m: m.record_event_time_span(_COMPILE, None, 3.0),
    lambda m: m.record_event_duration_secs(_READ, "long"),
    lambda m: m.record_event(None, what=object()),
    lambda m: m.record_event_time_span(_LOWER, 1.0, float("nan"))])
def test_a_malformed_event_raises_nothing_into_jax(watched, bad):
    import jax
    import jax.monitoring as mon

    bad(mon)   # jax.monitoring itself guards nothing: a raise lands in jit
    assert int(jax.jit(lambda x: x + 2)(1)) == 3


def test_watch_compiles_twice_registers_once(watched):
    import jax.monitoring as mon

    watched.watch_compiles()
    watched.watch_compiles()
    n = _row("xla.trace")["count"]
    t = time.time()
    mon.record_event_time_span(_TRACE, t - 0.001, t, fun_name="p35_once")
    assert _row("xla.trace")["count"] == n + 1
    assert len(_by_fun("p35_once")) == 1


def test_nested_spans_are_counted_once_through_self_ns(monkeypatch):
    """JAX reports a nested trace inside its parent's span, the child
    first. A parent carries `self_ns`, its time less that of the spans
    inside it that are long enough for the slow ring; shorter ones stay in
    its own time, so sums of `self_ns` over the ring count an instant once."""
    import jax.monitoring as mon

    from ray_tpu.util import jaxenv

    jaxenv.watch_compiles()
    # the long spans this thread remembers from the tests before (one of 500
    # ms, 0.6-1.5 s ago by the load) are not inside the ones made here
    getattr(jaxenv._compiling, "long", collections.deque()).clear()
    t = time.time()
    span = lambda a, b, fun, ev=_TRACE: mon.record_event_time_span(  # noqa
        ev, t - a, t - b, fun_name=fun)
    span(1.2, 1.1, "p35n_before")       # ends before the parent starts
    span(0.90, 0.80, "p35n_child_a")    # 100 ms, inside
    span(0.70, 0.69, "p35n_short")      # 10 ms: not in the ring
    span(0.55, 0.45, "p35n_grandchild")  # 100 ms, inside child_b
    span(0.60, 0.40, "p35n_child_b")    # 200 ms, inside the parent
    span(0.30, 0.20, "jit(p35n_inner)", _COMPILE)   # a compile a trace needed
    span(1.00, 0.10, "p35n_parent")
    by = {p["attrs"]["fun_name"]: p for p in tracing.slow_phases()
          if "p35n_" in str(p["attrs"].get("fun_name", ""))}
    assert "p35n_short" not in by
    assert "self_ns" not in by["p35n_child_a"]["attrs"]
    assert abs(by["p35n_child_b"]["attrs"]["self_ns"] - 100_000_000) < 3000
    parent = by["p35n_parent"]
    assert abs(parent["dur_ns"] - 900_000_000) < 1000
    assert abs(parent["attrs"]["self_ns"] - 500_000_000) < 3000
    own = sum(p["attrs"].get("self_ns", p["dur_ns"]) for n, p in by.items()
              if n != "p35n_before")
    assert abs(own - parent["dur_ns"]) < 5000   # the union, counted once
    span(2.00, 0.05, "p35n_grand")   # later, around all of it
    grand = [p for p in tracing.slow_phases()
             if p["attrs"].get("fun_name") == "p35n_grand"][-1]
    assert abs(grand["attrs"]["self_ns"] - 950_000_000) < 5000


def test_a_real_nested_jit_is_reported_inside_its_parent(watched):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def p35_in(x):
        return jnp.sin(x) * 2

    @jax.jit
    def p35_out(x):
        return p35_in(x) + p35_in(x * 3)

    p35_out(jnp.ones((8,))).block_until_ready()
    (outer,) = [p for p in _by_fun("p35_out") if p["name"] == "xla.trace"]
    inner = [p for p in _by_fun("p35_in") if p["name"] == "xla.trace"]
    assert inner and all(
        outer["start_monotonic_ns"] <= p["start_monotonic_ns"]
        and p["start_monotonic_ns"] + p["dur_ns"]
        <= outer["start_monotonic_ns"] + outer["dur_ns"] + 1000
        for p in inner)   # whole durations double-count ...
    assert outer["attrs"]["self_ns"] <= outer["dur_ns"] - sum(
        p["dur_ns"] for p in inner) + 1000   # ... self_ns does not


def test_devices_tells_the_runtimes_start_once():
    code = (
        "import sys\n"
        "from ray_tpu.util import jaxenv, tracing\n"
        "assert 'jax' not in sys.modules\n"
        "devs = jaxenv.devices()\n"
        "t = tracing.phase_table()\n"
        "assert t['runtime.import_jax']['count'] == 1, t\n"
        "assert t['runtime.backend_init']['count'] == 1, t\n"
        "slow = {p['name']: p for p in tracing.slow_phases()}\n"
        "assert slow['runtime.import_jax']['dur_ns'] >= tracing.SLOW_NS\n"
        "assert len(jaxenv.devices(local=True)) == len(devs)\n"
        "t = tracing.phase_table()\n"
        "assert t['runtime.import_jax']['count'] == 1\n"
        "assert t['runtime.backend_init']['count'] == 1\n"
        "import jax\n"
        "jax.jit(lambda x: x + 1)(1)\n"
        "assert tracing.phase_table()['xla.trace']['count'] >= 1\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=180)
    assert r.returncode == 0, r.stderr[-2000:]


def test_backend_init_carries_platform_and_count(monkeypatch):
    import jax

    from ray_tpu.util import jaxenv

    monkeypatch.setattr(jaxenv, "_listed", False)
    monkeypatch.setattr(tracing, "SLOW_NS", 0)
    devs = jaxenv.devices()
    assert devs == jax.devices()
    p = _slow("runtime.backend_init")[-1]
    assert {k: v for k, v in p["attrs"].items()
            if k not in tracing._SPENT + ("over_ns",)} == {
        "platform": devs[0].platform, "count": len(devs)}
    assert p["attrs"]["cpu_ns"] >= 0 and p["attrs"]["inblock"] >= 0


class _Worker:
    role = "worker"
    extra = {"worker_id": "w35"}


def _as_worker(monkeypatch, initialized=True):
    """This process as a registered worker whose cluster events land in a
    list."""
    from ray_tpu.core import context as ctx
    from ray_tpu.core import events

    sent = []
    monkeypatch.setattr(ctx, "is_initialized", lambda: initialized)
    monkeypatch.setattr(ctx, "get_worker_context", lambda: _Worker)
    monkeypatch.setattr(events, "enabled", lambda: True)
    monkeypatch.setattr(events, "emit", lambda *a, **kw: sent.append(kw))
    monkeypatch.setattr(tracing, "_slow_event_budget", [
        float(tracing._SLOW_EVENTS_PER_10S), time.monotonic(), 0])
    # a slow collection's event, from an earlier test or during this one,
    # would be one more through the limit
    monkeypatch.setattr(tracing, "_deferred", collections.deque(maxlen=0))
    return sent


def test_dropped_before_counts_what_the_rate_limit_dropped(monkeypatch):
    sent = _as_worker(monkeypatch)
    n = tracing._SLOW_EVENTS_PER_10S + 9
    for i in range(n):
        tracing.observe("t.flood", tracing.SLOW_NS, fun_name="f", i=i)
    assert len(sent) == tracing._SLOW_EVENTS_PER_10S   # the bucket, then dry
    assert all("dropped_before" not in e["data"] for e in sent)
    assert sent[0]["data"]["attrs"] == {"fun_name": "f", "i": 0}
    tracing._slow_event_budget[1] -= 1.0   # a second later: 3.2 tokens
    tracing.observe("t.flood", tracing.SLOW_NS)
    tracing.observe("t.flood", tracing.SLOW_NS)
    assert sent[-2]["data"]["dropped_before"] == 9
    assert "dropped_before" not in sent[-1]["data"]   # told once
    # the controller's side keeps the count on the ring's entry
    tracing.ingest_slow_event({"worker_id": "w35", "data": dict(
        sent[-2]["data"], pid=1)})
    assert tracing.slow_phases()[-1]["attrs"]["dropped_before"] == 9


def test_phases_from_before_the_context_are_sent_once_after_it(monkeypatch):
    from ray_tpu.core import context as ctx

    sent = _as_worker(monkeypatch, initialized=False)
    monkeypatch.setattr(tracing, "_unsent", [])
    tracing.observe("boot.interpreter", tracing.SLOW_NS, start_ns=10)
    tracing.observe("boot.imports", tracing.SLOW_NS + 1, start_ns=20)
    tracing.observe("t.short", 5)
    assert sent == []   # no worker context yet: held, not lost
    monkeypatch.setattr(ctx, "is_initialized", lambda: True)
    tracing.send_unsent()
    assert [e["data"]["name"] for e in sent] == [
        "boot.interpreter", "boot.imports"]
    assert [e["data"]["start_monotonic_ns"] for e in sent] == [10, 20]
    tracing.send_unsent()   # once
    assert len(sent) == 2 and tracing._unsent is None
    tracing.observe("boot.connect", tracing.SLOW_NS)   # now sent as it ends
    assert sent[-1]["data"]["name"] == "boot.connect"


def test_a_spawned_worker_tells_its_boot_in_order(ray_start_regular):
    """boot.interpreter, boot.imports, boot.connect in a worker's own
    table; those of 50 ms or more also in the controller's ring (sent after
    registration), beside the controller's `ctrl.worker_spawn` of the same
    pid; an actor's constructor under `boot.actor_init`. No wall-clock
    limit: presence and the order of starts."""
    import os

    import ray_tpu
    from ray_tpu.core import events
    from ray_tpu.util import state

    @ray_tpu.remote
    class P35Slow:
        def __init__(self):
            time.sleep(0.06)

        def pid(self):
            events.flush_events()
            return os.getpid()

    a = P35Slow.remote()
    pid = ray_tpu.get(a.pid.remote())
    out = state.phase_table()
    dump = next(w for w in out["workers"].values()
                if "boot.actor_init" in w.get("table", {}))
    for name in ("boot.interpreter", "boot.imports", "boot.connect"):
        assert dump["table"][name]["count"] == 1, dump["table"].keys()
    order = ["boot.interpreter", "boot.imports", "boot.connect",
             "boot.actor_init"]
    starts = {p["name"]: p["start_monotonic_ns"] for p in dump["slow"]
              if p["name"] in order}
    assert "boot.interpreter" in starts and "boot.actor_init" in starts
    seen = [n for n in order if n in starts]
    assert [starts[n] for n in seen] == sorted(starts[n] for n in seen)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not [
            p for p in _slow("boot.actor_init")
            if p["attrs"].get("pid") == pid]:
        time.sleep(0.05)
    ring = [p for p in tracing.slow_phases() if p["attrs"].get("pid") == pid]
    boot = {p["name"]: p for p in ring if not p["name"].startswith("ctrl.")}
    assert boot["boot.actor_init"]["attrs"]["cls"] == "P35Slow"
    assert boot["boot.interpreter"]["start_monotonic_ns"] \
        == starts["boot.interpreter"]
    (spawn,) = [p for p in ring if p["name"] == "ctrl.worker_spawn"]
    # the controller's view of the same start: Popen .. registration holds
    # the worker's interpreter start
    assert spawn["start_monotonic_ns"] - 20e6 \
        <= starts["boot.interpreter"] \
        <= spawn["start_monotonic_ns"] + spawn["dur_ns"]


# ------------- why a stretch was slow: rusage deltas, host.gc, beat (PR 51)

_DELTAS = tracing._SPENT + ("over_ns",)


def _counts_switches():
    """1 where this kernel fills a thread's switch counts, 0 where it reads
    them as 0 (a sandbox's 4.4.0, as the chip machines run)."""
    import resource

    n0 = resource.getrusage(resource.RUSAGE_THREAD).ru_nvcsw
    time.sleep(0.002)
    return int(resource.getrusage(resource.RUSAGE_THREAD).ru_nvcsw > n0)


def _burn(cpu_ns):
    """Compute until this thread has had `cpu_ns` of the CPU."""
    c0 = time.thread_time_ns()
    while time.thread_time_ns() - c0 < cpu_ns:
        pass


@pytest.mark.parametrize("how", ["busy", "sleep"])
@pytest.mark.parametrize("kind", ["phase", "steps"])
def test_a_slow_stretch_carries_what_its_thread_did(kind, how):
    """The five rusage deltas and the interval they cover, on a slow `phase`
    and a slow `steps` stretch: a loop that computes reads `cpu_ns` near its
    wall, a sleep near none of it and, where the kernel counts them, at
    least one voluntary switch."""
    name = f"t.spent.{kind}.{how}"
    work = (lambda: _burn(80_000_000)) if how == "busy" else (
        lambda: time.sleep(0.12))
    if kind == "phase":
        with tracing.phase(name, k=1):
            work()
    else:
        async def handler():
            work()

        async def main():
            await tracing.steps(name, handler())

        asyncio.run(main())
    (p,) = _slow(name)
    a = p["attrs"]
    assert set(a) == set(_DELTAS) | ({"k"} if kind == "phase" else set())
    assert all(isinstance(a[k], int) and a[k] >= 0 for k in _DELTAS)
    # the stamp was at most STAMP_NS old when the stretch began
    assert p["dur_ns"] <= a["over_ns"] <= p["dur_ns"] + tracing.STAMP_NS
    if how == "busy":
        assert a["cpu_ns"] >= 75_000_000
    else:
        assert a["cpu_ns"] < p["dur_ns"] / 2
        assert a["nvcsw"] >= _counts_switches()


def test_a_stretch_under_50_ms_makes_no_getrusage_call(monkeypatch):
    """Counted on this thread (the cluster's threads of this file fold
    phases of their own), with the collector off (a slow collection is a
    slow stretch, and reads it)."""
    import gc
    import resource

    calls, me = [], threading.get_ident()
    real = resource.getrusage

    def counting(who):
        if threading.get_ident() == me:
            calls.append(who)
        return real(who)

    async def short():
        for _ in range(20):
            await tracing.steps("t.cheap.steps", asyncio.sleep(0))

    def stretches(n):
        for _ in range(n):
            with tracing.phase("t.cheap"):
                pass
        asyncio.run(short())

    was = gc.isenabled()
    gc.disable()
    try:
        monkeypatch.setattr(resource, "getrusage", counting)
        monkeypatch.setattr(tracing, "STAMP_NS", 2 ** 62)  # no stamp ages
        stretches(1)        # this thread's table exists from here
        tracing._table().restamp(time.monotonic_ns(), tracing._usage())
        del calls[:]
        stretches(2000)
        # (a stretch that the machine held for 50 ms is a slow one, and reads)
        assert len(calls) == len(_slow("t.cheap") + _slow("t.cheap.steps"))
        # with the real interval: one refresh in STAMP_NS a thread, no more
        monkeypatch.setattr(tracing, "STAMP_NS", 10_000_000)
        tracing._table().restamp(time.monotonic_ns(), tracing._usage())
        del calls[:]
        t0 = time.monotonic_ns()
        while time.monotonic_ns() - t0 < 5 * tracing.STAMP_NS:
            stretches(50)
        spent = time.monotonic_ns() - t0
        slow = len(_slow("t.cheap") + _slow("t.cheap.steps"))
        assert 1 <= len(calls) <= 1 + spent // tracing.STAMP_NS + slow
        assert set(calls) == {resource.RUSAGE_THREAD}
        # and a slow one reads it once, where it ends
        del calls[:]
        with tracing.phase("t.cheap.slow"):
            time.sleep(0.06)
        assert 1 <= len(calls) <= 2  # its exit; its entry if the stamp was old
        assert "cpu_ns" in _slow("t.cheap.slow")[-1]["attrs"]
    finally:
        if was:
            gc.enable()


def test_host_gc_folds_a_full_collection_and_not_a_young_pass(monkeypatch):
    import gc

    monkeypatch.setattr(tracing, "SLOW_NS", 0)
    was = gc.isenabled()
    gc.disable()      # no collection but the ones asked for below
    try:
        n0, total0 = _row("host.gc")["count"], tracing._gc_total_ns[0]
        gc.collect(0)
        assert _row("host.gc")["count"] == n0
        junk = [[] for _ in range(1000)]
        for j in junk:
            j.append(j)      # cycles: only the collector frees them
        del junk, j
        gc.collect(2)
        assert _row("host.gc")["count"] == n0 + 1
    finally:
        if was:
            gc.enable()
    p = _slow("host.gc")[-1]
    assert p["attrs"]["generation"] == 2 and p["attrs"]["collected"] >= 1000
    assert set(_DELTAS) <= set(p["attrs"])
    assert tracing._gc_total_ns[0] - total0 == p["dur_ns"]
    # its cluster event waits for a caller that holds no lock
    assert ("host.gc", p["start_monotonic_ns"], p["dur_ns"],
            p["attrs"]) in tracing._deferred
    tracing.observe("t.after_gc", 1)    # SLOW_NS is 0: a slow fold drains it
    assert not tracing._deferred


def _beat_through(name, periods_ms, profiling=()):
    """A fresh beat driven with injected stamps: one tick, then one a
    period. Returns the last tick's record (or None) and the stamps."""
    b = tracing._Beat(name, {"work_ms": name + ".work"})
    t = time.monotonic_ns()
    stamps, out = [t], b.tick(t, bool(profiling and profiling[0]))
    for i, ms in enumerate(periods_ms):
        t += ms * 1_000_000
        stamps.append(t)
        out = b.tick(t, bool(profiling and profiling[i + 1]))
    return out, stamps


@pytest.mark.parametrize("case,periods,excess_ms", [
    ("seven_known", [100] * 7 + [1000], None),
    ("eight_known", [100] * 8 + [1000], 900),
    ("m300_500", [300] * 8 + [500], None),
    ("m300_560", [300] * 8 + [560], 260),
    ("m1200_1450", [1200] * 8 + [1450], None),
    ("m1200_1510", [1200] * 8 + [1510], 310),
    ("median_of_16", [100] * 4 + [2000] * 3 + [100] * 14 + [400], 300),
])
def test_the_stall_rule(case, periods, excess_ms):
    """With eight periods known at least, a period whose excess over the
    median of the last sixteen is 250 ms and a quarter of that median makes
    one record: it starts a median after the last beat, lasts the excess."""
    name = "t.rule." + case
    out, stamps = _beat_through(name, periods)
    got = _slow(name + ".stall")
    assert _row(name + ".stall")["count"] == len(got)
    if excess_ms is None:
        assert out is None and got == []
        return
    (p,) = got
    median = sorted(periods[-17:-1])[7] if len(periods) > 16 else periods[0]
    assert p["dur_ns"] == excess_ms * 1_000_000
    assert p["start_monotonic_ns"] == stamps[-2] + median * 1_000_000
    a = p["attrs"]
    assert a == out
    assert (a["index"], a["period_ms"], a["median_ms"]) == (
        len(periods) + 1, float(periods[-1]), float(median))
    assert a["profiler"] == 0 and a["work_ms"] == 0.0 and a["gc_ms"] >= 0.0
    assert a["outside_ms"] <= a["period_ms"]
    assert all(isinstance(a[k], int) for k in tracing._SPENT)
    assert "over_ns" not in a      # the deltas are the whole period's


@pytest.mark.parametrize("flags,profiler", [
    ((False,) * 9 + (True,), 1),     # the session started inside the period
    ((False,) * 8 + (True, False), 1),    # it stopped inside it
    ((False,) * 8 + (True, True), 0),     # wholly inside a session
    ((False,) * 10, 0)])
def test_a_stall_says_whether_a_profiler_session_began_or_ended_in_it(
        flags, profiler):
    name = f"t.rule.prof.{'.'.join(str(int(f)) for f in flags[-2:])}"
    out, _ = _beat_through(name, [300] * 8 + [900], flags)
    assert out["profiler"] == profiler
    assert _row(name + ".stall")["count"] == 1


def test_a_stall_record_passes_an_exhausted_rate_limit(monkeypatch, capsys):
    sent = _as_worker(monkeypatch)
    for i in range(tracing._SLOW_EVENTS_PER_10S + 5):
        tracing.observe("t.flood2", tracing.SLOW_NS)
    assert len(sent) == tracing._SLOW_EVENTS_PER_10S
    monkeypatch.setattr(tracing, "_stall_said", [0.0])
    _beat_through("t.unlimited", [20] * 9 + [620])
    _beat_through("t.unlimited", [20] * 9 + [720])
    assert [e["data"]["name"] for e in sent[-2:]] == ["t.unlimited.stall"] * 2
    assert sent[-2]["data"]["dropped_before"] == 5
    assert sent[-2]["data"]["attrs"]["median_ms"] == 20.0
    # one line on stderr, and one a second at most
    err = [line for line in capsys.readouterr().err.splitlines()
           if line.startswith("[tracing] t.unlimited.stall")]
    assert len(err) == 1
    assert err[0].startswith(
        "[tracing] t.unlimited.stall 600 ms at beat 11: outside 620, work 0, "
        "gc 0, cpu ")
    assert ", majflt " in err[0]


def _nap(seconds, until):
    """Sleeps `seconds`, then on until `until()` (bounded): the frame a
    stalled loop's stack should name."""
    time.sleep(seconds)
    deadline = time.monotonic() + 10
    while not until() and time.monotonic() < deadline:
        time.sleep(0.02)


def test_a_real_stall_names_the_sleeping_frame(monkeypatch, capsys):
    """Sixteen beats 20 ms apart with 5 ms of the loop's own phase in each,
    then 0.6 s asleep: one record, whose `outside_ms` holds the sleep and
    whose `stack` (the watchdog's, taken while the period was open) names
    the frame that slept."""
    monkeypatch.setattr(tracing, "WATCHDOG_S", 0.05)
    monkeypatch.setattr(tracing, "_stall_said", [0.0])
    parts = {"work_ms": "t.real.work"}
    for _ in range(17):
        tracing.beat("t.real", parts)
        with tracing.phase("t.real.work"):
            time.sleep(0.005)
        time.sleep(0.015)
    assert _slow("t.real.stall") == []
    b = tracing._table().beats["t.real"]
    _nap(0.6, lambda: b.sample is not None)
    tracing.beat("t.real", parts)
    (p,) = _slow("t.real.stall")
    a = p["attrs"]
    assert a["index"] == 18 and a["profiler"] == 0
    assert a["period_ms"] >= 600 and a["median_ms"] >= 20
    assert abs(p["dur_ns"] - (a["period_ms"] - a["median_ms"]) * 1e6) < 2000
    assert a["outside_ms"] >= 600 - a["median_ms"]
    # the parts are the period's: they sum to no more than it
    assert a["outside_ms"] + a["work_ms"] + a["gc_ms"] <= a["period_ms"] + 0.002
    assert "_nap" in a["stack"] and len(a["stack"]) <= tracing.STACK_CHARS
    assert a["stack"].startswith(
        f"--- thread {threading.current_thread().name} ")
    assert a["watchdog_late_ms"] >= 0.0
    assert a["cpu_ns"] < p["dur_ns"] / 2
    err = [line for line in capsys.readouterr().err.splitlines()
           if line.startswith("[tracing] t.real.stall")]
    assert len(err) == 1 and " at beat 18: outside " in err[0]


def test_format_stacks_leads_with_the_thread_asked_for():
    from ray_tpu.serve import trace as serve_trace

    done = threading.Event()
    t = threading.Thread(target=done.wait, name="t-stacks", daemon=True)
    t.start()
    try:
        text = tracing.format_stacks(t.ident, innermost_first=True)
        assert text.startswith(f"--- thread t-stacks ({t.ident}) ---")
        first = text.split("--- thread ")[1]
        assert first.index("in wait") < first.index("in run")  # innermost first
        plain = tracing.format_stacks()
        assert f"--- thread MainThread ({threading.get_ident()}) ---" in plain
        assert "test_format_stacks_leads_with_the_thread_asked_for" in plain
        assert serve_trace.capture_stacks(64) == plain[:64]
    finally:
        done.set()
        t.join(5)


def test_an_induced_stall_of_the_train_step_records_itself(tmp_path, capsys,
                                                           monkeypatch):
    """ShardedTrainStep.step beats as `train`: nothing for the first eight
    beats whatever they took (the first call compiles), one `train.stall`
    for a sleep between two steps, with where the thread spent the period
    by the loop's own phases; across `jax.profiler.start_trace` the record
    says `profiler` 1."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.parallel.mesh import single_device_mesh
    from ray_tpu.train.step import ShardedTrainStep

    monkeypatch.setattr(tracing, "WATCHDOG_S", 0.05)
    monkeypatch.setattr(tracing, "_stall_said", [0.0])
    tracing._table().beats.pop("train", None)
    n0 = len(_slow("train.stall"))
    ts = ShardedTrainStep(
        init_params_fn=lambda key: {"w": jnp.ones((4,))},
        loss_fn=lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2),
        logical_specs={"w": (None,)}, mesh=single_device_mesh())
    params, opt = ts.init(jax.random.key(0))
    x = np.ones((2, 4), np.float32)

    def step():
        nonlocal params, opt
        params, opt, loss = ts.step(params, opt, ts.shard_batch({"x": x}))
        return float(loss)

    for _ in range(8):
        step()
        time.sleep(0.3 if _ == 3 else 0.0)   # long, but among the first eight
    assert len(_slow("train.stall")) == n0
    for _ in range(9):
        step()
    b = tracing._table().beats["train"]
    _nap(0.6, lambda: b.sample is not None)
    step()
    (p,) = _slow("train.stall")[n0:]
    a = p["attrs"]
    assert a["index"] == 18 and a["profiler"] == 0
    assert a["outside_ms"] >= 600 - a["median_ms"]
    assert sum(a[k] for k in ("outside_ms", "enqueue_ms", "shard_ms",
                              "report_ms", "gc_ms")) <= a["period_ms"] + 0.003
    assert a["report_ms"] == 0.0 and "_nap" in a["stack"]
    err = [line for line in capsys.readouterr().err.splitlines()
           if line.startswith("[tracing] train.stall")]
    assert len(err) == 1 and "outside" in err[0] and "enqueue" in err[0]
    assert len(b.periods) == tracing.BEAT_KEPT    # the rule's, and no row
    # the same across the start of a profiler session
    jax.profiler.start_trace(str(tmp_path))
    try:
        time.sleep(0.4)
        step()
    finally:
        jax.profiler.stop_trace()
    (q,) = _slow("train.stall")[n0 + 1:]
    assert q["attrs"]["profiler"] == 1 and q["attrs"]["index"] == 19
