"""Continuous-batching engine (serve/llm_engine.py): requests joining a
RUNNING batch must produce exactly the tokens of isolated per-prompt
greedy generation — slot reuse, mid-flight attach, early retirement and
eos can never perturb other slots."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import llama_tiny
from ray_tpu.serve.llm_engine import ContinuousBatchingEngine


@pytest.fixture(scope="module")
def engine_setup():
    cfg = llama_tiny(remat=False)
    params = tfm.init_params(jax.random.key(0), cfg)
    return cfg, params


def _naive(params, cfg, prompt, n, eos=None):
    toks = jnp.asarray([prompt], jnp.int32)
    out = []
    for _ in range(n):
        logits = tfm.forward(params, toks, cfg)[:, -1]
        nxt = int(jnp.argmax(logits, -1)[0])
        out.append(nxt)
        if eos is not None and nxt == eos:
            break
        toks = jnp.concatenate(
            [toks, jnp.asarray([[nxt]], jnp.int32)], axis=1)
    return out


def test_interleaved_requests_match_isolated(engine_setup):
    cfg, params = engine_setup
    eng = ContinuousBatchingEngine(cfg, params, num_slots=3,
                                   max_prompt_len=16, max_new_tokens=6)
    # Request A starts alone; B and C attach after A has already emitted
    # tokens (mid-flight joins), with different lengths and budgets.
    a = eng.submit([5, 9, 2], max_new_tokens=6)
    eng.tick(); eng.tick()
    b = eng.submit([7, 1, 3, 3, 8, 1], max_new_tokens=4)
    eng.tick()
    c = eng.submit([4], max_new_tokens=3)
    while eng.tick():
        pass
    for slot, prompt, n in ((a, [5, 9, 2], 6), (b, [7, 1, 3, 3, 8, 1], 4),
                            (c, [4], 3)):
        got = eng.result(slot, timeout=60)
        assert got == _naive(params, cfg, prompt, n), (prompt, got)


def test_slot_reuse_after_retirement(engine_setup):
    cfg, params = engine_setup
    eng = ContinuousBatchingEngine(cfg, params, num_slots=1,
                                   max_prompt_len=16, max_new_tokens=4)
    s1 = eng.submit([5, 9, 2], max_new_tokens=2)
    while eng.tick():
        pass
    r1 = eng.result(s1, timeout=60)
    # num_slots=1: the SAME physical slot must serve the next request with
    # prior state fully replaced; request ids stay distinct and readable.
    s2 = eng.submit([7, 7, 7, 7], max_new_tokens=3)
    assert s2 != s1
    while eng.tick():
        pass
    assert eng.result(s2, timeout=60) == _naive(params, cfg, [7, 7, 7, 7], 3)
    assert r1 == _naive(params, cfg, [5, 9, 2], 2)


def test_eos_retires_early(engine_setup):
    cfg, params = engine_setup
    probe = _naive(params, cfg, [5, 9, 2], 4)
    eos = probe[1]  # an early stop: at the second token, or where it came first
    want = _naive(params, cfg, [5, 9, 2], 4, eos=eos)
    assert want[-1] == eos and len(want) <= 2
    eng = ContinuousBatchingEngine(cfg, params, num_slots=2,
                                   max_prompt_len=16, max_new_tokens=4)
    s = eng.submit([5, 9, 2], eos_id=eos)
    while eng.tick():
        pass
    assert eng.result(s, timeout=60) == want


def test_background_thread_and_blocking_submit(engine_setup):
    cfg, params = engine_setup
    eng = ContinuousBatchingEngine(cfg, params, num_slots=2,
                                   max_prompt_len=16, max_new_tokens=3)
    stop = threading.Event()
    t = threading.Thread(target=eng.run_forever, args=(stop,), daemon=True)
    t.start()
    try:
        prompts = [[5, 9, 2], [7, 1, 3], [4, 4], [8, 8, 8, 8]]
        reqs = [eng.submit(p, timeout=120) for p in prompts]  # 3rd blocks
        # Request ids survive slot recycling: ALL four are retrievable.
        for p, r in zip(prompts, reqs):
            assert eng.result(r, timeout=120) == _naive(params, cfg, p, 3)
    finally:
        stop.set()
        t.join(timeout=10)


def test_discard_releases_state_and_ticker_failure_surfaces(engine_setup):
    cfg, params = engine_setup
    eng = ContinuousBatchingEngine(cfg, params, num_slots=1,
                                   max_prompt_len=16, max_new_tokens=4)
    # Discard mid-generation: slot frees at the next tick, stored state gone.
    r = eng.submit([5, 9, 2])
    eng.discard(r)
    eng.tick()
    assert not eng._results and r not in eng._req_slot
    # The slot is immediately reusable.
    r2 = eng.submit([7, 7], max_new_tokens=2)
    while eng.tick():
        pass
    assert eng.result(r2, timeout=60) == _naive(params, cfg, [7, 7], 2)
    eng.pop_result(r2)
    assert not eng._results and not eng._done_ev

    # Ticker failure: waiters wake and result() raises instead of hanging.
    r3 = eng.submit([5, 9, 2])
    stop = threading.Event()
    orig = eng._tick
    eng._tick = lambda *a: (_ for _ in ()).throw(RuntimeError("device lost"))
    t = threading.Thread(target=eng.run_forever, args=(stop,), daemon=True)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive() and eng.failed is not None
    with pytest.raises(RuntimeError, match="engine failed"):
        eng.result(r3, timeout=5)
    eng._tick = orig


def test_abort_frees_slot_between_steps(engine_setup):
    """abort() is the disconnect path: the slot frees immediately under
    the engine lock (no tick required), double-abort is a no-op, and
    aborting a finished request drops its stored output."""
    cfg, params = engine_setup
    eng = ContinuousBatchingEngine(cfg, params, num_slots=1,
                                   max_prompt_len=16, max_new_tokens=8)
    r1 = eng.submit([5, 9, 2])
    eng.tick()
    assert eng.abort(r1) is True
    assert r1 not in eng._req_slot and r1 not in eng._done_ev \
        and not eng._results
    # Capacity is back WITHOUT another tick: a bounded-wait submit on the
    # single-slot engine succeeds right away.
    r2 = eng.submit([7, 7], max_new_tokens=2, timeout=0.5)
    assert eng.abort(r1) is False  # unknown id now: no-op
    while eng.tick():
        pass
    assert eng.result(r2, timeout=60) == _naive(params, cfg, [7, 7], 2)
    # Abort after completion releases the stored output; repeating it is
    # a no-op again.
    assert eng.abort(r2) is True
    assert not eng._results and not eng._done_ev
    assert eng.abort(r2) is False


def test_serve_metrics_reach_prometheus(engine_setup, ray_start_regular):
    """A generate call records TTFT, decode-token, and slot-occupancy
    metrics that surface on the controller's /metrics endpoint tagged by
    model — the ROADMAP serve item: serving health must be first-class
    telemetry, not benchmark printouts."""
    import time
    import urllib.request

    from ray_tpu.util import state as state_api
    from ray_tpu.util.metrics import flush_metrics

    cfg, params = engine_setup
    eng = ContinuousBatchingEngine(cfg, params, num_slots=2,
                                   max_prompt_len=16, max_new_tokens=3,
                                   model="tiny-test")
    r = eng.submit([5, 9, 2])
    while eng.tick():
        pass
    assert len(eng.result(r, timeout=60)) == 3
    flush_metrics()

    addr = state_api.metrics_address()
    assert addr, "metrics endpoint not enabled in test session"
    deadline = time.time() + 15
    text = ""
    while time.time() < deadline:
        with urllib.request.urlopen(f"http://{addr}/metrics",
                                    timeout=5) as resp:
            text = resp.read().decode()
        if "rtpu_serve_ttft_s" in text:
            break
        time.sleep(0.3)
    assert '# TYPE rtpu_serve_ttft_s histogram' in text, text[-800:]
    assert 'rtpu_serve_ttft_s_bucket{model="tiny-test",le="+Inf"} 1' in text
    assert 'rtpu_serve_ttft_s_count{model="tiny-test"} 1' in text
    # 1 prefill token + 2 decode ticks = 3 tokens for the request.
    assert 'rtpu_serve_decode_tokens_total{model="tiny-test"} 3.0' in text
    # All slots idle again after the request retired.
    assert 'rtpu_serve_slots_busy{model="tiny-test"} 0.0' in text


def test_sampled_slots_vary_and_respect_budget(engine_setup):
    cfg, params = engine_setup
    outs = []
    for seed in (1, 2):
        eng = ContinuousBatchingEngine(cfg, params, num_slots=2,
                                       max_prompt_len=16, max_new_tokens=5,
                                       seed=seed)
        r = eng.submit([5, 9, 2], temperature=1.1)
        while eng.tick():
            pass
        outs.append(eng.result(r, timeout=60))
    assert all(len(o) == 5 for o in outs)
    assert outs[0] != outs[1], "different seeds sampled identical streams"


def test_attach_prefilled_matches_submit(engine_setup):
    """The disagg handoff path (prefill_only on one engine ->
    attach_prefilled on another) must replay the exact greedy stream that
    a unified submit() produces — K/V splice, logits carry-over, and
    length bookkeeping are all byte-equivalent."""
    cfg, params = engine_setup
    prefiller = ContinuousBatchingEngine(cfg, params, num_slots=1,
                                         max_prompt_len=16, max_new_tokens=6)
    decoder = ContinuousBatchingEngine(cfg, params, num_slots=2,
                                       max_prompt_len=16, max_new_tokens=6)
    for prompt in ([5, 9, 2], [7, 1, 3, 3, 8, 1, 2, 2, 4]):
        r_ref = decoder.submit(prompt, max_new_tokens=6)
        while decoder.tick():
            pass
        ref = decoder.result(r_ref, timeout=60)
        decoder.discard(r_ref)

        k, v, length, logits = prefiller.prefill_only(prompt)
        assert length == len(prompt)
        r = decoder.attach_prefilled(k, v, length, logits, max_new_tokens=6)
        while decoder.tick():
            pass
        got = decoder.result(r, timeout=60)
        decoder.discard(r)
        assert got == ref == _naive(params, cfg, prompt, 6), (prompt, got)


def test_attach_prefilled_validates_shapes(engine_setup):
    cfg, params = engine_setup
    eng = ContinuousBatchingEngine(cfg, params, num_slots=1,
                                   max_prompt_len=16, max_new_tokens=4)
    k, v, length, logits = eng.prefill_only([5, 9, 2])
    with pytest.raises(ValueError):
        eng.attach_prefilled(k[0], v, length, logits)  # ndim != 4
    with pytest.raises(ValueError):
        eng.attach_prefilled(k, v, 0, logits)  # empty prefix
    with pytest.raises(ValueError):
        eng.attach_prefilled(k, v, k.shape[1] + 1, logits)  # length > S


def test_ttft_measures_from_arrival_not_prefill(engine_setup, monkeypatch):
    """Satellite fix: TTFT is measured from request ARRIVAL (queue wait
    included), not from when prefill starts. A request stamped as having
    arrived 5s ago must observe a TTFT >= 5s even though its prefill runs
    immediately; an unstamped request stays near zero."""
    import time as _time

    from ray_tpu.serve.llm_engine import _serve_metrics

    cfg, params = engine_setup
    eng = ContinuousBatchingEngine(cfg, params, num_slots=1,
                                   max_prompt_len=16, max_new_tokens=2,
                                   model="ttft-test")
    hist = _serve_metrics()["ttft"]
    seen = []
    orig = hist.observe

    def spy(value, tags=None):
        seen.append(float(value))
        return orig(value, tags=tags)

    monkeypatch.setattr(hist, "observe", spy)
    r = eng.submit([5, 9, 2], arrival_ts=_time.time() - 5.0)
    while eng.tick():
        pass
    eng.result(r, timeout=60)
    eng.discard(r)
    assert seen and seen[0] >= 5.0, seen
    r2 = eng.submit([5, 9, 2])
    while eng.tick():
        pass
    eng.result(r2, timeout=60)
    assert len(seen) == 2 and seen[1] < 5.0, seen
