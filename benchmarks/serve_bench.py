"""Disaggregated serving benchmark -> benchmarks/BENCH_r13.json.

Drives concurrent STREAMED HTTP requests through the serve proxy into
the disaggregated LLM plane (serve/disagg.py: prefill pool -> KV handoff
-> decode pool with prefix cache) and records:

- serve_ttft_cold_ms / serve_ttft_hit_ms: client-observed time to first
  token for cold prompts (prefill pool + handoff) vs prefix-cache hits
  (resident K/V splice) at the SAME bucket length — the headline
  `serve_ttft_hit_speedup` is the acceptance ratio (target >= 5x).
- serve_hop_*_ms: the trace plane's per-hop dwell baseline — median
  exclusive time per hop name (proxy ingress, router assign, ingress
  replica, decode attempt, KV handoff, engine attach, stream) read back
  from the controller request ledger, plus the attributed fraction
  (exclusive dwells over end-to-end wall — the waterfall must account
  for the latency it claims to explain).
- serve_trace_overhead_pct: traced-vs-untraced A/B on the same live
  deployment (RTPU_SERVE_TRACE toggled at the ingress, which gates
  trace identity end to end) — acceptance <= 10%.
- serve_stream_tokens_per_s + TTFT p50/p99 under a concurrent flood.
- serve_prefix_cache_hit_rate and serve_handoff_bytes (scraped from the
  Prometheus endpoint's rtpu_serve_handoff_bytes_total).
- serve_autoscale_*: sustained queue pressure must grow the decode pool
  to its max, idle must drain it back to min, with ZERO failed streams
  across both resizes (`serve_failed_streams`).

Usage:
    python benchmarks/serve_bench.py [--smoke] [--out PATH]

--smoke shrinks request counts ~10x for the slow-tier CI check; the
committed BENCH_r13.json comes from the full profile on the same 1-CPU
host as PERF.json.

A host benchmark of the serving control path with llama_tiny: the whole
process tree is pinned to the cpu platform below (cpu_mesh_env exports
JAX_PLATFORMS=cpu to every child), so none of its numbers is a device
number. The on-chip serving path is exercised by chip_smoke.py.
"""
import argparse
import json
import os
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from ray_tpu.util.jaxenv import cpu_mesh_env  # noqa: E402

cpu_mesh_env(8)

import numpy as np  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu import serve  # noqa: E402
from ray_tpu.models import transformer as tfm  # noqa: E402
from ray_tpu.models.configs import llama_tiny  # noqa: E402
from ray_tpu.serve.disagg import build_disagg_llm_deployment  # noqa: E402

PORT = 8310
# llama_tiny scaled up (~6M params) so prefill of a 256-token bucket does
# real work (~80ms on the CI CPU) while a decode tick stays ~12ms: the
# cold-vs-hit TTFT ratio then measures the prefill actually skipped, not
# fixed HTTP/router overhead.
CFG = llama_tiny(remat=False, max_seq_len=512, d_model=256, n_layers=6,
                 n_heads=8, n_kv_heads=4)
NAME = "bench-llm"


def _factory():
    import jax

    return tfm.init_params(jax.random.key(0), CFG)


def _prompt(rng, length):
    return rng.integers(1, CFG.vocab_size - 1, size=length).tolist()


def _stream_request(body, timeout=120.0, request_id=None):
    """POST a streamed generation; returns (tokens, ttft_s, total_s).
    Raises on transport errors or in-band {'error': ...} chunks."""
    headers = {"Content-Type": "application/json"}
    if request_id:
        headers["X-Request-Id"] = request_id
    req = urllib.request.Request(
        f"http://127.0.0.1:{PORT}/llm", data=json.dumps(body).encode(),
        headers=headers)
    t0 = time.perf_counter()
    ttft = None
    toks = []
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        for line in resp:
            line = line.strip()
            if not line:
                continue
            chunk = json.loads(line)
            if "error" in chunk:
                raise RuntimeError(chunk["error"])
            if ttft is None:
                ttft = time.perf_counter() - t0
            toks.append(chunk["token"])
    return toks, ttft, time.perf_counter() - t0


def _flood(bodies, concurrency):
    """Run the request bodies through a bounded thread pool; returns
    (results, failures) where results are (tokens, ttft_s, total_s)."""
    results = []
    failures = []
    lock = threading.Lock()
    it = iter(bodies)

    def worker():
        while True:
            with lock:
                body = next(it, None)
            if body is None:
                return
            try:
                r = _stream_request(body)
                with lock:
                    results.append(r)
            except Exception as e:
                with lock:
                    failures.append(repr(e))

    threads = [threading.Thread(target=worker) for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results, failures


def _scrape_metric(name):
    """Sum a counter across series on the Prometheus endpoint."""
    from ray_tpu.util import state as state_api

    try:
        addr = state_api.metrics_address()
        if not addr:
            return None
        with urllib.request.urlopen(f"http://{addr}/metrics",
                                    timeout=5) as resp:
            text = resp.read().decode()
        total = 0.0
        seen = False
        for line in text.splitlines():
            if line.startswith(name) and not line.startswith("#"):
                total += float(line.rsplit(None, 1)[1])
                seen = True
        return total if seen else None
    except Exception:
        return None


def _ledger_rows(rids, timeout=30.0):
    """Fetch the request ledger rows (with waterfalls) for the given
    request ids, waiting out the replica shippers' 0.5s flush cadence."""
    from ray_tpu.serve import trace as serve_trace
    from ray_tpu.util import state as state_api

    rows = {}
    deadline = time.time() + timeout
    while time.time() < deadline and len(rows) < len(rids):
        serve_trace.flush_serve_trace()
        for rid in rids:
            if rid in rows:
                continue
            try:
                row = state_api.serve_trace(rid)
            except KeyError:
                continue
            if row.get("status") == "ok" and row.get("waterfall"):
                rows[rid] = row
        time.sleep(0.5)
    return list(rows.values())


def _serve_stats():
    ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
    return ray_tpu.get(ctrl.get_serve_stats.remote(), timeout=10)


def _decode_cache_stats():
    ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
    _, reps = ray_tpu.get(ctrl.get_replicas.remote(f"{NAME}-decode"))
    hits = misses = 0
    for r in reps:
        try:
            st = ray_tpu.get(r.handle_request.remote("cache_stats", (), {}),
                             timeout=10)
            hits += st["hits"]
            misses += st["misses"]
        except Exception:
            pass
    return hits, misses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="~10x smaller request counts (CI slow tier)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r13.json"))
    args = ap.parse_args()

    n_ttft = 6 if args.smoke else 20          # cold/hit prompt pairs
    n_hop = 4 if args.smoke else 12           # traced-waterfall requests
    n_ab = 10 if args.smoke else 40           # traced/untraced A/B reqs
    n_flood = 60 if args.smoke else 600       # streamed flood requests
    conc = 8 if args.smoke else 32
    conc_auto = 24                             # autoscale-phase clients:
    # each 48-token stream holds a slot only ~half its life (the rest is
    # chunk relay), so sustained queue pressure on 4 slots needs ~6x more
    # concurrent streams than slots.
    flood_new = 8                              # tokens per flood stream
    prompt_len = 200                           # bucket 256 for every prompt

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    app = build_disagg_llm_deployment(
        CFG, _factory, name=NAME, num_prefill_replicas=1,
        num_decode_replicas=1, num_slots=4, max_prompt_len=256,
        max_new_tokens=64,
        decode_scaling_policy={
            "min_replicas": 1, "max_replicas": 2, "queue_depth_high": 2.0,
            "queue_depth_low": 0.5, "occupancy_low": 0.6, "up_for_s": 2.0,
            "down_for_s": 4.0, "cooldown_s": 0.0})
    serve.run(app, route_prefix="/llm", _http=True, http_port=PORT)
    rng = np.random.default_rng(0)
    out = {}

    def rec(metric, value, unit, **extra):
        out[metric] = {"metric": metric, "value": round(float(value), 4),
                       "unit": unit, **extra}
        print(f"  {metric}: {out[metric]['value']} {unit}", flush=True)

    try:
        # ---------------------------------------------- warm-up (compiles)
        print("warming jit caches ...", flush=True)
        warm = _prompt(rng, prompt_len)
        _stream_request({"tokens": warm, "max_new_tokens": 4})
        _stream_request({"tokens": warm, "max_new_tokens": 4})

        # ------------------------------------- TTFT: cold vs prefix hit
        print(f"TTFT cold vs hit ({n_ttft} prompt pairs) ...", flush=True)
        cold_ttft, hit_ttft = [], []
        for _ in range(n_ttft):
            p = _prompt(rng, prompt_len)  # unseen tokens -> cache miss
            _, t_cold, _ = _stream_request(
                {"tokens": p, "max_new_tokens": 2})
            _, t_hit, _ = _stream_request(
                {"tokens": p, "max_new_tokens": 2})
            cold_ttft.append(t_cold)
            hit_ttft.append(t_hit)
        cold_ms = float(np.median(cold_ttft) * 1e3)
        hit_ms = float(np.median(hit_ttft) * 1e3)
        rec("serve_ttft_cold_ms", cold_ms, "ms",
            note="prefill pool + worker-to-worker KV handoff + splice")
        rec("serve_ttft_hit_ms", hit_ms, "ms",
            note="prefix-cache hit: resident K/V splice, no prefill")
        rec("serve_ttft_hit_speedup", cold_ms / max(hit_ms, 1e-9), "x",
            bucket_len=256)

        # --------------------------------- per-hop breakdown (trace plane)
        print(f"per-hop breakdown: {n_hop} traced cold streams ...",
              flush=True)
        rids = []
        for i in range(n_hop):
            rid = f"bench-hop-{i:03d}"
            # Fresh tokens per request: the cold path exercises every hop
            # (prefill pool + KV handoff), not just the resident splice.
            _stream_request({"tokens": _prompt(rng, prompt_len),
                             "max_new_tokens": 8}, request_id=rid)
            rids.append(rid)
        rows = _ledger_rows(rids)
        assert len(rows) >= max(1, n_hop // 2), \
            f"only {len(rows)}/{n_hop} traced requests reached the ledger"
        hop_self = {}
        attributed = []
        for row in rows:
            wall = max(row["wall_s"], 1e-9)
            attributed.append(
                sum(s["self_s"] for s in row["waterfall"]) / wall)
            for s in row["waterfall"]:
                hop_self.setdefault(s["name"], []).append(s["self_s"])
        for hop_name in sorted(hop_self):
            key = "serve_hop_" + hop_name.replace("serve.", "") \
                                         .replace(".", "_") + "_ms"
            rec(key, float(np.median(hop_self[hop_name])) * 1e3, "ms",
                hop=hop_name, samples=len(hop_self[hop_name]),
                note="median EXCLUSIVE dwell (self time) per request")
        rec("serve_trace_attributed_fraction",
            float(np.median(attributed)), "ratio", requests=len(rows),
            note="per-hop exclusive dwells over end-to-end wall — the "
                 "waterfall accounts for this share of measured latency")

        # ------------------------------ traced-vs-untraced A/B (overhead)
        print(f"trace overhead A/B: {n_ab} streams per arm ...",
              flush=True)

        def ab_arm():
            times = []
            for i in range(n_ab):
                _, _, tot = _stream_request(
                    {"tokens": pool_ab[i % len(pool_ab)],
                     "max_new_tokens": 4})
                times.append(tot)
            return float(np.median(times))

        pool_ab = [_prompt(rng, prompt_len) for _ in range(4)]
        # Untraced FIRST so each arm's prompts are equally cache-warm by
        # its measured half (warm once up front). The ingress flag gates
        # trace IDENTITY end to end: with it off no root exists, so no
        # process allocates or ships a span (the engine's bounded token
        # ring is governed by the replica's own env and stays on in both
        # arms — its cost is two deque ops per token, identical here).
        for p in pool_ab:
            _stream_request({"tokens": p, "max_new_tokens": 4})
        os.environ["RTPU_SERVE_TRACE"] = "0"
        try:
            off_s = ab_arm()
        finally:
            os.environ.pop("RTPU_SERVE_TRACE", None)
        on_s = ab_arm()
        overhead_pct = (on_s - off_s) / off_s * 100.0
        rec("serve_trace_overhead_pct", overhead_pct, "%",
            traced_ms=round(on_s * 1e3, 3),
            untraced_ms=round(off_s * 1e3, 3), requests_per_arm=n_ab,
            note="median streamed-request wall, traced vs "
                 "RTPU_SERVE_TRACE=0 on the same live deployment "
                 "(acceptance <= 10%)")

        # ----------------------------------------- concurrent stream flood
        print(f"flood: {n_flood} streams, concurrency {conc} ...",
              flush=True)
        pool = [_prompt(rng, prompt_len) for _ in range(8)]
        bodies = [{"tokens": pool[i % len(pool)],
                   "max_new_tokens": flood_new} for i in range(n_flood)]
        h0 = _scrape_metric("rtpu_serve_handoff_bytes_total") or 0.0
        t0 = time.perf_counter()
        results, failures = _flood(bodies, conc)
        wall = time.perf_counter() - t0
        toks = sum(len(r[0]) for r in results)
        ttfts = sorted(r[1] for r in results)
        rec("serve_stream_tokens_per_s", toks / wall, "tokens/s",
            requests=n_flood, concurrency=conc, wall_s=round(wall, 2))
        rec("serve_flood_ttft_p50_ms",
            ttfts[len(ttfts) // 2] * 1e3, "ms")
        rec("serve_flood_ttft_p99_ms",
            ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))] * 1e3, "ms")
        hits, misses = _decode_cache_stats()
        rec("serve_prefix_cache_hit_rate",
            hits / max(1, hits + misses), "ratio", hits=hits,
            misses=misses)
        h1 = _scrape_metric("rtpu_serve_handoff_bytes_total")
        if h1 is not None:
            rec("serve_handoff_bytes", h1, "bytes",
                note="cumulative prefill->decode KV handoff volume")
        flood_failures = len(failures)

        # ------------------------------------------------ autoscale cycle
        # The flood above may itself have scaled the pool up; wait for it
        # to drain back to min so the cycle below measures a full
        # quiesced -> pressured -> quiesced round trip.
        print("autoscale: settling to min_replicas ...", flush=True)
        deadline = time.time() + 120
        while time.time() < deadline:
            st = _serve_stats().get(f"{NAME}-decode", {})
            if st.get("replicas", 1) <= 1 and st.get("draining", 0) == 0:
                break
            time.sleep(0.5)
        else:
            raise AssertionError("pool never settled to min before the "
                                 "autoscale cycle")
        print("autoscale: flood until the decode pool grows ...",
              flush=True)
        as_results = []
        as_failures = []
        stop_flood = threading.Event()

        def background_flood():
            i = 0
            while not stop_flood.is_set():
                body = {"tokens": pool[i % len(pool)],
                        "max_new_tokens": 48}
                i += 1
                try:
                    as_results.append(_stream_request(body))
                except Exception as e:
                    as_failures.append(repr(e))

        floods = [threading.Thread(target=background_flood)
                  for _ in range(conc_auto)]
        t0 = time.perf_counter()
        for t in floods:
            t.start()
        grew_at = None
        deadline = time.time() + 120
        while time.time() < deadline:
            st = _serve_stats().get(f"{NAME}-decode", {})
            if st.get("replicas", 1) >= 2:
                grew_at = time.perf_counter() - t0
                break
            time.sleep(0.5)
        stop_flood.set()
        for t in floods:
            t.join()
        assert grew_at is not None, \
            "decode pool never scaled up under sustained pressure"
        rec("serve_autoscale_up_s", grew_at, "s",
            note="sustained queue depth -> +1 decode replica")

        print("autoscale: idle drain back to min ...", flush=True)
        t0 = time.perf_counter()
        drained_at = None
        deadline = time.time() + 120
        while time.time() < deadline:
            st = _serve_stats().get(f"{NAME}-decode", {})
            if st.get("replicas", 2) <= 1 and st.get("draining", 0) == 0:
                drained_at = time.perf_counter() - t0
                break
            time.sleep(0.5)
        assert drained_at is not None, \
            "decode pool never drained back down when idle"
        rec("serve_autoscale_down_s", drained_at, "s",
            note="idle -> drain-aware scale down to min_replicas")
        # Post-resize sanity: the plane still serves correctly.
        toks, _, _ = _stream_request(
            {"tokens": pool[0], "max_new_tokens": 4})
        assert len(toks) == 4
        rec("serve_failed_streams", flood_failures + len(as_failures),
            "streams", flood=flood_failures,
            autoscale_cycle=len(as_failures),
            note="transport or in-band errors across every phase, "
                 "including both pool resizes")
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()

    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out}")
    speedup = out["serve_ttft_hit_speedup"]["value"]
    failed = out["serve_failed_streams"]["value"]
    if speedup < 5.0:
        print(f"WARNING: hit speedup {speedup}x below the 5x target",
              file=sys.stderr)
    if failed:
        print(f"WARNING: {failed} failed streams", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
