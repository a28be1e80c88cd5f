#!/usr/bin/env python3
"""Checks of the yardstick itself, run by hand on the CPU:

    JAX_PLATFORMS=cpu python3 chipbench/selfcheck.py [--skip-cells]

1. BENCHMARK.json and the files it names agree (every configuration, mix,
   driver and metric reader is found by name; a metric file's unit, layer
   and `moves` are the manifest's).
2. The traffic generators give the same schedule for the same seed, and the
   same multiset of sizes and gaps for different seeds.
3. Per-layer and vmapped weight generation agree bit for bit.
4. The trace reduction gives the recorded trace's known busy time, idle
   gaps, op self times and program times (reduce/recorded_trace.json).
5. The flash operation and byte counts match a hand count at one shape.
6. The runner walks every cell's control flow at its tiny preset on the host
   and refuses to print a result line there (exit code 3, no JSON result on
   stdout); without --rehearse it refuses outright.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench import common, traffic_gen  # noqa: E402
from chipbench.reduce import flash_counts, xplane  # noqa: E402


def check_manifest() -> None:
    man = common.load_manifest()
    cand = common.load_json("candidates.json")
    man = {k: man[k] + cand[k]
           for k in ("workloads", "end_to_end", "per_layer")}
    e2e = {m["name"] for m in man["end_to_end"]}
    for w in man["workloads"]:
        cell = common.load_cell(w["name"])
        kind = cell["mix"]["kind"]
        assert os.path.exists(os.path.join(HERE, "drivers", kind + ".py")), kind
        assert len(w["why"]) <= 200, (w["name"], len(w["why"]))
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"], w["name"]
    for m in man["per_layer"]:
        assert m["moves"] in e2e, m
        base = os.path.join(HERE, "metrics", m["name"])
        if os.path.exists(base + ".json"):
            spec = json.load(open(base + ".json"))
            for k in ("unit", "layer", "moves", "source"):
                assert spec[k] == m[k], (m["name"], k, spec[k], m[k])
        else:
            assert os.path.exists(base + ".py"), m["name"]
    print("manifest: ok,", len(man["workloads"]), "cells,",
          len(man["per_layer"]), "per-layer metrics")


def check_traffic() -> None:
    mix = common.load_json("traffic", "chat_open.json")
    a = traffic_gen.open_loop(mix, 7, 30, 1000)
    b = traffic_gen.open_loop(mix, 7, 30, 1000)
    c = traffic_gen.open_loop(mix, 8, 30, 1000)
    assert a == b, "same seed, different schedule"
    assert a != c

    def sizes(rs):
        return sorted((len(r["tokens"]), r["max_new_tokens"]) for r in rs)

    def gaps(rs):
        due = [r["due"] for r in rs]
        return sorted(round(y - x, 9) for x, y in zip(due, due[1:]))

    assert sizes(a) == sizes(c), "seeds change the work"
    assert len(a) == len(c) == round(mix["rate_rps"] * (30 + mix["ramp_s"]))
    # consecutive differences drop each run's own first gap
    assert len(set(gaps(a)) ^ set(gaps(c))) <= 2, "seeds change the gaps"
    assert -mix["ramp_s"] <= a[0]["due"] and a[-1]["due"] < 30
    doc = common.load_json("traffic", "doc_batch.json")
    d, e = (traffic_gen.closed_loop(doc, s, 1000) for s in (1, 2))
    assert sizes(d) == sizes(e) and d != e
    assert traffic_gen.closed_loop(doc, 1, 1000) == d
    print("traffic: ok,", len(a), "requests in 30 s + ramp, prompt tokens",
          sum(len(r["tokens"]) for r in a))


def check_weights() -> None:
    import jax
    import numpy as np

    from chipbench import inworker, weights

    sz = inworker.sizes(common.load_json("configs", "internlm2_1_8b.json"),
                        True)
    key = jax.random.key(3)
    p = weights.program_params(key, sz)
    for l in range(sz.L):
        w = weights.layer(weights.layer_key(key, l), sz)
        assert np.array_equal(p["layers"]["wo"][l], w["wo"])
        assert np.array_equal(
            p["layers"]["wkv"][l][:, 1].reshape(sz.d, -1), w["wv"])
        assert np.array_equal(p["layers"]["w_gate_up"][l][:, 0], w["w_gate"])
    print("weights: ok, per-layer == vmapped")


def check_reduction() -> None:
    path = os.path.join(HERE, "reduce", "recorded_trace.json")
    with open(path) as f:
        rec = json.load(f)
    red = xplane.reduce(xplane.load_json(path))
    for key, want in rec["expect"].items():
        got = red[key]
        if isinstance(want, dict):
            for k, v in want.items():
                g = got[k]
                g = sum(g) / len(g) if isinstance(g, list) else g
                assert abs(g - v) <= 1e-9 + 1e-6 * abs(v), (key, k, g, v)
        else:
            assert abs(got - want) <= 1e-9 + 1e-6 * abs(want), (key, got, want)
    print("reduction: ok,", len(rec["events"]), "recorded events, busy",
          red["busy_s"], "of", red["window_s"])


def check_flash_counts() -> None:
    # By hand, B=16 H=12 KVH=12 S=1024 D=64, bf16: one head's Q K^T is
    # 2*1024*1024*64 = 134,217,728 flops, P V the same; halved by causality
    # -> 134,217,728 a head; x 192 heads = 25,769,803,776.
    fwd = flash_counts.flash_fwd(16, 12, 12, 1024, 64)
    assert fwd["flops"] == 25_769_803_776, fwd
    # Bytes: Q, K, V, O each 16*1024*12*64*2 = 25,165,824 -> 100,663,296,
    # plus the float32 row statistics 16*12*1024*4 = 786,432.
    assert fwd["bytes"] == 100_663_296 + 786_432, fwd
    bwd = flash_counts.flash_bwd(16, 12, 12, 1024, 64)
    assert bwd["flops"] == 2.5 * fwd["flops"]
    assert bwd["bytes"] == 8 * 25_165_824 + 786_432
    peaks = common.load_json("peaks.json")["devices"]["TPU v5 lite"]
    t, bound = flash_counts.roofline_s(fwd, peaks)
    assert bound == "compute" and abs(t - fwd["flops"] / 197e12) < 1e-12
    print("flash counts: ok, forward", fwd["flops"], "flops,", bound, "bound")


def check_cells() -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for w in (common.load_manifest()["workloads"]
              + common.load_json("candidates.json")["workloads"]):
        base = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
                w["name"], "--seed", "2147483650", "--seconds", "3"]
        r = subprocess.run(base + ["--trace", "0"], env=env,
                           capture_output=True, text=True)
        assert r.returncode not in (0, 3) and '"metrics"' not in r.stdout, \
            f"{w['name']}: ran off the chip without --rehearse"
        r = subprocess.run(base + ["--trace", "0", "--rehearse"], env=env,
                           capture_output=True, text=True)
        assert r.returncode == 3, (w["name"], r.returncode, r.stderr[-2000:])
        assert '"metrics"' not in r.stdout, "a result line off the chip"
        assert '"correct": true' in r.stderr, r.stderr[-1500:]
        print(f"cell {w['name']}: walked on the host, no result line")


def main() -> int:
    check_manifest()
    check_traffic()
    check_flash_counts()
    check_reduction()
    check_weights()
    if "--skip-cells" not in sys.argv:
        check_cells()
    print("selfcheck: all ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
