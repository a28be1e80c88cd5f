"""Data-plane fault-tolerance benchmark -> benchmarks/BENCH_r11.json.

Drives the streaming data plane (read -> actor-pool map_batches ->
random_shuffle -> train ingest) through its failure modes and records:

- data_rows_per_s_healthy / data_rows_per_s_ft_disabled: end-to-end
  pipeline throughput with RTPU_DATA_FT on (default) vs off, same shape —
  `data_ft_overhead_pct` is the healthy-path tax of the fault-tolerance
  machinery (acceptance: small; the disabled path is the fail-fast
  byte-identical baseline).
- data_pool_kill_*: a pool actor is SIGKILLed mid-map; the run must
  produce exactly the same rows as a clean run (`recovered_ok`), with the
  wall-clock slowdown and `rtpu_data_retries_total` burn recorded.
- data_rederive_*: shuffle outputs live on a second node that dies after
  the shuffle completes; ft_get must re-derive every lost block from the
  surviving inputs (`blocks_rederived`, recovery seconds).
- data_ingest_resume_*: DataIterator cursor journal (resume_key) overhead
  vs plain iteration, plus a drop-and-resume pass that must replay the
  exact remaining batches.

Usage:
    python benchmarks/data_bench.py [--smoke] [--out PATH]

--smoke shrinks row counts ~10x for the slow-tier CI check; the
committed BENCH_r11.json comes from the full profile on the same 1-CPU
host as PERF.json.
"""
import argparse
import json
import os
import signal
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from ray_tpu.util.jaxenv import cpu_mesh_env  # noqa: E402

cpu_mesh_env(8)

import numpy as np  # noqa: E402

import ray_tpu  # noqa: E402
import ray_tpu.data as rd  # noqa: E402
from ray_tpu.data import executor as dx  # noqa: E402
from ray_tpu.data import logical as L  # noqa: E402
from ray_tpu.data.block import BlockAccessor  # noqa: E402
from ray_tpu.data.dataset import Dataset  # noqa: E402


class HashBatch:
    """Compute-bound map UDF: a few rounds of mixing, order-independent
    output so retried batches are byte-identical."""

    def __call__(self, batch):
        x = batch["id"].astype(np.uint64)
        for _ in range(4):
            x = (x ^ (x >> np.uint64(33))) * np.uint64(0xFF51AFD7ED558CCD)
        batch["value"] = x.astype(np.int64)
        return batch


class MarkBatch(HashBatch):
    """HashBatch that also appends each batch's min id to a marker file
    (the kill trigger) and sleeps so the killer can land mid-stage."""

    def __init__(self, path, sleep_s):
        self.path = path
        self.sleep_s = sleep_s

    def __call__(self, batch):
        with open(self.path, "a") as f:
            f.write(f"{int(batch['id'].min())}\n")
            f.flush()
        time.sleep(self.sleep_s)
        return super().__call__(batch)


def _client():
    from ray_tpu.core import context as ctx

    return ctx.get_worker_context().client


def _pipeline(n, parallelism, udf, **mb_kw):
    return (rd.range(n, parallelism=parallelism)
            .map_batches(udf, concurrency=2, **mb_kw)
            .random_shuffle(seed=11))


def _ingest(ds, batch_size):
    rows = 0
    csum = 0
    for b in ds.iter_batches(batch_size=batch_size):
        rows += len(b["id"])
        csum += int(b["value"].sum() & 0xFFFFFFFF)
    return rows, csum & 0xFFFFFFFF


def bench_healthy(n, parallelism, batch_size, reps=2):
    """Best of `reps` passes (pool actors respawn per pass, so a single
    pass is dominated by spawn jitter on the CI host)."""
    best = None
    for _ in range(reps):
        dx.reset_ft_counters()
        t0 = time.perf_counter()
        rows, csum = _ingest(_pipeline(n, parallelism, HashBatch),
                             batch_size)
        dt = time.perf_counter() - t0
        assert rows == n, (rows, n)
        r = {"rows_per_s": rows / dt, "wall_s": dt, "checksum": csum,
             "counters": dx.ft_counters()}
        if best is None or r["rows_per_s"] > best["rows_per_s"]:
            best = r
    return best


def bench_pool_kill(n, parallelism, batch_size, do_kill,
                    ref_checksum=None):
    """Run the marker/sleep pipeline; with do_kill, SIGKILL one alive pool
    actor once >=2 batches have started — the self-healing pool must
    finish with byte-identical output. Without, this is the like-for-like
    healthy reference for the slowdown ratio."""
    dx.reset_ft_counters()
    mark = os.path.join(tempfile.gettempdir(),
                        f"data_bench_mark_{os.getpid()}.txt")
    try:
        os.unlink(mark)
    except FileNotFoundError:
        pass

    killed = {}

    def killer():
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                started = len(open(mark).read().split())
            except FileNotFoundError:
                started = 0
            if started >= 2:
                acts = [a for a in _client().request(
                            {"kind": "list_state", "what": "actors"})
                        if a["state"] == "ALIVE" and a.get("worker_id")]
                if acts:
                    pids = {w["worker_id"]: w["pid"]
                            for w in _client().request(
                                {"kind": "list_state", "what": "workers"})}
                    pid = pids.get(acts[0]["worker_id"])
                    if pid and pid != os.getpid():
                        os.kill(pid, signal.SIGKILL)
                        killed["pid"] = pid
                        return
            time.sleep(0.05)

    ds = _pipeline(n, parallelism, MarkBatch,
                   fn_constructor_args=(mark, 0.15))
    t = None
    if do_kill:
        t = threading.Thread(target=killer)
        t.start()
    t0 = time.perf_counter()
    rows, csum = _ingest(ds, batch_size)
    dt = time.perf_counter() - t0
    if t is not None:
        t.join()
    c = dx.ft_counters()
    return {"rows_per_s": rows / dt, "wall_s": dt, "checksum": csum,
            "killed": bool(killed), "retries": c["retries"],
            "recovered_ok": rows == n and (ref_checksum is None
                                           or csum == ref_checksum),
            "counters": c}


def bench_rederive(n, parts):
    """Shuffle outputs land on a worker node that dies after the shuffle;
    ft_get re-derives every lost block from the head-resident inputs."""
    from ray_tpu.core.cluster_utils import Cluster

    os.environ["RTPU_LINEAGE_MAX"] = "0"  # force the data-plane path
    try:
        cluster = Cluster(head_resources={"CPU": 1})

        @ray_tpu.remote(num_cpus=1)
        class Hog:
            def ping(self):
                return "ok"

        # Pin to the head and keep its only CPU busy for the shuffle, so
        # all shuffle tasks (and outputs) land on node B.
        hog = Hog.remote()
        ray_tpu.get(hog.ping.remote())
        nid = cluster.add_node({"CPU": 4}, remote=True,
                               host_id="bench-node-b")

        blocks = [{"id": np.arange(i * (n // parts), (i + 1) * (n // parts),
                                   dtype=np.int64)} for i in range(parts)]
        src = Dataset([L.InputData(
            refs=[ray_tpu.put(b) for b in blocks])])
        refs = src.random_shuffle(seed=7).to_block_refs()
        ray_tpu.wait(refs, num_returns=len(refs))

        dx.reset_ft_counters()
        cluster._agent_procs[0].kill()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            nodes = {x["node_id"]: x for x in ray_tpu.nodes()}
            if not nodes[nid]["alive"]:
                break
            time.sleep(0.2)
        ray_tpu.kill(hog)
        time.sleep(0.3)

        t0 = time.perf_counter()
        out = dx.ft_get(refs)
        dt = time.perf_counter() - t0
        ids = np.sort(np.concatenate(
            [BlockAccessor(b).to_numpy()["id"] for b in out]))
        c = dx.ft_counters()
        return {"recovery_s": dt, "blocks_rederived": c["rederived"],
                "recovered_ok": ids.tolist() == list(range(n)),
                "counters": c}
    finally:
        os.environ.pop("RTPU_LINEAGE_MAX", None)
        try:
            cluster.shutdown()
        except Exception:
            pass


def bench_ingest_resume(n, parallelism, batch_size, ckpt_dir):
    """Cursor-journal overhead + drop-and-resume correctness."""
    os.environ["RTPU_CHECKPOINT_DIR"] = ckpt_dir
    try:
        ds = rd.range(n, parallelism=parallelism)
        # Unmeasured pass: both measured passes then ride the same warm
        # block cache instead of the first one paying materialization.
        for _ in ds.iter_batches(batch_size=batch_size):
            pass
        # Plain iteration (no journal).
        t0 = time.perf_counter()
        plain = [b["id"].tolist() for b in ds.iter_batches(
            batch_size=batch_size)]
        plain_dt = time.perf_counter() - t0
        # Journaled iteration, full pass.
        it = ds.iterator(resume_key="bench_ingest")
        t0 = time.perf_counter()
        journaled = [b["id"].tolist() for b in it.iter_batches(
            batch_size=batch_size)]
        jour_dt = time.perf_counter() - t0
        assert journaled == plain
        # Drop after k batches, resume, splice must equal the clean pass.
        it2 = ds.iterator(resume_key="bench_resume")
        g = it2.iter_batches(batch_size=batch_size)
        k = max(1, len(plain) // 3)
        head = [next(g)["id"].tolist() for _ in range(k)]
        del g
        t0 = time.perf_counter()
        it3 = ds.iterator(resume_key="bench_resume")
        tail = [b["id"].tolist() for b in it3.iter_batches(
            batch_size=batch_size)]
        resume_dt = time.perf_counter() - t0
        rows = sum(len(b) for b in plain)
        return {"rows_per_s_plain": rows / plain_dt,
                "rows_per_s_journaled": rows / jour_dt,
                "journal_overhead_pct":
                    100.0 * (jour_dt - plain_dt) / plain_dt,
                "resume_tail_s": resume_dt,
                "resume_ok": head + tail == plain}
    finally:
        os.environ.pop("RTPU_CHECKPOINT_DIR", None)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    scale = 10 if args.smoke else 1
    # Big enough that map+shuffle compute dominates pool-actor spawn
    # jitter — the FT-on vs FT-off delta is meaningless otherwise.
    n = 1_600_000 // scale
    n_kill = 96_000 // scale
    # Re-derivation needs blocks big enough to stay node-resident (tiny
    # shuffle outputs grow head replicas and nothing is ever lost), so it
    # does not shrink with --smoke.
    n_rederive = 200_000
    parallelism = 8
    batch_size = 4096 // scale

    out = {"smoke": bool(args.smoke), "rows": n}

    # FT-off baseline in its OWN session: pipeline passes leave their
    # blocks in the in-process object store, and a fuller store taxes
    # every later pass ~30% on this host — sharing one session makes the
    # A/B delta measure run order, not the FT machinery.
    os.environ["RTPU_DATA_FT"] = "0"
    ray_tpu.init(num_cpus=4)
    try:
        # Warm-up: first-ever pool spawn pays worker fork + JAX import;
        # none of the measured passes should.
        bench_healthy(max(n // 10, 1000), parallelism, batch_size, reps=1)
        disabled = bench_healthy(n, parallelism, batch_size)
    finally:
        ray_tpu.shutdown()
        os.environ.pop("RTPU_DATA_FT", None)

    ray_tpu.init(num_cpus=4)
    try:
        bench_healthy(max(n // 10, 1000), parallelism, batch_size, reps=1)
        healthy = bench_healthy(n, parallelism, batch_size)
        out["data_rows_per_s_healthy"] = round(healthy["rows_per_s"], 1)
        out["data_healthy_counters"] = healthy["counters"]
        assert disabled["checksum"] == healthy["checksum"], \
            "RTPU_DATA_FT=0 output differs from the FT-on run"
        out["data_rows_per_s_ft_disabled"] = round(disabled["rows_per_s"], 1)
        out["data_ft_overhead_pct"] = round(
            100.0 * (disabled["rows_per_s"] - healthy["rows_per_s"])
            / disabled["rows_per_s"], 2)

        # Like-for-like kill reference: same marker/sleep UDF, no killer.
        kill_ref = bench_pool_kill(n_kill, parallelism, batch_size,
                                   do_kill=False)
        kill = bench_pool_kill(n_kill, parallelism, batch_size,
                               do_kill=True,
                               ref_checksum=kill_ref["checksum"])
        out["data_pool_kill_rows_per_s"] = round(kill["rows_per_s"], 1)
        out["data_pool_kill_slowdown_x"] = round(
            kill_ref["rows_per_s"] / max(kill["rows_per_s"], 1e-9), 2)
        out["data_pool_kill_retries"] = kill["retries"]
        out["data_pool_kill_recovered_ok"] = kill["recovered_ok"]
        out["data_pool_kill_fired"] = kill["killed"]

        # Resumable ingest.
        with tempfile.TemporaryDirectory() as ckpt:
            res = bench_ingest_resume(n, parallelism, batch_size, ckpt)
        out["data_ingest_rows_per_s_plain"] = round(
            res["rows_per_s_plain"], 1)
        out["data_ingest_rows_per_s_journaled"] = round(
            res["rows_per_s_journaled"], 1)
        out["data_ingest_journal_overhead_pct"] = round(
            res["journal_overhead_pct"], 2)
        out["data_ingest_resume_ok"] = res["resume_ok"]
    finally:
        ray_tpu.shutdown()

    # Node-death re-derivation (own cluster: needs a second node).
    red = bench_rederive(n_rederive, 4)
    out["data_rederive_recovery_s"] = round(red["recovery_s"], 3)
    out["data_blocks_rederived"] = red["blocks_rederived"]
    out["data_rederive_recovered_ok"] = red["recovered_ok"]

    path = args.out or os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "BENCH_r11.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(out, indent=2, sort_keys=True))
    ok = (out["data_pool_kill_recovered_ok"] and out["data_pool_kill_fired"]
          and out["data_pool_kill_retries"] >= 1
          and out["data_rederive_recovered_ok"]
          and out["data_blocks_rederived"] >= 1
          and out["data_ingest_resume_ok"])
    print("ACCEPTANCE:", "PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
