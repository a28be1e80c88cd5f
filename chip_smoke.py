#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the two main paths through the entry points a user would call, at the
full width of ``bench_350m`` (llama-family, d_model 1024, 24 layers, 16
heads, vocab 32000; random weights from a seed):

1. serve — ``ray_tpu.init()`` with chips auto-detected, ``serve.run`` of
   ``build_streaming_llm_deployment(continuous_batching=True, num_tpus=1)``
   with one replica per chip, streamed HTTP POSTs (one cold, a concurrent
   round across the prefill buckets, one greedy prompt twice), then
   ``serve.delete`` / ``ray_tpu.shutdown()`` and the leak checks;
2. train — after the serve phase has let go of the chips, ``JaxTrainer``
   with one worker that owns every chip, a handful of
   ``transformer_train_step`` steps at batch 8 x seq 1024.

Each phase checks, from inside the process that owns the chip, that JAX is on
the tpu platform, sees exactly the chips it was granted, keeps its arrays
there, and that the compiled program carries the Pallas flash kernel. Any
failure exits non-zero naming the phase. Where no chip is found it fails at
once: it never runs the phases on the host. This driver process never
initialises a JAX backend (one process per chip: the workers own them).

Last line of stdout on success:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""
from __future__ import annotations

import json
import os
import shutil
import signal
import socket
import sys
import tempfile
import threading
import time
import traceback
import urllib.request

TIME_LIMIT_S = 1150  # the contract allows 1200, compilation included
SERVE_NAME = "smoke-llm"
MAX_PROMPT, MAX_NEW, SLOTS = 256, 64, 4
# (prompt length, new tokens): the lengths land in the 8, 16, 64, 128 and
# 256 prefill buckets (serve/llm_engine.py bucket_len).
ROUND = [(3, 16), (12, 64), (40, 32), (100, 8), (200, 24), (256, 64)]
BATCH, SEQ, STEPS = 8, 1024, 6


def log(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# --------------------------------------------------------------- on workers


def params_factory():
    """bench_350m weights from seed 0, created on the replica's device."""
    import jax

    from ray_tpu.models.configs import bench_350m
    from ray_tpu.models.transformer import init_params

    cfg = bench_350m()
    return jax.jit(lambda key: init_params(key, cfg))(jax.random.key(0))


def train_loop(config):
    """A handful of sharded train steps; reports device facts, losses and
    where the parameters live."""
    import jax
    import numpy as np

    from ray_tpu import flags, train
    from ray_tpu.models.configs import bench_350m
    from ray_tpu.train.step import transformer_train_step

    t0 = time.monotonic()
    mesh = train.get_mesh()
    cfg = bench_350m(remat=True, remat_policy="dots")
    ts = transformer_train_step(cfg, mesh, shift_inputs=True)
    params, opt_state = ts.init(jax.random.key(0))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (config["batch"], config["seq"] + 1),
        dtype=np.int32)
    batch = ts.shard_batch({"tokens": tokens})
    losses, step_s = [], []
    for _ in range(config["steps"]):
        t = time.monotonic()
        params, opt_state, loss = ts.step(params, opt_state, batch)
        losses.append(float(loss))
        step_s.append(round(time.monotonic() - t, 3))
    first_step_s = round(time.monotonic() - t0 - sum(step_s[1:]), 2)
    # After the steps, so this lowering is a compile-cache hit.
    hlo = ts.lower_step(params, opt_state, batch).compile().as_text()
    devs = jax.local_devices()
    big = params["layers"]["w_gate_up"]
    train.report({
        "pid": os.getpid(),
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(jax.devices()),
        "local_devices": [str(d) for d in devs],
        "visible_chips": flags.get("TPU_VISIBLE_CHIPS"),
        "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
        "params_on": sorted({str(d) for x in jax.tree.leaves(params)
                             for d in x.devices()}),
        "w_gate_up_spec": str(big.sharding.spec),
        "w_gate_up_shape": list(big.shape),
        "w_gate_up_shard_shape": list(big.addressable_shards[0].data.shape),
        "bytes_in_use": [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                         for d in devs],
        "losses": [round(x, 4) for x in losses],
        "first_step_s": first_step_s,
        "step_s": step_s,
        "step_has_tpu_custom_call": "tpu_custom_call" in hlo,
    })


# ---------------------------------------------------------------- the driver


def stream(port: int, tokens, n: int, out: dict) -> None:
    """POST one request; record its chunks and time to first token."""
    t0 = time.monotonic()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/llm",
            data=json.dumps({"tokens": tokens, "max_new_tokens": n}).encode(),
            headers={"Content-Type": "application/json"})
        chunks = []
        with urllib.request.urlopen(req, timeout=300) as resp:
            for line in resp:
                if "ttft_s" not in out:
                    out["ttft_s"] = round(time.monotonic() - t0, 3)
                chunks.append(json.loads(line))
        out["chunks"] = chunks
    except Exception as e:  # surfaced by check_stream in the main thread
        out["error"] = repr(e)
    out["total_s"] = round(time.monotonic() - t0, 3)


def check_stream(out: dict, n: int, vocab: int) -> list:
    check("error" not in out, f"request failed: {out.get('error')}")
    chunks = out["chunks"]
    bad = [c for c in chunks if "token" not in c]
    check(not bad, f"stream carried non-token chunks: {bad[:2]}")
    toks = [c["token"] for c in chunks]
    check(len(toks) == n, f"asked for {n} tokens, got {len(toks)}")
    check(all(isinstance(t, int) and 0 <= t < vocab for t in toks),
          f"token outside the vocabulary: {toks}")
    return toks


def our_workers() -> list:
    """Pids of live worker_main children of this process (a child that is
    exiting has already dropped its command line; a zombie is gone)."""
    pids = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                state, ppid = f.read().rsplit(")", 1)[1].split()[:2]
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read()
        except (OSError, ValueError):
            continue
        if int(ppid) == os.getpid() and state != "Z" \
                and (b"worker_main" in cmd or not cmd):
            pids.append(int(pid))
    return pids


def chip_holders() -> dict:
    """pid -> chip device files it holds open."""
    held = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue
        for fd in fds:
            try:
                path = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if path.startswith(("/dev/accel", "/dev/vfio/")) \
                    and path != "/dev/vfio/vfio":
                held.setdefault(int(pid), set()).add(path)
    return {p: sorted(v) for p, v in held.items()}


def rtpu_shm() -> set:
    return {f for f in os.listdir("/dev/shm") if "rtpu" in f}


def leak_checks(shm_before: set) -> None:
    """After shutdown: chips released the moment it returned (the next
    program may start at once), then no worker left and no new rtpu shm
    segment."""
    check(not chip_holders(),
          f"chips still held after shutdown(): {chip_holders()}")
    deadline = time.monotonic() + 20
    while our_workers() and time.monotonic() < deadline:
        time.sleep(0.2)
    check(not our_workers(), f"worker_main left running: {our_workers()}")
    shm = rtpu_shm() - shm_before
    check(not shm, f"rtpu shm segments left: {sorted(shm)}")


def serve_phase(chips: int) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.configs import bench_350m
    from ray_tpu.serve.llm import build_streaming_llm_deployment

    cfg = bench_350m()
    shm_before = rtpu_shm()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    t0 = time.monotonic()
    ray_tpu.init()
    try:
        found = int(ray_tpu.cluster_resources().get("TPU", 0))
        check(found == chips, f"init() found {found} chips, expected {chips}")
        app = build_streaming_llm_deployment(
            cfg, params_factory, name=SERVE_NAME, continuous_batching=True,
            num_tpus=1, num_replicas=chips, max_prompt_len=MAX_PROMPT,
            max_new_tokens=MAX_NEW, num_slots=SLOTS)
        serve.run(app.bind(), route_prefix="/llm", _http=True, http_port=port)
        ready_s = round(time.monotonic() - t0, 2)

        prompt = lambda n, seed: [
            (7919 * (i + seed) + 13) % cfg.vocab_size for i in range(n)]
        cold: dict = {}
        stream(port, prompt(5, 1), 8, cold)
        check_stream(cold, 8, cfg.vocab_size)

        ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
        _, replicas = ray_tpu.get(ctrl.get_replicas.remote(SERVE_NAME))
        check(len(replicas) == chips, f"{len(replicas)} replicas != {chips}")
        rounds = 0
        while True:  # concurrent rounds until every replica took traffic
            rounds += 1
            outs = [dict() for _ in ROUND * chips]
            threads = [
                threading.Thread(target=stream, args=(
                    port, prompt(plen, i), n, out))
                for i, ((plen, n), out) in enumerate(zip(ROUND * chips, outs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for (plen, n), out in zip(ROUND * chips, outs):
                check_stream(out, n, cfg.vocab_size)
            served = [s["total"] for s in ray_tpu.get(
                [r.stats.remote() for r in replicas], timeout=60)]
            if all(served):
                break
            check(rounds < 5, f"a replica took no traffic: {served}")

        twice = [dict(), dict()]
        for out in twice:
            stream(port, prompt(33, 99), 24, out)
        a, b = (check_stream(out, 24, cfg.vocab_size) for out in twice)
        check(a == b, f"greedy stream not repeatable: {a} vs {b}")

        reports = ray_tpu.get(
            [r.handle_request.remote("device_report", (), {})
             for r in replicas], timeout=300)
        for rep in reports:
            check(rep["platform"] == "tpu", f"replica not on tpu: {rep}")
            check(len(rep["local_devices"]) == 1,
                  f"replica granted 1 chip sees {rep['local_devices']}")
            check(rep["params_on"] == rep["local_devices"]
                  and rep["cache_on"] == rep["local_devices"],
                  f"params or KV cache off the replica's chip: {rep}")
            check(rep["prefill_has_tpu_custom_call"],
                  "no tpu_custom_call in the compiled prefill")
        check(len({rep["pid"] for rep in reports}) == chips,
              "replicas share a process")
        if chips > 1:
            seen = [rep["visible_chips"] for rep in reports]
            check(None not in seen and len(set(seen)) == chips,
                  f"replicas not on distinct chips: {seen}")
        strangers = set(chip_holders()) - {rep["pid"] for rep in reports}
        check(not strangers,
              f"a process other than the replicas holds a chip: {strangers}")
        facts = {
            "chips": chips, "replicas": chips,
            "device_kind": reports[0]["device_kind"],
            "visible_chips": [rep["visible_chips"] for rep in reports],
            "ready_s": ready_s, "cold_ttft_s": cold["ttft_s"],
            "cold_total_s": cold["total_s"],
            "round_ttft_s": sorted(o["ttft_s"] for o in outs),
            "rounds": rounds, "requests_per_replica": served,
            "warm_ttft_s": twice[1]["ttft_s"],
            "prefill_has_tpu_custom_call": all(
                rep["prefill_has_tpu_custom_call"] for rep in reports),
        }
        serve.delete(SERVE_NAME)
    finally:
        t0 = time.monotonic()
        serve.shutdown()
        ray_tpu.shutdown()
    leak_checks(shm_before)
    facts["shutdown_s"] = round(time.monotonic() - t0, 2)
    return facts


def train_phase(chips: int) -> dict:
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    # Several chips: parameters spread over fsdp x tensor, not resident on
    # the first device.
    mesh_shape = None if chips == 1 else (
        {"fsdp": chips // 2, "tensor": 2} if chips % 2 == 0
        else {"fsdp": chips})
    storage = tempfile.mkdtemp(prefix="chip_smoke_train_")
    shm_before = rtpu_shm()
    ray_tpu.init()
    try:
        trainer = JaxTrainer(
            train_loop,
            train_loop_config={"batch": BATCH, "seq": SEQ, "steps": STEPS},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True, tpus_per_worker=chips),
            run_config=RunConfig(name="chip_smoke", storage_path=storage),
            mesh_shape=mesh_shape)
        m = trainer.fit().metrics
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(storage, ignore_errors=True)
    check(m.get("platform") == "tpu", f"train worker not on tpu: {m}")
    check(len(m["local_devices"]) == chips and m["device_count"] == chips,
          f"worker granted {chips} chips sees {m['local_devices']}")
    check(m["params_on"] == sorted(m["local_devices"]),
          f"params on {m['params_on']}, not on all of {m['local_devices']}")
    losses = m["losses"]
    check(all(x == x and abs(x) != float("inf") for x in losses),
          f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(m["step_has_tpu_custom_call"],
          "no tpu_custom_call in the compiled train step")
    if chips > 1:
        check(m["w_gate_up_shard_shape"] != m["w_gate_up_shape"],
              f"w_gate_up not sharded: {m['w_gate_up_spec']}")
        used = m["bytes_in_use"]
        check(min(used) > 0 and max(used) < 0.5 * sum(used),
              f"model resident on one device: bytes_in_use {used}")
    leak_checks(shm_before)
    m["chips"] = chips
    return m


def main() -> int:
    from ray_tpu import flags
    from ray_tpu.util.accelerators import detect_tpu_chips

    platforms = flags.get("JAX_PLATFORMS")
    if platforms is not None and "tpu" not in platforms.split(","):
        print(f"chip_smoke: no chip to run on: JAX_PLATFORMS={platforms!r} "
              "excludes the tpu platform; refusing to run on the host",
              file=sys.stderr)
        return 1
    chips = detect_tpu_chips()
    if not chips:
        print("chip_smoke: no TPU chip found (no /dev/accel*, no "
              "/dev/vfio/<n>); refusing to run on the host", file=sys.stderr)
        return 1

    def on_alarm(signum, frame):
        raise TimeoutError(f"chip_smoke exceeded {TIME_LIMIT_S}s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(TIME_LIMIT_S)
    train = None
    for name, phase in (("serve", serve_phase), ("train", train_phase)):
        t0 = time.monotonic()
        try:
            facts = phase(chips)
        except BaseException:
            traceback.print_exc()
            print(f"chip_smoke: phase {name!r} FAILED", file=sys.stderr)
            for pid in our_workers():  # stop every process we started
                os.kill(pid, signal.SIGKILL)
            return 1
        log(name, seconds=round(time.monotonic() - t0, 1), **facts)
        train = facts
    import jax

    if jax._src.xla_bridge.backends_are_initialized():
        print("chip_smoke: the driver process initialised a JAX backend",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": train["platform"], "kind": train["device_kind"],
        "count": train["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
