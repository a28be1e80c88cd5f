"""What the two serve drivers share: bring the deployment up through
`serve.run`, reach the replica's control calls, run the load generator
(chipbench/client.py) as a process of its own, tear down, and wait for the
chip owner to be gone.

The runner process hosts the program's controller and HTTP proxy
(serve.run(_http=True) starts it in the caller); it never touches a JAX
backend."""
from __future__ import annotations

import asyncio
import gc
import json
import os
import socket
import subprocess
import sys
import time
from functools import partial
from typing import Any, Dict, List

from chipbench import common, inworker

NAME = "bench-llm"
ROUTE = "/llm"


class Bench:
    """One serve cell's life: up(), the driver's traffic, down()."""

    def __init__(self, cell: Dict[str, Any], args, phases: Dict[str, float]):
        self.cell, self.args, self.phases = cell, args, phases
        self.mix = dict(cell["mix"])
        if args.rehearse:
            self.mix.update(self.mix.get("rehearsal", {}))
        self.vocab = inworker.sizes(cell["config"], args.rehearse).V
        self.replica = None
        self.port = None

    # ------------------------------------------------------------- set-up

    def up(self) -> None:
        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.serve.deployment import Deployment
        from ray_tpu.serve.llm import build_streaming_llm_deployment

        self.rt, self.serve = ray_tpu, serve
        mix, args = self.mix, self.args
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        ray_tpu.init(**({"num_cpus": 4} if args.rehearse else {}))
        if not args.rehearse:
            found = int(ray_tpu.cluster_resources().get("TPU", 0))
            if found < self.cell["chips"]:
                raise SystemExit(f"chipbench: {found} chips, cell needs "
                                 f"{self.cell['chips']}")
        cfg = inworker.transformer_config(
            self.cell["config"], args.rehearse,
            param_dtype=mix.get("param_dtype"))
        dep = build_streaming_llm_deployment(
            cfg, partial(inworker.serve_params, self.cell["config"],
                         args.seed, args.rehearse, mix.get("param_dtype")),
            name=NAME, continuous_batching=True,
            num_tpus=None if args.rehearse else 1,
            max_prompt_len=mix["max_prompt_len"],
            max_new_tokens=mix["max_new_tokens"], num_slots=mix["slots"])
        dep = Deployment(inworker.replica_class(dep.func_or_class), dep.name,
                         dep.config).options(
            max_ongoing_requests=mix["max_ongoing_requests"])
        serve.run(dep.bind(), route_prefix=ROUTE, _http=True,
                  http_port=self.port)
        self.phases["ready"] = time.time()
        ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
        _, replicas = ray_tpu.get(ctrl.get_replicas.remote(NAME))
        self.replica = replicas[0]
        self.check = self.ctl("check", config=self.cell["config"],
                              seed=args.seed, spec=mix["check"],
                              rehearse=args.rehearse)
        self.phases["check_done"] = time.time()
        self.setup = self.ctl("setup_report")
        gc.collect()  # the set-up's garbage, now and not inside the window

    def ctl(self, op: str, **kw):
        return self.rt.get(self.replica.handle_request.remote(
            "bench", (op,), kw), timeout=600)

    async def actl(self, op: str, **kw):
        return await asyncio.get_running_loop().run_in_executor(
            None, partial(self.ctl, op, **kw))

    # ------------------------------------------------------------ traffic

    def traffic(self, kind: str, **job) -> Dict[str, Any]:
        """Run the load generator (chipbench/client.py, a process of its
        own) beside the window's control calls; returns its records with
        `t0`, the window's start on CLOCK_MONOTONIC."""
        side: Dict[str, Any] = {}
        mix = dict(self.mix, **job.pop("mix", {}))
        # 2 s for the client to start and make its traffic, then the ramp.
        t0 = time.monotonic() + 2.0 + mix["ramp_s"]
        job = dict(job, kind=kind, mix=mix, seed=self.args.seed, t0=t0,
                   seconds=self.args.seconds, vocab=self.vocab,
                   url=f"http://127.0.0.1:{self.port}{ROUTE}")
        paths = [os.path.join(common.RUN_DIR, n)
                 for n in ("client_job.json", "client_out.json")]
        with open(paths[0], "w") as f:
            json.dump(job, f)
        child = subprocess.Popen(
            [sys.executable, os.path.join(common.HERE, "client.py")] + paths)
        try:
            if not job.get("drain"):
                asyncio.run(self.window(t0, side))
            if child.wait(timeout=600):
                raise RuntimeError(f"load generator exited {child.returncode}")
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        with open(paths[1]) as f:
            out = json.load(f)
        out.update(t0=t0, side=side)
        return out

    async def window(self, t0: float, side: Dict[str, Any]) -> None:
        """Mark the window in the replica (compile and request counters),
        trace a few seconds of it when asked, and read the replica at its
        end."""
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        side["window_wall"] = time.time()
        await self.actl("window_start")
        if self.args.trace:
            spec = self.mix["trace"]
            await asyncio.sleep(
                max(0.0, t0 + spec["start_s"] - time.monotonic()))
            await self.actl("trace_start")
            await asyncio.sleep(spec["seconds"])
            side["trace"] = await self.actl("trace_stop")
        await asyncio.sleep(
            max(0.0, t0 + self.args.seconds - time.monotonic()))
        side["end"] = await self.actl("window_end")

    # ---------------------------------------------------------- tear-down

    def down(self) -> Dict[str, Any]:
        t = time.time()
        owners = common.child_pids()
        try:
            self.serve.delete(NAME)
        except Exception:
            pass
        self.serve.shutdown()
        self.rt.shutdown()
        left = common.wait_gone(owners, 90)
        if left:
            raise SystemExit(f"chipbench: workers still alive: {left}")
        return {"teardown_s": time.time() - t}


def in_window(recs, w0: float, w1: float):
    """(requests finished in the window, those of them that were ok, requests
    whose first token landed in it)."""
    done = [r for r in recs if "done" in r and w0 <= r["done"] < w1]
    first = [r for r in recs if r.get("stamps") and w0 <= r["stamps"][0] < w1]
    return done, [r for r in done if r["ok"]], first


def gaps_in(recs, w0: float, w1: float) -> List[float]:
    """Every gap between consecutive streamed tokens that ended in the
    window, over all requests, finished or not."""
    out = []
    for r in recs:
        st = r.get("stamps") or []
        out.extend(b - a for a, b in zip(st, st[1:]) if w0 <= b < w1)
    return out


def slot_seconds(recs, w0: float, w1: float) -> float:
    """Seconds of the window in which a stream held a slot: first token
    (the slot is taken at the splice) to last."""
    tot = 0.0
    for r in recs:
        st = r.get("stamps") or []
        if st:
            tot += max(0.0, min(st[-1], w1) - max(st[0], w0))
    return tot
