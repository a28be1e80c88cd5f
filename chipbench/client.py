#!/usr/bin/env python3
"""The load generator: a process of its own, one asyncio loop, no JAX.

The runner hosts the program's controller and HTTP proxy; a client sharing
its interpreter lock would stall the very relay it is timing (measured, PR
24: engine-side time per token 24.6-24.8 ms in every run, client-side
27.9-32.7 ms with the client in the runner). Real clients are elsewhere, so
this one is too: `python3 chipbench/client.py job.json out.json`.

The job names the kind of loop, the mix, the seed and `t0`, the window's
start on CLOCK_MONOTONIC (one clock for every process of the machine).
Open loop: each request is sent when it is due, from `ramp_s` before t0,
whether or not earlier ones have finished. Closed loop: `clients` callers
each send their next request when the last has finished. At the window's end
the unfinished streams are dropped (or, for the knee sweep, drained). Every
streamed line is stamped as it lands and parsed only afterwards."""
from __future__ import annotations

import asyncio
import itertools
import json
import os
import sys
import time
from typing import Any, Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import traffic_gen  # noqa: E402


async def stream(session, url: str, req: Dict[str, Any], rec: Dict[str, Any],
                 temperature: float) -> None:
    """POST one request and stamp every streamed line as it lands."""
    rec.update(sent=time.monotonic(), stamps=[], lines=[],
               prompt_len=len(req["tokens"]), want=req["max_new_tokens"])
    body = json.dumps({"tokens": req["tokens"],
                       "max_new_tokens": req["max_new_tokens"],
                       "temperature": temperature})
    try:
        async with session.post(url, data=body, headers={
                "Content-Type": "application/json",
                "X-Request-Timeout-S": "300"}) as resp:
            rec["status"] = resp.status
            async for line in resp.content:
                rec["stamps"].append(time.monotonic())
                rec["lines"].append(line)
        rec["done"] = time.monotonic()
    except asyncio.CancelledError:
        raise
    except Exception as e:  # counted as failed by settle()
        rec["error"] = repr(e)
        rec["done"] = time.monotonic()


def settle(recs: List[Dict[str, Any]], vocab: int) -> None:
    """A finished request is ok when it carried exactly the tokens asked
    for, all in range."""
    for r in recs:
        lines = r.pop("lines", [])
        if "done" not in r:
            continue
        ok = r.get("status") == 200 and "error" not in r
        for line in lines:
            try:
                tok = json.loads(line).get("token")
            except ValueError:
                tok = None
            if not isinstance(tok, int) or not 0 <= tok < vocab:
                ok = False
        r["n_out"] = len(lines)
        r["ok"] = ok and r["n_out"] == r["want"]


async def run(job: Dict[str, Any]) -> Dict[str, Any]:
    import aiohttp

    mix, t0, seconds = job["mix"], job["t0"], job["seconds"]
    url, temp = job["url"], mix.get("temperature", 0.0)
    end = t0 + seconds
    recs: List[Dict[str, Any]] = []
    tasks = []
    async with aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None)) as session:
        if job["kind"] == "open":
            reqs = traffic_gen.open_loop(mix, job["seed"], seconds,
                                         job["vocab"])
            for req in reqs:
                rec = {"due": req["due"]}
                recs.append(rec)
                wait = t0 + req["due"] - time.monotonic()
                if wait > 0:
                    await asyncio.sleep(wait)
                tasks.append(asyncio.ensure_future(
                    stream(session, url, req, rec, temp)))
        else:
            todo = itertools.cycle(
                traffic_gen.closed_loop(mix, job["seed"], job["vocab"]))

            async def caller():
                while time.monotonic() < end:
                    rec: Dict[str, Any] = {}
                    recs.append(rec)
                    await stream(session, url, next(todo), rec, temp)

            await asyncio.sleep(max(0.0, t0 - mix["ramp_s"]
                                    - time.monotonic()))
            tasks = [asyncio.ensure_future(caller())
                     for _ in range(mix["clients"])]
        await asyncio.sleep(max(0.0, end - time.monotonic()))
        t_end = time.monotonic()
        if job.get("drain"):
            await asyncio.gather(*tasks)
        else:
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
    drained = time.monotonic()
    settle(recs, job["vocab"])
    return {"recs": recs, "t_end": t_end, "drain_s": drained - t_end}


def main() -> int:
    with open(sys.argv[1]) as f:
        job = json.load(f)
    out = asyncio.run(run(job))
    with open(sys.argv[2], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
