"""HTTP proxy: route prefix -> deployment handle.

Parity: reference serve/_private/proxy.py:1112 (ProxyActor, HTTPProxy :748
ASGI). An aiohttp server runs on a dedicated thread (inside the driver or a
proxy actor); requests route by longest-prefix match against the
controller's route table and dispatch through the same DeploymentHandle /
power-of-two router as Python callers. JSON in/out; non-JSON bodies pass
through as text.
"""
from __future__ import annotations

import asyncio
import json
import threading
import time
from typing import Any, Dict, Optional

import ray_tpu
from ray_tpu import flags
from ray_tpu.util import tracing
from ray_tpu.core.controller import DeadlineExceededError

from . import trace
from .admission import BackPressureError
from .controller import CONTROLLER_NAME
from .handle import DeploymentHandle, DeploymentNotFoundError


def _request_timeout_s(request) -> float:
    """Per-request end-to-end budget: X-Request-Timeout-S header when the
    client sends one, else the RTPU_SERVE_REQUEST_TIMEOUT_S flag default
    (the fix for the old hard-coded 60s)."""
    hdr = request.headers.get("X-Request-Timeout-S")
    if hdr:
        try:
            v = float(hdr)
            if v > 0:
                return v
        except ValueError:
            pass
    return float(flags.get("RTPU_SERVE_REQUEST_TIMEOUT_S"))


class HTTPProxy:
    def __init__(self, host: str = "127.0.0.1", port: int = 8000):
        self.host = host
        self.port = port
        self._handles: Dict[str, DeploymentHandle] = {}
        self._routes: Dict[str, str] = {}
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._started = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._runner = None
        # Streaming responses park a thread per open connection between
        # chunks; a dedicated pool keeps slow streams from starving the
        # default executor that serves every non-streaming request.
        from concurrent.futures import ThreadPoolExecutor

        self._stream_pool = ThreadPoolExecutor(
            max_workers=64, thread_name_prefix="proxy-stream")

    # ----------------------------------------------------------------- serve

    def start(self) -> None:
        self._thread.start()
        if not self._started.wait(10):
            raise RuntimeError("HTTP proxy failed to start")

    def _refresh_routes(self) -> None:
        ctrl = ray_tpu.get_actor(CONTROLLER_NAME)
        self._routes = ray_tpu.get(ctrl.get_route_info.remote())

    def _match(self, path: str) -> Optional[Dict[str, Any]]:
        best = None
        for prefix, info in self._routes.items():
            if path == prefix or path.startswith(prefix.rstrip("/") + "/") \
                    or prefix == "/":
                if best is None or len(prefix) > len(best[0]):
                    best = (prefix, info)
        return best[1] if best else None

    async def _handle(self, request):
        from aiohttp import web

        info = self._match(request.path)
        if info is None:
            self._refresh_routes()
            info = self._match(request.path)
        if info is None:
            return web.json_response(
                {"error": f"no route for {request.path}"}, status=404)
        name = info["name"]
        if request.method == "GET":
            arg: Any = dict(request.query)
        else:
            body = await request.read()
            try:
                arg = json.loads(body) if body else None
            except json.JSONDecodeError:
                arg = body.decode()
        handle = self._handles.setdefault(name, DeploymentHandle(name))
        timeout_s = _request_timeout_s(request)
        # Ingress stamping: the client's X-Request-Id or a generated one —
        # every ledger row / cancellation event downstream carries it, and
        # it echoes back on the response for log correlation. The proxy
        # owns the trace root, so the record's wall is true end-to-end
        # (handle dispatch + replica + result/stream relay).
        rid = request.headers.get("X-Request-Id") or trace.new_request_id()
        root = trace.start_request(request_id=rid, deployment=name,
                                   proto="http", method=request.method)
        tctx = root.trace_ctx if root is not None else None
        hdrs = {"X-Request-Id": rid}
        if info.get("stream"):
            return await self._handle_streaming(request, handle, name, arg,
                                                timeout_s, rid, root)
        try:
            # The deadline threads end-to-end: router admission, replica
            # dequeue, and batch seal all honor it — result() just waits
            # out the same budget.
            resp = await asyncio.get_running_loop().run_in_executor(
                None, lambda: handle.options(
                    deadline_s=timeout_s, request_id=rid, trace_ctx=tctx)
                .remote(arg).result())
        except DeploymentNotFoundError:
            # Deployment was deleted: drop the stale route + handle.
            self._handles.pop(name, None)
            self._refresh_routes()
            if root is not None:
                root.finish("error", error="deployment not found")
            return web.json_response(
                {"error": f"deployment {name} not found"}, status=404,
                headers=hdrs)
        except BackPressureError as e:
            if root is not None:
                root.finish("shed", error=str(e), http_status=503)
            return web.json_response(
                {"error": str(e)}, status=503,
                headers=dict(hdrs, **{"Retry-After":
                                      f"{max(1, round(e.retry_after_s))}"}))
        except DeadlineExceededError as e:
            if root is not None:
                root.finish("deadline", error=str(e), http_status=504)
            return web.json_response({"error": str(e)}, status=504,
                                     headers=hdrs)
        except Exception as e:
            if root is not None:
                root.finish("error", error=str(e), http_status=500)
            return web.json_response({"error": str(e)}, status=500,
                                     headers=hdrs)
        if root is not None:
            root.finish("ok", http_status=200)
        if isinstance(resp, (dict, list, int, float, bool)) or resp is None:
            return web.json_response({"result": resp}, headers=hdrs)
        return web.Response(text=str(resp), headers=hdrs)

    async def _handle_streaming(self, request, handle, name: str, arg,
                                timeout_s: Optional[float] = None,
                                rid: str = "", root=None):
        """Chunked-transfer response fed by a streaming deployment call
        (reference: serve HTTP streaming responses over the generator
        protocol). Each yielded item becomes one chunk; str/bytes pass
        through, anything else is JSON + newline. Client disconnect closes
        the deployment stream, which aborts the replica-side generator
        (GeneratorExit) and frees its engine slot immediately."""
        from aiohttp import web

        hdrs = {"X-Request-Id": rid} if rid else {}
        tctx = root.trace_ctx if root is not None else None
        loop = asyncio.get_running_loop()
        try:
            # assign() does blocking controller/replica RPCs — keep them off
            # the proxy event loop (the non-streaming path does the same).
            gen = await loop.run_in_executor(
                self._stream_pool,
                lambda: iter(handle.options(
                    stream=True, deadline_s=timeout_s, request_id=rid,
                    trace_ctx=tctx).remote(arg)))
        except BackPressureError as e:
            if root is not None:
                root.finish("shed", error=str(e), http_status=503)
            return web.json_response(
                {"error": str(e)}, status=503,
                headers=dict(hdrs, **{"Retry-After":
                                      f"{max(1, round(e.retry_after_s))}"}))
        except DeadlineExceededError as e:
            if root is not None:
                root.finish("deadline", error=str(e), http_status=504)
            return web.json_response({"error": str(e)}, status=504,
                                     headers=hdrs)
        except Exception as e:
            if root is not None:
                root.finish("error", error=str(e), http_status=500)
            return web.json_response({"error": str(e)}, status=500,
                                     headers=hdrs)
        resp = web.StreamResponse(headers=hdrs)
        resp.enable_chunked_encoding()
        await resp.prepare(request)
        _END = object()
        items = 0
        complete = False
        failed = deadline = False
        try:
            while True:
                try:
                    item = await loop.run_in_executor(
                        self._stream_pool, lambda: next(gen, _END))
                except DeadlineExceededError:
                    deadline = True
                    break
                except Exception:
                    failed = True
                    break  # mid-stream failure: terminate the chunked body
                if item is _END:
                    complete = True
                    break
                if isinstance(item, bytes):
                    data = item
                elif isinstance(item, str):
                    data = item.encode()
                else:
                    data = (json.dumps(item) + "\n").encode()
                t0 = time.monotonic_ns()  # across an await: observed,
                await resp.write(data)    # not a per-thread phase
                tracing.observe("proxy.write", time.monotonic_ns() - t0, t0)
                items += 1
        finally:
            # Reached on normal end AND on client disconnect (aiohttp
            # raises/cancels out of resp.write): cancel the producer so a
            # walked-away client never keeps a KV slot warm.
            if root is not None:
                root.finish("ok" if complete
                            else "deadline" if deadline
                            else "error" if failed else "cancelled",
                            items=items)
            close = getattr(gen, "close", None)
            if close is not None:
                await loop.run_in_executor(self._stream_pool, close)
        await resp.write_eof()
        return resp

    def _run(self) -> None:
        from aiohttp import web

        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)
        app = web.Application()
        app.router.add_route("*", "/{tail:.*}", self._handle)
        self._runner = web.AppRunner(app)

        async def _start():
            await self._runner.setup()
            site = web.TCPSite(self._runner, self.host, self.port)
            await site.start()

        self._loop.run_until_complete(_start())
        self._started.set()
        self._loop.run_forever()

    def stop(self) -> None:
        if self._loop is None:
            return

        async def _cleanup():
            await self._runner.cleanup()
            self._loop.stop()

        asyncio.run_coroutine_threadsafe(_cleanup(), self._loop)
        self._thread.join(timeout=5)
