"""Per-host daemon: the node-level agent of the cluster.

Role-equivalent to the reference's raylet (ray: src/ray/raylet/main.cc:123
starting NodeManager + local object manager, node_manager.h:119): one agent
per host, it

- registers its host as a node with the controller over TCP,
- owns the host's object arena (creates it; local workers inherit it),
- spawns and supervises worker processes on *its* host when the controller
  grants a lease (spawn delegation replaces the controller's local Popen),
- serves chunked object pulls to remote peers (core.transfer protocol,
  reference object_manager.proto Push/Pull),
- heartbeats node health + arena stats to the controller
  (gcs_health_check_manager.h:39 semantics),
- fate-shares: when the controller connection drops, it kills its workers
  and exits (raylet workers fate-share with their raylet).

Entrypoint: ``python -m ray_tpu.core.host_agent --controller HOST:PORT``.
Tests simulate a second host on one machine by overriding RTPU_HOST_ID
(--host-id), which forces every cross-"host" object read through the real
TCP pull path.
"""
from __future__ import annotations

from ray_tpu import flags

import argparse
import asyncio
import json
import os
import signal
import subprocess
import sys
import time
import uuid
from typing import Any, Dict, Optional

from . import native_store, protocol, transfer, worker_env
from .ids import NodeID

HEARTBEAT_S = flags.get("RTPU_HEARTBEAT_S")


class HostAgent:
    def __init__(
        self,
        controller_addr: str,
        *,
        resources: Optional[Dict[str, float]] = None,
        labels: Optional[Dict[str, str]] = None,
        host_id: Optional[str] = None,
        serve_host: str = "127.0.0.1",
        serve_port: int = 0,
    ):
        self.controller_addr = controller_addr
        self.node_id = NodeID.generate()
        self.resources = dict(resources or {"CPU": float(os.cpu_count() or 1)})
        # Unit-instance chip pool for TPU_VISIBLE_CHIPS assignment (the agent
        # owns its worker processes, so it owns the per-worker chip ids —
        # reference: raylet-side GPU instance accounting).
        self.tpu_free: list = list(range(int(self.resources.get("TPU", 0))))
        self.tpu_alloc: Dict[str, list] = {}  # spawn_token -> chip ids
        self.labels = dict(labels or {})
        self.serve_host = serve_host
        self.serve_port = serve_port
        self.ctrl: Optional[protocol.Connection] = None
        self.server: Optional[asyncio.base_events.Server] = None
        self.procs: Dict[str, subprocess.Popen] = {}  # spawn_token -> proc
        self.worker_tokens: Dict[str, str] = {}  # worker_id -> spawn_token
        self._stop = asyncio.Event()
        self._draining = False  # a self-drain request is in flight
        # Unshipped cluster events (core/events.py records): flushed on the
        # heartbeat path, so delivery is reconnect-safe for free — a batch
        # pending across a controller bounce rides the first heartbeat on
        # the re-established connection.
        self._pending_events: list = []
        if host_id:
            flags.set_env("RTPU_HOST_ID", host_id)
        from .object_store import current_host_id

        self.host_id = current_host_id()
        # The agent owns this host's arena; spawned workers inherit RTPU_ARENA.
        self.arena = native_store.create_node_arena(self.node_id)

    # ---------------------------------------------------------------- startup

    async def start(self) -> None:
        self.server = await asyncio.start_server(
            self._on_peer, self.serve_host, self.serve_port
        )
        self.serve_port = self.server.sockets[0].getsockname()[1]
        host, port = self.controller_addr.rsplit(":", 1)
        self.ctrl = await protocol.connect(
            host, int(port), self._on_controller_msg, name="agent->controller"
        )
        await self.ctrl.request(self._register_msg())
        loop = asyncio.get_running_loop()
        loop.create_task(self._heartbeat_loop())
        loop.create_task(self._watch_controller())
        loop.create_task(self._reap_loop())
        if flags.get("RTPU_PREEMPTION_WATCHER"):
            loop.create_task(self._preemption_watch_loop())

    def _register_msg(self) -> Dict[str, Any]:
        return {
            "kind": "register_node",
            "node_id": self.node_id,
            "resources": self.resources,
            "labels": self.labels,
            "agent_addr": [self.serve_host, self.serve_port],
            "host_id": self.host_id,
            "arena": self.arena.name if self.arena else None,
            # Live state, re-reported on reconnect so a restarted
            # controller can reconcile (harmless on first contact): chips
            # currently granted to worker processes, and the live workers.
            "tpu_in_use": sorted(
                c for ids in self.tpu_alloc.values() for c in ids),
            "workers": {tok: proc.pid for tok, proc in self.procs.items()
                        if proc.poll() is None},
        }

    async def _watch_controller(self) -> None:
        """Reconnect with capped exponential backoff when the controller
        connection drops (reference: raylet re-registration on
        NotifyGCSRestart, node_manager.proto:373). Only after the reconnect
        deadline passes does the agent fate-share: kill workers and exit."""
        while not self._stop.is_set():
            ctrl = self.ctrl
            await ctrl.closed.wait()
            if self._stop.is_set():
                return
            if self.ctrl is not ctrl:
                continue  # deliberately swapped by _try_reregister
            if not await self._reconnect():
                self._terminate_workers()
                self._stop.set()
                return

    async def _reconnect(self) -> bool:
        host, port = self.controller_addr.rsplit(":", 1)
        max_s = flags.get("RTPU_RECONNECT_MAX_S")
        deadline = time.monotonic() + max_s
        backoff = flags.get("RTPU_RECONNECT_BACKOFF_S")
        while not self._stop.is_set():
            try:
                ctrl = await protocol.connect(
                    host, int(port), self._on_controller_msg,
                    name="agent->controller")
                await ctrl.request(self._register_msg(), timeout=10)
                self.ctrl = ctrl
                sys.stderr.write(
                    f"[host_agent] reconnected to controller at "
                    f"{self.controller_addr}\n")
                return True
            except Exception as e:
                now = time.monotonic()
                if now >= deadline:
                    sys.stderr.write(
                        f"[host_agent] controller unreachable after "
                        f"{max_s:.0f}s ({e!r}); shutting down\n")
                    return False
                await asyncio.sleep(min(backoff, deadline - now))
                backoff = min(backoff * 2, 2.0)
        return False

    async def _try_reregister(self, rpc_t: float) -> bool:
        """Dial a fresh connection and re-register on it WITHOUT dropping
        the current one; only a successful handshake swaps them (the
        controller's register handler updates node.agent_conn, so the old
        conn's close is then harmless)."""
        host, port = self.controller_addr.rsplit(":", 1)
        ctrl = None
        try:
            ctrl = await protocol.connect(
                host, int(port), self._on_controller_msg,
                name="agent->controller")
            await ctrl.request(self._register_msg(),
                               timeout=max(rpc_t * 2, 2.0))
        except Exception:
            if ctrl is not None:
                try:
                    await ctrl.close()
                except Exception:
                    pass
            return False
        old, self.ctrl = self.ctrl, ctrl
        sys.stderr.write("[host_agent] re-registered over a fresh "
                         "connection after unacknowledged heartbeats\n")
        try:
            await old.close()
        except Exception:
            pass
        return True

    # ------------------------------------------------- drain / preemption

    def _emit_event(self, severity: str, kind: str, message: str,
                    **entities) -> None:
        """Queue one cluster event for the next heartbeat flush."""
        from . import events

        if not events.enabled():
            return
        self._pending_events.append(events.make_event(
            severity, "agent", kind, message,
            node_id=entities.pop("node_id", self.node_id), **entities))
        del self._pending_events[:-256]  # bounded, oldest drop first

    async def _flush_events(self) -> None:
        if not self._pending_events:
            return
        batch, self._pending_events = self._pending_events, []
        try:
            await self.ctrl.send({"kind": "cluster_events", "events": batch})
        except Exception:
            # Controller unreachable: re-buffer for the next heartbeat.
            self._pending_events = batch + self._pending_events
            del self._pending_events[:-256]

    async def _preemption_watch_loop(self) -> None:
        """Poll the cloud metadata preemption endpoint (GCE: the
        instance/preempted key flips to TRUE ~30s before the VM dies;
        RTPU_PREEMPTION_URL makes it pluggable so tests serve a fake) and
        self-drain on the first notice — the cluster migrates this host's
        actors/tasks/objects during the notice window instead of taking a
        crash."""
        url = flags.get("RTPU_PREEMPTION_URL")
        poll = flags.get("RTPU_PREEMPTION_POLL_S")
        while not self._stop.is_set():
            try:
                await asyncio.wait_for(self._stop.wait(), poll)
                return
            except asyncio.TimeoutError:
                pass
            try:
                notice = await asyncio.to_thread(self._poll_preemption, url)
            except Exception:
                continue  # metadata server flake: keep watching
            if notice:
                sys.stderr.write(
                    f"[host_agent] preemption notice at {url}; draining "
                    f"node {self.node_id[:8]}\n")
                self._emit_event(
                    "WARNING", "NODE_PREEMPTION_NOTICE",
                    f"preemption notice received on node "
                    f"{self.node_id[:8]}; self-draining",
                    data={"url": url})
                self.initiate_drain("preemption")
                return

    @staticmethod
    def _poll_preemption(url: str) -> bool:
        import urllib.request

        req = urllib.request.Request(
            url, headers={"Metadata-Flavor": "Google"})
        with urllib.request.urlopen(req, timeout=2) as resp:
            body = resp.read(256).decode("utf-8", "replace").strip()
        return body.upper() not in ("", "FALSE", "NONE", "0")

    def initiate_drain(self, reason: str) -> None:
        """Ask the controller to drain this node (idempotent). Called from
        the preemption watcher and the SIGTERM handler — both run on the
        event loop. A second call (second SIGTERM, or drain already
        pending) forces immediate shutdown instead."""
        if self._draining:
            self._stop.set()
            return
        self._draining = True
        deadline_s = flags.get("RTPU_DRAIN_DEADLINE_S")

        async def _drain():
            try:
                await self.ctrl.request(
                    {"kind": "drain_node", "node_id": self.node_id,
                     "reason": reason, "deadline_s": deadline_s},
                    timeout=10)
            except Exception as e:
                sys.stderr.write(
                    f"[host_agent] drain request failed ({e!r}); "
                    f"shutting down hard\n")
                self._stop.set()
                return
            # The controller finishes the drain by sending us "shutdown".
            # Backstop: if that never arrives (controller died mid-drain),
            # exit once the grace window (plus slack) has passed rather
            # than serving a cluster that thinks we're gone.
            try:
                await asyncio.wait_for(self._stop.wait(), deadline_s + 15)
            except asyncio.TimeoutError:
                sys.stderr.write(
                    "[host_agent] drain never completed; exiting\n")
                self._stop.set()

        asyncio.get_running_loop().create_task(_drain())

    async def run_forever(self) -> None:
        await self._stop.wait()
        if self.server is not None:
            self.server.close()
        self._terminate_workers()
        native_store.close_arena(destroy=True)

    def _terminate_workers(self) -> None:
        for proc in self.procs.values():
            if proc.poll() is None:
                try:
                    proc.terminate()
                except Exception:
                    pass
        self.procs.clear()

    # --------------------------------------------------------- controller rpc

    async def _on_controller_msg(self, conn, msg: Dict[str, Any]) -> Any:
        kind = msg["kind"]
        if kind == "spawn_worker":
            renv = msg.get("runtime_env")
            if renv and (renv.get("pip") or renv.get("conda")):
                # venv/conda creation takes seconds: keep the agent loop
                # live (same pip-or-conda gate as the controller's local
                # spawn path — they must not diverge or one side silently
                # launches env-hashed workers without the env).
                from .runtime_env import spawner_python

                try:
                    python = await asyncio.to_thread(spawner_python, renv)
                except Exception as e:
                    sys.stderr.write(
                        f"[host_agent] runtime env build failed: {e!r}\n")
                    await self.ctrl.send(
                        {"kind": "spawn_exited",
                         "spawn_token": msg["spawn_token"],
                         "node_id": self.node_id, "returncode": -1,
                         "env_failed": renv.get("hash", ""),
                         "env_error": str(e)[:500]})
                    return {"ok": False}
                return self._spawn_worker(msg, python=python)
            return self._spawn_worker(msg)
        if kind == "kill_worker":
            tok = msg.get("spawn_token") or self.worker_tokens.get(
                msg.get("worker_id", "")
            )
            # Terminate but leave the proc in self.procs: chips must return
            # to the pool only when the process has ACTUALLY exited (the
            # reap loop frees them) — a SIGTERM'd worker can hold the
            # devices open for seconds, and granting its chips to a new
            # spawn meanwhile hits libtpu "device in use".
            proc = self.procs.get(tok) if tok else None
            if proc is not None and proc.poll() is None:
                try:
                    proc.terminate()
                except Exception:
                    pass
            return {"ok": True}
        if kind == "kill_pgid":
            # Job-plane orphan/stop sweep: escalate through one process
            # group (a dead supervisor's entrypoint and its shell=True
            # children live in their own session on THIS host). Runs off
            # the agent loop — the grace window would stall heartbeats.
            from .job_manager import kill_process_group

            ok = await asyncio.to_thread(
                kill_process_group, int(msg.get("pgid") or 0),
                float(msg.get("grace_s") or 3.0))
            return {"ok": bool(ok)}
        if kind == "free_object":
            loc = msg["loc"]
            from .object_store import free_location

            try:
                free_location(loc)
            except Exception:
                pass
            return {"ok": True}
        if kind == "shutdown":
            self._stop.set()
            return {"ok": True}
        if kind in transfer.PULL_SERVER_KINDS:
            return await transfer.handle_pull_server_message(conn, msg)
        if kind == "replicate_push":
            # Broadcast source on this host: stream the object's bytes down
            # the hop chain (each byte leaves this host once) and report
            # how many were shipped so the controller's per-broadcast
            # source-byte accounting stays truthful.
            async def _push(msg=msg):
                sent = 0
                err = None
                try:
                    sent = await transfer.push_replicate_chain(
                        msg["loc"], msg["chain"], msg["bid"],
                        chunk=msg.get("chunk"), window=msg.get("window"))
                except Exception as e:  # noqa: BLE001 — reported, re-routed
                    err = repr(e)[:300]
                try:
                    await self.ctrl.send(
                        {"kind": "replicate_push_done", "bid": msg["bid"],
                         "bytes": sent, "error": err})
                except Exception:
                    pass

            asyncio.get_running_loop().create_task(_push())
            return {"ok": True}
        if kind == "list_logs":
            # This host's worker log files with sizes (cluster log index
            # building block; reference: the dashboard log API's per-node
            # file listing).
            from .worker_logs import list_log_files

            return list_log_files()
        if kind == "tail_log":
            # Bounded tail of one worker log (dashboard log viewer + crash
            # post-mortems; attribution markers are stripped so the tail
            # reads like the process's console did).
            from .worker_logs import log_dir, read_tail

            name = os.path.basename(msg["name"])  # no traversal
            nbytes = min(int(msg.get("bytes", 65536)), 1 << 20)
            try:
                return read_tail(os.path.join(log_dir(), name), nbytes)
            except OSError as e:
                return f"<log unavailable: {e}>"
        if kind == "get_log":
            # Ranged / task-filtered / long-poll log read (the `rtpu logs`
            # fetch + follow backend; reference: the `ray logs` CLI and
            # dashboard log endpoints streaming any file on any node).
            from .worker_logs import serve_get_log_wait

            m = dict(msg)
            m["name"] = os.path.basename(m.get("name") or "")
            return await serve_get_log_wait(m)
        raise ValueError(f"host_agent: unknown message kind {kind!r}")

    def _spawn_worker(self, msg: Dict[str, Any],
                      python: Optional[str] = None) -> Dict[str, Any]:
        spawn_token = msg["spawn_token"]
        chips = None
        if msg.get("tpu"):
            chips = worker_env.grant_chips(
                self.tpu_free, max(1, int(msg.get("tpu_chips") or 1)))
            if chips:
                self.tpu_alloc[spawn_token] = chips
        plumbing = {"RTPU_HOST_ID": self.host_id}
        if self.arena is not None:
            plumbing["RTPU_ARENA"] = self.arena.name
        env = worker_env.worker_env(
            controller=self.controller_addr, node_id=self.node_id,
            spawn_token=spawn_token, tpu_chips=chips,
            node_chips=int(self.resources.get("TPU", 0)),
            sys_path=msg.get("sys_path"),
            runtime_env=msg.get("runtime_env"), **plumbing)
        from .worker_logs import worker_log_file

        log_f = worker_log_file(spawn_token)
        cmd = [python or sys.executable, "-m", "ray_tpu.core.worker_main"]
        renv_spec = msg.get("runtime_env")
        if renv_spec and renv_spec.get("container"):
            from .runtime_env import container_command

            cmd = container_command(renv_spec, cmd)
        try:
            proc = subprocess.Popen(
                cmd,
                env=env,
                stdout=log_f,
                stderr=subprocess.STDOUT if log_f else None,
            )
        except OSError as e:
            # Unwind the chip grant: a launch that never produced a process
            # has no reap event to return the chips through. The synthetic
            # spawn_exited unwinds the controller's spawning counters the
            # same way a pre-register death would.
            self.tpu_free.extend(self.tpu_alloc.pop(spawn_token, []))
            sys.stderr.write(f"[host_agent] worker launch failed: {e!r}\n")
            self._emit_event(
                "ERROR", "WORKER_LAUNCH_FAILED",
                f"worker launch failed on node {self.node_id[:8]}: {e!r}",
                data={"error": str(e)})
            asyncio.get_running_loop().create_task(self.ctrl.send(
                {"kind": "spawn_exited", "spawn_token": spawn_token,
                 "node_id": self.node_id, "returncode": -1}))
            return {"ok": False, "error": str(e)}
        self.procs[spawn_token] = proc
        return {"ok": True, "pid": proc.pid}

    async def _reap_loop(self) -> None:
        """Report workers that die before (or after) registering so the
        controller's spawning counters and worker table stay truthful."""
        while not self._stop.is_set():
            await asyncio.sleep(0.2)
            for tok, proc in list(self.procs.items()):
                if proc.poll() is not None:
                    self.procs.pop(tok, None)
                    self.tpu_free.extend(self.tpu_alloc.pop(tok, []))
                    try:
                        await self.ctrl.send(
                            {"kind": "spawn_exited", "spawn_token": tok,
                             "node_id": self.node_id,
                             "returncode": proc.returncode}
                        )
                    except Exception:
                        pass

    def _proc_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-worker-process cpu%/rss (reference: the dashboard agent's
        reporter sampling its node's worker processes). cpu_percent uses
        the interval since the previous heartbeat's call — free."""
        out: Dict[str, Dict[str, float]] = {}
        try:
            import psutil
        except Exception:
            return out
        for token, proc in list(self.procs.items()):
            if proc.poll() is not None:
                continue
            try:
                p = self._psutil_cache.get(proc.pid)
                if p is None:
                    p = psutil.Process(proc.pid)
                    self._psutil_cache[proc.pid] = p
                    p.cpu_percent(None)  # prime the interval
                with p.oneshot():
                    out[str(proc.pid)] = {
                        "cpu_percent": p.cpu_percent(None),
                        "rss": float(p.memory_info().rss),
                    }
            except Exception:
                self._psutil_cache.pop(proc.pid, None)
        return out

    async def _heartbeat_loop(self) -> None:
        self._psutil_cache: Dict[int, Any] = {}
        # Partition detection (RTPU_RPC_TIMEOUT_S > 0): heartbeats become
        # acknowledged requests; once the controller has not answered one
        # for RTPU_NODE_TIMEOUT_S the agent assumes the connection is
        # blackholed-but-open and closes it, entering the reconnect loop —
        # a healed partition re-registers (the controller's suspect phase
        # kept the node's actors), a dead controller fate-shares as before.
        # 0 (default) keeps heartbeats fire-and-forget.
        last_ack = time.monotonic()
        while not self._stop.is_set():
            stats = self.arena.stats() if self.arena else {}
            try:
                import psutil

                mem_fraction = psutil.virtual_memory().percent / 100.0
            except Exception:
                mem_fraction = None
            try:
                import psutil as _ps

                cpu_percent = _ps.cpu_percent(None)
            except Exception:
                cpu_percent = None
            from .worker_logs import log_volume_bytes
            try:
                from .object_store import spill_stats

                spill = spill_stats()
            except Exception:
                spill = {}
            try:
                from .object_store import host_channel_stats

                channels = host_channel_stats()
            except Exception:
                channels = {}

            hb = {
                "kind": "heartbeat",
                "node_id": self.node_id,
                "t": time.time(),
                "arena": stats,
                # Host-wide spill usage ({files, bytes}): the census
                # "spill" tier and the `rtpu status` STORE column.
                "spill": spill,
                # Channel-fabric footprint ({segments, bytes}): live
                # rtpu_ch_* shm rings on this host — the node-level view
                # of the compiled-DAG channel plane.
                "channels": channels,
                "num_workers": len(self.procs),
                "mem_fraction": mem_fraction,
                # Host CPU% (the `rtpu status` per-node column).
                "cpu_percent": cpu_percent,
                "proc_stats": self._proc_stats(),
                # Per-node log volume (rtpu_worker_log_bytes gauge).
                "log_bytes": log_volume_bytes(),
            }
            rpc_t = flags.get("RTPU_RPC_TIMEOUT_S")
            if rpc_t:
                try:
                    await self.ctrl.request(hb, timeout=max(rpc_t, 1.0))
                    last_ack = time.monotonic()
                except Exception:
                    if (time.monotonic() - last_ack
                            > flags.get("RTPU_NODE_TIMEOUT_S")):
                        # Suspected partition: try a PARALLEL re-register.
                        # The old connection stays up meanwhile — closing
                        # it would FIN through the blackhole and make the
                        # controller declare this node dead, exactly the
                        # churn the suspect phase avoids; an app-level heal
                        # resumes the old conn, a TCP-level death heals via
                        # the fresh dial.
                        if await self._try_reregister(rpc_t):
                            last_ack = time.monotonic()
            else:
                try:
                    await self.ctrl.send(hb)
                except Exception:
                    pass
            await self._flush_events()
            try:
                await asyncio.wait_for(self._stop.wait(), HEARTBEAT_S)
            except asyncio.TimeoutError:
                pass

    # ------------------------------------------------------------ pull server

    async def _on_peer(self, reader, writer) -> None:
        conn = protocol.Connection(reader, writer, self._on_peer_msg, name="agent-peer")
        conn.start()
        await conn.closed.wait()

    async def _on_peer_msg(self, conn, msg: Dict[str, Any]) -> Any:
        kind = msg["kind"]
        if kind in transfer.PULL_SERVER_KINDS:
            return await transfer.handle_pull_server_message(conn, msg)
        if kind in transfer.REPLICATE_KINDS:
            # Broadcast chain hop: write incoming chunks into this host's
            # arena/shm and forward downstream while still receiving; the
            # sealed replica is reported to the controller over the agent's
            # control connection (reconnect-safe channel).
            async def _report(payload):
                await self.ctrl.send(payload)

            return await transfer.handle_replicate_message(
                conn, msg, node_id=self.node_id, report=_report)
        if kind == "ping":
            return {"pong": True, "node_id": self.node_id}
        raise ValueError(f"host_agent peer: unknown message kind {kind!r}")


async def _amain(args) -> int:
    agent = HostAgent(
        args.controller,
        resources=json.loads(args.resources) if args.resources else None,
        labels=json.loads(args.labels) if args.labels else None,
        host_id=args.host_id or None,
        serve_port=args.port,
    )

    def _sigterm(*_a):
        # Graceful departure: SIGTERM triggers a drain — workers keep
        # running while the controller migrates actors and re-queues tasks
        # — instead of an immediate worker kill. A second SIGTERM (or
        # SIGINT) forces the old immediate shutdown.
        agent.initiate_drain("manual")

    def _sigint(*_a):
        agent._stop.set()

    loop = asyncio.get_running_loop()
    try:
        loop.add_signal_handler(signal.SIGTERM, _sigterm)
    except NotImplementedError:
        pass
    try:
        loop.add_signal_handler(signal.SIGINT, _sigint)
    except NotImplementedError:
        pass
    try:
        await agent.start()
    except (ConnectionError, OSError) as e:
        sys.stderr.write(f"host_agent: cannot reach controller: {e!r}\n")
        return 2
    await agent.run_forever()
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description="ray_tpu per-host agent daemon")
    ap.add_argument("--controller", required=True, help="controller HOST:PORT")
    ap.add_argument("--resources", default="", help='JSON, e.g. {"CPU": 4}')
    ap.add_argument("--labels", default="", help="JSON labels")
    ap.add_argument("--host-id", default="", help="override host identity (tests)")
    ap.add_argument("--port", type=int, default=0, help="pull-server port")
    args = ap.parse_args()
    if args.host_id:
        # Must be set before the arena env leaks to children.
        flags.set_env("RTPU_HOST_ID", args.host_id)
    return asyncio.run(_amain(args))


if __name__ == "__main__":
    raise SystemExit(main())
