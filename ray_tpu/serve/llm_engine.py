"""Continuous-batching generation engine (slot-based, vLLM-style shape).

The batch LLM deployment coalesces requests that ARRIVE together; this
engine lets requests join and leave a RUNNING batch: a fixed pool of B
slots shares one ragged KV cache (models/generate.py per-row positions),
every tick runs ONE decode_step over all slots, and a request attaches by
splicing its prefilled K/V into a free slot mid-flight. Short requests
retire without stalling long ones; new arrivals don't wait for the batch
to drain.

Compiled units (all static shapes, reused forever):
- per-length-bucket prefill of a single prompt,
- the slot splice (dynamic_update_slice on the batch axis),
- one decode tick (the [B] ragged decode_step + sampling).

The engine is deliberately serve-independent and synchronous-core: attach/
tick/poll are plain methods driven by one background thread, so it can be
tested exhaustively without actors and wired into any serving surface.
Inactive slots still compute through the tick (their rows are masked at
the sampling layer) — wasted FLOPs bounded by B, the price of a single
compiled program.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu.util import tracing

_serve_metrics_cache = None


def _serve_metrics():
    """Lazy shared serve metrics (util/metrics.py plane; tagged by model
    so every engine in the process shares the three instruments). The
    ROADMAP serve item: TTFT p99 and tokens/s must be first-class on
    /metrics, not benchmark-script printouts."""
    global _serve_metrics_cache
    if _serve_metrics_cache is None:
        from ray_tpu.util.metrics import Counter, Gauge, Histogram

        _serve_metrics_cache = {
            "ttft": Histogram(
                "rtpu_serve_ttft_s",
                description="Serve time-to-first-token: request submit "
                            "to first sampled token (prefill + splice "
                            "wait)",
                boundaries=[0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                            10.0, 30.0],
                tag_keys=("model",)),
            "tokens": Counter(
                "rtpu_serve_decode_tokens_total",
                description="Decode tokens emitted by the "
                            "continuous-batching engine",
                tag_keys=("model",)),
            "slots": Gauge(
                "rtpu_serve_slots_busy",
                description="Continuous-batching slots currently "
                            "generating",
                tag_keys=("model",)),
            "itl": Histogram(
                "rtpu_serve_itl_s",
                description="Serve inter-token latency: gap between "
                            "consecutive sampled tokens of one stream "
                            "(per decode tick, engine-side)",
                boundaries=[0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                            0.5, 1.0, 5.0],
                tag_keys=("model",)),
        }
    return _serve_metrics_cache


# Per-request token-timestamp ring capacity and the bounded finished-stats
# map: enough stamps to characterize ITL tails without unbounded growth on
# very long generations.
_TOKEN_RING = 2048
_DONE_STATS_MAX = 1024


def bucket_len(n: int, max_len: int, floor: int = 8) -> int:
    """Power-of-2 length bucket (>= floor, <= max_len): THE compile-count
    bound shared by the batch deployment and the engine — one definition
    so the two paths can't drift apart in how many programs they compile."""
    S = floor
    while S < n:
        S <<= 1
    return min(S, max_len)


class ContinuousBatchingEngine:
    """B-slot continuous batching over a shared ragged KV cache."""

    def __init__(self, cfg, params, *, num_slots: int = 4,
                 max_prompt_len: int = 128, max_new_tokens: int = 64,
                 seed: int = 0, model: str = ""):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.generate import KVCache, decode_step, prefill

        self.cfg = cfg
        self.params = params
        self.model = model or "default"
        self._mtags = {"model": self.model}
        self.B = num_slots
        self.max_prompt_len = max_prompt_len
        self.max_new = max_new_tokens
        self.max_len = max_prompt_len + max_new_tokens
        self._jax, self._jnp = jax, jnp

        L = cfg.n_layers
        KVH, hd = cfg.kv_heads, cfg.head_dim
        kv_shape = (L, self.B, self.max_len, KVH, hd)
        self.cache = KVCache(
            k=jnp.zeros(kv_shape, cfg.dtype),
            v=jnp.zeros(kv_shape, cfg.dtype),
            pos=jnp.zeros((self.B,), jnp.int32))
        self.cur_tok = jnp.zeros((self.B,), jnp.int32)

        # Host-side slot bookkeeping (engine lock; the arrays above are
        # replaced wholesale under it).
        self.lock = threading.Lock()
        self.active = [False] * self.B
        self.budget = [0] * self.B      # tokens left to emit per slot
        self.eos = [None] * self.B      # per-request eos id
        self.temp = np.zeros(self.B, np.float32)
        self.out: List[List[int]] = [[] for _ in range(self.B)]
        # Slots recycle; REQUESTS are the stable identity. submit() returns
        # a request id, finished outputs move to _results keyed by it, and
        # readers can never observe a successor request's tokens.
        self.slot_req: List[Optional[int]] = [None] * self.B
        self._req_seq = 0
        self._req_slot: Dict[int, int] = {}
        self._results: Dict[int, List[int]] = {}
        self._done_ev: Dict[int, threading.Event] = {}
        self._discarded: set = set()
        self.failed: Optional[BaseException] = None
        self._free = list(range(self.B))
        self._free_cv = threading.Condition(self.lock)
        # Token timeline (trace plane): per-live-request monotonic token
        # stamps in a bounded ring + per-request TTFT; finished requests
        # fold into a bounded summary map so the final span / ledger row
        # can carry token stats after slot recycling. _stall_flagged makes
        # the stream-stall event exactly-once per request.
        self._token_times: Dict[int, Any] = {}
        self._ttft_vals: Dict[int, float] = {}
        self._token_stats_done: "collections.OrderedDict[int, Dict]" = \
            collections.OrderedDict()
        self._stall_flagged: set = set()
        # Submitters blocked waiting for a slot: the queue-depth signal the
        # serve autoscaler scales decode pools on.
        self._waiting = 0
        self._rng = jax.random.key(seed)
        self._draws = 0

        # ---- compiled units ----
        def _prefill_one(params, tokens, length):
            # [1, S] -> (logits [1, V], k/v [L, 1, S, KVH, hd], pos [1])
            logits, cache = prefill(params, tokens, self.cfg, tokens.shape[1],
                                    lengths=length)
            return logits[0], cache.k[:, 0], cache.v[:, 0]

        self._prefill_one = jax.jit(_prefill_one)

        def _splice(ck, cv, pos, cur, slot_k, slot_v, slot_pos,
                    slot_tok, slot):
            # Insert one request's prefilled K/V + state into slot `slot`.
            ck = jax.lax.dynamic_update_slice(
                ck, slot_k[:, None], (0, slot, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cv, slot_v[:, None], (0, slot, 0, 0, 0))
            pos = pos.at[slot].set(slot_pos)
            cur = cur.at[slot].set(slot_tok)
            return ck, cv, pos, cur

        self._splice = jax.jit(_splice)

        def _tick(params, cache, cur, rng, temps):
            logits, cache = decode_step(params, cache, cur, self.cfg)
            greedy = jnp.argmax(logits, -1).astype(jnp.int32)
            scaled = logits / jnp.maximum(temps[:, None], 1e-6)
            sampled = jax.random.categorical(rng, scaled).astype(jnp.int32)
            nxt = jnp.where(temps <= 0.0, greedy, sampled)
            return nxt, logits, cache

        self._tick = jax.jit(_tick)

    # ------------------------------------------------------------ requests

    def submit(self, tokens, *, max_new_tokens: Optional[int] = None,
               temperature: float = 0.0, eos_id: Optional[int] = None,
               timeout: Optional[float] = None,
               arrival_ts: Optional[float] = None,
               queue_wait_s: Optional[float] = None) -> int:
        """Attach a request to a free slot (blocking while all slots busy).
        Returns a stable REQUEST id; poll with peek(), collect with
        result() — valid even after the slot is recycled.

        TTFT accounting measures from request ARRIVAL (queue wait
        included, the signal the serve autoscaler scales on), not from
        prefill start. ``queue_wait_s`` is the time the request already
        spent upstream, accumulated per-host with monotonic clocks
        (serve_context.elapsed_s()); the engine adds its local
        prefill + slot wait monotonically, so cross-machine wall-clock
        skew never touches the histogram. ``arrival_ts`` (epoch seconds)
        is the SAME-PROCESS alternative for embedders/tests; ignored when
        queue_wait_s is given. With neither, arrival is now."""
        mono0 = time.monotonic()
        ids = np.asarray(tokens, np.int32)
        if ids.ndim != 1 or ids.size == 0:
            raise ValueError("tokens must be a non-empty 1-D integer list")
        ids = ids[-self.max_prompt_len:]
        S = bucket_len(len(ids), self.max_prompt_len)
        # Prefill OUTSIDE the engine lock.
        from . import trace as serve_trace

        hop = serve_trace.start_hop(
            "serve.prefill", kind="prefill",
            attributes={"model": self.model, "prompt_len": len(ids),
                        "bucket": S, "local": True})
        try:
            logits1, k1, v1 = self._prefill_padded(ids, S)
        except BaseException as e:
            if hop is not None:
                hop.end(error=type(e).__name__)
            raise
        if hop is not None:
            hop.end()
        return self._attach(k1, v1, len(ids), np.asarray(logits1),
                            max_new_tokens=max_new_tokens,
                            temperature=temperature, eos_id=eos_id,
                            timeout=timeout, arrival_ts=arrival_ts,
                            queue_wait_s=queue_wait_s, mono0=mono0)

    def _prefill_padded(self, ids: np.ndarray, S: int):
        """Bucketed prefill of one prompt, K/V padded out to the engine
        max_len: (logits [V], k, v [L, max_len, KVH, hd])."""
        jnp = self._jnp
        padded = np.zeros((1, S), np.int32)
        padded[0, :len(ids)] = ids
        logits1, k1, v1 = self._prefill_one(
            self.params, jnp.asarray(padded),
            jnp.asarray([len(ids)], jnp.int32))
        pad = self.max_len - S
        if pad:
            with tracing.phase("engine.prefill.pad", bucket=S):
                k1 = jnp.pad(k1, ((0, 0), (0, pad), (0, 0), (0, 0)))
                v1 = jnp.pad(v1, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return logits1, k1, v1

    def warmup(self) -> None:
        """Compile every program a request can reach — each prefill bucket,
        the slot splice and the decode tick — by running each once on
        throwaway inputs, concurrently (XLA compiles outside the GIL).

        A serving replica calls this from its constructor, so compilation
        is start-up time covered by the deployment's ready deadline: no
        request deadline and no stream-stall window ever spans a compile.
        Nothing here touches slot state (the programs are functional and
        their results are dropped)."""
        from concurrent.futures import ThreadPoolExecutor

        jnp = self._jnp
        buckets = sorted({bucket_len(n, self.max_prompt_len)
                          for n in range(1, self.max_prompt_len + 1)})

        def bucket(S: int):
            return self._prefill_padded(np.zeros(1, np.int32), S)

        def tick():
            key = self._jax.random.fold_in(self._rng, 0)
            return self._tick(self.params, self.cache, self.cur_tok, key,
                              jnp.asarray(self.temp))

        with ThreadPoolExecutor(max_workers=len(buckets) + 1) as pool:
            futs = [pool.submit(bucket, S) for S in buckets]
            futs.append(pool.submit(tick))
            done = [f.result() for f in futs]
        _, k1, v1 = done[0]
        done.append(self._splice(
            self.cache.k, self.cache.v, self.cache.pos, self.cur_tok,
            k1, v1, jnp.asarray(1, jnp.int32), jnp.asarray(0, jnp.int32), 0))
        self._jax.block_until_ready(done)

    def device_report(self) -> Dict[str, Any]:
        """Where this engine computes, asked from inside its process: the
        devices the process sees, the devices its params and KV cache live
        on, and whether the compiled prefill carries the Pallas flash
        kernel (custom_call_target "tpu_custom_call")."""
        import os

        from ray_tpu import flags
        from ray_tpu.util import jaxenv

        jax, jnp = self._jax, self._jnp
        devs = jaxenv.devices(local=True)

        def homes(tree) -> List[str]:
            return sorted({str(d) for x in jax.tree.leaves(tree)
                           for d in x.devices()})

        hlo = self._prefill_one.lower(
            self.params, jnp.zeros((1, self.max_prompt_len), jnp.int32),
            jnp.ones((1,), jnp.int32)).compile().as_text()
        return {"pid": os.getpid(), "platform": devs[0].platform,
                "device_kind": devs[0].device_kind,
                "local_devices": [str(d) for d in devs],
                "visible_chips": flags.get("TPU_VISIBLE_CHIPS"),
                "params_on": homes(self.params),
                "cache_on": homes((self.cache.k, self.cache.v)),
                "prefill_has_tpu_custom_call": "tpu_custom_call" in hlo}

    def attach_prefilled(self, k, v, length: int, logits, *,
                         max_new_tokens: Optional[int] = None,
                         temperature: float = 0.0,
                         eos_id: Optional[int] = None,
                         timeout: Optional[float] = None,
                         arrival_ts: Optional[float] = None,
                         queue_wait_s: Optional[float] = None) -> int:
        """Attach a request whose prefill ran ELSEWHERE — a prefill-pool
        replica's handoff or a prefix-cache hit — splicing the K/V
        straight into a free slot with no prefill compute here.

        ``k``/``v`` are one request's [L, S, KVH, hd] (S = any length
        bucket <= max_len); ``logits`` the prefill's last-position [V]
        row that decides the first token. Everything else matches
        submit()."""
        jnp = self._jnp
        mono0 = time.monotonic()
        k = jnp.asarray(k, self.cfg.dtype)
        v = jnp.asarray(v, self.cfg.dtype)
        if k.ndim != 4 or v.shape != k.shape:
            raise ValueError("k/v must be [L, S, KVH, hd] for one request")
        S = int(k.shape[1])
        length = int(length)
        if not (0 < length <= S <= self.max_len):
            raise ValueError(
                f"bad handoff: length={length} bucket={S} "
                f"max_len={self.max_len}")
        pad = self.max_len - S
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return self._attach(k, v, length, np.asarray(logits),
                            max_new_tokens=max_new_tokens,
                            temperature=temperature, eos_id=eos_id,
                            timeout=timeout, arrival_ts=arrival_ts,
                            queue_wait_s=queue_wait_s, mono0=mono0)

    def _attach(self, k1, v1, length: int, logits1: np.ndarray, *,
                max_new_tokens: Optional[int], temperature: float,
                eos_id: Optional[int], timeout: Optional[float],
                arrival_ts: Optional[float],
                queue_wait_s: Optional[float] = None,
                mono0: Optional[float] = None) -> int:
        """Shared slot-wait + splice tail of submit()/attach_prefilled():
        k1/v1 are already padded to max_len, logits1 is the host [V] row.
        ``mono0`` is the caller's entry stamp so prefill time counts
        toward TTFT; ``queue_wait_s``/``arrival_ts`` as in submit().

        Trace plane: the engine-attach hop covers this host's slot wait +
        splice (the "engine slot wait" bar of the waterfall); it rides the
        caller thread's serve context, so it nests under the replica span
        automatically."""
        from . import trace as serve_trace

        hop = serve_trace.start_hop(
            "serve.engine_attach", kind="engine",
            attributes={"model": self.model})
        try:
            req = self._attach_locked(
                k1, v1, length, logits1, max_new_tokens=max_new_tokens,
                temperature=temperature, eos_id=eos_id, timeout=timeout,
                arrival_ts=arrival_ts, queue_wait_s=queue_wait_s,
                mono0=mono0, hop=hop)
        except BaseException as e:
            if hop is not None:
                hop.end(error=type(e).__name__)
            raise
        if hop is not None:
            hop.end()
        return req

    def _attach_locked(self, k1, v1, length: int, logits1: np.ndarray, *,
                       max_new_tokens: Optional[int], temperature: float,
                       eos_id: Optional[int], timeout: Optional[float],
                       arrival_ts: Optional[float],
                       queue_wait_s: Optional[float] = None,
                       mono0: Optional[float] = None, hop=None) -> int:
        if mono0 is None:
            mono0 = time.monotonic()
        # engine.attach.wait: the engine lock (held for a whole tick and
        # retaken at once) and then a free slot; .splice: the rest, under it.
        with tracing.phase("engine.attach.wait"):
            self._free_cv.acquire()
            try:
                self._wait_free_locked(timeout)
            except BaseException:
                self._free_cv.release()
                raise
        try:
            with tracing.phase("engine.attach.splice"):
                return self._splice_locked(
                    k1, v1, length, logits1, max_new_tokens=max_new_tokens,
                    temperature=temperature, eos_id=eos_id,
                    arrival_ts=arrival_ts, queue_wait_s=queue_wait_s,
                    mono0=mono0, hop=hop)
        finally:
            self._free_cv.release()

    def _wait_free_locked(self, timeout: Optional[float]) -> None:
        # One monotonic deadline for the whole wait: contended submits
        # that wake repeatedly must not restart the clock each time.
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        self._waiting += 1
        try:
            while not self._free:
                # A dead ticker thread recorded the failure and notified
                # this condition; blocking the full timeout (or forever)
                # on an engine that will never free a slot helps nobody.
                if self.failed is not None:
                    raise RuntimeError(
                        f"engine failed: {self.failed!r}")
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    raise TimeoutError("no free generation slot")
                self._free_cv.wait(timeout=remaining)
        finally:
            self._waiting -= 1
        if self.failed is not None:
            raise RuntimeError(f"engine failed: {self.failed!r}")

    def _splice_locked(self, k1, v1, length: int, logits1: np.ndarray, *,
                       max_new_tokens: Optional[int], temperature: float,
                       eos_id: Optional[int], arrival_ts: Optional[float],
                       queue_wait_s: Optional[float], mono0: float,
                       hop) -> int:
        jnp = self._jnp
        slot = self._free.pop()
        self._req_seq += 1
        req = self._req_seq
        self.slot_req[slot] = req
        self._req_slot[req] = slot
        self._done_ev[req] = threading.Event()
        # First token comes from the prefill logits, decided under the
        # lock with the slot's sampling config.
        first = self._pick_host(logits1, temperature)
        m = _serve_metrics()
        # Skew-free TTFT: upstream wait is a per-host monotonic
        # accumulation, local wait (prefill + slot) is this host's
        # monotonic delta. The epoch arrival_ts path is same-process
        # only, where wall-clock deltas are safe.
        local_wait = time.monotonic() - mono0
        if queue_wait_s is not None:
            ttft = max(0.0, float(queue_wait_s)) + local_wait
        elif arrival_ts is not None:
            ttft = max(0.0, time.time() - float(arrival_ts))
        else:
            ttft = local_wait
        m["ttft"].observe(ttft, tags=self._mtags)
        m["tokens"].inc(1.0, tags=self._mtags)
        # Token timeline: stamp the first token on this host's
        # monotonic clock; tick() appends one stamp per decode token.
        # Gated on the trace flag so RTPU_SERVE_TRACE=0 keeps the
        # timeline/ITL/stall plane to a single flag check.
        from . import trace as serve_trace

        if serve_trace.enabled():
            self._token_times[req] = collections.deque(
                [time.monotonic()], maxlen=_TOKEN_RING)
            self._ttft_vals[req] = float(ttft)
        if hop is not None:
            hop.attributes.update(
                slot=slot, ttft_s=round(float(ttft), 6),
                slot_wait_s=round(local_wait, 6))
        n = min(max_new_tokens or self.max_new, self.max_new)
        self.active[slot] = True
        self.budget[slot] = n - 1
        self.eos[slot] = eos_id
        self.temp[slot] = temperature
        self.out[slot] = [int(first)]
        ck, cv, pos, cur = self._splice(
            self.cache.k, self.cache.v, self.cache.pos, self.cur_tok,
            k1, v1, jnp.asarray(length, jnp.int32),
            jnp.asarray(int(first), jnp.int32), slot)
        from ray_tpu.models.generate import KVCache

        self.cache = KVCache(k=ck, v=cv, pos=pos)
        self.cur_tok = cur
        if self.budget[slot] <= 0 or (eos_id is not None
                                      and int(first) == eos_id):
            self._retire_locked(slot)
        m["slots"].set(self.B - len(self._free), tags=self._mtags)
        return req

    def prefill_only(self, tokens):
        """Run this engine's bucketed prefill WITHOUT taking a slot:
        returns host ``(k, v, length, logits)`` with k/v [L, S, KVH, hd]
        (S = the length bucket) — exactly the handoff blob
        attach_prefilled() accepts. The prefill pool and the prefix cache
        both speak this format."""
        jnp = self._jnp
        ids = np.asarray(tokens, np.int32)
        if ids.ndim != 1 or ids.size == 0:
            raise ValueError("tokens must be a non-empty 1-D integer list")
        ids = ids[-self.max_prompt_len:]
        S = bucket_len(len(ids), self.max_prompt_len)
        padded = np.zeros((1, S), np.int32)
        padded[0, :len(ids)] = ids
        logits1, k1, v1 = self._prefill_one(
            self.params, jnp.asarray(padded),
            jnp.asarray([len(ids)], jnp.int32))
        return (np.asarray(k1), np.asarray(v1), len(ids),
                np.asarray(logits1))

    def stats(self) -> Dict[str, float]:
        """Load snapshot for the serve controller's signal poll: busy/total
        slots, occupancy in [0,1], and submitters blocked on a slot."""
        with self.lock:
            busy = self.B - len(self._free)
            return {"slots_busy": float(busy),
                    "slots_total": float(self.B),
                    "occupancy": busy / float(self.B),
                    "queued": float(self._waiting)}

    def _pick_host(self, logits: np.ndarray, temperature: float) -> int:
        if temperature <= 0:
            return int(np.argmax(logits))
        jax = self._jax
        self._draws += 1
        key = jax.random.fold_in(self._rng, self._draws)
        return int(jax.random.categorical(
            key, self._jnp.asarray(logits) / max(temperature, 1e-6)))

    def _summarize_locked(self, req: int, *, cause: str = "") -> None:
        """Fold a request's token ring into the bounded finished-stats
        map (called at retirement, under the engine lock) so the final
        stream span / ledger row can read token counts + ITL percentiles
        after the slot recycles."""
        dq = self._token_times.pop(req, None)
        ttft = self._ttft_vals.pop(req, None)
        self._stall_flagged.discard(req)
        if dq is None:
            return
        stamps = list(dq)
        itls = [b - a for a, b in zip(stamps, stamps[1:])]
        slot = self._req_slot.get(req)
        tokens = len(self.out[slot]) if slot is not None else len(stamps)
        st: Dict[str, Any] = {"tokens": tokens, "ttft_s": ttft,
                              "abort_cause": cause}
        if itls:
            srt = sorted(itls)
            st.update(
                itl_mean_s=sum(itls) / len(itls),
                itl_p50_s=srt[len(srt) // 2],
                itl_p99_s=srt[min(len(srt) - 1, int(len(srt) * 0.99))],
                itl_max_s=srt[-1])
        self._token_stats_done[req] = st
        while len(self._token_stats_done) > _DONE_STATS_MAX:
            self._token_stats_done.popitem(last=False)

    def _retire_locked(self, slot: int) -> None:
        self.active[slot] = False
        req = self.slot_req[slot]
        if req is not None:
            self._summarize_locked(
                req, cause="discarded" if req in self._discarded else "")
            if req in self._discarded:
                # Consumer went away mid-stream: drop the output instead
                # of storing it for a reader that will never come.
                self._discarded.discard(req)
                self._done_ev.pop(req, None)
            else:
                self._results[req] = list(self.out[slot])
                self._done_ev[req].set()
            self._req_slot.pop(req, None)
            self.slot_req[slot] = None
        self._free.append(slot)
        self._free_cv.notify_all()

    def discard(self, req: int) -> None:
        """Consumer abandoned the request (client disconnect): release its
        stored output now, or mark it to be dropped at retirement — either
        way no per-request state outlives the reader."""
        with self.lock:
            if req in self._results or (req in self._done_ev
                                        and req not in self._req_slot):
                self._results.pop(req, None)
                self._done_ev.pop(req, None)
                return
            slot = self._req_slot.get(req)
            if slot is not None:
                self._discarded.add(req)
                self.budget[slot] = 0  # retire at the next tick

    def abort(self, req: int) -> bool:
        """Cancel a request NOW, between engine steps: the slot (and its
        KV rows) frees immediately under the engine lock and any stored
        output is dropped. Unlike discard(), which lets the slot retire at
        the NEXT tick, abort is the disconnect path's guarantee that
        capacity frees within one step. Returns True if the request was
        known (live or finished), False for an unknown/already-released
        id — callers treat double-abort as a no-op."""
        with self.lock:
            slot = self._req_slot.get(req)
            if slot is not None:
                # Summarize FIRST with the abort cause: _retire_locked's
                # own summarize is then a no-op (ring already folded).
                self._summarize_locked(req, cause="aborted")
                self._discarded.add(req)
                self._retire_locked(slot)
                _serve_metrics()["slots"].set(
                    self.B - len(self._free), tags=self._mtags)
                return True
            if req in self._results or req in self._done_ev:
                self._results.pop(req, None)
                self._done_ev.pop(req, None)
                return True
            return False

    # ---------------------------------------------------------------- tick

    def tick(self) -> int:
        """One decode step for every active slot; returns #active after.

        The whole tick holds the engine lock: a snapshot-compute-swap
        design would let a submit() splice land between snapshot and swap
        and be ERASED by the swap. submit's slow part (prefill compile/run)
        is outside the lock, so attaches wait at most one tick for the
        fast splice. Inactive slots compute garbage rows (their pos keeps
        advancing; writes clamp harmlessly) — the price of one compiled
        program; a splice fully re-initializes a slot on attach."""
        jax, jnp = self._jax, self._jnp
        if not any(self.active):  # unlocked look: an idle pass takes no lock
            return 0
        # Every phase is entered and folded INSIDE the lock, and the wait
        # for it is observed after the fact: the loop gives the lock up and
        # retakes it at once, and bookkeeping between the two would hand it
        # to waiters more often than an untraced engine does (measured).
        t_wait = time.monotonic_ns()
        with self.lock:
            tracing.observe("engine.tick.lock",
                            time.monotonic_ns() - t_wait, t_wait)
            if not any(self.active):
                return 0
            with tracing.phase("engine.tick", live=sum(self.active),
                               waiting=self._waiting):
                with tracing.phase("engine.tick.dispatch"):
                    self._draws += 1
                    key = jax.random.fold_in(self._rng, self._draws)
                    temps = jnp.asarray(self.temp)
                    nxt, logits, cache = self._tick(
                        self.params, self.cache, self.cur_tok, key, temps)
                with tracing.phase("engine.tick.readback"):
                    nxt_host = np.asarray(nxt)
                with tracing.phase("engine.tick.emit"):
                    self.cache = cache
                    self.cur_tok = nxt
                    self._emit_locked(nxt_host)
            return sum(self.active)

    def _emit_locked(self, nxt_host: np.ndarray) -> None:
        """Hand one tick's tokens to their streams: append, stamp, retire."""
        emitted = 0
        now_m = time.monotonic()
        m_itl = _serve_metrics()["itl"]
        for s in range(self.B):
            if not self.active[s]:
                continue
            tok = int(nxt_host[s])
            self.out[s].append(tok)
            # Token timeline: one monotonic stamp per emitted token
            # feeds the ITL histogram and the stream-stall detector.
            dq = self._token_times.get(self.slot_req[s])
            if dq is not None:
                m_itl.observe(now_m - dq[-1], tags=self._mtags)
                dq.append(now_m)
            emitted += 1
            self.budget[s] -= 1
            if self.budget[s] <= 0 or (self.eos[s] is not None
                                       and tok == self.eos[s]):
                self._retire_locked(s)
        if emitted:
            m = _serve_metrics()
            m["tokens"].inc(float(emitted), tags=self._mtags)
            m["slots"].set(self.B - len(self._free), tags=self._mtags)

    # ------------------------------------------------------------- results

    def result(self, req: int, timeout: Optional[float] = None) -> List[int]:
        """Block until the request finished; returns its tokens. The
        result stays retrievable (and peek-able) after slot recycling;
        pop_result() releases it."""
        ev = self._done_ev.get(req)
        if ev is None:
            raise KeyError(f"unknown request {req}")
        if not ev.wait(timeout=timeout):
            raise TimeoutError(f"request {req} still generating")
        with self.lock:
            if self.failed is not None and req not in self._results:
                raise RuntimeError(
                    f"generation engine failed: {self.failed!r}")
            return list(self._results[req])

    def pop_result(self, req: int) -> List[int]:
        """result() + release the stored output (bounds memory for
        long-running engines)."""
        out = self.result(req)
        with self.lock:
            self._results.pop(req, None)
            self._done_ev.pop(req, None)
        return out

    def is_done(self, req: int) -> bool:
        ev = self._done_ev.get(req)
        return ev is not None and ev.is_set()

    def check_failed(self) -> Optional[BaseException]:
        return self.failed

    def peek(self, req: int) -> List[int]:
        return self.peek_stamped(req)[0]

    def peek_stamped(self, req: int, sent: int = 0):
        """Tokens emitted so far (streaming consumers poll this), and the
        engine's stamp (CLOCK_MONOTONIC) of token number ``sent``, the
        oldest one a consumer that has taken ``sent`` tokens has not seen
        (None when there is none, the token timeline is off, or the request
        has finished).

        The stream-stall detector lives here rather than in the ticker:
        a hung tick thread (the main way a stream stalls) can't run its
        own watchdog, but the consumer polling peek() is alive by
        definition — it notices the silence and fires the exactly-once
        STREAM_STALLED event with a stack capture of every thread."""
        stalled_age = None
        with self.lock:
            done = self._results.get(req)
            if done is not None:
                return list(done), None
            slot = self._req_slot.get(req)
            if slot is None:
                raise KeyError(f"unknown request {req}")
            out = list(self.out[slot])
            dq = self._token_times.get(req)
            stamp = None
            if dq and sent < len(out):  # the ring holds the last stamps
                stamp = dq[max(0, len(dq) - (len(out) - sent))]
            if dq is not None and req not in self._stall_flagged:
                from ray_tpu import flags

                stall_s = float(flags.get("RTPU_SERVE_STALL_S") or 0.0)
                if stall_s > 0:
                    age = time.monotonic() - dq[-1]
                    if age > stall_s:
                        self._stall_flagged.add(req)
                        stalled_age = age
        if stalled_age is not None:
            self._emit_stall(req, stalled_age)
        return out, stamp

    def _emit_stall(self, req: int, age_s: float) -> None:
        """Ship the STREAM_STALLED cluster event (outside the engine lock:
        the stack capture walks every thread's frames)."""
        from ray_tpu.core import events
        from . import context as serve_context
        from . import trace as serve_trace

        rid = serve_context.get_request_id()
        try:
            events.emit(
                "WARNING", "STREAM_STALLED",
                f"stream {rid or req} on model {self.model} emitted no "
                f"token for {age_s:.1f}s with a live slot",
                source="serve",
                data={"stack": serve_trace.capture_stacks(),
                      "request_id": rid, "engine_req": req,
                      "model": self.model, "age_s": round(age_s, 3)})
        except Exception:
            pass

    # ------------------------------------------------------- token stats

    def token_stats(self, req: int) -> Optional[Dict[str, Any]]:
        """Per-request token timeline summary: token count, TTFT, ITL
        mean/p50/p99/max, abort cause. Live requests get an in-flight
        summary; finished ones read the bounded done-map (so the final
        stream span can attach stats AFTER the slot recycled — call this
        BEFORE abort() on cleanup paths, which records cause=aborted)."""
        with self.lock:
            st = self._token_stats_done.get(req)
            if st is not None:
                return dict(st)
            dq = self._token_times.get(req)
            if dq is None:
                return None
            stamps = list(dq)
            itls = [b - a for a, b in zip(stamps, stamps[1:])]
            slot = self._req_slot.get(req)
            out: Dict[str, Any] = {
                "tokens": len(self.out[slot]) if slot is not None
                else len(stamps),
                "ttft_s": self._ttft_vals.get(req), "abort_cause": ""}
            if itls:
                srt = sorted(itls)
                out.update(
                    itl_mean_s=sum(itls) / len(itls),
                    itl_p50_s=srt[len(srt) // 2],
                    itl_p99_s=srt[min(len(srt) - 1,
                                      int(len(srt) * 0.99))],
                    itl_max_s=srt[-1])
            return out

    def last_token_age(self, req: int) -> Optional[float]:
        """Seconds since the request's newest token (monotonic), None for
        unknown/finished requests — the stall detector's raw signal."""
        with self.lock:
            dq = self._token_times.get(req)
            if dq is None:
                return None
            return time.monotonic() - dq[-1]

    # ------------------------------------------------------- driver thread

    def run_forever(self, stop: threading.Event, idle_sleep: float = 0.005):
        """Tick loop for a background thread: ticks while any slot is
        active, sleeps briefly when idle."""
        import time

        while not stop.is_set():
            try:
                n = self.tick()
            except BaseException as e:  # device/runtime failure
                # A dead ticker must not strand pollers: record the
                # failure, wake every waiter, and stop. is_done()/result()
                # surface the error instead of hanging forever.
                with self.lock:
                    self.failed = e
                    for ev in self._done_ev.values():
                        ev.set()
                    self._free_cv.notify_all()
                return
            if n == 0:
                with tracing.phase("engine.idle"):
                    time.sleep(idle_sleep)
