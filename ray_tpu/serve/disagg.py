"""Disaggregated LLM serving: prefill and decode as separate pools.

The continuous-batching engine (llm_engine.py) couples two very
different workloads on one replica: prefill (compute-bound, O(prompt)
FLOPs, bursty) and decode (HBM-bound, steady per-token). Splitting them
— the DistServe/Mooncake shape, and the decoupled generate path LlamaRL
builds on — lets each pool scale on its own signal and keeps long
prefills from stealing decode ticks.

Data path per request (ingress -> decode -> prefill):

1. The ingress hashes the prompt (prefix_cache.prefix_key) and dispatches
   the stream to a DECODE replica with rendezvous affinity on that hash —
   plus the controller's hot-prefix routing table (handle.py), so the
   request lands where its K/V already lives.
2. Prefix-cache HIT: the decode replica splices the resident K/V into a
   free slot (engine.attach_prefilled) — no prefill anywhere, TTFT is
   just the splice + first tick.
3. MISS: the decode replica calls its prefill-pool handle. The prefill
   replica runs length-bucketed prefill and returns the K/V blob as its
   result; pulling that result IS PR 7's streamed raw-tail worker<->worker
   transfer (producer-serves-own-objects, recv_into the destination
   buffer) — bytes move prefill->decode directly, never through the
   ingress or controller. The blob lands in the replica's prefix cache,
   then splices mid-flight into a slot.
4. The ingress relays tokens, counting what it has delivered. If the
   decode replica dies mid-stream it re-dispatches to another replica
   (router refresh + the same affinity hash, so a cached holder is
   preferred; re-prefill otherwise) and SKIPS the tokens already sent —
   greedy decoding replays exactly, so the client sees no duplicate and
   no lost token. Sampled requests (temperature > 0) cannot be resumed
   this way: each replica follows its own sampling trajectory, so a
   mid-stream death after tokens were delivered surfaces as an error
   instead of silently stitching two incompatible generations (a
   sampled stream with NO tokens delivered yet still retries — a fresh
   trajectory is a valid response).

Prefix hashes are derived server-side from the tokens, always: the hash
keys the prefix cache, so trusting a client-supplied ``prefix_hash``
would let one request poison (or read) the cached K/V of another
prompt. The field is stripped from incoming requests.

``RTPU_SERVE_DISAGG=0`` collapses build_disagg_llm_deployment to the
unified single-pool continuous-batching deployment with the identical
request/response contract.
"""
from __future__ import annotations

import logging
import time
from typing import Any, Dict, Optional

import numpy as np

import ray_tpu
from ray_tpu import flags
from ray_tpu.util import tracing

from .deployment import deployment
from .llm import build_streaming_llm_deployment
from .prefix_cache import PrefixCache, prefix_key

logger = logging.getLogger(__name__)

_disagg_metrics_cache = None


def _disagg_metrics():
    global _disagg_metrics_cache
    if _disagg_metrics_cache is None:
        from ray_tpu.util.metrics import Counter

        _disagg_metrics_cache = {
            "handoff": Counter(
                "rtpu_serve_handoff_bytes_total",
                description="K/V bytes handed off prefill->decode over "
                            "the streamed worker-to-worker object path",
                tag_keys=("model",)),
            "reroutes": Counter(
                "rtpu_serve_reroutes_total",
                description="Token streams re-dispatched to another "
                            "decode replica after a mid-stream replica "
                            "failure",
                tag_keys=("model",)),
        }
    return _disagg_metrics_cache


def build_disagg_llm_deployment(cfg, params_factory, *, name: str = "llm",
                                num_prefill_replicas: int = 1,
                                num_decode_replicas: int = 1,
                                num_slots: int = 4,
                                max_prompt_len: int = 256,
                                max_new_tokens: int = 64,
                                num_tpus: Optional[int] = None,
                                quantize_int8: bool = False,
                                prefill_scaling_policy: Optional[Dict] = None,
                                decode_scaling_policy: Optional[Dict] = None,
                                prefix_cache_mb: Optional[float] = None):
    """The disaggregated LLM application: returns an Application for
    serve.run whose ingress speaks the same streamed
    {"tokens": [...]} -> {"token": id}* contract as
    build_streaming_llm_deployment (which it degrades to, byte-identical,
    when RTPU_SERVE_DISAGG=0).

    ``*_scaling_policy`` dicts (serve/autoscaler.py ScalingPolicy fields)
    put each pool under the signal-driven autoscaler."""
    if not flags.get("RTPU_SERVE_DISAGG"):
        return build_streaming_llm_deployment(
            cfg, params_factory, name=name,
            max_prompt_len=max_prompt_len,
            max_new_tokens=max_new_tokens,
            num_replicas=num_decode_replicas, num_tpus=num_tpus,
            quantize_int8=quantize_int8, continuous_batching=True,
            num_slots=num_slots).bind()

    actor_opts = {"num_tpus": num_tpus} if num_tpus else None

    @deployment(name=f"{name}-prefill",
                num_replicas=num_prefill_replicas,
                ray_actor_options=actor_opts, pool="prefill",
                scaling_policy=prefill_scaling_policy)
    class PrefillWorker:
        """Length-bucketed prefill; returns the handoff blob as its call
        result — the decode replica's pull of that result is the
        streamed worker<->worker transfer."""

        def __init__(self):
            import threading

            import jax

            from ray_tpu.models.generate import prefill

            self._params = params_factory()
            if quantize_int8:
                from ray_tpu.models.quantize import quantize_params_int8

                self._params = quantize_params_int8(self._params)

            def _pf(params, tokens, length):
                logits, cache = prefill(params, tokens, cfg,
                                        tokens.shape[1], lengths=length)
                return logits[0], cache.k[:, 0], cache.v[:, 0]

            self._prefill = jax.jit(_pf)
            self._lock = threading.Lock()
            self._inflight = 0

        def prefill(self, tokens) -> Dict[str, Any]:
            import jax.numpy as jnp

            from ray_tpu.serve import trace
            from ray_tpu.serve.llm_engine import bucket_len

            with self._lock:
                self._inflight += 1
            # Prefill-execution span on the PREFILL replica's own clock:
            # the nested handle call carried the trace over, so this
            # lands in the same waterfall as the decode-side hops.
            hop = trace.start_hop("serve.prefill", kind="prefill",
                                  attributes={"model": name})
            try:
                ids = np.asarray(tokens, np.int32)
                if ids.ndim != 1 or ids.size == 0:
                    raise ValueError("tokens must be a non-empty 1-D "
                                     "integer list")
                ids = ids[-max_prompt_len:]
                S = bucket_len(len(ids), max_prompt_len)
                padded = np.zeros((1, S), np.int32)
                padded[0, :len(ids)] = ids
                logits, k, v = self._prefill(
                    self._params, jnp.asarray(padded),
                    jnp.asarray([len(ids)], jnp.int32))
                if hop is not None:
                    hop.attributes.update(prompt_len=len(ids), bucket=S)
                return {"k": np.asarray(k), "v": np.asarray(v),
                        "length": len(ids), "logits": np.asarray(logits)}
            except BaseException as e:
                if hop is not None:
                    hop.end(error=type(e).__name__)
                    hop = None
                raise
            finally:
                if hop is not None:
                    hop.end()
                with self._lock:
                    self._inflight -= 1

        def __call__(self, tokens) -> Dict[str, Any]:
            return self.prefill(tokens)

        def serve_stats(self) -> Dict[str, float]:
            n = self._inflight
            return {"queued": float(max(0, n - 1)),
                    "slots_busy": float(min(n, 1)),
                    "slots_total": 1.0,
                    "occupancy": float(min(n, 1))}

    @deployment(name=f"{name}-decode", num_replicas=num_decode_replicas,
                # Well above num_slots: excess streams block INSIDE the
                # engine's slot wait (where they register as queue depth —
                # the autoscaler's primary signal) instead of saturating
                # the actor mailbox, which would starve the controller's
                # stats/health probes exactly when the pool is overloaded.
                max_ongoing_requests=max(64, 4 * num_slots), stream=True,
                ray_actor_options=actor_opts, pool="decode",
                scaling_policy=decode_scaling_policy)
    class DecodeWorker:
        """Continuous-batching decode replica with a resident prefix
        cache; prefill comes from the cache, the prefill pool, or (last
        resort) locally."""

        def __init__(self, prefill_handle=None):
            import os
            import threading

            from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

            self._params = params_factory()
            if quantize_int8:
                from ray_tpu.models.quantize import quantize_params_int8

                self._params = quantize_params_int8(self._params)
            self._engine = ContinuousBatchingEngine(
                cfg, self._params, num_slots=num_slots,
                max_prompt_len=max_prompt_len,
                max_new_tokens=max_new_tokens,
                seed=int.from_bytes(os.urandom(4), "little"), model=name)
            self._prefill_pool = prefill_handle
            mb = prefix_cache_mb
            self._cache = PrefixCache(
                max_bytes=None if mb is None else int(mb * 2**20),
                model=name)
            self._mtags = {"model": name}
            self._stop = threading.Event()
            self._ticker = threading.Thread(
                target=self._engine.run_forever, args=(self._stop,),
                daemon=True)
            self._ticker.start()

        # ------------------------------------------------------ the stream

        def _obtain_prefill(self, h: str, ids: np.ndarray,
                            timeout: Optional[float]):
            """(k, v, length, logits) for this prompt: cache hit ->
            resident blob; miss -> prefill pool (streamed handoff pull);
            pool failure -> local prefill fallback."""
            from ray_tpu.serve import trace

            e = self._cache.get(h)
            if e is not None:
                return e.k, e.v, e.length, e.logits
            blob = None
            if self._prefill_pool is not None:
                # KV-handoff span: the prefill-pool RPC + result pull —
                # its dwell IS the transfer time, bytes attached below.
                hop = trace.start_hop("serve.kv_handoff", kind="handoff",
                                      attributes={"model": name})
                try:
                    blob = self._prefill_pool.prefill.remote(
                        [int(t) for t in ids]).result(timeout=timeout)
                    nbytes = float(blob["k"].nbytes + blob["v"].nbytes
                                   + blob["logits"].nbytes)
                    _disagg_metrics()["handoff"].inc(
                        nbytes, tags=self._mtags)
                    if hop is not None:
                        hop.end(bytes=int(nbytes))
                        hop = None
                except Exception as exc:
                    if hop is not None:
                        hop.end(error=type(exc).__name__)
                        hop = None
                    logger.warning(
                        "prefill pool unavailable (%s); falling back to "
                        "local prefill", exc)
                    blob = None
            if blob is None:
                hop = trace.start_hop("serve.prefill", kind="prefill",
                                      attributes={"model": name,
                                                  "local": True})
                try:
                    k, v, length, logits = self._engine.prefill_only(ids)
                except BaseException as exc:
                    if hop is not None:
                        hop.end(error=type(exc).__name__)
                    raise
                if hop is not None:
                    hop.end()
                blob = {"k": k, "v": v, "length": length,
                        "logits": logits}
            self._cache.put(h, blob["k"], blob["v"], blob["length"],
                            blob["logits"])
            return (blob["k"], blob["v"], blob["length"], blob["logits"])

        def __call__(self, request: Dict[str, Any]):
            from ray_tpu.serve import context as serve_context
            from ray_tpu.serve import trace

            try:
                ids = np.asarray(request["tokens"], np.int32)
                if ids.ndim != 1 or ids.size == 0:
                    raise ValueError("tokens must be a non-empty 1-D "
                                     "integer list")
                n = int(request.get("max_new_tokens", max_new_tokens))
                if n <= 0:
                    raise ValueError("max_new_tokens must be positive")
                n = min(n, max_new_tokens)
                temp = float(request.get("temperature", 0.0))
                eos = request.get("eos_id")
                eos = None if eos is None else int(eos)
            except Exception as e:
                yield {"error": f"bad request: {e}"}
                return
            ids = ids[-max_prompt_len:]
            # Always derived from the tokens, never read from the request:
            # a forged hash would poison the cache entry for another
            # prompt (or serve that prompt's cached K/V and logits here).
            h = prefix_key(ids)
            timeout = serve_context.remaining_s(default=300.0)
            # The stream span covers prefill-obtain -> attach -> last
            # token on THIS replica; its end attaches the engine's token
            # stats (computed BEFORE abort, which would drop the ring).
            hop = trace.start_hop(
                "serve.stream", kind="decode",
                attributes={"model": name,
                            "prefix_hit": h in self._cache})
            req = None
            sent = 0
            status = "ok"
            try:
                try:
                    k, v, length, logits = self._obtain_prefill(h, ids,
                                                                timeout)
                    req = self._engine.attach_prefilled(
                        k, v, length, logits, max_new_tokens=n,
                        temperature=temp, eos_id=eos, timeout=timeout,
                        queue_wait_s=serve_context.elapsed_s())
                except TimeoutError as e:
                    status = "slot_timeout"
                    yield {"error": f"overloaded: {e}"}
                    return
                while True:
                    if serve_context.expired():
                        from ray_tpu.core.controller import (
                            DeadlineExceededError,
                        )

                        status = "deadline"
                        raise DeadlineExceededError(
                            "request deadline passed mid-stream")
                    toks, stamp = self._engine.peek_stamped(req, sent)
                    if stamp is not None:
                        tracing.observe("stream.poll_lag", int(
                            (time.monotonic() - stamp) * 1e9))
                    while sent < len(toks):
                        yield {"token": toks[sent]}
                        sent += 1
                    if self._engine.check_failed() is not None \
                            and not self._engine.is_done(req):
                        status = "engine_failed"
                        yield {"error": "generation engine failed"}
                        return
                    if self._engine.is_done(req):
                        try:
                            tail = self._engine.pop_result(req)[sent:]
                        except RuntimeError as e:
                            status = "engine_failed"
                            yield {"error": str(e)}
                            return
                        for tok in tail:
                            yield {"token": tok}
                            sent += 1
                        return
                    time.sleep(0.005)
            except BaseException as e:
                if status == "ok":
                    status = ("cancelled"
                              if isinstance(e, GeneratorExit)
                              else type(e).__name__)
                raise
            finally:
                st = (self._engine.token_stats(req) or {}) \
                    if req is not None else {}
                if req is not None:
                    self._engine.abort(req)
                if hop is not None:
                    attrs: Dict[str, Any] = {"sent": sent,
                                             "status": status}
                    for k_, v_ in st.items():
                        if v_ is not None:
                            attrs[k_] = (round(v_, 6)
                                         if isinstance(v_, float) else v_)
                    hop.end(**attrs)

        # -------------------------------------------------- prefix plane

        def has_prefix(self, h: str) -> bool:
            return h in self._cache

        def export_prefix(self, h: str) -> Optional[Dict[str, Any]]:
            return self._cache.export(h)

        def pull_prefix(self, h: str, holder) -> bool:
            """Promotion pull: fetch a cluster-hot blob straight from the
            holder replica actor (controller only brokers WHO, the bytes
            stream holder->here)."""
            if not self._cache.enabled or h in self._cache:
                return True
            try:
                blob = ray_tpu.get(
                    holder.handle_request.remote("export_prefix", (h,),
                                                 {}),
                    timeout=30.0)
            except Exception:
                return False
            if not blob:
                return False
            return self._cache.insert_blob(h, blob)

        def cache_stats(self) -> Dict[str, Any]:
            return self._cache.stats()

        def pid(self) -> int:
            import os

            return os.getpid()

        def serve_stats(self) -> Dict[str, Any]:
            out: Dict[str, Any] = self._engine.stats()
            out["prefix"] = self._cache.stats()
            return out

        def __del__(self):
            try:
                self._stop.set()
            except Exception:
                pass

    @deployment(name=name, stream=True, max_ongoing_requests=64)
    class DisaggIngress:
        """Routes streams to the decode pool with prefix affinity and
        replays across decode-replica death without duplicating or
        losing tokens (exact replay needs greedy decoding; a sampled
        stream that already delivered tokens fails over to an error)."""

        def __init__(self, decode_handle):
            self._decode = decode_handle
            self._mtags = {"model": name}

        def __call__(self, request: Dict[str, Any]):
            from ray_tpu.core.controller import DeadlineExceededError

            from .admission import BackPressureError
            from .trace import start_hop

            if not isinstance(request, dict) or "tokens" not in request:
                yield {"error": "expected {'tokens': [...]} request body"}
                return
            try:
                ids = np.asarray(request["tokens"],
                                 np.int32)[-max_prompt_len:]
                # Server-derived affinity/cache key; any client-supplied
                # prefix_hash is dropped (cache-poisoning vector).
                h = prefix_key(ids)
                greedy = float(request.get("temperature", 0.0) or 0.0) <= 0.0
            except Exception as e:
                yield {"error": f"bad request: {e}"}
                return
            request = dict(request)
            request.pop("prefix_hash", None)
            retries = int(flags.get("RTPU_SERVE_DISAGG_RETRIES"))
            sent = 0
            attempt = 0
            while True:
                stream = None
                # One span PER ATTEMPT, all on the ingress replica's
                # clock and sharing the request's trace_id: when a decode
                # replica dies mid-stream and its unshipped spans die with
                # it, the ledger row still links every attempt.
                attempt_hop = start_hop(
                    "serve.decode_attempt", kind="ingress",
                    attributes={"model": name, "attempt": attempt + 1,
                                "skip": sent})
                try:
                    stream = self._decode.options(
                        stream=True,
                        multiplexed_model_id=h).remote(request)
                    skip = sent
                    for chunk in stream:
                        if isinstance(chunk, dict) and "error" in chunk:
                            if "engine failed" in str(chunk["error"]):
                                # Sick replica: retryable elsewhere.
                                raise RuntimeError(chunk["error"])
                            yield chunk
                            return
                        if skip:
                            # Replayed prefix of a re-dispatched stream:
                            # the client already has these tokens.
                            skip -= 1
                            continue
                        sent += 1
                        yield chunk
                    return
                except (BackPressureError, DeadlineExceededError):
                    raise
                except Exception as e:
                    if attempt_hop is not None:
                        attempt_hop.end(error=type(e).__name__,
                                        sent=sent)
                        attempt_hop = None
                    if sent and not greedy:
                        # Sampled streams don't replay: another replica
                        # follows a different trajectory, so skipping
                        # `sent` tokens would stitch two incompatible
                        # generations. Surface the failure instead.
                        yield {"error": "decode replica died mid-stream; "
                                        "sampled (temperature > 0) "
                                        "streams cannot be resumed: "
                                        f"{e}"}
                        return
                    attempt += 1
                    if attempt > retries:
                        yield {"error": f"decode stream failed after "
                                        f"{attempt} attempts: {e}"}
                        return
                    _disagg_metrics()["reroutes"].inc(1.0,
                                                      tags=self._mtags)
                    logger.warning(
                        "decode stream for %s died (%s); re-routing "
                        "(attempt %d, %d tokens already delivered)",
                        name, e, attempt, sent)
                    try:
                        self._decode._ensure_router()._refresh(force=True)
                    except Exception:
                        pass
                    time.sleep(min(0.25 * attempt, 1.0))
                finally:
                    if attempt_hop is not None:
                        attempt_hop.end(sent=sent)
                    if stream is not None:
                        try:
                            stream.close()
                        except Exception:
                            pass

    return DisaggIngress.bind(DecodeWorker.bind(PrefillWorker.bind()))
