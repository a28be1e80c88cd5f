"""LLM serving deployment: models/generate.py behind Serve batching.

The reference serves LLMs by hosting external engines; here the framework's
own model layer IS the engine, so the deployment is thin and TPU-shaped:

- requests batch via @serve.batch into ONE ragged generate per batch
  (models/generate.py generate_ragged): right-padded prompts with
  per-row cache positions and per-row temperatures, padded to power-of-2
  length buckets so at most log2(max_prompt_len) programs ever compile;
- the replica reserves chips with num_tpus like any other TPU actor, so
  the Data/Train/Serve stacks share one accelerator accounting scheme.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu.util import tracing

from . import batching
from .deployment import deployment


def build_llm_deployment(cfg, params_factory, *, name: str = "llm",
                         max_batch_size: int = 4,
                         batch_wait_timeout_s: float = 0.05,
                         max_prompt_len: int = 256,
                         max_new_tokens: int = 64,
                         pad_id: int = 0,
                         num_replicas: int = 1,
                         num_tpus: Optional[int] = None,
                         quantize_int8: bool = False):
    """A Serve deployment class generating continuations for
    {"tokens": [...], optional "max_new_tokens", "temperature"} requests.

    `params_factory` is a zero-arg picklable callable returning the model
    params ON THE REPLICA (load from a checkpoint path, don't ship arrays
    through the deployment config).

    Batching: every coalesced batch runs as ONE ragged generate
    (models/generate.py generate_ragged) — prompts right-pad with per-row
    cache positions (pads can never leak into attention) and temperature
    rides as a per-row vector, so batch composition never recompiles.
    The padded length is the batch's longest prompt rounded up to a
    power of two (capped at max_prompt_len): short-prompt traffic doesn't
    pay max_prompt_len prefill FLOPs, and at most ~log2(max_prompt_len)
    programs ever compile. Returns the deployment (call .bind())."""
    @deployment(name=name, num_replicas=num_replicas,
                ray_actor_options=(
                    {"num_tpus": num_tpus} if num_tpus else None))
    class LLM:
        def __init__(self):
            import os

            import jax

            self._params = params_factory()
            if quantize_int8:
                # Weight-only int8 (models/quantize.py): decode is
                # HBM-bound, so halving the layer-weight bytes each step
                # streams is a direct throughput lever.
                from ray_tpu.models.quantize import quantize_params_int8

                self._params = quantize_params_int8(self._params)
            # Distinct stream per replica: key(0) everywhere would make
            # replicas sample bit-identical continuations.
            self._rng = jax.random.key(
                int.from_bytes(os.urandom(4), "little"))

            from ray_tpu.models.generate import generate_ragged

            # One program for every batch composition: fixed [B, S] padded
            # shape, per-row lengths and temperatures all traced.
            @jax.jit
            def _gen(params, tokens, lengths, rng, temps):
                return generate_ragged(
                    params, tokens, lengths, cfg,
                    max_new_tokens=max_new_tokens, temperature=temps,
                    rng=rng)

            self._gen = _gen

        @batching.batch(max_batch_size=max_batch_size,
                        batch_wait_timeout_s=batch_wait_timeout_s)
        def _generate_batch(self, requests: List[Dict[str, Any]]):
            import jax

            # Per-request validation: one malformed request must answer
            # with its own error, never poison the coalesced batch.
            results: List[Optional[Dict[str, Any]]] = [None] * len(requests)
            rows: List[tuple] = []  # (request idx, ids, temp, want, trunc)
            for i, req in enumerate(requests):
                try:
                    ids = np.asarray(req["tokens"], np.int32)
                    if ids.ndim != 1 or ids.size == 0:
                        raise ValueError("tokens must be a non-empty 1-D "
                                         "integer list")
                    temp = float(req.get("temperature", 0.0))
                    want = int(req.get("max_new_tokens", max_new_tokens))
                    if want <= 0:
                        raise ValueError("max_new_tokens must be positive")
                except Exception as e:
                    results[i] = {"error": f"bad request: {e}"}
                    continue
                trunc = len(ids) > max_prompt_len
                rows.append((i, ids[-max_prompt_len:], temp, want, trunc))
            if rows:
                from ray_tpu.serve.llm_engine import bucket_len

                S = bucket_len(max(len(ids) for _, ids, _, _, _ in rows),
                               max_prompt_len)
                toks = np.full((max_batch_size, S), pad_id, np.int32)
                lengths = np.ones(max_batch_size, np.int32)
                temps = np.zeros(max_batch_size, np.float32)
                for row, (_, ids, temp, _, _) in enumerate(rows):
                    toks[row, :len(ids)] = ids
                    lengths[row] = len(ids)
                    temps[row] = temp
                self._rng, sub = jax.random.split(self._rng)
                out = np.asarray(self._gen(
                    self._params, toks, lengths, sub, temps))
                for row, (i, ids, _, want, trunc) in enumerate(rows):
                    n = min(want, max_new_tokens)
                    res = {"tokens": [int(t) for t in out[row, :n]]}
                    if want > max_new_tokens:
                        # Signal the cap instead of silently truncating.
                        res["max_new_tokens_capped"] = max_new_tokens
                    if trunc:
                        res["prompt_truncated_to"] = max_prompt_len
                    results[i] = res
            return results

        def __call__(self, request: Dict[str, Any]) -> Dict[str, Any]:
            if not isinstance(request, dict) or "tokens" not in request:
                return {"error": "expected {'tokens': [...]} request body"}
            return self._generate_batch(request)

    return LLM


def build_streaming_llm_deployment(cfg, params_factory, *, name: str = "llm-stream",
                                   max_prompt_len: int = 256,
                                   max_new_tokens: int = 64,
                                   num_replicas: int = 1,
                                   num_tpus: Optional[int] = None,
                                   quantize_int8: bool = False,
                                   continuous_batching: bool = False,
                                   num_slots: int = 4):
    """Token-by-token streaming generation (reference: serve streaming
    responses; LLM engines' SSE token streams).

    Unlike build_llm_deployment's one-compiled-scan batch path, each
    request runs prefill once and then jitted decode_step per token,
    yielding {"token": id} chunks as they land — first-token latency is
    prefill + one step instead of the whole generation.

    ``continuous_batching=True`` backs the replica with a
    ContinuousBatchingEngine (serve/llm_engine.py): `num_slots` concurrent
    streams share ONE decode tick over a slot-pooled ragged cache —
    requests join the running batch mid-flight and retire independently,
    so a replica's decode throughput is shared instead of serialized."""
    @deployment(name=name, num_replicas=num_replicas, stream=True,
                ray_actor_options=(
                    {"num_tpus": num_tpus} if num_tpus else None))
    class StreamingLLM:
        def __init__(self):
            import os

            import jax

            from ray_tpu.models.generate import decode_step, prefill

            self._params = params_factory()
            if quantize_int8:
                from ray_tpu.models.quantize import quantize_params_int8

                self._params = quantize_params_int8(self._params)
            import itertools

            # Interleaved streams on one replica must never share a
            # subkey: fold a thread-safe monotonic counter into a fixed
            # base key instead of racing on a split-and-reassign.
            self._base_rng = jax.random.key(
                int.from_bytes(os.urandom(4), "little"))
            self._draws = itertools.count()
            self._engine = None
            if continuous_batching:
                import threading

                from ray_tpu.serve.llm_engine import (
                    ContinuousBatchingEngine,
                )

                self._engine = ContinuousBatchingEngine(
                    cfg, self._params, num_slots=num_slots,
                    max_prompt_len=max_prompt_len,
                    max_new_tokens=max_new_tokens,
                    seed=int.from_bytes(os.urandom(4), "little"),
                    model=name)
                self._engine.warmup()
                self._stop = threading.Event()
                self._ticker = threading.Thread(
                    target=self._engine.run_forever, args=(self._stop,),
                    daemon=True)
                self._ticker.start()
                return
            self._prefill = jax.jit(
                lambda p, t: prefill(p, t, cfg,
                                     max_len=max_prompt_len + max_new_tokens))
            self._step = jax.jit(
                lambda p, c, t: decode_step(p, c, t, cfg))

        def serve_stats(self) -> Dict[str, Any]:
            """Engine load for the controller's signal poll (slot
            occupancy + blocked submitters drive the serve autoscaler)."""
            if self._engine is None:
                return {}
            return self._engine.stats()

        def device_report(self) -> Dict[str, Any]:
            """The engine's device_report(), from inside this replica."""
            if self._engine is None:
                raise RuntimeError(
                    "device_report needs continuous_batching=True")
            return self._engine.device_report()

        def __call__(self, request: Dict[str, Any]):
            import jax
            import jax.numpy as jnp

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
            if self._engine is not None:
                # Continuous batching: attach to the shared tick loop and
                # stream tokens as the slot emits them.
                import time as _t

                from ray_tpu.serve import context as serve_context
                from ray_tpu.serve import trace

                # Final stream span: the engine's token stats (counts +
                # ITL percentiles + abort cause) attach at end, computed
                # BEFORE abort() drops the timeline ring.
                hop = trace.start_hop("serve.stream", kind="decode",
                                      attributes={"model": name})
                try:
                    # The slot wait is bounded by the request's remaining
                    # deadline budget (serve context) when one is set.
                    # TTFT measures from system arrival (queue wait
                    # counts): elapsed_s() is the per-host monotonic
                    # accumulation, immune to cross-machine clock skew.
                    req = self._engine.submit(
                        ids, max_new_tokens=n, temperature=temp,
                        eos_id=eos,
                        timeout=serve_context.remaining_s(default=300.0),
                        queue_wait_s=serve_context.elapsed_s())
                except TimeoutError as e:
                    # Backpressure uses the same error-chunk contract as
                    # malformed requests — not a raw stream exception.
                    if hop is not None:
                        hop.end(status="slot_timeout")
                    yield {"error": f"overloaded: {e}"}
                    return
                except BaseException as e:
                    if hop is not None:
                        hop.end(error=type(e).__name__)
                    raise
                sent = 0
                status = "ok"
                try:
                    while True:
                        if serve_context.expired():
                            # Deadline passed mid-decode: stop emitting;
                            # the finally's abort() frees the slot now.
                            from ray_tpu.core.controller import (
                                DeadlineExceededError,
                            )

                            status = "deadline"
                            raise DeadlineExceededError(
                                "request deadline passed mid-stream")
                        toks, stamp = self._engine.peek_stamped(req, sent)
                        if stamp is not None:
                            # engine stamp of the oldest token not yet
                            # yielded -> now: the wait for the engine lock
                            # and this loop's 5 ms poll.
                            tracing.observe("stream.poll_lag", int(
                                (_t.monotonic() - stamp) * 1e9))
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
                        _t.sleep(0.005)
                except BaseException as e:
                    if status == "ok":
                        status = ("cancelled"
                                  if isinstance(e, GeneratorExit)
                                  else type(e).__name__)
                    raise
                finally:
                    # Client disconnect (GeneratorExit) or deadline closes
                    # this generator mid-loop: abort frees the KV slot
                    # between engine steps, not at some later tick. After
                    # a normal pop_result this is a no-op.
                    st = self._engine.token_stats(req) or {}
                    self._engine.abort(req)
                    if hop is not None:
                        attrs = {"sent": sent, "status": status}
                        for k_, v_ in st.items():
                            if v_ is not None:
                                attrs[k_] = (round(v_, 6)
                                             if isinstance(v_, float)
                                             else v_)
                        hop.end(**attrs)
            logits, cache = self._prefill(self._params, ids[None])
            for i in range(n):
                if temp > 0:
                    sub = jax.random.fold_in(self._base_rng,
                                             next(self._draws))
                    tok = jax.random.categorical(
                        sub, logits / max(temp, 1e-6))
                else:
                    tok = jnp.argmax(logits, -1)
                tok_i = int(tok[0])
                yield {"token": tok_i}
                if eos is not None and tok_i == eos:
                    return
                if i < n - 1:  # the last yielded token needs no next logits
                    logits, cache = self._step(self._params, cache,
                                               tok.astype(jnp.int32))

    return StreamingLLM
