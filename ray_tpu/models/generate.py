"""Autoregressive decoding with a static-shape KV cache.

The reference framework ships no model layer (SURVEY.md §5.7) — this is the
TPU-first inference path its Serve/Data users would otherwise build by hand:

- **Static shapes end to end**: the cache is allocated at `max_len` up
  front; the decode loop is ONE `lax.scan` over step indices, so the whole
  generation compiles once (no per-length recompiles, no dynamic shapes —
  XLA's requirement, not a style choice).
- **Prefill/decode split**: the prompt runs through the normal batched
  forward (MXU-friendly [B, S] matmuls) capturing per-layer K/V; each
  decode step is a [B, 1] pass attending over the cache (a dot against
  cached keys — flash tiling buys nothing for a single query row).
- **GQA-aware**: cached K/V keep `kv_heads`; query heads fold into groups
  at the attention einsum exactly like ops/attention.py's training path.

Layout: cache K/V are [L, B, max_len, KVH, hd] — layer-major so the decode
scan over layers consumes them as `xs` alongside the stacked layer params.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .transformer import (
    MIXERS,
    TransformerConfig,
    _layer_body,
    _mlp_block,
    _norm,
    _qkv_proj,
    _w,
    embed_tokens,
    final_hidden_and_head,
    one_kind_stack,
)

Params = Dict[str, jax.Array]


class KVCache(NamedTuple):
    k: jax.Array  # [L, B, max_len, KVH, hd] (cfg.dtype)
    v: jax.Array  # [L, B, max_len, KVH, hd]
    # Tokens filled so far: [] int32 (uniform batch) or [B] int32 (ragged
    # batch — per-row prompt lengths; decode masks and writes per row).
    pos: jax.Array


def _kv_stack(params: Params, cfg: TransformerConfig):
    """(kind, leaves [L, ...]) of the one stacked tree the cache's [L, ...]
    keys and values are scanned beside; what decode cannot serve is refused
    (ROADMAP R7)."""
    for mixer, _ in cfg.layer_kinds():
        if MIXERS[mixer].no_decode:
            raise NotImplementedError(MIXERS[mixer].no_decode)
    if cfg.attn_out_gate or cfg.attn_head_gate or cfg.norm_offset:
        raise NotImplementedError(
            "decode does not apply attn_out_gate / attn_head_gate (the "
            "attention output's sigmoid gate, a column or a head) / "
            "norm_offset yet")
    if (cfg.embed_scale, cfg.residual_scale, cfg.attn_scale,
            cfg.logit_scale) != (1.0, 1.0, None, 1.0):
        raise NotImplementedError(
            "decode does not apply embed_scale / residual_scale / attn_scale "
            "/ logit_scale yet")
    if cfg.moe_held is not None:
        raise NotImplementedError(
            f"decode needs every expert held, not moe_held={cfg.moe_held}")
    if cfg.loop_steps > 1 or cfg.post_norm:
        raise NotImplementedError(
            "decode holds one slab of keys and values a layer and its layer "
            "body has one norm a sublayer: a looped stack (loop_steps > 1) "
            "needs a cache slab a pass a layer, and post_norm is not applied "
            "yet; the stack trains but does not serve")
    return one_kind_stack(params, cfg, "decoding")


def prefill(params: Params, tokens: jax.Array, cfg: TransformerConfig,
            max_len: int,
            lengths: Optional[jax.Array] = None) -> Tuple[jax.Array, KVCache]:
    """Run the prompt [B, S] through the batched forward, returning logits
    for the last REAL position [B, V] and the primed cache.

    `lengths` [B] enables RAGGED prompts: rows are right-padded to S, each
    row's logits come from index lengths[i]-1, and cache.pos = lengths.
    Right-padding is safe without a key mask: causal attention means real
    tokens never attend pad positions (pads sit after them), pad rows'
    outputs go unused, and decode overwrites the pad K/V slot at pos[i]
    BEFORE the attention einsum runs (the valid mask is slot <= pos[i],
    which includes the just-written slot — ordering of _write before
    attend in decode_step's body is load-bearing)."""
    kind, layers = _kv_stack(params, cfg)
    B, S = tokens.shape
    if S > max_len:
        raise ValueError(f"prompt length {S} exceeds cache max_len {max_len}")
    x = embed_tokens(params, tokens, cfg)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(carry, layer):
        x, _, k, v = _layer_body(cfg, kind, carry, layer, positions)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, layers)
    # Pad [L, B, S, KVH, hd] out to the static max_len.
    pad = [(0, 0), (0, 0), (0, max_len - S), (0, 0), (0, 0)]
    if lengths is None:
        last = x[:, -1:]
        pos = jnp.asarray(S, jnp.int32)
    else:
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)
        pos = lengths.astype(jnp.int32)
    cache = KVCache(k=jnp.pad(ks, pad), v=jnp.pad(vs, pad), pos=pos)
    h, head = final_hidden_and_head(params, last, cfg)
    logits = (h @ head).astype(jnp.float32)[:, 0]
    return logits, cache


def decode_step(params: Params, cache: KVCache, token: jax.Array,
                cfg: TransformerConfig) -> Tuple[jax.Array, KVCache]:
    """One token [B] int32 -> logits [B, V] + the cache advanced by one."""
    if cfg.positional == "learned":
        raise NotImplementedError(
            "decode_step: learned positional embeddings index by absolute "
            "position, which embed_tokens applies only for full sequences; "
            "use rope (the flagship configs) for incremental decoding")
    (_, ffn), layers = _kv_stack(params, cfg)
    B = token.shape[0]
    H, KVH, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    max_len = cache.k.shape[2]
    pos = cache.pos
    # Overflow guard (eager callers only — the manual prefill/decode_step
    # loop): under jit `pos` is traced and dynamic_update_slice would CLAMP
    # the write to the last slot, silently overwriting it. generate() can't
    # overflow (its scan length is sized against max_len); hand-rolled
    # loops get the same contract as prefill's length check where possible.
    try:
        hi = int(pos) if getattr(pos, "ndim", 0) == 0 else int(pos.max())
        if hi >= max_len:
            raise ValueError(
                f"decode_step: cache full (pos {hi} >= max_len "
                f"{max_len}); size prefill's max_len for the tokens you "
                f"intend to generate")
    except (jax.errors.TracerIntegerConversionError,
            jax.errors.ConcretizationTypeError):
        pass
    ragged = getattr(pos, "ndim", 0) == 1  # per-row positions [B]
    x = embed_tokens(params, token[:, None], cfg)  # [B, 1, d]
    if ragged:
        positions = pos[:, None].astype(jnp.int32)
        # [B,1,1,S]: row i may attend cache slots < pos[i] plus its own
        # just-written slot.
        valid = (jnp.arange(max_len)[None] <= pos[:, None])[:, None, None]
    else:
        positions = jnp.full((B, 1), pos, jnp.int32)
        valid = (jnp.arange(max_len) <= pos)[None, None, None, :]

    def _write(ck, k):
        """Append this step's K (or V) at each row's position."""
        if ragged:
            return jax.vmap(
                lambda c, kk, p: jax.lax.dynamic_update_slice(
                    c, kk, (p, 0, 0)))(ck, k.astype(ck.dtype), pos)
        return jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                            (0, pos, 0, 0))

    def body(x, xs):
        layer, ck, cv = xs  # ck/cv: [B, max_len, KVH, hd]
        h = _norm(x, layer["attn_norm"], layer.get("attn_norm_b"), cfg.norm,
                  cfg.norm_eps)
        q, k, v = _qkv_proj(cfg, h, layer, positions)
        ck = _write(ck, k)
        cv = _write(cv, v)
        # GQA: fold query heads into KVH groups of size G.
        G = H // KVH
        qg = q.reshape(B, 1, KVH, G, hd)
        scores = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                            ck.astype(jnp.float32)) / (hd ** 0.5)
        scores = jnp.where(valid[:, :, :, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bkgqs,bskh->bqkgh", probs,
                       cv.astype(jnp.float32)).astype(cfg.dtype)
        o = o.reshape(B, 1, H * hd)
        x = x + o @ _w(layer, "wo", cfg)

        h = _norm(x, layer["mlp_norm"], layer.get("mlp_norm_b"), cfg.norm,
                  cfg.norm_eps)
        x = x + _mlp_block(cfg, ffn, h, layer)[0]
        return x, (ck, cv)

    x, (nk, nv) = jax.lax.scan(body, x, (layers, cache.k, cache.v))
    x, head = final_hidden_and_head(params, x, cfg)
    logits = (x @ head).astype(jnp.float32)[:, 0]
    return logits, KVCache(k=nk, v=nv, pos=pos + 1)


def _decode_loop(params, cfg, cache, logits, pick, rng, max_new_tokens,
                 eos_id):
    """Shared first-token + eos-freeze + lax.scan machinery for
    generate()/generate_ragged() — ONE home so sampling/eos semantics can
    never drift between the uniform and ragged paths. Returns
    (first [B], rest [max_new_tokens-1, B])."""
    B = logits.shape[0]
    rng, r0 = jax.random.split(rng)
    first = pick(logits, r0)
    # The first generated token may itself be eos — done0 reflects it.
    done0 = jnp.zeros((B,), bool) if eos_id is None else first == eos_id

    def step(carry, step_rng):
        cache, tok, done = carry
        logits, cache = decode_step(params, cache, tok, cfg)
        nxt = pick(logits, step_rng)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        return (cache, nxt, done), nxt

    keys = jax.random.split(rng, max(max_new_tokens - 1, 0))
    (_, _, _), rest = jax.lax.scan(step, (cache, first, done0), keys)
    return first, rest


def generate(params: Params, tokens: jax.Array, cfg: TransformerConfig,
             max_new_tokens: int, *, temperature: float = 0.0,
             top_k: int = 0, rng: Optional[jax.Array] = None,
             eos_id: Optional[int] = None) -> jax.Array:
    """Greedy (temperature=0) or sampled continuation of `tokens` [B, S] ->
    [B, S + max_new_tokens]. Once a row emits `eos_id` it keeps repeating
    it (the static output shape never changes — consumers mask on eos).
    jit-able as a whole; the step loop is a lax.scan. `temperature` may be
    a traced jax scalar (serving passes client values without recompiles);
    a Python float stays static and compiles only its branch."""
    B, S = tokens.shape
    max_len = S + max_new_tokens
    logits, cache = prefill(params, tokens, cfg, max_len)
    if rng is None:
        rng = jax.random.key(0)

    static_temp = isinstance(temperature, (int, float))

    def pick(logits, step_rng):
        # `temperature` may be a TRACED scalar (a serving path must not
        # recompile per client-supplied float): then both branches compute
        # and a where() selects. A static Python float keeps the one-branch
        # program.
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)
        if static_temp and temperature <= 0.0:
            return greedy
        scaled = logits / jnp.maximum(temperature, 1e-6)
        if top_k:
            kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]  # O(V log k)
            scaled = jnp.where(scaled < kth, -1e30, scaled)
        sampled = jax.random.categorical(step_rng, scaled).astype(jnp.int32)
        if static_temp:
            return sampled
        return jnp.where(temperature <= 0.0, greedy, sampled)

    first, rest = _decode_loop(params, cfg, cache, logits, pick, rng,
                               max_new_tokens, eos_id)
    out = jnp.concatenate(
        [tokens, first[:, None], rest.T.astype(tokens.dtype)], axis=1)
    return out[:, :max_len]


def generate_ragged(params: Params, tokens: jax.Array, lengths: jax.Array,
                    cfg: TransformerConfig, max_new_tokens: int, *,
                    temperature=0.0, rng: Optional[jax.Array] = None,
                    eos_id: Optional[int] = None) -> jax.Array:
    """Mixed-length batched generation: prompts right-padded to [B, S] with
    true `lengths` [B] -> GENERATED tokens [B, max_new_tokens].

    One compiled program serves every batch composition: per-row cache
    positions remove the uniform-prompt-length restriction, and
    `temperature` may be a [B] vector (per-request sampling — rows with
    temperature<=0 decode greedily) or a scalar/float. Serving uses this
    to batch heterogeneous requests without per-length recompiles."""
    B, S = tokens.shape
    max_len = S + max_new_tokens
    logits, cache = prefill(params, tokens, cfg, max_len, lengths=lengths)
    if rng is None:
        rng = jax.random.key(0)
    temp = jnp.asarray(temperature, jnp.float32)
    if temp.ndim == 0:
        temp = jnp.broadcast_to(temp, (B,))
    tcol = temp[:, None]

    def pick(logits, step_rng):
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)
        scaled = logits / jnp.maximum(tcol, 1e-6)
        sampled = jax.random.categorical(step_rng, scaled).astype(jnp.int32)
        return jnp.where(temp <= 0.0, greedy, sampled)

    first, rest = _decode_loop(params, cfg, cache, logits, pick, rng,
                               max_new_tokens, eos_id)
    return jnp.concatenate([first[:, None], rest.T], axis=1).astype(jnp.int32)
