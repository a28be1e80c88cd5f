"""The plain reference of the learned-sparse-attention expert stack
(Kwai-Keye/Keye-VL-2.0-30B-A3B's language model, `model_type: KeyeVL2`):
forward pass, loss and gradients in straightforward jax.numpy, float32,
matmuls at Precision.HIGHEST. Nothing from ray_tpu, no kernel, no tile. It
follows the published config (32 query / 4 key heads of 128, `mrope_section`
[16, 24, 24] at theta 1e7, 128 softmax-routed experts of 768 of which 8 a
token, `norm_topk_prob`, `sa_config`: an indexer of 16 heads of 64 over one
key head, `topk` 2048) and, for what the config does not give, the published
description of DeepSeek Sparse Attention (DeepSeek-V3.2-Exp, 2025) and the
Qwen3-MoE / Qwen2-VL conventions; each such choice is a comment at its line
and an item of `assumed` in configs/keye_vl_2_0_30b_a3b.json.

    x = embed[tokens];  p [3, B, S] integer positions (temporal, height, width)
    each layer:  h = RMSNorm(x)
        q, k, v = W_q h, W_k h, W_v h;  q, k RMSNorm'd a head, then M-RoPE
        indexer, on hd = stop_gradient(h):
            qi = RoPE_half(W_Iq hd) [16 x 64], ki = RoPE_half(LayerNorm(W_Ik hd)),
            w = W_Iw hd / sqrt(16 x 64)
            I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])          (s <= t)
        S_t = the min(t + 1, 2048) keys of largest I[t, .] (lax.top_k's
              threshold; ties to the lower index)
        P[t, a, .] = softmax over S_t of q[t, a] . k[., g(a)] / sqrt(128)
        x = x + W_o (P v)
        L_I += mean_t KL(stop_gradient(mean_a P[t, a, .]) || softmax over S_t
                          of I[t, .])
        h = RMSNorm(x);  experts as reference/mellum2.py (softmax over all
            128, top 8, renormalised, the held range's part of the sum)
    logits = W_head RMSNorm(x)
    L = L_LM (next-token loss over the positions the mask keeps) + c_I L_I

A query block at a time (`ROW_BLOCK` rows against every key), so that the
scores of 32,768 positions fit. `selection` (bits [L, B, S, S / 32] int32,
bit p of word l of row t: key p S / 32 + l is kept; the layout is the
interface, packed and unpacked here) puts a given selection in S_t's place:
the benchmark compares loss and gradients GIVEN the program's selection, and
the selection itself apart (`missed`: the given pairs the reference's own
top-k does not hold; `margin`: how far under the reference's threshold the
furthest of them scores, in units of the row's score spread; `margin_sum`:
the same distance summed over them).

Departures from the published model: the held range of experts and the
vocabulary slice (as mellum2.py); no vision tower (its output rows are not
spliced in: ids at image positions are ids like any other).

Weights come from the seed alone (chipbench/weights_keye_vl2.py), one layer
at a time. `mm` is the one place a matmul happens: the control swaps in
float8 operands. `index_dtype` is what L_I is formed in (the scores I as
the loss reads them, their logsumexp, the target pbar, the KL's terms and
sum): float32 (what the configuration states), or bfloat16 for the control
one step below it; the selection is made from the float32 scores either
way. `delta` adds to the compared leaves so that the gradient
with respect to it, at zero, is the gradient of those weights."""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench import weights_keye_vl2 as W
from chipbench.reference.dense_decoder import mm_f32, mm_fp8  # noqa: F401
from chipbench.reference.mellum2 import _experts, _rms
from chipbench.weights import layer_key

ROW_BLOCK = 64      # query rows a block of the indexer and the attention
C_INDEX = 1.0       # Assumed: c_I = 1 (the config has no key for it)
LOGIT_BLOCK = 2048  # positions a block of the head and the loss


def _layernorm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _turn(x, ang):
    """x [B,S,n,D] with its halves rotated against each other by ang
    [B,S,D/2] (pair i = columns i and D / 2 + i)."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mrope(x, positions, sz: W.KeyeSizes):
    """M-RoPE: pair i of hd / 2 turns by p[sigma(i)] theta^(-2i / hd),
    sigma the section of i. Assumed: contiguous sections (Qwen2-VL's), not
    interleaved; the config has no key for the order."""
    half = sz.hd // 2
    f = sz.theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    stream = jnp.repeat(jnp.arange(3), jnp.asarray(sz.sections),
                        total_repeat_length=half)
    p = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)     # [B,S,3]
    return _turn(x, jnp.take(p, stream, axis=-1) * f)


def rope_first_half(x, pos, sz: W.KeyeSizes):
    """The indexer's rotation: plain RoPE at the temporal stream on the
    first half of the 64 columns. Assumed: V3.2 turns half an indexer head
    (pairs i and 16 + i of the first 32, f_i = theta^(-i / 16))."""
    rot = sz.dI // 2
    f = sz.theta ** (-jnp.arange(rot // 2, dtype=jnp.float32) / (rot // 2))
    ang = pos.astype(jnp.float32)[:, :, None] * f
    return jnp.concatenate([_turn(x[..., :rot], ang), x[..., rot:]], -1)


def pack(mask):
    """[..., S] booleans -> [..., S / 32] int32, bit p of word l = key
    p S / 32 + l."""
    planes = mask.shape[-1] // 32
    m = mask.reshape(mask.shape[:-1] + (32, planes)).astype(jnp.int32)
    return jnp.sum(m << jnp.arange(32, dtype=jnp.int32)[:, None], axis=-2)


def unpack(bits):
    m = (bits[..., None, :] >> jnp.arange(32, dtype=jnp.int32)[:, None]) & 1
    return m.reshape(bits.shape[:-1] + (32 * bits.shape[-1],)) != 0


def _mixer(h, w, positions, given, sz: W.KeyeSizes, mm, index_dtype):
    """-> (the layer's attention output [B,S,d], mean KL, the reference's
    own selection as bits, kept pairs, missed pairs, margin, margin sum)."""
    r = lambda a: a.astype(index_dtype)
    B, S, _ = h.shape
    H, KVH, hd, HI, dI = sz.H, sz.KVH, sz.hd, sz.HI, sz.dI
    # Assumed: an RMSNorm a head on q and k before the rotation (Qwen3-MoE's
    # q_norm / k_norm; the config has no key for it).
    q = mrope(_rms(mm(h, w["wq"]).reshape(B, S, H, hd), w["q_norm"],
                   sz.norm_eps), positions, sz)
    k = mrope(_rms(mm(h, w["wk"]).reshape(B, S, KVH, hd), w["k_norm"],
                   sz.norm_eps), positions, sz)
    # query head a reads key / value head a // (H / KVH): a block's query
    # heads go group by group against their one key head
    G = H // KVH
    k = k.transpose(0, 2, 3, 1)                                   # [B,KVH,hd,S]
    v = mm(h, w["wv"]).reshape(B, S, KVH, hd).transpose(0, 2, 1, 3)
    # The indexer reads the layer's input detached (the sparse stage of the
    # published recipe). Assumed: its queries come from h (V3.2's come from a
    # query latent this model does not have), its key through a LayerNorm.
    hs = jax.lax.stop_gradient(h)
    qi = rope_first_half(mm(hs, w["index_wq"]).reshape(B, S, HI, dI),
                         positions[0], sz)
    ki = rope_first_half(_layernorm(mm(hs, w["index_wk"]), w["index_k_norm"],
                                    w["index_k_norm_b"], sz.norm_eps
                                    )[:, :, None], positions[0], sz)
    ki = ki[:, :, 0].transpose(0, 2, 1)[:, None]                   # [B,1,dI,S]
    wi = mm(hs, w["index_ww"]) * (HI * dI) ** -0.5                 # [B,S,HI]
    blk = ROW_BLOCK if S % ROW_BLOCK == 0 else S
    cols, kk = jnp.arange(S), min(sz.topk, S)

    @jax.checkpoint
    def rows(args):
        qb, qib, wib, r0, given_b = args
        z = mm(qib.transpose(0, 2, 1, 3), ki)                      # [B,HI,blk,S]
        score = jnp.sum(wib.transpose(0, 2, 1)[..., None] * jax.nn.relu(z), 1)
        causal = ((r0 + jnp.arange(blk))[:, None] >= cols[None, :])[None]
        sc = jnp.where(causal, score, -jnp.inf)
        # The exact top-k: lax.top_k's k-th value, every key above it, and of
        # the keys AT it the lowest indices (lax.top_k's own order).
        kth = jax.lax.top_k(sc, kk)[0][..., -1:]
        above, at = sc > kth, sc == kth
        need = kk - jnp.sum(above, -1, keepdims=True)
        own = (above | (at & (jnp.cumsum(at, -1) <= need))) & causal
        sel = unpack(given_b) if given is not None else own
        qg = qb.transpose(0, 2, 1, 3).reshape(B, KVH, G * blk, hd)
        s = mm(qg, k) / jnp.sqrt(jnp.float32(hd))                  # [B,KVH,G blk,S]
        p = jax.nn.softmax(jnp.where(
            sel[:, None, None], s.reshape(B, KVH, G, blk, S), -jnp.inf), -1)
        o = mm(p.reshape(B, KVH, G * blk, S), v).reshape(
            B, H, blk, hd).transpose(0, 2, 1, 3)                   # [B,blk,H,hd]
        pbar = jax.lax.stop_gradient(r(jnp.mean(p, (1, 2))))       # [B,blk,S]
        logq = jax.nn.log_softmax(jnp.where(sel, r(score), -jnp.inf), -1)
        kl = jnp.sum(jnp.where(sel, pbar * (
            jnp.log(jnp.maximum(pbar, 1e-30)) - jnp.where(sel, logq, 0.0)),
            0.0), -1).astype(jnp.float32)
        # The given selection against the reference's own.
        miss = sel & ~own
        n = jnp.maximum(jnp.sum(causal, -1, keepdims=True), 1)
        mean = jnp.sum(jnp.where(causal, score, 0.0), -1, keepdims=True) / n
        spread = jnp.sqrt(jnp.sum(jnp.where(causal, (score - mean) ** 2, 0.0),
                                  -1, keepdims=True) / n)
        under = jnp.where(miss, (kth - score) / jnp.maximum(spread, 1e-30),
                          0.0)
        return (o, kl, pack(own), jnp.sum(sel), jnp.sum(miss),
                jnp.max(under), jnp.sum(under))

    split = lambda a: jnp.moveaxis(
        a.reshape((B, S // blk, blk) + a.shape[2:]), 1, 0)
    given_b = split(given if given is not None
                    else jnp.zeros((B, S, S // 32), jnp.int32))
    o, kl, bits, kept, missed, under, under_sum = jax.lax.map(rows, (
        split(q), split(qi), split(wi), jnp.arange(S // blk) * blk, given_b))
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape((B, S) + a.shape[3:])
    return (mm(join(o).reshape(B, S, H * hd), w["wo"]), jnp.mean(join(kl)),
            join(bits), jnp.sum(kept), jnp.sum(missed), jnp.max(under),
            jnp.sum(under_sum))


def zero_delta(sz: W.KeyeSizes) -> Dict[str, jax.Array]:
    z = lambda *s: jnp.zeros(s, jnp.float32)
    q, kv, qi = sz.H * sz.hd, sz.KVH * sz.hd, sz.HI * sz.dI
    return {"final_norm": z(sz.d), "wo": z(q, sz.d), "wq": z(sz.d, q),
            "wkv": z(sz.d, 2 * kv), "q_norm": z(sz.hd),
            "index_wq": z(sz.d, qi), "index_wk": z(sz.d, sz.dI),
            "index_ww": z(sz.d, sz.HI), "index_wq_last": z(sz.d, qi),
            "expert_down": z(sz.Fe, sz.d), "router": z(sz.d, sz.E)}


def hidden(key, tokens, positions, sz: W.KeyeSizes, mm: Callable = mm_f32,
           delta: Optional[Dict[str, Any]] = None, selection=None,
           index_dtype=jnp.float32):
    """tokens [B,S], positions [3,B,S] -> (the final norm's output [B,S,d],
    {"index": sum over layers of the mean KL, "bits" [L,B,S,S/32] the
    reference's own selection, "kept", "missed" pairs, "margin",
    "margin_sum"})."""
    delta = delta or zero_delta(sz)
    x = W.top(key, sz)["embed"][tokens]
    kv = sz.KVH * sz.hd

    @jax.checkpoint
    def run(x, l, given, delta):
        first = (l == sz.l_first).astype(x.dtype)
        last = (l == sz.l_last).astype(x.dtype)
        w = W.layer(layer_key(key, l), sz)
        for n in ("wo", "wq", "q_norm", "index_wk", "index_ww", "router"):
            w[n] = w[n] + first * delta[n]
        w["wk"] = w["wk"] + first * delta["wkv"][:, :kv]
        w["wv"] = w["wv"] + first * delta["wkv"][:, kv:]
        w["index_wq"] = (w["index_wq"] + first * delta["index_wq"]
                         + last * delta["index_wq_last"])
        w["e_down"] = w["e_down"].at[sz.e_pick].add(
            first * delta["expert_down"])
        h = _rms(x, w["attn_norm"], sz.norm_eps)
        y, *out = _mixer(h, w, positions, given, sz, mm, index_dtype)
        x = x + y
        return x + _experts(_rms(x, w["mlp_norm"], sz.norm_eps), w, sz, mm), out

    def layer(x, at):
        l, given = at
        return run(x, l, given if selection is not None else None, delta)

    given = (selection if selection is not None
             else jnp.zeros((sz.L, 1), jnp.int32))
    x, (kl, bits, kept, missed, under, under_sum) = jax.lax.scan(
        layer, x, (jnp.arange(sz.L), given))
    out = {"index": jnp.sum(kl), "bits": bits, "kept": jnp.sum(kept),
           "missed": jnp.sum(missed), "margin": jnp.max(under),
           "margin_sum": jnp.sum(under_sum)}
    return _rms(x, W.top(key, sz)["final_norm"] + delta["final_norm"],
                sz.norm_eps), out


def loss(key, batch, sz: W.KeyeSizes, mm: Callable = mm_f32,
         delta: Optional[Dict[str, Any]] = None, selection=None,
         index_dtype=jnp.float32):
    """batch: tokens [B,S+1], positions [3,B,S+1], mask [B,S+1] -> (L, aux):
    the next-token cross-entropy over the positions the mask keeps (the head
    and the softmax in blocks of positions) plus `C_INDEX` times the layers'
    KL."""
    tokens, mask = batch["tokens"], batch["mask"]
    x, aux = hidden(key, tokens[:, :-1], batch["positions"][:, :, :-1], sz,
                    mm, delta, selection, index_dtype)
    B, S, d = x.shape
    blk = LOGIT_BLOCK if S % LOGIT_BLOCK == 0 else S
    head = W.top(key, sz)["lm_head"]

    @jax.checkpoint
    def nll(args):
        xb, tb, mb = args                              # [B,blk,d] [B,blk] x2
        ll = jax.nn.log_softmax(mm(xb, head), -1)
        return -jnp.sum(jnp.take_along_axis(ll, tb[..., None], -1)[..., 0]
                        * mb)

    split = lambda a: jnp.moveaxis(
        a.reshape((B, S // blk, blk) + a.shape[2:]), 1, 0)
    valid = mask[:, 1:].astype(jnp.float32)
    lm = jnp.sum(jax.lax.map(nll, (split(x), split(tokens[:, 1:]),
                                   split(valid)))
                 ) / jnp.maximum(jnp.sum(valid), 1.0)
    return lm + C_INDEX * aux["index"], dict(aux, lm=lm)


def loss_and_grads(key, batch, sz: W.KeyeSizes, mm: Callable = mm_f32,
                   selection=None, index_dtype=jnp.float32):
    """(loss, {leaf of zero_delta: gradient}, aux of `loss`)."""
    (value, aux), g = jax.value_and_grad(
        lambda dl: loss(key, batch, sz, mm, dl, selection, index_dtype),
        has_aux=True)(zero_delta(sz))
    return value, g, aux
