"""The plain reference of the sliding-window / full-attention expert stack
(JetBrains/Mellum2-12B-A2.5B-Instruct, `model_type: mellum`): forward pass,
loss and gradients in straightforward jax.numpy, float32, matmuls at
Precision.HIGHEST. Nothing from ray_tpu, no kernel, no tile. It follows the
published config (`layer_types`, `sliding_window`, `rope_parameters` a layer
kind, 64 experts of which 8 a token, `norm_topk_prob`, the untied head) and,
for what the config does not give, the family's convention, each item listed
under `assumed` in configs/mellum2_12b_a2_5b.json:

    x = embed[tokens]
    each layer:  h = RMSNorm(x);  q, k, v = W_q h, W_k h, W_v h
                 sliding_attention: q, k rotated by RoPE(theta); query i
                     sees keys j with 0 <= i - j < sliding_window
                 full_attention: q, k rotated with YaRN's frequencies, cos
                     and sin times attention_factor; query i sees keys j <= i
                 x = x + W_o softmax(q k^T / sqrt(head_dim)) v
                 h = RMSNorm(x);  p = softmax(W_r h) over all E experts
                 T = top-k of p;  g_e = p_e / sum_T p
                 x = x + sum_{e in T, held} g_e W_down_e (silu(W_gate_e h)
                                                          * W_up_e h)
    logits = W_head RMSNorm(x)

- attention: H query heads, KVH key/value heads each shared by H / KVH query
  heads, no bias, full softmax rows taken in blocks of query rows so that
  the scores fit, the band written as a mask on i - j.
- YaRN (`rope_parameters.full_attention`): pair i of hd / 2 has
  f_i = theta^(-2i / hd); low = floor(hd ln(L0 / (beta_fast 2 pi)) /
  (2 ln theta)), high = ceil(hd ln(L0 / (beta_slow 2 pi)) / (2 ln theta));
  r_i = clip((i - low) / (high - low), 0, 1); the inverse frequency is
  f_i (1 - r_i) + (f_i / factor) r_i.
- experts: a loop over the held ones, each applied to EVERY token and
  weighted by g_e (zero where e is not among the token's top k); the routing
  is over all E and always exact (it is not the control's subject).

Departures from the published model: the held range (experts outside it are
left out of the sum, in the program alike: one expert-parallel rank), the
vocabulary slice, no multi-token-prediction head (`config.json` has no key
for one).

Weights come from the seed alone (chipbench/weights_mellum2.py), one layer
at a time. `mm` is the one place a projection's matmul happens: the control
swaps in float8 operands. `delta` adds to the compared leaves so that the
gradient with respect to it, at zero, is the gradient of those weights, and
no other gradient is held."""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench import weights_mellum2 as W
from chipbench.reference.dense_decoder import mm_f32, mm_fp8  # noqa: F401
from chipbench.weights import layer_key

ROW_BLOCK = 256     # query rows a block of the softmax attention
LOGIT_BLOCK = 2048  # positions a block of the head and the loss


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def inv_freq(sz: W.MellumSizes, yarn: bool) -> jax.Array:
    """Inverse frequencies of the hd / 2 pairs, YaRN-blended or plain."""
    half = sz.hd // 2
    i = jnp.arange(half, dtype=jnp.float32)
    f = sz.theta ** (-i / half)
    if not yarn:
        return f
    factor, L0, beta_fast, beta_slow, _ = sz.yarn
    at = lambda turns: (sz.hd * math.log(L0 / (turns * 2 * math.pi))
                        / (2 * math.log(sz.theta)))
    low = max(math.floor(at(beta_fast)), 0)
    high = min(math.ceil(at(beta_slow)), sz.hd - 1)
    r = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return f * (1.0 - r) + f / factor * r


def _rotate(x, sz: W.MellumSizes, yarn: bool):
    """x [B,S,n,hd], halves rotated against each other (x1, x2 = the first
    and the second hd / 2 channels)."""
    S, half = x.shape[1], sz.hd // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq(sz, yarn)
    scale = sz.yarn[4] if yarn else 1.0
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(h, w, sz: W.MellumSizes, windowed: bool, mm):
    B, S, _ = h.shape
    H, KVH, hd = sz.H, sz.KVH, sz.hd
    q = _rotate(mm(h, w["wq"]).reshape(B, S, H, hd), sz, not windowed)
    k = _rotate(mm(h, w["wk"]).reshape(B, S, KVH, hd), sz, not windowed)
    rep = lambda a: jnp.repeat(a, H // KVH, axis=2)
    k = rep(k).transpose(0, 2, 3, 1)                                # [B,H,hd,S]
    v = rep(mm(h, w["wv"]).reshape(B, S, KVH, hd)).transpose(0, 2, 1, 3)
    blk = ROW_BLOCK if S % ROW_BLOCK == 0 else S
    cols = jnp.arange(S)

    @jax.checkpoint
    def rows(args):
        qb, r0 = args                                  # [B,blk,H,hd]
        s = mm(qb.transpose(0, 2, 1, 3), k) / jnp.sqrt(jnp.float32(hd))
        diff = (r0 + jnp.arange(blk))[:, None] - cols[None, :]   # i - j
        keep = diff >= 0
        if windowed:
            keep = keep & (diff < sz.window)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        return mm(p, v).transpose(0, 2, 1, 3)          # [B,blk,H,hd]

    qb = jnp.moveaxis(q.reshape(B, S // blk, blk, H, hd), 1, 0)
    o = jax.lax.map(rows, (qb, jnp.arange(S // blk) * blk))
    return mm(jnp.moveaxis(o, 0, 1).reshape(B, S, H * hd), w["wo"])


def _experts(x, w, sz: W.MellumSizes, mm):
    """The held experts' part of the layer's sum."""
    p = jax.nn.softmax(mm_f32(x, w["router"]), -1)     # always exact: the
    top, idx = jax.lax.top_k(p, sz.k)                  # routing is not the
    gate = top / jnp.sum(top, -1, keepdims=True)       # control's subject

    @jax.checkpoint
    def one(x, gate, idx, e_gate, e_up, e_down, e):
        we = jnp.sum(jnp.where(idx == sz.held_first + e, gate, 0.0), -1)
        return we[..., None] * mm(jax.nn.silu(mm(x, e_gate)) * mm(x, e_up),
                                  e_down)

    def expert(y, e):  # a loop over the held experts (one compiled body)
        return y + one(x, gate, idx, w["e_gate"][e], w["e_up"][e],
                       w["e_down"][e], e), None

    return jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(sz.held))[0]


def zero_delta(sz: W.MellumSizes) -> Dict[str, jax.Array]:
    z = lambda *s: jnp.zeros(s, jnp.float32)
    q, kv = sz.H * sz.hd, sz.KVH * sz.hd
    out = {"final_norm": z(sz.d), "expert_down": z(sz.Fe, sz.d),
           "router": z(sz.d, sz.E)}
    if sz.l_full is not None:
        out.update(full_wo=z(q, sz.d), full_wq=z(sz.d, q))
    if sz.l_swa is not None:
        out.update(swa_wo=z(q, sz.d), swa_wkv=z(sz.d, 2 * kv))
    return out


def block(x, w, sz: W.MellumSizes, kind, mm):
    """One layer of `kind` = ("swa" | "attn", "moe")."""
    h = _rms(x, w["attn_norm"], sz.norm_eps)
    x = x + _attention(h, w, sz, kind[0] == "swa", mm)
    return x + _experts(_rms(x, w["mlp_norm"], sz.norm_eps), w, sz, mm)


def hidden(key, tokens, sz: W.MellumSizes, mm: Callable = mm_f32,
           delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """tokens [B,S] int32 -> the final norm's output [B,S,d] float32."""
    delta = delta or zero_delta(sz)
    x = W.top(key, sz)["embed"][tokens]
    # One loop over the layers; each kind of layer is one branch, so that a
    # kind is compiled once however many layers have it. `is_l(n)` is 1 on
    # the layer whose leaf is compared (never, where the stack lacks it).
    kinds = sorted(set(sz.kinds))
    kv = sz.KVH * sz.hd

    def branch(kind):
        @jax.checkpoint
        def run(x, l, delta):
            is_l = lambda n: 0.0 if n is None else (l == n).astype(x.dtype)
            w = W.layer(layer_key(key, l), sz)
            if kind[0] == "attn":
                w["wo"] = w["wo"] + is_l(sz.l_full) * delta["full_wo"]
                w["wq"] = w["wq"] + is_l(sz.l_full) * delta["full_wq"]
            else:
                w["wo"] = w["wo"] + is_l(sz.l_swa) * delta["swa_wo"]
                w["wk"] = w["wk"] + is_l(sz.l_swa) * delta["swa_wkv"][:, :kv]
                w["wv"] = w["wv"] + is_l(sz.l_swa) * delta["swa_wkv"][:, kv:]
            w["router"] = w["router"] + is_l(sz.l_moe) * delta["router"]
            w["e_down"] = w["e_down"].at[sz.e_pick].add(
                is_l(sz.l_moe) * delta["expert_down"])
            return block(x, w, sz, kind, mm)
        return run

    branches = [branch(k) for k in kinds]
    which = jnp.asarray([kinds.index(k) for k in sz.kinds])

    def layer(x, l):
        return jax.lax.switch(which[l], branches, x, l, delta), None

    x, _ = jax.lax.scan(layer, x, jnp.arange(sz.L))
    return _rms(x, W.top(key, sz)["final_norm"] + delta["final_norm"],
                sz.norm_eps)


def forward(key, tokens, sz: W.MellumSizes, mm: Callable = mm_f32,
            delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """tokens [B,S] int32 -> logits [B,S,V] float32."""
    return mm(hidden(key, tokens, sz, mm, delta), W.top(key, sz)["lm_head"])


def loss(key, tokens, sz: W.MellumSizes, mm: Callable = mm_f32,
         delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """Mean next-token cross-entropy of tokens [B,S+1], the head and the
    softmax taken in blocks of positions so that the logits of 16,384
    positions are never alive at once."""
    x = hidden(key, tokens[:, :-1], sz, mm, delta)
    B, S, d = x.shape
    blk = LOGIT_BLOCK if S % LOGIT_BLOCK == 0 else S
    head = W.top(key, sz)["lm_head"]

    @jax.checkpoint
    def nll(args):
        xb, tb = args                                  # [B,blk,d] [B,blk]
        ll = jax.nn.log_softmax(mm(xb, head), -1)
        return -jnp.sum(jnp.take_along_axis(ll, tb[..., None], -1))

    xb = jnp.moveaxis(x.reshape(B, S // blk, blk, d), 1, 0)
    tb = jnp.moveaxis(tokens[:, 1:].reshape(B, S // blk, blk), 1, 0)
    return jnp.sum(jax.lax.map(nll, (xb, tb))) / (B * S)


def loss_and_grads(key, tokens, sz: W.MellumSizes, mm: Callable = mm_f32):
    """(loss, {leaf of zero_delta: gradient})."""
    return jax.value_and_grad(
        lambda dl: loss(key, tokens, sz, mm, dl))(zero_delta(sz))
