"""The plain reference of the mixed-head window / full attention expert stack
(poolside/Laguna-S-2.1, `model_type: laguna`): forward pass, loss and
gradients in straightforward jax.numpy, float32, matmuls at
Precision.HIGHEST. Nothing from ray_tpu, no kernel, no tile, no scan over
the layers. It follows the published config
(`layer_types`, `num_attention_heads_per_layer`, `sliding_window`,
`rope_parameters` a layer kind, `gating: per-head`, `mlp_layer_types`, 256
experts of which 10 a token, `norm_topk_prob`, `moe_routed_scaling_factor`,
one shared expert, the untied head) and, for what the config does not give,
the convention each key comes from, each item listed under `assumed` in
configs/laguna_s_2_1.json:

    x = embed[tokens]
    each layer:  h = N(x);  N(x) = x / sqrt(mean(x^2) + eps) * w, float32
                 q = W_q h [H x 128]; k = W_k h, v = W_v h [KVH x 128]
                 g = sigmoid(W_g h) [H]              (one scalar a head)
                 H = 48 (full_attention) or 72 (sliding_attention) over the
                 same 8 key heads: query head i is served by key head
                 i // (H / 8) (groups of 6 and of 9)
                 full_attention: the FIRST 64 columns of every q and k head
                     rotate (pair i = columns i and 32 + i, f_i = 500000^(-2i
                     / 64)), YaRN over those 32 pairs (low 9, high 18, factor
                     128), cos and sin times attention_factor; columns
                     64..127 pass; query i sees keys j <= i
                 sliding_attention: all 128 columns rotate (pair i = columns
                     i and 64 + i, f_i = 10000^(-2i / 128)), no scaling;
                     query i sees keys j with 0 <= i - j < 512
                 o_n = softmax(q_n k^T / sqrt(128)) v           a head n
                 x = x + W_o [g_1 o_1; ...; g_H o_H]
                 h = N(x)
                 layer 1:  x = x + W_down (silu(W_gate h) * W_up h)  (12,288)
                 others:   s = sigmoid(W_r h) over all E experts
                           T = top-k of s + b;  w_e = scale s_e / sum_T s
                           x = x + sum_{e in T, held} w_e SwiGLU_e(h)
                                 + SwiGLU_shared(h)
    logits = W_head N(x)

- YaRN (`rope_parameters.full_attention`), over the ROTATED columns rot = 64:
  pair i of rot / 2 has f_i = theta^(-2i / rot); low = floor(rot ln(L0 /
  (beta_fast 2 pi)) / (2 ln theta)), high = ceil(rot ln(L0 / (beta_slow 2
  pi)) / (2 ln theta)); r_i = clip((i - low) / (high - low), 0, 1); the
  inverse frequency is f_i (1 - r_i) + (f_i / factor) r_i.
- attention: full softmax rows taken in blocks of query rows so that the
  scores fit, the band written as a mask on i - j, keys and values repeated
  over their group.
- experts: a loop over the held ones, each applied to EVERY token and
  weighted by w_e (zero where e is not among the token's top k); the routing
  is over all E and always exact (it is not the control's subject).

Departures from the published model: the held heads (`W_o`'s partial sum
over the query heads this chip holds goes on to the residual; what the other
chips' heads would add is left out, in the program alike: one
tensor-parallel rank without its all-reduce), the held range of experts
(likewise: one expert-parallel rank), the vocabulary slice, the 1e-20 the
family adds to the top-k's sum is not added (sigmoid scores are positive).

Weights come from the seed alone (chipbench/weights_laguna.py), one layer at
a time. `mm` is the one place a projection's matmul happens: the control
swaps in float8 operands. `delta` adds to the compared leaves so that the
gradient with respect to it, at zero, is the gradient of those weights, and
no other gradient is held."""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench import weights_laguna as W
from chipbench.reference.dense_decoder import mm_f32, mm_fp8  # noqa: F401
from chipbench.weights import layer_key

ROW_BLOCK = 256     # query rows a block of the softmax attention
LOGIT_BLOCK = 2048  # positions a block of the head and the loss


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def inv_freq(sz: W.LagunaSizes, mixer: str) -> jax.Array:
    """Inverse frequencies of the rot / 2 pairs of a `mixer` layer's rotated
    columns: plain on a sliding layer, YaRN-blended on a full one."""
    rot, theta = sz.rot[mixer], sz.theta[mixer]
    i = jnp.arange(rot // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / rot)
    if mixer == "swa":
        return f
    factor, L0, beta_fast, beta_slow, _ = sz.yarn
    at = lambda turns: (rot * math.log(L0 / (turns * 2 * math.pi))
                        / (2 * math.log(theta)))
    low = max(math.floor(at(beta_fast)), 0)
    high = min(math.ceil(at(beta_slow)), rot - 1)
    r = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return f * (1.0 - r) + f / factor * r


def _rotate(x, sz: W.LagunaSizes, mixer: str):
    """x [B,S,n,hd]: the first rot columns' halves rotated against each
    other, the rest passed."""
    S, rot = x.shape[1], sz.rot[mixer]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq(sz, mixer)
    scale = sz.yarn[4] if mixer == "attn" else 1.0
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rot:]], -1)


def _attention(h, w, sz: W.LagunaSizes, mixer: str, mm):
    """The held heads' part of the layer's W_o sum."""
    B, S, _ = h.shape
    H, KVH, hd = sz.H[mixer], sz.KVH, sz.hd
    q = _rotate(mm(h, w["wq"]).reshape(B, S, H, hd), sz, mixer)
    k = _rotate(mm(h, w["wk"]).reshape(B, S, KVH, hd), sz, mixer)
    rep = lambda a: jnp.repeat(a, H // KVH, axis=2)
    k = rep(k).transpose(0, 2, 3, 1)                                # [B,H,hd,S]
    v = rep(mm(h, w["wv"]).reshape(B, S, KVH, hd)).transpose(0, 2, 1, 3)
    # (float32 whatever `mm` is: the program keeps the gate's product so)
    gate = jax.nn.sigmoid(mm_f32(h, w["wg"]))                       # [B,S,H]
    blk = ROW_BLOCK if S % ROW_BLOCK == 0 else S
    cols = jnp.arange(S)

    @jax.checkpoint
    def rows(args):
        qb, r0 = args                                  # [B,blk,H,hd]
        s = mm(qb.transpose(0, 2, 1, 3), k) / jnp.sqrt(jnp.float32(hd))
        diff = (r0 + jnp.arange(blk))[:, None] - cols[None, :]   # i - j
        keep = diff >= 0
        if mixer == "swa":
            keep = keep & (diff < sz.window)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        return mm(p, v).transpose(0, 2, 1, 3)          # [B,blk,H,hd]

    qb = jnp.moveaxis(q.reshape(B, S // blk, blk, H, hd), 1, 0)
    o = jax.lax.map(rows, (qb, jnp.arange(S // blk) * blk))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, hd) * gate[..., None]
    return mm(o.reshape(B, S, H * hd), w["wo"])


def _swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def _experts(x, w, sz: W.LagunaSizes, mm):
    """The held experts' part plus the shared expert."""
    s = jax.nn.sigmoid(mm_f32(x, w["router"]))         # always exact: the
    _, idx = jax.lax.top_k(s + w["router_bias"], sz.k)  # routing is not the
    gate = jnp.take_along_axis(s, idx, -1)              # control's subject
    gate = gate / jnp.sum(gate, -1, keepdims=True) * sz.routed_scale

    @jax.checkpoint
    def one(x, gate, idx, e_gate, e_up, e_down, e):
        we = jnp.sum(jnp.where(idx == sz.held_first + e, gate, 0.0), -1)
        return we[..., None] * _swiglu(x, e_gate, e_up, e_down, mm)

    def expert(y, e):  # a loop over the held experts (one compiled body)
        return y + one(x, gate, idx, w["e_gate"][e], w["e_up"][e],
                       w["e_down"][e], e), None

    y = _swiglu(x, w["s_gate"], w["s_up"], w["s_down"], mm)
    return jax.lax.scan(expert, y, jnp.arange(sz.held))[0]


def zero_delta(sz: W.LagunaSizes) -> Dict[str, jax.Array]:
    z = lambda *s: jnp.zeros(s, jnp.float32)
    out = {"final_norm": z(sz.d)}
    for name, l, mixer in (("full", sz.l_full, "attn"),
                           ("swa", sz.l_swa, "swa")):
        if l is None:
            continue
        q = sz.H[mixer] * sz.hd
        out[name + "_wq"] = z(sz.d, q)
        out[name + "_wo"] = z(q, sz.d)
        out[name + "_gate"] = z(sz.d, sz.H[mixer])
    if sz.l_swa is not None:
        out["swa_wkv"] = z(sz.d, 2 * sz.KVH * sz.hd)
    if sz.l_dense is not None:
        out["w_down"] = z(sz.F, sz.d)
    if sz.l_moe is not None:
        out.update(expert_down=z(sz.Fe, sz.d), router=z(sz.d, sz.E))
    return out


def block(x, w, sz: W.LagunaSizes, kind, mm):
    """One layer of `kind` = ("swa" | "attn", "dense" | "moe")."""
    x = x + _attention(_rms(x, w["attn_norm"], sz.norm_eps), w, sz, kind[0],
                       mm)
    h = _rms(x, w["mlp_norm"], sz.norm_eps)
    if kind[1] == "dense":
        return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mm)
    return x + _experts(h, w, sz, mm)


def hidden(key, tokens, sz: W.LagunaSizes, mm: Callable = mm_f32,
           delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """tokens [B,S] int32 -> the final norm's output [B,S,d] float32. The
    layers in turn (a Python loop: five layers of three kinds); `delta`
    lands on the layer whose leaf is compared."""
    delta = delta or zero_delta(sz)
    x = W.top(key, sz)["embed"][tokens]
    kv = sz.KVH * sz.hd
    for l, kind in enumerate(sz.kinds):

        @jax.checkpoint
        def run(x, delta, l=l, kind=kind):
            w = W.layer(layer_key(key, l), sz, kind)
            name = {sz.l_full: "full", sz.l_swa: "swa"}.get(l)
            if name:
                w["wq"] = w["wq"] + delta[name + "_wq"]
                w["wo"] = w["wo"] + delta[name + "_wo"]
                w["wg"] = w["wg"] + delta[name + "_gate"]
            if l == sz.l_swa:
                w["wk"] = w["wk"] + delta["swa_wkv"][:, :kv]
                w["wv"] = w["wv"] + delta["swa_wkv"][:, kv:]
            if l == sz.l_dense:
                w["w_down"] = w["w_down"] + delta["w_down"]
            if l == sz.l_moe:
                w["router"] = w["router"] + delta["router"]
                w["e_down"] = w["e_down"].at[sz.e_pick].add(
                    delta["expert_down"])
            return block(x, w, sz, kind, mm)

        x = run(x, delta)
    return _rms(x, W.top(key, sz)["final_norm"] + delta["final_norm"],
                sz.norm_eps)


def forward(key, tokens, sz: W.LagunaSizes, mm: Callable = mm_f32,
            delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """tokens [B,S] int32 -> logits [B,S,V] float32."""
    return mm(hidden(key, tokens, sz, mm, delta), W.top(key, sz)["lm_head"])


def loss(key, tokens, sz: W.LagunaSizes, mm: Callable = mm_f32,
         delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """Mean next-token cross-entropy of tokens [B,S+1], the head and the
    softmax taken in blocks of positions so that the logits of 8,192
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


def loss_and_grads(key, tokens, sz: W.LagunaSizes, mm: Callable = mm_f32):
    """(loss, {leaf of zero_delta: gradient})."""
    return jax.value_and_grad(
        lambda dl: loss(key, tokens, sz, mm, dl))(zero_delta(sz))
