"""The plain reference of the gated-short-convolution / GQA expert stack
(LiquidAI/LFM2-8B-A1B, `model_type: lfm2_moe`): forward pass, loss and
gradients in straightforward jax.numpy, float32, matmuls at
Precision.HIGHEST. Nothing from ray_tpu, no kernel, no tile. It follows the
published config (`layer_types`, `conv_L_cache` 3, `conv_bias` false,
`num_attention_heads` 32, `num_key_value_heads` 8, `rope_theta` 1e6,
`norm_eps` 1e-5, `num_dense_layers`, `intermediate_size` 7,168,
`num_experts` 32, `num_experts_per_tok` 4, `moe_intermediate_size` 1,792,
`norm_topk_prob`, `use_expert_bias`, `routed_scaling_factor` 1) and, for
what the config names and does not define, the family's public
implementation, each item listed under `assumed` in
configs/lfm2_8b_a1b.json:

    N(x) = x / sqrt(mean(x^2) + eps) * w                plain weights
    x = embed[tokens]
    each layer:  x = x + mixer(N(x));  x = x + ffn(N(x))
    logits = N(x) embed^T                               the tied table

    gated short convolution (`layer_types[l] == "conv"`), u = N(x):
      [Bg ; Cg ; x] = u W_in                            three chunks of d
      z = Bg * x
      c_t = w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t         a channel; z before
                                                        a sequence's start 0
      out = (Cg * c) W_out                              no activation, no bias

    attention ("full_attention"), u = N(x):
      q = W_q u, k = W_k u, v = W_v u                   32 | 8 | 8 heads of 64
      q <- N_64(q), k <- N_64(k) a head (one weight each), THEN the rotation
      over the whole head: pair i = columns (i, 32 + i), f_i = 1e6^(-2i/64)
      o = softmax_causal(q k^T / sqrt(64)) v            4 query heads a key head
      out = W_o o

    feed-forward, u = N(x):
      layers before `num_dense_layers`: W_2 (silu(W_1 u) * W_3 u)
      every other: s = sigmoid(W_r u) over all E;  T = top-k of s + b
        g_e = s_e / (sum_T s + 1e-6) * routed_scaling_factor
        x = x + sum_{e in T, held} g_e SwiGLU_e(u)      no shared expert

- the convolution is a sum over the taps of shifted copies, zero-padded at
  the start of every sequence of the batch.
- attention: full softmax rows taken in blocks of query rows so that the
  scores of 8,192 positions fit.
- experts: a loop over the held ones, each applied to EVERY token and
  weighted by g_e (zero where e is not among the token's top k); the routing
  is over all E and always exact (it is not the control's subject).

Departures from the published model: the held range (experts outside it are
left out of the sum, in the program alike: one expert-parallel rank), the
vocabulary slice, the selection bias b a fixed buffer (its balancing update
between steps is not run), no auxiliary loss.

Weights come from the seed alone (chipbench/weights_lfm2_moe.py), one layer
at a time. `mm` is the one place a projection's matmul happens: the control
swaps in float8 operands. `delta` adds to the compared leaves so that the
gradient with respect to it, at zero, is the gradient of those weights, and
no other gradient is held."""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench import weights_lfm2_moe as W
from chipbench.reference.dense_decoder import mm_f32, mm_fp8  # noqa: F401
from chipbench.weights import layer_key

ROW_BLOCK = 256     # query rows a block of the softmax attention
LOGIT_BLOCK = 2048  # positions a block of the head and the loss
TOPK_EPS = 1e-6     # what the family adds to the selected scores' sum


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _conv(x, w):
    """Causal depthwise convolution: x [B,S,n], w [K,n]; w[K-1] meets x_t."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + S] * w[j] for j in range(K))


def _shortconv(x, w, sz: W.Lfm2Sizes, mm):
    d = sz.d
    p = mm(x, w["win"])
    bg, cg, xx = p[..., :d], p[..., d:2 * d], p[..., 2 * d:]
    return mm(cg * _conv(bg * xx, w["conv"]), w["wout"])


def inv_freq(sz: W.Lfm2Sizes) -> jax.Array:
    i = jnp.arange(sz.hd // 2, dtype=jnp.float32)
    return sz.theta ** (-2.0 * i / sz.hd)


def rotate(x, sz: W.Lfm2Sizes):
    """x [B,S,H,hd] at positions 0..S-1: pair i = columns (i, hd / 2 + i)."""
    S, half = x.shape[1], sz.hd // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq(sz)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attn(x, w, sz: W.Lfm2Sizes, mm):
    B, S, _ = x.shape
    H, KVH, hd = sz.H, sz.KVH, sz.hd
    q = mm(x, w["wq"]).reshape(B, S, H, hd)
    k = mm(x, w["wk"]).reshape(B, S, KVH, hd)
    v = mm(x, w["wv"]).reshape(B, S, KVH, hd)
    q = rotate(_rms(q, w["q_norm"], sz.norm_eps), sz)
    k = rotate(_rms(k, w["k_norm"], sz.norm_eps), sz)
    G = H // KVH                                       # query heads a key head
    kt = k.transpose(0, 2, 3, 1)                       # [B,KVH,hd,S]
    vt = v.transpose(0, 2, 1, 3)                       # [B,KVH,S,hd]
    blk = ROW_BLOCK if S % ROW_BLOCK == 0 else S
    cols = jnp.arange(S)

    @jax.checkpoint
    def rows(args):
        qb, r0 = args                                  # [B,blk,H,hd]
        qb = qb.reshape(B, blk, KVH, G, hd).transpose(0, 2, 3, 1, 4)
        s = mm(qb, kt[:, :, None]) / jnp.sqrt(jnp.float32(hd))
        keep = (r0 + jnp.arange(blk))[:, None] >= cols[None, :]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        o = mm(p, vt[:, :, None])                      # [B,KVH,G,blk,hd]
        return o.transpose(0, 3, 1, 2, 4).reshape(B, blk, H, hd)

    qb = jnp.moveaxis(q.reshape(B, S // blk, blk, H, hd), 1, 0)
    o = jax.lax.map(rows, (qb, jnp.arange(S // blk) * blk))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * hd)
    return mm(o, w["wo"])


def _swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def _experts(x, w, sz: W.Lfm2Sizes, mm):
    """The held experts' part; no shared expert."""
    s = jax.nn.sigmoid(mm_f32(x, w["router"]))         # always exact: the
    _, idx = jax.lax.top_k(s + w["router_bias"], sz.k)  # routing is not the
    gate = jnp.take_along_axis(s, idx, -1)              # control's subject
    gate = gate / (jnp.sum(gate, -1, keepdims=True) + TOPK_EPS
                   ) * sz.routed_scale

    @jax.checkpoint
    def one(x, gate, idx, e_gate, e_up, e_down, e):
        we = jnp.sum(jnp.where(idx == sz.held_first + e, gate, 0.0), -1)
        return we[..., None] * _swiglu(x, e_gate, e_up, e_down, mm)

    def expert(y, e):  # a loop over the held experts (one compiled body)
        return y + one(x, gate, idx, w["e_gate"][e], w["e_up"][e],
                       w["e_down"][e], e), None

    return jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(sz.held))[0]


def zero_delta(sz: W.Lfm2Sizes) -> Dict[str, jax.Array]:
    z = lambda *s: jnp.zeros(s, jnp.float32)
    d, q = sz.d, sz.H * sz.hd
    out = {"final_norm": z(d)}
    if sz.l_conv is not None:
        out.update(conv_win=z(d, 3 * d), conv_wout=z(d, d),
                   conv_taps=z(sz.K, d), conv_taps_last=z(sz.K, d))
    if sz.l_attn is not None:
        out.update(attn_wq=z(d, q), attn_wo=z(q, d), attn_q_norm=z(sz.hd))
    if sz.l_dense is not None:
        out["w_down"] = z(sz.F, d)
    if sz.l_moe is not None:
        out.update(expert_down=z(sz.Fe, d), router=z(d, sz.E))
    return out


def block(x, w, sz: W.Lfm2Sizes, kind, mm):
    """One layer of `kind` = ("shortconv" | "attn", "dense" | "moe")."""
    mixer = _shortconv if kind[0] == "shortconv" else _attn
    x = x + mixer(_rms(x, w["attn_norm"], sz.norm_eps), w, sz, mm)
    h = _rms(x, w["mlp_norm"], sz.norm_eps)
    if kind[1] == "dense":
        return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mm)
    return x + _experts(h, w, sz, mm)


def with_delta(w, l, sz: W.Lfm2Sizes, kind, delta):
    """Layer l's weights with the compared leaves' deltas added (a delta
    counts on its own layer alone)."""
    is_l = lambda n: float(l == n)
    w = dict(w)
    if kind[0] == "shortconv":
        w["win"] = w["win"] + is_l(sz.l_conv) * delta["conv_win"]
        w["wout"] = w["wout"] + is_l(sz.l_conv) * delta["conv_wout"]
        w["conv"] = (w["conv"] + is_l(sz.l_conv) * delta["conv_taps"]
                     + is_l(sz.l_conv_last) * delta["conv_taps_last"])
    else:
        for n in ("wq", "wo", "q_norm"):
            w[n] = w[n] + is_l(sz.l_attn) * delta["attn_" + n]
    if kind[1] == "dense":
        w["w_down"] = w["w_down"] + is_l(sz.l_dense) * delta["w_down"]
    else:
        w["router"] = w["router"] + is_l(sz.l_moe) * delta["router"]
        w["e_down"] = w["e_down"].at[sz.e_pick].add(
            is_l(sz.l_moe) * delta["expert_down"])
    return w


def hidden(key, tokens, sz: W.Lfm2Sizes, mm: Callable = mm_f32,
           delta: Optional[Dict[str, Any]] = None, given=None):
    """tokens [B,S] int32 -> (the final norm's output [B,S,d] float32, the
    tied table). `given` = (`W.top`'s dict, a list of `W.layer`'s dicts):
    the weights where the caller holds them (the CPU tests differentiate
    with respect to every one); None: made from the key, a layer at a
    time."""
    delta = delta or zero_delta(sz)
    top = given[0] if given else W.top(key, sz)
    x = top["embed"][tokens]
    for l, kind in enumerate(sz.kinds):
        @jax.checkpoint
        def run(x, delta, l=l, kind=kind):
            w = (given[1][l] if given
                 else W.layer(layer_key(key, l), sz, kind))
            return block(x, with_delta(w, l, sz, kind, delta), sz, kind, mm)

        x = run(x, delta)
    return (_rms(x, top["final_norm"] + delta["final_norm"], sz.norm_eps),
            top["embed"])


def forward(key, tokens, sz: W.Lfm2Sizes, mm: Callable = mm_f32,
            delta: Optional[Dict[str, Any]] = None, given=None) -> jax.Array:
    """tokens [B,S] int32 -> logits [B,S,V] float32."""
    x, embed = hidden(key, tokens, sz, mm, delta, given)
    return mm(x, embed.T)


def loss(key, tokens, sz: W.Lfm2Sizes, mm: Callable = mm_f32,
         delta: Optional[Dict[str, Any]] = None, given=None) -> jax.Array:
    """Mean next-token cross-entropy of tokens [B,S+1], the head and the
    softmax taken in blocks of positions so that the logits of 8,192
    positions are never alive at once."""
    x, embed = hidden(key, tokens[:, :-1], sz, mm, delta, given)
    B, S, d = x.shape
    blk = LOGIT_BLOCK if S % LOGIT_BLOCK == 0 else S

    @jax.checkpoint
    def nll(args):
        xb, tb = args                                  # [B,blk,d] [B,blk]
        ll = jax.nn.log_softmax(mm(xb, embed.T), -1)
        return -jnp.sum(jnp.take_along_axis(ll, tb[..., None], -1))

    xb = jnp.moveaxis(x.reshape(B, S // blk, blk, d), 1, 0)
    tb = jnp.moveaxis(tokens[:, 1:].reshape(B, S // blk, blk), 1, 0)
    return jnp.sum(jax.lax.map(nll, (xb, tb))) / (B * S)


def loss_and_grads(key, tokens, sz: W.Lfm2Sizes, mm: Callable = mm_f32):
    """(loss, {leaf of zero_delta: gradient})."""
    return jax.value_and_grad(
        lambda dl: loss(key, tokens, sz, mm, dl))(zero_delta(sz))
