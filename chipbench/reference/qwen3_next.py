"""The plain reference of the Gated DeltaNet / gated-attention expert stack
(Qwen/Qwen3-Next-80B-A3B-Instruct, `model_type: qwen3_next`): forward pass,
loss and gradients in straightforward jax.numpy, float32, matmuls at
Precision.HIGHEST. Nothing from ray_tpu, no kernel, no chunk, no tile. It
follows the published config (`full_attention_interval` 4,
`linear_num_key_heads` 16, `linear_num_value_heads` 32,
`linear_key_head_dim` = `linear_value_head_dim` 128,
`linear_conv_kernel_dim` 4, `head_dim` 256, `partial_rotary_factor` 0.25,
`rope_theta` 1e7, `rope_scaling` null, `num_experts` 512,
`num_experts_per_tok` 10, `norm_topk_prob`,
`shared_expert_intermediate_size` 512, `decoder_sparse_step` 1,
`mlp_only_layers` []; `intermediate_size` is read nowhere) and, for what the
config names and does not define, the family's public implementation, each
item listed under `assumed` in configs/qwen3_next_80b_a3b.json:

    N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)         zero-centred weights
    x = embed[tokens]
    each layer:  x = x + mixer(N(x));  x = x + experts(N(x))
    logits = W_head N(x)

    Gated DeltaNet (layers l with l % 4 != 0, 1-based), h = N(x):
      [q ; k ; v ; z] = W_qkvz h;  [b ; a] = W_ba h
      [q ; k ; v] <- SiLU(causal depthwise conv_4 over the channels of
                          [q ; k ; v]), no bias
      beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   a value head
      q, k <- x / sqrt(sum x^2 + 1e-6) a head;  q <- q / sqrt(128)
      key head i serves value heads 2i and 2i + 1
      S_t = exp(g_t) S_{t-1};  S_t <- S_t + beta_t k_t (v_t - S_t^T k_t)^T
      o_t = S_t^T q_t                                   S [128,128] a head
      y = (o / sqrt(mean(o^2) + eps) * w_o) * SiLU(z)   w_o NOT zero-centred
      out = W_o y

    gated attention (layers l with l % 4 == 0), h = N(x):
      [q ; gate] = W_q h  (a head: 256 query columns, then 256 gate columns)
      k = W_k h,  v = W_v h;  q <- N_256(q), k <- N_256(k) a head
      the first 64 columns of every q and k head rotate, pair i = columns
        (i, 32 + i), f_i = 1e7^(-2i/64); columns 64..255 pass
      o = softmax_causal(q k^T / sqrt(256)) v           8 query heads a key head
      out = W_o (o * sigmoid(gate))

    experts, h = N(x):
      p = softmax(W_r h) over all E;  T = top-k of p;  g_e = p_e / sum_T p
      x = x + sum_{e in T, held} g_e SwiGLU_e(h)
            + sigmoid(w_sg . h) SwiGLU_shared(h)

- the delta rule is the recurrence itself, one token at a time (`lax.scan`
  over positions, in checkpointed blocks so that the backward holds one
  block's states).
- attention: full softmax rows taken in blocks of query rows so that the
  scores of 16,384 positions fit.
- experts: a loop over the held ones, each applied to EVERY token and
  weighted by g_e (zero where e is not among the token's top k); the routing
  is over all E and always exact (it is not the control's subject).

Departures from the published model: the held range (experts outside it are
left out of the sum, in the program alike: one expert-parallel rank; the
shared expert is whole on every rank), the vocabulary slice, no auxiliary
loss, no multi-token-prediction layer (`config.json` has no key for one).

Weights come from the seed alone (chipbench/weights_qwen3_next.py), one
layer at a time. `mm` is the one place a projection's matmul happens: the
control swaps in float8 operands. `delta` adds to the compared leaves so
that the gradient with respect to it, at zero, is the gradient of those
weights, and no other gradient is held."""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench import weights_qwen3_next as W
from chipbench.reference.dense_decoder import mm_f32, mm_fp8  # noqa: F401
from chipbench.weights import layer_key

GDN_BLOCK = 64      # tokens a checkpointed block of the recurrence
ROW_BLOCK = 256     # query rows a block of the softmax attention
LOGIT_BLOCK = 2048  # positions a block of the head and the loss


def _rms(x, w, eps):
    """w is the factor itself: a zero-centred weight comes in as 1 + w."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _conv(x, w):
    """Causal depthwise convolution: x [B,S,n], w [K,n]; w[K-1] meets x_t."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + S] * w[j] for j in range(K))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _delta_rule(q, k, v, g, beta):
    """The recurrence. q, k, v [B,S,H,dh] (q scaled, q and k already one a
    value head), g and beta [B,S,H]."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    blk = GDN_BLOCK if S % GDN_BLOCK == 0 else S

    def token(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None, None]
        u = bt[..., None] * (vt - jnp.sum(s * kt[..., None], axis=-2))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.sum(s * qt[..., None], axis=-2)

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(token, s, xs)

    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((S // blk, blk) + a.shape[:1]
                                             + a.shape[2:])
               for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((B, H, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape(S, B, H, dv), 0, 1)


def _gdn(x, w, sz: W.QwenNextSizes, mm):
    B, S, _ = x.shape
    Hk, Hv, hd = sz.Hk, sz.Hv, sz.ghd
    nk, nv = Hk * hd, Hv * hd
    qkvz, ba = mm(x, w["wqkvz"]), mm(x, w["wba"])
    qkv = jax.nn.silu(_conv(qkvz[..., :2 * nk + nv], w["conv"]))
    z = qkvz[..., 2 * nk + nv:].reshape(B, S, Hv, hd)
    q = _l2(qkv[..., :nk].reshape(B, S, Hk, hd)) / jnp.sqrt(jnp.float32(hd))
    k = _l2(qkv[..., nk:2 * nk].reshape(B, S, Hk, hd))
    v = qkv[..., 2 * nk:].reshape(B, S, Hv, hd)
    # key head i serves value heads 2i and 2i + 1 (r = Hv / Hk of them)
    q, k = (jnp.repeat(a, Hv // Hk, axis=2) for a in (q, k))
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[..., Hv:] + w["dt_bias"])
    o = _delta_rule(q, k, v, g, beta)
    y = _rms(o, w["o_norm"], sz.norm_eps) * jax.nn.silu(z)
    return mm(y.reshape(B, S, nv), w["wo"])


def inv_freq(sz: W.QwenNextSizes) -> jax.Array:
    """f_i = theta^(-2i / rot) of the rot / 2 pairs."""
    i = jnp.arange(sz.rot // 2, dtype=jnp.float32)
    return sz.theta ** (-2.0 * i / sz.rot)


def rotate(x, sz: W.QwenNextSizes):
    """x [B,S,H,hd] at positions 0..S-1: the first `rot` columns rotate,
    pair i = columns (i, rot / 2 + i); the rest pass."""
    S, half = x.shape[1], sz.rot // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq(sz)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:sz.rot]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., sz.rot:]], -1)


def _attn(x, w, sz: W.QwenNextSizes, mm):
    B, S, _ = x.shape
    H, KVH, hd = sz.H, sz.KVH, sz.hd
    qg = mm(x, w["wq"]).reshape(B, S, H, 2, hd)
    q, gate = qg[..., 0, :], qg[..., 1, :]
    k = mm(x, w["wk"]).reshape(B, S, KVH, hd)
    v = mm(x, w["wv"]).reshape(B, S, KVH, hd)
    q = rotate(_rms(q, 1.0 + w["q_norm"], sz.norm_eps), sz)
    k = rotate(_rms(k, 1.0 + w["k_norm"], sz.norm_eps), sz)
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
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, hd) * jax.nn.sigmoid(gate)
    return mm(o.reshape(B, S, H * hd), w["wo"])


def _swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def _experts(x, w, sz: W.QwenNextSizes, mm):
    """The held experts' part plus the gated shared expert."""
    p = jax.nn.softmax(mm_f32(x, w["router"]), -1)     # always exact: the
    gate, idx = jax.lax.top_k(p, sz.k)                 # routing is not the
    gate = gate / jnp.sum(gate, -1, keepdims=True)     # control's subject

    @jax.checkpoint
    def one(x, gate, idx, e_gate, e_up, e_down, e):
        we = jnp.sum(jnp.where(idx == sz.held_first + e, gate, 0.0), -1)
        return we[..., None] * _swiglu(x, e_gate, e_up, e_down, mm)

    def expert(y, e):  # a loop over the held experts (one compiled body)
        return y + one(x, gate, idx, w["e_gate"][e], w["e_up"][e],
                       w["e_down"][e], e), None

    sg = jax.nn.sigmoid(jnp.sum(x * w["shared_gate"], -1, keepdims=True))
    y = sg * _swiglu(x, w["s_gate"], w["s_up"], w["s_down"], mm)
    return jax.lax.scan(expert, y, jnp.arange(sz.held))[0]


def zero_delta(sz: W.QwenNextSizes) -> Dict[str, jax.Array]:
    z = lambda *s: jnp.zeros(s, jnp.float32)
    nk, nv = sz.Hk * sz.ghd, sz.Hv * sz.ghd
    out = {"final_norm": z(sz.d), "expert_down": z(sz.Fe, sz.d),
           "router": z(sz.d, sz.E), "shared_gate": z(sz.d)}
    if sz.l_gdn is not None:
        out.update(gdn_wo=z(nv, sz.d), gdn_wqkvz=z(sz.d, 2 * nk + 2 * nv),
                   gdn_A_log=z(sz.Hv), gdn_dt_bias=z(sz.Hv),
                   gdn_conv=z(sz.conv, 2 * nk + nv))
    if sz.l_attn is not None:
        q = sz.H * sz.hd
        out.update(attn_wq=z(sz.d, 2 * q), attn_wo=z(q, sz.d),
                   attn_q_norm=z(sz.hd))
    return out


def block(x, w, sz: W.QwenNextSizes, kind, mm):
    """One layer of `kind` = ("gdn" | "attn", "moe")."""
    mixer = _gdn if kind[0] == "gdn" else _attn
    x = x + mixer(_rms(x, 1.0 + w["attn_norm"], sz.norm_eps), w, sz, mm)
    return x + _experts(_rms(x, 1.0 + w["mlp_norm"], sz.norm_eps), w, sz, mm)


def with_delta(w, l, sz: W.QwenNextSizes, kind, delta):
    """Layer l's weights with the compared leaves' deltas added (`l` may be
    traced: a delta counts on its own layer alone)."""
    is_l = lambda n: 0.0 if n is None else jnp.asarray(l == n, jnp.float32)
    w = dict(w)
    if kind[0] == "gdn":
        for n in ("wo", "wqkvz", "A_log", "dt_bias", "conv"):
            w[n] = w[n] + is_l(sz.l_gdn) * delta["gdn_" + n]
    else:
        for n in ("wq", "wo", "q_norm"):
            w[n] = w[n] + is_l(sz.l_attn) * delta["attn_" + n]
    w["router"] = w["router"] + is_l(sz.l_moe) * delta["router"]
    w["shared_gate"] = w["shared_gate"] + is_l(sz.l_moe) * delta["shared_gate"]
    w["e_down"] = w["e_down"].at[sz.e_pick].add(
        is_l(sz.l_moe) * delta["expert_down"])
    return w


def hidden(key, tokens, sz: W.QwenNextSizes, mm: Callable = mm_f32,
           delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """tokens [B,S] int32 -> the final norm's output [B,S,d] float32."""
    delta = delta or zero_delta(sz)
    x = W.top(key, sz)["embed"][tokens]
    # One loop over the layers; each kind of layer is one branch, so that a
    # kind is compiled once however many layers have it.
    kinds = sorted(set(sz.kinds))

    def branch(kind):
        @jax.checkpoint
        def run(x, l, delta):
            w = with_delta(W.layer(layer_key(key, l), sz, kind), l, sz, kind,
                           delta)
            return block(x, w, sz, kind, mm)
        return run

    branches = [branch(k) for k in kinds]
    which = jnp.asarray([kinds.index(k) for k in sz.kinds])

    def layer(x, l):
        return jax.lax.switch(which[l], branches, x, l, delta), None

    x, _ = jax.lax.scan(layer, x, jnp.arange(sz.L))
    return _rms(x, 1.0 + W.top(key, sz)["final_norm"] + delta["final_norm"],
                sz.norm_eps)


def forward(key, tokens, sz: W.QwenNextSizes, mm: Callable = mm_f32,
            delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """tokens [B,S] int32 -> logits [B,S,V] float32."""
    return mm(hidden(key, tokens, sz, mm, delta), W.top(key, sz)["lm_head"])


def loss(key, tokens, sz: W.QwenNextSizes, mm: Callable = mm_f32,
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


def loss_and_grads(key, tokens, sz: W.QwenNextSizes, mm: Callable = mm_f32):
    """(loss, {leaf of zero_delta: gradient})."""
    return jax.value_and_grad(
        lambda dl: loss(key, tokens, sz, mm, dl))(zero_delta(sz))
