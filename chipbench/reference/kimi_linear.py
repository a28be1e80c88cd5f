"""The plain reference of the hybrid stack (moonshotai/Kimi-Linear-48B-A3B):
forward pass, loss and gradients in straightforward jax.numpy, float32,
matmuls at Precision.HIGHEST. Nothing from ray_tpu, no kernel, no chunked
form. It follows the published config (kda_layers / full_attn_layers, MLA
sizes, `mla_use_nope`, sigmoid router, `moe_renormalize`,
`routed_scaling_factor`, one shared expert) and, for what the config does
not give, the family's convention, each item listed under `assumed` in
configs/kimi_linear_48b_a3b.json:

- KDA, per head, token by token: q, k = L2Norm(SiLU(Conv4(W x))), v =
  SiLU(Conv4(W_v x)); g = -exp(A_log) softplus(W_f2 W_f1 x + dt_bias);
  beta = sigmoid(W_b x); S = (I - beta k k^T) Diag(e^g) S + beta k v^T;
  o = S^T q / sqrt(d_k); out = W_o [RMSNorm(o) * sigmoid(W_g2 W_g1 x)].
  The recurrence is a scan over tokens, checkpointed in blocks of 64 so that
  its backward fits at 8,192 positions (a flat scan would save a 2 MB state
  a token and head set: 17 GB a layer).
- MLA: [c; kr] = W_kva x; c = RMSNorm(c); [k_nope; v] = W_kvb c per head;
  k = [k_nope; kr], kr shared by the heads; q = W_q x; no rotation; causal
  softmax(q k^T / sqrt(192)) v over the full row, taken in blocks of query
  rows so that the scores fit.
- Experts: s = sigmoid(W_r h); top 8 of s + b; w = s_sel / sum * 2.446; a
  loop over the experts HELD (the chip's share), each applied to every token
  under its mask; plus the shared expert. What absent experts would add is
  left out, as in the program.

Weights come from the seed alone (chipbench/weights_kimi_linear.py), one
layer at a time. `mm` is the one place a projection's matmul happens: the
control swaps in float8 operands. `delta` adds to five leaves so that the
gradient with respect to it, at zero, is the gradient of those weights."""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench import weights_kimi_linear as W
from chipbench.reference.dense_decoder import mm_f32, mm_fp8  # noqa: F401
from chipbench.weights import layer_key

KDA_BLOCK = 64    # tokens a checkpointed block of the recurrence
ROW_BLOCK = 512   # query rows a block of the softmax attention


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _conv(x, w):
    """Causal depthwise convolution: x [B,S,n], w [K,n]; w[K-1] meets x_t."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + S] * w[j] for j in range(K))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _delta_rule(q, k, v, g, beta):
    """The recurrence. q,k,g [B,S,H,dk], v [B,S,H,dv], beta [B,S,H]."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    blk = KDA_BLOCK if S % KDA_BLOCK == 0 else S

    def token(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None]
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
    return jnp.moveaxis(o.reshape(S, B, H, dv), 0, 1) / jnp.sqrt(
        jnp.float32(dk))


def _kda(x, w, sz: W.HybridSizes, mm):
    B, S, _ = x.shape
    H, hd = sz.kda_H, sz.kda_hd
    heads = lambda a: a.reshape(B, S, H, hd)
    q, k, v = (heads(jax.nn.silu(_conv(mm(x, w["w" + n]), w["conv_" + n])))
               for n in ("q", "k", "v"))
    q, k = _l2(q), _l2(k)
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        heads(mm(mm(x, w["wf1"]), w["wf2"]) + w["dt_bias"]))
    beta = jax.nn.sigmoid(mm(x, w["wb"]))
    o = _rms(_delta_rule(q, k, v, g, beta), w["o_norm"], sz.norm_eps)
    o = o * jax.nn.sigmoid(heads(mm(mm(x, w["wg1"]), w["wg2"])))
    return mm(o.reshape(B, S, H * hd), w["wo"])


def _mla(x, w, sz: W.HybridSizes, mm):
    B, S, _ = x.shape
    H, nope, rope, dv = sz.H, sz.nope, sz.rope, sz.dv
    q = mm(x, w["wq"]).reshape(B, S, H, nope + rope)
    ckr = mm(x, w["wkva"])
    c = _rms(ckr[..., :sz.lat], w["kv_norm"], sz.norm_eps)
    kv = mm(c, w["wkvb"]).reshape(B, S, H, nope + dv)
    kr = jnp.broadcast_to(ckr[:, :, None, sz.lat:], (B, S, H, rope))
    k = jnp.concatenate([kv[..., :nope], kr], -1).transpose(0, 2, 3, 1)
    v = kv[..., nope:].transpose(0, 2, 1, 3)           # [B,H,S,dv]
    blk = ROW_BLOCK if S % ROW_BLOCK == 0 else S
    cols = jnp.arange(S)

    @jax.checkpoint
    def rows(args):
        qb, r0 = args                                  # [B,blk,H,qk]
        s = mm(qb.transpose(0, 2, 1, 3), k) / jnp.sqrt(
            jnp.float32(nope + rope))                  # [B,H,blk,S]
        keep = (r0 + jnp.arange(blk))[:, None] >= cols[None, :]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        return mm(p, v).transpose(0, 2, 1, 3)          # [B,blk,H,dv]

    qb = jnp.moveaxis(q.reshape(B, S // blk, blk, H, nope + rope), 1, 0)
    o = jax.lax.map(rows, (qb, jnp.arange(S // blk) * blk))
    return mm(jnp.moveaxis(o, 0, 1).reshape(B, S, H * dv), w["wo"])


def _swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def _experts(x, w, sz: W.HybridSizes, mm):
    """The held experts' part plus the shared expert."""
    s = jax.nn.sigmoid(mm_f32(x, w["router"]))         # always exact: the
    _, idx = jax.lax.top_k(s + w["router_bias"], sz.k)  # routing is not the
    gate = jnp.take_along_axis(s, idx, -1)              # control's subject
    gate = gate / jnp.sum(gate, -1, keepdims=True) * sz.routed_scale
    y = _swiglu(x, w["s_gate"], w["s_up"], w["s_down"], mm)

    def expert(y, e):  # a loop over the held experts (one compiled body)
        we = jnp.sum(jnp.where(idx == sz.held_first + e, gate, 0.0), -1)
        return y + we[..., None] * _swiglu(
            x, w["e_gate"][e], w["e_up"][e], w["e_down"][e], mm), None

    return jax.lax.scan(expert, y, jnp.arange(sz.held))[0]


def zero_delta(sz: W.HybridSizes) -> Dict[str, jax.Array]:
    z = lambda *s: jnp.zeros(s, jnp.float32)
    return {"final_norm": z(sz.d),
            "kda_wo": z(sz.kda_H * sz.kda_hd, sz.d),
            "mla_wkvb": z(sz.lat, sz.H * (sz.nope + sz.dv)),
            "expert_down": z(sz.Fe, sz.d),
            "router": z(sz.d, sz.E)}


def block(x, w, sz: W.HybridSizes, kind, mm):
    """One layer of `kind` = (mixer, feed-forward)."""
    h = _rms(x, w["attn_norm"], sz.norm_eps)
    x = x + (_kda if kind[0] == "kda" else _mla)(h, w, sz, mm)
    h = _rms(x, w["mlp_norm"], sz.norm_eps)
    if kind[1] == "dense":
        return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mm)
    return x + _experts(h, w, sz, mm)


def forward(key, tokens, sz: W.HybridSizes, mm: Callable = mm_f32,
            delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """tokens [B,S] int32 -> logits [B,S,V] float32."""
    delta = delta or zero_delta(sz)
    t = W.top(key, sz)
    x = t["embed"][tokens]
    # One loop over the layers; each kind of layer is one branch, so that a
    # kind is compiled once however many layers have it. `is_l(n)` is 1 on
    # the layer whose leaf is compared (never, where the stack lacks it).
    kinds = sorted(set(sz.kinds))

    def branch(kind):
        @jax.checkpoint
        def run(x, l, delta):
            is_l = lambda n: 0.0 if n is None else (l == n).astype(x.dtype)
            w = W.layer(layer_key(key, l), sz, kind)
            if kind[0] == "kda":
                w["wo"] = w["wo"] + is_l(sz.l_kda) * delta["kda_wo"]
            else:
                w["wkvb"] = w["wkvb"] + is_l(sz.l_mla) * delta["mla_wkvb"]
            if kind[1] == "moe":
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
    x = _rms(x, t["final_norm"] + delta["final_norm"], sz.norm_eps)
    return mm(x, t["lm_head"])


def loss(key, tokens, sz: W.HybridSizes, mm: Callable = mm_f32,
         delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """Mean next-token cross-entropy of tokens [B,S+1]."""
    ll = jax.nn.log_softmax(forward(key, tokens[:, :-1], sz, mm, delta), -1)
    return -jnp.mean(jnp.take_along_axis(ll, tokens[:, 1:, None], -1))


def loss_and_grads(key, tokens, sz: W.HybridSizes, mm: Callable = mm_f32):
    """(loss, {leaf of zero_delta: gradient})."""
    return jax.value_and_grad(
        lambda dl: loss(key, tokens, sz, mm, dl))(zero_delta(sz))
