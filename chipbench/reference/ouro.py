"""The plain reference of the looped dense stack (ByteDance/Ouro-2.6B,
`model_type: ouro`): forward passes, the expected-exit loss and gradients in
straightforward jax.numpy, float32, matmuls at Precision.HIGHEST. Nothing
from ray_tpu, no kernel. It follows the published config (`total_ut_steps`
passes over one stack of full-attention layers, 16 | 16 heads of 128, RoPE
theta 1e6 with no scaling, SwiGLU, RMSNorm eps 1e-6, an untied head) and,
for what the config does not give, what is listed under `assumed` in
configs/ouro_2_6b.json. With h^0 = embed[tokens], T passes, L layers whose
weights every pass shares, positions 0..S-1 the same in every pass:

    pass t = 1..T:  x = h^(t-1)
                    each layer:  x = x + N2a(Attn(N1a(x)))
                                 x = x + N2m(W_down(silu(W_gate h) * W_up h)),  h = N1m(x)
                    h^t = Nf(x)                       the one final norm
                    CE_i^t = -log softmax(h_i^t W_head)[y_i]
                    lam_i^t = sigmoid(w_g . h_i^t + b_g)        for t < T
    exit:           S_i^0 = 1,  S_i^t = S_i^(t-1) (1 - lam_i^t)
                    p_i(t) = lam_i^t S_i^(t-1) for t < T,  p_i(T) = S_i^(T-1)
    loss:           mean_i [ sum_t p_i(t) CE_i^t - beta H(p_i) ],
                    H(p) = -sum_t p(t) log p(t)

Every N is x / rms(x) * w. Attention is causal softmax over the full row,
rotate-half RoPE on all 128 columns, taken in blocks of query rows so that
the scores fit; the head in blocks of positions so that one block's logits
[rows, V] are all that is alive. The layers are a scan inside a scan over
the passes (one layer body compiled), each application checkpointed.

Weights come from the seed alone (chipbench/weights_ouro.py), one layer at a
time. `mm` is the one place a product of activations with a weight or of two
activations happens: the control swaps in float8 operands (the gate's dot
product with one vector is float32 by the model's statement and stays so).
`delta` adds to the compared leaves so that the gradient with respect to
it, at zero, is the gradient of those weights over all their uses, and no
other gradient is held."""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench import weights_ouro as W
from chipbench.reference.dense_decoder import (  # noqa: F401
    _rope, mm_f32, mm_fp8)
from chipbench.weights import layer_key

ROW_BLOCK = 512    # query rows a block of the softmax attention
HEAD_BLOCK = 1024  # positions a block of the head and cross-entropy


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _attention(h, w, sz: W.OuroSizes, mm):
    B, S, _ = h.shape
    H, hd = sz.H, sz.hd
    heads = lambda a: a.reshape(B, S, H, hd)
    q = _rope(heads(mm(h, w["wq"])), sz.rope_theta)
    k = _rope(heads(mm(h, w["wk"])), sz.rope_theta).transpose(0, 2, 3, 1)
    v = heads(mm(h, w["wv"])).transpose(0, 2, 1, 3)    # [B,H,S,hd]
    blk = ROW_BLOCK if S % ROW_BLOCK == 0 else S
    cols = jnp.arange(S)

    @jax.checkpoint
    def rows(args):
        qb, r0 = args                                  # [B,blk,H,hd]
        s = mm(qb.transpose(0, 2, 1, 3), k) / jnp.sqrt(jnp.float32(hd))
        keep = (r0 + jnp.arange(blk))[:, None] >= cols[None, :]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        return mm(p, v).transpose(0, 2, 1, 3)          # [B,blk,H,hd]

    qb = jnp.moveaxis(q.reshape(B, S // blk, blk, H, hd), 1, 0)
    o = jax.lax.map(rows, (qb, jnp.arange(S // blk) * blk))
    return mm(jnp.moveaxis(o, 0, 1).reshape(B, S, H * hd), w["wo"])


def block(x, w, sz: W.OuroSizes, mm):
    """One layer: two norms round each sublayer."""
    eps = sz.norm_eps
    a = _attention(_rms(x, w["attn_norm"], eps), w, sz, mm)
    x = x + _rms(a, w["attn_post_norm"], eps)
    h = _rms(x, w["mlp_norm"], eps)
    m = mm(jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"]), w["w_down"])
    return x + _rms(m, w["mlp_post_norm"], eps)


def zero_delta(sz: W.OuroSizes) -> Dict[str, jax.Array]:
    z = lambda *s: jnp.zeros(s, jnp.float32)
    q = sz.H * sz.hd
    return {"final_norm": z(sz.d), "gate_w": z(sz.d),
            "lm_head_rows": z(sz.d, sz.head_rows),
            "wo_last": z(q, sz.d), "w_down_last": z(sz.F, sz.d),
            "attn_post_norm_last": z(sz.d), "mlp_post_norm_last": z(sz.d),
            "wq_first": z(sz.d, q)}


def cross_entropy(h, head, targets, mm):
    """-log softmax(h W_head)[target] of every position, [B,S]; whole
    logits a block of positions at a time."""
    B, S, d = h.shape
    blk = HEAD_BLOCK if S % HEAD_BLOCK == 0 else S

    @jax.checkpoint
    def rows(args):
        hb, tb = args                                  # [B,blk,d] [B,blk]
        ll = jax.nn.log_softmax(mm(hb, head), -1)
        return -jnp.take_along_axis(ll, tb[..., None], -1)[..., 0]

    split = lambda a: jnp.moveaxis(
        a.reshape((B, S // blk, blk) + a.shape[2:]), 1, 0)
    ce = jax.lax.map(rows, (split(h), split(targets)))
    return jnp.moveaxis(ce, 0, 1).reshape(B, S)


def passes(key, tokens, targets, sz: W.OuroSizes, mm: Callable = mm_f32,
           delta: Optional[Dict[str, Any]] = None):
    """tokens, targets [B,S] -> (CE [T,B,S], lam [T,B,S]): every pass's
    cross-entropy and gate (the last pass's gate is computed and not used)."""
    delta = delta or zero_delta(sz)
    t = W.top(key, sz)
    final_norm = t["final_norm"] + delta["final_norm"]
    head = t["lm_head"].at[:, :sz.head_rows].add(delta["lm_head_rows"])
    w_g = t["exit_gate_w"] + delta["gate_w"]

    @jax.checkpoint
    def layer(x, l):
        is_l = lambda n: (l == n).astype(x.dtype)
        w = W.layer(layer_key(key, l), sz)
        w["wq"] = w["wq"] + is_l(0) * delta["wq_first"]
        for n in ("wo", "w_down", "attn_post_norm", "mlp_post_norm"):
            w[n] = w[n] + is_l(sz.L - 1) * delta[n + "_last"]
        return block(x, w, sz, mm), None

    def one_pass(x, _):
        x, _ = jax.lax.scan(layer, x, jnp.arange(sz.L))
        h = _rms(x, final_norm, sz.norm_eps)
        lam = jax.nn.sigmoid(jnp.sum(h * w_g, -1) + t["exit_gate_b"])
        return h, (cross_entropy(h, head, targets, mm), lam)

    _, (ce, lam) = jax.lax.scan(one_pass, t["embed"][tokens], None,
                                length=sz.T)
    return ce, lam


def exit_distribution(lam):
    """lam [T,...] (the last not used) -> p [T,...], summing to 1 over T."""
    p, alive = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * alive)
        alive = alive * (1.0 - lam[t])
    return jnp.stack(p + [alive])


def expected_loss(ce, lam, beta):
    p = exit_distribution(lam)
    entropy = -jnp.sum(p * jnp.log(p), 0)
    return jnp.mean(jnp.sum(p * ce, 0) - beta * entropy)


def loss(key, tokens, sz: W.OuroSizes, mm: Callable = mm_f32,
         delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """The expected-exit loss of tokens [B,S+1]."""
    ce, lam = passes(key, tokens[:, :-1], tokens[:, 1:], sz, mm, delta)
    return expected_loss(ce, lam, sz.beta)


def loss_and_grads(key, tokens, sz: W.OuroSizes, mm: Callable = mm_f32):
    """(loss, {leaf of zero_delta: gradient})."""
    return jax.value_and_grad(
        lambda dl: loss(key, tokens, sz, mm, dl))(zero_delta(sz))
