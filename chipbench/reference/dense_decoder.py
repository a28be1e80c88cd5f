"""The plain reference: a dense decoder-only transformer's forward pass, loss
and gradients in straightforward jax.numpy, float32, matmuls at
`default_matmul_precision("highest")`. No kernel, no cache, no batching,
nothing from ray_tpu. It follows the published descriptions:

- InternLM2 (internlm/internlm2-1_8b modeling_internlm2.py): pre-RMSNorm,
  grouped-query attention with rotate-half RoPE, SwiGLU, untied head, no
  bias;
- GPT-2 (openai-community/gpt2): pre-LayerNorm, learned positions, tanh-GELU,
  tied head. Departure, noted in configs/gpt2_124m.json: no bias on the
  linear layers, because the program's TransformerConfig has none.

Weights come from the seed alone (chipbench/weights.py), one layer at a time
inside the scan, so the reference holds no copy of the model. `mm` is the
one place a matmul happens: the control (tests/test_control.py, limits.py)
swaps in a lower-precision one. `delta` adds to three leaves so that the
gradient with respect to it, at zero, is the gradient of those weights."""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench import weights as W


def mm_f32(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def mm_fp8(x, w):
    """The control's matmul: operands rounded to float8_e4m3fn (per-tensor
    scaled into its range), the nearest precision below bfloat16. The
    rounding is straight-through for the gradient, as fp8 training recipes
    have it: the backward sees the rounded forward, not rounded tangents."""
    def q(a):
        s = jax.lax.stop_gradient(jnp.max(jnp.abs(a)) / 448.0 + 1e-30)
        r = (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return a + jax.lax.stop_gradient(r - a)
    return mm_f32(q(x), q(w))


def mm_bf16(x, w):
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _norm(x, w, b, sz: W.Sizes):
    if sz.norm == "rmsnorm":
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + sz.norm_eps) * w
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + sz.norm_eps) * w + b


def _rope(x, theta: float):
    """[B,S,N,hd], rotate-half convention of the published code."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, mm):
    """Causal softmax attention, [B,S,H,hd]; K/V heads already repeated."""
    B, S, H, hd = q.shape
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    s = mm(q, k.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return mm(jax.nn.softmax(s, -1), v).transpose(0, 2, 1, 3)


def _block(x, w, sz: W.Sizes, mm):
    B, S, d = x.shape
    h = _norm(x, w["attn_norm"], w.get("attn_norm_b"), sz)
    q = mm(h, w["wq"]).reshape(B, S, sz.H, sz.hd)
    k = mm(h, w["wk"]).reshape(B, S, sz.KVH, sz.hd)
    v = mm(h, w["wv"]).reshape(B, S, sz.KVH, sz.hd)
    if sz.positional == "rope":
        q, k = _rope(q, sz.rope_theta), _rope(k, sz.rope_theta)
    rep = sz.H // sz.KVH
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    x = x + mm(_attention(q, k, v, mm).reshape(B, S, sz.H * sz.hd), w["wo"])
    h = _norm(x, w["mlp_norm"], w.get("mlp_norm_b"), sz)
    if sz.activation == "swiglu":
        a = jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"])
    else:
        a = jax.nn.gelu(mm(h, w["w_up"]), approximate=True)
    return x + mm(a, w["w_down"])


def zero_delta(sz: W.Sizes) -> Dict[str, jax.Array]:
    return {"final_norm": jnp.zeros((sz.d,), jnp.float32),
            "wo_last": jnp.zeros((sz.H * sz.hd, sz.d), jnp.float32),
            "attn_norm_first": jnp.zeros((sz.d,), jnp.float32)}


def forward(key, tokens, sz: W.Sizes, mm: Callable = mm_f32,
            delta: Optional[Dict[str, Any]] = None,
            at: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B,S] int32 -> logits [B,S,V] float32 (at positions `at` only,
    [B,len(at),V], when given)."""
    delta = delta or zero_delta(sz)
    t = W.top(key, sz)
    x = t["embed"][tokens]
    if sz.positional == "learned":
        x = x + t["pos_embed"][:tokens.shape[1]][None]

    @jax.checkpoint
    def body(x, l):
        w = W.layer(W.layer_key(key, l), sz)
        w["wo"] = w["wo"] + (l == sz.L - 1) * delta["wo_last"]
        w["attn_norm"] = w["attn_norm"] + (l == 0) * delta["attn_norm_first"]
        return _block(x, w, sz, mm), None

    x, _ = jax.lax.scan(body, x, jnp.arange(sz.L))
    if at is not None:
        x = x[:, at]
    x = _norm(x, t["final_norm"] + delta["final_norm"],
              t.get("final_norm_b"), sz)
    head = t["embed"].T if sz.tied else t["lm_head"]
    return mm(x, head)


def loss(key, tokens, sz: W.Sizes, mm: Callable = mm_f32,
         delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """Mean next-token cross-entropy of tokens [B,S+1]."""
    ll = jax.nn.log_softmax(forward(key, tokens[:, :-1], sz, mm, delta), -1)
    return -jnp.mean(jnp.take_along_axis(ll, tokens[:, 1:, None], -1))


def loss_and_grads(key, tokens, sz: W.Sizes, mm: Callable = mm_f32):
    """(loss, {final_norm, wo_last, attn_norm_first: gradient})."""
    return jax.value_and_grad(
        lambda dl: loss(key, tokens, sz, mm, dl))(zero_delta(sz))
