"""The plain reference of the state-space hybrid stack
(ibm-granite/granite-4.0-h-micro, `granitemoehybrid` with no experts):
forward pass, loss and gradients in straightforward jax.numpy, float32,
matmuls at Precision.HIGHEST. Nothing from ray_tpu, no kernel, no chunked
form. It follows the published config (`layer_types`, the Mamba-2 sizes,
`position_embedding_type` "nope", the four multipliers, the tied head) and,
for what the config does not give, the family's convention, each item listed
under `assumed` in configs/granite_4_0_h_micro.json:

    x = embed[tokens] * embedding_multiplier
    each layer:  x = x + residual_multiplier * mixer(RMSNorm(x))
                 x = x + residual_multiplier * mlp(RMSNorm(x))
    mlp(h) = W_down (silu(W_gate h) * W_up h)
    logits = (RMSNorm(x) embed^T) / logits_scaling

- Mamba-2: [z, xBC, dt] = split(W_in h; d_i, d_i + 2 G N, H); xBC =
  silu(conv4(xBC) + bias); [X, B, C] = split(xBC); dt = softplus(dt +
  dt_bias); A = -exp(A_log); per head, token by token, state S [P, N]:
  S = exp(dt A) S + dt X B^T; y = S C + D X; then y = RMSNorm(y * silu(z))
  over all d_i channels times w, and W_out y. The recurrence is a scan over
  tokens, checkpointed in blocks of 64 so that its backward fits at 4,096
  positions (a flat scan would save a 2 MB state a token: 8.6 GB a layer).
- attention: q = W_q h (H heads), k, v = W_k h, W_v h (KVH heads, each shared
  by H / KVH query heads), no bias, no rotation; causal
  softmax(q k^T * attention_multiplier) v over the full row, taken in blocks
  of query rows so that the scores fit.

Weights come from the seed alone (chipbench/weights_granite_hybrid.py), one
layer at a time. `mm` is the one place a projection's matmul happens: the
control swaps in float8 operands. `delta` adds to the compared leaves so
that the gradient with respect to it, at zero, is the gradient of those
weights, and no other gradient is held."""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench import weights_granite_hybrid as W
from chipbench.reference.dense_decoder import mm_f32, mm_fp8  # noqa: F401
from chipbench.weights import layer_key

SCAN_BLOCK = 64   # tokens a checkpointed block of the recurrence
ROW_BLOCK = 512   # query rows a block of the softmax attention


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _conv(x, w, b):
    """Causal depthwise convolution: x [B,S,n], w [K,n]; w[K-1] meets x_t."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + S] * w[j] for j in range(K)) + b


def _selective_scan(x, dt, A, Bm, Cm, D):
    """The recurrence. x [B,S,H,P]; dt [B,S,H]; A, D [H]; Bm, Cm [B,S,N]
    (one group)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    blk = SCAN_BLOCK if S % SCAN_BLOCK == 0 else S

    def token(s, t):
        xt, dtt, bt, ct = t                     # [B,H,P] [B,H] [B,N] [B,N]
        s = (s * jnp.exp(dtt * A)[..., None, None]
             + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return s, jnp.sum(s * ct[:, None, None, :], -1) + D[:, None] * xt

    @jax.checkpoint
    def block(s, ts):
        return jax.lax.scan(token, s, ts)

    ts = tuple(jnp.moveaxis(a, 1, 0).reshape((S // blk, blk) + a.shape[:1]
                                             + a.shape[2:])
               for a in (x, dt, Bm, Cm))
    _, y = jax.lax.scan(block, jnp.zeros((B, H, P, N), jnp.float32), ts)
    return jnp.moveaxis(y.reshape(S, B, H, P), 0, 1)


def _mamba(h, w, sz: W.StackSizes, mm):
    B, S, _ = h.shape
    assert sz.G == 1, "the reference is written for one group of B / C"
    di, N = sz.di, sz.N
    zxd = mm(h, w["in_proj"])
    z, xbc, dt = zxd[..., :di], zxd[..., di:di + sz.conv_ch], zxd[
        ..., di + sz.conv_ch:]
    xbc = jax.nn.silu(_conv(xbc, w["conv_w"], w["conv_b"]))
    x, Bm, Cm = xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = _selective_scan(x.reshape(B, S, sz.Hm, sz.P), dt, -jnp.exp(w["A_log"]),
                        Bm, Cm, w["D"]).reshape(B, S, di)
    return mm(_rms(y * jax.nn.silu(z), w["norm"], sz.norm_eps), w["out_proj"])


def _attention(h, w, sz: W.StackSizes, mm):
    B, S, _ = h.shape
    H, KVH, hd = sz.H, sz.KVH, sz.hd
    q = mm(h, w["wq"]).reshape(B, S, H, hd)
    rep = lambda a: jnp.repeat(a.reshape(B, S, KVH, hd), H // KVH, axis=2)
    k = rep(mm(h, w["wk"])).transpose(0, 2, 3, 1)      # [B,H,hd,S]
    v = rep(mm(h, w["wv"])).transpose(0, 2, 1, 3)      # [B,H,S,hd]
    blk = ROW_BLOCK if S % ROW_BLOCK == 0 else S
    cols = jnp.arange(S)

    @jax.checkpoint
    def rows(args):
        qb, r0 = args                                  # [B,blk,H,hd]
        s = mm(qb.transpose(0, 2, 1, 3), k) * sz.attn_scale
        keep = (r0 + jnp.arange(blk))[:, None] >= cols[None, :]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        return mm(p, v).transpose(0, 2, 1, 3)          # [B,blk,H,hd]

    qb = jnp.moveaxis(q.reshape(B, S // blk, blk, H, hd), 1, 0)
    o = jax.lax.map(rows, (qb, jnp.arange(S // blk) * blk))
    return mm(jnp.moveaxis(o, 0, 1).reshape(B, S, H * hd), w["wo"])


def zero_delta(sz: W.StackSizes) -> Dict[str, jax.Array]:
    z = lambda *s: jnp.zeros(s, jnp.float32)
    out = {"final_norm": z(sz.d)}
    if sz.l_mamba_first is not None:
        out.update(dt_bias=z(sz.Hm), A_log=z(sz.Hm),
                   conv_w=z(sz.K, sz.conv_ch), out_proj=z(sz.di, sz.d))
    if sz.l_attn is not None:
        out["attn_wo"] = z(sz.H * sz.hd, sz.d)
    return out


def block(x, w, sz: W.StackSizes, kind, mm):
    """One layer of `kind` = (mixer, "dense")."""
    h = _rms(x, w["attn_norm"], sz.norm_eps)
    mixer = _mamba if kind[0] == "mamba2" else _attention
    x = x + sz.residual_scale * mixer(h, w, sz, mm)
    h = _rms(x, w["mlp_norm"], sz.norm_eps)
    return x + sz.residual_scale * mm(
        jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"]), w["w_down"])


def forward(key, tokens, sz: W.StackSizes, mm: Callable = mm_f32,
            delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """tokens [B,S] int32 -> logits [B,S,V] float32."""
    delta = delta or zero_delta(sz)
    t = W.top(key, sz)
    x = t["embed"][tokens] * sz.embed_scale
    # One loop over the layers; each kind of layer is one branch, so that a
    # kind is compiled once however many layers have it. `is_l(n)` is 1 on
    # the layer whose leaf is compared.
    kinds = sorted(set(sz.kinds))

    def branch(kind):
        @jax.checkpoint
        def run(x, l, delta):
            is_l = lambda n: (l == n).astype(x.dtype)
            w = W.layer(layer_key(key, l), sz, kind)
            if kind[0] == "mamba2":
                first = is_l(sz.l_mamba_first)
                for n in ("dt_bias", "A_log", "conv_w"):
                    w[n] = w[n] + first * delta[n]
                w["out_proj"] = w["out_proj"] + is_l(
                    sz.l_mamba_last) * delta["out_proj"]
            else:
                w["wo"] = w["wo"] + is_l(sz.l_attn) * delta["attn_wo"]
            return block(x, w, sz, kind, mm)
        return run

    branches = [branch(k) for k in kinds]
    which = jnp.asarray([kinds.index(k) for k in sz.kinds])

    def layer(x, l):
        return jax.lax.switch(which[l], branches, x, l, delta), None

    x, _ = jax.lax.scan(layer, x, jnp.arange(sz.L))
    x = _rms(x, t["final_norm"] + delta["final_norm"], sz.norm_eps)
    return mm(x, t["embed"].T) / sz.logit_scale


def loss(key, tokens, sz: W.StackSizes, mm: Callable = mm_f32,
         delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """Mean next-token cross-entropy of tokens [B,S+1]."""
    ll = jax.nn.log_softmax(forward(key, tokens[:, :-1], sz, mm, delta), -1)
    return -jnp.mean(jnp.take_along_axis(ll, tokens[:, 1:, None], -1))


def loss_and_grads(key, tokens, sz: W.StackSizes, mm: Callable = mm_f32):
    """(loss, {leaf of zero_delta: gradient})."""
    return jax.value_and_grad(
        lambda dl: loss(key, tokens, sz, mm, dl))(zero_delta(sz))
