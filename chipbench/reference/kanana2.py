"""The plain reference of the all-latent-attention expert stack
(kakaocorp/kanana-2-30b-a3b-instruct-2601, `model_type: deepseek_v3`):
forward pass, loss and gradients in straightforward jax.numpy, float32,
matmuls at Precision.HIGHEST. Nothing from ray_tpu, no kernel, no tile. It
follows the published config (`q_lora_rank` null, `kv_lora_rank` 512,
`qk_nope_head_dim` 128 + `qk_rope_head_dim` 64, `v_head_dim` 128,
`rope_interleave`, `rope_scaling` null, `first_k_dense_replace` 1,
`scoring_func` sigmoid, `topk_method` noaux_tc with `n_group` 1,
`norm_topk_prob`, `routed_scaling_factor`, `n_shared_experts` 2) and, for
what the config does not give, the family's convention, each item listed
under `assumed` in configs/kanana_2_30b_a3b.json:

    x = embed[tokens]
    each layer:  h = RMSNorm(x);  q = W_q h -> H heads of [q_nope ; q_rot]
                 [c ; k_rot] = W_kva h;  [k_nope ; v] = W_kvb RMSNorm(c)
                 R_p on a 64-wide vector, pair i = columns (2i, 2i + 1):
                   (a, b) -> (a cos(p f_i) - b sin(p f_i),
                              a sin(p f_i) + b cos(p f_i)), f_i = theta^(-2i/64)
                 q_n = [q_nope ; R_p q_rot],  k_n = [k_nope ; R_p k_rot]
                   (k_rot rotated once, the same for every head)
                 x = x + W_o softmax(q k^T / sqrt(192), keys j <= i) v
                 h = RMSNorm(x)
                 layer 1:  x = x + W_down (silu(W_gate h) * W_up h)
                 others:   s = sigmoid(W_r h);  T = top-k of (s + b)
                           g_e = scale s_e / sum_T s
                           x = x + sum_{e in T, held} g_e SwiGLU_e(h)
                                 + SwiGLU_shared(h)
    logits = W_head RMSNorm(x)

- the rotation works the PUBLISHED layout of the rotated columns
  (interleaved pairs); the program holds them as halves and the weight maker
  turns them (weights_kanana2.py `turn`).
- attention: full softmax rows taken in blocks of query rows so that the
  scores fit; the shared key part broadcast over the heads after rotation.
- experts: a loop over the held ones, each applied to EVERY token and
  weighted by g_e (zero where e is not among the token's top k); the routing
  is over all E and always exact (it is not the control's subject). The two
  shared experts are one SwiGLU of twice the width.

Departures from the published model: the held range (experts outside it are
left out of the sum, in the program alike: one expert-parallel rank), the
vocabulary slice, the 1e-20 the family adds to the top-k's sum is not added
(sigmoid scores are positive: it changes nothing float32 can see), no
multi-token-prediction layer (`config.json` has no key for one).

Weights come from the seed alone (chipbench/weights_kanana2.py), one layer
at a time. `mm` is the one place a projection's matmul happens: the control
swaps in float8 operands. `delta` adds to the compared leaves so that the
gradient with respect to it, at zero, is the gradient of those weights, and
no other gradient is held."""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench import weights_kanana2 as W
from chipbench.reference.dense_decoder import mm_f32, mm_fp8  # noqa: F401
from chipbench.weights import layer_key

ROW_BLOCK = 256     # query rows a block of the softmax attention
LOGIT_BLOCK = 2048  # positions a block of the head and the loss


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def inv_freq(sz: W.KananaSizes) -> jax.Array:
    """f_i = theta^(-2i / rope) of the rope / 2 pairs."""
    i = jnp.arange(sz.rope // 2, dtype=jnp.float32)
    return sz.theta ** (-2.0 * i / sz.rope)


def rotate(x, sz: W.KananaSizes):
    """x [B,S,...,rope] at positions 0..S-1, pair i = columns (2i, 2i+1)."""
    S = x.shape[1]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq(sz)
    ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def _mla(x, w, sz: W.KananaSizes, mm):
    B, S, _ = x.shape
    H, nope, rope, dv = sz.H, sz.nope, sz.rope, sz.dv
    q = mm(x, w["wq"]).reshape(B, S, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], sz)], -1)
    ckr = mm(x, w["wkva"])
    c = _rms(ckr[..., :sz.lat], w["kv_norm"], sz.norm_eps)
    kv = mm(c, w["wkvb"]).reshape(B, S, H, nope + dv)
    kr = rotate(ckr[..., sz.lat:], sz)                 # once, [B,S,rope]
    kr = jnp.broadcast_to(kr[:, :, None], (B, S, H, rope))
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


def _experts(x, w, sz: W.KananaSizes, mm):
    """The held experts' part plus the shared experts."""
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


def zero_delta(sz: W.KananaSizes) -> Dict[str, jax.Array]:
    z = lambda *s: jnp.zeros(s, jnp.float32)
    H, qk = sz.H, sz.nope + sz.rope
    out = {"final_norm": z(sz.d), "mla_wo": z(H * sz.dv, sz.d),
           "mla_wq": z(sz.d, H * qk), "mla_wkva": z(sz.d, sz.lat + sz.rope),
           "mla_wkvb": z(sz.lat, H * (sz.nope + sz.dv))}
    if sz.l_dense is not None:
        out["w_down"] = z(sz.F, sz.d)
    if sz.l_moe is not None:
        out.update(expert_down=z(sz.Fe, sz.d), router=z(sz.d, sz.E))
    return out


def block(x, w, sz: W.KananaSizes, kind, mm):
    """One layer of `kind` = ("mla", "dense" | "moe")."""
    x = x + _mla(_rms(x, w["attn_norm"], sz.norm_eps), w, sz, mm)
    h = _rms(x, w["mlp_norm"], sz.norm_eps)
    if kind[1] == "dense":
        return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mm)
    return x + _experts(h, w, sz, mm)


def hidden(key, tokens, sz: W.KananaSizes, mm: Callable = mm_f32,
           delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """tokens [B,S] int32 -> the final norm's output [B,S,d] float32."""
    delta = delta or zero_delta(sz)
    x = W.top(key, sz)["embed"][tokens]
    # One loop over the layers; each kind of layer is one branch, so that a
    # kind is compiled once however many layers have it. `is_l(n)` is 1 on
    # the layer whose leaf is compared (never, where the stack lacks it).
    kinds = sorted(set(sz.kinds))

    def branch(kind):
        @jax.checkpoint
        def run(x, l, delta):
            is_l = lambda n: 0.0 if n is None else (l == n).astype(x.dtype)
            w = W.layer(layer_key(key, l), sz, kind)
            for n in ("wo", "wq", "wkva", "wkvb"):
                w[n] = w[n] + is_l(sz.l_mla) * delta["mla_" + n]
            if kind[1] == "dense":
                w["w_down"] = w["w_down"] + is_l(sz.l_dense) * delta["w_down"]
            else:
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


def forward(key, tokens, sz: W.KananaSizes, mm: Callable = mm_f32,
            delta: Optional[Dict[str, Any]] = None) -> jax.Array:
    """tokens [B,S] int32 -> logits [B,S,V] float32."""
    return mm(hidden(key, tokens, sz, mm, delta), W.top(key, sz)["lm_head"])


def loss(key, tokens, sz: W.KananaSizes, mm: Callable = mm_f32,
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


def loss_and_grads(key, tokens, sz: W.KananaSizes, mm: Callable = mm_f32):
    """(loss, {leaf of zero_delta: gradient})."""
    return jax.value_and_grad(
        lambda dl: loss(key, tokens, sz, mm, dl))(zero_delta(sz))
