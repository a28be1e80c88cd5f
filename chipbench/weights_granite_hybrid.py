"""Seed -> weights of the state-space hybrid stack (Granite-4.0-H: Mamba-2
mixers, a NoPE GQA attention layer every tenth, a dense SwiGLU in every
layer, a tied head). As weights_kimi_linear.py: `layer(key, sz, kind)` is the
one definition of a layer's values, float32, in the plain layout the
reference uses (x @ W; `in_proj` one matrix [d, z | x B C | dt], the
convolution one [K, channels] with its bias); `program_params` lays the same
values out as ray_tpu.models.transformer holds the stack (a list of segments,
heads and the z/x and B/C pairs as array dims) inside one jitted call with
the key an argument. The reference makes a layer again from the seed alone.

Scales: 1/sqrt(fan-in), output projections 1/sqrt(2 L fan-in), norms
1 + 0.1 n; for the decay a = exp(-exp(A_log) softplus(W_dt h + dt_bias)):
A in [1, 16] and softplus(dt_bias) in [0.001, 0.1], both log-uniform (the
Mamba family's initialisation); D = 1; the convolution's bias N(0, 0.02).

`program_leaves` picks the gradient leaves the check compares out of the
program's gradient tree, in the plain layout; `zero_delta` of the reference
has the same names."""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from chipbench.weights import _n, layer_key


class StackSizes:
    """The numbers of the configuration file's `transformer_config`."""

    def __init__(self, tc: Dict[str, Any], norm_eps: float):
        self.V, self.d, self.L = tc["vocab_size"], tc["d_model"], tc["n_layers"]
        self.H, self.KVH, self.F = tc["n_heads"], tc["n_kv_heads"], tc["d_ff"]
        self.hd = self.d // self.H
        self.norm_eps = float(norm_eps)
        self.Hm, self.P = tc["mamba_heads"], tc["mamba_head_dim"]
        self.N, self.G = tc["mamba_d_state"], tc["mamba_groups"]
        self.K, self.chunk = tc["mamba_conv"], tc["mamba_chunk"]
        self.di = self.Hm * self.P                 # the mixer's inner width
        self.conv_ch = self.di + 2 * self.G * self.N
        self.embed_scale = float(tc["embed_scale"])
        self.residual_scale = float(tc["residual_scale"])
        self.attn_scale = float(tc["attn_scale"])
        self.logit_scale = float(tc["logit_scale"])
        mamba = set(tc["mamba_layers"])
        self.kinds: List[Tuple[str, str]] = [
            ("mamba2" if l + 1 in mamba else "attn", "dense")
            for l in range(self.L)]
        # The layers whose gradient leaves the check compares (None: the
        # stack has no such layer, as a one-layer test stack).
        where = lambda what: [l for l, k in enumerate(self.kinds)
                              if k[0] == what]
        self.l_mamba_first = min(where("mamba2"), default=None)
        self.l_mamba_last = max(where("mamba2"), default=None)
        self.l_attn = min(where("attn"), default=None)


def sizes_of(config: Dict[str, Any], rehearse: bool) -> StackSizes:
    """A configuration file's sizes, at its tiny preset for a rehearsal."""
    tc = dict(config["transformer_config"])
    if rehearse:
        tc.update(config["rehearsal"]["transformer_config"])
    return StackSizes(tc, config["norm_eps"])


def layer(key, sz: StackSizes, kind: Tuple[str, str]) -> Dict[str, jax.Array]:
    """One layer's weights from its own key, float32, plain layout."""
    d, L = sz.d, sz.L
    fan = lambda n: 1 / math.sqrt(n)
    out = lambda n: 1 / math.sqrt(2 * L * n)
    w = {"attn_norm": 1.0 + _n(key, 0, (d,), 0.1),
         "mlp_norm": 1.0 + _n(key, 1, (d,), 0.1),
         "w_gate": _n(key, 40, (d, sz.F), fan(d)),
         "w_up": _n(key, 41, (d, sz.F), fan(d)),
         "w_down": _n(key, 42, (sz.F, d), out(sz.F))}
    if kind[0] == "mamba2":
        w["in_proj"] = _n(key, 10, (d, sz.di + sz.conv_ch + sz.Hm), fan(d))
        w["conv_w"] = _n(key, 11, (sz.K, sz.conv_ch), fan(sz.K))
        w["conv_b"] = _n(key, 12, (sz.conv_ch,), 0.02)
        u = jax.random.uniform(jax.random.fold_in(key, 13), (sz.Hm,))
        w["A_log"] = u * math.log(16.0)
        u = jax.random.uniform(jax.random.fold_in(key, 14), (sz.Hm,))
        dt = jnp.exp(math.log(1e-3) + u * math.log(100.0))
        w["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
        w["D"] = jnp.ones((sz.Hm,), jnp.float32)
        w["norm"] = 1.0 + _n(key, 15, (sz.di,), 0.1)
        w["out_proj"] = _n(key, 16, (sz.di, d), out(sz.di))
    else:
        q, kv = sz.H * sz.hd, sz.KVH * sz.hd
        w["wq"] = _n(key, 30, (d, q), fan(d))
        w["wk"] = _n(key, 31, (d, kv), fan(d))
        w["wv"] = _n(key, 32, (d, kv), fan(d))
        w["wo"] = _n(key, 33, (q, d), out(q))
    return w


def top(key, sz: StackSizes) -> Dict[str, jax.Array]:
    """The tied embedding / head and the final norm, float32."""
    k = jax.random.fold_in(key, 2)
    return {"embed": _n(k, 0, (sz.V, sz.d), 0.02),
            "final_norm": 1.0 + _n(k, 1, (sz.d,), 0.1)}


def to_program(w: Dict[str, jax.Array], sz: StackSizes,
               kind: Tuple[str, str]) -> Dict[str, jax.Array]:
    """One layer from the plain layout to the program's (leaf names and
    shapes of models/transformer.py `_layer_shapes`)."""
    d, Hm, P, G, N, K = sz.d, sz.Hm, sz.P, sz.G, sz.N, sz.K
    p = {"attn_norm": w["attn_norm"], "mlp_norm": w["mlp_norm"],
         "w_gate_up": jnp.stack([w["w_gate"], w["w_up"]], axis=1),
         "w_down": w["w_down"]}
    if kind[0] == "mamba2":
        di, ip = sz.di, w["in_proj"]
        p["mamba_wzx"] = jnp.stack([ip[:, :di].reshape(d, Hm, P),
                                    ip[:, di:2 * di].reshape(d, Hm, P)], 1)
        p["mamba_wbc"] = ip[:, 2 * di:di + sz.conv_ch].reshape(d, 2, G, N)
        p["mamba_wdt"] = ip[:, di + sz.conv_ch:]
        p["mamba_conv_x"] = w["conv_w"][:, :di].reshape(K, Hm, P)
        p["mamba_conv_x_b"] = w["conv_b"][:di].reshape(Hm, P)
        p["mamba_conv_bc"] = w["conv_w"][:, di:].reshape(K, 2, G, N)
        p["mamba_conv_bc_b"] = w["conv_b"][di:].reshape(2, G, N)
        p["mamba_A_log"], p["mamba_dt_bias"] = w["A_log"], w["dt_bias"]
        p["mamba_D"] = w["D"]
        p["mamba_norm"] = w["norm"].reshape(Hm, P)
        p["mamba_wo"] = w["out_proj"].reshape(Hm, P, d)
    else:
        p["wq"] = w["wq"].reshape(d, sz.H, sz.hd)
        p["wkv"] = jnp.stack([w["wk"].reshape(d, sz.KVH, sz.hd),
                              w["wv"].reshape(d, sz.KVH, sz.hd)], axis=1)
        p["wo"] = w["wo"]
    return p


def program_params(key, sz: StackSizes, cfg, param_dtype=jnp.float32):
    """The same values as the program holds them: `cfg` is the program's
    TransformerConfig, whose `stack_plan()` says how layers are grouped."""
    segments, l = [], 0
    for pattern, r in cfg.stack_plan():
        seg = []
        for pos, kind in enumerate(pattern):
            ids = jnp.asarray([l + pos + i * len(pattern) for i in range(r)])
            seg.append(jax.vmap(lambda i, kind=kind: to_program(
                layer(layer_key(key, i), sz, kind), sz, kind))(ids))
        segments.append(seg)
        l += len(pattern) * r
    params = dict(top(key, sz))
    params["layers"] = segments
    return jax.tree.map(lambda a: a.astype(param_dtype), params)


def program_leaves(cfg, sz: StackSizes, g) -> Dict[str, jax.Array]:
    """The compared gradient leaves from the program's gradient tree, in
    the reference's plain layout (`reference/granite_hybrid.zero_delta`)."""
    from ray_tpu.models.transformer import layer_params

    out = {"final_norm": g["final_norm"]}
    if sz.l_mamba_first is not None:
        first = layer_params(g, cfg, sz.l_mamba_first)
        last = layer_params(g, cfg, sz.l_mamba_last)
        out["dt_bias"], out["A_log"] = first["mamba_dt_bias"], first["mamba_A_log"]
        out["conv_w"] = jnp.concatenate(
            [first["mamba_conv_x"].reshape(sz.K, -1),
             first["mamba_conv_bc"].reshape(sz.K, -1)], axis=1)
        out["out_proj"] = last["mamba_wo"].reshape(sz.di, sz.d)
    if sz.l_attn is not None:
        out["attn_wo"] = layer_params(g, cfg, sz.l_attn)["wo"]
    return out
