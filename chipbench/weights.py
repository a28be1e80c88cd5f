"""Seed -> weights. The benchmark owns the weights: the program is handed
them (its `params_factory` / `init_params_fn` contract) and the reference
makes the same values again from the seed alone, one layer at a time, so
neither takes anything the other has made and no second copy is held.

`layer(key, l, sizes)` is the one definition of a layer's values, in the
plain layout the reference uses (x @ W, separate q/k/v, gate and up);
`program_params` stacks and fuses them into the layout of
ray_tpu.models.transformer (wq [L,d,H,hd], wkv [L,d,2,KVH,hd],
w_gate_up [L,d,2,F], ...) inside one jitted call, with the key an argument.
Threefry values depend on (key, shape) only, so vmapped and per-layer
generation agree bit for bit (selfcheck.py checks it)."""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


class Sizes:
    """The numbers of a configuration file's `transformer_config`."""

    def __init__(self, tc: Dict[str, Any], norm_eps: float):
        self.V = tc["vocab_size"]
        self.d = tc["d_model"]
        self.L = tc["n_layers"]
        self.H = tc["n_heads"]
        self.KVH = tc.get("n_kv_heads") or self.H
        self.hd = self.d // self.H
        self.F = tc["d_ff"]
        self.P = tc["max_seq_len"]
        self.norm = tc["norm"]
        self.activation = tc["activation"]
        self.positional = tc["positional"]
        self.rope_theta = float(tc.get("rope_theta", 10000.0))
        self.tied = bool(tc["tie_embeddings"])
        self.norm_eps = float(norm_eps)


def _n(key, i, shape, std):
    return jax.random.normal(jax.random.fold_in(key, i), shape,
                             jnp.float32) * std


def layer(key, sz: Sizes) -> Dict[str, jax.Array]:
    """One layer's weights from its own key, float32, plain layout."""
    d, F, L = sz.d, sz.F, sz.L
    q, kv = sz.H * sz.hd, sz.KVH * sz.hd
    w = {
        "attn_norm": 1.0 + _n(key, 0, (d,), 0.1),
        "wq": _n(key, 1, (d, q), 1 / math.sqrt(d)),
        "wk": _n(key, 2, (d, kv), 1 / math.sqrt(d)),
        "wv": _n(key, 3, (d, kv), 1 / math.sqrt(d)),
        "wo": _n(key, 4, (q, d), 1 / math.sqrt(2 * L * q)),
        "mlp_norm": 1.0 + _n(key, 5, (d,), 0.1),
        "w_up": _n(key, 6, (d, F), 1 / math.sqrt(d)),
        "w_down": _n(key, 7, (F, d), 1 / math.sqrt(2 * L * F)),
    }
    if sz.activation == "swiglu":
        w["w_gate"] = _n(key, 8, (d, F), 1 / math.sqrt(d))
    if sz.norm == "layernorm":
        w["attn_norm_b"] = _n(key, 9, (d,), 0.1)
        w["mlp_norm_b"] = _n(key, 10, (d,), 0.1)
    return w


def layer_key(key, l):
    return jax.random.fold_in(jax.random.fold_in(key, 1), l)


def top(key, sz: Sizes) -> Dict[str, jax.Array]:
    """Embedding, head, final norm and learned positions, float32."""
    k = jax.random.fold_in(key, 2)
    w = {"embed": _n(k, 0, (sz.V, sz.d), 0.02),
         "final_norm": 1.0 + _n(k, 1, (sz.d,), 0.1)}
    if not sz.tied:
        w["lm_head"] = _n(k, 2, (sz.d, sz.V), 0.02)
    if sz.norm == "layernorm":
        w["final_norm_b"] = _n(k, 3, (sz.d,), 0.1)
    if sz.positional == "learned":
        w["pos_embed"] = _n(k, 4, (sz.P, sz.d), 0.02)
    return w


def program_params(key, sz: Sizes, param_dtype=jnp.float32) -> Dict[str, Any]:
    """The same values in the program's stacked, fused layout."""
    lw = jax.vmap(lambda l: layer(layer_key(key, l), sz))(jnp.arange(sz.L))
    L, d, H, KVH, hd = sz.L, sz.d, sz.H, sz.KVH, sz.hd
    layers = {"attn_norm": lw["attn_norm"], "mlp_norm": lw["mlp_norm"],
              "wo": lw["wo"], "w_down": lw["w_down"]}
    wq = lw["wq"].reshape(L, d, H, hd)
    wk = lw["wk"].reshape(L, d, KVH, hd)
    wv = lw["wv"].reshape(L, d, KVH, hd)
    if KVH == H:
        layers["wqkv"] = jnp.stack([wq, wk, wv], axis=2)
    else:
        layers["wq"] = wq
        layers["wkv"] = jnp.stack([wk, wv], axis=2)
    if sz.activation == "swiglu":
        layers["w_gate_up"] = jnp.stack([lw["w_gate"], lw["w_up"]], axis=2)
    else:
        layers["w_up"] = lw["w_up"]
    for b in ("attn_norm_b", "mlp_norm_b"):
        if b in lw:
            layers[b] = lw[b]
    params = dict(top(key, sz))
    params["layers"] = layers
    return jax.tree.map(lambda a: a.astype(param_dtype), params)
