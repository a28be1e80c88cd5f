"""Seed -> weights of the hybrid stack (Kimi-Linear: KDA and MLA mixers, a
dense lead layer, sigmoid-routed experts with a shared one). As weights.py
for the dense decoder: `layer(key, l, sz)` is the one definition of a
layer's values, float32, in the plain layout the reference uses (x @ W,
every projection a matrix of its own); `program_params` lays the same values
out as ray_tpu.models.transformer holds a mixed stack (a list of segments,
heads and the gate/up pair as array dims), inside one jitted call with the
key an argument. The reference makes a layer again from the seed alone.

Scales are the dense decoder's (1/sqrt(fan-in), output projections
1/sqrt(2 L fan-in), norms 1 + 0.1 n) plus, for the decay
a = exp(-exp(A_log) softplus(W_f2 W_f1 x + dt_bias)): A in [1, 8] and
softplus(dt_bias) in [0.001, 0.1], both log-uniform, W_f2 at a quarter of
its fan-in scale. A channel then forgets over 3 to 1000 tokens, and no
channel decays by more than e^80 inside 32 tokens, where ops/kda.py's
chunked form stops being exact. The router's selection bias is N(0, 0.01):
small against the scores' spread (0.2), so that it decides close calls and
does not itself unbalance the experts' loads, which a trained bias evens."""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from chipbench.weights import _n, layer_key


class HybridSizes:
    """The numbers of a hybrid configuration file's `transformer_config`."""

    def __init__(self, tc: Dict[str, Any], norm_eps: float):
        self.V, self.d, self.L = tc["vocab_size"], tc["d_model"], tc["n_layers"]
        self.H, self.F = tc["n_heads"], tc["d_ff"]
        self.norm_eps = float(norm_eps)
        self.kda_hd = tc["kda_head_dim"]
        self.kda_H = tc.get("kda_heads") or self.H
        self.conv = tc["kda_conv"]
        self.rank = tc.get("kda_gate_rank") or self.kda_hd
        self.lat, self.rope = tc["kv_lora_rank"], tc["qk_rope_head_dim"]
        self.nope, self.dv = tc["qk_nope_head_dim"], tc["v_head_dim"]
        self.E, self.k = tc["moe_num_experts"], tc["moe_experts_per_token"]
        self.held_first, self.held = tc.get("moe_held") or (0, self.E)
        self.Fe = tc["moe_d_ff"]
        self.shared = tc["moe_shared_experts"]
        self.routed_scale = float(tc["moe_routed_scale"])
        kda, mla = set(tc["kda_layers"]), set(tc["mla_layers"])
        first_dense = tc["moe_first_dense"]
        self.kinds: List[Tuple[str, str]] = [
            ("kda" if l + 1 in kda else "mla" if l + 1 in mla else "attn",
             "dense" if l < first_dense else "moe") for l in range(self.L)]
        assert all(m != "attn" for m, _ in self.kinds), self.kinds
        # The gradient leaves the check compares, by layer (None: the stack
        # has no layer of that kind, as a one-layer test stack).
        where = lambda i, what: [l for l, k in enumerate(self.kinds)
                                 if k[i] == what]
        self.l_kda = max(where(0, "kda"), default=None)
        self.l_mla = min(where(0, "mla"), default=None)
        self.l_moe = min(where(1, "moe"), default=None)
        self.e_pick = self.held // 2  # a held expert, local number


def sizes_of(config: Dict[str, Any], rehearse: bool) -> HybridSizes:
    """A configuration file's sizes, at its tiny preset for a rehearsal."""
    tc = dict(config["transformer_config"])
    if rehearse:
        tc.update(config["rehearsal"]["transformer_config"])
    return HybridSizes(tc, config["norm_eps"])


def layer(key, sz: HybridSizes, kind: Tuple[str, str]) -> Dict[str, jax.Array]:
    """One layer's weights from its own key, float32, plain layout."""
    mixer, ffn = kind
    d, L = sz.d, sz.L
    fan = lambda n: 1 / math.sqrt(n)
    out = lambda n: 1 / math.sqrt(2 * L * n)
    w = {"attn_norm": 1.0 + _n(key, 0, (d,), 0.1),
         "mlp_norm": 1.0 + _n(key, 1, (d,), 0.1)}
    if mixer == "kda":
        n, r, K = sz.kda_H * sz.kda_hd, sz.rank, sz.conv
        for i, name in enumerate(("q", "k", "v")):
            w["w" + name] = _n(key, 10 + i, (d, n), fan(d))
            w["conv_" + name] = _n(key, 13 + i, (K, n), fan(K))
        w["wf1"] = _n(key, 16, (d, r), fan(d))
        w["wf2"] = _n(key, 17, (r, n), 0.25 * fan(r))
        w["wg1"] = _n(key, 18, (d, r), fan(d))
        w["wg2"] = _n(key, 19, (r, n), fan(r))
        u = jax.random.uniform(jax.random.fold_in(key, 20), (sz.kda_H,))
        w["A_log"] = u * math.log(8.0)
        u = jax.random.uniform(jax.random.fold_in(key, 21), (n,))
        dt = jnp.exp(math.log(1e-3) + u * math.log(100.0))
        w["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
        w["wb"] = _n(key, 22, (d, sz.kda_H), fan(d))
        w["o_norm"] = 1.0 + _n(key, 23, (sz.kda_hd,), 0.1)
        w["wo"] = _n(key, 24, (n, d), out(n))
    else:
        H, qk = sz.H, sz.nope + sz.rope
        w["wq"] = _n(key, 30, (d, H * qk), fan(d))
        w["wkva"] = _n(key, 31, (d, sz.lat + sz.rope), fan(d))
        w["kv_norm"] = 1.0 + _n(key, 32, (sz.lat,), 0.1)
        w["wkvb"] = _n(key, 33, (sz.lat, H * (sz.nope + sz.dv)), fan(sz.lat))
        w["wo"] = _n(key, 34, (H * sz.dv, d), out(H * sz.dv))
    if ffn == "dense":
        w["w_gate"] = _n(key, 40, (d, sz.F), fan(d))
        w["w_up"] = _n(key, 41, (d, sz.F), fan(d))
        w["w_down"] = _n(key, 42, (sz.F, d), out(sz.F))
    else:
        Fe, Fs = sz.Fe, sz.shared * sz.Fe
        w["router"] = _n(key, 50, (d, sz.E), fan(d))
        # Small against the scores' spread (0.2): the published bias keeps the
        # experts' loads even, and a random one of 0.05 moves an expert's
        # share 2.5x a sigma, a rank's load 0.75x-1.45x from seed to seed.
        w["router_bias"] = _n(key, 51, (sz.E,), 0.01)
        # Expert e's values depend on e alone, whichever experts are held.
        ek = jax.random.fold_in(key, 52)
        ids = sz.held_first + jnp.arange(sz.held)
        one = lambda i, shape, std: jax.vmap(
            lambda e: _n(jax.random.fold_in(ek, e), i, shape, std))(ids)
        w["e_gate"] = one(0, (d, Fe), fan(d))
        w["e_up"] = one(1, (d, Fe), fan(d))
        w["e_down"] = one(2, (Fe, d), out(Fe))
        w["s_gate"] = _n(key, 53, (d, Fs), fan(d))
        w["s_up"] = _n(key, 54, (d, Fs), fan(d))
        w["s_down"] = _n(key, 55, (Fs, d), out(Fs))
    return w


def top(key, sz: HybridSizes) -> Dict[str, jax.Array]:
    """Embedding, untied head and final norm, float32."""
    k = jax.random.fold_in(key, 2)
    return {"embed": _n(k, 0, (sz.V, sz.d), 0.02),
            "final_norm": 1.0 + _n(k, 1, (sz.d,), 0.1),
            "lm_head": _n(k, 2, (sz.d, sz.V), 0.02)}


def to_program(w: Dict[str, jax.Array], sz: HybridSizes,
               kind: Tuple[str, str]) -> Dict[str, jax.Array]:
    """One layer from the plain layout to the program's (leaf names and
    shapes of models/transformer.py `_mixed_layer_shapes`)."""
    mixer, ffn = kind
    d = sz.d
    p = {"attn_norm": w["attn_norm"], "mlp_norm": w["mlp_norm"]}
    if mixer == "kda":
        H, hd = sz.kda_H, sz.kda_hd
        for n in ("q", "k", "v"):
            p["kda_w" + n] = w["w" + n].reshape(d, H, hd)
            p["kda_conv_" + n] = w["conv_" + n].reshape(sz.conv, H, hd)
        for n in ("f", "g"):
            p[f"kda_w{n}1"] = w[f"w{n}1"]
            p[f"kda_w{n}2"] = w[f"w{n}2"].reshape(sz.rank, H, hd)
        p["kda_A_log"] = w["A_log"]
        p["kda_dt_bias"] = w["dt_bias"].reshape(H, hd)
        p["kda_wb"] = w["wb"]
        p["kda_o_norm"] = w["o_norm"]
        p["kda_wo"] = w["wo"].reshape(H, hd, d)
    else:
        H = sz.H
        p["mla_wq"] = w["wq"].reshape(d, H, sz.nope + sz.rope)
        p["mla_wkva"] = w["wkva"]
        p["mla_kv_norm"] = w["kv_norm"]
        p["mla_wkvb"] = w["wkvb"].reshape(sz.lat, H, sz.nope + sz.dv)
        p["mla_wo"] = w["wo"].reshape(H, sz.dv, d)
    if ffn == "dense":
        p["w_gate_up"] = jnp.stack([w["w_gate"], w["w_up"]], axis=1)
        p["w_down"] = w["w_down"]
    else:
        p["router"], p["router_bias"] = w["router"], w["router_bias"]
        p["moe_w_gate_up"] = jnp.stack([w["e_gate"], w["e_up"]], axis=2)
        p["moe_w_down"] = w["e_down"]
        p["shared_w_gate_up"] = jnp.stack([w["s_gate"], w["s_up"]], axis=1)
        p["shared_w_down"] = w["s_down"]
    return p


def program_params(key, sz: HybridSizes, cfg, param_dtype=jnp.float32):
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
