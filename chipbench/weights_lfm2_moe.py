"""Seed -> weights of the gated-short-convolution / GQA expert stack (LFM2-MoE,
`model_type: lfm2_moe`: most layers a double-gated short convolution, every
fourth or so GQA attention with q / k norms; the leading layers a dense
SwiGLU, the others sigmoid-routed experts with a selection bias and none
shared; a tied head). As weights_qwen3_next.py: `layer(key, sz, kind)` is
the one definition of a layer's values, float32, in the plain layout the
reference uses (x @ W): the convolution layer's `win` [d, Bg ; Cg ; x] as
the equations of reference/lfm2_moe.py write them, its taps `conv` [K, d]
(`conv[K - 1]` meets the current token: a checkpoint's depthwise
`conv.weight` [d, 1, K] is this transposed), `wout`; the attention's `wq`,
`wk`, `wv`, `wo` and one q / k norm weight a head width. `program_params`
lays the same values out as ray_tpu.models.transformer holds the stack (a
list of segments; Bg, Cg, x and k, v and gate, up as array dims). The
reference makes a layer again from the seed alone.

Scales: 1/sqrt(fan-in), output projections 1/sqrt(2 L fan-in), norm weights
1 + 0.1 n (plain: the factor itself), the router's selection bias N(0, 0.01)
(a buffer: no gradient, no update between steps here), the tied table
N(0, 0.02): it is the head too, and a head's scale decides the loss. With it
a token's residual is its first layers' outputs, which are functions of the
token's last three ids through the convolution and nothing all tokens share
(no attention before the first router but one whose mean value is a
thousandth of the residual), so the held range's share of the assignments
holds near its even share on every seed (the cell's `moe_load_mean`).

An expert's values depend on the key and on its number among ALL the layer's
experts, so the four ranks of one expert-parallel group make disjoint
experts and the same router and mixers from the same seed
(tests/test_expert_shares.py adds their parts)."""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from chipbench.weights import _n, layer_key

EMBED_STD = 0.02
TAPS = 3  # `conv_L_cache`: the program has the one count too


class Lfm2Sizes:
    """The numbers of the configuration file's `transformer_config`."""

    def __init__(self, tc: Dict[str, Any], norm_eps: float):
        self.V, self.d, self.L = tc["vocab_size"], tc["d_model"], tc["n_layers"]
        self.H, self.KVH, self.F = tc["n_heads"], tc["n_kv_heads"], tc["d_ff"]
        self.hd = self.d // self.H
        self.norm_eps = float(norm_eps)
        self.theta = float(tc["rope_theta"])
        self.K = TAPS
        self.E, self.k = tc["moe_num_experts"], tc["moe_experts_per_token"]
        self.held_first, self.held = tc.get("moe_held") or (0, self.E)
        self.Fe = tc["moe_d_ff"]
        self.routed_scale = float(tc.get("moe_routed_scale", 1.0))
        conv = set(tc["shortconv_layers"])
        self.kinds: List[Tuple[str, str]] = [
            ("shortconv" if l + 1 in conv else "attn",
             "dense" if l < tc["moe_first_dense"] else "moe")
            for l in range(self.L)]
        # The layers whose gradient leaves the check compares (None: the
        # stack has no such layer, as a one-layer test stack).
        where = lambda f: [l for l, k in enumerate(self.kinds) if f(k)]
        conv = where(lambda k: k[0] == "shortconv")
        self.l_conv = min(conv, default=None)
        self.l_conv_last = max(conv, default=None)
        self.l_attn = min(where(lambda k: k[0] == "attn"), default=None)
        self.l_dense = min(where(lambda k: k[1] == "dense"), default=None)
        # the attention layer's experts where it has them, else the first
        moe = where(lambda k: k[1] == "moe")
        self.l_moe = (self.l_attn if self.l_attn in moe
                      else min(moe, default=None))
        self.e_pick = self.held // 2  # a held expert, local number


def sizes_of(config: Dict[str, Any], rehearse: bool) -> Lfm2Sizes:
    """A configuration file's sizes, at its tiny preset for a rehearsal."""
    tc = dict(config["transformer_config"])
    if rehearse:
        tc.update(config["rehearsal"]["transformer_config"])
    return Lfm2Sizes(tc, config["norm_eps"])


def layer(key, sz: Lfm2Sizes, kind: Tuple[str, str]) -> Dict[str, jax.Array]:
    """One layer's weights from its own key, float32, plain layout."""
    d, L = sz.d, sz.L
    fan = lambda n: 1 / math.sqrt(n)
    out = lambda n: 1 / math.sqrt(2 * L * n)
    w = {"attn_norm": 1.0 + _n(key, 0, (d,), 0.1),
         "mlp_norm": 1.0 + _n(key, 1, (d,), 0.1)}
    if kind[0] == "shortconv":
        w["win"] = _n(key, 10, (d, 3 * d), fan(d))      # [Bg ; Cg ; x]
        w["conv"] = _n(key, 11, (sz.K, d), fan(sz.K))
        w["wout"] = _n(key, 12, (d, d), out(d))
    else:
        q, kv = sz.H * sz.hd, sz.KVH * sz.hd
        w["wq"] = _n(key, 20, (d, q), fan(d))
        w["wk"] = _n(key, 21, (d, kv), fan(d))
        w["wv"] = _n(key, 22, (d, kv), fan(d))
        w["q_norm"] = 1.0 + _n(key, 23, (sz.hd,), 0.1)
        w["k_norm"] = 1.0 + _n(key, 24, (sz.hd,), 0.1)
        w["wo"] = _n(key, 25, (q, d), out(q))
    if kind[1] == "dense":
        w["w_gate"] = _n(key, 30, (d, sz.F), fan(d))
        w["w_up"] = _n(key, 31, (d, sz.F), fan(d))
        w["w_down"] = _n(key, 32, (sz.F, d), out(sz.F))
        return w
    Fe = sz.Fe
    w["router"] = _n(key, 50, (d, sz.E), fan(d))
    w["router_bias"] = _n(key, 51, (sz.E,), 0.01)
    # Expert e's values depend on e alone, whichever experts are held.
    ek = jax.random.fold_in(key, 52)
    ids = sz.held_first + jnp.arange(sz.held)
    one = lambda i, shape, std: jax.vmap(
        lambda e: _n(jax.random.fold_in(ek, e), i, shape, std))(ids)
    w["e_gate"] = one(0, (d, Fe), fan(d))
    w["e_up"] = one(1, (d, Fe), fan(d))
    w["e_down"] = one(2, (Fe, d), out(Fe))
    return w


def top(key, sz: Lfm2Sizes) -> Dict[str, jax.Array]:
    """The tied embedding / head and the final norm, float32."""
    k = jax.random.fold_in(key, 2)
    return {"embed": _n(k, 0, (sz.V, sz.d), EMBED_STD),
            "final_norm": 1.0 + _n(k, 1, (sz.d,), 0.1)}


def to_program(w: Dict[str, jax.Array], sz: Lfm2Sizes,
               kind: Tuple[str, str]) -> Dict[str, jax.Array]:
    """One layer from the plain layout to the program's: leaf names and
    shapes of models/transformer.py."""
    d = sz.d
    p = {"attn_norm": w["attn_norm"], "mlp_norm": w["mlp_norm"]}
    if kind[0] == "shortconv":
        p["shortconv_win"] = w["win"].reshape(d, 3, d)
        p["shortconv_conv"] = w["conv"]
        p["shortconv_wout"] = w["wout"]
    else:
        p["wq"] = w["wq"].reshape(d, sz.H, sz.hd)
        p["wkv"] = jnp.stack([w["wk"].reshape(d, sz.KVH, sz.hd),
                              w["wv"].reshape(d, sz.KVH, sz.hd)], 1)
        p["q_norm"], p["k_norm"] = w["q_norm"], w["k_norm"]
        p["wo"] = w["wo"]
    if kind[1] == "dense":
        p["w_gate_up"] = jnp.stack([w["w_gate"], w["w_up"]], axis=1)
        p["w_down"] = w["w_down"]
        return p
    p["router"], p["router_bias"] = w["router"], w["router_bias"]
    p["moe_w_gate_up"] = jnp.stack([w["e_gate"], w["e_up"]], axis=2)
    p["moe_w_down"] = w["e_down"]
    return p


def program_params(key, sz: Lfm2Sizes, cfg, param_dtype=jnp.float32):
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


def program_leaves(cfg, sz: Lfm2Sizes, g) -> Dict[str, jax.Array]:
    """The compared gradient leaves from the program's gradient tree, in
    the reference's plain layout (`reference/lfm2_moe.zero_delta`): the
    final norm; the first convolution layer's joint projection, output
    projection and taps; the last convolution layer's taps; the attention
    layer's query and output projections and q-norm weight; the dense
    layer's down projection; a held expert's down projection and the router
    of the attention layer."""
    from ray_tpu.models.transformer import layer_params

    d = sz.d
    out = {"final_norm": g["final_norm"]}
    if sz.l_conv is not None:
        p = layer_params(g, cfg, sz.l_conv)
        out["conv_win"] = p["shortconv_win"].reshape(d, 3 * d)
        out["conv_wout"] = p["shortconv_wout"]
        out["conv_taps"] = p["shortconv_conv"]
        out["conv_taps_last"] = layer_params(
            g, cfg, sz.l_conv_last)["shortconv_conv"]
    if sz.l_attn is not None:
        p = layer_params(g, cfg, sz.l_attn)
        out["attn_wq"] = p["wq"].reshape(d, -1)
        out["attn_wo"] = p["wo"]
        out["attn_q_norm"] = p["q_norm"]
    if sz.l_dense is not None:
        out["w_down"] = layer_params(g, cfg, sz.l_dense)["w_down"]
    if sz.l_moe is not None:
        moe = layer_params(g, cfg, sz.l_moe)
        out["expert_down"] = moe["moe_w_down"][sz.e_pick]
        out["router"] = moe["router"]
    return out
