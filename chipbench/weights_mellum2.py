"""Seed -> weights of the sliding-window / full-attention expert stack
(Mellum2: GQA attention in every layer, three of four windowed, softmax-routed
experts with none shared, an untied head). As weights_granite_hybrid.py:
`layer(key, sz)` is the one definition of a layer's values, float32, in the
plain layout the reference uses (x @ W; q, k, v, o matrices of their own; the
held experts' gate, up and down stacked over the expert); `program_params`
lays the same values out as ray_tpu.models.transformer holds the stack (a list
of segments, heads and the k/v and gate/up pairs as array dims) inside one
jitted call with the key an argument. The reference makes a layer again from
the seed alone. Every layer has the same leaves: what differs between a
windowed and a full layer is the mask and the rotation, not a weight.

An expert's values depend on the key and on its number among ALL the layer's
experts, so the ranks of one expert-parallel group make disjoint experts and
the same router from the same seed (tests/test_mellum2.py adds their parts).

Scales: 1/sqrt(fan-in), output projections 1/sqrt(2 L fan-in), norms
1 + 0.1 n, the head N(0, 0.02); the embedding N(0, 1) (`EMBED_STD`). With
random weights an attention layer's output is in good part the mean value of
its window, a vector every token shares; beside an embedding row of norm 0.96
(N(0, 0.02) at d 2304) it decides which experts are popular, and the held
range's share of the assignments then swings with the seed (0.18-0.48 of a
layer's, 0.25 being even; CPU probe and chip runs of PR 33, PERF.md section 6),
and the step's time with it. At N(0, 1) the token's own row decides: 0.245-0.254
a layer on every seed, which is also what a trained router's balancing holds.

`program_leaves` picks the gradient leaves the check compares out of the
program's gradient tree, in the plain layout; `zero_delta` of the reference
has the same names."""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from chipbench.weights import _n, layer_key


EMBED_STD = 1.0


class MellumSizes:
    """The numbers of the configuration file's `transformer_config`."""

    def __init__(self, tc: Dict[str, Any], norm_eps: float):
        self.V, self.d, self.L = tc["vocab_size"], tc["d_model"], tc["n_layers"]
        self.H, self.KVH = tc["n_heads"], tc["n_kv_heads"]
        self.hd = tc["attn_head_dim"]
        self.norm_eps = float(norm_eps)
        self.theta = float(tc["rope_theta"])
        self.window = tc["sliding_window"]
        self.yarn = (float(tc["yarn_factor"]), tc["yarn_original_len"],
                     float(tc["yarn_beta_fast"]), float(tc["yarn_beta_slow"]),
                     float(tc["yarn_attn_factor"]))
        self.E, self.k = tc["moe_num_experts"], tc["moe_experts_per_token"]
        self.held_first, self.held = tc.get("moe_held") or (0, self.E)
        self.Fe = tc["moe_d_ff"]
        swa = set(tc["swa_layers"])
        self.kinds: List[Tuple[str, str]] = [
            ("swa" if l + 1 in swa else "attn", "moe") for l in range(self.L)]
        # The layers whose gradient leaves the check compares (None: the
        # stack has no such layer, as a one-layer test stack).
        where = lambda what: [l for l, k in enumerate(self.kinds)
                              if k[0] == what]
        self.l_full = min(where("attn"), default=None)
        self.l_swa = min(where("swa"), default=None)
        self.l_moe = 0
        self.e_pick = self.held // 2  # a held expert, local number


def sizes_of(config: Dict[str, Any], rehearse: bool) -> MellumSizes:
    """A configuration file's sizes, at its tiny preset for a rehearsal."""
    tc = dict(config["transformer_config"])
    if rehearse:
        tc.update(config["rehearsal"]["transformer_config"])
    return MellumSizes(tc, config["norm_eps"])


def expert(key, e, sz: MellumSizes) -> Dict[str, jax.Array]:
    """Expert `e` (its number among all E) of the layer made from `key`."""
    k = jax.random.fold_in(jax.random.fold_in(key, 50), e)
    return {"e_gate": _n(k, 0, (sz.d, sz.Fe), 1 / math.sqrt(sz.d)),
            "e_up": _n(k, 1, (sz.d, sz.Fe), 1 / math.sqrt(sz.d)),
            "e_down": _n(k, 2, (sz.Fe, sz.d),
                         1 / math.sqrt(2 * sz.L * sz.Fe))}


def layer(key, sz: MellumSizes) -> Dict[str, jax.Array]:
    """One layer's weights from its own key, float32, plain layout; of the
    experts, the held range only."""
    d = sz.d
    fan = lambda n: 1 / math.sqrt(n)
    q, kv = sz.H * sz.hd, sz.KVH * sz.hd
    w = {"attn_norm": 1.0 + _n(key, 0, (d,), 0.1),
         "mlp_norm": 1.0 + _n(key, 1, (d,), 0.1),
         "wq": _n(key, 30, (d, q), fan(d)),
         "wk": _n(key, 31, (d, kv), fan(d)),
         "wv": _n(key, 32, (d, kv), fan(d)),
         "wo": _n(key, 33, (q, d), 1 / math.sqrt(2 * sz.L * q)),
         "router": _n(key, 40, (d, sz.E), fan(d))}
    w.update(jax.vmap(lambda e: expert(key, e, sz))(
        sz.held_first + jnp.arange(sz.held)))
    return w


def top(key, sz: MellumSizes) -> Dict[str, jax.Array]:
    """Embedding, untied head and final norm, float32."""
    k = jax.random.fold_in(key, 2)
    return {"embed": _n(k, 0, (sz.V, sz.d), EMBED_STD),
            "final_norm": 1.0 + _n(k, 1, (sz.d,), 0.1),
            "lm_head": _n(k, 2, (sz.d, sz.V), 0.02)}


def to_program(w: Dict[str, jax.Array], sz: MellumSizes
               ) -> Dict[str, jax.Array]:
    """One layer from the plain layout to the program's (leaf names and
    shapes of models/transformer.py `_layer_shapes`)."""
    d = sz.d
    return {"attn_norm": w["attn_norm"], "mlp_norm": w["mlp_norm"],
            "wq": w["wq"].reshape(d, sz.H, sz.hd),
            "wkv": jnp.stack([w["wk"].reshape(d, sz.KVH, sz.hd),
                              w["wv"].reshape(d, sz.KVH, sz.hd)], axis=1),
            "wo": w["wo"], "router": w["router"],
            "moe_w_gate_up": jnp.stack([w["e_gate"], w["e_up"]], axis=2),
            "moe_w_down": w["e_down"]}


def program_params(key, sz: MellumSizes, cfg, param_dtype=jnp.float32):
    """The same values as the program holds them: `cfg` is the program's
    TransformerConfig, whose `stack_plan()` says how layers are grouped."""
    segments, l = [], 0
    for pattern, r in cfg.stack_plan():
        seg = []
        for pos in range(len(pattern)):
            ids = jnp.asarray([l + pos + i * len(pattern) for i in range(r)])
            seg.append(jax.vmap(lambda i: to_program(
                layer(layer_key(key, i), sz), sz))(ids))
        segments.append(seg)
        l += len(pattern) * r
    params = dict(top(key, sz))
    params["layers"] = segments
    return jax.tree.map(lambda a: a.astype(param_dtype), params)


def program_leaves(cfg, sz: MellumSizes, g) -> Dict[str, jax.Array]:
    """The compared gradient leaves from the program's gradient tree, in
    the reference's plain layout (`reference/mellum2.zero_delta`)."""
    from ray_tpu.models.transformer import layer_params

    moe = layer_params(g, cfg, sz.l_moe)
    out = {"final_norm": g["final_norm"],
           "expert_down": moe["moe_w_down"][sz.e_pick],
           "router": moe["router"]}
    if sz.l_full is not None:
        full = layer_params(g, cfg, sz.l_full)
        out["full_wo"] = full["wo"]
        out["full_wq"] = full["wq"].reshape(sz.d, -1)
    if sz.l_swa is not None:
        swa = layer_params(g, cfg, sz.l_swa)
        out["swa_wo"] = swa["wo"]
        out["swa_wkv"] = swa["wkv"].reshape(sz.d, -1)  # [d, k | v]
    return out
