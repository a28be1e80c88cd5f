"""Seed -> weights of the mixed-head window / full attention expert stack
(Laguna-S-2.1, `model_type: laguna`: GQA attention in every layer, three of
four under a window with MORE query heads than the full layers have over the
same key heads, a sigmoid gate a head on every attention output, a dense
lead layer, sigmoid-routed experts beside one shared expert, an untied
head). As weights_mellum2.py: `layer(key, sz, kind)` is the one definition
of a layer's values, float32, in the plain layout the reference uses (x @ W;
q, k, v, o and the gate matrices of their own; the held experts' gate, up
and down stacked over the expert); `program_params` lays the same values out
as ray_tpu.models.transformer holds the stack inside one jitted call with
the key an argument. The reference makes a layer again from the seed alone.

Two things are held here in part, and neither may move a value:
- an expert's values depend on the key and on its number among ALL the
  layer's experts (weights_kimi_linear.py's rule);
- a HEAD's values (its columns of W_q, W_k, W_v and W_g, its rows of W_o)
  depend on the key and on its number among all the layer's published heads.
  `heads = (rank, ways)` says which this chip holds: of `ways` chips that
  share a layer's heads, chip `rank` has query heads rank H .. rank H + H - 1
  and key heads rank KVH .. rank KVH + KVH - 1 (H, KVH the numbers held:
  whole groups, so query head j of the chip is served by its key head
  j // (H / KVH), as in the whole model). The ranks of one group make
  disjoint heads from the same seed, and their partial W_o sums add up to
  the whole layer's (tests/test_laguna.py).

Scales: 1/sqrt(fan-in), output projections 1/sqrt(2 L fan-in) at the WHOLE
layer's fan-in (a share must not change a value), norms 1 + 0.1 n, the
selection bias N(0, 0.01), the head N(0, 0.02), the embedding N(0, 1)
(weights_mellum2.py says why: the token's own row then decides the routing
and the held range's load holds steady over the seeds).

`program_leaves` picks the gradient leaves the check compares out of the
program's gradient tree, in the plain layout; `zero_delta` of the reference
has the same names."""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from chipbench.weights import _n, layer_key

EMBED_STD = 1.0
# Small against the scores' spread (0.2): the published bias keeps the
# experts' loads even (weights_kimi_linear.py); a test that wants a top-k of
# unbiased scores to differ on a few dozen tokens draws it wider.
BIAS_STD = 0.01


class LagunaSizes:
    """The numbers of the configuration file's `transformer_config`, and
    which of the published heads they are (`heads`: the file's
    `heads_held`, {"rank", "ways"}; None: all of them)."""

    def __init__(self, tc: Dict[str, Any], norm_eps: float,
                 heads: Optional[Dict[str, int]] = None):
        self.V, self.d, self.L = tc["vocab_size"], tc["d_model"], tc["n_layers"]
        self.KVH, self.hd = tc["n_kv_heads"], tc["attn_head_dim"]
        self.F = tc["d_ff"]
        self.norm_eps = float(norm_eps)
        self.window = tc["sliding_window"]
        # query heads, theta and rotated columns a kind of layer
        self.H = {"attn": tc["n_heads"],
                  "swa": tc.get("swa_heads") or tc["n_heads"]}
        self.theta = {"attn": float(tc["rope_theta"]),
                      "swa": float(tc.get("swa_rope_theta")
                                   or tc["rope_theta"])}
        full = tc.get("rope_fraction", 1.0)
        swa = tc.get("swa_rope_fraction")
        self.rot = {"attn": int(self.hd * full),
                    "swa": int(self.hd * (full if swa is None else swa))}
        self.yarn = (float(tc["yarn_factor"]), tc["yarn_original_len"],
                     float(tc["yarn_beta_fast"]), float(tc["yarn_beta_slow"]),
                     float(tc["yarn_attn_factor"]))
        self.rank = (heads or {}).get("rank", 0)
        self.ways = (heads or {}).get("ways", 1)
        self.E, self.k = tc["moe_num_experts"], tc["moe_experts_per_token"]
        self.held_first, self.held = tc.get("moe_held") or (0, self.E)
        self.Fe = tc["moe_d_ff"]
        self.shared = tc["moe_shared_experts"]
        self.routed_scale = float(tc["moe_routed_scale"])
        swa_layers = set(tc["swa_layers"])
        self.kinds: List[Tuple[str, str]] = [
            ("swa" if l + 1 in swa_layers else "attn",
             "dense" if l < tc["moe_first_dense"] else "moe")
            for l in range(self.L)]
        # The layers whose gradient leaves the check compares (None: the
        # stack has no such layer, as a one-layer test stack).
        where = lambda i, what: [l for l, k in enumerate(self.kinds)
                                 if k[i] == what]
        self.l_full = max(where(0, "attn"), default=None)
        self.l_swa = min(where(0, "swa"), default=None)
        self.l_dense = min(where(1, "dense"), default=None)
        self.l_moe = min(where(1, "moe"), default=None)
        self.e_pick = self.held // 2  # a held expert, local number


def sizes_of(config: Dict[str, Any], rehearse: bool) -> LagunaSizes:
    """A configuration file's sizes, at its tiny preset for a rehearsal."""
    tc = dict(config["transformer_config"])
    if rehearse:
        tc.update(config["rehearsal"]["transformer_config"])
    return LagunaSizes(tc, config["norm_eps"], config.get("heads_held"))


def _by_number(key, i, ids, shape, std):
    """[len(ids), *shape]: one draw a number of `ids`, whichever are held."""
    k = jax.random.fold_in(key, i)
    return jax.vmap(lambda n: jax.random.normal(
        jax.random.fold_in(k, n), shape, jnp.float32) * std)(ids)


def layer(key, sz: LagunaSizes, kind: Tuple[str, str]) -> Dict[str, jax.Array]:
    """One layer's weights from its own key, float32, plain layout; of the
    heads and of the experts, the held ones only."""
    mixer, ffn = kind
    d, hd, L = sz.d, sz.hd, sz.L
    fan = lambda n: 1 / math.sqrt(n)
    out = lambda n: 1 / math.sqrt(2 * L * n)
    H, KVH = sz.H[mixer], sz.KVH
    qs = sz.rank * H + jnp.arange(H)      # the held query heads' numbers
    ks = sz.rank * KVH + jnp.arange(KVH)  # and the key / value heads'
    cols = lambda a: jnp.moveaxis(a, 0, 1).reshape(d, -1)  # [n,d,hd] -> [d,n hd]
    w = {"attn_norm": 1.0 + _n(key, 0, (d,), 0.1),
         "mlp_norm": 1.0 + _n(key, 1, (d,), 0.1),
         "wq": cols(_by_number(key, 30, qs, (d, hd), fan(d))),
         "wk": cols(_by_number(key, 31, ks, (d, hd), fan(d))),
         "wv": cols(_by_number(key, 32, ks, (d, hd), fan(d))),
         "wo": _by_number(key, 33, qs, (hd, d),
                          out(sz.ways * H * hd)).reshape(H * hd, d),
         "wg": _by_number(key, 34, qs, (d,), fan(d)).T}
    if ffn == "dense":
        w["w_gate"] = _n(key, 40, (d, sz.F), fan(d))
        w["w_up"] = _n(key, 41, (d, sz.F), fan(d))
        w["w_down"] = _n(key, 42, (sz.F, d), out(sz.F))
        return w
    Fe, Fs = sz.Fe, sz.shared * sz.Fe
    w["router"] = _n(key, 50, (d, sz.E), fan(d))
    w["router_bias"] = _n(key, 51, (sz.E,), BIAS_STD)
    es = sz.held_first + jnp.arange(sz.held)
    ek = jax.random.fold_in(key, 52)
    one = lambda i, shape, std: jax.vmap(
        lambda e: _n(jax.random.fold_in(ek, e), i, shape, std))(es)
    w["e_gate"] = one(0, (d, Fe), fan(d))
    w["e_up"] = one(1, (d, Fe), fan(d))
    w["e_down"] = one(2, (Fe, d), out(Fe))
    w["s_gate"] = _n(key, 53, (d, Fs), fan(d))
    w["s_up"] = _n(key, 54, (d, Fs), fan(d))
    w["s_down"] = _n(key, 55, (Fs, d), out(Fs))
    return w


def top(key, sz: LagunaSizes) -> Dict[str, jax.Array]:
    """Embedding, untied head and final norm, float32."""
    k = jax.random.fold_in(key, 2)
    return {"embed": _n(k, 0, (sz.V, sz.d), EMBED_STD),
            "final_norm": 1.0 + _n(k, 1, (sz.d,), 0.1),
            "lm_head": _n(k, 2, (sz.d, sz.V), 0.02)}


def to_program(w: Dict[str, jax.Array], sz: LagunaSizes,
               kind: Tuple[str, str]) -> Dict[str, jax.Array]:
    """One layer from the plain layout to the program's (leaf names and
    shapes of models/transformer.py `_layer_shapes`)."""
    d, H = sz.d, sz.H[kind[0]]
    p = {"attn_norm": w["attn_norm"], "mlp_norm": w["mlp_norm"],
         "wq": w["wq"].reshape(d, H, sz.hd),
         "wkv": jnp.stack([w["wk"].reshape(d, sz.KVH, sz.hd),
                           w["wv"].reshape(d, sz.KVH, sz.hd)], axis=1),
         "wo": w["wo"], "w_head_gate": w["wg"]}
    if kind[1] == "dense":
        p["w_gate_up"] = jnp.stack([w["w_gate"], w["w_up"]], axis=1)
        p["w_down"] = w["w_down"]
        return p
    p["router"], p["router_bias"] = w["router"], w["router_bias"]
    p["moe_w_gate_up"] = jnp.stack([w["e_gate"], w["e_up"]], axis=2)
    p["moe_w_down"] = w["e_down"]
    p["shared_w_gate_up"] = jnp.stack([w["s_gate"], w["s_up"]], axis=1)
    p["shared_w_down"] = w["s_down"]
    return p


def program_params(key, sz: LagunaSizes, cfg, param_dtype=jnp.float32):
    """The same values as the program holds them: `cfg` is the program's
    TransformerConfig, whose `stack_plan()` says how layers are grouped. A
    program whose windowed layers cannot have a head count of their own (the
    parent of PR 45) has failed on the configuration's `swa_heads` before it
    gets here."""
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


def program_leaves(cfg, sz: LagunaSizes, g) -> Dict[str, jax.Array]:
    """The compared gradient leaves from the program's gradient tree, in the
    reference's plain layout (`reference/laguna.zero_delta`): the last full
    layer's query, output and gate matrices, the first windowed layer's
    query, key | value, output and gate matrices, the dense layer's down
    projection, a held expert's and the router of the first expert layer."""
    from ray_tpu.models.transformer import layer_params

    out = {"final_norm": g["final_norm"]}
    for name, l in (("full", sz.l_full), ("swa", sz.l_swa)):
        if l is None:
            continue
        a = layer_params(g, cfg, l)
        out[name + "_wq"] = a["wq"].reshape(sz.d, -1)
        out[name + "_wo"] = a["wo"]
        out[name + "_gate"] = a["w_head_gate"]
        if name == "swa":
            out["swa_wkv"] = a["wkv"].reshape(sz.d, -1)  # [d, k | v]
    if sz.l_dense is not None:
        out["w_down"] = layer_params(g, cfg, sz.l_dense)["w_down"]
    if sz.l_moe is not None:
        moe = layer_params(g, cfg, sz.l_moe)
        out["expert_down"] = moe["moe_w_down"][sz.e_pick]
        out["router"] = moe["router"]
    return out
