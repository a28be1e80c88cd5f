"""Seed -> weights of the learned-sparse-attention expert stack (Keye-VL-2.0's
language model: GQA attention with q / k norms and M-RoPE in every layer, a
lightning indexer beside it, softmax-routed experts with none shared, an
untied head). As weights_mellum2.py: `layer(key, sz)` is the one definition
of a layer's values, float32, in the plain layout the reference uses (x @ W;
q, k, v, o matrices of their own; the indexer's query, key and weight
projections; the held experts' gate, up and down stacked over the expert);
`program_params` lays the same values out as ray_tpu.models.transformer holds
the stack. The reference makes a layer again from the seed alone.

An expert's values are weights_mellum2.py's (they depend on the key and on
its number among ALL the layer's experts, so the eight ranks of a layer make
disjoint experts and the same router from the same seed;
tests/test_expert_shares.py adds their parts), as are the scales: 1/sqrt(fan-in),
output projections 1/sqrt(2 L fan-in), norms 1 + 0.1 n, the head N(0, 0.02),
the embedding N(0, 1) (weights_mellum2.py says why). The indexer: W_Iq, W_Ik
and W_Iw 1/sqrt(d), the key's LayerNorm 1 + 0.1 n with a bias of 0.1 n.

`program_leaves` picks the gradient leaves the check compares out of the
program's gradient tree, in the plain layout; `zero_delta` of the reference
has the same names."""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from chipbench.weights import _n, layer_key
from chipbench.weights_mellum2 import EMBED_STD, expert


class KeyeSizes:
    """The numbers of the configuration file's `transformer_config`."""

    def __init__(self, tc: Dict[str, Any], norm_eps: float):
        self.V, self.d, self.L = tc["vocab_size"], tc["d_model"], tc["n_layers"]
        self.H, self.KVH = tc["n_heads"], tc["n_kv_heads"]
        self.hd = tc["attn_head_dim"]
        self.norm_eps = float(norm_eps)
        self.theta = float(tc["rope_theta"])
        self.sections = tuple(tc["rope_sections"])
        self.HI, self.dI = tc["dsa_index_heads"], tc["dsa_index_head_dim"]
        self.topk = tc["dsa_topk"]
        self.E, self.k = tc["moe_num_experts"], tc["moe_experts_per_token"]
        self.held_first, self.held = tc.get("moe_held") or (0, self.E)
        self.Fe = tc["moe_d_ff"]
        self.kinds: List[Tuple[str, str]] = [("dsa", "moe")] * self.L
        # The layers whose gradient leaves the check compares: the first
        # (its gradient crosses the whole depth) and, of the indexer, the
        # last too (its q, k and lse have the most rounding behind them).
        self.l_first, self.l_last = 0, self.L - 1
        self.e_pick = self.held // 2  # a held expert, local number


def sizes_of(config: Dict[str, Any], rehearse: bool) -> KeyeSizes:
    """A configuration file's sizes, at its tiny preset for a rehearsal."""
    tc = dict(config["transformer_config"])
    if rehearse:
        tc.update(config["rehearsal"]["transformer_config"])
    return KeyeSizes(tc, config["norm_eps"])


def layer(key, sz: KeyeSizes) -> Dict[str, jax.Array]:
    """One layer's weights from its own key, float32, plain layout; of the
    experts, the held range only."""
    d = sz.d
    fan = lambda n: 1 / math.sqrt(n)
    q, kv = sz.H * sz.hd, sz.KVH * sz.hd
    w = {"attn_norm": 1.0 + _n(key, 0, (d,), 0.1),
         "mlp_norm": 1.0 + _n(key, 1, (d,), 0.1),
         "q_norm": 1.0 + _n(key, 2, (sz.hd,), 0.1),
         "k_norm": 1.0 + _n(key, 3, (sz.hd,), 0.1),
         "wq": _n(key, 30, (d, q), fan(d)),
         "wk": _n(key, 31, (d, kv), fan(d)),
         "wv": _n(key, 32, (d, kv), fan(d)),
         "wo": _n(key, 33, (q, d), 1 / math.sqrt(2 * sz.L * q)),
         "index_wq": _n(key, 34, (d, sz.HI * sz.dI), fan(d)),
         "index_wk": _n(key, 35, (d, sz.dI), fan(d)),
         "index_ww": _n(key, 36, (d, sz.HI), fan(d)),
         "index_k_norm": 1.0 + _n(key, 37, (sz.dI,), 0.1),
         "index_k_norm_b": _n(key, 38, (sz.dI,), 0.1),
         "router": _n(key, 40, (d, sz.E), fan(d))}
    w.update(jax.vmap(lambda e: expert(key, e, sz))(
        sz.held_first + jnp.arange(sz.held)))
    return w


def top(key, sz: KeyeSizes) -> Dict[str, jax.Array]:
    """Embedding, untied head and final norm, float32."""
    k = jax.random.fold_in(key, 2)
    return {"embed": _n(k, 0, (sz.V, sz.d), EMBED_STD),
            "final_norm": 1.0 + _n(k, 1, (sz.d,), 0.1),
            "lm_head": _n(k, 2, (sz.d, sz.V), 0.02)}


def to_program(w: Dict[str, jax.Array], sz: KeyeSizes) -> Dict[str, jax.Array]:
    """One layer from the plain layout to the program's (leaf names and
    shapes of models/transformer.py `_layer_shapes`)."""
    d = sz.d
    return {"attn_norm": w["attn_norm"], "mlp_norm": w["mlp_norm"],
            "q_norm": w["q_norm"], "k_norm": w["k_norm"],
            "wq": w["wq"].reshape(d, sz.H, sz.hd),
            "wkv": jnp.stack([w["wk"].reshape(d, sz.KVH, sz.hd),
                              w["wv"].reshape(d, sz.KVH, sz.hd)], axis=1),
            "wo": w["wo"],
            "dsa_wq": w["index_wq"].reshape(d, sz.HI, sz.dI),
            "dsa_wk": w["index_wk"], "dsa_ww": w["index_ww"],
            "dsa_k_norm": w["index_k_norm"],
            "dsa_k_norm_b": w["index_k_norm_b"],
            "router": w["router"],
            "moe_w_gate_up": jnp.stack([w["e_gate"], w["e_up"]], axis=2),
            "moe_w_down": w["e_down"]}


def program_params(key, sz: KeyeSizes, cfg, param_dtype=jnp.float32):
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


def program_leaves(cfg, sz: KeyeSizes, g) -> Dict[str, jax.Array]:
    """The compared gradient leaves from the program's gradient tree, in
    the reference's plain layout (`reference/keye_vl2.zero_delta`)."""
    from ray_tpu.models.transformer import layer_params

    first, last = (layer_params(g, cfg, l) for l in (sz.l_first, sz.l_last))
    return {"final_norm": g["final_norm"],
            "wo": first["wo"],
            "wq": first["wq"].reshape(sz.d, -1),
            "wkv": first["wkv"].reshape(sz.d, -1),  # [d, k | v]
            "q_norm": first["q_norm"],
            "index_wq": first["dsa_wq"].reshape(sz.d, -1),
            "index_wk": first["dsa_wk"],
            "index_ww": first["dsa_ww"],
            "index_wq_last": last["dsa_wq"].reshape(sz.d, -1),
            "expert_down": first["moe_w_down"][sz.e_pick],
            "router": first["router"]}
