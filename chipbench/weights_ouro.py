"""Seed -> weights of the looped dense stack (ByteDance/Ouro-2.6B, `ouro`:
one stack of full-attention SwiGLU layers applied `total_ut_steps` times a
step over the same weights, two norms round every sublayer, an untied head
and a scalar exit gate reading every pass). As weights_granite_hybrid.py:
`layer(key, sz)` is the one definition of a layer's values, float32, in the
plain layout the reference uses (x @ W; `wq`, `wk`, `wv`, `w_gate`, `w_up`
matrices of their own); `program_params` lays the same values out as
ray_tpu.models.transformer holds them (one dict of leaves [L, ...], q / k /
v and gate / up fused over an array dim) inside one jitted call with the key
an argument. The reference makes a layer again from the seed alone.

Scales: 1/sqrt(fan-in), output projections 1/sqrt(2 L fan-in), all four
norms of a layer and the final norm 1 + 0.1 n, the embedding N(0, 1)
(`EMBED_STD`, as the other stack references), the head N(0, 0.02), the gate's
weight N(0, 1/sqrt(d)) and its bias one N(0, 0.5) draw, so that the passes'
exit masses differ and the bias is seen.

`program_leaves` picks the gradient leaves the check compares out of the
program's gradient tree, in the plain layout; `zero_delta` of the reference
has the same names."""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.weights import _n, layer_key

EMBED_STD = 1.0
HEAD_ROWS = 2048  # vocabulary rows of `lm_head` whose gradient is compared


class OuroSizes:
    """The numbers of the configuration file's `transformer_config`."""

    def __init__(self, tc: Dict[str, Any], norm_eps: float):
        self.V, self.d, self.L = tc["vocab_size"], tc["d_model"], tc["n_layers"]
        self.H = tc["n_heads"]
        self.KVH = tc.get("n_kv_heads") or self.H
        self.hd = tc.get("attn_head_dim") or self.d // self.H
        self.F = tc["d_ff"]
        self.T = tc["loop_steps"]
        self.beta = float(tc["exit_entropy_coef"])
        self.rope_theta = float(tc["rope_theta"])
        self.norm_eps = float(norm_eps)
        self.head_rows = min(HEAD_ROWS, self.V)
        if self.KVH != self.H:
            raise ValueError("the looped stack's reference is written for "
                             "as many key heads as query heads")


def sizes_of(config: Dict[str, Any], rehearse: bool) -> OuroSizes:
    """A configuration file's sizes, at its tiny preset for a rehearsal."""
    tc = dict(config["transformer_config"])
    if rehearse:
        tc.update(config["rehearsal"]["transformer_config"])
    return OuroSizes(tc, config["norm_eps"])


def layer(key, sz: OuroSizes) -> Dict[str, jax.Array]:
    """One layer's weights from its own key, float32, plain layout."""
    d, L, q = sz.d, sz.L, sz.H * sz.hd
    fan = lambda n: 1 / math.sqrt(n)
    out = lambda n: 1 / math.sqrt(2 * L * n)
    return {"attn_norm": 1.0 + _n(key, 0, (d,), 0.1),
            "attn_post_norm": 1.0 + _n(key, 1, (d,), 0.1),
            "mlp_norm": 1.0 + _n(key, 2, (d,), 0.1),
            "mlp_post_norm": 1.0 + _n(key, 3, (d,), 0.1),
            "wq": _n(key, 10, (d, q), fan(d)),
            "wk": _n(key, 11, (d, q), fan(d)),
            "wv": _n(key, 12, (d, q), fan(d)),
            "wo": _n(key, 13, (q, d), out(q)),
            "w_gate": _n(key, 20, (d, sz.F), fan(d)),
            "w_up": _n(key, 21, (d, sz.F), fan(d)),
            "w_down": _n(key, 22, (sz.F, d), out(sz.F))}


def top(key, sz: OuroSizes) -> Dict[str, jax.Array]:
    """Embedding, untied head, final norm and the exit gate, float32."""
    k = jax.random.fold_in(key, 2)
    return {"embed": _n(k, 0, (sz.V, sz.d), EMBED_STD),
            "final_norm": 1.0 + _n(k, 1, (sz.d,), 0.1),
            "lm_head": _n(k, 2, (sz.d, sz.V), 0.02),
            "exit_gate_w": _n(k, 3, (sz.d,), 1 / math.sqrt(sz.d)),
            "exit_gate_b": _n(k, 4, (1,), 0.5)}


def to_program(w: Dict[str, jax.Array], sz: OuroSizes) -> Dict[str, jax.Array]:
    """One layer from the plain layout to the program's (leaf names and
    shapes of models/transformer.py `_layer_shapes`)."""
    heads = lambda a: a.reshape(sz.d, sz.H, sz.hd)
    p = {n: w[n] for n in ("attn_norm", "attn_post_norm", "mlp_norm",
                           "mlp_post_norm", "wo", "w_down")}
    p["wqkv"] = jnp.stack([heads(w["wq"]), heads(w["wk"]), heads(w["wv"])], 1)
    p["w_gate_up"] = jnp.stack([w["w_gate"], w["w_up"]], axis=1)
    return p


def program_params(key, sz: OuroSizes, cfg, param_dtype=jnp.float32):
    """The same values as the program holds them (`cfg`, the program's
    TransformerConfig, is not asked anything: a stack of one kind of softmax
    attention layers is one dict of leaves [L, ...]). A program without the
    loop (the parent of PR 49) has failed on the configuration's
    `loop_steps` before it gets here."""
    params = dict(top(key, sz))
    params["layers"] = jax.vmap(lambda l: to_program(
        layer(layer_key(key, l), sz), sz))(jnp.arange(sz.L))
    return jax.tree.map(lambda a: a.astype(param_dtype), params)


def program_leaves(cfg, sz: OuroSizes, g) -> Dict[str, jax.Array]:
    """The compared gradient leaves from the program's gradient tree, in the
    reference's plain layout (`reference/ouro.zero_delta`): the final norm
    and the gate's weight (read by every pass), `head_rows` vocabulary rows
    of the head (four products), the last layer's output and down
    projections and its two post-norms, the first layer's query projection
    (the deepest path: every later application of every layer lies behind
    it)."""
    from ray_tpu.models.transformer import layer_params

    first, last = layer_params(g, cfg, 0), layer_params(g, cfg, sz.L - 1)
    return {"final_norm": g["final_norm"], "gate_w": g["exit_gate_w"],
            "lm_head_rows": g["lm_head"][:, :sz.head_rows],
            "wo_last": last["wo"], "w_down_last": last["w_down"],
            "attn_post_norm_last": last["attn_post_norm"],
            "mlp_post_norm_last": last["mlp_post_norm"],
            "wq_first": first["wqkv"][:, 0].reshape(sz.d, -1)}
