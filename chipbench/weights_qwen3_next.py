"""Seed -> weights of the Gated DeltaNet / gated-attention expert stack
(Qwen3-Next, `model_type: qwen3_next`: of every four layers three are Gated
DeltaNet and the fourth gated softmax attention; every feed-forward
softmax-routed experts beside one shared expert behind a sigmoid gate; an
untied head). As weights_mellum2.py: `layer(key, sz, kind)` is the one
definition of a layer's values, float32, in the plain layout the reference
uses (x @ W): the DeltaNet's `wqkvz` [d, q ; k ; v ; z] and `wba` [d, b ; a]
as the equations of reference/qwen3_next.py write them, one convolution
weight over the channels of [q ; k ; v], the attention's `wq` [d, H x (256
query columns, then 256 gate columns)]. `program_params` lays the same
values out as ray_tpu.models.transformer holds the stack (a list of
segments; q and k, v and z, b and a, gate and up as array dims; the query
and its gate two leaves). The reference makes a layer again from the seed
alone. A published checkpoint interleaves `wqkvz` and `wba` a key head (a
key head's q, k, its two value heads' v and z side by side): that is a
permutation of columns where a checkpoint is loaded, as weights_kanana2.turn
is, and random weights owe it nothing; a loader owes it.

Norm weights are the family's zero-centred ones: a value w stands for the
factor 1 + w (`norm_offset` 1.0 in the program), w = 0.1 n, so the factor is
the other configurations' 1 + 0.1 n. The DeltaNet's gated norm is the
exception: its weight multiplies as it is, 1 + 0.1 n.

Scales: 1/sqrt(fan-in), output projections 1/sqrt(2 L fan-in), the head
N(0, 0.02), the embedding N(0, 1) (`EMBED_STD`; weights_mellum2.py says why:
the token's own row then decides the routing and the held range's share of
the assignments holds at its even share on every seed). The decay
g = -exp(A_log) softplus(a + dt_bias): A in [1, 16] uniform and
softplus(dt_bias) in [0.001, 0.1] log-uniform, as the program's own
initialisers draw them (models/transformer.py `_a_log_init`,
`_dt_bias_init`); the columns of `wba` that make `a` at a quarter of their
fan-in scale (`A_SCALE`, as the hybrid's `wf2`), so that no head decays by
more than e^80 inside 32 tokens, where ops/kda.py's chunked form stops being
exact (the fastest head: 16 x 32 x softplus(-2.25 + 0.25 n) = 53).

An expert's values depend on the key and on its number among ALL the layer's
experts, so the sixteen ranks of one expert-parallel group make disjoint
experts and the same router, shared expert and gate from the same seed
(tests/test_qwen3_next.py adds their parts)."""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from chipbench.weights import _n, layer_key

EMBED_STD = 1.0
A_SCALE = 0.25


class QwenNextSizes:
    """The numbers of the configuration file's `transformer_config`."""

    def __init__(self, tc: Dict[str, Any], norm_eps: float):
        self.V, self.d, self.L = tc["vocab_size"], tc["d_model"], tc["n_layers"]
        self.H, self.KVH = tc["n_heads"], tc["n_kv_heads"]
        self.hd = tc["attn_head_dim"]
        self.rot = int(self.hd * tc["rope_fraction"])
        self.norm_eps = float(norm_eps)
        self.theta = float(tc["rope_theta"])
        self.Hk, self.Hv = tc["gdn_k_heads"], tc["gdn_v_heads"]
        self.ghd, self.conv = tc["gdn_head_dim"], tc["gdn_conv"]
        self.chunk = tc.get("gdn_chunk", 128)
        self.E, self.k = tc["moe_num_experts"], tc["moe_experts_per_token"]
        self.held_first, self.held = tc.get("moe_held") or (0, self.E)
        self.Fe = tc["moe_d_ff"]
        self.shared = tc["moe_shared_experts"]
        gdn = set(tc["gdn_layers"])
        self.kinds: List[Tuple[str, str]] = [
            ("gdn" if l + 1 in gdn else "attn", "moe") for l in range(self.L)]
        # The layers whose gradient leaves the check compares (None: the
        # stack has no such layer, as a one-layer test stack).
        where = lambda what: [l for l, k in enumerate(self.kinds)
                              if k[0] == what]
        self.l_gdn = max(where("gdn"), default=None)
        self.l_attn = max(where("attn"), default=None)
        self.l_moe = 0
        self.e_pick = self.held // 2  # a held expert, local number


def sizes_of(config: Dict[str, Any], rehearse: bool) -> QwenNextSizes:
    """A configuration file's sizes, at its tiny preset for a rehearsal."""
    tc = dict(config["transformer_config"])
    if rehearse:
        tc.update(config["rehearsal"]["transformer_config"])
    return QwenNextSizes(tc, config["norm_eps"])


def layer(key, sz: QwenNextSizes, kind: Tuple[str, str]
          ) -> Dict[str, jax.Array]:
    """One layer's weights from its own key, float32, plain layout."""
    d, L = sz.d, sz.L
    fan = lambda n: 1 / math.sqrt(n)
    out = lambda n: 1 / math.sqrt(2 * L * n)
    w = {"attn_norm": _n(key, 0, (d,), 0.1),     # zero-centred: 1 + w
         "mlp_norm": _n(key, 1, (d,), 0.1)}
    if kind[0] == "gdn":
        nk, nv, K = sz.Hk * sz.ghd, sz.Hv * sz.ghd, sz.conv
        w["wqkvz"] = _n(key, 10, (d, 2 * nk + 2 * nv), fan(d))
        w["wba"] = _n(key, 11, (d, 2 * sz.Hv), fan(d)) * jnp.concatenate(
            [jnp.ones((sz.Hv,)), jnp.full((sz.Hv,), A_SCALE)])
        w["conv"] = _n(key, 12, (K, 2 * nk + nv), fan(K))
        u = jax.random.uniform(jax.random.fold_in(key, 13), (sz.Hv,))
        w["A_log"] = jnp.log(1.0 + 15.0 * u)
        u = jax.random.uniform(jax.random.fold_in(key, 14), (sz.Hv,))
        dt = jnp.exp(math.log(1e-3) + u * math.log(100.0))
        w["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
        w["o_norm"] = 1.0 + _n(key, 15, (sz.ghd,), 0.1)  # NOT zero-centred
        w["wo"] = _n(key, 16, (nv, d), out(nv))
    else:
        q, kv = sz.H * sz.hd, sz.KVH * sz.hd
        w["wq"] = _n(key, 20, (d, 2 * q), fan(d))  # a head: query, then gate
        w["wk"] = _n(key, 21, (d, kv), fan(d))
        w["wv"] = _n(key, 22, (d, kv), fan(d))
        w["q_norm"] = _n(key, 23, (sz.hd,), 0.1)   # zero-centred
        w["k_norm"] = _n(key, 24, (sz.hd,), 0.1)
        w["wo"] = _n(key, 25, (q, d), out(q))
    Fe, Fs = sz.Fe, sz.shared * sz.Fe
    w["router"] = _n(key, 50, (d, sz.E), fan(d))
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
    w["shared_gate"] = _n(key, 56, (d,), fan(d))
    return w


def top(key, sz: QwenNextSizes) -> Dict[str, jax.Array]:
    """Embedding, untied head and final norm (zero-centred), float32."""
    k = jax.random.fold_in(key, 2)
    return {"embed": _n(k, 0, (sz.V, sz.d), EMBED_STD),
            "final_norm": _n(k, 1, (sz.d,), 0.1),
            "lm_head": _n(k, 2, (sz.d, sz.V), 0.02)}


def _gdn_cuts(sz: QwenNextSizes):
    """Column ends of q, k, v (and z) in the plain [q ; k ; v ; z]."""
    nk, nv = sz.Hk * sz.ghd, sz.Hv * sz.ghd
    return nk, 2 * nk, 2 * nk + nv


def to_program(w: Dict[str, jax.Array], sz: QwenNextSizes,
               kind: Tuple[str, str]) -> Dict[str, jax.Array]:
    """One layer from the plain layout to the program's: leaf names and
    shapes of models/transformer.py."""
    d = sz.d
    p = {"attn_norm": w["attn_norm"], "mlp_norm": w["mlp_norm"]}
    if kind[0] == "gdn":
        a, b, c = _gdn_cuts(sz)
        heads = lambda x, n: x.reshape(x.shape[0], n, sz.ghd)
        qkvz, conv = w["wqkvz"], w["conv"]
        p["gdn_wqk"] = jnp.stack([heads(qkvz[:, :a], sz.Hk),
                                  heads(qkvz[:, a:b], sz.Hk)], 1)
        p["gdn_wvz"] = jnp.stack([heads(qkvz[:, b:c], sz.Hv),
                                  heads(qkvz[:, c:], sz.Hv)], 1)
        p["gdn_wba"] = w["wba"].reshape(d, 2, sz.Hv)
        p["gdn_conv_qk"] = jnp.stack([heads(conv[:, :a], sz.Hk),
                                      heads(conv[:, a:b], sz.Hk)], 1)
        p["gdn_conv_v"] = heads(conv[:, b:], sz.Hv)
        p["gdn_A_log"], p["gdn_dt_bias"] = w["A_log"], w["dt_bias"]
        p["gdn_o_norm"] = w["o_norm"]
        p["gdn_wo"] = w["wo"].reshape(sz.Hv, sz.ghd, d)
    else:
        wq = w["wq"].reshape(d, sz.H, 2, sz.hd)
        p["wq"], p["wq_gate"] = wq[:, :, 0], wq[:, :, 1]
        p["wkv"] = jnp.stack([w["wk"].reshape(d, sz.KVH, sz.hd),
                              w["wv"].reshape(d, sz.KVH, sz.hd)], 1)
        p["q_norm"], p["k_norm"] = w["q_norm"], w["k_norm"]
        p["wo"] = w["wo"]
    p["router"] = w["router"]
    p["moe_w_gate_up"] = jnp.stack([w["e_gate"], w["e_up"]], axis=2)
    p["moe_w_down"] = w["e_down"]
    p["shared_w_gate_up"] = jnp.stack([w["s_gate"], w["s_up"]], axis=1)
    p["shared_w_down"] = w["s_down"]
    p["shared_gate"] = w["shared_gate"]
    return p


def program_params(key, sz: QwenNextSizes, cfg, param_dtype=jnp.float32):
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


def program_leaves(cfg, sz: QwenNextSizes, g) -> Dict[str, jax.Array]:
    """The compared gradient leaves from the program's gradient tree, in
    the reference's plain layout (`reference/qwen3_next.zero_delta`): the
    final norm; the last DeltaNet layer's output and joint input
    projections, its two decay leaves and its convolution weight; the
    attention layer's doubled query projection, output projection and
    q-norm weight; a held expert's down projection, the router and the
    shared expert's gate of the first layer."""
    from ray_tpu.models.transformer import layer_params

    d = sz.d
    flat = lambda x: x.reshape(x.shape[0], -1)
    out = {"final_norm": g["final_norm"]}
    if sz.l_gdn is not None:
        p = layer_params(g, cfg, sz.l_gdn)
        qk, vz, cqk = p["gdn_wqk"], p["gdn_wvz"], p["gdn_conv_qk"]
        out["gdn_wo"] = p["gdn_wo"].reshape(-1, d)
        out["gdn_wqkvz"] = jnp.concatenate(
            [flat(qk[:, 0]), flat(qk[:, 1]), flat(vz[:, 0]), flat(vz[:, 1])],
            axis=1)
        out["gdn_A_log"], out["gdn_dt_bias"] = p["gdn_A_log"], p["gdn_dt_bias"]
        out["gdn_conv"] = jnp.concatenate(
            [flat(cqk[:, 0]), flat(cqk[:, 1]), flat(p["gdn_conv_v"])], axis=1)
    if sz.l_attn is not None:
        p = layer_params(g, cfg, sz.l_attn)
        out["attn_wq"] = jnp.stack([p["wq"], p["wq_gate"]], 2).reshape(d, -1)
        out["attn_wo"] = p["wo"]
        out["attn_q_norm"] = p["q_norm"]
    moe = layer_params(g, cfg, sz.l_moe)
    out["expert_down"] = moe["moe_w_down"][sz.e_pick]
    out["router"] = moe["router"]
    out["shared_gate"] = moe["shared_gate"]
    return out
