"""Seed -> weights of the all-latent-attention expert stack (Kanana-2,
`model_type: deepseek_v3`: MLA with a decoupled rotary part in every layer, a
dense lead layer, sigmoid-routed experts with two shared ones, an untied
head). A layer's values are weights_kimi_linear.py's `layer` (the hybrid's
scales: 1/sqrt(fan-in), output projections 1/sqrt(2 L fan-in), norms
1 + 0.1 n, the selection bias N(0, 0.01); an expert's values depend on its
number among ALL the layer's experts), float32, in the plain layout the
reference uses (x @ W, every projection a matrix of its own), which is also
the PUBLISHED layout of the rotated columns: pair i of the 64-wide part of a
query head, and of the shared key part, is columns (2i, 2i + 1)
(`rope_interleave: true`).

The program rotates halves against each other (models/transformer.py
`_rope`), so `to_program` TURNS those columns on the way in: even columns
first, then the odd ones (`turn`), in `W_q`'s last 64 columns a head and in
`W_kva`'s last 64. A permutation applied to the query's and the key's
rotated parts alike leaves every score as it was, so the program on turned
weights is the reference on published ones (tests/test_kanana2.py), and the
turn costs nothing in a step. `program_leaves` turns the gradients back.

The embedding is N(0, 1) (weights_mellum2.py says why: the token's own row,
not the window's mean, then decides the routing, and the held range's load
holds steady over the seeds); the head N(0, 0.02)."""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from chipbench import weights_kimi_linear as hybrid
from chipbench.weights import _n, layer_key

EMBED_STD = 1.0
layer = hybrid.layer  # (key, sz, kind) -> one layer, plain layout


class KananaSizes:
    """The numbers of the configuration file's `transformer_config`."""

    def __init__(self, tc: Dict[str, Any], norm_eps: float):
        self.V, self.d, self.L = tc["vocab_size"], tc["d_model"], tc["n_layers"]
        self.H, self.F = tc["n_heads"], tc["d_ff"]
        self.norm_eps = float(norm_eps)
        self.theta = float(tc["rope_theta"])
        self.lat, self.rope = tc["kv_lora_rank"], tc["qk_rope_head_dim"]
        self.nope, self.dv = tc["qk_nope_head_dim"], tc["v_head_dim"]
        self.E, self.k = tc["moe_num_experts"], tc["moe_experts_per_token"]
        self.held_first, self.held = tc.get("moe_held") or (0, self.E)
        self.Fe = tc["moe_d_ff"]
        self.shared = tc["moe_shared_experts"]
        self.routed_scale = float(tc["moe_routed_scale"])
        mla = set(tc["mla_layers"])
        if not all(l + 1 in mla for l in range(self.L)):
            raise ValueError("every layer of this stack is mla")
        self.kinds: List[Tuple[str, str]] = [
            ("mla", "dense" if l < tc["moe_first_dense"] else "moe")
            for l in range(self.L)]
        # The layers whose gradient leaves the check compares (None: the
        # stack has no such layer, as a one-layer test stack).
        where = lambda what: [l for l, k in enumerate(self.kinds)
                              if k[1] == what]
        self.l_mla = self.L - 1
        self.l_dense = min(where("dense"), default=None)
        self.l_moe = min(where("moe"), default=None)
        self.e_pick = self.held // 2  # a held expert, local number


def sizes_of(config: Dict[str, Any], rehearse: bool) -> KananaSizes:
    """A configuration file's sizes, at its tiny preset for a rehearsal."""
    tc = dict(config["transformer_config"])
    if rehearse:
        tc.update(config["rehearsal"]["transformer_config"])
    return KananaSizes(tc, config["norm_eps"])


def top(key, sz: KananaSizes) -> Dict[str, jax.Array]:
    """Embedding, untied head and final norm, float32."""
    k = jax.random.fold_in(key, 2)
    return {"embed": _n(k, 0, (sz.V, sz.d), EMBED_STD),
            "final_norm": 1.0 + _n(k, 1, (sz.d,), 0.1),
            "lm_head": _n(k, 2, (sz.d, sz.V), 0.02)}


def turn(x: jax.Array, back: bool = False) -> jax.Array:
    """The last axis from interleaved pairs (2i, 2i + 1) to halves (i,
    n / 2 + i): the even columns, then the odd ones. `back`: the inverse."""
    n = x.shape[-1]
    if back:
        return jnp.stack([x[..., :n // 2], x[..., n // 2:]], -1).reshape(x.shape)
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)


def _turned(w: Dict[str, jax.Array], sz: KananaSizes, back: bool = False):
    """`wq` [d, H x (nope + rope)] and `wkva` [d, lat + rope] with their
    rotated columns turned (published -> program, or `back`)."""
    q = w["wq"].reshape(sz.d, sz.H, sz.nope + sz.rope)
    q = jnp.concatenate([q[..., :sz.nope], turn(q[..., sz.nope:], back)], -1)
    kva = jnp.concatenate([w["wkva"][:, :sz.lat],
                           turn(w["wkva"][:, sz.lat:], back)], -1)
    return dict(w, wq=q.reshape(sz.d, -1), wkva=kva)


def to_program(w: Dict[str, jax.Array], sz: KananaSizes,
               kind: Tuple[str, str]) -> Dict[str, jax.Array]:
    """One layer from the plain (published) layout to the program's: leaf
    names and shapes of models/transformer.py, the rotated columns turned."""
    return hybrid.to_program(_turned(w, sz), sz, kind)


def program_params(key, sz: KananaSizes, cfg, param_dtype=jnp.float32):
    """The same values as the program holds them: `cfg` is the program's
    TransformerConfig, whose `stack_plan()` says how layers are grouped. A
    program whose latent mixer cannot rotate has no `mla_rotates` and fails
    here, before anything is compiled: it would run the cell as another
    model."""
    if not cfg.mla_rotates:
        raise ValueError("the configuration's latent layers rotate")
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


def program_leaves(cfg, sz: KananaSizes, g) -> Dict[str, jax.Array]:
    """The compared gradient leaves from the program's gradient tree, in
    the reference's plain layout (`reference/kanana2.zero_delta`): the last
    layer's four latent-attention matrices (the rotated columns turned
    back), the dense layer's down projection, a held expert's and the
    router of the first expert layer."""
    from ray_tpu.models.transformer import layer_params

    mla = layer_params(g, cfg, sz.l_mla)
    back = _turned({"wq": mla["mla_wq"].reshape(sz.d, -1),
                    "wkva": mla["mla_wkva"]}, sz, back=True)
    out = {"final_norm": g["final_norm"],
           "mla_wo": mla["mla_wo"].reshape(-1, sz.d),
           "mla_wq": back["wq"], "mla_wkva": back["wkva"],
           "mla_wkvb": mla["mla_wkvb"].reshape(sz.lat, -1)}
    if sz.l_dense is not None:
        out["w_down"] = layer_params(g, cfg, sz.l_dense)["w_down"]
    if sz.l_moe is not None:
        moe = layer_params(g, cfg, sz.l_moe)
        out["expert_down"] = moe["moe_w_down"][sz.e_pick]
        out["router"] = moe["router"]
    return out
