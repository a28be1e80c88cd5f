"""Decoder-only transformer, TPU-first: one functional implementation covering
the GPT-2 family (LayerNorm/GELU/learned positions) and the Llama family
(RMSNorm/SwiGLU/RoPE/GQA), selected by config.

Design choices driven by XLA/TPU, not by the reference (which has no models —
it hosts torch):
- Pure functional: params are a pytree of arrays; no module framework in the
  hot path, nothing to trace but array math.
- Layers are stacked and iterated with lax.scan → one compiled layer body,
  O(1) compile time in depth, and the natural seam for pipeline parallelism.
- Every array dim carries a logical axis name; `param_logical_specs` returns
  the matching pytree so any sharding strategy (DP/FSDP/TP/SP) is a rule
  table away (ray_tpu.parallel.sharding).
- Activations in bfloat16, params/optimizer in float32 (MXU-native mix).
- Optional jax.checkpoint on the layer body (remat) to trade FLOPs for HBM.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import attention
from ray_tpu.parallel.sharding import maybe_constrain

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # None => MHA
    d_ff: Optional[int] = None  # None => 4*d_model (gelu) or 8/3*d_model (swiglu)
    max_seq_len: int = 2048
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    activation: str = "swiglu"  # swiglu | gelu
    positional: str = "rope"  # rope | learned
    rope_theta: float = 500000.0
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    # Remat granularity when remat=True:
    # - "full": recompute the whole layer body in the backward (max memory
    #   saving, ~33% extra FLOPs, flash forward kernel included).
    # - "dots": save matmul outputs and the flash kernel's own residuals
    #   (o [B,H,S,hd] and lse [B,H,S], named inside its forward rule,
    #   ops/flash_attention.py RESIDUAL_NAMES); recompute elementwise/norm
    #   work. The forward kernel runs once a layer.
    # - "half_dots" / "half_full": the first half of the stack under
    #   "dots" / "full", the second half without remat.
    # - "min": save everything except the two fat fused-projection outputs
    #   (qkv and gate_up, tagged via checkpoint_name below) — flash
    #   residuals stay saved, recompute is one einsum + elementwise. The
    #   cheapest policy that still bounds activation memory.
    # Default "dots": keeping o and lse takes the second forward-kernel call
    # out of every layer's backward (gpt2_124m, batch 16 x 1024, one v5e
    # chip: step 184.2 -> 179.5 ms, step memory 13.96 -> 15.19 GB; chip
    # runs of PR 26, PERF.md section 6).
    remat_policy: str = "dots"
    # Mixture-of-Experts MLP (ops/moe.py, GShard capacity-based top-k):
    # 0 = dense. The expert dim shards over the `expert` mesh axis.
    moe_num_experts: int = 0
    moe_experts_per_token: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01
    # Chunked fused lm-head+CE (ops/fused_ce.py): never materializes the
    # [B*S, V] logits/dlogits tensors (~1GB each way at bench shapes) —
    # vocab chunks stream through online logsumexp fwd / recompute bwd.
    fused_ce: bool = False

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def ff_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        if self.activation == "swiglu":
            # Llama sizing: 2/3 * 4d rounded to a multiple of 128 (MXU tile).
            d = int(8 * self.d_model / 3)
            return (d + 127) // 128 * 128
        return 4 * self.d_model

    def num_params(self) -> int:
        d, L, V = self.d_model, self.n_layers, self.vocab_size
        h = self.head_dim
        attn = d * (self.n_heads * h) + 2 * d * (self.kv_heads * h) + (self.n_heads * h) * d
        if self.moe_num_experts:
            mlp = self.moe_num_experts * 3 * d * self.ff_dim + d * self.moe_num_experts
        elif self.activation == "swiglu":
            mlp = 3 * d * self.ff_dim
        else:
            mlp = 2 * d * self.ff_dim
        norms = 2 * d * L + d
        if self.norm == "layernorm":
            norms *= 2  # biases alongside scales
        emb = V * d * (1 if self.tie_embeddings else 2)
        pos = 0 if self.positional == "rope" else self.max_seq_len * d
        return L * (attn + mlp) + norms + emb + pos

    def num_active_params(self) -> int:
        """Params touched per token: for MoE, only experts_per_token of the
        E experts execute, so compute-oriented uses (FLOPs/MFU) must not
        count the full expert bank."""
        if not self.moe_num_experts:
            return self.num_params()
        d, L, F = self.d_model, self.n_layers, self.ff_dim
        full_mlp = self.moe_num_experts * 3 * d * F
        active_mlp = self.moe_experts_per_token * 3 * d * F
        return self.num_params() - L * (full_mlp - active_mlp)

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Forward+backward FLOPs/token ≈ 6*N_active + 12*L*S*d (attn)."""
        S = seq_len or self.max_seq_len
        return (6.0 * self.num_active_params()
                + 12.0 * self.n_layers * S * self.d_model)


def _dense_init(key, shape, param_dtype, scale: Optional[float] = None):
    fan_in = shape[0]
    std = scale if scale is not None else (1.0 / math.sqrt(fan_in))
    return (jax.random.normal(key, shape) * std).astype(param_dtype)


def init_params(key: jax.Array, cfg: TransformerConfig) -> Params:
    d, L, V, F = cfg.d_model, cfg.n_layers, cfg.vocab_size, cfg.ff_dim
    H, KVH, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    keys = jax.random.split(key, 12)

    def stack(initializer, shape, k):
        ks = jax.random.split(k, L)
        return jnp.stack([initializer(ks[i], shape, cfg.param_dtype) for i in range(L)])

    # Projections are FUSED into single matmuls (one MXU op instead of 2-3:
    # q/k/v together for MHA, k/v together for GQA, gate/up together for
    # swiglu). The fusion factor is its own array dim — NOT folded into the
    # feature dim — so tensor-parallel sharding of heads/mlp stays aligned
    # to shard boundaries (Megatron fused-qkv, done the GSPMD-friendly way).
    layers = {
        "attn_norm": jnp.ones((L, d), cfg.param_dtype),
        "wo": stack(lambda k, s, pd: _dense_init(k, s, pd, scale=1.0 / math.sqrt(2 * L * s[0])),
                    (H * hd, d), keys[3]),
        "mlp_norm": jnp.ones((L, d), cfg.param_dtype),
    }
    if not cfg.moe_num_experts:
        layers["w_down"] = stack(
            lambda k, s, pd: _dense_init(k, s, pd,
                                         scale=1.0 / math.sqrt(2 * L * s[0])),
            (F, d), keys[5])
    if KVH == H:
        layers["wqkv"] = stack(_dense_init, (d, 3, H, hd), keys[0])
    else:
        layers["wq"] = stack(_dense_init, (d, H, hd), keys[0])
        layers["wkv"] = stack(_dense_init, (d, 2, KVH, hd), keys[1])
    if cfg.moe_num_experts:
        E = cfg.moe_num_experts
        layers["router"] = stack(_dense_init, (d, E), keys[6])
        # Explicit scales: _dense_init's shape[0] fan-in heuristic would read
        # E (the expert dim) instead of the real matmul fan-ins d and F.
        layers["moe_w_gate_up"] = stack(
            lambda k, s, pd: _dense_init(k, s, pd, scale=1.0 / math.sqrt(d)),
            (E, d, 2, F), keys[4])
        layers["moe_w_down"] = stack(
            lambda k, s, pd: _dense_init(k, s, pd,
                                         scale=1.0 / math.sqrt(2 * L * F)),
            (E, F, d), keys[5])
    elif cfg.activation == "swiglu":
        layers["w_gate_up"] = stack(_dense_init, (d, 2, F), keys[4])
    else:
        layers["w_up"] = stack(_dense_init, (d, F), keys[4])
    if cfg.norm == "layernorm":
        layers["attn_norm_b"] = jnp.zeros((L, d), cfg.param_dtype)
        layers["mlp_norm_b"] = jnp.zeros((L, d), cfg.param_dtype)

    params: Params = {
        "embed": (jax.random.normal(keys[7], (V, d)) * 0.02).astype(cfg.param_dtype),
        "final_norm": jnp.ones((d,), cfg.param_dtype),
        "layers": layers,
    }
    if cfg.norm == "layernorm":
        params["final_norm_b"] = jnp.zeros((d,), cfg.param_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(keys[8], (d, V), cfg.param_dtype, scale=0.02)
    if cfg.positional == "learned":
        params["pos_embed"] = (
            jax.random.normal(keys[9], (cfg.max_seq_len, d)) * 0.02
        ).astype(cfg.param_dtype)
    return params


def param_logical_specs(cfg: TransformerConfig) -> Params:
    """Pytree of logical axis names matching init_params' structure
    (consumed by parallel.sharding.tree_shardings)."""
    # The leading dim is the layer stack: logical axis "layers" maps onto
    # the `pipe` mesh axis so each pipeline stage holds a contiguous range
    # of layers (parallel/pipeline.py).
    layers = {
        "attn_norm": ("layers", None),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", None),
    }
    if not cfg.moe_num_experts:
        layers["w_down"] = ("layers", "mlp", "embed")
    if cfg.kv_heads == cfg.n_heads:
        layers["wqkv"] = ("layers", "embed", None, "heads", None)
    else:
        layers["wq"] = ("layers", "embed", "heads", None)
        layers["wkv"] = ("layers", "embed", None, "kv_heads", None)
    if cfg.moe_num_experts:
        layers["router"] = ("layers", "embed", None)
        layers["moe_w_gate_up"] = ("layers", "expert", "embed", None, "mlp")
        layers["moe_w_down"] = ("layers", "expert", "mlp", "embed")
    elif cfg.activation == "swiglu":
        layers["w_gate_up"] = ("layers", "embed", None, "mlp")
    else:
        layers["w_up"] = ("layers", "embed", "mlp")
    if cfg.norm == "layernorm":
        layers["attn_norm_b"] = ("layers", None)
        layers["mlp_norm_b"] = ("layers", None)
    specs: Params = {
        "embed": ("vocab", "embed"),
        "final_norm": (None,),
        "layers": layers,
    }
    if cfg.norm == "layernorm":
        specs["final_norm_b"] = (None,)
    if not cfg.tie_embeddings:
        specs["lm_head"] = ("embed", "vocab")
    if cfg.positional == "learned":
        specs["pos_embed"] = (None, "embed")
    return specs


def _norm(x, w, b, kind: str):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        x2 = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(x2 + 1e-6) * w.astype(jnp.float32)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * w.astype(jnp.float32)
        if b is not None:
            out = out + b.astype(jnp.float32)
    return out.astype(x.dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over the last dim of [B, S, H, D]."""
    D = x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _w(layer: Params, name: str, cfg: TransformerConfig) -> jax.Array:
    """Weight access for the layer helpers: compute-dtype view,
    transparently dequantizing int8 weight-only params
    (models/quantize.py) when a scale sibling is present."""
    from .quantize import maybe_dequant

    return maybe_dequant(layer, name, cfg.dtype)


def _qkv_proj(cfg: TransformerConfig, h: jax.Array, layer: Params,
              positions: jax.Array):
    """Projection + rope shared by training forward and KV-cache decode
    (models/generate.py) — ONE home for the layer's q/k/v convention."""
    if "wqkv" in layer:
        qkv = jnp.einsum("bsd,dcnh->bscnh", h, _w(layer, "wqkv", cfg))
        qkv = checkpoint_name(qkv, "qkv_proj")
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    else:
        q = jnp.einsum("bsd,dnh->bsnh", h, _w(layer, "wq", cfg))
        kv = jnp.einsum("bsd,dcnh->bscnh", h, _w(layer, "wkv", cfg))
        kv = checkpoint_name(kv, "qkv_proj")
        k, v = kv[:, :, 0], kv[:, :, 1]
    if cfg.positional == "rope":
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mlp_block(cfg: TransformerConfig, h: jax.Array, layer: Params):
    """Post-attention FFN (moe / swiglu / gelu), shared with the decode
    path; returns (delta, moe_aux)."""
    if cfg.moe_num_experts:
        from ray_tpu.ops.moe import moe_ffn

        return moe_ffn(
            h, layer["router"], layer["moe_w_gate_up"], layer["moe_w_down"],
            experts_per_token=cfg.moe_experts_per_token,
            capacity_factor=cfg.moe_capacity_factor,
            dtype=cfg.dtype)
    aux = jnp.zeros((), jnp.float32)
    if cfg.activation == "swiglu":
        gu = jnp.einsum("bsd,dcf->bscf", h, _w(layer, "w_gate_up", cfg))
        gu = checkpoint_name(gu, "gate_up")
        act = jax.nn.silu(gu[:, :, 0]) * gu[:, :, 1]
        return act @ _w(layer, "w_down", cfg), aux
    act = checkpoint_name(h @ _w(layer, "w_up", cfg), "gate_up")
    act = jax.nn.gelu(act)
    return act @ _w(layer, "w_down", cfg), aux


def _layer_body(cfg: TransformerConfig, x: jax.Array, layer: Params,
                positions: jax.Array, return_kv: bool = False):
    B, S, d = x.shape
    H, KVH, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim

    h = _norm(x, layer["attn_norm"], layer.get("attn_norm_b"), cfg.norm)
    q, k, v = _qkv_proj(cfg, h, layer, positions)
    q = maybe_constrain(q, ("batch", "seq_act", "heads", None))
    o = attention(q, k, v, causal=True)
    x = x + o.reshape(B, S, H * hd) @ _w(layer, "wo", cfg)
    x = maybe_constrain(x, ("batch", "seq_act", "embed"))

    h = _norm(x, layer["mlp_norm"], layer.get("mlp_norm_b"), cfg.norm)
    delta, aux = _mlp_block(cfg, h, layer)
    x = x + delta
    x = maybe_constrain(x, ("batch", "seq_act", "embed"))
    if return_kv:
        return x, aux, k, v
    return x, aux


def _layer_body_kv(cfg: TransformerConfig, x: jax.Array, layer: Params,
                   positions: jax.Array):
    """Layer forward that also surfaces this layer's (roped) K/V — the
    prefill path of models/generate.py primes its cache from these."""
    x, _aux, k, v = _layer_body(cfg, x, layer, positions, return_kv=True)
    return x, k, v


def embed_tokens(params: Params, tokens: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """tokens [B, S] -> embeddings [B, S, d] (cfg.dtype)."""
    B, S = tokens.shape
    # Replicate the table for the lookup (FSDP all-gather-at-use): a gather
    # from a vocab/embed-sharded operand forces GSPMD into involuntary full
    # rematerialization when resharding the output onto the batch/seq axes
    # (the SPMD partitioner's warning on the 8-device cpu mesh). With a
    # replicated operand the gather partitions trivially along the token
    # sharding; the vocab-sharded original still feeds the lm_head matmul
    # below, and the backward scatter-add reduce-scatters back into the
    # sharded param layout.
    tbl = maybe_constrain(params["embed"].astype(cfg.dtype), (None, None))
    x = tbl[tokens]
    x = maybe_constrain(x, ("batch", "seq_act", "embed"))
    if cfg.positional == "learned":
        x = x + params["pos_embed"].astype(cfg.dtype)[:S][None]
    return x


def layer_scan_body(cfg: TransformerConfig, positions: jax.Array):
    """The (remat-wrapped) per-layer scan body; shared by the plain forward
    and the pipeline-parallel stage apply (parallel/pipeline.py). The scan's
    per-layer output is the MoE aux loss (zeros for dense layers)."""
    body = lambda carry, layer: _layer_body(cfg, carry, layer, positions)
    if cfg.remat:
        if cfg.remat_policy == "dots":
            from ray_tpu.ops.flash_attention import RESIDUAL_NAMES

            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.save_from_both_policies(
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                    jax.checkpoint_policies.save_only_these_names(
                        *RESIDUAL_NAMES),
                ),
            )
        elif cfg.remat_policy == "min":
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.save_anything_except_these_names(
                    "qkv_proj", "gate_up"
                ),
            )
        elif cfg.remat_policy == "full":
            body = jax.checkpoint(body)
        else:
            # "half_*" is resolved by forward_with_aux (it splits the stack
            # and re-enters here with full/dots/remat=False); any other name
            # reaching this point is a config error — a silent full-remat
            # fallback would mis-measure the policy being asked for.
            raise ValueError(
                f"unhandled remat_policy {cfg.remat_policy!r} at the scan "
                f"level (half_* composes only through the plain forward, "
                f"not the pipeline path)")
    return body


def forward(params: Params, tokens: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, V] (f32)."""
    return forward_with_aux(params, tokens, cfg)[0]


def forward_with_aux(
    params: Params, tokens: jax.Array, cfg: TransformerConfig
) -> Tuple[jax.Array, jax.Array]:
    """forward + summed MoE load-balancing aux loss (0 for dense stacks)."""
    x, aux = backbone_with_aux(params, tokens, cfg)
    return lm_head(params, x, cfg), aux


def backbone_with_aux(
    params: Params, tokens: jax.Array, cfg: TransformerConfig
) -> Tuple[jax.Array, jax.Array]:
    """Everything before the lm head: tokens -> hidden [B,S,d] + MoE aux
    (the fused-CE loss path consumes the hidden states directly)."""
    B, S = tokens.shape
    x = embed_tokens(params, tokens, cfg)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if cfg.remat and cfg.remat_policy.startswith("half"):
        # Mixed remat: the FIRST half of the stack checkpoints (its saved
        # activations would live longest — from forward until the very end
        # of the backward), the second half keeps activations. Halves the
        # backward recompute at roughly half of full-remat's memory saving,
        # using only standard policies the AOT helper accepts.
        inner = dataclasses.replace(
            cfg, remat_policy="dots" if cfg.remat_policy == "half_dots"
            else "full")
        plain = dataclasses.replace(cfg, remat=False)
        half = cfg.n_layers // 2
        first = jax.tree.map(lambda a: a[:half], params["layers"])
        second = jax.tree.map(lambda a: a[half:], params["layers"])
        x, aux1 = jax.lax.scan(layer_scan_body(inner, positions), x, first)
        x, aux2 = jax.lax.scan(layer_scan_body(plain, positions), x, second)
        aux = aux1.sum() + aux2.sum()
    else:
        x, auxs = jax.lax.scan(
            layer_scan_body(cfg, positions), x, params["layers"])
        aux = auxs.sum()
    return x, aux


def final_hidden_and_head(
    params: Params, x: jax.Array, cfg: TransformerConfig
) -> Tuple[jax.Array, jax.Array]:
    """THE head-weight convention (final norm + tied-or-separate head),
    shared by the unfused lm_head and the fused-CE loss path so the two
    can never drift."""
    x = _norm(x, params["final_norm"], params.get("final_norm_b"), cfg.norm)
    head = params.get("lm_head", None)
    if head is None:
        head = params["embed"].T
    return x, head.astype(cfg.dtype)


def lm_head(params: Params, x: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """Final norm + (tied) output projection: hidden [B,S,d] -> logits f32."""
    x, head = final_hidden_and_head(params, x, cfg)
    return (x @ head).astype(jnp.float32)


def token_cross_entropy(logits: jax.Array, targets: jax.Array,
                        valid: jax.Array) -> jax.Array:
    """Mean CE of logits [B,S,V] vs targets [B,S] over positions where
    ``valid`` (f32 weights) is nonzero.

    Fused: ll = logits[target] - logsumexp(logits) avoids materializing a
    second [B, S, V] f32 log-softmax tensor (at V=32k that tensor dominates
    HBM traffic for the loss epilogue).
    """
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    at_target = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ll = at_target - lse
    return -(ll * valid).sum() / jnp.maximum(valid.sum(), 1.0)


def shift_targets_valid(tokens: jax.Array, mask: Optional[jax.Array] = None):
    """targets/valid weights for the shift_inputs convention: tokens is
    [B,S+1], the forward ran on tokens[:, :-1]. Shared by loss_fn and
    parallel.pipeline.pipeline_loss_fn so the convention cannot drift."""
    targets = tokens[:, 1:]
    valid = jnp.ones(targets.shape, jnp.float32)
    if mask is not None:
        valid = valid * mask[:, 1:].astype(jnp.float32)
    return targets, valid


def inplace_targets_valid(batch: Dict[str, jax.Array]):
    """targets/valid for the in-place convention (final position masked)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1)
    valid = jnp.concatenate(
        [jnp.ones((B, S - 1), jnp.float32), jnp.zeros((B, 1), jnp.float32)],
        axis=1)
    mask = batch.get("mask")
    if mask is not None:
        shifted = jnp.concatenate(
            [mask[:, 1:], jnp.zeros((B, 1), mask.dtype)], axis=1)
        valid = valid * shifted.astype(jnp.float32)
    return targets, valid


def next_token_loss(logits: jax.Array, batch: Dict[str, jax.Array]) -> jax.Array:
    """Next-token CE over logits [B,S,V]; loss over tokens[1:] (the final
    position is masked out — in-place convention, see loss_fn)."""
    targets, valid = inplace_targets_valid(batch)
    return token_cross_entropy(logits, targets, valid)


def loss_fn(params: Params, batch: Dict[str, jax.Array], cfg: TransformerConfig,
            *, shift_inputs: bool = False) -> jax.Array:
    """Next-token cross-entropy.

    Two token conventions:
    - in-place (default): batch tokens [B,S]; the forward runs on the FULL
      sequence and the final position's logits are masked out of the loss.
      Keeps the activation sequence length equal to the (power-of-two)
      input length, which the `seq` mesh axis divides under context
      parallelism.
    - shift_inputs: batch tokens [B,S+1]; forward on tokens[:, :-1],
      targets tokens[:, 1:], every position valid. This is the
      high-throughput convention: with S+1 fed through the in-place path
      the whole model would run at an odd length (e.g. 1025), misaligning
      every matmul tile and forcing an extra padded+masked block row/col
      into the flash grid — measured ~12% step-time overhead at bench
      shapes. The sliced length S is the power of two, so context
      parallelism composes too.
    """
    tokens = batch["tokens"]
    if cfg.fused_ce:
        from ..ops.fused_ce import fused_next_token_loss

        tokens_in = tokens[:, :-1] if shift_inputs else tokens
        x, aux = backbone_with_aux(params, tokens_in, cfg)
        x, head = final_hidden_and_head(params, x, cfg)
        if shift_inputs:
            targets, valid = shift_targets_valid(tokens, batch.get("mask"))
        else:
            targets, valid = inplace_targets_valid(batch)
        loss = fused_next_token_loss(
            x.astype(cfg.dtype), head, targets, valid)
    elif shift_inputs:
        logits, aux = forward_with_aux(params, tokens[:, :-1], cfg)
        targets, valid = shift_targets_valid(tokens, batch.get("mask"))
        loss = token_cross_entropy(logits, targets, valid)
    else:
        logits, aux = forward_with_aux(params, tokens, cfg)  # [B, S, V]
        loss = next_token_loss(logits, batch)
    if cfg.moe_num_experts:
        loss = loss + cfg.moe_aux_coef * aux
    return loss
