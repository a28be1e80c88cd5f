"""Decoder-only transformer, TPU-first: one functional implementation covering
the GPT-2 family (LayerNorm/GELU/learned positions) and the Llama family
(RMSNorm/SwiGLU/RoPE/GQA), selected by config.

Design choices driven by XLA/TPU, not by the reference (which has no models —
it hosts torch):
- Pure functional: params are a pytree of arrays; no module framework in the
  hot path, nothing to trace but array math.
- The stack is a plan of segments (`TransformerConfig.stack_plan`): runs of
  layers whose kinds (mixer, feed-forward) repeat, each leaf stacked over the
  repeats and iterated with one lax.scan → compile time follows the number
  of segments and not the depth, and a stack of one kind (a dense decoder)
  is one scan, the natural seam for pipeline parallelism.
- Every array dim carries a logical axis name; `param_logical_specs` returns
  the matching pytree so any sharding strategy (DP/FSDP/TP/SP) is a rule
  table away (ray_tpu.parallel.sharding).
- Activations in bfloat16, params/optimizer in float32 (MXU-native mix).
- Optional jax.checkpoint on the layer body (remat) to trade FLOPs for HBM.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import dispatch
from ray_tpu.ops.attention import attention
from ray_tpu.parallel import tensor_overlap as tp
from ray_tpu.parallel.sharding import maybe_constrain
from ray_tpu.util import tracing

Params = Dict[str, Any]


# `moe_router`s whose layer holds a range of the experts and drops nothing.
_HELD_ROUTERS = ("sigmoid", "softmax")


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
    # rope | learned | none: no position signal at all. KDA and Mamba-2
    # mixers never take one; with "none" an "attn" layer runs unrotated too
    # (NoPE attention: the stack's recurrent layers carry order) and so does
    # an "mla" layer, which rotates its decoupled part under "rope" alone.
    positional: str = "rope"
    rope_theta: float = 500000.0
    # YaRN on the "attn" layers' rotation (None: plain RoPE, and nothing is
    # traced for it): inverse frequencies blended between theta's and theirs
    # over `yarn_factor` across the pairs whose turns in
    # `yarn_original_len` positions lie between `yarn_beta_slow` and
    # `yarn_beta_fast`, cos and sin times `yarn_attn_factor`
    # (docs/model_layers.md). Windowed ("swa") layers keep plain RoPE.
    yarn_factor: Optional[float] = None
    yarn_original_len: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attn_factor: float = 1.0
    # M-RoPE (None: one position stream, and nothing is traced for it): the
    # rotation's pairs in three contiguous sections (temporal, height, width;
    # they add up to the rotated pairs), pair i turning by the position of
    # its section's stream. `loss_fn` then reads `batch["positions"]`
    # [3, B, S]; without it the three streams are `arange` and the rotation
    # is plain RoPE. The rotation of the "attn", "swa" and "dsa" kinds.
    rope_sections: Optional[Tuple[int, int, int]] = None
    # Width of an attention head; None => d_model // n_heads.
    attn_head_dim: Optional[int] = None
    # Three properties of the "attn" / "swa" layers and one of the stack's
    # norms (docs/model_layers.md); at these defaults nothing is traced for
    # them and no leaf is made.
    # - attn_qk_norm: an RMSNorm over every query head and every key head
    #   (leaves `q_norm`, `k_norm` [head_dim]) before the rotation.
    # - rope_fraction: the share of a head's columns the rotation turns, the
    #   first head_dim * rope_fraction of them (pair i = columns i and
    #   rot / 2 + i of those, f_i = theta^(-2i / rot)); the rest pass.
    # - attn_out_gate: the attention's output times sigmoid(gate) before
    #   `wo`, the gate a second query-sized projection (leaf `wq_gate`
    #   [d, H, head_dim]; a checkpoint that doubles its query projection is
    #   split where it is loaded).
    # - norm_offset: RMSNorm weights are held zero-centred, x / rms(x) *
    #   (norm_offset + w), 0.0 or 1.0: the layer norms, the final norm and
    #   the q / k norms (not a `gdn` layer's gated norm).
    # - attn_head_gate: the other published form of that gate, one scalar a
    #   head: sigmoid(h @ `w_head_gate` [d, H]) in float32 times the head's
    #   output before `wo`. One form or the other.
    attn_qk_norm: bool = False
    rope_fraction: float = 1.0
    attn_out_gate: bool = False
    attn_head_gate: bool = False
    norm_offset: float = 0.0
    # What a "swa" layer has of its own (None: the "attn" layers' value, and
    # the two kinds then share their leaves' shapes): its number of query
    # heads over the same `n_kv_heads`, and its rotation's theta and share
    # of a head. YaRN is the "attn" layers' alone.
    swa_heads: Optional[int] = None
    swa_rope_theta: Optional[float] = None
    swa_rope_fraction: Optional[float] = None
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    # Remat granularity when remat=True, every layer alike. The rule of
    # "full": what XLA makes is recomputed, what a hand-written kernel's
    # forward rule names is kept, so the backward re-runs no such kernel.
    # - "full": save the layer's input and the kernels' own residuals (flash:
    #   o [B,H,S,hd] and lse [B,H,S]; KDA / DeltaNet: o, chunk states,
    #   inverses; SSD: y, chunk states; a held range of experts: the first
    #   window's two grouped products; named in their forward rules,
    #   RESIDUAL_NAMES of ops/flash_attention.py, ops/kda.py, ops/ssd.py and
    #   ops/moe.py; a "dsa" layer: the selection's bits, both logsumexps,
    #   o and the index loss's pass, ops/sparse_attention.py) and recompute
    #   the rest of the layer body in the backward:
    #   projections, rotations, norms, convolutions, routing, every product.
    #   What a sizing sweep falls back to when "dots" does not fit. What the
    #   kept residuals cost a layer at one sequence of 16,384 on one v5e chip
    #   (compiled for a v5e, PR 46): +0.14 GB a DeltaNet layer and +0.24 GB an
    #   expert layer (qwen3_next_80b_a3b.train_rank16_16k), +0.46 GB an expert
    #   layer (kanana_2_30b_a3b.train_rank8_16k); a program that fitted under
    #   "full" by less no longer fits, and there is no smaller policy. What
    #   they buy is a kernel's second forward call less a copy of each
    #   residual into the layer scan's stack and one out (chip runs, PERF.md
    #   section 6): the flash outputs 120 ms a step in kanana (PR 40), the
    #   delta-rule core's 4 ms and the experts' 1 ms a step in qwen3_next
    #   (PR 46; the experts' nothing in kanana).
    # - "dots": also save matmul outputs and, under a `tensor` axis, the
    #   products inside a ring (RESIDUAL_NAMES of parallel/tensor_overlap.py);
    #   recompute the rest.
    # Under either a forward kernel runs once a layer. Default "dots": keeping
    # o and lse takes the second forward-kernel call out of every layer's
    # backward (gpt2_124m, batch 16 x 1024, one v5e chip: step 184.2 -> 179.5
    # ms, step memory 13.96 -> 15.19 GB; chip runs of PR 26, PERF.md section
    # 6).
    remat_policy: str = "dots"
    # RMSNorm / LayerNorm epsilon; None = 1e-6 / 1e-5 (what was hard-coded).
    norm_eps: Optional[float] = None
    # Mixture-of-Experts MLP (ops/moe.py): 0 = dense; else the number of
    # experts the router scores. The expert dim shards over the `expert`
    # mesh axis. Two routings (`moe_router`):
    # - "softmax_capacity" (GShard): softmax gates, top-k renormalised, a
    #   capacity a routing group (`moe_capacity_factor`) past which tokens
    #   are DROPPED, every expert held, aux loss `moe_aux_coef`. Every layer
    #   is an expert layer.
    # - "sigmoid" (DeepSeek-V3 / Kimi): sigmoid scores, top-k of score +
    #   correction bias, renormalised, times `moe_routed_scale`; NOTHING is
    #   dropped, no aux loss; `moe_shared_experts` always-on experts beside
    #   the routed ones; layers before `moe_first_dense` (0-based count) keep
    #   the dense MLP of `d_ff`; experts are `moe_d_ff` wide.
    # - "softmax": the same held-range layer routed by softmax over all the
    #   experts' scores, top-k of the probabilities, renormalised; no bias
    #   leaf, no scale. `moe_held` =
    #   (first, count) is the contiguous range of experts THIS program holds
    #   (None = all): it routes over all `moe_num_experts`, computes its own
    #   experts' part and leaves the rest out (one expert-parallel rank).
    #   Gathered assignments are worked in windows of a margin over the held
    #   experts' even share (ops/moe.py `HELD_WINDOW_FACTOR`, no fewer than a
    #   row a token; every assignment where all are held): the layer's time
    #   follows the rows the routing filled, a block of the window at a time
    #   (`moe_rows_worked`), so a routing within the margin takes one trip
    #   and only one past it more, as many as it needs; what falls past the
    #   first is counted (`moe_past_buffer`, `moe_trips`): nothing is
    #   dropped.
    moe_num_experts: int = 0
    moe_experts_per_token: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01
    moe_router: str = "softmax_capacity"
    moe_held: Optional[Tuple[int, int]] = None
    moe_d_ff: Optional[int] = None
    moe_shared_experts: int = 0
    moe_routed_scale: float = 1.0
    moe_first_dense: int = 0
    # The shared experts' output times sigmoid(w . x), w one leaf [d]
    # (`shared_gate`); a held range only. False: added unweighted.
    moe_shared_gate: bool = False
    # What each layer is (docs/model_layers.md). Layer numbers are 1-based,
    # as published configs list them; numbers past n_layers are ignored, so
    # a cut in depth keeps the published lists. A layer in neither list has
    # the softmax attention above ("attn").
    # - swa_layers: the same attention where query i sees keys j with
    #   0 <= i - j < `sliding_window` ("swa"), plain RoPE; the same leaves
    #   unless `swa_heads` gives the kind a head count of its own.
    # - kda_layers: Kimi Delta Attention (ops/kda.py), `kda_heads` heads of
    #   `kda_head_dim`, a causal depthwise convolution of `kda_conv`, gate
    #   projections of rank `kda_gate_rank`, chunks of `kda_chunk` tokens.
    # - mla_layers: multi-head latent attention (`kv_lora_rank` latent +
    #   `qk_rope_head_dim` shared key part, keys `qk_nope_head_dim` +
    #   `qk_rope_head_dim` wide, values `v_head_dim`). With `positional`
    #   "rope" the `qk_rope_head_dim` part of every query head and of the
    #   shared key is rotated (plain RoPE at `rope_theta`, no YaRN); with
    #   any other it is not (NoPE).
    # - mamba_layers: Mamba-2 (ops/ssd.py), `mamba_heads` heads of
    #   `mamba_head_dim` with a state `mamba_d_state` wide, B and C shared by
    #   the heads of each of `mamba_groups` groups, a biased causal depthwise
    #   convolution of `mamba_conv` over x, B and C, chunks of `mamba_chunk`.
    # - gdn_layers: Gated DeltaNet (ops/kda.py at a decay a head):
    #   `gdn_k_heads` query / key heads and `gdn_v_heads` value heads of
    #   `gdn_head_dim` (key head i serves value heads r i .. r i + r - 1), a
    #   causal depthwise convolution of `gdn_conv` over q, k and v, a
    #   SiLU-gated norm a head, chunks of `gdn_chunk`.
    kda_layers: Tuple[int, ...] = ()
    mla_layers: Tuple[int, ...] = ()
    mamba_layers: Tuple[int, ...] = ()
    swa_layers: Tuple[int, ...] = ()
    gdn_layers: Tuple[int, ...] = ()
    dsa_layers: Tuple[int, ...] = ()
    sliding_window: Optional[int] = None
    kda_heads: Optional[int] = None       # None => n_heads
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_gate_rank: Optional[int] = None   # None => kda_head_dim
    kda_chunk: int = 128
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_d_state: int = 128
    mamba_groups: int = 1
    mamba_conv: int = 4
    mamba_chunk: int = 256
    gdn_k_heads: int = 16
    gdn_v_heads: int = 32
    gdn_head_dim: int = 128
    gdn_conv: int = 4
    gdn_chunk: int = 128
    # - dsa_layers: learned sparse attention (ops/sparse_attention.py): the
    #   "attn" layer's projections, q / k norms and rotation, and beside them
    #   an indexer on the layer's DETACHED input: `dsa_index_heads` query
    #   heads of `dsa_index_head_dim` over one key head (a LayerNorm on the
    #   key, plain RoPE at the first position stream on the first half of
    #   both), a weight a head; I[t, s] = sum_j w[t, j] relu(qi[t, j] .
    #   ki[s]) / sqrt(heads x dim). A query attends to the `dsa_topk` earlier
    #   keys of largest I (all of them while there are no more; ties to the
    #   lower index). The layer adds `DSA_LOSS_COEF` x mean_t KL(mean over
    #   heads of the attention's probabilities, detached || softmax of I
    #   over the kept keys) to the loss: the indexer's leaves learn from that
    #   term alone and no other leaf from it.
    dsa_topk: int = 2048
    dsa_index_heads: int = 16
    dsa_index_head_dim: int = 64
    # - mamba1_layers: Mamba-1 (ops/selective_scan.py): `mamba1_inner`
    #   channels (None: 2 d_model), each with a state of `mamba1_d_state`, its
    #   own time step through a rank-`mamba1_dt_rank` projection (None:
    #   ceil(d_model / 16)) and a [inner, state] matrix of decay rates; a
    #   biased causal depthwise convolution of `mamba1_conv`; the scan in
    #   chunks of `mamba1_chunk` tokens. It hands on `m`, its scan's output
    #   before the gate, where a later layer reads it.
    # - gmu_layers: a Gated Memory Unit: (m * silu(h W_1)) W_2 on the `m` the
    #   last `mamba1` layer before it handed on. No scan, no convolution.
    # - xattn_layers: cross attention to an earlier layer's keys and values:
    #   a query projection and `wo` of its own, k and v the last "attn"
    #   layer's before it (the full-attention layer of a stack whose windowed
    #   layers are "swa"), full causal.
    # A stack with readers carries those tensors beside the residual
    # (`handed_plan`; docs/model_layers.md, "What layers hand on"); a stack
    # without carries the residual alone and traces what it always traced.
    mamba1_layers: Tuple[int, ...] = ()
    gmu_layers: Tuple[int, ...] = ()
    xattn_layers: Tuple[int, ...] = ()
    mamba1_inner: Optional[int] = None
    mamba1_d_state: int = 16
    mamba1_conv: int = 4
    mamba1_dt_rank: Optional[int] = None
    mamba1_chunk: int = 128
    # - shortconv_layers: a double-gated short convolution as the layer's
    #   whole mixer (ops/shortconv.py): [Bg ; Cg ; x] = h W_in, a causal
    #   depthwise convolution of three taps (`_SHORTCONV_TAPS`, the family's
    #   `conv_L_cache`) over Bg * x, times Cg, W_out; d_model channels, no
    #   activation, no bias.
    shortconv_layers: Tuple[int, ...] = ()
    # Two properties of the "attn" / "swa" / "xattn" kinds; at the defaults
    # nothing is traced for them and no leaf is made.
    # - diff_attn: differential attention. Query and key heads split even /
    #   odd into two sets, the values of a pair of heads side by side as one
    #   head twice as wide; o = P_1 V - lam P_2 V with two softmax maps, lam =
    #   exp(lq1 . lk1) - exp(lq2 . lk2) + lam0 from four learned vectors a
    #   layer (leaf `diff_lam` [4, head_dim]), an RMSNorm over the doubled
    #   head (leaf `diff_norm`, eps 1e-5) times (1 - lam0), heads read back
    #   interleaved into `wo`. lam0 = 0.8 - 0.6 exp(-0.3 i), i the layer's
    #   0-based index in the PUBLISHED stack: `layer_offset` + its place here
    #   (a stage of a deeper model starts past 0).
    # - attn_bias: biases on the q / k / v projections and on `wo`.
    diff_attn: bool = False
    attn_bias: bool = False
    layer_offset: int = 0
    # Four scalars some families multiply by (docs/model_layers.md); at
    # these defaults no operation is traced for them. Embeddings times
    # `embed_scale`; both residual branches of every layer times
    # `residual_scale`; `attn_scale` in place of head_dim ** -0.5 in "attn"
    # layers (None: that default); logits divided by `logit_scale`.
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    attn_scale: Optional[float] = None
    logit_scale: float = 1.0
    # Chunked fused lm-head+CE (ops/fused_ce.py): never materializes the
    # [B*S, V] logits/dlogits tensors (~1GB each way at bench shapes) —
    # vocab chunks stream through online logsumexp fwd / recompute bwd.
    fused_ce: bool = False
    # A looped stack (docs/model_layers.md, "The loop"); at these defaults
    # nothing is traced for it and no leaf is made.
    # - loop_steps: the whole stack is applied this many times a step over
    #   ONE set of leaves (every weight's gradient the sum of its uses), the
    #   final norm closing every pass: its output is the next pass's input
    #   and that pass's head input. Positions are the same in every pass.
    # - post_norm: a second norm round each sublayer, x + N2(f(N1(x)))
    #   (leaves `attn_post_norm`, `mlp_post_norm` [d]; rmsnorm).
    # - exit_gate: a scalar gate reads every pass's normed output, lam =
    #   sigmoid(h . `exit_gate_w` [d] + `exit_gate_b` [1]) in float32; pass
    #   t < T is left with probability p(t) = lam_t prod_{u<t} (1 - lam_u),
    #   the last with what remains, and training minimises the expected
    #   next-token loss under p less `exit_entropy_coef` times p's entropy
    #   (`loss_fn`). False: the last pass's loss alone. All passes always
    #   run: there is no early exit here.
    loop_steps: int = 1
    post_norm: bool = False
    exit_gate: bool = False
    exit_entropy_coef: float = 0.0

    def __post_init__(self):
        fields = [m.layers_field for m in MIXERS.values() if m.layers_field]
        for name in fields + ["moe_held", "rope_sections"]:
            v = getattr(self, name)
            if isinstance(v, list):  # from a JSON file
                object.__setattr__(self, name, tuple(v))
        if self.remat_policy not in ("dots", "full"):
            raise ValueError(f"unknown remat_policy {self.remat_policy!r}: "
                             "'dots' or 'full'")
        if self.moe_router not in ("softmax_capacity",) + _HELD_ROUTERS:
            raise ValueError(f"unknown moe_router {self.moe_router!r}")
        lists = sum((getattr(self, f) for f in fields), ())
        if len(set(lists)) != len(lists):
            raise ValueError("a layer is in two of " + ", ".join(fields))
        if self.gdn_layers and self.gdn_v_heads % self.gdn_k_heads:
            raise ValueError("gdn_v_heads is a multiple of gdn_k_heads")
        if self.norm_offset not in (0.0, 1.0) or (
                self.norm_offset and self.norm != "rmsnorm"):
            raise ValueError("norm_offset is 0.0, or 1.0 with norm='rmsnorm'")
        for mixer in ("attn", "swa"):
            rot = self.attn_rope(mixer)[1]
            if not 0 < rot <= self.head_dim or rot % 2:
                raise ValueError(
                    "rope_fraction / swa_rope_fraction rotate an even number "
                    "of a head's columns, at most all of them")
            if self.rope_sections and 2 * sum(self.rope_sections) != rot:
                raise ValueError("rope_sections add up to the rotated pairs "
                                 f"of a head, {rot // 2}")
        if self.rope_sections and self.mla_rotates and any(
                l <= self.n_layers for l in self.mla_layers):
            raise ValueError("an mla layer's rotation takes one position "
                             "stream: no rope_sections")
        if self.swa_heads and self.swa_heads % self.kv_heads:
            raise ValueError("swa_heads is a multiple of the key / value "
                             "heads")
        if self.attn_out_gate and self.attn_head_gate:
            raise ValueError("attn_out_gate (a gate a column) or "
                             "attn_head_gate (a gate a head), not both")
        if self.moe_shared_gate and not (self.moe_holds_range
                                         and self.moe_shared_experts):
            raise ValueError("moe_shared_gate gates the shared experts of a "
                             "held range (moe_router 'sigmoid' / 'softmax')")
        if self.swa_layers and not (self.sliding_window or 0) >= 1:
            raise ValueError("swa_layers need a sliding_window of >= 1")
        if (self.yarn_factor is not None and self.mla_rotates
                and any(l <= self.n_layers for l in self.mla_layers)):
            raise ValueError("an mla layer's rotation takes no YaRN scaling "
                             "(no mscale on its scores yet)")
        if (self.moe_num_experts and not self.moe_holds_range
                and any(m != _PLAIN for m, _ in self.layer_kinds())):
            raise ValueError(
                " / ".join(fields) + " compose with moe_router='sigmoid' "
                "or 'softmax' only")
        if self.moe_router == "softmax" and self.moe_routed_scale != 1.0:
            raise ValueError("moe_router='softmax' takes no moe_routed_scale")
        if self.loop_steps < 1 or (self.exit_gate and self.loop_steps < 2):
            raise ValueError("loop_steps is at least 1, and at least 2 where "
                             "an exit_gate chooses among the passes")
        if self.post_norm and self.norm != "rmsnorm":
            raise ValueError("post_norm is an RMSNorm: norm='rmsnorm'")
        if self.diff_attn and (self.n_heads % 2 or self.kv_heads % 2
                               or (self.swa_heads or 0) % 2):
            raise ValueError("diff_attn splits query and key heads even / "
                             "odd: even head counts")
        if self.diff_attn and (self.attn_gated or self.attn_scale):
            raise ValueError("diff_attn composes with no attn_qk_norm / "
                             "attn_out_gate / attn_head_gate / attn_scale")
        self.handed_plan()  # a reader with no writer before it raises

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def moe_holds_range(self) -> bool:
        """Experts routed with nothing dropped, a range of them held here
        (ops/moe.py `moe_ffn_held`), not GShard's capacity dispatch."""
        return self.moe_num_experts > 0 and self.moe_router in _HELD_ROUTERS

    @property
    def mla_rotates(self) -> bool:
        """Whether an `mla` layer rotates its decoupled part (the last
        `qk_rope_head_dim` columns of every query head and the shared key
        part): under `positional="rope"`, never otherwise."""
        return self.positional == "rope"

    @property
    def rope_yarn(self) -> Optional[Tuple[float, int, float, float, float]]:
        if self.yarn_factor is None:
            return None
        return (self.yarn_factor, self.yarn_original_len, self.yarn_beta_fast,
                self.yarn_beta_slow, self.yarn_attn_factor)

    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, feed-forward) of every layer: mixer a name of `MIXERS`
        (the kind whose list names the layer, else the one no list names),
        feed-forward dense | moe."""
        out = []
        for l in range(self.n_layers):
            mixer = next((m.name for m in MIXERS.values() if m.layers_field
                          and l + 1 in getattr(self, m.layers_field)), _PLAIN)
            moe = self.moe_num_experts and (
                not self.moe_holds_range or l >= self.moe_first_dense)
            out.append((mixer, "moe" if moe else "dense"))
        return tuple(out)

    def stack_plan(self) -> Tuple[Tuple[Tuple[Tuple[str, str], ...], int], ...]:
        """The stack as segments (pattern of layer kinds, repeats): greedily,
        from each layer on, the period (up to 10) whose repeats cover the
        most layers; a layer that starts no repeat is a segment of its own.
        Each segment is one `lax.scan` over its repeats, so compile time
        follows the number of segments and not the depth. A stack of one
        kind is one segment: (((kind,), n_layers),)."""
        kinds, plan, i = self.layer_kinds(), [], 0
        while i < len(kinds):
            best = (1, 1)
            for p in range(1, 11):
                r = 1
                while kinds[i + r * p:i + (r + 1) * p] == kinds[i:i + p]:
                    r += 1
                if r >= 2 and p * r > best[0] * best[1]:
                    best = (p, r)
            p, r = best
            plan.append((kinds[i:i + p], r))
            i += p * r
        return tuple(plan)

    def layer_slot(self, l: int) -> Tuple[int, int, int]:
        """Where layer l (0-based) lives in `stack_segments(params, cfg)`:
        [segment][position], row `repeat` of each leaf."""
        i = 0
        for seg, (pattern, r) in enumerate(self.stack_plan()):
            n = len(pattern) * r
            if l < i + n:
                return seg, (l - i) % len(pattern), (l - i) // len(pattern)
            i += n
        raise IndexError(l)

    def handed_plan(self) -> Tuple[Tuple[Tuple[str, ...], Tuple[str, ...]], ...]:
        """(reads, writes) of every layer: the names of the tensors it takes
        from an earlier layer and of those it hands on, by its row of
        `MIXERS` (`Mixer.reads`, `Mixer.writes`). A layer hands a tensor on
        only where a later layer reads it before another writes it, so a
        stack without readers hands nothing on."""
        mixers = [m for m, _ in self.layer_kinds()]
        plan = []
        for l, m in enumerate(mixers):
            row = MIXERS[m]
            for name in row.reads:
                if not any(name in MIXERS[w].writes for w in mixers[:l]):
                    raise ValueError(
                        f"layer {l + 1} ({m}) reads {name!r}, which no "
                        "layer before it hands on")
            live = []
            for name in row.writes:
                for later in mixers[l + 1:]:
                    if name in MIXERS[later].reads:
                        live.append(name)
                    if name in MIXERS[later].reads + MIXERS[later].writes:
                        break
            plan.append((row.reads, tuple(live)))
        return tuple(plan)

    @property
    def mamba1_channels(self) -> int:
        return self.mamba1_inner or 2 * self.d_model

    @property
    def mamba1_rank(self) -> int:
        return self.mamba1_dt_rank or -(-self.d_model // 16)

    def attn_heads(self, mixer: str) -> int:
        """Query heads of an "attn" or a "swa" layer."""
        return (self.swa_heads if mixer == "swa" else None) or self.n_heads

    def attn_rope(self, mixer: str):
        """(theta, rotated columns of a head, YaRN or None) of the rotation
        of an "attn" or a "swa" layer."""
        if mixer != "swa":
            return (self.rope_theta, int(self.head_dim * self.rope_fraction),
                    self.rope_yarn)
        fraction = (self.rope_fraction if self.swa_rope_fraction is None
                    else self.swa_rope_fraction)
        return (self.swa_rope_theta or self.rope_theta,
                int(self.head_dim * fraction), None)

    @property
    def attn_gated(self) -> bool:
        """Whether the "attn" / "swa" kinds carry what the `gattn` scope
        marks."""
        return self.attn_qk_norm or self.attn_out_gate or self.attn_head_gate

    @property
    def kda_n_heads(self) -> int:
        return self.kda_heads or self.n_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def moe_ff_dim(self) -> int:
        return self.moe_d_ff or self.ff_dim

    @property
    def moe_held_range(self) -> Tuple[int, int]:
        return self.moe_held or (0, self.moe_num_experts)

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.d_model // self.n_heads

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
        """The leaves `init_params` makes, counted from their shapes."""
        d = self.d_model
        layers = sum(_size(_layer_shapes(self, kind))
                     for kind in self.layer_kinds())
        final_norm = d * (2 if self.norm == "layernorm" else 1)
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        pos = self.max_seq_len * d if self.positional == "learned" else 0
        return layers + final_norm + emb + pos + _size(_exit_shapes(self))

    def num_active_params(self) -> int:
        """Params touched per token: for MoE, only experts_per_token of the
        E experts execute (of the held ones, under even routing, their share
        of that; no shape says how many that is), so compute-oriented uses
        (FLOPs/MFU) must not count the full expert bank."""
        if not self.moe_num_experts:
            return self.num_params()
        d, E = self.d_model, self.moe_num_experts
        k = self.moe_experts_per_token
        if self.moe_holds_range:  # under even routing, k * held / E of them
            k = k * self.moe_held_range[1] / E + self.moe_shared_experts
        active = {  # the selection bias is no matmul
            "dense": _size(_ffn_shapes(self, "dense")),
            "moe": k * 3 * d * self.moe_ff_dim + d * E + (
                d if self.moe_shared_gate else 0)}
        ffns = [f for _, f in self.layer_kinds()]
        full = sum(_size(_ffn_shapes(self, f)) for f in ffns)
        return int(self.num_params() - full + sum(active[f] for f in ffns))

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Forward+backward FLOPs/token: 6 per matmul parameter a token
        touches (no embedding lookup) and each layer's `Mixer.core_flops`:
        causal attention 3*S*H*(d_qk + d_v) a softmax layer, the chunked
        algorithm's operations a KDA, Gated DeltaNet or Mamba-2 layer
        (chipbench/reduce/kda_counts.py, ssd_counts.py, qwen3_next_counts.py).
        A looped stack's leaves are used `loop_steps` times a token, the
        head as often where an `exit_gate` reads every pass, else once."""
        S = seq_len or self.max_seq_len
        n = self.num_active_params()
        if not self.tie_embeddings:  # the lookup; a tied table is the head
            n -= self.vocab_size * self.d_model
        if self.positional == "learned":
            n -= self.max_seq_len * self.d_model
        total = 6.0 * n
        for mixer, _ in self.layer_kinds():
            total += MIXERS[mixer].core_flops(self, S)
        if self.loop_steps > 1:
            skipped = 0 if self.exit_gate else self.loop_steps - 1
            total = (total * self.loop_steps
                     - 6.0 * self.vocab_size * self.d_model * skipped)
        return total


@dataclasses.dataclass(frozen=True)
class Mixer:
    """One kind of token mixer, everything this file and decoding ask of it
    (`MIXERS` is the table; docs/model_layers.md, "Adding a mixer"):
    `layers_field`, the `TransformerConfig` field that lists its layers
    (1-based; None: the kind of a layer no list names); `shapes(cfg)`, its
    part of `_layer_shapes`; `apply(cfg, kind, h, layer, positions, overlap)
    -> (delta, k, v)`, h [B,S,d] the normed residual, k and v its (roped)
    keys and values or None; `core_flops(cfg, S)`, its term of
    `flops_per_token`; `scope(cfg)`, the device scope its layers run under
    (chipbench/metrics read it) or None; `cut_rows`, whether it works a
    rank's rows of a residual cut over `tensor` (`overlap`) or is handed
    them all; `no_decode`, why models/generate.py cannot serve it (None:
    its keys and values are what the cache holds); `writes`, the names of
    the tensors it can hand on to later layers ("k", "v": what `apply`
    returns second and third; any other: a key of what it returns fourth)
    and `reads`, the names it takes from earlier ones, which `apply` is then
    called with as `handed={name: tensor}` (`TransformerConfig.handed_plan`
    says which layers do)."""
    name: str
    layers_field: Optional[str]
    shapes: Callable[[TransformerConfig], Dict[str, Any]]
    apply: Callable[..., Tuple[jax.Array, Any, Any]]
    core_flops: Callable[[TransformerConfig, int], float]
    scope: Callable[[TransformerConfig], Optional[str]] = lambda cfg: None
    cut_rows: bool = False
    no_decode: Optional[str] = None
    writes: Tuple[str, ...] = ()
    reads: Tuple[str, ...] = ()


# The decay a = exp(-exp(A_log) * softplus(. + dt_bias)) of the `mamba2` and
# `kda` mixers: A in [1, 16], softplus(dt_bias) in [0.001, 0.1] log-uniform
# (the Mamba family's parametrisation, which the gated delta rules follow).


def _a_log_init(shape):
    return lambda k: jnp.log(jax.random.uniform(k, shape, minval=1.0,
                                                maxval=16.0))


def _dt_bias_init(shape):
    def init(k):
        dt = jnp.exp(jax.random.uniform(
            k, shape, minval=math.log(1e-3), maxval=math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
    return init


def _unit_norm_init(cfg: TransformerConfig) -> str:
    """The init at which an RMSNorm multiplies by one: its weight is held
    as w, or zero-centred as 1 + w (`norm_offset`)."""
    return "zeros" if cfg.norm_offset else "ones"


# Leaves are {leaf: (shape, logical axes, init)}, init "ones" | "zeros" |
# ("normal", std) | a callable key -> array: `_fan` for a product of fan-in
# n, `_out_std` for one back into the residual. A `Mixer.shapes` each kind:
_fan = lambda n: ("normal", 1.0 / math.sqrt(n))
_out_std = lambda cfg, n: ("normal", 1.0 / math.sqrt(2 * cfg.n_layers * n))
_size = lambda shapes: sum(math.prod(sh) for sh, _, _ in shapes.values())


def _attn_shapes(cfg: TransformerConfig, mixer: str = "attn"):
    # The leaves of an "attn" layer or of a "swa" one (`mixer`: a "swa"
    # layer may have its own number of query heads, `cfg.swa_heads`).
    # Projections are FUSED into single matmuls (one MXU op instead of 2-3:
    # q/k/v together for MHA, k/v together for GQA; gate/up for swiglu). The
    # fusion factor is its own array dim — NOT folded into the feature dim —
    # so tensor-parallel sharding of heads/mlp stays aligned to shard
    # boundaries (Megatron fused-qkv, done the GSPMD-friendly way).
    d, hd, KVH = cfg.d_model, cfg.head_dim, cfg.kv_heads
    H = cfg.attn_heads(mixer)
    fan, unit = _fan(d), _unit_norm_init(cfg)
    cross = mixer == "xattn"  # keys and values are an earlier layer's
    sh = {"wo": ((H * hd, d), ("heads", "embed"), _out_std(cfg, H * hd))}
    if KVH == H and not cross:
        sh["wqkv"] = ((d, 3, H, hd), ("embed", None, "heads", None), fan)
    else:
        sh["wq"] = ((d, H, hd), ("embed", "heads", None), fan)
        if not cross:
            sh["wkv"] = ((d, 2, KVH, hd),
                         ("embed", None, "kv_heads", None), fan)
    if cfg.attn_out_gate:
        sh["wq_gate"] = ((d, H, hd), ("embed", "heads", None), fan)
    if cfg.attn_head_gate:
        sh["w_head_gate"] = ((d, H), ("embed", "heads"), fan)
    if cfg.attn_qk_norm:
        sh["q_norm"] = ((hd,), (None,), unit)
        sh["k_norm"] = ((hd,), (None,), unit)
    if cfg.attn_bias:
        for n in [n for n in ("wq", "wkv", "wqkv") if n in sh]:
            sh[n + "_b"] = (sh[n][0][1:], sh[n][1][1:], "zeros")
        sh["wo_b"] = ((d,), (None,), "zeros")
    if cfg.diff_attn:
        sh["diff_lam"] = ((4, hd), (None, None), ("normal", 0.1))
        sh["diff_norm"] = ((2 * hd,), (None,), "ones")
    return sh


def _mamba1_shapes(cfg: TransformerConfig):
    # [u | z] fused over an array dim of its own, as `w_gate_up` is; r, B_t
    # and C_t come out of ONE narrow product of the convolved u.
    d, Di, N = cfg.d_model, cfg.mamba1_channels, cfg.mamba1_d_state
    R, K = cfg.mamba1_rank, cfg.mamba1_conv
    return {
        "mamba1_win": ((d, 2, Di), ("embed", None, "mlp"), _fan(d)),
        "mamba1_conv": ((K, Di), (None, "mlp"), _fan(K)),
        "mamba1_conv_b": ((Di,), ("mlp",), "zeros"),
        "mamba1_wx": ((Di, R + 2 * N), ("mlp", None), _fan(Di)),
        "mamba1_wdt": ((R, Di), (None, "mlp"), _fan(R)),
        "mamba1_dt_bias": ((Di,), ("mlp",), _dt_bias_init((Di,))),
        # A = 1 .. N a channel (the S4D-real initialisation)
        "mamba1_A_log": ((Di, N), ("mlp", None), lambda k: jnp.log(
            jnp.broadcast_to(jnp.arange(1.0, N + 1.0), (Di, N)))),
        "mamba1_D": ((Di,), ("mlp",), "ones"),
        "mamba1_wo": ((Di, d), ("mlp", "embed"), _out_std(cfg, Di)),
    }


def _gmu_shapes(cfg: TransformerConfig):
    d, Di = cfg.d_model, cfg.mamba1_channels
    return {"gmu_w1": ((d, Di), ("embed", "mlp"), _fan(d)),
            "gmu_w2": ((Di, d), ("mlp", "embed"), _out_std(cfg, Di))}


# The one tap count in use (LFM2's `conv_L_cache`): the mixer and the kernels
# take it from the taps leaf's shape, so a second count is a field the day a
# configuration needs it.
_SHORTCONV_TAPS = 3


def _shortconv_shapes(cfg: TransformerConfig):
    # [Bg ; Cg ; x] fused over an array dim of its own, as `w_gate_up` is.
    d, K = cfg.d_model, _SHORTCONV_TAPS
    return {"shortconv_win": ((d, 3, d), ("embed", None, "mlp"), _fan(d)),
            "shortconv_conv": ((K, d), (None, "mlp"), _fan(K)),
            "shortconv_wout": ((d, d), ("mlp", "embed"), _out_std(cfg, d))}


def _dsa_shapes(cfg: TransformerConfig):
    # The "attn" layer's leaves and the indexer's: queries a head, one key
    # head with its LayerNorm, a weight a head.
    d, HI, dI = cfg.d_model, cfg.dsa_index_heads, cfg.dsa_index_head_dim
    fan = _fan(d)
    return {**_attn_shapes(cfg),
            "dsa_wq": ((d, HI, dI), ("embed", None, None), fan),
            "dsa_wk": ((d, dI), ("embed", None), fan),
            "dsa_k_norm": ((dI,), (None,), "ones"),
            "dsa_k_norm_b": ((dI,), (None,), "zeros"),
            "dsa_ww": ((d, HI), ("embed", None), fan)}


def _gdn_shapes(cfg: TransformerConfig):
    # q and k (key heads) and v and the gate z (value heads) each fused over
    # an array dim of their own, as `mamba_wzx` is; beta and the decay's
    # input are narrow. The convolution is depthwise: one over [q; k; v] is
    # one over each.
    d, Hq, Hv = cfg.d_model, cfg.gdn_k_heads, cfg.gdn_v_heads
    hg, K, fan = cfg.gdn_head_dim, cfg.gdn_conv, _fan(cfg.d_model)
    return {
        "gdn_wqk": ((d, 2, Hq, hg), ("embed", None, "heads", None), fan),
        "gdn_wvz": ((d, 2, Hv, hg), ("embed", None, "heads", None), fan),
        "gdn_wba": ((d, 2, Hv), ("embed", None, "heads"), fan),
        "gdn_conv_qk": ((K, 2, Hq, hg), (None, None, "heads", None), _fan(K)),
        "gdn_conv_v": ((K, Hv, hg), (None, "heads", None), _fan(K)),
        "gdn_A_log": ((Hv,), ("heads",), _a_log_init((Hv,))),
        "gdn_dt_bias": ((Hv,), ("heads",), _dt_bias_init((Hv,))),
        "gdn_o_norm": ((hg,), (None,), "ones"),
        "gdn_wo": ((Hv, hg, d), ("heads", None, "embed"),
                   _out_std(cfg, Hv * hg)),
    }


def _mla_shapes(cfg: TransformerConfig):
    d, H, lat, fan = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, _fan(cfg.d_model)
    rope, nope, dv = cfg.qk_rope_head_dim, cfg.qk_nope_head_dim, cfg.v_head_dim
    return {
        "mla_wq": ((d, H, nope + rope), ("embed", "heads", None), fan),
        "mla_wkva": ((d, lat + rope), ("embed", None), fan),
        "mla_kv_norm": ((lat,), (None,), "ones"),
        "mla_wkvb": ((lat, H, nope + dv), (None, "heads", None), _fan(lat)),
        "mla_wo": ((H, dv, d), ("heads", None, "embed"),
                   _out_std(cfg, H * dv)),
    }


def _mamba_shapes(cfg: TransformerConfig):
    # in_proj's three parts are leaves of their own (gate z and x fused over
    # an array dim of their own, as wkv is): heads stay a dim that a
    # tensor-parallel rule can cut, B / C and dt are narrow.
    d, Hm, P = cfg.d_model, cfg.mamba_heads, cfg.mamba_head_dim
    G, N, K = cfg.mamba_groups, cfg.mamba_d_state, cfg.mamba_conv
    fan = _fan(d)
    return {
        "mamba_wzx": ((d, 2, Hm, P), ("embed", None, "heads", None), fan),
        "mamba_wbc": ((d, 2, G, N), ("embed", None, None, None), fan),
        "mamba_wdt": ((d, Hm), ("embed", "heads"), fan),
        "mamba_conv_x": ((K, Hm, P), (None, "heads", None), _fan(K)),
        "mamba_conv_x_b": ((Hm, P), ("heads", None), "zeros"),
        "mamba_conv_bc": ((K, 2, G, N), (None, None, None, None), _fan(K)),
        "mamba_conv_bc_b": ((2, G, N), (None, None, None), "zeros"),
        "mamba_A_log": ((Hm,), ("heads",), _a_log_init((Hm,))),
        "mamba_dt_bias": ((Hm,), ("heads",), _dt_bias_init((Hm,))),
        "mamba_D": ((Hm,), ("heads",), "ones"),
        "mamba_norm": ((Hm, P), ("heads", None), "ones"),
        "mamba_wo": ((Hm, P, d), ("heads", None, "embed"),
                     _out_std(cfg, Hm * P)),
    }


def _kda_shapes(cfg: TransformerConfig):
    d, Hk, hd = cfg.d_model, cfg.kda_n_heads, cfg.kda_head_dim
    rank, K, fan = cfg.kda_gate_rank or hd, cfg.kda_conv, _fan(cfg.d_model)
    sh = {}
    for n in ("q", "k", "v"):
        sh["kda_w" + n] = ((d, Hk, hd), ("embed", "heads", None), fan)
        sh["kda_conv_" + n] = ((K, Hk, hd), (None, "heads", None), _fan(K))
    for n in ("f", "g"):  # decay and output gate, low rank
        sh[f"kda_w{n}1"] = ((d, rank), ("embed", None), fan)
        sh[f"kda_w{n}2"] = ((rank, Hk, hd), (None, "heads", None), _fan(rank))
    sh["kda_A_log"] = ((Hk,), ("heads",), _a_log_init((Hk,)))
    sh["kda_dt_bias"] = ((Hk, hd), ("heads", None), _dt_bias_init((Hk, hd)))
    sh["kda_wb"] = ((d, Hk), ("embed", "heads"), fan)
    sh["kda_o_norm"] = ((hd,), (None,), "ones")
    sh["kda_wo"] = ((Hk, hd, d), ("heads", None, "embed"),
                    _out_std(cfg, Hk * hd))
    return sh


def _ffn_shapes(cfg: TransformerConfig, ffn: str):
    """The feed-forward's leaves, as `Mixer.shapes` gives a mixer's."""
    d, fan = cfg.d_model, _fan(cfg.d_model)
    if ffn == "dense":
        F = cfg.ff_dim
        sh = {"w_down": ((F, d), ("mlp", "embed"), _out_std(cfg, F))}
        if cfg.activation == "swiglu":
            sh["w_gate_up"] = ((d, 2, F), ("embed", None, "mlp"), fan)
        else:
            sh["w_up"] = ((d, F), ("embed", "mlp"), fan)
        return sh
    E, F = cfg.moe_num_experts, cfg.moe_ff_dim
    held = cfg.moe_holds_range
    Eh = cfg.moe_held_range[1] if held else E  # GShard holds them all
    sh = {"router": ((d, E), ("embed", None), fan)}
    if cfg.moe_router == "sigmoid":
        sh["router_bias"] = ((E,), (None,), "zeros")  # a buffer: no gradient
    # The scales are the matmuls' fan-ins d and F, not the leading dim E.
    sh["moe_w_gate_up"] = ((Eh, d, 2, F), ("expert", "embed", None, "mlp"), fan)
    sh["moe_w_down"] = ((Eh, F, d), ("expert", "mlp", "embed"),
                        _out_std(cfg, F))
    Fs = cfg.moe_shared_experts * F if held else 0
    if Fs:
        sh["shared_w_gate_up"] = ((d, 2, Fs), ("embed", None, "mlp"), fan)
        sh["shared_w_down"] = ((Fs, d), ("mlp", "embed"), _out_std(cfg, Fs))
        if cfg.moe_shared_gate:
            sh["shared_gate"] = ((d,), ("embed",), fan)
    return sh


def _layer_shapes(cfg: TransformerConfig, kind: Tuple[str, str]):
    """The leaves of one layer of `kind`: THE table `init_params`,
    `param_logical_specs` and `num_params` are built from, in the order
    `init_params` folds into a leaf's key."""
    mixer, ffn = kind
    d, unit = cfg.d_model, _unit_norm_init(cfg)
    sh = {"attn_norm": ((d,), (None,), unit), "mlp_norm": ((d,), (None,), unit),
          **MIXERS[mixer].shapes(cfg), **_ffn_shapes(cfg, ffn)}
    if cfg.norm == "layernorm":
        sh["attn_norm_b"] = ((d,), (None,), "zeros")
        sh["mlp_norm_b"] = ((d,), (None,), "zeros")
    if cfg.post_norm:
        sh["attn_post_norm"] = ((d,), (None,), unit)
        sh["mlp_post_norm"] = ((d,), (None,), unit)
    return sh


def _exit_shapes(cfg: TransformerConfig):
    """The exit gate's two leaves, beside the final norm at the top of the
    tree (`cfg.exit_gate`; else none)."""
    if not cfg.exit_gate:
        return {}
    return {"exit_gate_w": ((cfg.d_model,), ("embed",), _fan(cfg.d_model)),
            "exit_gate_b": ((1,), (None,), "zeros")}


# params["layers"] is stored in one of two formats, both part of what callers
# outside this file build and read (chipbench/weights*.py, generate, quantize,
# pipeline): a stack of softmax-attention layers with dense or GShard
# feed-forwards is ONE dict of leaves [L, ...]; every other stack (another
# mixer, windowed attention among them, or a held range of experts) is a list of
# segments (cfg.stack_plan()), a segment a list over its pattern's positions
# of one layer kind's leaves, each stacked over the segment's repeats. The
# three functions below are the only code that knows which.


def _one_tree(cfg: TransformerConfig) -> bool:
    return (all(m == _PLAIN for m, _ in cfg.layer_kinds())
            and not cfg.moe_holds_range)


def stack_segments(params: Params, cfg: TransformerConfig):
    """params["layers"] as the list of segments `cfg.stack_plan()` describes,
    whichever way it is stored (`params` may be any tree of that structure:
    gradients, `param_logical_specs`)."""
    return [[params["layers"]]] if _one_tree(cfg) else params["layers"]


def _stored(segments, cfg: TransformerConfig):
    """The inverse: segments as params["layers"] stores them."""
    return segments[0][0] if _one_tree(cfg) else segments


def one_kind_stack(params: Params, cfg: TransformerConfig, what: str):
    """(kind, leaves [L, ...]) of a stack that is one segment of layers of
    one kind: the only stack `what` (decoding, pipeline parallelism) takes,
    because it scans or cuts ONE stacked tree."""
    plan = cfg.stack_plan()
    if len(plan) != 1 or len(plan[0][0]) != 1:
        raise NotImplementedError(
            f"{what} takes one segment of layers of one kind; this stack's "
            f"plan is {plan}")
    return plan[0][0][0], stack_segments(params, cfg)[0][0]


def layer_params(params: Params, cfg: TransformerConfig, l: int) -> Params:
    """The parameters of layer l (0-based), whichever way they are stacked."""
    seg, pos, rep = cfg.layer_slot(l)
    return jax.tree.map(lambda a: a[rep], stack_segments(params, cfg)[seg][pos])


def init_params(key: jax.Array, cfg: TransformerConfig) -> Params:
    d, V = cfg.d_model, cfg.vocab_size
    keys = jax.random.split(key, 12)

    def leaf(k, r, shape, init):
        if init == "ones":
            return jnp.ones((r,) + shape, cfg.param_dtype)
        if init == "zeros":
            return jnp.zeros((r,) + shape, cfg.param_dtype)
        if callable(init):
            make = init
        else:
            make = lambda kk: jax.random.normal(kk, shape) * init[1]
        return jnp.stack([make(kk) for kk in jax.random.split(k, r)]
                         ).astype(cfg.param_dtype)

    segments = []
    for si, (pattern, r) in enumerate(cfg.stack_plan()):
        seg = []
        for pi, kind in enumerate(pattern):
            k = jax.random.fold_in(jax.random.fold_in(keys[0], si), pi)
            seg.append({n: leaf(jax.random.fold_in(k, i), r, shape, init)
                        for i, (n, (shape, _, init)) in enumerate(
                            _layer_shapes(cfg, kind).items())})
        segments.append(seg)
    params: Params = {
        "embed": (jax.random.normal(keys[7], (V, d)) * 0.02).astype(cfg.param_dtype),
        "final_norm": getattr(jnp, _unit_norm_init(cfg))((d,),
                                                         cfg.param_dtype),
        "layers": _stored(segments, cfg),
    }
    if cfg.norm == "layernorm":
        params["final_norm_b"] = jnp.zeros((d,), cfg.param_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(keys[8], (d, V)) * 0.02
                             ).astype(cfg.param_dtype)
    if cfg.positional == "learned":
        params["pos_embed"] = (
            jax.random.normal(keys[9], (cfg.max_seq_len, d)) * 0.02
        ).astype(cfg.param_dtype)
    for i, (n, (shape, _, init)) in enumerate(_exit_shapes(cfg).items()):
        params[n] = leaf(jax.random.fold_in(keys[10], i), 1, shape, init)[0]
    return params


def param_logical_specs(cfg: TransformerConfig) -> Params:
    """Pytree of logical axis names matching init_params' structure
    (consumed by parallel.sharding.tree_shardings)."""
    # A leaf's leading dim is the layer stack: logical axis "layers" maps
    # onto the `pipe` mesh axis so each pipeline stage holds a contiguous
    # range of layers (parallel/pipeline.py).
    segments = [[{n: ("layers",) + axes for n, (_, axes, _) in
                  _layer_shapes(cfg, kind).items()}
                 for kind in pattern] for pattern, _ in cfg.stack_plan()]
    specs: Params = {
        "embed": ("vocab", "embed"),
        "final_norm": (None,),
        "layers": _stored(segments, cfg),
    }
    if cfg.norm == "layernorm":
        specs["final_norm_b"] = (None,)
    if not cfg.tie_embeddings:
        specs["lm_head"] = ("embed", "vocab")
    if cfg.positional == "learned":
        specs["pos_embed"] = (None, "embed")
    specs.update({n: axes for n, (_, axes, _) in _exit_shapes(cfg).items()})
    return specs


def _norm(x, w, b, kind: str, eps: Optional[float] = None,
          offset: float = 0.0):
    """`eps` None: 1e-6 (rmsnorm) / 1e-5 (layernorm); callers pass
    cfg.norm_eps. `offset` (rmsnorm; `TransformerConfig.norm_offset`): the
    weight is held zero-centred and `offset + w` multiplies; at 0.0 nothing
    is traced for it."""
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        x2 = jnp.mean(xf * xf, axis=-1, keepdims=True)
        wf = w.astype(jnp.float32)
        out = xf * jax.lax.rsqrt(x2 + (1e-6 if eps is None else eps)
                                 ) * (wf + offset if offset else wf)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + (1e-5 if eps is None else eps)
                                        ) * w.astype(jnp.float32)
        if b is not None:
            out = out + b.astype(jnp.float32)
    return out.astype(x.dtype)


def yarn_ramp(half: int, theta: float, original_len: int, beta_fast: float,
              beta_slow: float) -> np.ndarray:
    """YaRN's blend r_i of pair i of `half`: 0 where the pair turns more
    than `beta_fast` times in `original_len` positions (its frequency is
    kept), 1 where it turns less than `beta_slow` times (its frequency is
    divided by the factor), linear between; the two bounds truncated to
    whole pairs."""
    at = lambda turns: (2 * half * math.log(original_len / (turns * 2 * math.pi))
                        / (2 * math.log(theta)))
    low = max(math.floor(at(beta_fast)), 0)
    high = min(math.ceil(at(beta_slow)), 2 * half - 1)
    return np.clip((np.arange(half, dtype=np.float32) - low)
                   / max(high - low, 0.001), 0.0, 1.0)


def _rope(x: jax.Array, positions: jax.Array, theta: float,
          yarn=None, sections=None) -> jax.Array:
    """Rotary embedding over the last dim of [B, S, H, D]. `yarn`
    (`TransformerConfig.rope_yarn`): blended inverse frequencies, and cos
    and sin times the attention factor. Frequencies, angles and the factor
    are float32: at position 16,383 an angle in bfloat16 is radians off.
    `sections` (`TransformerConfig.rope_sections`) with `positions`
    [3, B, S]: pair i turns by the stream of its section (M-RoPE), the
    sections contiguous; positions [B, S] are the one stream of all."""
    D = x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if yarn is not None:
        factor, original_len, beta_fast, beta_slow, attn_factor = yarn
        r = yarn_ramp(half, theta, original_len, beta_fast, beta_slow)
        freqs = freqs * (1.0 - r) + freqs / factor * r
    if positions.ndim == 3:
        if sections is None:
            raise ValueError("positions [3, B, S] need rope_sections")
        ends = np.cumsum((0,) + tuple(sections))
        angles = jnp.concatenate(
            [positions[i, :, :, None].astype(jnp.float32) * freqs[a:b]
             for i, (a, b) in enumerate(zip(ends[:-1], ends[1:]))], axis=-1)
    else:
        angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if yarn is not None:
        cos, sin = cos * attn_factor, sin * attn_factor
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _rope_first(x: jax.Array, rot: int, positions: jax.Array, theta: float,
                yarn=None, sections=None) -> jax.Array:
    """`_rope` on the first `rot` columns of every head, the rest passed
    (`TransformerConfig.rope_fraction`); all of them: `_rope` itself."""
    if rot == x.shape[-1]:
        return _rope(x, positions, theta, yarn, sections)
    return jnp.concatenate(
        [_rope(x[..., :rot], positions, theta, yarn, sections), x[..., rot:]],
        axis=-1)


def _w(layer: Params, name: str, cfg: TransformerConfig) -> jax.Array:
    """Weight access for the layer helpers: compute-dtype view,
    transparently dequantizing int8 weight-only params
    (models/quantize.py) when a scale sibling is present."""
    from .quantize import maybe_dequant

    return maybe_dequant(layer, name, cfg.dtype)


def _qkv_proj(cfg: TransformerConfig, h: jax.Array, layer: Params,
              positions: jax.Array, mixer: str = "attn",
              overlap: Optional[tp.Overlap] = None):
    """Projection + rope shared by training forward and KV-cache decode
    (models/generate.py) — ONE home for the layer's q/k/v convention. The
    rotation is the kind's (`cfg.attn_rope`): an "attn" layer's takes cfg's
    YaRN scaling, a "swa" layer's never does and may have a theta and a
    share of the head of its own. `overlap` (a layer body whose residual's
    rows are cut over `tensor`): h's rows are gathered behind the
    products."""
    eqs = {n: eq for n, eq in (("wqkv", "bsd,dcnh->bscnh"),
                               ("wq", "bsd,dnh->bsnh"),
                               ("wkv", "bsd,dcnh->bscnh")) if n in layer}
    outs = None
    if overlap is not None:
        axes = _attn_shapes(cfg, mixer)
        outs = overlap.gather_matmul(
            "qkv", h, [(eq, _w(layer, n, cfg), axes[n][1])
                       for n, eq in eqs.items()])
    if outs is None:
        outs = [jnp.einsum(eq, h, _w(layer, n, cfg)) for n, eq in eqs.items()]
    if cfg.attn_bias:
        outs = [o + layer[n + "_b"].astype(o.dtype) for o, n in zip(outs, eqs)]
    if "wqkv" in layer:
        qkv = checkpoint_name(outs[0], "qkv_proj")
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    elif "wkv" not in layer:  # "xattn": an earlier layer's keys and values
        return outs[0], None, None  # unrotated: a NoPE stack's kind
    else:
        q = outs[0]
        kv = checkpoint_name(outs[1], "qkv_proj")
        k, v = kv[:, :, 0], kv[:, :, 1]
    if cfg.attn_qk_norm:
        q, k = (_norm(x, layer[n], None, "rmsnorm", cfg.norm_eps,
                      cfg.norm_offset)
                for x, n in ((q, "q_norm"), (k, "k_norm")))
    if cfg.positional == "rope":
        theta, rot, yarn = cfg.attn_rope(mixer)
        q = _rope_first(q, rot, positions, theta, yarn, cfg.rope_sections)
        k = _rope_first(k, rot, positions, theta, yarn, cfg.rope_sections)
    return q, k, v


def _mlp_block(cfg: TransformerConfig, ffn: str, h: jax.Array,
               layer: Params, overlap: Optional[tp.Overlap] = None):
    """Post-mixer feed-forward of kind `ffn`, shared with the decode path ->
    (delta, extras): {} for the dense MLP (SwiGLU where the layer has a
    fused gate/up leaf, else GELU), {"aux": balancing loss} for GShard
    experts, the routing counters of ops/moe.py `moe_ffn_held` for a held
    range of them (sigmoid- or softmax-routed). `overlap` (as `_qkv_proj`;
    the dense MLP only): h and the delta have their rows cut over `tensor`,
    gathered and scattered behind the two products."""
    if ffn == "dense":
        if "w_gate_up" in layer:
            w_in, eq = "w_gate_up", "bsd,dcf->bscf"

            def act(gu):
                gu = checkpoint_name(gu, "gate_up")
                return jax.nn.silu(gu[:, :, 0]) * gu[:, :, 1]
        else:
            w_in, eq = "w_up", "bsd,df->bsf"
            act = lambda u: jax.nn.gelu(checkpoint_name(u, "gate_up"))
        delta = None
        if overlap is not None:
            axes = _ffn_shapes(cfg, "dense")
            delta = overlap.gathered_mlp(
                ("gate_up", "w_down"), h, eq, _w(layer, w_in, cfg),
                axes[w_in][1], act, _w(layer, "w_down", cfg),
                axes["w_down"][1])
        if delta is None:
            delta = act(jnp.einsum(eq, h, _w(layer, w_in, cfg))
                        ) @ _w(layer, "w_down", cfg)
        return delta, {}
    if not cfg.moe_holds_range:
        from ray_tpu.ops.moe import moe_ffn

        delta, aux = moe_ffn(
            h, layer["router"], layer["moe_w_gate_up"], layer["moe_w_down"],
            experts_per_token=cfg.moe_experts_per_token,
            capacity_factor=cfg.moe_capacity_factor,
            dtype=cfg.dtype)
        return delta, {"aux": aux}
    from ray_tpu.ops import moe

    if cfg.moe_router == "sigmoid":
        route = functools.partial(
            moe.sigmoid_route, bias=layer["router_bias"],
            experts_per_token=cfg.moe_experts_per_token,
            routed_scale=cfg.moe_routed_scale)
    else:
        route = functools.partial(
            moe.softmax_route, experts_per_token=cfg.moe_experts_per_token)
    delta, counters = moe.moe_ffn_held(
        h, layer["router"], layer["moe_w_gate_up"], layer["moe_w_down"],
        route=route, held_first=cfg.moe_held_range[0], dtype=cfg.dtype)
    shared = {n[len("shared_"):]: a for n, a in layer.items()
              if n.startswith("shared_w_")}
    if shared and cfg.moe_shared_gate:  # times sigmoid(w . x), float32
        with jax.named_scope("moe.shared"):
            y = _mlp_block(cfg, "dense", h, shared)[0]
            gate = jax.nn.sigmoid(jnp.einsum(
                "bsd,d->bs", h, _w(layer, "shared_gate", cfg),
                preferred_element_type=jnp.float32))
            delta = delta + y * gate[..., None].astype(y.dtype)
    elif shared:  # the always-on experts: one dense SwiGLU of their joint width
        delta = delta + _mlp_block(cfg, "dense", h, shared)[0]
    return delta, counters


# The mixers, each a `Mixer.apply`.


def _kda_mixer(cfg, kind, h, layer, positions, overlap):
    from ray_tpu.ops.kda import gated_norm, kda_chunked, mixer_conv

    f32 = jnp.float32

    def proj(n, l2):  # convolution, SiLU and the norm: one call a tensor
        y = jnp.einsum("bsd,dnh->bsnh", h, _w(layer, "kda_w" + n, cfg))
        return mixer_conv(y, layer["kda_conv_" + n], l2=l2, scope="kda")

    q, k, v = proj("q", True), proj("k", True), proj("v", False)

    def low_rank(n):
        return jnp.einsum("bsr,rnh->bsnh", h @ _w(layer, f"kda_w{n}1", cfg),
                          _w(layer, f"kda_w{n}2", cfg))

    g = -jnp.exp(layer["kda_A_log"].astype(f32))[:, None] * jax.nn.softplus(
        low_rank("f").astype(f32) + layer["kda_dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(jnp.einsum(
        "bsd,dn->bsn", h, _w(layer, "kda_wb", cfg)).astype(f32))
    with jax.named_scope("kda.core"):
        o, _ = kda_chunked(q, k, v, g, beta, chunk=cfg.kda_chunk)
    o = gated_norm(o, low_rank("g"), layer["kda_o_norm"], group=o.shape[-1],
                   gate_act="sigmoid", gate_first=False, eps=cfg.norm_eps,
                   scope="kda")
    return jnp.einsum("bsnh,nhd->bsd", o, _w(layer, "kda_wo", cfg)), None, None


def _gdn_mixer(cfg, kind, h, layer, positions, overlap):
    """Gated DeltaNet: the delta rule of ops/kda.py at one decay a value
    head, `gdn_k_heads` key heads serving `gdn_v_heads` value heads. The
    output's norm is over a head's columns with a plain weight (never
    zero-centred), then times SiLU(z)."""
    from ray_tpu.ops.kda import gated_norm, kda_chunked, mixer_conv

    f32 = jnp.float32

    # A joint leaf's halves come out of its product one after the other
    # ([2,B,S,n,h]), so each lies whole as its reader takes it: a column
    # slice of [B,S,2,n,h] is a copy. (The leaf itself is not sliced: weights
    # sliced inside the scanned layer gave the first layer's experts wrong
    # gradients on the chip, PERF.md section 6, PR 52.)
    q, k = jnp.einsum("bsd,dcnh->cbsnh", h, _w(layer, "gdn_wqk", cfg))
    v, z = jnp.einsum("bsd,dcnh->cbsnh", h, _w(layer, "gdn_wvz", cfg))
    ba = jnp.einsum("bsd,dcn->bscn", h, _w(layer, "gdn_wba", cfg))
    conv_qk = layer["gdn_conv_qk"]
    q = mixer_conv(q, conv_qk[:, 0], l2=True, scope="gdn")
    k = mixer_conv(k, conv_qk[:, 1], l2=True, scope="gdn")
    v = mixer_conv(v, layer["gdn_conv_v"], scope="gdn")
    beta = jax.nn.sigmoid(ba[:, :, 0].astype(f32))
    g = -jnp.exp(layer["gdn_A_log"].astype(f32)) * jax.nn.softplus(
        ba[:, :, 1].astype(f32) + layer["gdn_dt_bias"].astype(f32))
    with jax.named_scope("gdn.core"):
        o, _ = kda_chunked(q, k, v, g, beta, chunk=cfg.gdn_chunk)
    o = gated_norm(o, z, layer["gdn_o_norm"], group=o.shape[-1],
                   gate_act="silu", gate_first=False, eps=cfg.norm_eps,
                   scope="gdn")
    return jnp.einsum("bsnh,nhd->bsd", o, _w(layer, "gdn_wo", cfg)), None, None


def _mamba_mixer(cfg, kind, h, layer, positions, overlap):
    from ray_tpu.ops.kda import gated_norm, mixer_conv
    from ray_tpu.ops.ssd import ssd_chunked

    f32 = jnp.float32
    # z, then x, each whole (`_gdn_mixer`'s note)
    z, x = jnp.einsum("bsd,dcnp->cbsnp", h, _w(layer, "mamba_wzx", cfg))
    bc = jnp.einsum("bsd,dcgn->bscgn", h, _w(layer, "mamba_wbc", cfg))
    dt = jnp.einsum("bsd,dn->bsn", h, _w(layer, "mamba_wdt", cfg))

    def conv(y, n):
        return mixer_conv(y, layer["mamba_conv_" + n],
                          layer[f"mamba_conv_{n}_b"], scope="mamba")

    x, bc = conv(x, "x"), conv(bc, "bc")
    dt = jax.nn.softplus(dt.astype(f32) + layer["mamba_dt_bias"].astype(f32))
    with jax.named_scope("ssd.core"):
        y, _ = ssd_chunked(x, dt, -jnp.exp(layer["mamba_A_log"].astype(f32)),
                           bc[:, :, 0], bc[:, :, 1], layer["mamba_D"],
                           chunk=cfg.mamba_chunk)
    # The gated norm: times silu(z) first, then RMSNorm over a group's
    # channels (all of them where there is one group), float32.
    y = gated_norm(y, z, layer["mamba_norm"],
                   group=layer["mamba_norm"].size // cfg.mamba_groups,
                   gate_act="silu", gate_first=True, eps=cfg.norm_eps,
                   scope="mamba")
    return (jnp.einsum("bsnp,npd->bsd", y, _w(layer, "mamba_wo", cfg)),
            None, None)


def _mla_mixer(cfg, kind, h, layer, positions, overlap):
    """Latent attention. Where the stack's `positional` is "rope" the last
    `qk_rope_head_dim` columns of every query head and the one key part the
    heads share are rotated (decoupled RoPE, plain theta), the key part once,
    before it is broadcast over the heads; what comes out of the latent is
    not. The rotated columns are held as halves, as `_rope` rotates them: a
    checkpoint that pairs neighbouring columns is turned where it is loaded
    (docs/model_layers.md). Any other `positional`: no rotation (NoPE)."""
    lat, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = jnp.einsum("bsd,dnh->bsnh", h, _w(layer, "mla_wq", cfg))
    ckr = h @ _w(layer, "mla_wkva", cfg)               # [B,S,lat+rope]
    c = _norm(ckr[..., :lat], layer["mla_kv_norm"], None, "rmsnorm", cfg.norm_eps)
    kv = jnp.einsum("bsl,lnh->bsnh", c, _w(layer, "mla_wkvb", cfg))
    kr = ckr[:, :, None, lat:]                         # [B,S,1,rope]
    if cfg.mla_rotates:
        with jax.named_scope("mla.rope"):
            q = jnp.concatenate(
                [q[..., :nope],
                 _rope(q[..., nope:], positions, cfg.rope_theta)], axis=-1)
            kr = _rope(kr, positions, cfg.rope_theta)
    kr = jnp.broadcast_to(kr, kv.shape[:3] + (cfg.qk_rope_head_dim,))
    k = jnp.concatenate([kv[..., :nope], kr], axis=-1)
    q = maybe_constrain(q, ("batch", "seq_act", "heads", None))
    o = attention(q, k, kv[..., nope:], causal=True)   # / sqrt(nope + rope)
    return jnp.einsum("bsnh,nhd->bsd", o, _w(layer, "mla_wo", cfg)), None, None


def _diff_attend(cfg, layer, q, k, v, window):
    """Differential attention (`cfg.diff_attn`) on q [B,S,H,hd], k, v
    [B,S,KVH,hd] -> [B,S,H*hd]: two softmax maps through the same kernels
    as any layer's, each H/2 query heads over KVH/2 key heads of hd and
    the SAME values, KVH/2 heads of 2 hd; the subtraction, the norm and
    its (1 - lam0) in float32."""
    f32 = jnp.float32
    B, S, H, hd = q.shape
    pairs = lambda a: a.reshape(B, S, a.shape[2] // 2, 2, hd)
    q, k = pairs(q), pairs(k)
    vv = v.reshape(B, S, v.shape[2] // 2, 2 * hd)
    o1, o2 = (attention(q[:, :, :, c], k[:, :, :, c], vv, causal=True,
                        window=window).astype(f32) for c in (0, 1))
    lam = layer["diff_lam"].astype(f32)
    index = (cfg.layer_offset + layer["_index"]).astype(f32)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * index)
    lam = (jnp.exp(jnp.sum(lam[0] * lam[1]))
           - jnp.exp(jnp.sum(lam[2] * lam[3])) + lam0)
    o = _norm(o1 - lam * o2, layer["diff_norm"], None, "rmsnorm", 1e-5)
    return (o * (1.0 - lam0)).astype(q.dtype).reshape(B, S, H * hd)


def _attn_mixer(cfg, kind, h, layer, positions, overlap, handed=None):
    """Softmax attention ("attn", or "swa" under its window and with the
    heads and rotation `cfg` gives that kind, or "xattn" over the keys and
    values `handed` holds) -> (delta, k, v). Under `cfg.diff_attn` two maps
    and their difference (`_diff_attend`), everything of the mixer under the
    scope `diffattn` (the row's). Where
    `cfg.attn_gated`, what a plain layer lacks (the q / k norms, a rotation
    of part of a head, the output's gate a column or a head) runs under the
    scope `gattn.gate`; at the defaults nothing of it is traced. One
    `attn.plan` observation a traced layer body says what the layer is."""
    mixer = kind[0]
    B, S, _ = h.shape
    swa = mixer == "swa"
    theta, rot, yarn = cfg.attn_rope(mixer)
    tracing.observe(
        "attn.plan", 0, slow=False, kind=mixer, heads=cfg.attn_heads(mixer),
        kv_heads=cfg.kv_heads, window=cfg.sliding_window if swa else 0,
        rotated=rot if cfg.positional == "rope" else 0, theta=theta,
        yarn=yarn[0] if yarn else 0,
        gate=("column" if cfg.attn_out_gate else
              "head" if cfg.attn_head_gate else "none"))
    gated = lambda: (jax.named_scope("gattn.gate") if cfg.attn_gated
                     else contextlib.nullcontext())
    with gated():
        q, k, v = _qkv_proj(cfg, h, layer, positions, mixer, overlap)
    if mixer == "xattn":
        k, v = handed["k"], handed["v"]
    q = maybe_constrain(q, ("batch", "seq_act", "heads", None))
    if cfg.diff_attn:
        attend = lambda window=None: _diff_attend(cfg, layer, q, k, v, window)
    else:
        attend = functools.partial(attention, q, k, v, causal=True,
                                   scale=cfg.attn_scale)
    if swa:  # the scope tells its kernels from a full layer's
        with jax.named_scope("swa"):
            o = attend(window=cfg.sliding_window)
    else:
        o = attend()
    if cfg.attn_out_gate:
        with gated():
            gate = jnp.einsum("bsd,dnh->bsnh", _whole(h, overlap),
                              _w(layer, "wq_gate", cfg))
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
    if cfg.attn_head_gate:
        with gated():
            gate = jnp.einsum("bsd,dn->bsn", _whole(h, overlap),
                              _w(layer, "w_head_gate", cfg),
                              preferred_element_type=jnp.float32)
            o = o * jax.nn.sigmoid(gate)[..., None].astype(o.dtype)
    o, wo, delta = o.reshape(B, S, -1), _w(layer, "wo", cfg), None
    if overlap is not None:
        delta = overlap.matmul_scatter(
            "wo", o, "bsf,fd->bsd", wo, _attn_shapes(cfg, mixer)["wo"][1])
    if delta is None:
        delta = o @ wo
    if cfg.attn_bias:
        delta = delta + layer["wo_b"].astype(delta.dtype)
    return delta, k, v


def _mamba1_mixer(cfg, kind, h, layer, positions, overlap):
    """Mamba-1 -> (delta, None, None, {"m": y}): y the scan's output before
    the gate, what a later `gmu` layer reads. dt (its bias inside the
    softplus), A and the scan are float32."""
    from ray_tpu.ops.kda import mixer_conv
    from ray_tpu.ops.selective_scan import selective_scan

    f32 = jnp.float32
    N, R = cfg.mamba1_d_state, cfg.mamba1_rank
    # u, then z, each whole (`_gdn_mixer`'s note)
    u, z = jnp.einsum("bsd,dcf->cbsf", h, _w(layer, "mamba1_win", cfg))
    u = mixer_conv(u, layer["mamba1_conv"], layer["mamba1_conv_b"],
                   scope="mamba1")
    rbc = u @ _w(layer, "mamba1_wx", cfg)              # [r | B_t | C_t]
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rf->bsf", rbc[..., :R], _w(layer, "mamba1_wdt", cfg),
                   preferred_element_type=f32)
        + layer["mamba1_dt_bias"].astype(f32))
    with jax.named_scope("mamba1.core"):
        y = selective_scan(
            u, dt, -jnp.exp(layer["mamba1_A_log"].astype(f32)),
            rbc[..., R:R + N], rbc[..., R + N:], layer["mamba1_D"],
            chunk=cfg.mamba1_chunk).astype(h.dtype)
    delta = (y * jax.nn.silu(z)) @ _w(layer, "mamba1_wo", cfg)
    return delta, None, None, {"m": y}


def _gmu_mixer(cfg, kind, h, layer, positions, overlap, handed=None):
    """A Gated Memory Unit: the handed-on memory `m` [B,S,inner] gated by
    this layer's own input, (m * silu(h W_1)) W_2."""
    gate = jax.nn.silu(h @ _w(layer, "gmu_w1", cfg))
    return (handed["m"] * gate) @ _w(layer, "gmu_w2", cfg), None, None


def _shortconv_mixer(cfg, kind, h, layer, positions, overlap):
    """A double-gated short convolution: the joint projection's [B,S,3d]
    output goes to the core as it lies ([Bg ; Cg ; x]: neither it nor the
    leaf is sliced here, `_gdn_mixer`'s note), and the core's output to
    W_out. No activation anywhere."""
    from ray_tpu.ops.shortconv import gated_conv

    w_in = _w(layer, "shortconv_win", cfg)
    p = h @ w_in.reshape(w_in.shape[0], -1)
    y = gated_conv(p, layer["shortconv_conv"])
    return y @ _w(layer, "shortconv_wout", cfg), None, None


def _dsa_mixer(cfg, kind, h, layer, positions, overlap):
    """Learned sparse attention -> (delta, k, v, extras): the "attn" layer's
    projections (`_qkv_proj`), the indexer on the detached input, the
    selection and attention over it (ops/sparse_attention.py). extras:
    `dsa_kl` the layer's mean KL (the indexer's loss, `loss_fn` adds it),
    `dsa_selected` the kept (query, key) pairs, `dsa_select_passes` and
    `dsa_select_fallback` the search's counting passes (in eighths) and its
    blocks off the short way, `dsa_bits` the selection (the kernels' kept
    residual; `loss_fn(with_selection=True)` returns it, any other program
    drops it)."""
    from ray_tpu.ops import sparse_attention as sa
    from ray_tpu.ops.flash_attention import grid_steps

    dispatch.one_chip("a learned-sparse-attention (dsa) layer's kernels")
    B, S, _ = h.shape
    HI, dI = cfg.dsa_index_heads, cfg.dsa_index_head_dim
    pn = sa.plan(S)
    steps, idle = grid_steps(S, pn.bq, pn.bk, True)
    tracing.observe(
        "dsa.plan", 0, slow=False, seq=S, topk=cfg.dsa_topk, impl="threshold",
        index_heads=HI, index_dim=dI, grid_steps=steps, grid_steps_idle=idle,
        **pn._asdict())
    q, k, v = _qkv_proj(cfg, h, layer, positions, "dsa")
    q = maybe_constrain(q, ("batch", "seq_act", "heads", None))
    with jax.named_scope("dsa.index"):
        hs = jax.lax.stop_gradient(h)
        first = positions[0] if positions.ndim == 3 else positions
        qi = jnp.einsum("bsd,dnh->bsnh", hs, _w(layer, "dsa_wq", cfg))
        ki = _norm(hs @ _w(layer, "dsa_wk", cfg), layer["dsa_k_norm"],
                   layer["dsa_k_norm_b"], "layernorm", cfg.norm_eps)
        qi = _rope_first(qi, dI // 2, first, cfg.rope_theta)
        ki = _rope_first(ki[:, :, None], dI // 2, first, cfg.rope_theta)[:, :, 0]
        w = jnp.einsum("bsd,dn->bsn", hs, _w(layer, "dsa_ww", cfg),
                       preferred_element_type=jnp.float32) * (HI * dI) ** -0.5
    o, kl, count, bits, passes, way = sa.sparse_attention(
        q, k, v, qi, ki, w, topk=cfg.dsa_topk,
        scale=cfg.attn_scale or cfg.head_dim ** -0.5)
    extras = {"dsa_kl": kl.mean(), "dsa_selected": count.sum(),
              "dsa_bits": bits, "dsa_select_passes": passes.sum(),
              "dsa_select_fallback": (way != 0).sum()}
    return o.reshape(B, S, -1) @ _w(layer, "wo", cfg), k, v, extras


# The indexer's loss in the stack's: L = L_LM + DSA_LOSS_COEF x sum over the
# "dsa" layers of their mean KL (one value in use, so no field).
DSA_LOSS_COEF = 1.0


def _swa_flops(cfg: TransformerConfig, S: int) -> float:
    W = min(cfg.sliding_window, S)  # the band's pairs for the triangle's
    return (6.0 * (2 * W - W * (W - 1) / S) * cfg.attn_heads("swa")
            * cfg.head_dim)


def _mamba_flops(cfg: TransformerConfig, S: int) -> float:
    C, N = cfg.mamba_chunk, cfg.mamba_d_state
    return 3.0 * (cfg.mamba_groups * C * N  # C B^T, lower
                  + cfg.mamba_inner * (C + 4 * N))


# A head's operations a token of the chunked delta rule (ops/kda.py): n = 5
# at a decay a channel; 3 a value head at a scalar's, K K^T, Q K^T a key head.
_delta_flops = lambda C, hd, n: 6 * hd * hd + C * n * hd + C * C / 3
# A gated layer runs under a scope that tells its kernels from a plain one's.
_GATTN = lambda cfg: ("diffattn" if cfg.diff_attn else
                      "gattn" if cfg.attn_gated else None)
# A differential map multiplies into values twice as wide: 1.5 x a plain one.
_DIFF = lambda cfg: 1.5 if cfg.diff_attn else 1.0
_NO_CACHE = (
    "decode holds keys and values of one length a layer only: a stack with "
    "KDA / MLA / Mamba-2 / windowed layers trains but does not serve yet")

MIXERS: Dict[str, Mixer] = {m.name: m for m in (
    Mixer("attn", None, _attn_shapes, _attn_mixer,
          lambda cfg, S: _DIFF(cfg) * 3.0 * S * cfg.n_heads * 2 * cfg.head_dim,
          scope=_GATTN, cut_rows=True, writes=("k", "v")),
    Mixer("swa", "swa_layers", functools.partial(_attn_shapes, mixer="swa"),
          _attn_mixer, lambda cfg, S: _DIFF(cfg) * _swa_flops(cfg, S),
          scope=_GATTN, cut_rows=True, no_decode=_NO_CACHE),
    Mixer("mla", "mla_layers", _mla_shapes, _mla_mixer,
          lambda cfg, S: 3.0 * S * cfg.n_heads * (
              cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim),
          scope=lambda cfg: "mla", no_decode=_NO_CACHE),
    Mixer("kda", "kda_layers", _kda_shapes, _kda_mixer,
          lambda cfg, S: 3.0 * cfg.kda_n_heads * _delta_flops(
              cfg.kda_chunk, cfg.kda_head_dim, 5),
          scope=lambda cfg: "kda", no_decode=_NO_CACHE),
    Mixer("mamba2", "mamba_layers", _mamba_shapes, _mamba_mixer, _mamba_flops,
          scope=lambda cfg: "mamba", no_decode=_NO_CACHE),
    Mixer("gdn", "gdn_layers", _gdn_shapes, _gdn_mixer,
          lambda cfg, S: 3.0 * (
              cfg.gdn_k_heads * 2 * cfg.gdn_chunk * cfg.gdn_head_dim
              + cfg.gdn_v_heads * _delta_flops(
                  cfg.gdn_chunk, cfg.gdn_head_dim, 3)),
          scope=lambda cfg: "gdn", no_decode=(
              "decode cannot serve a Gated DeltaNet (gdn) layer: it would "
              "hold a delta-rule state and the convolution's last tokens in "
              "place of keys and values (ROADMAP R7 / R9); the stack trains "
              "but does not serve yet")),
    # What the algorithm needs a token: attention over the kept sets and the
    # indexer's scores of every earlier key (chipbench/reduce/
    # keye_vl2_counts.py), not what the thresholded kernels spend.
    Mixer("dsa", "dsa_layers", _dsa_shapes, _dsa_mixer,
          lambda cfg, S: 3.0 * (
              2 * cfg.head_dim * cfg.n_heads * 2 * sum(
                  min(t + 1, cfg.dsa_topk) for t in range(S)) / S
              + 2 * cfg.dsa_index_heads * cfg.dsa_index_head_dim * (S + 1) / 2),
          scope=lambda cfg: "dsa", no_decode=(
              "decode cannot serve a learned-sparse-attention (dsa) layer: a "
              "tick would score the slab's keys with the indexer (a cache of "
              "indexer keys beside the key / value slab) and attend to its "
              "top-k (ROADMAP R22 (a)); the stack trains but does not serve "
              "yet")),
    Mixer("xattn", "xattn_layers",
          functools.partial(_attn_shapes, mixer="xattn"), _attn_mixer,
          lambda cfg, S: _DIFF(cfg) * 3.0 * S * cfg.n_heads * 2 * cfg.head_dim,
          scope=_GATTN, reads=("k", "v"), no_decode=(
              "decode cannot serve a cross-attention (xattn) layer: its keys "
              "and values are an earlier layer's, one slab that several "
              "layers read, where the cache holds a slab a layer (ROADMAP "
              "R22 (h)); the stack trains but does not serve yet")),
    Mixer("mamba1", "mamba1_layers", _mamba1_shapes, _mamba1_mixer,
          # the scan's multiply-adds (no matrix product holds them):
          # decay, input and read-out a state, forward and backward
          lambda cfg, S: 3.0 * 6 * cfg.mamba1_channels * cfg.mamba1_d_state,
          scope=lambda cfg: "mamba1", writes=("m",), no_decode=(
              "decode cannot serve a Mamba-1 (mamba1) layer: it would hold a "
              "state a channel and the convolution's last tokens in place of "
              "keys and values, and hand its scan's output on to the layers "
              "that read it (ROADMAP R7 / R22 (h)); the stack trains but "
              "does not serve yet")),
    Mixer("gmu", "gmu_layers", _gmu_shapes, _gmu_mixer, lambda cfg, S: 0.0,
          scope=lambda cfg: "gmu", reads=("m",), no_decode=(
              "decode cannot serve a Gated Memory Unit (gmu) layer: a tick "
              "would read the memory an earlier layer made for the same "
              "token, which no cache hands from layer to layer (ROADMAP R22 "
              "(h)); the stack trains but does not serve yet")),
    # The two projections are matmul parameters (6 a token each, counted
    # with every other leaf); the taps and the gates are no matmuls.
    Mixer("shortconv", "shortconv_layers", _shortconv_shapes,
          _shortconv_mixer, lambda cfg, S: 0.0,
          scope=lambda cfg: "shortconv", no_decode=(
              "decode cannot serve a gated short convolution (shortconv) "
              "layer: a tick would hold the last taps - 1 = 2 rows of "
              "Bg * x a slot and layer in place of keys and values "
              "(ROADMAP R7); the stack trains but does not serve yet")),
)}
_PLAIN = next(n for n, m in MIXERS.items() if m.layers_field is None)


def _whole(x: jax.Array, overlap: Optional[tp.Overlap]) -> jax.Array:
    """x with its rows gathered where a plan has cut them over `tensor`."""
    return x if overlap is None else maybe_constrain(x, tp.ACTIVATION)


def _scaled(x: jax.Array, scale: float) -> jax.Array:
    """x * scale in x's dtype; at 1.0 x itself, so that nothing is traced
    for a configuration without the scalar."""
    return x if scale == 1.0 else x * jnp.asarray(scale, x.dtype)


def _layer_body(cfg: TransformerConfig, kind: Tuple[str, str], x: jax.Array,
                layer: Params, positions: jax.Array,
                handed: Optional[Dict[str, jax.Array]] = None):
    """One layer of `kind` -> (x, extras, k, v, made): extras as `_mlp_block`
    gives them, k and v as the mixer does (`Mixer.apply`; the prefill of
    models/generate.py primes its cache from them). `handed`: the tensors
    earlier layers handed on, of which the mixer gets those its row `reads`;
    `made`: every tensor its row `writes`, by name (the caller keeps the
    ones `handed_plan` says a later layer reads)."""
    mixer, ffn = kind
    B, S, _ = x.shape
    # Under a mesh with a `tensor` axis the residual's rows are cut over it
    # (parallel/tensor_overlap.py): norms and adds work a rank's rows, as do
    # a `Mixer.cut_rows` mixer and the dense MLP; any other gets them gathered
    # (`whole`) and leaves its sum over `tensor` to the partitioner.
    overlap = tp.plan(B, S)
    residual = tp.RESIDUAL if overlap else tp.ACTIVATION
    whole = functools.partial(_whole, overlap=overlap)
    h = _norm(x, layer["attn_norm"], layer.get("attn_norm_b"), cfg.norm,
              cfg.norm_eps, cfg.norm_offset)
    row = MIXERS[mixer]
    scope = row.scope(cfg)
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        # a mixer with a loss or counters of its own returns them fourth
        delta, k, v, *own = row.apply(
            cfg, kind, h if row.cut_rows else whole(h), layer, positions,
            overlap, **({"handed": {n: handed[n] for n in row.reads}}
                        if row.reads else {}))
    own = dict(own[0]) if own else {}
    made = {n: {"k": k, "v": v}[n] if n in ("k", "v") else own.pop(n)
            for n in row.writes}
    # `cfg.post_norm`: x + N2(f(N1(x))), the sublayer's output normed too.
    post = lambda delta, n: _norm(delta, layer[n], None, cfg.norm,
                                  cfg.norm_eps, cfg.norm_offset)
    if cfg.post_norm:
        delta = post(delta, "attn_post_norm")
    x = maybe_constrain(x + _scaled(delta, cfg.residual_scale), residual)
    h = _norm(x, layer["mlp_norm"], layer.get("mlp_norm_b"), cfg.norm,
              cfg.norm_eps, cfg.norm_offset)
    delta, extras = _mlp_block(cfg, ffn, h if ffn == "dense" else whole(h),
                               layer, overlap)
    if cfg.post_norm:
        delta = post(delta, "mlp_post_norm")
    x = maybe_constrain(x + _scaled(delta, cfg.residual_scale), residual)
    if overlap is not None:
        overlap.observe()
    if own:
        extras = {**extras, **own}
    return x, extras, k, v, made


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
    x = _scaled(tbl[tokens], cfg.embed_scale)
    x = maybe_constrain(x, ("batch", "seq_act", "embed"))
    if cfg.positional == "learned":
        x = x + params["pos_embed"].astype(cfg.dtype)[:S][None]
    if tp.plan(B, S) is not None:
        # The table is looked up for all of a rank's tokens and the rank's
        # rows cut from the result: the way back is a gather of dx, where a
        # lookup of a rank's rows alone would leave the table's gradient to
        # be summed over `tensor`. (Two constraints: the first keeps the
        # adds above on all the rows, and a position table's rows at home.)
        x = maybe_constrain(maybe_constrain(x, tp.ACTIVATION), tp.RESIDUAL)
    return x


class Carry(NamedTuple):
    """What a layer body takes from the layer before it and gives the next:
    the residual, and the tensors layers hand on by name (`Mixer.writes` /
    `Mixer.reads`: "m" [B,S,inner], "k" / "v" [B,S,KVH,hd], cfg.dtype). A
    body is given the names its layer reads and returns the names its layer
    hands on (`TransformerConfig.handed_plan`): for every stack without
    readers both are empty and the carry is the residual alone."""
    x: jax.Array
    handed: Dict[str, jax.Array]


def layer_scan_body(cfg: TransformerConfig, kind: Tuple[str, str],
                    positions: jax.Array):
    """(x, layer) -> (x, extras): one layer of `kind` under cfg's remat
    policy, for a layer that hands nothing on and reads nothing handed on:
    the body the pipeline-parallel stage apply (parallel/pipeline.py)
    shares with `_stack_once`."""
    body = carry_scan_body(cfg, kind, positions)

    def one(x, layer):
        carry, extras = body(Carry(x, {}), layer)
        return carry.x, extras

    return one


def carry_scan_body(cfg: TransformerConfig, kind: Tuple[str, str],
                    positions: jax.Array, writes: Tuple[str, ...] = ()):
    """(Carry, layer) -> (Carry, extras): one layer of `kind` under cfg's
    remat policy, the body `_stack_once` scans. The carry in holds what the
    layer reads, the carry out the `writes` among what it made. Under remat
    a body's inputs are kept as any layer's are: a handed-on tensor once a
    reader, with the readers' cotangents summed into it by autodiff."""
    def body(carry, layer):
        x, extras, _, _, made = _layer_body(cfg, kind, carry.x, layer,
                                            positions, carry.handed)
        return Carry(x, {n: made[n] for n in writes}), extras

    if not cfg.remat:
        return body
    # "full" recomputes what XLA makes and keeps what a hand-written kernel's
    # forward rule names, so the backward re-runs no such kernel. "dots" also
    # keeps every dot and the products inside a ring over `tensor` (a
    # `custom_vjp` hides its dots; they are products, not kernels).
    dots = cfg.remat_policy == "dots"
    names = dispatch.residual_names() + (tp.RESIDUAL_NAMES if dots else ())
    policy = jax.checkpoint_policies.save_only_these_names(*names)
    if dots:
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable, policy)
    tracing.observe("train.remat", 0, slow=False, policy=cfg.remat_policy,
                    kept=",".join(names))
    return jax.checkpoint(body, policy=policy)


def forward(params: Params, tokens: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, V] (f32)."""
    return forward_with_aux(params, tokens, cfg)[0]


def forward_with_aux(
    params: Params, tokens: jax.Array, cfg: TransformerConfig
) -> Tuple[jax.Array, jax.Array]:
    """forward + summed GShard load-balancing aux loss (0 for other stacks)."""
    x, extras = _backbone(params, tokens, cfg)
    return lm_head(params, x, cfg), extras.get("aux", jnp.zeros((), jnp.float32))


def _stack_once(params: Params, x: jax.Array, positions: jax.Array,
                cfg: TransformerConfig):
    """The segments in turn, each a scan over its repeats, every layer under
    the remat policy -> (x, the layers' extras, each leaf [repeats])."""
    per_layer, first, plan = [], 0, cfg.handed_plan()
    handed: Dict[str, jax.Array] = {}   # what later segments read, by name
    if any(r or w for r, w in plan):
        tracing.observe("train.carry", 0, slow=False, **_carry_facts(cfg, x))
    for (pattern, r), seg in zip(cfg.stack_plan(), stack_segments(params, cfg)):
        n = len(pattern)
        # A position hands on what any of its repeats does; what the segment
        # writes rides the scan's carry (zeros in, where nothing came before),
        # what it only reads is the scan's constant.
        writes = [tuple(dict.fromkeys(
            w for i in range(r) for w in plan[first + i * n + p][1]))
            for p in range(n)]
        reads = [plan[first + p][0] for p in range(n)]
        bodies = [carry_scan_body(cfg, kind, positions, w)
                  for kind, w in zip(pattern, writes)]
        if cfg.diff_attn:  # lam0 reads a layer's place in the stack
            seg = [dict(layers, _index=first + p + n * jnp.arange(r))
                   for p, layers in enumerate(seg)]
        carried = {w: handed[w] if w in handed else jnp.zeros(
            _handed_shape(cfg, w, x), cfg.dtype) for ws in writes for w in ws}

        def period(carry, layers, bodies=bodies, reads=reads, const=handed):
            x, have, outs = carry.x, {**const, **carry.handed}, []
            for body, rd, layer in zip(bodies, reads, layers):
                out, extras = body(Carry(x, {m: have[m] for m in rd}), layer)
                x, have = out.x, {**have, **out.handed}
                outs.append(extras)
            return Carry(x, {w: have[w] for w in carry.handed}), outs

        (x, made), outs = jax.lax.scan(period, Carry(x, carried), seg)
        first += n * r
        handed = {m: a for m, a in {**handed, **made}.items()
                  if any(m in rd for rd, _ in plan[first:])}  # still read
        per_layer.extend(e for e in outs if e)
    return x, per_layer


def _handed_shape(cfg: TransformerConfig, name: str, x: jax.Array):
    B, S, _ = x.shape
    return {"m": (B, S, cfg.mamba1_channels),
            "k": (B, S, cfg.kv_heads, cfg.head_dim),
            "v": (B, S, cfg.kv_heads, cfg.head_dim)}[name]


def _carry_facts(cfg: TransformerConfig, x: jax.Array) -> Dict[str, Any]:
    """What the stack carries beside the residual, for `train.carry`: every
    handed-on name with its shape, bytes, writers and readers (1-based
    layers, as the configuration lists them)."""
    plan = cfg.handed_plan()
    names = dict.fromkeys(n for _, w in plan for n in w)
    shapes = {n: _handed_shape(cfg, n, x) for n in names}
    size = jnp.dtype(cfg.dtype).itemsize
    return dict(
        names=",".join(names),
        shapes=";".join("x".join(map(str, shapes[n])) for n in names),
        bytes=sum(math.prod(shapes[n]) * size for n in names),
        writers=";".join(",".join(str(l + 1) for l, (_, w) in enumerate(plan)
                                  if n in w) for n in names),
        readers=";".join(",".join(str(l + 1) for l, (r, _) in enumerate(plan)
                                  if n in r) for n in names))


def _backbone(params: Params, tokens: jax.Array, cfg: TransformerConfig,
              read: Optional[Callable] = None,
              positions: Optional[jax.Array] = None):
    """Everything before the lm head: the stack (`_stack_once`), under
    `cfg.loop_steps` > 1 as many times over the same leaves, the final norm
    closing every pass (its output h the next pass's input). The passes are
    written out, each its own scans over the layers: a scan over the passes
    round them took 15% longer a step on the chip (PERF.md section 6, PR 49)
    and, with the plain head inside it, did not fit. -> (hidden [B,S,d]
    before the final norm, of the last pass; the stack's extras from its
    layers' (`_mlp_block`): {} for dense feed-forwards, {"aux": sum} for
    GShard, and for a held range of experts moe_assigned, moe_dropped,
    moe_past_buffer, moe_trips, moe_rows_worked (sums), moe_load_max,
    moe_window_rows (max), moe_load_mean (mean) over the expert layers'
    applications). `read` (a looped stack's head, `loss_fn`): called on every
    pass's h under the device scope `loop.head`, what it returns stacked
    over the passes is returned third. `positions` [3, B, S] (a stack with
    `rope_sections`): the three streams the rotations turn by; None: every
    token's place in its row."""
    B, S = tokens.shape
    x = embed_tokens(params, tokens, cfg)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
    if cfg.loop_steps == 1:
        x, per_layer = _stack_once(params, x, positions, cfg)
        return x, _stack_extras(per_layer)
    tracing.observe(
        "train.loop", 0, slow=False, steps=cfg.loop_steps,
        layers=cfg.n_layers, post_norm=cfg.post_norm, gate=cfg.exit_gate,
        head=("none" if read is None else
              "chunked" if cfg.fused_ce else "plain"))
    h, per_layer, reads = x, [], []
    for t in range(cfg.loop_steps):
        x, extras = _stack_once(params, h, positions, cfg)
        per_layer.extend(extras)
        if read is None and t == cfg.loop_steps - 1:
            break  # the caller's head norms the last pass's output itself
        with jax.named_scope("loop.head"):
            h = _norm(x, params["final_norm"], params.get("final_norm_b"),
                      cfg.norm, cfg.norm_eps, cfg.norm_offset)
            if read is not None:
                reads.append(read(h))
    out = (x, _stack_extras(per_layer))
    if read is None:
        return out
    return out + (jax.tree.map(lambda *a: jnp.stack(a), *reads),)


def _stack_extras(per_layer) -> Dict[str, jax.Array]:
    if not per_layer:
        return {}
    cat = {n: jnp.concatenate([e[n] for e in per_layer if n in e])
           for n in dict.fromkeys(n for e in per_layer for n in e)}
    out = {}
    if "dsa_kl" in cat:  # `_dsa_mixer`: the indexer's loss and its counters
        out = {"dsa_index_loss": cat["dsa_kl"].sum(),
               "dsa_selected": cat["dsa_selected"].sum(),
               "dsa_select_passes": cat["dsa_select_passes"].sum(),
               "dsa_select_fallback": cat["dsa_select_fallback"].sum(),
               "dsa_selection": cat["dsa_bits"]}
    if "aux" in cat:
        return {**out, "aux": cat["aux"].sum()}
    if "assigned" not in cat:
        return out
    return {**out, "moe_assigned": cat["assigned"].sum(),
            "moe_dropped": cat["dropped"].sum(),
            "moe_past_buffer": cat["past_buffer"].sum(),
            "moe_load_max": cat["load_max"].max(),
            "moe_load_mean": cat["load_mean"].mean(),
            "moe_trips": cat["trips"].sum(),
            "moe_window_rows": cat["window_rows"].max(),
            "moe_rows_worked": cat["rows_worked"].sum()}


def final_hidden_and_head(
    params: Params, x: jax.Array, cfg: TransformerConfig
) -> Tuple[jax.Array, jax.Array]:
    """THE head-weight convention (final norm + tied-or-separate head),
    shared by the unfused lm_head and the fused-CE loss path so the two
    can never drift. The norm works the rows a rank holds of the residual
    (parallel/tensor_overlap.py); they are gathered before the head."""
    x = _norm(x, params["final_norm"], params.get("final_norm_b"), cfg.norm,
              cfg.norm_eps, cfg.norm_offset)
    x = _whole(x, tp.plan(*x.shape[:2]))
    return x, _head_weight(params).astype(cfg.dtype)


def _head_weight(params: Params) -> jax.Array:
    """[d, V] in the parameters' type: `lm_head`, or the tied table."""
    head = params.get("lm_head", None)
    if head is None:
        head = params["embed"].T
    return head


def lm_head(params: Params, x: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """Final norm + (tied) output projection: hidden [B,S,d] -> logits f32."""
    x, head = final_hidden_and_head(params, x, cfg)
    return _scaled((x @ head).astype(jnp.float32), 1.0 / cfg.logit_scale)


def token_cross_entropy(logits: jax.Array, targets: jax.Array,
                        valid: jax.Array) -> jax.Array:
    """Mean CE of logits [B,S,V] vs targets [B,S] over positions where
    ``valid`` (f32 weights) is nonzero.

    Fused: ll = logits[target] - logsumexp(logits) avoids materializing a
    second [B, S, V] f32 log-softmax tensor (at V=32k that tensor dominates
    HBM traffic for the loss epilogue).
    """
    ll = _token_ll(logits, targets)
    return -(ll * valid).sum() / jnp.maximum(valid.sum(), 1.0)


def _token_ll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """log softmax(logits)[target] of every position, [B,S]."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    at_target = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return at_target - lse


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
            *, shift_inputs: bool = False, with_counters: bool = False,
            with_selection: bool = False):
    """Next-token cross-entropy; of a stack with an exit gate the expected
    one under its exit distribution (`_expected_exit_loss`). `with_counters`:
    return (loss, counters) for `ShardedTrainStep(has_aux=True)`: device
    scalars moe_assigned, moe_dropped, moe_past_buffer, moe_load_max,
    moe_load_mean, moe_trips, moe_window_rows, moe_rows_worked of a stack
    with a held range of experts (`_backbone`), exit_mass_pm_1..T and
    exit_entropy_pm of one with an exit gate, dsa_selected (kept pairs, summed
    over layers and queries), dsa_index_loss (the layers' KL terms, which
    the loss holds `DSA_LOSS_COEF` times), dsa_select_passes (the selection's
    counting passes in eighths of a pass, summed over layers and blocks of
    query rows) and dsa_select_fallback (the blocks whose search left the
    short way, summed over layers; both whole numbers whose mean a block
    follows from shapes: ops/sparse_attention.py `select`) of one with "dsa"
    layers, {} for any other; `with_selection`: also `dsa_selection`, every
    "dsa" layer's kept keys as bits ([L, B, S, S / 32] int32; a comparison's, not a
    step's). `batch["positions"]` ([3, B, S], or [3, B, S + 1] under
    `shift_inputs`, cut as the tokens are; a stack with `rope_sections`):
    the rotations' three position streams; absent: `arange`, and the program
    is the one a batch of tokens alone always traced.

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
    inputs = tokens[:, :-1] if shift_inputs else tokens
    positions = batch.get("positions")
    if positions is not None:
        dispatch.one_chip("a batch's three position streams")
        if shift_inputs:
            positions = positions[:, :, :-1]
    targets_valid = lambda: (
        shift_targets_valid(tokens, batch.get("mask")) if shift_inputs
        else inplace_targets_valid(batch))

    def done(loss, extras):
        if not with_selection:  # dropped here, so never lowered
            extras.pop("dsa_selection", None)
        return (loss, extras) if with_counters else loss

    if cfg.exit_gate:
        return done(*_expected_exit_loss(params, inputs, *targets_valid(),
                                         cfg, positions))
    x, extras = _backbone(params, inputs, cfg, positions=positions)
    targets, valid = targets_valid()
    if cfg.fused_ce:
        from ..ops.fused_ce import fused_next_token_loss

        x, head = final_hidden_and_head(params, x, cfg)
        loss = fused_next_token_loss(
            _scaled(x.astype(cfg.dtype), 1.0 / cfg.logit_scale), head,
            targets, valid)
    else:
        loss = token_cross_entropy(lm_head(params, x, cfg), targets, valid)
    return done(_with_layer_losses(loss, extras, cfg), extras)


def _with_layer_losses(loss, extras, cfg: TransformerConfig):
    """The loss with the terms the layers returned through `extras`: GShard's
    balancing loss (popped: what is left are counters) and the "dsa" layers'
    indexer loss (kept, a counter too)."""
    if "aux" in extras:
        loss = loss + cfg.moe_aux_coef * extras.pop("aux")
    if "dsa_index_loss" in extras:
        loss = loss + DSA_LOSS_COEF * extras["dsa_index_loss"]
    return loss


def exit_log_probs(gate_logits: jax.Array) -> jax.Array:
    """The exit distribution of a looped stack, in logarithms and float32:
    every pass's gate logit [T, ...] (the last pass's is not read) -> log p
    [T, ...], p(t) = lam_t prod_{u<t} (1 - lam_u) for t < T and p(T) =
    prod_{u<T} (1 - lam_u), what no gate took: the T masses sum to 1."""
    g = gate_logits[:-1].astype(jnp.float32)
    stay = jax.nn.log_sigmoid(-g)                 # log (1 - lam_t)
    reached = jnp.cumsum(stay, axis=0) - stay     # log S^(t-1)
    return jnp.concatenate([jax.nn.log_sigmoid(g) + reached,
                            stay.sum(0, keepdims=True)])


def _expected_exit_loss(params: Params, inputs: jax.Array,
                        targets: jax.Array, valid: jax.Array,
                        cfg: TransformerConfig,
                        positions: Optional[jax.Array] = None):
    """`loss_fn` of a stack with an exit gate -> (loss, counters): the mean
    over valid tokens of sum_t p(t) CE^t - `exit_entropy_coef` H(p), CE^t a
    token's cross-entropy under pass t's head (the one `lm_head`, the final
    norm's output of that pass) and p its exit distribution
    (`exit_log_probs`). A pass's float32 logits [B,S,V] are never kept:
    the plain head is recomputed in the backward (a checkpoint round the
    product and the cross-entropy), the chunked one (`cfg.fused_ce`,
    ops/fused_ce.py `fused_token_ce`) never forms them. Counters, device
    scalars in parts per thousand: `exit_mass_pm_<t>` the mean mass of pass
    t, `exit_entropy_pm` the mean entropy in nats."""
    # Cast inside a pass: the passes' head gradients then add up in the
    # parameter's own type, not in cfg.dtype.
    head = _head_weight(params)
    w_g = params["exit_gate_w"].astype(jnp.float32)
    b_g = params["exit_gate_b"].astype(jnp.float32)

    @jax.checkpoint
    def plain(h, head):
        logits = _scaled((h @ head).astype(jnp.float32), 1.0 / cfg.logit_scale)
        return -_token_ll(logits, targets)

    def chunked(h, head):
        from ..ops.fused_ce import fused_token_ce

        B, S, d = h.shape
        return fused_token_ce(
            _scaled(h, 1.0 / cfg.logit_scale).reshape(B * S, d), head,
            targets.reshape(B * S).astype(jnp.int32)).reshape(B, S)

    def read(h):
        h = _whole(h, tp.plan(*h.shape[:2]))
        ce = (chunked if cfg.fused_ce else plain)(h, head.astype(cfg.dtype))
        return ce, jnp.sum(h.astype(jnp.float32) * w_g, -1) + b_g

    _, extras, (ce, gate) = _backbone(params, inputs, cfg, read, positions)
    logp = exit_log_probs(gate)
    p = jnp.exp(logp)
    entropy = -(p * logp).sum(0)
    per_token = (p * ce).sum(0) - cfg.exit_entropy_coef * entropy
    mean = lambda a: (a * valid).sum() / jnp.maximum(valid.sum(), 1.0)
    loss = _with_layer_losses(mean(per_token), extras, cfg)
    for t in range(cfg.loop_steps):
        extras[f"exit_mass_pm_{t + 1}"] = 1000.0 * mean(p[t])
    extras["exit_entropy_pm"] = 1000.0 * mean(entropy)
    return loss, extras
