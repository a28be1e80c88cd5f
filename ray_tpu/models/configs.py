"""Named model configs covering the baseline workloads (BASELINE.json):
GPT-2 125M (config 2), Llama-3-8B (config 3), plus tiny variants for tests."""
from __future__ import annotations

from .transformer import TransformerConfig


def gpt2_125m(**overrides) -> TransformerConfig:
    kw = dict(
        vocab_size=50257,
        d_model=768,
        n_layers=12,
        n_heads=12,
        max_seq_len=1024,
        norm="layernorm",
        activation="gelu",
        positional="learned",
        tie_embeddings=True,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def llama3_8b(**overrides) -> TransformerConfig:
    kw = dict(
        vocab_size=128256,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        max_seq_len=8192,
        norm="rmsnorm",
        activation="swiglu",
        positional="rope",
        rope_theta=500000.0,
        tie_embeddings=False,
        remat=True,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def llama_tiny(**overrides) -> TransformerConfig:
    """Llama-family shape small enough for CPU tests and dry-runs."""
    kw = dict(
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        max_seq_len=128,
        norm="rmsnorm",
        activation="swiglu",
        positional="rope",
        tie_embeddings=True,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def gpt2_tiny(**overrides) -> TransformerConfig:
    kw = dict(
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        max_seq_len=128,
        norm="layernorm",
        activation="gelu",
        positional="learned",
        tie_embeddings=True,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


# The single-chip bench model: large enough to saturate the MXU on one chip,
# small enough to fit HBM with optimizer state.
def bench_350m(**overrides) -> TransformerConfig:
    kw = dict(
        vocab_size=32000,
        d_model=1024,
        n_layers=24,
        n_heads=16,
        max_seq_len=1024,
        norm="rmsnorm",
        activation="swiglu",
        positional="rope",
        tie_embeddings=True,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def moe_tiny(**overrides) -> TransformerConfig:
    """Tiny mixture-of-experts decoder for CPU tests and EP dry-runs."""
    kw = dict(
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        max_seq_len=128,
        norm="rmsnorm",
        activation="swiglu",
        positional="rope",
        tie_embeddings=True,
        moe_num_experts=4,
        moe_experts_per_token=2,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def kimi_linear_tiny(**overrides) -> TransformerConfig:
    """A mixed stack in the Kimi-Linear pattern at widths small enough for
    CPU tests (docs/model_layers.md): layers 4, 8, ... are MLA and the rest
    KDA (three to one), the first layer keeps a dense SwiGLU and the others
    have 16 sigmoid-routed experts, 4 a token, one shared. Published sizes
    live in chipbench/configs/ only."""
    kw = dict(
        vocab_size=256,
        d_model=64,
        n_layers=5,
        n_heads=4,
        d_ff=128,
        max_seq_len=64,
        norm="rmsnorm",
        norm_eps=1e-5,
        activation="swiglu",
        positional="none",
        tie_embeddings=False,
        kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                    22, 23, 25, 26),
        mla_layers=(4, 8, 12, 16, 20, 24, 27),
        kda_head_dim=16,
        kda_chunk=16,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        moe_num_experts=16,
        moe_experts_per_token=4,
        moe_router="sigmoid",
        moe_d_ff=32,
        moe_shared_experts=1,
        moe_routed_scale=2.446,
        moe_first_dense=1,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def kanana2_tiny(**overrides) -> TransformerConfig:
    """An all-latent-attention stack in the Kanana-2 (DeepSeek-V3) pattern at
    widths small enough for CPU tests (docs/model_layers.md): every layer is
    MLA whose 8-wide decoupled part rotates (`positional="rope"`, theta 1e6)
    beside 16 unrotated columns, values 16 wide; the first layer keeps a
    dense SwiGLU and the others have 16 sigmoid-routed experts, 3 a token,
    two shared, experts 4-7 held here; an untied head. Published sizes live
    in chipbench/configs/ only."""
    kw = dict(
        vocab_size=256,
        d_model=64,
        n_layers=4,
        n_heads=4,
        d_ff=128,
        max_seq_len=64,
        norm="rmsnorm",
        norm_eps=1e-6,
        activation="swiglu",
        positional="rope",
        rope_theta=1e6,
        tie_embeddings=False,
        mla_layers=tuple(range(1, 49)),
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        moe_num_experts=16,
        moe_experts_per_token=3,
        moe_router="sigmoid",
        moe_held=(4, 4),
        moe_d_ff=32,
        moe_shared_experts=2,
        moe_routed_scale=2.448,
        moe_first_dense=1,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def granite_hybrid_tiny(**overrides) -> TransformerConfig:
    """A Mamba-2 / NoPE-attention stack in the Granite-4.0-H pattern at
    widths small enough for CPU tests (docs/model_layers.md): of every ten
    layers the sixth is GQA attention without positions and the rest are
    Mamba-2, every feed-forward a dense SwiGLU, the head tied, and the
    family's four multipliers. Published sizes live in chipbench/configs/
    only."""
    kw = dict(
        vocab_size=256,
        d_model=64,
        n_layers=10,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=64,
        norm="rmsnorm",
        norm_eps=1e-5,
        activation="swiglu",
        positional="none",
        tie_embeddings=True,
        mamba_layers=tuple(l for l in range(1, 41) if l % 10 != 6),
        mamba_heads=8,
        mamba_head_dim=16,
        mamba_d_state=32,
        mamba_groups=1,
        mamba_conv=4,
        mamba_chunk=16,
        embed_scale=12.0,
        residual_scale=0.22,
        attn_scale=1.0 / 16,  # the family's 1 / head_dim, not its root
        logit_scale=8.0,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def mellum2_tiny(**overrides) -> TransformerConfig:
    """A sliding-window / full-attention stack in the Mellum2 pattern at
    widths small enough for CPU tests (docs/model_layers.md): of every four
    layers three see a window of 8 keys under plain RoPE and the fourth the
    whole sequence under YaRN-scaled RoPE; heads of 32 (4 x 32 is not
    d_model); every feed-forward 8 softmax-routed experts, 2 a token, none
    shared, experts 4-7 held here; an untied head. Published sizes live in
    chipbench/configs/ only."""
    kw = dict(
        vocab_size=256,
        d_model=64,
        n_layers=4,
        n_heads=4,
        n_kv_heads=1,
        attn_head_dim=32,
        d_ff=128,
        max_seq_len=64,
        norm="rmsnorm",
        norm_eps=1e-6,
        activation="swiglu",
        positional="rope",
        rope_theta=500000.0,
        yarn_factor=16.0,
        yarn_original_len=32,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_attn_factor=1.2772588722239782,
        tie_embeddings=False,
        swa_layers=tuple(l for l in range(1, 29) if l % 4),
        sliding_window=8,
        moe_num_experts=8,
        moe_experts_per_token=2,
        moe_router="softmax",
        moe_held=(4, 4),
        moe_d_ff=32,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def qwen3_next_tiny(**overrides) -> TransformerConfig:
    """A Gated DeltaNet / gated-attention stack in the Qwen3-Next pattern at
    widths small enough for CPU tests (docs/model_layers.md): of every four
    layers three are `gdn` (2 key heads serving 4 value heads of 16, a
    convolution of 4, chunks of 16) and the fourth softmax attention at 4 / 1
    heads of 32 with q / k norms, a rotation of a quarter of a head and a
    sigmoid gate on its output; zero-centred norm weights; every
    feed-forward 32 softmax-routed experts, 4 a token, experts 8-15 held
    here, beside one shared expert behind a sigmoid gate; an untied head.
    Published sizes live in chipbench/configs/ only."""
    kw = dict(
        vocab_size=256,
        d_model=64,
        n_layers=4,
        n_heads=4,
        n_kv_heads=1,
        attn_head_dim=32,
        d_ff=128,
        max_seq_len=64,
        norm="rmsnorm",
        norm_eps=1e-6,
        norm_offset=1.0,
        activation="swiglu",
        positional="rope",
        rope_theta=1e7,
        rope_fraction=0.25,
        attn_qk_norm=True,
        attn_out_gate=True,
        tie_embeddings=False,
        gdn_layers=tuple(l for l in range(1, 49) if l % 4),
        gdn_k_heads=2,
        gdn_v_heads=4,
        gdn_head_dim=16,
        gdn_conv=4,
        gdn_chunk=16,
        moe_num_experts=32,
        moe_experts_per_token=4,
        moe_router="softmax",
        moe_held=(8, 8),
        moe_d_ff=32,
        moe_shared_experts=1,
        moe_shared_gate=True,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def laguna_tiny(**overrides) -> TransformerConfig:
    """A mixed-head window / full attention stack in the Laguna pattern at
    widths small enough for CPU tests (docs/model_layers.md): of every four
    layers the first sees the whole sequence at 4 query heads, half of each
    head rotated under YaRN-scaled RoPE (theta 1e4), and the other three a
    window of 8 keys at 6 query heads under plain RoPE of theta 100 over the
    whole head, all over the same 2 key heads of 16 (groups of 2 and of 3); a
    sigmoid gate a head on every attention output; the first layer keeps a
    dense SwiGLU and the others have 16 sigmoid-routed experts, 3 a token,
    one shared, experts 4-7 held here; an untied head. Published sizes live
    in chipbench/configs/ only."""
    kw = dict(
        vocab_size=256,
        d_model=64,
        n_layers=5,
        n_heads=4,
        n_kv_heads=2,
        attn_head_dim=16,
        d_ff=128,
        max_seq_len=64,
        norm="rmsnorm",
        norm_eps=1e-6,
        activation="swiglu",
        positional="rope",
        rope_theta=10000.0,
        rope_fraction=0.5,
        yarn_factor=16.0,
        yarn_original_len=64,  # the ramp over 8 rotated columns is not
        yarn_beta_fast=32.0,   # the one over the head's 16 at these
        yarn_beta_slow=1.0,
        yarn_attn_factor=1.2772588722239782,
        attn_head_gate=True,
        tie_embeddings=False,
        swa_layers=tuple(l for l in range(1, 49) if l % 4 != 1),
        sliding_window=8,
        swa_heads=6,
        swa_rope_theta=100.0,
        swa_rope_fraction=1.0,
        moe_num_experts=16,
        moe_experts_per_token=3,
        moe_router="sigmoid",
        moe_held=(4, 4),
        moe_d_ff=32,
        moe_shared_experts=1,
        moe_routed_scale=2.5,
        moe_first_dense=1,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def ouro_tiny(**overrides) -> TransformerConfig:
    """A looped stack in the Ouro (LoopLM) pattern at widths small enough for
    CPU tests (docs/model_layers.md, "The loop"): two layers applied four
    times a step over one set of leaves, 4 query and 4 key heads of 16 under
    plain RoPE (theta 1e6), a dense SwiGLU, two norms round every sublayer,
    the final norm closing every pass, an untied head and a scalar exit gate
    reading every pass, the loss the expectation over the exit distribution
    less 0.05 of its entropy. Published sizes live in chipbench/configs/
    only."""
    kw = dict(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,
        attn_head_dim=16,
        d_ff=128,
        max_seq_len=64,
        norm="rmsnorm",
        norm_eps=1e-6,
        activation="swiglu",
        positional="rope",
        rope_theta=1000000.0,
        tie_embeddings=False,
        loop_steps=4,
        post_norm=True,
        exit_gate=True,
        exit_entropy_coef=0.05,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def keye_vl2_tiny(**overrides) -> TransformerConfig:
    """A learned-sparse-attention stack in the Keye-VL-2.0 pattern at widths
    small enough for CPU tests (docs/model_layers.md, "dsa"): every layer 4
    query heads over 2 key heads of 16 with q / k norms and M-RoPE in three
    sections (2, 3, 3 of the 8 pairs; theta 1e4), an indexer of 4 heads of 8
    over one key head, each query attending to the 16 earlier keys it ranks
    highest, the indexer's loss added at coefficient 1; 16 softmax-routed
    experts, 3 a token, none shared, experts 4-7 held here; an untied head.
    Published sizes live in chipbench/configs/ only."""
    kw = dict(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        attn_head_dim=16,
        d_ff=128,
        max_seq_len=64,
        norm="rmsnorm",
        norm_eps=1e-6,
        activation="swiglu",
        positional="rope",
        rope_theta=10000.0,
        rope_sections=(2, 3, 3),
        attn_qk_norm=True,
        tie_embeddings=False,
        dsa_layers=tuple(range(1, 49)),
        dsa_topk=16,
        dsa_index_heads=4,
        dsa_index_head_dim=8,
        moe_num_experts=16,
        moe_experts_per_token=3,
        moe_router="softmax",
        moe_held=(4, 4),
        moe_d_ff=32,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def phi4_mini_flash(**overrides) -> TransformerConfig:
    """microsoft/Phi-4-mini-flash-reasoning (`phi4flash`, the SambaY
    decoder-hybrid-decoder, arXiv:2507.06607) at its published sizes: 32
    layers of d 2,560, every attention differential with biases, 40 query /
    20 key-value heads of 64, SwiGLU of 10,240, LayerNorm 1e-5, no position
    signal, a tied table of 200,064. Published 0-based layers 0-15: eight
    (Mamba-1, window-512 attention) pairs; 16, 17: a Mamba-1 layer whose
    scan output `m` and a full-attention layer whose keys and values are
    handed on; 18-31: seven (Gated Memory Unit, cross attention) pairs that
    read them and compute no scan, key or value of their own. The Mamba-1
    sizes are the modelling file's defaults (inner 5,120, state 16,
    convolution 4, dt rank 160). chipbench/configs/ holds the six-layer
    stage a chip trains (docs/model_layers.md)."""
    kw = dict(
        vocab_size=200064,
        d_model=2560,
        n_layers=32,
        n_heads=40,
        n_kv_heads=20,
        d_ff=10240,
        max_seq_len=262144,
        norm="layernorm",
        norm_eps=1e-5,
        activation="swiglu",
        positional="none",
        tie_embeddings=True,
        sliding_window=512,
        mamba1_layers=tuple(range(1, 18, 2)),
        swa_layers=tuple(range(2, 17, 2)),
        gmu_layers=tuple(range(19, 32, 2)),
        xattn_layers=tuple(range(20, 33, 2)),
        mamba1_inner=5120,
        mamba1_d_state=16,
        mamba1_conv=4,
        mamba1_dt_rank=160,
        diff_attn=True,
        attn_bias=True,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def phi4_flash_tiny(**overrides) -> TransformerConfig:
    """The Phi-4-mini-flash pattern at widths small enough for CPU tests
    (docs/model_layers.md): published layers 14-19 as a six-layer stack, a
    Mamba-1 layer, a window-8 differential layer, the Mamba-1 layer that
    hands its scan output on, the full differential layer that hands its
    keys and values on, a Gated Memory Unit and a cross differential layer;
    4 query / 2 key-value heads of 16, 128 channels with a state of 4 in
    chunks of 8, lam0 from the published indices (`layer_offset` 14)."""
    kw = dict(
        vocab_size=256,
        d_model=64,
        n_layers=6,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=64,
        norm="layernorm",
        norm_eps=1e-5,
        activation="swiglu",
        positional="none",
        tie_embeddings=True,
        sliding_window=8,
        mamba1_layers=(1, 3),
        swa_layers=(2,),
        gmu_layers=(5,),
        xattn_layers=(6,),
        mamba1_inner=128,
        mamba1_d_state=4,
        mamba1_conv=4,
        mamba1_dt_rank=4,
        mamba1_chunk=8,
        diff_attn=True,
        attn_bias=True,
        layer_offset=14,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


_LFM2_ATTN = (3, 7, 11, 15, 19, 22)  # published full_attention layers, 1-based


def lfm2_8b_a1b(**overrides) -> TransformerConfig:
    """LiquidAI/LFM2-8B-A1B (`lfm2_moe`) at its published sizes: 24 layers
    of d 2,048, 18 of them a double-gated short convolution of 3 taps
    (`shortconv`) and 6 GQA attention at 32 query / 8 key heads of 64 with
    an RMSNorm over every query and key head before full RoPE at theta
    1e6; the first 2 layers a SwiGLU of 7,168, the other 22 carry 32
    sigmoid-routed experts of 1,792, 4 a token by score + selection bias,
    renormalised, none shared; RMSNorm 1e-5, a tied table of 65,536.
    chipbench/configs/lfm2_8b_a1b.json holds one expert rank's five-layer
    stage (docs/model_layers.md)."""
    kw = dict(
        vocab_size=65536,
        d_model=2048,
        n_layers=24,
        n_heads=32,
        n_kv_heads=8,
        d_ff=7168,
        max_seq_len=128000,
        norm="rmsnorm",
        norm_eps=1e-5,
        activation="swiglu",
        positional="rope",
        rope_theta=1e6,
        attn_qk_norm=True,
        tie_embeddings=True,
        shortconv_layers=tuple(l for l in range(1, 25)
                               if l not in _LFM2_ATTN),
        moe_num_experts=32,
        moe_experts_per_token=4,
        moe_router="sigmoid",
        moe_d_ff=1792,
        moe_first_dense=2,
        moe_routed_scale=1.0,
        moe_aux_coef=0.0,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def lfm2_moe_tiny(**overrides) -> TransformerConfig:
    """The LFM2-MoE pattern at widths small enough for CPU tests
    (docs/model_layers.md), every kind of layer once and the convolution
    expert layer twice: a `shortconv` layer with the dense SwiGLU, an
    attention layer (4 query / 2 key heads of 16, q / k norms, full RoPE)
    with experts, two `shortconv` layers with experts; 8 sigmoid-routed
    experts, 2 a token, experts 2-5 held here; a tied head."""
    kw = dict(
        vocab_size=256,
        d_model=64,
        n_layers=4,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=64,
        norm="rmsnorm",
        norm_eps=1e-5,
        activation="swiglu",
        positional="rope",
        rope_theta=1e6,
        attn_qk_norm=True,
        tie_embeddings=True,
        shortconv_layers=(1, 3, 4),
        moe_num_experts=8,
        moe_experts_per_token=2,
        moe_router="sigmoid",
        moe_held=(2, 4),
        moe_d_ff=32,
        moe_first_dense=1,
        moe_aux_coef=0.0,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)
