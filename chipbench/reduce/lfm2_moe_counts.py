"""Operations and bytes of the gated-short-convolution / GQA expert stack
(configs/lfm2_8b_a1b.json), from shapes: what the algorithm needs, not what
an implementation spends.

- the gated convolution's core (`shortconv_core`, the `shortconv.core`
  scope): y = Cg * conv_K(Bg * x) over the joint projection's [B, S, 3C]
  output. No matrix product holds it: 2 K + 2 multiply-adds a channel and
  token forward and about twice that backward, a few hundredths of what the
  bytes cost on this chip, so the floor is HBM's. Forward: [Bg ; Cg ; x]
  read, y written (4 C elements a token); backward: the three and dy read,
  d[Bg ; Cg ; x] written (7 C). The taps and their gradient are K x C.
  Recomputation under remat is in the measured time and not in the count.
- the held experts (`moe.experts`): reduce/mellum2_counts.py `experts`,
  imported (`moe_experts_roofline` reads it). The flash forward on the
  attention layer (32 query heads over 8 key heads of 64) is read by the
  accepted `flash_fwd_roofline` from reduce/flash_counts.py, not from here.
- the whole stack a token, for `train_mfu_stack_pct`: 6 a matmul parameter a
  token touches (a convolution layer's W_in and W_out; the attention
  layer's W_q, W_k, W_v, W_o; the dense SwiGLU; the router and k x held / E
  of the held experts under even routing; the tied head once; no embedding
  lookup, no norm, no tap) and 12 H D a pair of the attention layer's
  triangle (no recomputation)."""
from __future__ import annotations

from typing import Dict

from chipbench.reduce.mellum2_counts import experts, triangle_pairs  # noqa: F401


def shortconv_core(B: int, S: int, C: int, K: int, itemsize: int = 2
                   ) -> Dict[str, float]:
    """Forward and backward of one convolution layer's core on [B, S]
    tokens."""
    rows = float(B) * S
    taps = 4.0 * K * C * 3  # read twice, their float32 gradient written
    return {"vector_ops": rows * C * 3.0 * (2 * K + 2),
            "bytes_fwd": rows * 4 * C * itemsize,
            "bytes_bwd": rows * 7 * C * itemsize,
            "bytes": rows * 11 * C * itemsize + taps}


def roofline_s(cost: Dict[str, float], peaks: Dict[str, float]) -> float:
    """The least seconds the chip could take: the bytes over HBM's rate."""
    return cost["bytes"] / peaks["hbm_bytes_per_s"]


def layer_matmul_params(sz, kind) -> float:
    """Matmul parameters a token touches in one layer of `kind` (`sz`: a
    weights_lfm2_moe.Lfm2Sizes)."""
    d = sz.d
    if kind[0] == "shortconv":
        n = 3 * d * d + d * d
    else:
        n = 2 * d * sz.H * sz.hd + 2 * d * sz.KVH * sz.hd
    if kind[1] == "dense":
        return n + 3 * d * sz.F
    return n + d * sz.E + sz.k * sz.held / sz.E * 3 * d * sz.Fe


def stack_flops_per_token(sz, seq: int) -> float:
    """Forward + backward operations a token of the whole stack."""
    n = sz.V * sz.d + sum(layer_matmul_params(sz, k) for k in sz.kinds)
    attn = sum(m == "attn" for m, _ in sz.kinds)
    return 6.0 * n + 12.0 * sz.H * sz.hd * attn * triangle_pairs(seq) / seq
