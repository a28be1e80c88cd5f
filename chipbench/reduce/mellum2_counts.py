"""Operations and bytes of the sliding-window / full-attention expert stack
(configs/mellum2_12b_a2_5b.json), from shapes: what the algorithm needs, not
what an implementation spends.

- pairs: a windowed layer's queries meet `band_pairs(S, W)` = S W - W (W - 1)
  / 2 keys in all (query i sees min(i + 1, W) of them), a full layer's the
  triangle S (S + 1) / 2.
- the flash kernels, a call on [B, H, S, D] with KVH key/value heads, as
  reduce/flash_counts.py counts them with the pairs in place of S^2 / 2:
  forward Q K^T and P V, 4 B H D a pair; backward (dQ and dK/dV kernels
  together) five products, S recomputed among them, 10 B H D a pair. Bytes:
  Q, K, V read and O written (+ float32 row statistics) forward; Q, K, V, O,
  dO read and dQ, dK, dV written backward.
- the held experts (`moe.experts`): gate, up and down of a worked row, 3 x
  d x F multiply-adds, forward and both transposed products backward:
  6 x 3 x d x F operations a row. Bytes: the gathered rows in, the rows
  out, both again with their gradients backward; the held weights read
  forward and backward and their gradients written.
- the whole stack a token, for `train_mfu_stack_pct`: 6 a matmul parameter a
  token touches (attention projections, the router, k x held / E of the
  held experts under even routing, the untied head; no embedding lookup)
  and 12 H D a pair of its attention layers (no recomputation)."""
from __future__ import annotations

from typing import Dict


def band_pairs(S: int, W: int) -> float:
    W = min(W, S)
    return float(S) * W - W * (W - 1) / 2.0


def triangle_pairs(S: int) -> float:
    return S * (S + 1) / 2.0


def _flash(B, H, KVH, S, D, pairs, per_pair, tensors, itemsize):
    return {"flops": per_pair * B * H * D * pairs,
            "bytes": itemsize * float(B * S * D) * tensors * (H + KVH)
            + 4.0 * B * H * S}


def swa_flash_fwd(B: int, H: int, KVH: int, S: int, D: int, W: int,
                  itemsize: int = 2) -> Dict[str, float]:
    return _flash(B, H, KVH, S, D, band_pairs(S, W), 4.0, 2, itemsize)


def swa_flash_bwd(B: int, H: int, KVH: int, S: int, D: int, W: int,
                  itemsize: int = 2) -> Dict[str, float]:
    return _flash(B, H, KVH, S, D, band_pairs(S, W), 10.0, 4, itemsize)


def full_flash_fwd(B: int, H: int, KVH: int, S: int, D: int,
                   itemsize: int = 2) -> Dict[str, float]:
    return _flash(B, H, KVH, S, D, triangle_pairs(S), 4.0, 2, itemsize)


def full_flash_bwd(B: int, H: int, KVH: int, S: int, D: int,
                   itemsize: int = 2) -> Dict[str, float]:
    return _flash(B, H, KVH, S, D, triangle_pairs(S), 10.0, 4, itemsize)


def experts(rows: float, held: int, d: int, F: int,
            itemsize: int = 2) -> Dict[str, float]:
    """Forward and backward of the grouped products over `rows` worked rows
    (assignments that fell on the `held` experts)."""
    return {"flops": 6.0 * 3 * d * F * rows,
            "bytes": itemsize * (6.0 * rows * d + 3.0 * held * 3 * d * F)}


def stack_flops_per_token(sz, seq: int) -> float:
    """Forward + backward operations a token of the whole stack (`sz`: a
    weights_mellum2.MellumSizes)."""
    d, q, kv = sz.d, sz.H * sz.hd, sz.KVH * sz.hd
    n = sz.V * d  # the head
    pairs = 0.0
    for mixer, _ in sz.kinds:
        n += 2 * d * q + 2 * d * kv + d * sz.E
        n += sz.k * sz.held / sz.E * 3 * d * sz.Fe
        pairs += (band_pairs(seq, sz.window) if mixer == "swa"
                  else triangle_pairs(seq))
    return 6.0 * n + 12.0 * sz.H * sz.hd * pairs / seq
