"""Operations and bytes of the learned-sparse-attention expert stack
(configs/keye_vl_2_0_30b_a3b.json), from shapes: what the algorithm needs,
not what an implementation spends (the thresholded kernels of
ops/sparse_attention.py visit every earlier key; the count is the kept
sets').

- pairs: query t keeps min(t + 1, k) keys, `selected_pairs(S, k)` in all
  (12.1% of the triangle's S (S + 1) / 2 at 32,768 and 2,048); the indexer
  scores the whole triangle.
- `dsa_core_fwd` / `dsa_core_bwd`, a call on [B, H, S, D] with KVH key/value
  heads, as reduce/mellum2_counts.py counts the flash kernels with the kept
  pairs in place of the triangle: forward Q K^T and P V, 4 B H D a pair;
  backward five products, S recomputed among them, 10 B H D a pair. Bytes:
  Q, K, V read and O written (+ float32 row statistics) forward; Q, K, V, O,
  dO read and dQ, dK, dV written backward; the selection's bits (S^2 / 8
  bytes) read by each.
- `dsa_index`: the scores of the triangle once, 2 HI dI a pair (they decide
  the selection; the indexer's loss and its gradient need them again and
  three transposed products, 8 HI dI a pair in all), and the attention's
  probabilities of the KEPT pairs once more for the loss's target, 2 H D a
  kept pair. Bytes: qi, ki, w, the lse and q, k read, the bits written and
  read.
- the held experts: reduce/mellum2_counts.py `experts`, imported.
- the whole stack a token, for `train_mfu_stack_pct`: 6 a matmul parameter a
  token touches (attention and indexer projections, the router, k x held / E
  of the held experts under even routing, the untied head; no embedding
  lookup), 12 H D a kept pair of every layer's attention and 6 HI dI a pair
  of the triangle for the indexer's scores (forward and the two gradients;
  no recomputation, and not the loss's second look at the attention)."""
from __future__ import annotations

from typing import Dict

from chipbench.reduce.mellum2_counts import experts, triangle_pairs  # noqa: F401


def selected_pairs(S: int, k: int) -> float:
    k = min(k, S)
    return k * (k + 1) / 2.0 + float(S - k) * k


def _core(B, H, KVH, S, D, k, per_pair, tensors, itemsize):
    return {"flops": per_pair * B * H * D * selected_pairs(S, k),
            "bytes": itemsize * float(B * S * D) * tensors * (H + KVH)
            + 4.0 * B * H * S + B * S * S / 8.0}


def dsa_core_fwd(B: int, H: int, KVH: int, S: int, D: int, k: int,
                 itemsize: int = 2) -> Dict[str, float]:
    return _core(B, H, KVH, S, D, k, 4.0, 2, itemsize)


def dsa_core_bwd(B: int, H: int, KVH: int, S: int, D: int, k: int,
                 itemsize: int = 2) -> Dict[str, float]:
    return _core(B, H, KVH, S, D, k, 10.0, 4, itemsize)


def dsa_index(B: int, H: int, KVH: int, S: int, D: int, HI: int, dI: int,
              k: int, itemsize: int = 2) -> Dict[str, float]:
    """The indexer of one layer and one step: selection, loss, gradient."""
    return {"flops": B * (8.0 * HI * dI * triangle_pairs(S)
                          + 2.0 * H * D * selected_pairs(S, k)),
            "bytes": B * (itemsize * float(S) * (2 * HI * dI + 2 * dI
                                                 + (H + KVH) * D)
                          + 4.0 * S * (2 * HI + H + 2) + 2 * S * S / 8.0)}


def layer_matmul_params(sz) -> float:
    """Matmul parameters a token touches in one layer (`sz`: a
    weights_keye_vl2.KeyeSizes)."""
    d, q, kv = sz.d, sz.H * sz.hd, sz.KVH * sz.hd
    return (2 * d * q + 2 * d * kv + d * (sz.HI * sz.dI + sz.dI + sz.HI)
            + d * sz.E + sz.k * sz.held / sz.E * 3 * d * sz.Fe)


def stack_flops_per_token(sz, seq: int) -> float:
    """Forward + backward operations a token of the whole stack."""
    n = sz.V * sz.d + sz.L * layer_matmul_params(sz)
    return 6.0 * n + sz.L * (
        12.0 * sz.H * sz.hd * selected_pairs(seq, sz.topk)
        + 6.0 * sz.HI * sz.dI * triangle_pairs(seq)) / seq
