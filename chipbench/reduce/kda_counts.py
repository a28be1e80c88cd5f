"""Operations and bytes the KDA core needs (ops/kda.py `kda_chunked`, the
`kda.core` scope), from shapes: the chunked algorithm's, forward and
backward together, not what an implementation spends.

Forward, a token and head, chunks of C tokens, keys d_k and values d_v wide
(flops = 2 x multiply-adds; a triangular product counts its lower half):
- between chunks: W S, (Q e^G) S and the state update K^T U: 3 x 2 d_k d_v;
- inside a chunk: A = K K^T (strictly lower) C d_k; P = Q K^T (lower)
  C d_k; W = T (beta K e^G) C d_k; U0 = T (beta V) C d_v; P U C d_v;
- the inverse T = (I + A)^-1 by substitution: C^3 / 3 a chunk, C^2 / 3 a
  token.
The backward pass of a product of two matrices is two products of the same
size: backward = 2 x forward. Recomputation under remat is not counted.
Bytes: q, k, v in the compute type, the log-decay g [.., d_k] and beta in
float32, read once and o written once in the forward; all of them and do
read, and a gradient of each written, in the backward."""
from __future__ import annotations

from typing import Dict


def kda_core_fwd_flops_per_token(H: int, dk: int, dv: int, C: int) -> float:
    return H * (6.0 * dk * dv + C * (3.0 * dk + 2.0 * dv) + C * C / 3.0)


def kda_core(B: int, H: int, S: int, dk: int, dv: int, C: int,
             itemsize: int = 2) -> Dict[str, float]:
    """Forward and backward of one KDA layer's core on [B, S] tokens."""
    flops = 3.0 * B * S * kda_core_fwd_flops_per_token(H, dk, dv, C)
    ins = (2 * dk + dv) * itemsize + 4 * dk + 4      # q k v, g, beta
    out = dv * itemsize
    bytes_ = float(B * S * H) * ((ins + out) + (ins + out + ins))
    return {"flops": flops, "bytes": bytes_}
