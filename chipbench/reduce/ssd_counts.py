"""Operations and bytes the Mamba-2 core needs (ops/ssd.py `ssd_chunked`, the
`ssd.core` scope), from shapes: the chunked algorithm's, forward and backward
together, not what an implementation spends; and the whole stack's
operations a token for `train_mfu_stack_pct`.

Forward, a token, H heads of P with a state of N, G groups of B / C, chunks
of C tokens (flops = 2 x multiply-adds; a triangular product counts its
lower half):
- inside a chunk: C B^T (lower), once a GROUP: C N; ((C B^T) * L * dt) X
  (lower), a head: C P;
- between chunks: a chunk's own state X^T B: 2 P N a head; Y_inter =
  S_prev C_i: 2 P N a head. The carry S <- decay S + state is P N a head and
  CHUNK: left out (under 0.1%).
So G C N + H P (C + 4 N). The backward pass of a product of two matrices is
two products of the same size: backward = 2 x forward. Recomputation under
remat is not counted; nor are the elementwise passes (L, the decay factors),
which cost time and no matmul operation.
Bytes: x in the compute type, dt in float32, B and C in the compute type,
read once, y written once in the forward; all of them and dy read, and a
gradient of each written, in the backward."""
from __future__ import annotations

from typing import Dict


def ssd_core_fwd_flops_per_token(H: int, P: int, N: int, G: int,
                                 C: int) -> float:
    return float(G * C * N + H * P * (C + 4 * N))


def ssd_core(B: int, S: int, H: int, P: int, N: int, G: int, C: int,
             itemsize: int = 2) -> Dict[str, float]:
    """Forward and backward of one Mamba-2 layer's core on [B, S] tokens."""
    flops = 3.0 * B * S * ssd_core_fwd_flops_per_token(H, P, N, G, C)
    ins = H * P * itemsize + 4 * H + 2 * G * N * itemsize    # x, dt, B C
    out = H * P * itemsize
    bytes_ = float(B * S) * ((ins + out) + (ins + out + ins))
    return {"flops": flops, "bytes": bytes_}


def stack_flops_per_token(sz, seq: int) -> float:
    """Forward + backward operations a token of the whole stack (`sz`: a
    weights_granite_hybrid.StackSizes): 6 per matmul parameter a token
    touches (the tied head once, no embedding lookup; convolutions, norms
    and biases are no matmuls), causal attention 3 S H 2 hd an attention
    layer, the chunked core a Mamba-2 layer."""
    d = sz.d
    n = sz.V * d  # the head
    total = 0.0
    for mixer, _ in sz.kinds:
        n += 3 * d * sz.F
        if mixer == "mamba2":
            n += d * (sz.di + sz.conv_ch + sz.Hm) + sz.di * d
            total += 3.0 * ssd_core_fwd_flops_per_token(
                sz.Hm, sz.P, sz.N, sz.G, sz.chunk)
        else:
            n += 2 * d * sz.H * sz.hd + 2 * d * sz.KVH * sz.hd
            total += 3.0 * seq * sz.H * 2 * sz.hd
    return 6.0 * n + total
