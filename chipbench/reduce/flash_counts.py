"""Operations and bytes the flash attention kernels need, from shapes.

What the algorithm needs, not what an implementation spends: causal attention
over [B, H, S, D] with KVH key/value heads touches half the S x S score
matrix; recomputation inside a backward kernel is not counted.

- forward: Q K^T and P V, 2 matmuls of 2*S*S*D flops per head, halved by
  causality: 2 * B*H*S*S*D. Bytes: read Q, K, V, write O (+ the row
  statistics, float32).
- backward (dQ and dK/dV kernels together): dV = P^T dO, dP = dO V^T,
  dQ = dS K, dK = dS^T Q, plus recomputing S = Q K^T that the algorithm needs
  because P is never stored: 5 matmuls, halved: 5 * B*H*S*S*D. Bytes: read
  Q, K, V, O, dO, write dQ, dK, dV.
The ops/flash_attention.py kernels split the backward into `flash_dq` and
`flash_dkv`; each recomputes S and dP, so the two together are measured
against the 5 matmuls the algorithm needs."""
from __future__ import annotations

from typing import Dict


def flash_fwd(B: int, H: int, KVH: int, S: int, D: int,
              itemsize: int = 2) -> Dict[str, float]:
    flops = 2.0 * B * H * S * S * D
    bytes_ = itemsize * B * S * D * (2 * H + 2 * KVH) + 4.0 * B * H * S
    return {"flops": flops, "bytes": bytes_}


def flash_bwd(B: int, H: int, KVH: int, S: int, D: int,
              itemsize: int = 2) -> Dict[str, float]:
    flops = 5.0 * B * H * S * S * D
    bytes_ = itemsize * B * S * D * (4 * H + 4 * KVH) + 4.0 * B * H * S
    return {"flops": flops, "bytes": bytes_}


def roofline_s(cost: Dict[str, float], peaks: Dict[str, float]):
    """(least seconds the chip could take, which bound it is)."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
