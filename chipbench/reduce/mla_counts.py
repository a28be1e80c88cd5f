"""Operations and bytes the flash kernels need when keys and values differ in
width (latent attention: q, k [B, H, S, D] with D = 192, v [B, H, S, Dv]
with Dv = 128), as reduce/flash_counts.py counts them for D = Dv: causal,
half the S x S scores; recomputation inside a backward kernel not counted.

- forward: Q K^T (D wide) and P V (Dv wide), halved: B H S^2 (D + Dv).
  Bytes: read Q, K (D), V (Dv), write O (Dv), + float32 row statistics.
- backward: S = Q K^T again, dQ = dS K, dK = dS^T Q (D wide each); dP = dO
  V^T, dV = P^T dO (Dv wide each), halved: B H S^2 (3 D + 2 Dv). Bytes:
  read Q, K, V, O, dO, write dQ, dK, dV."""
from __future__ import annotations

from typing import Dict


def flash_fwd(B: int, H: int, S: int, D: int, Dv: int,
              itemsize: int = 2) -> Dict[str, float]:
    return {"flops": float(B * H) * S * S * (D + Dv),
            "bytes": itemsize * float(B * S * H) * (2 * D + 2 * Dv)
            + 4.0 * B * H * S}


def flash_bwd(B: int, H: int, S: int, D: int, Dv: int,
              itemsize: int = 2) -> Dict[str, float]:
    return {"flops": float(B * H) * S * S * (3 * D + 2 * Dv),
            "bytes": itemsize * float(B * S * H) * (4 * D + 4 * Dv)
            + 4.0 * B * H * S}
