"""Operations and bytes of the mixed-head window / full attention expert
stack (configs/laguna_s_2_1.json), from shapes: what the algorithm needs, not
what an implementation spends. The two kinds of attention layer have
different numbers of query heads over the same key heads, so every count
takes the kind's own.

- the flash kernels, reduce/mellum2_counts.py's (imported): a sliding
  layer's call (`band_flash_fwd` / `_bwd`) at the band's pairs `S W - W (W -
  1) / 2`, a full layer's (`full_flash_fwd` / `_bwd`) at the triangle's
  `S (S + 1) / 2`; 4 B H D a pair forward, 10 B H D backward (dQ and dK/dV
  together, S recomputed once); bytes Q, K, V read and O written (+ float32
  row statistics), K and V once a KEY head.
- the held experts (`moe.experts`): reduce/mellum2_counts.py `experts`, 6 x 3
  x d x F operations a worked row, imported.
- the whole stack a token, for `train_mfu_stack_pct`: 6 a matmul parameter a
  token touches (an attention layer's W_q, W_k, W_v, W_o and W_g at the
  kind's heads; layer 1's dense SwiGLU; the router, the shared expert and
  k x held / E of the held experts under even routing; the untied head; no
  embedding lookup, no norm, no selection bias) and 12 H D a pair of each
  attention layer at the kind's heads and pairs (no recomputation). The
  rotations' and gates' few operations an element are not counted."""
from __future__ import annotations

from chipbench.reduce.mellum2_counts import (  # noqa: F401
    band_pairs, experts, full_flash_bwd, full_flash_fwd,
    swa_flash_bwd as band_flash_bwd, swa_flash_fwd as band_flash_fwd,
    triangle_pairs)


def layer_matmul_params(sz, kind) -> float:
    """Matmul parameters a token touches in one layer of `kind` (`sz`: a
    weights_laguna.LagunaSizes)."""
    mixer, ffn = kind
    d, q, kv = sz.d, sz.H[mixer] * sz.hd, sz.KVH * sz.hd
    n = 2 * d * q + 2 * d * kv + d * sz.H[mixer]
    if ffn == "dense":
        return n + 3 * d * sz.F
    return n + d * sz.E + (sz.shared + sz.k * sz.held / sz.E) * 3 * d * sz.Fe


def stack_flops_per_token(sz, seq: int) -> float:
    """Forward + backward operations a token of the whole stack."""
    n = sz.V * sz.d + sum(layer_matmul_params(sz, k) for k in sz.kinds)
    pairs = sum(sz.H[m] * (band_pairs(seq, sz.window) if m == "swa"
                           else triangle_pairs(seq)) for m, _ in sz.kinds)
    return 6.0 * n + 12.0 * sz.hd * pairs / seq
