"""Operations and bytes of the all-latent-attention expert stack
(configs/kanana_2_30b_a3b.json), from shapes: what the algorithm needs, not
what an implementation spends.

- the flash kernels on a latent layer (keys 192 wide, values 128): reduce/
  mla_counts.py `flash_fwd` / `flash_bwd`, the hybrid cell's, imported.
- the held experts (`moe.experts`): reduce/mellum2_counts.py `experts`, 6 x 3
  x d x F operations a worked row, imported.
- the whole stack a token, for `train_mfu_stack_pct`: 6 a matmul parameter a
  token touches (a latent layer's W_q, W_kva, W_kvb and W_o; the dense
  SwiGLU; the router, the shared experts and k x held / E of the held
  experts under even routing; the untied head; no embedding lookup, no
  norm) and, a latent layer, the causal triangle's S (S + 1) / 2 pairs at 2
  (D + Dv) operations a pair and head forward, three times that with the
  backward (no recomputation): 3 (S + 1) H (D + Dv) a token. The rotation's
  few operations an element are not counted."""
from __future__ import annotations

from chipbench.reduce.mellum2_counts import experts, triangle_pairs  # noqa: F401
from chipbench.reduce.mla_counts import flash_bwd, flash_fwd  # noqa: F401


def layer_matmul_params(sz, ffn: str) -> float:
    """Matmul parameters a token touches in one layer with feed-forward
    `ffn` (`sz`: a weights_kanana2.KananaSizes)."""
    d, H = sz.d, sz.H
    n = (d * H * (sz.nope + sz.rope) + d * (sz.lat + sz.rope)
         + sz.lat * H * (sz.nope + sz.dv) + H * sz.dv * d)
    if ffn == "dense":
        return n + 3 * d * sz.F
    return n + d * sz.E + (sz.shared + sz.k * sz.held / sz.E) * 3 * d * sz.Fe


def stack_flops_per_token(sz, seq: int) -> float:
    """Forward + backward operations a token of the whole stack."""
    n = sz.V * sz.d + sum(layer_matmul_params(sz, f) for _, f in sz.kinds)
    pairs = len(sz.kinds) * triangle_pairs(seq)
    return 6.0 * n + 6.0 * sz.H * (sz.nope + sz.rope + sz.dv) * pairs / seq
