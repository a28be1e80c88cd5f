"""Operations of the looped dense stack (configs/ouro_2_6b.json), from
shapes: what the algorithm needs, not what an implementation spends.

- the whole stack a token, for `train_mfu_stack_pct`: 6 a matmul parameter a
  USE (a layer's W_q, W_k, W_v, W_o and its SwiGLU's three matrices, once a
  pass: `sz.T` uses a step of one set of weights; the untied head as often,
  one a pass; no embedding lookup, no norm, no gate: its dot product with
  one vector is 2 d a pass) and causal softmax attention, 3 S H 2 hd, a
  layer APPLICATION (`sz.T * sz.L` of them). Recomputation (remat, the head
  formed again in the backward) is not counted.
- the head's own share of that (`head_flops_per_token`): what the
  `loop.head` scope's device time is to be held against.
The flash kernels' costs are reduce/flash_counts.py's at `sz.H` | `sz.KVH`
heads of `sz.hd` (metrics/_flash.py sizes a call from them)."""
from __future__ import annotations


def layer_matmul_params(sz) -> float:
    """Matmul parameters a token touches in one application of one layer
    (`sz`: a weights_ouro.OuroSizes)."""
    q = sz.H * sz.hd
    return 4 * sz.d * q + 3 * sz.d * sz.F


def head_flops_per_token(sz) -> float:
    """Forward + backward operations a token of the `sz.T` heads."""
    return 6.0 * sz.T * sz.V * sz.d


def stack_flops_per_token(sz, seq: int) -> float:
    """Forward + backward operations a token of the whole looped stack."""
    layers = 6.0 * layer_matmul_params(sz) + 3.0 * seq * sz.H * 2 * sz.hd
    return sz.T * sz.L * layers + head_flops_per_token(sz)
