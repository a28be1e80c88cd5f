"""Operations and bytes of the Gated DeltaNet / gated-attention expert stack
(configs/qwen3_next_80b_a3b.json), from shapes: what the algorithm needs, not
what an implementation spends.

- the delta rule's core at a SCALAR decay (`gdn_core_fwd` / `gdn_core_bwd`,
  the `gdn.core` scope), chunks of C tokens, H_k key heads serving H_v value
  heads of d_k / d_v (flops = 2 x multiply-adds; a triangular product counts
  its lower half). With one decay a head e^(G_t - G_s) is a [C, C] matrix a
  value head, so K K^T and Q K^T are products a KEY head, scaled a value
  head on the vector unit:
    a key head and token:    A's and P's raw products, C d_k each;
    a value head and token:  W = T (beta K e^G) C d_k; U0 = T (beta V) C d_v;
                             P U C d_v; between chunks W S, (Q e^G) S and
                             the state update, 3 x 2 d_k d_v; the inverse
                             T = (I + A)^-1 by substitution, C^2 / 3.
  The backward of a product of two matrices is two products of its size:
  backward = 2 x forward. Recomputation under remat is not counted. Bytes:
  q and k once a KEY head, v in the compute type, g and beta [B,S,H_v]
  float32, read once and o written once forward; all of them and do read,
  and a gradient of each written, backward. Whatever body runs the rule (a
  broadcast into the per-channel kernels today, ops/kda.py) is held to
  these, so the reading stays comparable when a lighter one replaces it.
- the flash kernels on the gated attention layer (16 query heads over 2 key
  heads of 256): reduce/mellum2_counts.py `full_flash_fwd` / `_bwd`, the
  triangle's pairs, K and V counted once a key head, imported.
- the held experts (`moe.experts`): reduce/mellum2_counts.py `experts`,
  imported.
- the whole stack a token, for `train_mfu_stack_pct`: 6 a matmul parameter a
  token touches (a DeltaNet layer's W_qkvz, W_ba and W_o; the attention
  layer's doubled W_q, W_k, W_v, W_o; the router, the shared expert and its
  gate, k x held / E of the held experts under even routing; the untied
  head; no embedding lookup, no norm, no convolution), 12 H D a pair of the
  attention layer's triangle, and three times the core's forward a DeltaNet
  layer (no recomputation)."""
from __future__ import annotations

from typing import Dict

from chipbench.reduce.mellum2_counts import (  # noqa: F401
    experts, full_flash_bwd as flash_bwd, full_flash_fwd as flash_fwd,
    triangle_pairs)


def gdn_core_fwd_flops_per_token(Hk: int, Hv: int, dk: int, dv: int,
                                 C: int) -> float:
    return (Hk * 2.0 * C * dk
            + Hv * (6.0 * dk * dv + C * (dk + 2.0 * dv) + C * C / 3.0))


def _core_io(Hk, Hv, dk, dv, itemsize):
    """(bytes in, bytes out) a token of the forward."""
    return ((2 * Hk * dk + Hv * dv) * itemsize + 8 * Hv,  # q k v, g, beta
            Hv * dv * itemsize)


def gdn_core_fwd(B: int, Hk: int, Hv: int, S: int, dk: int, dv: int, C: int,
                 itemsize: int = 2) -> Dict[str, float]:
    """The forward of one DeltaNet layer's core on [B, S] tokens."""
    ins, out = _core_io(Hk, Hv, dk, dv, itemsize)
    return {"flops": B * S * gdn_core_fwd_flops_per_token(Hk, Hv, dk, dv, C),
            "bytes": float(B * S) * (ins + out)}


def gdn_core_bwd(B: int, Hk: int, Hv: int, S: int, dk: int, dv: int, C: int,
                 itemsize: int = 2) -> Dict[str, float]:
    """The backward: twice the forward's operations; the inputs and do
    read, a gradient of each input written."""
    ins, out = _core_io(Hk, Hv, dk, dv, itemsize)
    return {"flops": 2.0 * B * S * gdn_core_fwd_flops_per_token(
        Hk, Hv, dk, dv, C), "bytes": float(B * S) * (ins + out + ins)}


def layer_matmul_params(sz, mixer: str) -> float:
    """Matmul parameters a token touches in one layer with `mixer` (`sz`: a
    weights_qwen3_next.QwenNextSizes); every feed-forward is the experts."""
    d = sz.d
    if mixer == "gdn":
        nk, nv = sz.Hk * sz.ghd, sz.Hv * sz.ghd
        n = d * (2 * nk + 2 * nv) + d * 2 * sz.Hv + nv * d
    else:
        q, kv = sz.H * sz.hd, sz.KVH * sz.hd
        n = d * 2 * q + 2 * d * kv + q * d
    return (n + d * sz.E + d
            + (sz.shared + sz.k * sz.held / sz.E) * 3 * d * sz.Fe)


def stack_flops_per_token(sz, seq: int) -> float:
    """Forward + backward operations a token of the whole stack."""
    n = sz.V * sz.d + sum(layer_matmul_params(sz, m) for m, _ in sz.kinds)
    gdn = sum(m == "gdn" for m, _ in sz.kinds)
    attn = len(sz.kinds) - gdn
    return (6.0 * n + 12.0 * sz.H * sz.hd * attn * triangle_pairs(seq) / seq
            + 3.0 * gdn * gdn_core_fwd_flops_per_token(
                sz.Hk, sz.Hv, sz.ghd, sz.ghd, sz.chunk))
