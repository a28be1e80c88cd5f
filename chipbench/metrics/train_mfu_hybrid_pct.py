"""Model FLOP/s utilization of a traced training run of the hybrid stack, by
this model's own operations a token: 6 per matmul parameter a token touches
(KDA and MLA projections, the dense layer, router, shared expert, the held
experts at their even share k * held / E, the head; no embedding lookup),
causal softmax attention 3 S H (d_qk + d_v) a latent layer, and the chunked
KDA core's operations (reduce/kda_counts.py) a KDA layer; times the tokens a
second of the traced steps, over the bf16 peak. Recomputation does not
count. layer: train step; moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _hybrid, readers
from chipbench.reduce import kda_counts


def flops_per_token(sz, seq: int, chunk: int) -> float:
    d = sz.d
    n = sz.d * sz.V  # the head
    for mixer, ffn in sz.kinds:
        if mixer == "kda":
            hh = sz.kda_H * sz.kda_hd
            n += 4 * d * hh + 2 * (d * sz.rank + sz.rank * hh) + d * sz.kda_H
        else:
            n += (d * sz.H * (sz.nope + sz.rope) + d * (sz.lat + sz.rope)
                  + sz.lat * sz.H * (sz.nope + sz.dv) + sz.H * sz.dv * d)
        if ffn == "dense":
            n += 3 * d * sz.F
        else:
            n += d * sz.E + 3 * d * sz.Fe * (sz.shared
                                             + sz.k * sz.held / sz.E)
    total = 6.0 * n
    for mixer, _ in sz.kinds:
        if mixer == "kda":
            total += 3.0 * kda_counts.kda_core_fwd_flops_per_token(
                sz.kda_H, sz.kda_hd, sz.kda_hd, chunk)
        else:
            total += 3.0 * seq * sz.H * (sz.nope + sz.rope + sz.dv)
    return total


def read(ctx):
    sz = _hybrid.sizes(ctx)
    step_ms = readers.trace_module_mean_ms(ctx, pattern="^jit__step$")
    if sz is None or not step_ms:
        return None
    st = ctx["stats"]
    chunk = ctx["cell"]["config"]["transformer_config"]["kda_chunk"]
    tok_s = st["tokens_per_step"] / (step_ms / 1e3)
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["cell"]["chips"]
    return 100.0 * tok_s * flops_per_token(sz, st["seq"], chunk) / peak
