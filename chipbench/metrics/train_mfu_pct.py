"""Model FLOP/s utilization of a traced training run: the operations the
forward and backward passes need per token (6 per matmul parameter, plus
causal attention's 6*L*S*d; embedding lookups and recomputation do not
count), times the tokens a second the traced steps made, over chips x the
bf16 peak. layer: train step; moves train_tok_s_chip; source device_trace
(the step's device time)."""
from chipbench.metrics import readers


def flops_per_token(sz, seq: int) -> float:
    q, kv = sz.H * sz.hd, sz.KVH * sz.hd
    mlp = (3 if sz.activation == "swiglu" else 2) * sz.d * sz.F
    n = sz.L * (sz.d * q + 2 * sz.d * kv + q * sz.d + mlp) + sz.V * sz.d
    return 6.0 * n + 6.0 * sz.L * seq * sz.d


def read(ctx):
    step_ms = readers.trace_module_mean_ms(ctx, pattern="^jit__step$")
    if not step_ms:
        return None
    st = ctx["stats"]
    tok_s = st["tokens_per_step"] / (step_ms / 1e3)
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["cell"]["chips"]
    return 100.0 * tok_s * flops_per_token(ctx["sizes"], st["seq"]) / peak
