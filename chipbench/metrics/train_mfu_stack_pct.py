"""Model FLOP/s utilization of a traced training run of a `train_stack`
cell, by the configuration's own counts module (`config["stack"]["counts"]`,
`stack_flops_per_token(sz, seq)`: 6 per matmul parameter a token touches,
causal softmax attention an attention layer, the chunked core a Mamba-2
layer); times the tokens a second of the traced steps, over the bf16 peak.
Recomputation does not count. layer: train step; moves train_tok_s_chip;
source device_trace."""
from chipbench.metrics import _stack, readers


def read(ctx):
    sz, counts = _stack.sizes_and_counts(ctx)
    step_ms = readers.trace_module_mean_ms(ctx, pattern="^jit__step$")
    if sz is None or not step_ms:
        return None
    st = ctx["stats"]
    tok_s = st["tokens_per_step"] / (step_ms / 1e3)
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["cell"]["chips"]
    return 100.0 * tok_s * counts.stack_flops_per_token(sz, st["seq"]) / peak
