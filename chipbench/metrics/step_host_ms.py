"""Host time of one train step in the traced window: the `train.step`
(enqueue of the step program) and `train.shard_batch` (placing the batch)
phases of the program, summed, over the steps traced. What is left of a
step's wall time is the wait for the device. None when the program emits
no such phases. layer: train step; moves train_tok_s_chip; source
program_span."""
from chipbench.reduce import host_spans


def read(ctx):
    ph = host_spans.picture(ctx).get("trace_phases") or {}
    step = ph.get("train.step")
    if not step:
        return None
    shard = ph.get("train.shard_batch", {"total_ms": 0.0})
    return (step["total_ms"] + shard["total_ms"]) / step["count"]
