"""Time the train loop lost to stalls inside the window: the sum of the
chip-owning worker's `train.stall` records (each the excess of one step's
period over the median of the last sixteen, at least 250 ms and a quarter of
that median; ray_tpu/util/tracing.py `beat`) that start inside the window
and hold neither the start nor the stop of a profiler session, ms
(reduce/slow_causes.py, from the runner's slow ring after shutdown). 0.0
when there is none; every record is whole in ctx["notes"]["slow_causes"].
None when no entry of the ring says what its thread did (an older commit).
layer: train step; moves train_tok_s_chip; source program_span."""
from chipbench.reduce import slow_causes


def read(ctx):
    return slow_causes.picture(ctx).get("step_stall_ms")
