"""jaxpr -> StableHLO: the chip-owning worker's `xla.lower` phases (self time)
that start before the window and lie outside the reference comparison's
stretch, seconds
(reduce/setup_spans.py; phases of 50 ms or more, read from the runner's slow
ring after shutdown). None when the program emits no such phases (an older
commit). layer: runtime; moves setup_s; source program_span."""
from chipbench.reduce import setup_spans


def read(ctx):
    return setup_spans.picture(ctx).get("setup_lower_s")
