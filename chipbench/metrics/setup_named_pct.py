"""Share of the system's set-up (runner process start to the window's start,
less the reference comparison's stretch) that lies under a `boot.*`,
`runtime.*` or `xla.*` phase of the chip-owning worker or a `ctrl.*` stretch
of the runner, as a union of intervals (overlaps count once). Runs of
programs and waits for the device carry no phase. What is left, and where, is
in ctx["notes"]["setup_spans"]
(reduce/setup_spans.py; phases of 50 ms or more, read from the runner's slow
ring after shutdown). None when the program emits no such phases (an older
commit). layer: runtime; moves setup_s; source program_span."""
from chipbench.reduce import setup_spans


def read(ctx):
    return setup_spans.picture(ctx).get("setup_named_pct")
