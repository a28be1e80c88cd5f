"""Share of the device's idle time in the traced window (idle gaps of 2 us
or more, reduce/xplane.py's definition) that lies under a named host phase
of the program, on the thread that launched the next program on that device
(reduce/host_spans.py). The rest is `unattributed`: the host was outside
every phase, or the launching thread could not be found. None when the
program emits no phases (an older commit) or the device was never idle.
layer: device; moves train_tok_s_chip; source device_trace + program_span.
The first of the host-span readers to run also writes the whole picture
(idle by phase, per-phase times, relay hops, stalls) to ctx["notes"]."""
from chipbench.reduce import host_spans


def read(ctx):
    return host_spans.picture(ctx).get("idle_attributed_pct")
