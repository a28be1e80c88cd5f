"""The start of the runtime in the chip-owning worker, from the program's own
phases: `runtime.import_jax` (the process's first `import jax`),
`runtime.backend_init` (the first listing of the devices: libtpu's start) and
`runtime.mesh`, seconds
(reduce/setup_spans.py; phases of 50 ms or more, read from the runner's slow
ring after shutdown). None when the program emits no such phases (an older
commit). layer: runtime; moves setup_s; source program_span."""
from chipbench.reduce import setup_spans


def read(ctx):
    return setup_spans.picture(ctx).get("setup_runtime_init_s")
