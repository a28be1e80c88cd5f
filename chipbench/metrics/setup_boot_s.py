"""A worker's boot, from the program's own phases: `boot.interpreter` (process
creation to `worker_main.main()`: the interpreter and `import ray_tpu`),
`boot.imports`, `boot.connect` (to the controller's acknowledgement) and
`boot.actor_init` (class load and constructor) of the chip-owning worker,
seconds, each less what lies under that worker's own runtime phases
(reduce/setup_spans.py; phases of 50 ms or more, read from the runner's slow
ring after shutdown). None when the program emits no such phases (an older
commit). layer: driver API / cluster control; moves setup_s; source program_span."""
from chipbench.reduce import setup_spans


def read(ctx):
    return setup_spans.picture(ctx).get("setup_boot_s")
