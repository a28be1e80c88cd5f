"""Shared by the per-layer metrics of a `train_stack` cell (a configuration
file with a `stack` section): its sizes and counts module, device time under
the Mamba-2 scopes, and the steps in the traced window (`_hybrid`'s). The
scopes are read here, with this file's own list: reduce/scopes.SCOPES is the
hybrid cell's, and `scopes.picture` caches under it."""
import glob
import importlib
import os

from chipbench.metrics._hybrid import steps_traced  # noqa: F401 (shared)
from chipbench.reduce import scopes

SCOPES = ("ssd.core", "mamba")  # the inner scope first


def sizes_and_counts(ctx):
    """(sizes, counts module) of the cell's configuration, (None, None)
    for a configuration without a `stack` section."""
    config = ctx["cell"]["config"]
    st = config.get("stack")
    if not st:
        return None, None
    rehearse = bool(int(os.environ.get("CHIPBENCH_REHEARSE", "0")))
    return (importlib.import_module(st["weights"]).sizes_of(config, rehearse),
            importlib.import_module(st["counts"]))


def picture(ctx):
    """scopes.by_scope of the run's trace file over SCOPES, once a run
    (ctx["stack_scopes"]); {} when the run was not traced or no op carries
    either scope (a program without the mixer)."""
    if "stack_scopes" not in ctx:
        from chipbench import inworker

        files = glob.glob(os.path.join(inworker.TRACE_DIR, "**",
                                       "*.xplane.pb"), recursive=True)
        pic = scopes.by_scope(scopes.load(files[0]), SCOPES) if files and \
            ctx.get("trace") else {}
        if not any(s in pic.get("scope_s", {}) for s in SCOPES):
            pic = {}
        if pic:
            ctx.setdefault("notes", {})["stack_scope_s"] = pic["scope_s"]
        ctx["stack_scopes"] = pic
    return ctx["stack_scopes"]


def scope_share_pct(ctx, name):
    pic = picture(ctx)
    if not pic.get("busy_s") or name not in pic["scope_s"]:
        return None
    return 100.0 * pic["scope_s"][name] / pic["busy_s"]
