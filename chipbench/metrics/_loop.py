"""Shared by the per-layer metrics of a looped `stack` configuration
(configs/ouro_2_6b.json): device time under `loop.head`, the scope
models/transformer.py `_backbone` opens round each pass's final norm, head
product, cross-entropy and exit gate (forward, the head formed again in the
backward, and the backward itself all carry it). This file's own scope list,
as `_mixed_heads.py` has its own: reduce/scopes.SCOPES is the hybrid cell's
fixed tuple and `scopes.picture` caches under it. Every reader returns None
where no op carries the scope (an older program, another cell, an untraced
run)."""
from chipbench.metrics import _routed
from chipbench.reduce import scopes

SCOPES = ("loop.head",)


def picture(ctx):
    """{"busy_s", "scope_s"} of the run's trace file, once a run
    (ctx["loop"]); {} when the run was not traced or no op carries
    `loop.head`."""
    if "loop" not in ctx:
        path, pic = _routed._trace_file(ctx), {}
        if path:
            pic = scopes.by_scope(scopes.load(path), SCOPES)
            if "loop.head" not in pic.get("scope_s", {}):
                pic = {}
        if pic:
            ctx.setdefault("notes", {})["loop_scope_s"] = pic["scope_s"]
        ctx["loop"] = pic
    return ctx["loop"]


def head_share_pct(ctx):
    pic = picture(ctx)
    if not pic.get("busy_s"):
        return None
    return 100.0 * pic["scope_s"]["loop.head"] / pic["busy_s"]
