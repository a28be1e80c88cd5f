"""`ray_tpu/ops/dispatch.py`'s table as a test: every kernel family, at a
shape of a cell that runs it, on the CPU, on a TPU and on a TPU under a mesh
-> the body its dispatcher takes (and the phase-table row it writes) or the
refusal it raises; and no other module of `ray_tpu/ops` or `ray_tpu/models`
reads the platform or the sharding context. The bodies are stubs and the
arguments shapes: each case is the dispatcher's own lines, milliseconds."""
import ast
import glob
import os
import types

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import (attention as att, dispatch, flash_attention as fa,
                         kda, moe, selective_scan as ss, shortconv as sc, ssd)
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BF16, F32 = jnp.bfloat16, jnp.float32
sd = jax.ShapeDtypeStruct


def _stub(monkeypatch, module, **bodies):
    for name, says in bodies.items():
        monkeypatch.setattr(module, name, lambda *a, _says=says, **kw: _says)


def _flash(mp):  # gpt2_124m.train_1chip: 16 x 1,024, 12 heads of 64
    _stub(mp, fa, flash_attention="pallas")
    _stub(mp, att, reference_attention="xla")
    mp.setattr(att, "_shard_mapped_attention",
               lambda q, k, v, causal, scale, kernels, window:
               "shard_map(pallas)" if kernels else None)
    q = sd((16, 1024, 12, 64), BF16)
    return att.attention(q, q, q)


def _kda(mp):  # kimi_linear_48b_a3b.train_share_8k: 32 heads of 128
    _stub(mp, kda, kda_chunked_pallas="pallas", kda_chunked_xla="xla")
    q, beta = sd((1, 8192, 32, 128), BF16), sd((1, 8192, 32), F32)
    return kda.kda_chunked(q, q, q, sd(q.shape, F32), beta, chunk=128)


def _gdn(mp):  # qwen3_next_80b_a3b.train_rank16_16k: 32 value heads over 16
    _stub(mp, kda, kda_chunked_pallas="pallas", kda_chunked_xla="xla")
    q, v = sd((1, 16384, 16, 128), BF16), sd((1, 16384, 32, 128), BF16)
    g = sd((1, 16384, 32), F32)
    return kda.kda_chunked(q, q, v, g, g, chunk=128)


def _conv(mp):  # the same cell's q | k: four taps, the norm over 128
    _stub(mp, kda, mixer_conv_pallas="pallas", mixer_conv_xla="xla")
    return kda.mixer_conv(sd((1, 8192, 32, 128), BF16),
                          sd((4, 32, 128), F32), l2=True)


def _norm(mp):  # the same cell's way out of the core: SiLU(z) after the norm
    _stub(mp, kda, gated_norm_pallas="pallas", gated_norm_xla="xla")
    o = sd((1, 16384, 32, 128), BF16)
    return kda.gated_norm(o, o, sd((128,), F32), group=128, gate_act="silu",
                          gate_first=False, eps=1e-6)


def _ssd(mp):  # granite_4_0_h_micro.train_stage_4k: 64 heads of 64, state 128
    _stub(mp, ssd, ssd_chunked_pallas="pallas", ssd_chunked_xla="xla")
    x, bc = sd((1, 4096, 64, 64), BF16), sd((1, 4096, 1, 128), BF16)
    dt, a = sd((1, 4096, 64), F32), sd((64,), F32)
    return ssd.ssd_chunked(x, dt, a, bc, bc, a, chunk=256)


def _moe(mp):  # mellum2_12b_a2_5b.train_share_16k: 2,304 -> 896, bfloat16
    s = dispatch.site()  # (`moe_ffn_held` asks so before its first window)
    return "pallas" if moe.use_kernels(s.platform, BF16, (2304, 896),
                                       s.on_mesh) else "xla"


def _dsa(mp):  # keye_vl_2_0_30b_a3b.train_sparse_rank8: no rule, no XLA body
    dispatch.one_chip("a learned-sparse-attention (dsa) layer's kernels")
    return "interpreted" if dispatch.interpret() else "pallas"


def _mamba1(mp):  # phi4_mini_flash_reasoning.train_stage_16k: 5,120 x 16
    _stub(mp, ss, selective_scan_pallas="pallas", selective_scan_xla="xla")
    u, bc = sd((1, 16384, 5120), F32), sd((1, 16384, 16), F32)
    return ss.selective_scan(u, u, sd((5120, 16), F32), bc, bc,
                             sd((5120,), F32), chunk=256)


def _shortconv(mp):  # lfm2_8b_a1b.train_rank4_8k: [Bg ; Cg ; x] of 2,048
    _stub(mp, sc, gated_conv_pallas="pallas", gated_conv_xla="xla")
    return sc.gated_conv(sd((4, 8192, 6144), BF16), sd((3, 2048), F32))


REFUSES = NotImplementedError
# family: (its dispatcher, the phase-table row, then what it takes on the
# CPU, on a TPU, on a TPU under a mesh, on the CPU under a mesh)
TABLE = {
    "flash": (_flash, None, "xla", "pallas", "shard_map(pallas)", "xla"),
    "kda": (_kda, "kda.core", "xla", "pallas", "xla", "xla"),
    "gdn": (_gdn, "gdn.core", "xla", "pallas", "xla", "xla"),
    "mixer.conv": (_conv, "mixer.conv", "xla", "pallas", "xla", "xla"),
    "mixer.gated_norm": (_norm, "mixer.gated_norm", "xla", "pallas", "xla",
                         "xla"),
    "ssd": (_ssd, "ssd.core", "xla", "pallas", "xla", "xla"),
    "moe": (_moe, None, "xla", "pallas", "xla", "xla"),
    "dsa": (_dsa, None, "interpreted", "pallas", REFUSES, REFUSES),
    "mamba1": (_mamba1, "mamba1.core", "xla", "pallas", REFUSES, REFUSES),
    "shortconv": (_shortconv, "shortconv.core", "xla", "pallas", "xla",
                  "xla"),
}
SITES = [("cpu", False), ("tpu", False), ("tpu", True), ("cpu", True)]


@pytest.mark.parametrize("platform,on_mesh", SITES)
@pytest.mark.parametrize("family", sorted(TABLE))
def test_a_family_takes_the_body_the_table_says(monkeypatch, family,
                                                platform, on_mesh):
    ask, row, *takes = TABLE[family]
    want = takes[SITES.index((platform, on_mesh))]
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(platform=platform)])
    monkeypatch.setattr(dispatch, "current_sharding_ctx", lambda: (
        types.SimpleNamespace(size=4), None) if on_mesh else None)
    assert dispatch.site() == (platform, on_mesh)
    count = lambda: {n: r["count"] for n, r in tracing.phase_table().items()
                     if row and n.startswith(row + ".")}
    before = count()
    if want is REFUSES:
        with pytest.raises(REFUSES, match="one chip, not under a mesh of 4"):
            ask(monkeypatch)
        assert count() == before
        return
    assert ask(monkeypatch) == want
    if row:
        name = row + "." + want
        assert count() == dict(before, **{name: before.get(name, 0) + 1})


def _readers(path):
    """The top-level functions of a file that name `current_sharding_ctx` or
    call `jax.devices`."""
    with open(path) as f:
        tree = ast.parse(f.read())
    named = lambda node: {getattr(node, a, None) for a in ("id", "name",
                                                           "attr")}
    return {getattr(top, "name", "<module>")
            for top in tree.body for node in ast.walk(top)
            if "current_sharding_ctx" in named(node)
            or "devices" in named(node)
            and "jax" in named(getattr(node, "value", None))}


def test_only_dispatch_reads_the_platform_and_the_context():
    """A kernel is chosen, interpreted or refused from what `dispatch.site`,
    `interpret` and `one_chip` read, nowhere else in `ray_tpu/ops` and
    `ray_tpu/models`. The other readers, by name, choose no kernel:
    `attention._shard_mapped_attention` takes the mesh and the rules for its
    `shard_map`'s specs, and `parallel/tensor_overlap.py` `plan` for the
    rings over `tensor`."""
    files = (glob.glob(os.path.join(ROOT, "ray_tpu", "ops", "*.py"))
             + glob.glob(os.path.join(ROOT, "ray_tpu", "models", "*.py"))
             + glob.glob(os.path.join(ROOT, "ray_tpu", "parallel",
                                      "tensor_overlap.py")))
    assert len(files) > 15
    readers = {(os.path.basename(p), fn) for p in files for fn in _readers(p)}
    assert readers == {
        ("dispatch.py", "<module>"), ("dispatch.py", "site"),
        ("dispatch.py", "interpret"), ("dispatch.py", "one_chip"),
        ("attention.py", "_shard_mapped_attention"),
        ("tensor_overlap.py", "plan")}, readers
