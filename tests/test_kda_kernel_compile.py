"""The KDA, SSD, flash and sparse-selection kernels compiled for a v5e at the cells' real shapes, with no chip:
the TPU compiler is installed here and compiles for a described device.
Interpret mode (tests/test_kda.py, tests/test_ssd.py) cannot see what Mosaic refuses
(unaligned slices, VMEM over the limit, an op with no lowering). Nothing
runs, so this says nothing about results or times. The topology is described
inside a fixture, never at import (one process at a time may load libtpu)."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops import (dispatch, flash_attention as fa, kda,
                         shortconv as sc, ssd)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compiled_not_interpreted(monkeypatch):
    """The platform here is cpu, which `dispatch.interpret()` reads; and a
    compile for a device that is not attached must not be read back from the
    cache."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(dispatch, "interpret", lambda: False)
    # The SSD and convolution calls are jitted on their own: no trace made in
    # the other mode.
    forget = lambda: [f.clear_cache() for f in (
        ssd._ssd_fwd_call, ssd._ssd_bwd_call, kda._conv_fwd_call,
        kda._conv_bwd_call, kda._norm_fwd_call, kda._norm_bwd_call,
        sc._fwd_call, sc._bwd_call)]
    forget()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    forget()


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_kda_kernels_compile_for_v5e(one_chip, compiled_not_interpreted,
                                     what):
    """1 x 8192 tokens, 32 heads of 128, chunks of 128, bfloat16: the shape
    of `kimi_linear_48b_a3b.train_share_8k`. The program holds the two
    Mosaic calls and no [B,H,S,d] copy of an input."""
    B, S, H, d = 1, 8192, 32, 128
    sd = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    q = sd((B, S, H, d), jnp.bfloat16)
    g, beta = sd((B, S, H, d), jnp.float32), sd((B, S, H), jnp.float32)

    def loss(q, k, v, g, beta):
        o, _ = kda.kda_chunked_pallas(q, k, v, g, beta, chunk=128)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    fn = loss if what == "forward" else jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    text = jax.jit(fn).lower(q, q, q, g, beta).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == (
        1 if what == "forward" else 2)


@pytest.mark.parametrize("decay", ["channel", "head"])
def test_a_chunk_of_256_is_refused_for_v5e(one_chip, compiled_not_interpreted,
                                           decay):
    """What `kda.use_kernels` goes by when it admits a chunk of 128 alone: at
    256 Mosaic refuses the per-channel and the scalar-decay call alike (the
    inverse's strided load wants a last dimension of 128), which interpret
    mode does not see."""
    B, S, H, d = 1, 1024, 2, 128
    sd = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    q, beta = sd((B, S, H, d), jnp.bfloat16), sd((B, S, H), jnp.float32)
    g = sd((B, S, H, d), jnp.float32) if decay == "channel" else beta
    lowered = jax.jit(lambda q, k, v, g, beta: kda.kda_chunked_pallas(
        q, k, v, g, beta, chunk=256)[0]).lower(q, q, q, g, beta)
    with pytest.raises(Exception, match="last dim size is not 128"):
        lowered.compile()


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_gdn_core_compiles_for_v5e(one_chip, compiled_not_interpreted, what):
    """1 x 16,384 tokens, 32 value heads over 16 key heads of 128, a decay a
    head, chunks of 128, bfloat16: the shape of
    `qwen3_next_80b_a3b.train_rank16_16k`. The scalar-decay, shared-key call
    has its own two Mosaic calls (one forward, two in the gradient), which
    read q, k a key head and g [B,S,H_v] where they lie: no [B,H,S,d] copy
    of an input, and no float32 [B,S,H_v,d_k] (g over the channels, or its
    gradient before the sum) anywhere in the compiled program."""
    B, S, Hk, Hv, d = 1, 16384, 16, 32, 128
    sd = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    q, v = sd((B, S, Hk, d), jnp.bfloat16), sd((B, S, Hv, d), jnp.bfloat16)
    g = beta = sd((B, S, Hv), jnp.float32)

    def loss(q, k, v, g, beta):
        o, _ = kda.kda_chunked_pallas(q, k, v, g, beta, chunk=128)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    fn = loss if what == "forward" else jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    text = jax.jit(fn).lower(q, q, v, g, beta).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == (
        1 if what == "forward" else 2)
    assert f"[{B},{Hv},{S},{d}]" not in text
    assert f"f32[{B},{S},{Hv},{d}]" not in text


def test_mixer_conv_kernels_compile_for_v5e(one_chip,
                                            compiled_not_interpreted):
    """The convolution path's pair (ops/kda.py `mixer_conv_pallas`) in one
    gradient program at two of the cells' calls, bfloat16, four taps: q | k
    of `kimi_linear_48b_a3b.train_share_8k` with the norm ([1,8192,32,128];
    qwen3_next's v at 16,384 rows is the same blocks) and x of
    `granite_4_0_h_micro.train_stage_4k` with its bias ([1,4096,64,64]:
    4,096 channels, no norm). A forward and a backward Mosaic call each,
    which read x [B,S,C] where a projection's product writes it (the
    arguments lie so here: one that lies [B,S,H,d] tiles its heads and is
    copied first) and write y and dx the same way: no copy of x's size."""
    sd = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    q, x = sd((1, 8192, 4096), jnp.bfloat16), sd((1, 4096, 4096),
                                                 jnp.bfloat16)
    wq, wx = sd((4, 32, 128), jnp.float32), sd((4, 64, 64), jnp.float32)

    def loss(q, wq, x, wx, bx):
        y = kda.mixer_conv_pallas(q.reshape(1, 8192, 32, 128), wq, l2=True)
        z = kda.mixer_conv_pallas(x.reshape(1, 4096, 64, 64), wx, bx)
        return (jnp.sum(y.astype(jnp.float32) ** 2)
                + jnp.sum(z.astype(jnp.float32) ** 2))

    text = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        q, wq, x, wx, sd((64, 64), jnp.float32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert not re.search(r"%copy[.\d]* = bf16\[1,(8192|4096),", text)


def test_shortconv_kernels_compile_for_v5e(one_chip,
                                           compiled_not_interpreted):
    """The gated short convolution's pair (ops/shortconv.py) in one gradient
    program at `lfm2_8b_a1b.train_rank4_8k`'s call: the joint projection's
    output [4,8192,6144] bfloat16, three taps over 2,048 channels. A forward
    and a backward Mosaic call, which read [Bg ; Cg ; x] where the
    projection writes them and write d[Bg ; Cg ; x] as one tensor: no slice
    and no copy of a part's or the whole's size."""
    sd = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    p, w = sd((4, 8192, 6144), jnp.bfloat16), sd((3, 2048), jnp.float32)

    def loss(p, w):
        return jnp.sum(sc.gated_conv_pallas(p, w).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        p, w).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert not re.search(r"%(copy|slice)[.\d]* = bf16\[4,8192,(2048|6144)\]",
                         text)


@pytest.mark.parametrize("S,ch,group,act,first", [
    (16384, (32, 128), 128, "silu", False),    # qwen3_next train_rank16_16k
    (8192, (32, 128), 128, "sigmoid", False),  # kimi_linear train_share_8k
    (4096, (64, 64), 4096, "silu", True)])     # granite train_stage_4k
def test_gated_norm_kernels_compile_for_v5e(one_chip,
                                            compiled_not_interpreted, S, ch,
                                            group, act, first):
    """The gated norm's pair (ops/kda.py `gated_norm_pallas`) alone in one
    gradient program at the three cells' calls, bfloat16: a norm a head of
    128 times SiLU(z) or a sigmoid, and SiLU(z) times y under one norm over
    4,096 channels (a block all of them, a row chunk in two passes). A
    forward and a backward Mosaic call, which read y and the gate [B,S,C]
    where the core and a projection's product write them: no copy of their
    size and nothing float32 of it anywhere in the program."""
    sd = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    C = ch[0] * ch[1]
    y = sd((1, S, C), jnp.bfloat16)
    w = sd(ch[-1:] if ch[-1] == group else ch, jnp.float32)

    def pair(y, gate, w, do):
        out, vjp = jax.vjp(lambda y, gate, w: kda.gated_norm_pallas(
            y.reshape((1, S) + ch), gate.reshape((1, S) + ch), w, group=group,
            gate_act=act, gate_first=first, eps=1e-6).reshape(1, S, C),
                           y, gate, w)
        return (out,) + vjp(do)

    text = jax.jit(pair).lower(y, y, w, y).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert not re.search(rf"%copy[.\d]* = \w+\[1,{S},", text)
    assert f"f32[1,{S},{C}]" not in text and f"f32[1,{S},{ch[0]}," not in text


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_ssd_kernels_compile_for_v5e(one_chip, compiled_not_interpreted,
                                     what):
    """1 x 4096 tokens, 64 heads of 64, state 128, one group, chunks of 256,
    bfloat16: the shape of `granite_4_0_h_micro.train_stage_4k`. The program
    holds the two Mosaic calls, no [B,H,S,P] copy of x and nothing
    [chunk, chunk] a head."""
    B, S, H, P, N, chunk = 1, 4096, 64, 64, 128, 256
    sd = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    x, bc = sd((B, S, H, P), jnp.bfloat16), sd((B, S, 1, N), jnp.bfloat16)
    dt, a = sd((B, S, H), jnp.float32), sd((H,), jnp.float32)

    def loss(x, dt, A, Bm, Cm, D):
        y, _ = ssd.ssd_chunked_pallas(x, dt, A, Bm, Cm, D, chunk=chunk)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    fn = loss if what == "forward" else jax.grad(loss, argnums=range(6))
    text = jax.jit(fn).lower(x, dt, a, bc, bc, a).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == (
        1 if what == "forward" else 2)
    assert f"[{B},{H},{S},{P}]" not in text
    assert f",{chunk},{chunk}]" not in text.replace(f"f32[{chunk},{chunk}]", "")


FLASH_SHAPES = {  # (B, S, H, KVH, D, Dv, window)
    "gpt2": (16, 1024, 12, 12, 64, 64, None),    # gpt2_124m.train_1chip
    "internlm2_shard": (4, 2048, 8, 4, 128, 128, None),  # train_mesh4, a device
    "kimi_mla": (1, 8192, 32, 32, 192, 128, None),  # kimi_linear train_share_8k
    "granite_gqa64": (1, 4096, 32, 8, 64, 64, None),  # granite train_stage_4k
    "mellum2_full": (1, 16384, 32, 4, 128, 128, None),  # mellum2 train_share_16k,
    "mellum2_swa": (1, 16384, 32, 4, 128, 128, 1024),  # a full and a windowed
    "kanana_mla": (1, 16384, 32, 32, 192, 128, None),  # kanana train_rank8_16k
    "qwen3_next_gattn": (1, 16384, 16, 2, 256, 256, None),  # train_rank16_16k
    "laguna_full": (1, 8192, 24, 4, 128, 128, None),  # laguna train_rank32_8k: a
    "laguna_swa": (1, 8192, 36, 4, 128, 128, 512),  # full (groups of 6), a windowed (9)
}
# Shapes compiled in ONE program (ROADMAP D11: the file's case-seconds): a
# configuration's two attention kinds. (ouro_2_6b's 16 | 16 heads of 128 at
# 8,192 are in its whole step's compile, the last test of the file.)
FLASH_GROUPS = (("mellum2_full", "mellum2_swa"), ("laguna_full", "laguna_swa"))


@pytest.fixture(scope="module")
def flash_texts():
    """{group: compiled text}: a group is compiled by its first case."""
    return {}


def _flash_text(one_chip, texts, name):
    """The compiled gradient program that holds `name`'s two kernels (and
    its group's), compiled once a group."""
    group = next((g for g in FLASH_GROUPS if name in g), (name,))
    if group not in texts:
        sd = lambda sh: jax.ShapeDtypeStruct(sh, jnp.bfloat16,
                                             sharding=one_chip)
        args, windows = [], []
        for B, S, H, KVH, D, Dv, window in map(FLASH_SHAPES.get, group):
            args.append((sd((B, S, H, D)), sd((B, S, KVH, D)),
                         sd((B, S, KVH, Dv))))
            windows.append(window)

        def loss(qkvs):
            return sum(jnp.sum(fa.flash_attention(*qkv, window=w).astype(
                jnp.float32) ** 2) for qkv, w in zip(qkvs, windows))

        texts[group] = jax.jit(jax.grad(loss)).lower(
            args).compile().as_text()
    return texts[group], len(group)


@pytest.mark.parametrize("name", sorted(FLASH_SHAPES))
def test_flash_kernels_compile_for_v5e(one_chip, compiled_not_interpreted,
                                       flash_texts, name):
    """The forward and the one backward kernel (dK, dV and dQ; a head's
    float32 dQ accumulator and dQ's whole-head output block in VMEM, 16 +
    16 MiB at kanana's shape) at the tiles `_TILES` gives each cell's shape,
    bfloat16, causal: two Mosaic calls a shape in the gradient's program,
    every cell's shape taking the fused backward, and the statistics cross
    them with the sequence on the lanes ([B,H,1,S])."""
    B, S, H, KVH, D, Dv, window = FLASH_SHAPES[name]
    text, shapes = _flash_text(one_chip, flash_texts, name)
    assert fa.bwd_kind(S, D, Dv, jnp.bfloat16) == "fused"
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * shapes
    assert f"f32[{B},{H},1,{S}]" in text and f"f32[{B},{H},{S},1]" not in text


def test_flash_pair_compiles_past_the_budget_for_v5e(
        one_chip, compiled_not_interpreted):
    """A head whose dQ does not fit the fused backward's VMEM budget (65,536
    positions at keys 192 wide: 64 + 64 MiB; no cell has one) takes the dQ
    and dK/dV kernels by the same rule: three Mosaic calls."""
    B, S, H, D, Dv = 1, 65536, 2, 192, 128
    sd = lambda sh: jax.ShapeDtypeStruct(sh, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v).astype(jnp.float32) ** 2)

    assert fa.bwd_kind(S, D, Dv, jnp.bfloat16) == "split"
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        sd((B, S, H, D)), sd((B, S, H, D)), sd((B, S, H, Dv))).compile(
        ).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_the_sparse_selection_compiles_for_v5e(one_chip,
                                               compiled_not_interpreted):
    """`dsa_select` at the Keye-VL-2.0 cell's shape (1 x 32,768 positions,
    an indexer of 16 heads of 64, the top 2,048): a block of 128 rows' scores
    in 16 MiB of VMEM, and a search whose loops a vector reduced to a scalar
    steers (`lax.while_loop` and `lax.cond` inside the Mosaic kernel), which
    interpret mode cannot refuse. One Mosaic call, five outputs."""
    from ray_tpu.ops import sparse_attention as sa

    B, S, HI, dI = 1, 32768, 16, 64
    sd = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    text = jax.jit(lambda a, b, c: sa.select(a, b, c, 2048)).lower(
        sd((B, HI, S, dI), jnp.bfloat16), sd((B, dI, S), jnp.bfloat16),
        sd((B, HI, 1, S), jnp.float32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"s32[{B},{S},{S // 32}]" in text


@pytest.mark.parametrize("T,E,first,Eh,F,kind,d,k", [
    (16384, 64, 16, 16, 896, "softmax", 2304, 8),
    (8192, 256, 104, 8, 1024, "sigmoid", 2304, 8),
    (16384, 128, 48, 16, 768, "sigmoid", 2048, 6),
    (16384, 512, 224, 32, 512, "softmax", 2048, 10),
    (8192, 256, 120, 8, 1024, "sigmoid", 3072, 10)])
def test_held_experts_compile_for_v5e(one_chip, compiled_not_interpreted,
                                      monkeypatch, T, E, first, Eh, F, kind,
                                      d, k):
    """One expert layer, forward and gradient, at the shapes of
    `mellum2_12b_a2_5b.train_share_16k`,
    `kimi_linear_48b_a3b.train_share_8k` (d 2304, 8 experts a token) and
    `kanana_2_30b_a3b.train_rank8_16k` (d 2048, 6 a token, experts 768 wide:
    `_tiles` takes 1024 x 768 and 768 x 1024) and
    `qwen3_next_80b_a3b.train_rank16_16k` (32 of 512 experts 512 wide, 10 a
    token: a first window of 25,600 rows) and
    `laguna_s_2_1.train_rank32_8k` (d 3072, 8 of 256 experts 1,024 wide, 10
    a token: the row-a-token window of 8,192), bfloat16, with the grouped
    products dispatched as on the chip: the
    first window's two products and their four transposes are the Pallas
    grouped matmul at `_tiles` (`gmm` / `tgmm` in the program's text), and
    its two sums back to the tokens (the combine and dx) two more `tgmm`s
    over token blocks at `_sum_tiles` (`moe.block_sums`); the loop of
    further windows keeps `ragged-dot` and the scatter-add."""
    import functools

    from ray_tpu.ops import moe

    rule = moe.use_kernels  # the platform here is cpu
    monkeypatch.setattr(moe, "use_kernels", lambda _, *a: rule("tpu", *a))
    sd = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    if kind == "sigmoid":
        route = functools.partial(moe.sigmoid_route, bias=jnp.zeros((E,)),
                                  experts_per_token=k, routed_scale=2.446)
    else:
        route = functools.partial(moe.softmax_route, experts_per_token=k)

    def loss(x, rw, wgu, wd):
        y, _ = moe.moe_ffn_held(x, rw, wgu, wd, route=route,
                                held_first=first)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        sd((1, T, d), jnp.bfloat16), sd((d, E), jnp.float32),
        sd((Eh, d, 2, F), jnp.float32), sd((Eh, F, d), jnp.float32)
    ).compile().as_text()
    assert text.count(" custom-call(") >= 8
    assert len(re.findall(r"%t?gmm[.\d]* = ", text)) == 8, text.count("gmm")
    assert "ragged-dot" in text and " scatter(" in text


@pytest.mark.parametrize("mixer", ["gdn", "kda"])
def test_full_remat_reruns_no_core_kernel_for_v5e(
        one_chip, compiled_not_interpreted, monkeypatch, mixer):
    """The mechanism of remat "full" itself: the gradient of one
    checkpointed delta-rule layer with a dense feed-forward (`layer_scan_body`,
    what the stack scans), 1 x 512 tokens, heads of 128, chunks of 128,
    bfloat16, holds two Mosaic calls, the core's forward and its backward:
    "full" keeps `kda.RESIDUAL_NAMES`, so the backward's recomputation of
    the layer body does not run the forward kernel again (a third call)."""
    from ray_tpu.models import configs, transformer as tfm

    rule = kda.use_kernels  # the platform here is cpu
    monkeypatch.setattr(kda, "use_kernels", lambda _, *a: rule("tpu", *a))
    kw = dict(n_layers=1, d_model=256, d_ff=512, max_seq_len=512,
              moe_num_experts=0, moe_held=None, moe_shared_experts=0,
              remat=True, remat_policy="full")
    if mixer == "gdn":
        cfg = configs.qwen3_next_tiny(gdn_head_dim=128, gdn_chunk=128,
                                      moe_shared_gate=False, **kw)
    else:
        cfg = configs.kimi_linear_tiny(kda_head_dim=128, kda_chunk=128, **kw)
    kind = cfg.layer_kinds()[0]
    assert kind == (mixer, "dense")
    B, S = 1, 512
    sd = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    layer = jax.tree.map(sd, jax.eval_shape(
        lambda key: tfm.layer_params(tfm.init_params(key, cfg), cfg, 0),
        jax.random.key(0)))
    x = sd(jax.ShapeDtypeStruct((B, S, cfg.d_model), cfg.dtype))

    def loss(x, layer):
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        body = tfm.layer_scan_body(cfg, kind, positions)
        return jnp.sum(body(x, layer)[0].astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        x, layer).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_the_looped_cells_step_fits_a_v5e(one_chip, compiled_not_interpreted,
                                          flash_kernels):
    """ouro_2_6b.train_loop4_8k's whole step as its files give it (published
    widths, 8 layers run 4 times, one sequence of 8,192, the remat policy
    and the head the traffic's sweep chose, AdamW's update inside, weights
    and moments donated): the v5e's compiler takes it, so 9.8 GB of state,
    the kept inputs and flash residuals of 32 layer applications and a
    pass's head fit the 15.75 GiB a program runs in; the passes are written
    out, each with its own scans: a forward and a backward flash call a
    pass."""
    import json
    import os

    import optax

    from ray_tpu.models import transformer as tfm

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench")
    with open(os.path.join(root, "configs", "ouro_2_6b.json")) as f:
        tc = dict(json.load(f)["transformer_config"])
    with open(os.path.join(root, "traffic", "train_loop4_8k.json")) as f:
        mix = json.load(f)
    tc.update(dtype=jnp.bfloat16, param_dtype=jnp.float32, remat=mix["remat"],
              remat_policy=mix["remat_policy"])
    cfg = tfm.TransformerConfig(**tc)
    assert (cfg.loop_steps, cfg.n_layers, mix["seq"]) == (4, 8, 8192)
    opt = optax.adamw(mix["lr"], weight_decay=0.0)
    sd = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.key(0))
    state = jax.tree.map(sd, jax.eval_shape(opt.init, params))
    params = jax.tree.map(sd, params)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (mix["batch"], mix["seq"] + 1), jnp.int32, sharding=one_chip)}

    def step(p, o, b):
        loss, g = jax.value_and_grad(lambda p: tfm.loss_fn(
            p, b, cfg, shift_inputs=True))(p)
        updates, o = opt.update(g, o, p)
        return optax.apply_updates(p, updates), o, loss

    exe = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, state, batch).compile()
    ma = exe.memory_analysis()
    # weights and both moments are donated: the new ones take their place
    assert ma.alias_size_in_bytes >= 12 * cfg.num_params()
    assert ma.argument_size_in_bytes - ma.alias_size_in_bytes < 1 << 16
    assert exe.as_text().count('custom_call_target="tpu_custom_call"') == (
        2 * cfg.loop_steps)
