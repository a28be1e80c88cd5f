"""An expert layer cut into the ranges its ranks hold (ops/moe.py
`moe_ffn_held`, the layer `models/transformer.py` runs for
`moe_router="sigmoid"` / `"softmax"`), on the CPU: the shares add up to the
uncut layer, a loop over all the experts, with no assignment dropped or
counted twice. First on a layer made here (windows, a skewed router, the
`dropped` counter), then for every family whose cell holds a range, with
each rank's weights made from the seed by the benchmark's maker and the
uncut layer the family's plain reference (chipbench/reference/)."""
import dataclasses
import functools
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, transformer as tfm
from ray_tpu.models.configs import kimi_linear_tiny
from ray_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

pytestmark = pytest.mark.usefixtures("exact_matmuls")


def _layer_case(seed=0, B=2, S=32, d=32, E=16, F=24, k=4):
    ks = jax.random.split(jax.random.key(seed), 8)
    return dict(
        x=jax.random.normal(ks[0], (B, S, d)),
        rw=jax.random.normal(ks[1], (d, E)) * 0.3,
        b=jax.random.normal(ks[2], (E,)) * 0.1,
        wgu=jax.random.normal(ks[3], (E, d, 2, F)) * 0.2,
        wd=jax.random.normal(ks[4], (E, F, d)) * 0.2,
        sgu=jax.random.normal(ks[5], (d, 2, F)) * 0.2,
        sd=jax.random.normal(ks[6], (F, d)) * 0.2, k=k, E=E)


def _shared_expert(c):
    """The always-on expert as the program computes it: the dense SwiGLU."""
    return tfm._mlp_block(
        kimi_linear_tiny(dtype=jnp.float32), "dense", c["x"],
        {"w_gate_up": c["sgu"], "w_down": c["sd"]})[0]


def _sigmoid(c, scale=2.446):
    """`moe_ffn_held`'s `route` for the case: sigmoid scores, its bias."""
    import functools

    return functools.partial(moe.sigmoid_route, bias=c["b"],
                             experts_per_token=c["k"], routed_scale=scale)


def _uncut_layer(c, scale=2.446):
    """The whole layer by the published equations, a loop over all experts."""
    x = c["x"].reshape(-1, c["x"].shape[-1])
    s = jax.nn.sigmoid(x @ c["rw"])
    _, idx = jax.lax.top_k(s + c["b"], c["k"])
    w = jnp.take_along_axis(s, idx, -1)
    w = w / w.sum(-1, keepdims=True) * scale
    y = (jax.nn.silu(x @ c["sgu"][:, 0]) * (x @ c["sgu"][:, 1])) @ c["sd"]
    for e in range(c["E"]):
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        h = jax.nn.silu(x @ c["wgu"][e, :, 0]) * (x @ c["wgu"][e, :, 1])
        y = y + we[:, None] * (h @ c["wd"][e])
    return y.reshape(c["x"].shape)


def _window_factor(monkeypatch, factor):
    """The first window as `factor` times the even share alone, without the
    module's row a token under it."""
    monkeypatch.setattr(moe, "HELD_WINDOW_FACTOR", factor)
    monkeypatch.setattr(moe, "HELD_WINDOW_MIN_TOKENS", 0.0)


@pytest.mark.parametrize("shares,factor", [(1, None), (4, None), (16, None),
                                           (4, 0.5), (4, 4.0)])
def test_expert_shares_add_up_to_the_uncut_layer(shares, factor, monkeypatch):
    """The parts all the shares give, the shared expert counted once, add
    up to the uncut layer's output; no assignment is dropped or counted
    twice (the shares' `assigned` add up to tokens x k). A share's window
    is its even load times the module's factor (None), 2.5, and a
    row a token at least (the sixteenth shares: 256 rows for an even load of
    64): what a share's routing puts past it takes further, smaller windows
    and is counted. At factor 0.5 the window is half the even load: two or three
    trips of the loop, with experts' runs that straddle the windows; at 4.0
    (the rule of PRs 27-33) a quarter share's window is every assignment."""
    if factor:
        _window_factor(monkeypatch, factor)
    c = _layer_case(S=128)
    per, every = c["E"] // shares, c["x"].shape[0] * c["x"].shape[1] * c["k"]
    rows = moe.held_window_rows(every // c["k"], c["k"], c["E"], per)
    assert rows == {(1, None): every, (4, None): 640, (16, None): 256,
                    (4, 0.5): 128, (4, 4.0): every}[shares, factor]
    # (one program for every share: `held_first` is an operand)
    share = jax.jit(lambda first, wgu, wd: moe.moe_ffn_held(
        c["x"], c["rw"], wgu, wd, route=_sigmoid(c), held_first=first,
        dtype=jnp.float32))
    total, assigned = 0.0, 0.0
    for r in range(shares):
        y, cnt = share(r * per, c["wgu"][r * per:(r + 1) * per],
                       c["wd"][r * per:(r + 1) * per])
        assert float(cnt["dropped"]) == 0.0
        held = float(cnt["assigned"])
        assert float(cnt["window_rows"]) == rows
        assert float(cnt["trips"]) == 1 + max(
            -(-(held - rows) // moe.further_window_rows(rows)), 0)
        assert float(cnt["past_buffer"]) == max(held - rows, 0)
        assert (factor != 0.5) or float(cnt["trips"]) > 1
        total, assigned = total + y, assigned + held
    shared = _shared_expert(c)
    np.testing.assert_allclose(total + shared, _uncut_layer(c), atol=2e-5)
    assert assigned == every


def test_no_assignment_dropped_under_a_skewed_router(monkeypatch):
    """A router that sends every token to the same four experts. With every
    expert held one window holds all tokens x k assignments: one trip. A
    share that holds those four works them in two trips at the module's
    windows (2.5 x its even quarter, 640 rows, then one of 384, half of it
    to a multiple of 128) and in eight at a window an eighth of their load:
    nothing is dropped, `past_buffer` counts what went beyond the first
    window, and output and gradients are those of a window that holds
    everything."""
    c = _layer_case(seed=1, S=128)
    c["b"] = c["b"].at[:4].add(10.0)  # experts 0-3 win every selection
    kw = dict(route=_sigmoid(c), dtype=jnp.float32)
    T = c["x"].shape[0] * c["x"].shape[1]
    shared = _shared_expert(c)
    y, cnt = moe.moe_ffn_held(c["x"], c["rw"], c["wgu"], c["wd"], **kw)
    assert float(cnt["dropped"]) == 0.0 == float(cnt["past_buffer"])
    assert float(cnt["trips"]) == 1.0 and float(cnt["window_rows"]) == T * 4
    assert float(cnt["load_max"]) == T and float(cnt["assigned"]) == T * 4
    np.testing.assert_allclose(y + shared, _uncut_layer(c), atol=2e-5)
    y, cnt = moe.moe_ffn_held(c["x"], c["rw"], c["wgu"][:4], c["wd"][:4],
                              **kw)
    assert moe.held_window_rows(T, 4, 16, 4) == 640 == float(
        cnt["window_rows"])
    assert moe.further_window_rows(640) == 384
    assert float(cnt["trips"]) == 2.0 and float(cnt["dropped"]) == 0.0
    assert float(cnt["past_buffer"]) == T * 4 - 640
    np.testing.assert_allclose(y + shared, _uncut_layer(c), atol=2e-5)

    def share(x, wgu, factor):
        _window_factor(monkeypatch, factor)
        return moe.moe_ffn_held(x, c["rw"], wgu, c["wd"][:4], **kw)

    _window_factor(monkeypatch, 0.5)
    rows = moe.held_window_rows(T, 4, 16, 4)
    assert rows == 128 and T * 4 == 1024  # eight trips
    y, cnt = share(c["x"], c["wgu"][:4], 0.5)
    assert float(cnt["assigned"]) == T * 4
    assert float(cnt["past_buffer"]) == T * 4 - rows
    assert float(cnt["dropped"]) == 0.0 and float(cnt["trips"]) == 8.0
    np.testing.assert_allclose(y + shared, _uncut_layer(c), atol=2e-5)
    loss = lambda f: lambda x, w: jnp.sum(jnp.sin(share(x, w, f)[0]))
    for got, want in zip(
            jax.grad(loss(0.5), (0, 1))(c["x"], c["wgu"][:4]),
            jax.grad(loss(8.0), (0, 1))(c["x"], c["wgu"][:4])):
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_dropped_counts_what_the_loop_did_not_work(monkeypatch):
    """`dropped` is read from the loop (each trip's own count of valid rows),
    not reckoned from the sizes: a loop that stops a trip short says so."""
    c = _layer_case(seed=1, S=128)
    c["b"] = c["b"].at[:4].add(10.0)
    _window_factor(monkeypatch, 0.5)
    monkeypatch.setattr(moe, "_trips", lambda held, rows, more, further: 7)
    _, cnt = moe.moe_ffn_held(c["x"], c["rw"], c["wgu"][:4], c["wd"][:4],
                              route=_sigmoid(c, 1.0), dtype=jnp.float32)
    assert float(cnt["assigned"]) == 1024 and float(cnt["dropped"]) == 128


# The families whose cell holds a range of the experts: the benchmark's
# weights module, its sizes, the family's reference, the kind of the expert
# layer made (None: the maker takes none) and which layer's key, the experts
# of the tiny preset, and the limit on the sum of the shares. Also read by
# tests/test_preset_programs.py.
FAMILIES = {
    "mellum2_tiny": dict(weights="weights_mellum2", sizes="MellumSizes",
                         reference="mellum2", kind=None, layer=0, experts=8,
                         atol=2e-5),
    "kanana2_tiny": dict(weights="weights_kanana2", sizes="KananaSizes",
                         reference="kanana2", kind=("mla", "moe"), layer=1,
                         experts=16, atol=3e-5),
    "qwen3_next_tiny": dict(weights="weights_qwen3_next",
                            sizes="QwenNextSizes", reference="qwen3_next",
                            kind=("gdn", "moe"), layer=1, experts=32,
                            atol=3e-5),
    "laguna_tiny": dict(weights="weights_laguna", sizes="LagunaSizes",
                        reference="laguna", kind=("swa", "moe"), layer=1,
                        experts=16, atol=3e-5),
    # eight shares of two: the eight ranks of the cell's layer
    "keye_vl2_tiny": dict(weights="weights_keye_vl2", sizes="KeyeSizes",
                          reference="keye_vl2", kind=None, layer=0,
                          experts=16, atol=3e-5),
    # four shares of two: the four ranks of the cell's layer (the reference
    # adds 1e-6 to the top-k's sum, the program does not: 5e-7 of a gate)
    "lfm2_moe_tiny": dict(weights="weights_lfm2_moe", sizes="Lfm2Sizes",
                          reference="lfm2_moe", kind=("shortconv", "moe"),
                          layer=2, experts=8, atol=3e-5),
}


# Families with no experts: no share to add up here, the same three pieces
# for tests/test_preset_programs.py.
DENSE_FAMILIES = {
    "ouro_tiny": dict(weights="weights_ouro", sizes="OuroSizes",
                      reference="ouro"),
}


def family(preset):
    """(weights module, reference module, cfg -> sizes) of a family."""
    f = {**DENSE_FAMILIES, **FAMILIES}[preset]
    W = importlib.import_module("chipbench." + f["weights"])
    ref = importlib.import_module("chipbench.reference." + f["reference"])

    def sizes(cfg, **changes):
        tc = {fl.name: getattr(cfg, fl.name) for fl in dataclasses.fields(cfg)}
        return getattr(W, f["sizes"])(dict(tc, **changes), cfg.norm_eps)

    return W, ref, sizes


@pytest.mark.parametrize("preset", sorted(FAMILIES))
def test_the_shares_add_up(preset):
    """One expert layer of the tiny preset: the routed parts the held ranges
    of two experts give (the program's `moe_ffn_held` under the family's
    routing, each rank's weights made from the seed by the benchmark's
    maker) plus the shared experts (kanana2's two; qwen3_next's one behind
    its sigmoid gate), counted once, sum to the uncut reference's layer, a
    loop over all the experts; a rank's part is the reference's for its
    range and what `_mlp_block` runs for it; no assignment is dropped or
    counted twice."""
    from chipbench.weights import layer_key

    f, (W, ref, sizes) = FAMILIES[preset], family(preset)
    cfg = getattr(configs, preset)(dtype=jnp.float32)
    kind = (f["kind"],) if f["kind"] else ()
    key = layer_key(jax.random.key(31), f["layer"])
    x = jax.random.normal(jax.random.key(32), (2, 40, cfg.d_model))
    whole = sizes(cfg, moe_held=None)
    w_all = W.layer(key, whole, *kind)
    want = ref._experts(x, w_all, whole, ref.mm_f32)
    shared = 0.0
    if "s_gate" in w_all:
        shared = ref._swiglu(x, w_all["s_gate"], w_all["s_up"],
                             w_all["s_down"], ref.mm_f32)
    if "shared_gate" in w_all:
        shared = jax.nn.sigmoid(x @ w_all["shared_gate"])[..., None] * shared
    if cfg.moe_router == "sigmoid":
        route = functools.partial(
            moe.sigmoid_route, experts_per_token=cfg.moe_experts_per_token,
            routed_scale=cfg.moe_routed_scale)
    else:
        route = functools.partial(
            moe.softmax_route, experts_per_token=cfg.moe_experts_per_token)
    # One program for every rank (`held_first` is an operand, the router's
    # bias one where the family has it): op by op each rank's scans and
    # conds compile again, ROADMAP D11.
    held = jax.jit(lambda first, router, wgu, wd, **bias: moe.moe_ffn_held(
        x, router, wgu, wd, route=functools.partial(route, **bias),
        held_first=first, dtype=jnp.float32))
    total, assigned = shared, 0.0
    for first in range(0, f["experts"], 2):
        sz = sizes(cfg, moe_held=(first, 2))
        made = W.layer(key, sz, *kind)
        w = W.to_program(made, sz, *kind)
        np.testing.assert_array_equal(  # a rank's experts are the model's
            w["moe_w_down"], w_all["e_down"][first:first + 2])
        bias = {"bias": w["router_bias"]} if "router_bias" in w else {}
        y, cnt = held(first, w["router"], w["moe_w_gate_up"],
                      w["moe_w_down"], **bias)
        assert float(cnt["dropped"]) == 0.0
        total, assigned = total + y, assigned + float(cnt["assigned"])
        part = ref._experts(x, made, sz, ref.mm_f32)
        np.testing.assert_allclose(y + shared, part, atol=2e-5)
        # the layer a rank runs: its routed part plus the shared experts whole
        rank_cfg = dataclasses.replace(cfg, moe_held=(first, 2))
        np.testing.assert_allclose(tfm._mlp_block(rank_cfg, "moe", x, w)[0],
                                   y + shared, atol=2e-5)
    np.testing.assert_allclose(total, want, atol=f["atol"])
    assert assigned == 2 * 40 * cfg.moe_experts_per_token
