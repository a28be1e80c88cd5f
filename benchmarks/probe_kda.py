"""Delta-rule core probe on the chip, at the shape of
`qwen3_next_80b_a3b.train_rank16_16k` (q, k [1,16384,16,128], v
[1,16384,32,128], one decay a value head, chunks of 128, bfloat16): do the
scalar-decay kernels of ops/kda.py agree with the rule, and how long does each
body take.

1. `check`: every gradient (q, k, v, g, beta) of the scalar body
   (`kda_chunked_pallas` on g [B,S,H]) and of the broadcast into the
   per-channel body (q, k repeated over their value heads, g over the
   channels, as every rank-3 call ran before PR 42), on bfloat16 operands,
   each against `kda_chunked_xla` on float32 operands at full matmul
   precision; `dg_heads` is dg summed over the tokens of a head (what
   reaches `dt_bias`), where the two bodies differ most.
2. `time`: the forward and the backward `pallas_call` alone, per-channel
   (32 heads, g [1,16384,32*128]) and scalar (16 key heads, g [1,16384,32]),
   each inside a `fori_loop` of 20 on the device (a host loop over calls
   this short measures the host), and the whole `value_and_grad` of both
   ways from the host: the difference is the traffic round the kernels.

3. `conv` (PR 52): the way from a projection to the core,
   [l2norm](SiLU(short_conv(x, w) [+ b])), alone: XLA's formulation
   (`kda.mixer_conv_xla`, what the mixers wrote out) and the Pallas pair
   (`kda.mixer_conv_pallas`), the forward and the forward + backward, eight
   independent calls in one program, at [1,16384,32,128] K 4 with
   and without the norm, at [1,8192,32,128], and at `_mamba_mixer`'s two
   ([1,4096,4096] and [1,4096,256] with a bias); `rel_*` is each result
   against the XLA body on float32 operands. `conv sweep` times the pair at
   other blocks (rows, lanes, chunk, lanes worked, chunks a loop turn) as
   well.

4. `norm` (PR 59): the way from the core to the output projection, an
   RMSNorm over a channel group times a gate, alone: XLA's formulation
   (`kda.gated_norm_xla`, the three lines each mixer wrote out) and the
   Pallas pair (`kda.gated_norm_pallas`), the forward and the forward +
   backward, eight independent calls in one program, at `_gdn_mixer`'s call
   ([1,16384,32,128], SiLU(z) after the norm), `_kda_mixer`'s ([1,8192,32,128],
   a sigmoid after) and `_mamba_mixer`'s ([1,4096,4096] as one group, SiLU(z)
   before); `max_*` is each result's largest error over the reference's
   largest value, against the XLA body on float32 operands.

One JSON line a case. Off the chip the script fails at once.

    python3 benchmarks/probe_kda.py          # check + time
    python3 benchmarks/probe_kda.py time     # no check
    python3 benchmarks/probe_kda.py conv [sweep]
    python3 benchmarks/probe_kda.py norm
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import functools
import json
import math
import time

import jax
import jax.numpy as jnp

from ray_tpu.ops import kda
from ray_tpu.util.jaxenv import enable_compile_cache, require_tpu

B, S, HK, HV, D, CHUNK, SUB = 1, 16384, 16, 32, 128, 128, 32
NAMES = ("q", "k", "v", "g", "beta")
REPEAT = 20


def _inputs(seed=0):
    """Operands as the `gdn` mixer hands them over: unit q and k, a decay
    -A softplus(a + dt_bias) with A 1-16 by head, beta a sigmoid."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q = kda.l2_normalize(jax.random.normal(ks[0], (B, S, HK, D)))
    k = kda.l2_normalize(jax.random.normal(ks[1], (B, S, HK, D)))
    v = jax.random.normal(ks[2], (B, S, HV, D))
    A = jnp.exp(jnp.linspace(0.0, 2.77, HV))
    g = -A * jax.nn.softplus(jax.random.normal(ks[3], (B, S, HV)) * 0.25 - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, HV)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, S, HV, D))


def _broadcast(body):
    """`body` on the per-channel rule's operands, made outside it."""
    def fn(q, k, v, g, beta, **kw):
        q, k, g = kda._per_channel(q, k, v, g)
        return body(q, k, v, g, beta, **kw)
    return fn


def _grad_fn(body, wo):
    def loss(*a):
        return jnp.sum(body(*a, chunk=CHUNK)[0].astype(jnp.float32) * wo)
    return jax.jit(jax.value_and_grad(loss, argnums=range(5)))


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _ms(fn, args, steps=1, repeats=3):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / steps * 1e3)
    return best


def _half(args):
    return tuple(a.astype(jnp.bfloat16) if n in "qkv" else a
                 for n, a in zip(NAMES, args))


def check(args, wo):
    with jax.default_matmul_precision("highest"):
        ref = _grad_fn(kda.kda_chunked_xla, wo)(*args)
    out = {"case": "check"}
    for name, body in (("scalar_bf16", kda.kda_chunked_pallas),
                       ("per_channel_bf16",
                        _broadcast(kda.kda_chunked_pallas))):
        fn = _grad_fn(body, wo)
        got = fn(*_half(args))
        out[name] = {n: _rel(a, b) for n, a, b in zip(NAMES, got[1], ref[1])}
        out[name]["dg_heads"] = _rel(got[1][3].sum(1), ref[1][3].sum(1))
        out[name]["fwd_bwd_ms_from_host"] = _ms(fn, _half(args), steps=10)
    print(json.dumps(out), flush=True)
    return all(out["scalar_bf16"][n] < 2.0 * out["per_channel_bf16"][n]
               for n in NAMES)


def kernels_ms(args, wo):
    q, k, v, g, beta = _half(args)
    flat = lambda a: a.reshape(B, S, -1)
    s0, scale = jnp.zeros((B, HV, D, D)), D ** -0.5
    do = flat(wo.astype(jnp.bfloat16))
    per = _broadcast(lambda q, k, v, g, beta: (q, k, v, g, beta))
    for name, ops, fwd_call, bwd_call in (
            ("per_channel", tuple(map(flat, per(q, k, v, g, beta)[:4]))
             + (beta,), kda._kda_fwd_call, kda._kda_bwd_call),
            ("scalar", (flat(q), flat(k), flat(v), g, beta),
             kda._gdn_fwd_call, kda._gdn_bwd_call)):
        tail = (scale, CHUNK, SUB, "probe")
        states, tinv = fwd_call(*ops, s0, *tail)[2:]

        @jax.jit  # every operand an argument: a closed-over array is a constant
        def fwd(q, k, v, g, beta, s0):
            return jax.lax.fori_loop(0, REPEAT, lambda i, v: fwd_call(
                q, k, v, g, beta, s0, *tail)[0], v)

        @jax.jit
        def bwd(do, states, tinv, s0, *ops):
            return jax.lax.fori_loop(0, REPEAT, lambda i, do: bwd_call(
                *ops, states, tinv, do, s0, *tail)[2], do)

        print(json.dumps({"case": "time", "body": name,
                          "fwd_ms": _ms(fwd, ops + (s0,)) / REPEAT,
                          "bwd_ms": _ms(bwd, (do, states, tinv, s0) + ops)
                          / REPEAT}), flush=True)


CONV_CASES = (  # rows, channel shape, taps, norm, bias
    (16384, (32, 128), 4, True, False), (16384, (32, 128), 4, False, False),
    (8192, (32, 128), 4, True, False), (4096, (4096,), 4, False, True),
    (4096, (256,), 4, False, True))
# (forward rows, backward rows), lanes, chunk rows, lanes worked at a time,
# chunks a turn of the row loop
CONV_BLOCKS = (((1024, 512), 512, 16, 512, 1), ((1024, 512), 512, 16, 512, 2),
               ((1024, 512), 512, 16, 512, 8), ((512, 512), 512, 16, 512, 4),
               ((1024, 1024), 512, 16, 512, 4), ((1024, 512), 1024, 16, 512, 4),
               ((1024, 512), 512, 32, 256, 4), ((1024, 256), 512, 16, 512, 4))
CALLS = 8


def _conv_case(S, ch, K, l2, bias, check=True):
    """One JSON line: both bodies' milliseconds a call (`CALLS` independent
    calls in one program, each on its own operands: a loop that carries x
    pays a copy of it a turn), and the kernels' results against the XLA body
    on float32 operands."""
    ks = jax.random.split(jax.random.key(S + K), 4)
    flat = (1, S, math.prod(ch))  # as a projection's product lies: a program
    xs = [jax.random.normal(k, flat, jnp.bfloat16)  # whose ARGUMENT is
          for k in jax.random.split(ks[0], CALLS)]  # [1,S,32,128] tiles the
    dys = [jax.random.normal(k, flat, jnp.bfloat16)  # heads and pays a copy
           for k in jax.random.split(ks[3], CALLS)]
    w = jax.random.normal(ks[1], (K,) + ch) * 0.5
    b = jax.random.normal(ks[2], ch) * 0.1 if bias else None
    out = {"case": "conv", "rows": S, "channels": list(ch), "taps": K,
           "l2": l2, "bias": bias,
           "block": [kda._CONV_ROWS, kda._CONV_COLS[0], kda._CONV_CHUNK,
                     kda._CONV_WORK, kda._CONV_UNROLL]}

    def pair(body, x, w, b, dy):
        y, vjp = jax.vjp(lambda x, w, b: body(
            x.reshape((1, S) + ch), w, b, l2=l2).reshape(flat), x, w, b)
        return (y,) + vjp(dy)

    if check:  # operands as the mixers hold them, [1,S,...ch] (this XLA's
        # body on float32 [1,S,C] reshaped INSIDE the program read 0.21 off
        # both kernels and itself on [1,S,32,128]: chiprun_out/p52g)
        f32 = lambda a: a.astype(jnp.float32).reshape((1, S) + ch)
        as_is = lambda a: a.reshape((1, S) + ch)

        def pair4(body, x, w, b, dy):
            y, vjp = jax.vjp(lambda x, w, b: body(x, w, b, l2=l2), x, w, b)
            return (y,) + vjp(dy)

        ref, got, was = (
            jax.jit(functools.partial(pair4, body))(cast(xs[0]), w, b,
                                                    cast(dys[0]))
            for body, cast in ((kda.mixer_conv_xla, f32),
                               (kda.mixer_conv_pallas, as_is),
                               (kda.mixer_conv_xla, as_is)))
        for n, r, g, o in zip(("y", "dx", "dw", "db"), ref, got, was):
            if r is not None:
                out["rel_" + n] = _rel(g, r)
                out["rel_" + n + "_xla"] = _rel(o, r)
    for name, body in (("xla", kda.mixer_conv_xla),
                       ("pallas", kda.mixer_conv_pallas)):
        fwd = jax.jit(lambda xs, w, b: [body(
            x.reshape((1, S) + ch), w, b, l2=l2).reshape(flat) for x in xs])
        both = jax.jit(lambda xs, w, b, dys: [
            pair(body, x, w, b, dy)[1:] for x, dy in zip(xs, dys)])
        out[name + "_fwd_ms"] = _ms(fwd, (xs, w, b)) / CALLS
        out[name + "_fwd_bwd_ms"] = _ms(both, (xs, w, b, dys)) / CALLS
    print(json.dumps(out), flush=True)
    return out


def conv(sweep):
    """The stop rule of PR 52: the kernels' forward + backward under half of
    XLA's at 16,384 rows."""
    first = [_conv_case(*c) for c in CONV_CASES][0]
    if sweep:
        for rows, cols, chunk, work, unroll in CONV_BLOCKS:
            kda._CONV_ROWS, kda._CONV_CHUNK, kda._CONV_WORK = rows, chunk, work
            kda._CONV_UNROLL = unroll
            kda._CONV_COLS = (cols, 256, 128)
            kda._conv_fwd_call.clear_cache()
            kda._conv_bwd_call.clear_cache()
            try:
                _conv_case(*CONV_CASES[0], check=False)
            except Exception as e:  # a block the compiler refuses
                print(json.dumps({"case": "conv", "error": repr(e)[:300],
                                  "block": [rows, cols, chunk, work,
                                            unroll]}),
                      flush=True)
    return first["pallas_fwd_bwd_ms"] < 0.5 * first["xla_fwd_bwd_ms"]


NORM_CASES = (  # rows, channel shape, group, the gate, gate first
    (16384, (32, 128), 128, "silu", False),
    (8192, (32, 128), 128, "sigmoid", False),
    (4096, (64, 64), 4096, "silu", True))


def _norm_case(S, ch, group, act, first):
    """One JSON line, as `_conv_case`'s: both bodies' milliseconds a call
    and each body's results on bfloat16 operands against the XLA body on
    float32 ones."""
    C = math.prod(ch)
    flat, full = (1, S, C), (1, S) + ch
    ks = jax.random.split(jax.random.key(S + group), 4)
    many = lambda k, scale=1.0: [
        (jax.random.normal(k, flat) * scale).astype(jnp.bfloat16)
        for k in jax.random.split(k, CALLS)]
    ys, gs, dos = many(ks[0]), many(ks[1], 2.0), many(ks[2])
    w = 1.0 + 0.1 * jax.random.normal(ks[3], ch[-1:] if ch[-1] == group
                                      else ch)
    kw = dict(group=group, gate_act=act, gate_first=first, eps=1e-6)
    out = {"case": "norm", "rows": S, "channels": list(ch), **kw,
           "block": [kda._CONV_ROWS, kda._norm_blocks(
               jax.ShapeDtypeStruct(flat, jnp.bfloat16), group,
               kda._CONV_ROWS[0])[:2]]}

    def pair(body, y, g, w, do):  # operands as the core and a product
        o, vjp = jax.vjp(lambda y, g, w: body(  # write them: [1, S, C]
            y.reshape(full), g.reshape(full), w, **kw).reshape(flat), y, g, w)
        return (o,) + vjp(do)

    f32 = lambda a: a.astype(jnp.float32)
    ref, got, was = (
        jax.jit(functools.partial(pair, body))(cast(ys[0]), cast(gs[0]), w,
                                               cast(dos[0]))
        for body, cast in ((kda.gated_norm_xla, f32),
                           (kda.gated_norm_pallas, lambda a: a),
                           (kda.gated_norm_xla, lambda a: a)))
    worst = lambda a, r: float(jnp.abs(f32(a) - r).max() / jnp.abs(r).max())
    for n, r, g, o in zip(("out", "dy", "dgate", "dw"), ref, got, was):
        out["max_" + n], out["max_" + n + "_xla"] = worst(g, r), worst(o, r)
    for name, body in (("xla", kda.gated_norm_xla),
                       ("pallas", kda.gated_norm_pallas)):
        fwd = jax.jit(lambda ys, gs, w: [
            body(y.reshape(full), g.reshape(full), w, **kw).reshape(flat)
            for y, g in zip(ys, gs)])
        both = jax.jit(lambda ys, gs, w, dos: [  # the result too: a forward
            pair(body, y, g, w, do)              # nobody reads is not run
            for y, g, do in zip(ys, gs, dos)])
        out[name + "_fwd_ms"] = _ms(fwd, (ys, gs, w)) / CALLS
        out[name + "_fwd_bwd_ms"] = _ms(both, (ys, gs, w, dos)) / CALLS
    print(json.dumps(out), flush=True)
    return out


def norm():
    """The stop rule of PR 59: the pair's forward + backward under half of
    XLA's at `_gdn_mixer`'s call."""
    first = [_norm_case(*c) for c in NORM_CASES][0]
    return first["pallas_fwd_bwd_ms"] < 0.5 * first["xla_fwd_bwd_ms"]


def main(argv):
    require_tpu()
    enable_compile_cache()
    if argv and argv[0] == "conv":
        return 0 if conv(argv[1:] == ["sweep"]) else 1
    if argv and argv[0] == "norm":
        return 0 if norm() else 1
    args, wo = _inputs()
    ok = True
    if not argv or argv[0] == "check":
        ok = check(args, wo)
    if not argv or argv[0] == "time":
        kernels_ms(args, wo)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
