"""What the benchmark runs inside the process that owns the chips: making
the weights, stamping the set-up phases, counting compiles, comparing the
program with the reference, tracing, and reading the device.

The serve replica is the class `build_streaming_llm_deployment` returns with
`Control` mixed in, so that these functions are reachable as actor calls
(`replica.handle_request.remote("bench", (op,), kw)`); the training loop
(drivers/train.py) calls them directly."""
from __future__ import annotations

import glob
import os
import shutil
import time
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import common

STAMPS: Dict[str, float] = {}
COUNTS: Dict[str, int] = {"cache_misses": 0, "cache_hits": 0, "compiles": 0}
_listening = False


FINE: List[Any] = []  # (name, wall time): finer marks inside a phase


def stamp(name: str) -> None:
    STAMPS[name] = time.time()


def mark(name: str) -> None:
    FINE.append((name, time.time()))


def listen() -> None:
    """Count persistent-cache hits and misses and backend compiles from
    jax.monitoring, from before the first program is built."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax.monitoring as mon

    def on_event(name, **_):
        if name.endswith("/cache_misses"):
            COUNTS["cache_misses"] += 1
        elif name.endswith("/cache_hits"):
            COUNTS["cache_hits"] += 1

    def on_duration(name, _secs, **_kw):
        if name.endswith("backend_compile_duration"):
            COUNTS["compiles"] += 1

    mon.register_event_listener(on_event)
    mon.register_event_duration_secs_listener(on_duration)


def cache_entries() -> int:
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    try:
        return sum(not f.endswith("-atime") for f in os.listdir(d))
    except OSError:
        return 0


def enter(rehearse: bool) -> Dict[str, Any]:
    """First thing in the chip-owning process: stamps, listeners, and the
    device, which must be a TPU in the peaks table (never the host)."""
    listen()
    STAMPS.setdefault("worker_proc", common.proc_start_wall())
    STAMPS.setdefault("cache_entries_start", cache_entries())
    mark("enter")
    import jax

    devs = jax.local_devices()
    mark("devices_listed")
    STAMPS.setdefault("backend", time.time())
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if not rehearse:
        if dev["platform"] != "tpu":
            raise RuntimeError(f"chipbench: worker is on {dev['platform']!r}, "
                               "not a TPU; refusing to measure the host")
        common.peaks_for(dev["kind"])
    return dev


def transformer_config(config: Dict[str, Any], rehearse: bool, **overrides):
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    tc = dict(config["transformer_config"])
    if rehearse:
        tc.update(config["rehearsal"]["transformer_config"])
    tc.update({k: v for k, v in overrides.items() if v is not None})
    tc["dtype"] = getattr(jnp, tc["dtype"])
    tc["param_dtype"] = getattr(jnp, tc["param_dtype"])
    return TransformerConfig(**tc)


def sizes(config: Dict[str, Any], rehearse: bool):
    from chipbench.weights import Sizes

    tc = dict(config["transformer_config"])
    if rehearse:
        tc.update(config["rehearsal"]["transformer_config"])
    return Sizes(tc, config["norm_eps"])


_make: Dict[Any, Any] = {}  # one jitted maker per (sizes, dtype)


def make_params(seed: int, config: Dict[str, Any], rehearse: bool,
                param_dtype: Optional[str] = None):
    """The model's weights on the device in the type they are held in (a
    mix's `param_dtype`, else the configuration's), one jitted call, the key
    an argument."""
    import jax
    import jax.numpy as jnp

    from chipbench import weights

    sz = sizes(config, rehearse)
    dt = param_dtype or config["transformer_config"]["param_dtype"]
    which = (config["name"], rehearse, dt)
    if which not in _make:
        _make[which] = jax.jit(lambda key: weights.program_params(
            key, sz, getattr(jnp, dt)))
    return jax.block_until_ready(_make[which](jax.random.key(seed)))


def serve_params(config: Dict[str, Any], seed: int, rehearse: bool,
                 param_dtype: Optional[str] = None):
    """`params_factory` of the streaming deployment."""
    enter(rehearse)
    params = make_params(seed, config, rehearse, param_dtype)
    stamp("weights")
    return params


# ------------------------------------------------------------------ device


def device_info(program_bytes: int = 0) -> Dict[str, Any]:
    """The device as JAX reports it, and the peak on the fullest chip.
    `peak_bytes_in_use` leaves out a running program's temporaries on this
    runtime (a train step read 1.56 GB with 3.3 GB of logits alive), so a
    caller passes what its largest program holds while it runs (arguments,
    temporaries, outputs; compiled.memory_analysis()) and the larger counts."""
    import jax

    devs = jax.local_devices()
    peak = program_bytes
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def setup_report() -> Dict[str, Any]:
    """Stamps and counters of the set-up, asked once it is over."""
    return {"stamps": dict(STAMPS), "counts": dict(COUNTS), "fine": list(FINE),
            "cache_entries_added":
                cache_entries() - int(STAMPS["cache_entries_start"])}


# ------------------------------------------------------------------- trace

TRACE_DIR = os.path.join(common.RUN_DIR, "trace")


def trace_start() -> float:
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    return time.time()


def trace_stop() -> Dict[str, Any]:
    import jax

    t = time.time()
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    return {"stopped": t, "xplane": files[0] if files else None}


# ------------------------------------------------------------------- check


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def weights_rel_err(params, sz, seed: int, layers=None) -> float:
    """The served weights against the seed's, on a sample of layers, read
    through the program's own access path (models/quantize.py maybe_dequant,
    which every layer helper uses): 0 for float32 storage, about 0.001 for
    bfloat16 storage (the same outputs), about 0.01 for int8 storage."""
    import jax
    import jax.numpy as jnp

    from chipbench import weights
    from ray_tpu.models.quantize import maybe_dequant

    layers = layers if layers is not None else sorted({0, sz.L // 2, sz.L - 1})
    plain = {"wo": lambda w: w["wo"], "w_down": lambda w: w["w_down"]}
    if sz.KVH == sz.H:
        plain["wqkv"] = lambda w: jnp.stack(
            [w[n].reshape(sz.d, -1, sz.hd) for n in ("wq", "wk", "wv")], 1)
    else:
        plain["wq"] = lambda w: w["wq"].reshape(sz.d, sz.H, sz.hd)
        plain["wkv"] = lambda w: jnp.stack(
            [w[n].reshape(sz.d, sz.KVH, sz.hd) for n in ("wk", "wv")], 1)
    if sz.activation == "swiglu":
        plain["w_gate_up"] = lambda w: jnp.stack([w["w_gate"], w["w_up"]], 1)
    else:
        plain["w_up"] = lambda w: w["w_up"]

    def sums(stack, key, idx):
        def one(carry, l):
            layer = jax.tree.map(lambda a: a[l], stack)
            want = weights.layer(weights.layer_key(key, l), sz)
            num, den = carry
            for name, fn in plain.items():
                w = fn(want)
                got = maybe_dequant(layer, name, jnp.float32)
                num += jnp.sum((got - w) ** 2)
                den += jnp.sum(w ** 2)
            return (num, den), None

        return jax.lax.scan(one, (0.0, 0.0), idx)[0]

    num, den = jax.jit(sums)(params["layers"], jax.random.key(seed),
                             jnp.asarray(layers, jnp.int32))
    return float(jnp.sqrt(num / den))


def serve_check(engine, sz, seed: int, spec: Dict[str, Any]) -> Dict[str, Any]:
    """Prefill then decode through the engine's own compiled programs and
    its slot cache, against the reference's full forward pass: logits at
    the prompt's last position and at each decoded position.

    Runs before any traffic, as the engine itself would (slot 0, under the
    engine lock), so it costs no memory the engine does not use."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import dense_decoder as ref
    from ray_tpu.models.generate import KVCache
    from ray_tpu.serve.llm_engine import bucket_len

    steps = spec["decode_steps"]
    lens = spec["prompt_lens"]
    s_ref = -(-(max(lens) + steps) // 8) * 8
    key = jax.random.key(seed)
    forward = jax.jit(lambda k, t, at: ref.forward(k, t, sz, at=at))
    got, want = [], []
    rng = np.random.default_rng(seed)
    temps = jnp.zeros((engine.B,), jnp.float32)
    tick_key = jax.random.fold_in(engine._rng, 0)
    for n in lens:
        ids = rng.integers(0, sz.V, n).astype(np.int32)
        with engine.lock:
            logits1, k1, v1 = engine._prefill_padded(
                ids, bucket_len(n, engine.max_prompt_len))
            rows = [np.asarray(logits1)]
            toks = [int(np.argmax(rows[0]))]
            ck, cv, pos, cur = engine._splice(
                engine.cache.k, engine.cache.v, engine.cache.pos,
                engine.cur_tok, k1, v1, jnp.asarray(n, jnp.int32),
                jnp.asarray(toks[0], jnp.int32), 0)
            engine.cache = KVCache(k=ck, v=cv, pos=pos)
            del ck, cv, k1, v1
            for _ in range(steps):
                cur, logits, engine.cache = engine._tick(
                    engine.params, engine.cache, cur, tick_key, temps)
                rows.append(np.asarray(logits[0]))
                toks.append(int(cur[0]))
            engine.cur_tok = cur
        full = np.zeros((1, s_ref), np.int32)
        full[0, :n] = ids
        full[0, n:n + steps] = toks[:steps]
        at = jnp.arange(n - 1, n + steps)
        want.append(np.asarray(forward(key, jnp.asarray(full), at))[0])
        got.append(np.stack(rows))
    per_row = [rel_err(g, w) for G, Wt in zip(got, want)
               for g, w in zip(G, Wt)]
    return {"serve_weights_rel_err": weights_rel_err(engine.params, sz, seed),
            "serve_logits_rel_err": rel_err(np.concatenate(got),
                                            np.concatenate(want)),
            "serve_logits_rel_err_max_row": max(per_row),
            "rows": len(per_row)}


def _sample(sz, seed: int, batch: int, seq: int):
    """The key the weights were made from, and a seeded sample of sequences
    (host-made: a device program for 2 k integers costs a second of set-up)."""
    import jax

    toks = np.random.default_rng(seed).integers(
        0, sz.V, (batch, seq + 1), dtype=np.int32)
    return jax.random.key(seed), toks


def _reference_grads(sz, key, toks, mm=None):
    import jax
    import jax.numpy as jnp

    from chipbench.reference import dense_decoder as ref

    dev0 = jax.local_devices()[0]
    args = (jax.device_put(key, dev0), jax.device_put(jnp.asarray(toks), dev0))
    lowered = jax.jit(lambda k, t: ref.loss_and_grads(
        k, t, sz, mm or ref.mm_f32)).lower(*args)
    mark("ref_lowered")
    compiled = lowered.compile()
    mark("ref_loaded")
    loss, g = compiled(*args)
    loss = float(loss)
    mark("ref_ran")
    return loss, g


def _train_numbers(loss_p, g_p, loss_r, g_r) -> Dict[str, Any]:
    grads = {n: rel_err(g_p[n], g_r[n]) for n in g_r}
    return {"train_loss_rel_err": abs(loss_p - loss_r) / abs(loss_r),
            "train_grad_rel_err": max(grads.values()),
            "loss_program": loss_p, "loss_reference": loss_r,
            "grad_rel_err_by_leaf": grads}


def train_control(sz, seed: int, batch: int, seq: int, mm) -> Dict[str, Any]:
    """The control of a train cell: the reference in the program's place,
    its matmuls in `mm` (a lower precision), against the reference."""
    key, toks = _sample(sz, seed, batch, seq)
    return _train_numbers(*_reference_grads(sz, key, toks, mm),
                          *_reference_grads(sz, key, toks))


def train_check(loss_fn, params, mesh, sz, seed: int, batch: int,
                seq: int) -> Dict[str, Any]:
    """The program's loss and three gradient leaves (final norm, the last
    layer's output projection, the first layer's attention norm) on a seeded
    sample of sequences, against the reference's."""
    import jax

    from ray_tpu.parallel import sharding as shd

    key, toks = _sample(sz, seed, batch, seq)
    mark("check_sample")

    def pick(p, b):
        with shd.sharding_ctx(mesh, shd.DEFAULT_RULES):
            loss, g = jax.value_and_grad(loss_fn)(p, b)
        return loss, {"final_norm": g["final_norm"],
                      "wo_last": g["layers"]["wo"][sz.L - 1],
                      "attn_norm_first": g["layers"]["attn_norm"][0]}

    loss_p, g_p = jax.jit(pick)(params, shd.shard_batch(mesh, {"tokens": toks}))
    loss_p = float(loss_p)
    mark("check_program")
    ref = _reference_grads(sz, key, toks)
    mark("check_reference")
    return _train_numbers(loss_p, g_p, *ref)


# ------------------------------------------------------- the serve replica


class Control:
    """Mixed into the streaming deployment's class: stamps its constructor
    (weights, engine, warm-up) and answers the runner's control calls."""

    def __init__(self):
        enter(bool(int(os.environ.get("CHIPBENCH_REHEARSE", "0"))))
        stamp("ctor_start")
        super().__init__()
        stamp("warm")

    def bench(self, op: str, **kw):
        return getattr(self, "_op_" + op)(**kw)

    def _op_setup_report(self):
        import gc

        gc.collect()  # as in the runner: not inside the window
        return setup_report()

    def _op_check(self, config, seed, spec, rehearse):
        t = time.time()
        out = serve_check(self._engine, sizes(config, rehearse), seed, spec)
        stamp("check")
        out["seconds"] = time.time() - t
        return out

    def _op_window_start(self):
        self._win = {"compiles": COUNTS["compiles"],
                     "req_seq": self._engine._req_seq}
        return time.time()

    def _op_window_end(self):
        eng = self._engine
        ttft, itl = [], []
        for r in range(self._win["req_seq"] + 1, eng._req_seq + 1):
            st = eng.token_stats(r) or {}
            if st.get("ttft_s") is not None:
                ttft.append(st["ttft_s"])
            if st.get("itl_mean_s") is not None:
                itl.append(st["itl_mean_s"])
        return {"compiles_in_window":
                    COUNTS["compiles"] - self._win["compiles"],
                "engine_ttft_s": ttft, "engine_itl_mean_s": itl,
                "device": device_info()}

    def _op_trace_start(self):
        return trace_start()

    def _op_trace_stop(self):
        return trace_stop()


def replica_class(base: type) -> type:
    return type("Bench" + base.__name__, (Control, base), {})
