"""The residual cut along the sequence over `tensor` and the projections'
collectives run in steps behind their matmuls (parallel/tensor_overlap.py):
the sharded step gives one device's loss and gradients, its compiled text
holds the ring's transfers and no all-reduce of an activation or collective
of a weight that the step without the mechanism lacks, and where nothing
engages the program is the one it was. The sharded step's numbers, mesh by
mesh, are tests/test_tensor_overlap_rows.py."""
import collections
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import gpt2_tiny, llama_tiny
from ray_tpu.models.generate import decode_step, prefill
from ray_tpu.parallel import tensor_overlap as tp
from ray_tpu.parallel.mesh import MeshSpec, make_mesh
from ray_tpu.parallel.sharding import RULES_TP, sharding_ctx
from ray_tpu.train.step import transformer_train_step
from ray_tpu.util import tracing

# Today's layout under the same mesh: no rule for the residual's rows.
RULES_WHOLE = {k: v for k, v in RULES_TP.items() if k != "seq_res"}
ONE = MeshSpec()
MESHES = {"fsdp2xtp2": MeshSpec(fsdp=2, tensor=2),
          "fsdp4xtp2": MeshSpec(fsdp=4, tensor=2),
          "dp2xfsdp2xtp2": MeshSpec(data=2, fsdp=2, tensor=2)}
MODELS = {"llama": llama_tiny, "gpt2": gpt2_tiny}


def _mesh(spec):
    return make_mesh(spec, devices=jax.devices()[:spec.num_devices])


def _tokens(cfg, shape=(8, 33)):
    return np.random.RandomState(3).randint(
        0, cfg.vocab_size, shape).astype(np.int32)


def _count():
    return tracing.phase_table().get("train.tp_overlap", {}).get("count", 0)


def _loss_and_grads(cfg, spec, rules, tokens):
    """One device's initial weights placed on the mesh, so that every mesh
    differentiates the same model."""
    ts = transformer_train_step(cfg, _mesh(spec), rules=rules,
                                shift_inputs=True)
    params = jax.device_put(tfm.init_params(jax.random.key(0), cfg),
                            ts.param_shardings)

    def f(p, b):
        with sharding_ctx(ts.mesh, ts.rules):
            return jax.value_and_grad(
                lambda p: tfm.loss_fn(p, b, cfg, shift_inputs=True))(p)

    loss, grads = jax.jit(f)(params, ts.shard_batch({"tokens": tokens}))
    return float(loss), jax.tree.map(np.asarray, grads)


# ------------------------------------------------- the compiled step's text


_COLLECTIVE = re.compile(
    r"= (\(?[^=]*?\)?) (all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")


def _groups(line):
    """The device groups of a collective's line, a frozenset of frozensets
    (a permute's pairs count as groups of two)."""
    m = re.search(r"source_target_pairs=\{(.*?)\}\}", line)
    if m:
        pairs = re.findall(r"\{(\d+),(\d+)", m.group(1) + "}")
        return frozenset(frozenset(map(int, p)) for p in pairs)
    m = re.search(r"replica_groups=\{(\{.*?\})\}", line)
    if m:
        return frozenset(frozenset(map(int, g.split(",")))
                         for g in re.findall(r"\{([\d,]+)\}", m.group(1)))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]"
                  r"(?:T\(([\d,]+)\))?", line)
    n, size, dims, perm = m.groups()
    ids = np.arange(np.prod(list(map(int, dims.split(","))))).reshape(
        list(map(int, dims.split(","))))
    if perm:
        ids = ids.transpose(list(map(int, perm.split(","))))
    return frozenset(frozenset(map(int, g))
                     for g in ids.reshape(int(n), int(size)))


def _tensor_collectives(text, mesh):
    """Counter of (op, operand dims) over the collectives of a compiled
    step whose groups join devices that differ along `tensor`."""
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    axis = mesh.axis_names.index("tensor")
    rest = np.moveaxis(ids, axis, -1).reshape(-1, ids.shape[axis])
    along = {int(i): r for r, row in enumerate(rest) for i in row}
    text = re.sub(r"/\*.*?\*/", "", text)
    out = collections.Counter()
    for line in text.splitlines():
        m = _COLLECTIVE.search(line)
        if not m:
            continue
        # Over `tensor`: some group holds two devices of one tensor row.
        if not any(len(g) > len({along[i] for i in g}) for g in _groups(line)):
            continue
        for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1)):
            out[(m.group(2), tuple(map(int, dims.split(","))) if dims
                 else ())] += 1
    return out


def _cuts(shape, sizes=(1, 2, 4)):
    """A weight's shape and every shape a mesh may cut it to."""
    return {tuple(n // s for n, s in zip(shape, by))
            for by in itertools.product(sizes, repeat=len(shape))
            if all(n % s == 0 for n, s in zip(shape, by))}


def _compiled_step(cfg, spec, rules, tokens):
    ts = transformer_train_step(cfg, _mesh(spec), rules=rules,
                                shift_inputs=True)
    params, opt = ts.init(jax.random.key(0))
    return ts, ts.lower_step(
        params, opt, ts.shard_batch({"tokens": tokens})).compile().as_text()


@pytest.mark.parametrize("model", ["llama", "gpt2"])
def test_compiled_step_moves_rows_and_no_weight_over_tensor(model):
    cfg = MODELS[model](remat=True, remat_policy="dots")
    tokens = _tokens(cfg)
    B, S, d = tokens.shape[0] // 2, tokens.shape[1] - 1, cfg.d_model
    ts, text = _compiled_step(cfg, MESHES["fsdp2xtp2"], RULES_TP, tokens)
    _, whole = _compiled_step(cfg, MESHES["fsdp2xtp2"], RULES_WHOLE, tokens)
    got, was = (_tensor_collectives(t, ts.mesh) for t in (text, whole))
    # Today's program closes four products a layer with an all-reduce of the
    # residual; this one sends a rank's rows round the ring instead.
    assert was[("all-reduce", (B, S, d))] >= 4
    assert got[("collective-permute", (B, S // 2, d))] >= 10
    # What is left of them is the head's: its input gathered, dx summed and
    # cut (an all-reduce and a slice here: the CPU forms no reduce-scatter),
    # as the embedding's dx is gathered. No layer's.
    assert got[("all-reduce", (B, S, d))] == 1, got
    assert got[("all-gather", (B, S, d))] == 2, got
    assert not got[("all-reduce", (B, S // 2, d))], got
    # No collective over `tensor` of a weight's shape (whole or cut) that
    # the step with the rows whole lacks: a table's gradient summed over
    # `tensor` is what a lookup of a rank's rows alone would bring. The
    # norms' scales (one dim) are summed over the rows' ranks by design.
    weights = set()
    for leaf in jax.tree.leaves(tfm.init_params(jax.random.key(0), cfg)):
        weights |= _cuts(leaf.shape) | _cuts(leaf.shape[1:])
    more = {k: n - was[k] for k, n in got.items()
            if len(k[1]) >= 2 and k[1] in weights and n > was[k]}
    assert not more, more


# ------------------------------------------------- where nothing engages


def test_one_device_has_no_collective_and_no_count():
    cfg = llama_tiny(remat=True)
    before = _count()
    _, text = _compiled_step(cfg, ONE, RULES_TP, _tokens(cfg))
    assert not _COLLECTIVE.search(text)
    assert _count() == before


@pytest.mark.parametrize("rules,batch,seq,engaged", [
    (RULES_TP, 4, 32, True), (RULES_TP, 4, 31, False), (RULES_TP, 4, 1, False),
    (RULES_TP, 3, 32, False), (RULES_WHOLE, 4, 32, False)],
    ids=["even", "odd", "one_row", "ragged_batch", "no_rule"])
def test_plan_follows_mesh_rules_and_shape(rules, batch, seq, engaged):
    assert tp.plan(batch, seq) is None  # no context
    with sharding_ctx(_mesh(MESHES["fsdp2xtp2"]), rules):
        plan = tp.plan(batch, seq)
    assert (plan is not None) == engaged
    if engaged:
        assert (plan.axis, plan.extent, plan.rows) == ("tensor", 2, seq // 2)
    with sharding_ctx(_mesh(ONE), rules):
        assert tp.plan(batch, seq) is None
    # A mesh that cuts the activations' rows over `seq` keeps its layout.
    with sharding_ctx(_mesh(MeshSpec(seq=2, tensor=2)), rules):
        assert tp.plan(batch, seq) is None


def test_odd_length_lowers_to_the_program_without_the_rule():
    """A sequence the extent does not divide: the same program, op for op,
    as under rules that never heard of `seq_res`."""
    cfg = llama_tiny(remat=True)
    tokens = _tokens(cfg, (8, 32))  # shift_inputs: 31 positions
    before, texts = _count(), []
    for rules in (RULES_TP, RULES_WHOLE):
        ts = transformer_train_step(cfg, _mesh(MESHES["fsdp2xtp2"]),
                                    rules=rules, shift_inputs=True)
        params, opt = ts.init(jax.random.key(0))
        texts.append(ts.lower_step(
            params, opt, ts.shard_batch({"tokens": tokens})).as_text())
    assert texts[0] == texts[1]
    assert _count() == before


def test_decode_tick_under_tensor_mesh_is_the_unsharded_one():
    """Prefill (an even prompt: the layers' products are decomposed) then a
    decode tick (one row: nothing engages) under fsdp=2 x tensor=2 against
    the same calls with no mesh."""
    cfg = llama_tiny()
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jnp.asarray(_tokens(cfg, (4, 16)))

    def run(params, tokens):
        logits, cache = prefill(params, tokens, cfg, max_len=24)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return logits, decode_step(params, cache, nxt, cfg)[0]

    want = jax.jit(run)(params, tokens)
    before = _count()

    def sharded(params, tokens):
        with sharding_ctx(_mesh(MESHES["fsdp2xtp2"]), RULES_TP):
            return run(params, tokens)

    got = jax.jit(sharded)(params, tokens)
    assert _count() == before + 1  # the prefill's layer body alone
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-2)


def test_counter_is_one_a_traced_body_with_its_attributes(monkeypatch):
    seen = []
    real = tracing.observe
    monkeypatch.setattr(tracing, "observe", lambda name, ns, **kw: (
        seen.append((name, kw)), real(name, ns, **kw))[1])
    cfg = llama_tiny(remat=True)
    tokens = _tokens(cfg)
    _loss_and_grads(cfg, MESHES["fsdp2xtp2"], RULES_TP, tokens)
    rows = [kw for name, kw in seen if name == "train.tp_overlap"]
    assert rows == [dict(slow=False, axis="tensor", extent=2, steps=2,
                         rows=16, products="qkv,wo,gate_up,w_down")]
    seen.clear()
    _loss_and_grads(cfg, MESHES["fsdp2xtp2"], RULES_WHOLE, tokens)
    assert not [1 for name, _ in seen if name == "train.tp_overlap"]


# ------------------------------------------------- the other layer kinds


@pytest.mark.parametrize("preset,over", [
    ("kimi_linear_tiny", {}), ("granite_hybrid_tiny", {}),
    ("mellum2_tiny", dict(n_kv_heads=2)),  # its one kv head cannot be cut
    ("moe_tiny", {})], ids=["kimi", "granite", "mellum2", "moe"])
def test_other_mixers_keep_one_devices_answer_under_tensor(preset, over):
    """KDA, MLA, Mamba-2, windowed layers and experts get the rows gathered
    and leave their sum over `tensor` to the partitioner: the loss is one
    device's."""
    from ray_tpu.models import configs

    cfg = getattr(configs, preset)(remat=True, **over)
    tokens = _tokens(cfg, (4, 65))
    made = tfm.init_params(jax.random.key(0), cfg)  # once for both meshes

    def loss(spec):
        ts = transformer_train_step(cfg, _mesh(spec), shift_inputs=True)
        params = jax.device_put(made, ts.param_shardings)
        out = ts.eval_loss(params, ts.shard_batch({"tokens": tokens}))
        return float(out[0] if isinstance(out, tuple) else out)

    assert abs(loss(MESHES["fsdp2xtp2"]) - loss(ONE)) < 1e-2


# ------------------------------------------------- the optimizer's moments


def test_init_shards_the_moments_as_their_parameters():
    """`ShardedTrainStep.init`: AdamW's mu and nu of a leaf cut over
    `tensor` (and `fsdp`) are cut as the leaf is, where propagation alone
    left them replicated; the step count is replicated."""
    ts = transformer_train_step(llama_tiny(), _mesh(MESHES["fsdp2xtp2"]))
    params, opt = ts.init(jax.random.key(0))
    adam = opt[0]
    for moment in (adam.mu, adam.nu):
        for p, m in zip(jax.tree.leaves(params), jax.tree.leaves(moment)):
            assert m.sharding == p.sharding, (p.shape, m.sharding)
    wq = adam.mu["layers"]["wq"]
    assert "tensor" in wq.sharding.spec and "fsdp" in wq.sharding.spec
    assert adam.count.sharding.is_fully_replicated
    # And the step takes and returns them so.
    batch = ts.shard_batch({"tokens": _tokens(llama_tiny(), (8, 32))})
    _, opt, _ = ts.step(params, opt, batch)
    after = opt[0].mu["layers"]["wq"]
    assert after.sharding.is_equivalent_to(wq.sharding, wq.ndim)
