"""The gradient exchange packs nothing on its plain path.

``collectives.allreduce_tree`` reduces a gradient tree leaf by leaf, each
leaf in the shape and layout backward gave it; XLA combines the collectives.
The flat buffer of ``ops/fusion.fused_apply_tree`` (ravel, concatenate, one
collective per dtype, slice, reshape) is the reference it is held to here:
the same elementwise sums over the same group, so the two agree bit for bit
as programs of their own, and to 2 ulp inside a whole step, where the
compiler fuses each with different neighbours. The paths that do need a
flat buffer (int8 cohorts, Adasum groups, size-bounded buckets) still get
theirs.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd_jax
from horovod_tpu.jax.compression import Compression
from horovod_tpu.ops.fusion import fused_apply_tree
from horovod_tpu.parallel import bucketing, collectives, dp, mesh as mesh_lib
from horovod_tpu.parallel.collectives import Adasum, Average, Sum

ULP2_FP32 = 2.4e-7  # two units in the last place of an fp32 value
WIRES = {"fp32": None, "bf16": Compression.bf16, "fp16": Compression.fp16}
SCALES = {"unscaled": (1.0, 1.0), "scaled": (0.5, 4.0)}


@pytest.fixture(scope="module")
def mesh4(devices):
    return mesh_lib.data_parallel_mesh(devices[:4])


def _tree(replicas):
    """Mixed shapes and two dtypes, a different value on every replica: one
    leading row per replica, which shard_map hands out."""
    rs = np.random.RandomState(5)

    def leaf(shape, dtype):
        return jnp.asarray(rs.randn(replicas, *shape), dtype)

    return {"embed": leaf((33, 8), jnp.float32),
            "block": {"kernel": leaf((8, 3, 5), jnp.float32),
                      "bias": leaf((5,), jnp.float32),
                      "scale": leaf((), jnp.float32)},
            "half": {"kernel": leaf((7, 9), jnp.bfloat16),
                     "bias": leaf((9,), jnp.bfloat16)}}


def _exchange(mesh, fn, tree):
    """``fn`` on every replica's own rows of ``tree``; replica 0's result."""
    axes = tuple(mesh.axis_names)

    def local(t):
        out = fn(jax.tree_util.tree_map(lambda v: v[0], t))
        return jax.tree_util.tree_map(lambda v: v[None], out)

    mapped = jax.shard_map(local, mesh=mesh, in_specs=P(axes),
                           out_specs=P(axes), check_vma=False)
    return jax.tree_util.tree_map(lambda v: np.asarray(v[0]),
                                  jax.jit(mapped)(tree))


def _packed_tree(tree, **kwargs):
    """The reference: the same reduction over one flat buffer per dtype."""
    return fused_apply_tree(
        functools.partial(collectives.wire_allreduce, **kwargs), tree)


def _assert_bit_equal(got, want):
    got, want = (jax.tree_util.tree_leaves(t) for t in (got, want))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("scale", list(SCALES))
@pytest.mark.parametrize("op", [Average, Sum], ids=lambda op: op.value)
@pytest.mark.parametrize("wire", list(WIRES))
def test_leaf_by_leaf_equals_the_packed_exchange(mesh4, wire, op, scale):
    pre, post = SCALES[scale]
    kwargs = dict(op=op, axis=dp.DP_AXES[:1], prescale_factor=pre,
                  postscale_factor=post, compression=WIRES[wire])
    tree = _tree(4)
    by_leaf = _exchange(mesh4, functools.partial(
        collectives.allreduce_tree, **kwargs), tree)
    packed = _exchange(mesh4, functools.partial(_packed_tree, **kwargs),
                       tree)
    _assert_bit_equal(by_leaf, packed)
    # and it is the reduction it says: fp32 leaves against numpy
    rows = np.asarray(tree["embed"], np.float64)
    if WIRES[wire] is None:
        want = (rows * pre).sum(0) * post / (4 if op is Average else 1)
        np.testing.assert_allclose(by_leaf["embed"], want, rtol=1e-6,
                                   atol=1e-6)


def test_hierarchical_leaf_by_leaf_equals_the_packed_exchange(devices):
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, fsdp=2), devices[:4])
    kwargs = dict(op=Average, axis=("data", "fsdp"), hierarchical=True)
    tree = _tree(4)
    by_leaf = _exchange(mesh, functools.partial(
        collectives.allreduce_tree, **kwargs), tree)
    packed = _exchange(mesh, functools.partial(_packed_tree, **kwargs), tree)
    _assert_bit_equal(by_leaf, packed)


# ---------------------------------------------------------------------------
# inside a whole step


def _loss(params, batch, rng):
    h = jnp.tanh(batch["x"] @ params["w1"] + params["b1"])
    return jnp.mean((h @ params["w2"] - batch["y"]) ** 2), {}


def _step_job(mesh, **kwargs):
    k1, k2, kx, ky = jax.random.split(jax.random.key(3), 4)
    params = dp.replicate({"w1": 0.3 * jax.random.normal(k1, (8, 16)),
                           "b1": jnp.zeros((16,)),
                           "w2": 0.3 * jax.random.normal(k2, (16, 4))}, mesh)
    optimizer = optax.sgd(0.1)
    step = dp.make_train_step(_loss, optimizer, mesh, donate=False, **kwargs)
    batch = dp.shard_batch({"x": jax.random.normal(kx, (16, 8)),
                            "y": jax.random.normal(ky, (16, 4))}, mesh)
    return step, (params, dp.replicate(optimizer.init(params), mesh)), batch


@pytest.mark.parametrize("wire", ["fp32", "bf16"])
def test_a_step_moves_by_at_most_2_ulp(mesh4, monkeypatch, wire):
    def train():
        step, state, batch = _step_job(mesh4, compression=WIRES[wire])
        for _ in range(3):
            out = step(*state, batch, jax.random.key(0))
            state = (out.params, out.opt_state)
        return jax.tree_util.tree_map(np.asarray, state[0]), float(out.loss)

    by_leaf, loss = train()
    monkeypatch.setattr(collectives, "allreduce_tree", _packed_tree)
    packed, packed_loss = train()
    for got, want in zip(jax.tree_util.tree_leaves(by_leaf),
                         jax.tree_util.tree_leaves(packed)):
        # SGD adds lr * gradient: 2 ulp of the value or of a step's update
        np.testing.assert_allclose(got, want, rtol=ULP2_FP32,
                                   atol=ULP2_FP32 * 0.1)
    assert loss == pytest.approx(packed_loss, rel=ULP2_FP32)


# ---------------------------------------------------------------------------
# the lowered text


def _ops_under(text, scope):
    """Operation names of a lowered module (``as_text(debug_info=True)``)
    whose location's name stack holds ``scope``."""
    named = {m.group(1) for m in re.finditer(
        r'^(#loc\d+) = loc\("([^"]*)"', text, re.M) if scope in m.group(2)}
    ops = []
    for line in text.splitlines():
        op = re.search(r'=\s*"?([a-z_]+\.[a-z_.]+)"?', line)
        where = re.search(r'loc\((#loc\d+)\)\s*$', line)
        if op and where and where.group(1) in named:
            ops.append(op.group(1))
    return ops


@pytest.mark.parametrize("replicas", [1, 4])
def test_no_concatenate_under_the_exchange(devices, replicas):
    mesh = mesh_lib.data_parallel_mesh(devices[:replicas])
    step, state, batch = _step_job(mesh)
    text = step.lower(*state, batch, jax.random.key(0)).as_text(
        debug_info=True)
    ops = _ops_under(text, "phase_grad_exchange")
    # the phase is there (a region op's location closes its region: the
    # all-reduce's own line carries none), and so is what it divides by
    assert "phase_grad_exchange/hvd_allreduce_average/psum" in text
    assert "stablehlo.divide" in ops
    for packing in ("concatenate", "reshape", "slice"):
        assert not [op for op in ops if packing in op], ops
    # the reader has teeth: the bucketed exchange does pack
    step, state, batch = _step_job(mesh, bucket_bytes=1 << 20)
    text = step.lower(*state, batch, jax.random.key(0)).as_text(
        debug_info=True)
    assert "stablehlo.concatenate" in _ops_under(text, "phase_grad_exchange")


# ---------------------------------------------------------------------------
# who still gets a flat buffer


def _spy(monkeypatch, module, name, calls):
    real = getattr(module, name)

    def spy(*args, **kwargs):
        calls.append(name)
        return real(*args, **kwargs)

    monkeypatch.setattr(module, name, spy)


def _spies(monkeypatch):
    calls = []
    _spy(monkeypatch, dp, "fused_apply_tree", calls)
    _spy(monkeypatch, hvd_jax, "fused_apply_tree", calls)
    _spy(monkeypatch, bucketing, "bucketed_apply_tree", calls)
    _spy(monkeypatch, collectives, "grouped_allreduce", calls)
    _spy(monkeypatch, collectives, "allreduce_tree", calls)
    return calls


ROUTES = {
    "plain": (dict(), "allreduce_tree"),
    "bf16": (dict(compression=Compression.bf16), "allreduce_tree"),
    "int8": (dict(compression=Compression.int8), "fused_apply_tree"),
    "adasum": (dict(op=Adasum), "grouped_allreduce"),
    "bucketed": (dict(bucket_bytes=256), "bucketed_apply_tree"),
    "int8-bucketed": (dict(compression=Compression.int8, bucket_bytes=256),
                      "bucketed_apply_tree"),
}


@pytest.mark.parametrize("route", list(ROUTES))
def test_the_step_builder_routes_by_what_it_observes(mesh4, monkeypatch,
                                                     route):
    kwargs, expected = ROUTES[route]
    calls = _spies(monkeypatch)
    step, state, batch = _step_job(mesh4, **kwargs)
    step.lower(*state, batch, jax.random.key(0))
    assert calls == [expected]


@pytest.mark.parametrize("route", ["plain", "bf16", "int8", "adasum"])
def test_distributed_optimizer_routes_like_the_step_builder(
        mesh4, monkeypatch, route):
    kwargs, expected = ROUTES[route]
    calls = _spies(monkeypatch)
    optimizer = hvd_jax.DistributedOptimizer(optax.sgd(0.1), **kwargs)
    tree = _tree(4)

    def update(grads):
        updates, _ = optimizer.update(grads, optimizer.init(grads), grads)
        return updates

    out = _exchange(mesh4, update, tree)
    assert calls == [expected]
    if route == "plain":
        want = -0.1 * np.asarray(tree["embed"], np.float64).mean(0)
        np.testing.assert_allclose(out["embed"], want, rtol=1e-6, atol=1e-6)
