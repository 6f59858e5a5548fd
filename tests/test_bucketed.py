"""Bucketed backward-overlap exchange: plan determinism + the
bit-exactness contract across {fp32, bf16, int8} x {allreduce, ZeRO-1
reduce-scatter} (parallel/bucketing.py, dp.make_train_step(bucket_bytes=),
zero.apply_sharded_update/sharded_opt_init(bucket_bytes=)).

Contract under test (the ISSUE-11 acceptance):

- plain/cast wire formats (fp32, bf16): every bucket partition is
  BIT-equal to every other — the collectives are elementwise, so the
  partition cannot change values. The unbucketed path reduces leaf by leaf
  with no flat buffer (collectives.allreduce_tree): the same sums in
  another program, whose compiler contracts the average and the update
  into their neighbours differently. It is held to 2 ulp of fp32, or to
  1e-4 of one Adam step where Adam's m/sqrt(v) amplifies a last-bit
  difference in a gradient near zero (tests/test_grad_exchange.py holds
  the exchange itself bit-equal to the packed one);
- int8 (block-quantized): bucketed results are BIT-identical across every
  bucket partition of the leaf-aligned layout (one giant bucket included)
  — block cohorts never span leaves, so re-tuning HOROVOD_BUCKET_BYTES
  never changes training numerics — and agree with the legacy unbucketed
  layout within the block-quantization error bound.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.jax.compression import Compression
from horovod_tpu.parallel import dp, zero
from horovod_tpu.parallel.bucketing import (Bucket, bucketed_apply_tree,
                                            plan_buckets,
                                            resolve_bucket_bytes)

ULP2_FP32 = 2.4e-7  # two units in the last place of an fp32 value
LR = 1e-2  # Adam bounds a step's update by it

# Tiny mixed-shape model: enough leaves for multi-bucket plans, compiles
# in a couple of seconds per config on the 8-device CPU mesh.
_RS = np.random.RandomState(0)
PARAMS = {
    "w1": jnp.asarray(_RS.randn(17, 33), jnp.float32),
    "b1": jnp.asarray(_RS.randn(33), jnp.float32),
    "w2": jnp.asarray(_RS.randn(33, 65), jnp.float32),
    "b2": jnp.asarray(_RS.randn(65), jnp.float32),
    "w3": jnp.asarray(_RS.randn(65, 10), jnp.float32),
}


def _loss_fn(params, batch, rng):
    h = jnp.tanh(batch["x"] @ params["w1"] + params["b1"])
    h = jnp.tanh(h @ params["w2"] + params["b2"])
    logits = h @ params["w3"]
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["y"]).mean()
    return loss, {}


def _batch(mesh):
    rs = np.random.RandomState(7)
    b = 64
    return {
        "x": dp.shard_batch(jnp.asarray(rs.randn(b, 17), jnp.float32),
                            mesh),
        "y": dp.shard_batch(jnp.asarray(rs.randint(0, 10, b)), mesh),
    }


def _train(mesh, *, sharded, compression, bucket_bytes, steps=3):
    """Final params (host numpy tree) after `steps` identical steps."""
    opt = optax.adam(LR)
    step = dp.make_train_step(_loss_fn, opt, mesh, donate=False,
                              sharded_update=sharded,
                              compression=compression,
                              bucket_bytes=bucket_bytes)
    p = dp.replicate(PARAMS, mesh)
    s = zero.sharded_opt_init(opt, PARAMS, mesh,
                              bucket_bytes=bucket_bytes) if sharded \
        else dp.replicate(opt.init(PARAMS), mesh)
    batch = _batch(mesh)
    loss = None
    for _ in range(steps):
        out = step(p, s, batch, jax.random.key(1))
        p, s, loss = out.params, out.opt_state, out.loss
    tree = jax.tree_util.tree_map(np.asarray, p)
    return tree, float(loss)


def _assert_tree_equal(a, b, exact=True, rtol=0.05, atol=0.05):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        if exact:
            np.testing.assert_array_equal(x, y)
        else:
            np.testing.assert_allclose(x, y, rtol=rtol, atol=atol)


def _assert_same_sums(bucketed, unbucketed, sharded):
    """ZeRO-1's unbucketed exchange packs like its bucketed one: bit-equal.
    The plain path's does not pack at all: 2 ulp, or 1e-4 of a step."""
    _assert_tree_equal(bucketed, unbucketed, exact=sharded,
                       rtol=ULP2_FP32, atol=1e-4 * LR)


# ---------------------------------------------------------------------------
# plan


def test_plan_buckets_reverse_ready_order_and_bound():
    leaves = [np.zeros(100, np.float32), np.zeros(10, np.float32),
              np.zeros(100, np.float32)]
    plan = plan_buckets(leaves, 460)  # 2 fp32 leaves of 100+10 fit, not 3
    # reverse flatten order: bucket 0 starts at the LAST leaf (first ready
    # in backward), runs are contiguous, payload stays under the bound
    assert plan[0].indices[0] == 2
    flat = [i for b in plan for i in b.indices]
    assert flat == [2, 1, 0]
    for b in plan:
        assert b.nbytes <= 460 or len(b.indices) == 1
    assert [b.index for b in plan] == list(range(len(plan)))


def test_plan_buckets_oversized_leaf_gets_own_bucket():
    leaves = [np.zeros(4, np.float32), np.zeros(10_000, np.float32),
              np.zeros(4, np.float32)]
    plan = plan_buckets(leaves, 64)
    big = [b for b in plan if 1 in b.indices]
    assert len(big) == 1 and big[0].indices == (1,)


def test_plan_buckets_unbounded_is_one_bucket():
    leaves = [np.zeros(4, np.float32), np.zeros(8, np.float32)]
    assert plan_buckets(leaves, 0) == (Bucket(0, (1, 0), 48),)
    assert plan_buckets([], 0) == ()


def test_resolve_bucket_bytes_env_default(monkeypatch):
    monkeypatch.setenv("HOROVOD_BUCKET_BYTES", "12345")
    assert resolve_bucket_bytes(None) == 12345
    assert resolve_bucket_bytes(7) == 7
    monkeypatch.delenv("HOROVOD_BUCKET_BYTES")
    assert resolve_bucket_bytes(None) == 0


def test_bucketed_apply_tree_identity_roundtrip():
    tree = {"a": jnp.arange(10, dtype=jnp.float32),
            "b": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "c": jnp.arange(5, dtype=jnp.int32)}
    out = bucketed_apply_tree(lambda v: v * 2, tree, bucket_bytes=16,
                              align=4)
    np.testing.assert_array_equal(out["a"], np.arange(10) * 2)
    np.testing.assert_array_equal(out["b"],
                                  (np.arange(6) * 2).reshape(2, 3))
    np.testing.assert_array_equal(out["c"], np.arange(5) * 2)


# ---------------------------------------------------------------------------
# bit-exactness matrix (fast tier: fp32 both paths + int8 ZeRO; the bf16
# and int8-allreduce legs ride the slow tier — same code, more compiles)


def test_bucketed_fp32_bit_exact(dp_mesh):
    for sharded in (False, True):
        legacy, l0 = _train(dp_mesh, sharded=sharded, compression=None,
                            bucket_bytes=0)
        one, l1 = _train(dp_mesh, sharded=sharded, compression=None,
                         bucket_bytes=1 << 30)
        many, l2 = _train(dp_mesh, sharded=sharded, compression=None,
                          bucket_bytes=4096)
        _assert_tree_equal(many, one, exact=True)
        _assert_same_sums(one, legacy, sharded)
        assert l1 == l2
        assert l0 == pytest.approx(l1, rel=ULP2_FP32)


def test_bucketed_int8_zero_partition_invariant(dp_mesh):
    """int8 + ZeRO-1: results are bit-identical across bucket partitions
    (the per-leaf block alignment pins cohorts to leaves), and within the
    quantization error bound of the legacy unbucketed layout."""
    legacy, _ = _train(dp_mesh, sharded=True, compression=Compression.int8,
                       bucket_bytes=0)
    one, _ = _train(dp_mesh, sharded=True, compression=Compression.int8,
                    bucket_bytes=1 << 30)
    many, _ = _train(dp_mesh, sharded=True, compression=Compression.int8,
                     bucket_bytes=4096)
    _assert_tree_equal(many, one, exact=True)
    _assert_tree_equal(many, legacy, exact=False)


@pytest.mark.slow
def test_bucketed_bf16_bit_exact_slow(dp_mesh):
    for sharded in (False, True):
        legacy, _ = _train(dp_mesh, sharded=sharded,
                           compression=Compression.bf16, bucket_bytes=0)
        one, _ = _train(dp_mesh, sharded=sharded,
                        compression=Compression.bf16, bucket_bytes=1 << 30)
        many, _ = _train(dp_mesh, sharded=sharded,
                         compression=Compression.bf16, bucket_bytes=4096)
        _assert_tree_equal(many, one, exact=True)
        _assert_same_sums(one, legacy, sharded)


@pytest.mark.slow
def test_bucketed_int8_allreduce_partition_invariant_slow(dp_mesh):
    legacy, _ = _train(dp_mesh, sharded=False,
                       compression=Compression.int8, bucket_bytes=0)
    one, _ = _train(dp_mesh, sharded=False, compression=Compression.int8,
                    bucket_bytes=1 << 30)
    many, _ = _train(dp_mesh, sharded=False, compression=Compression.int8,
                     bucket_bytes=4096)
    _assert_tree_equal(many, one, exact=True)
    _assert_tree_equal(many, legacy, exact=False)


def test_bucketed_zero_opt_state_geometry(dp_mesh):
    """sharded_opt_init(bucket_bytes=) lays the state out per
    (bucket, dtype) group matching zero.bucket_groups — the step and the
    init must derive the identical geometry."""
    opt = optax.adam(1e-2)
    state = zero.sharded_opt_init(opt, PARAMS, dp_mesh, bucket_bytes=4096)
    leaves = jax.tree_util.tree_leaves(PARAMS)
    groups = zero.bucket_groups(leaves, 8, 4096, zero.LANE)
    keys = {g.key for g in groups}
    assert len(keys) > 1  # the tiny model still spans several buckets
    mu = state[0].mu  # adam: ScaleByAdamState.mu is the sharded dict
    assert set(mu.keys()) == keys
    for g in groups:
        assert mu[g.key].shape == (8, g.shard)
        assert g.padded % (8 * zero.LANE) == 0
