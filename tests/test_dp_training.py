"""End-to-end data-parallel training tests — the analog of the reference's
DistributedOptimizer correctness tests (reference:
test/parallel/test_torch.py TorchTests.test_gradient_aggregation /
test_horovod_allreduce_grad patterns).

Gold test: an 8-way DP step over a global batch must produce the same params
as a single-device step on the full batch (gradient averaging correctness).
"""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu.models import MnistConvNet
from horovod_tpu.parallel import dp, mesh as mesh_lib


def _make_batch(n=64, seed=0):
    rng = np.random.RandomState(seed)
    images = rng.rand(n, 28, 28, 1).astype(np.float32)
    labels = rng.randint(0, 10, size=(n,))
    return {"image": jnp.asarray(images), "label": jnp.asarray(labels)}


def _loss_fn_factory(model):
    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["image"], train=False)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["label"]).mean()
        return loss, {"accuracy": jnp.mean(
            jnp.argmax(logits, -1) == batch["label"]).astype(jnp.float32)}
    return loss_fn


@pytest.fixture(scope="module")
def mnist_setup():
    model = MnistConvNet()
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 28, 28, 1)))["params"]
    return model, params


def test_dp_step_matches_single_device(dp_mesh, mnist_setup):
    model, params = mnist_setup
    loss_fn = _loss_fn_factory(model)
    opt = optax.sgd(0.1)
    batch = _make_batch(64)
    rng = jax.random.key(7)

    # Single-device reference: plain full-batch step.
    def single_step(params, opt_state, batch):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, rng)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    ref_params, _, ref_loss = jax.jit(single_step)(
        params, opt.init(params), batch)

    # 8-way DP step via the framework.
    step = dp.make_train_step(loss_fn, opt, dp_mesh, donate=False)
    out = step(dp.replicate(params, dp_mesh),
               dp.replicate(opt.init(params), dp_mesh),
               dp.shard_batch(batch, dp_mesh), rng)

    np.testing.assert_allclose(float(out.loss), float(ref_loss), rtol=1e-4)
    flat_ref = jax.tree_util.tree_leaves(ref_params)
    flat_dp = jax.tree_util.tree_leaves(out.params)
    for a, b in zip(flat_ref, flat_dp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_training_reduces_loss(dp_mesh, mnist_setup):
    model, params = mnist_setup
    loss_fn = _loss_fn_factory(model)
    opt = optax.sgd(0.5)
    step = dp.make_train_step(loss_fn, opt, dp_mesh, donate=False)

    params_d = dp.replicate(params, dp_mesh)
    opt_state = dp.replicate(opt.init(params), dp_mesh)
    batch = dp.shard_batch(_make_batch(64), dp_mesh)
    rng = jax.random.key(0)

    losses = []
    for i in range(8):
        out = step(params_d, opt_state, batch, jax.random.fold_in(rng, i))
        params_d, opt_state = out.params, out.opt_state
        losses.append(float(out.loss))
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"


def test_distributed_optimizer_wrapper(dp_mesh, mnist_setup):
    """DistributedOptimizer(optax.sgd) inside shard_map == dp.make_train_step
    semantics (allreduced grads)."""
    model, params = mnist_setup
    loss_fn = _loss_fn_factory(model)
    dist_opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    batch = _make_batch(64, seed=2)
    rng = jax.random.key(3)

    def local_step(params, opt_state, batch):
        grads, _ = jax.grad(
            lambda p, b: loss_fn(p, b, rng), has_aux=True)(params, batch)
        updates, opt_state = dist_opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    mapped = jax.shard_map(local_step, mesh=dp_mesh,
                           in_specs=(P(), P(), P(("data", "fsdp"))),
                           out_specs=(P(), P()), check_vma=False)
    new_params, _ = jax.jit(mapped)(
        dp.replicate(params, dp_mesh),
        dp.replicate(dist_opt.init(params), dp_mesh),
        dp.shard_batch(batch, dp_mesh))

    # Reference: single-device full batch step.
    def single(params, batch):
        grads, _ = jax.grad(
            lambda p, b: loss_fn(p, b, rng), has_aux=True)(params, batch)
        opt = optax.sgd(0.1)
        updates, _ = opt.update(grads, opt.init(params), params)
        return optax.apply_updates(params, updates)

    ref = jax.jit(single)(params, batch)
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(new_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_backward_passes_per_step(dp_mesh):
    """bpps=2: no update on odd microsteps, averaged aggregate applied on the
    boundary (reference: torch/optimizer.py backward_passes_per_step delay
    counters; tensorflow/gradient_aggregation.py)."""
    params = {"w": jnp.ones((4,), jnp.float32)}
    dist_opt = hvd.DistributedOptimizer(optax.sgd(1.0),
                                        backward_passes_per_step=2)

    def loss(p, x):
        return jnp.mean(p["w"] * x)

    def two_micro_steps(params, opt_state, x1, x2):
        g1 = jax.grad(loss)(params, x1)
        u1, opt_state = dist_opt.update(g1, opt_state, params)
        p1 = optax.apply_updates(params, u1)
        g2 = jax.grad(loss)(p1, x2)
        u2, opt_state = dist_opt.update(g2, opt_state, p1)
        return p1, optax.apply_updates(p1, u2)

    x1 = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    x2 = 2.0 * x1
    mapped = jax.shard_map(
        lambda p, s, a, b: two_micro_steps(p, s, a[0], b[0]),
        mesh=dp_mesh, in_specs=(P(), P(), P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False)
    p_mid, p_final = jax.jit(mapped)(params, dist_opt.init(params), x1, x2)

    # Microstep 1 applies nothing.
    np.testing.assert_allclose(np.asarray(p_mid["w"]), np.ones(4))
    # Boundary applies SGD on mean over replicas of mean of the two grads.
    g_expected = (np.mean(np.asarray(x1), axis=0) / 4 +
                  np.mean(np.asarray(x2), axis=0) / 4) / 2
    np.testing.assert_allclose(np.asarray(p_final["w"]),
                               1.0 - g_expected, rtol=1e-5)


@pytest.mark.parametrize("comp", ["fp16", "bf16"])
def test_compression(dp_mesh, mnist_setup, comp):
    model, params = mnist_setup
    loss_fn = _loss_fn_factory(model)
    compression = getattr(hvd.Compression, comp)
    opt = optax.sgd(0.1)
    step = dp.make_train_step(loss_fn, opt, dp_mesh,
                              compression=compression, donate=False)
    batch = _make_batch(64)
    out = step(dp.replicate(params, dp_mesh),
               dp.replicate(opt.init(params), dp_mesh),
               dp.shard_batch(batch, dp_mesh), jax.random.key(0))
    assert np.isfinite(float(out.loss))
    # Compressed-gradient step stays close to the uncompressed one.
    step_ref = dp.make_train_step(loss_fn, opt, dp_mesh, donate=False)
    out_ref = step_ref(dp.replicate(params, dp_mesh),
                       dp.replicate(opt.init(params), dp_mesh),
                       dp.shard_batch(batch, dp_mesh), jax.random.key(0))
    for a, b in zip(jax.tree_util.tree_leaves(out.params),
                    jax.tree_util.tree_leaves(out_ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-2, atol=5e-3)


def test_adasum_training_step(dp_mesh, mnist_setup):
    """Adasum op runs end-to-end in the DP step (reference:
    test/parallel/test_adasum_pytorch.py smoke behavior)."""
    model, params = mnist_setup
    loss_fn = _loss_fn_factory(model)
    opt = optax.sgd(0.1)
    step = dp.make_train_step(loss_fn, opt, dp_mesh, op=hvd.Adasum,
                              donate=False)
    batch = _make_batch(64)
    out = step(dp.replicate(params, dp_mesh),
               dp.replicate(opt.init(params), dp_mesh),
               dp.shard_batch(batch, dp_mesh), jax.random.key(0))
    assert np.isfinite(float(out.loss))


def test_metric_average(dp_mesh):
    def fn(v):
        return hvd.metric_average(v[0])

    vals = jnp.arange(8, dtype=jnp.float32)
    mapped = jax.shard_map(fn, mesh=dp_mesh, in_specs=(P("data"),),
                           out_specs=P(), check_vma=False)
    out = jax.jit(mapped)(vals)
    np.testing.assert_allclose(float(out), 3.5)


def test_stateful_train_step_threads_batch_stats(dp_mesh):
    """BatchNorm running stats update each step and stay replicated
    (make_stateful_train_step)."""
    import flax.linen as nn

    class TinyBN(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = nn.Dense(8)(x)
            x = nn.BatchNorm(use_running_average=not train, momentum=0.9)(x)
            return nn.Dense(3)(x)

    model = TinyBN()
    variables = model.init(jax.random.key(0), jnp.zeros((1, 4)), train=False)
    params, bstats = variables["params"], variables["batch_stats"]
    opt = optax.sgd(0.1)

    def loss_fn(params, model_state, batch, rng):
        logits, new_state = model.apply(
            {"params": params, "batch_stats": model_state}, batch["x"],
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()
        return loss, (new_state["batch_stats"], {})

    step = dp.make_stateful_train_step(loss_fn, opt, dp_mesh, donate=False)
    rs = np.random.RandomState(0)
    batch = {"x": dp.shard_batch(jnp.asarray(rs.rand(16, 4), jnp.float32),
                                 dp_mesh),
             "y": dp.shard_batch(jnp.asarray(rs.randint(0, 3, 16)), dp_mesh)}
    p = dp.replicate(params, dp_mesh)
    s = dp.replicate(opt.init(params), dp_mesh)
    b = dp.replicate(bstats, dp_mesh)
    prev = jax.tree_util.tree_map(np.asarray, bstats)
    for i in range(3):
        out = step(p, s, b, batch, jax.random.key(i))
        p, s, b = out.params, out.opt_state, out.model_state
    cur = jax.tree_util.tree_map(np.asarray, b)
    moved = jax.tree_util.tree_map(
        lambda a, bb: not np.allclose(a, bb), prev, cur)
    assert any(jax.tree_util.tree_leaves(moved)), "batch stats never updated"
    assert np.isfinite(float(out.loss))


def test_remat_step_matches_plain(dp_mesh, mnist_setup):
    """remat=True (jax.checkpoint: recompute activations in backward) gives
    the same params/loss as the plain step — only memory/FLOPs differ."""
    model, params = mnist_setup
    loss_fn = _loss_fn_factory(model)
    opt = optax.sgd(0.1)
    batch = _make_batch(32)
    rng = jax.random.key(3)

    def run(remat):
        step = dp.make_train_step(loss_fn, opt, dp_mesh, donate=False,
                                  remat=remat)
        return step(dp.replicate(params, dp_mesh),
                    dp.replicate(opt.init(params), dp_mesh),
                    dp.shard_batch(batch, dp_mesh), rng)

    plain = run(False)
    remat = run(True)
    np.testing.assert_allclose(float(remat.loss), float(plain.loss),
                               rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(plain.params),
                    jax.tree_util.tree_leaves(remat.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_all_step_options_compose(dp_mesh, mnist_setup):
    """compression + hierarchical + remat + prescale/postscale + donate all
    on at once: the combinations users flip must not interact badly."""
    model, params = mnist_setup
    loss_fn = _loss_fn_factory(model)
    opt = optax.sgd(0.1)
    from horovod_tpu.jax.compression import Compression

    step = dp.make_train_step(
        loss_fn, opt, dp_mesh, donate=True, remat=True,
        compression=Compression.bf16, hierarchical=True,
        prescale_factor=2.0, postscale_factor=0.5)
    batch = _make_batch(32)
    p = dp.replicate(params, dp_mesh)
    s = dp.replicate(opt.init(params), dp_mesh)
    losses = []
    for i in range(4):
        out = step(p, s, dp.shard_batch(batch, dp_mesh), jax.random.key(i))
        p, s = out.params, out.opt_state
        losses.append(float(out.loss))
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses


# -- the compile options of the asynchronous exchange stay off a CPU mesh -------

def _built(engaged):
    from horovod_tpu.metrics.registry import get_registry
    return get_registry().counter("hvd_async_exchange_steps_total",
                                  engaged=engaged).value


@pytest.mark.parametrize("stateful", [False, True],
                         ids=["plain", "stateful"])
@pytest.mark.parametrize("replicas", [1, 4, 8])
def test_cpu_mesh_step_is_compiled_without_options(devices, monkeypatch,
                                                   replicas, stateful):
    """``dp.ASYNC_EXCHANGE_COMPILER_OPTIONS`` are a TPU compiler's: on a CPU
    mesh of any size both step builders hand ``jax.jit`` none, and the
    registry says so."""
    seen = []
    real_jit = jax.jit

    def jit(fun, **kwargs):
        seen.append(kwargs.get("compiler_options", "not passed"))
        return real_jit(fun, **kwargs)

    monkeypatch.setattr(dp.jax, "jit", jit)
    mesh = mesh_lib.data_parallel_mesh(devices[:replicas])
    no, yes = _built("no"), _built("yes")

    def loss_fn(params, *rest):
        loss = jnp.sum(params["w"] ** 2)
        return (loss, ({}, {})) if stateful else (loss, {})

    make = dp.make_stateful_train_step if stateful else dp.make_train_step
    make(loss_fn, optax.sgd(0.1), mesh)
    assert seen == [None]
    assert (_built("no"), _built("yes")) == (no + 1, yes)


@pytest.mark.parametrize("replicas", [4, 8])
def test_exchange_on_a_cpu_mesh_is_the_leaf_by_leaf_one(devices, replicas):
    """The step's exchange is ``collectives.allreduce_tree`` and nothing
    around it: the gradients a step applies are, bit for bit, the ones that
    function gives for the same shards in a program of its own."""
    from horovod_tpu.parallel import collectives
    model = MnistConvNet()
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 28, 28, 1)))["params"]
    loss_fn = _loss_fn_factory(model)
    mesh = mesh_lib.data_parallel_mesh(devices[:replicas])
    axes = tuple(a for a in dp.DP_AXES if a in mesh.shape)
    batch = dp.shard_batch(_make_batch(8 * replicas), mesh)
    rng = jax.random.key(7)
    step = dp.make_train_step(loss_fn, optax.sgd(1.0), mesh, donate=False)
    out = step(dp.replicate(params, mesh),
               dp.replicate(optax.sgd(1.0).init(params), mesh), batch, rng)

    def local(params, batch, rng):
        rng = jax.random.fold_in(rng, collectives.axis_rank(axes))
        grads = jax.grad(lambda p: loss_fn(p, batch, rng)[0])(params)
        return collectives.allreduce_tree(grads, axis=axes)

    grads = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(axes), P()), out_specs=P(),
        check_vma=False))(dp.replicate(params, mesh), batch, rng)
    for p, g, new in zip(*map(jax.tree_util.tree_leaves,
                              (params, grads, out.params))):
        np.testing.assert_array_equal(np.asarray(p) - np.asarray(g),
                                      np.asarray(new))
