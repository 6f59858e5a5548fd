"""Model-family sanity tests (reference analog: the models exercised by
examples/pytorch/pytorch_mnist.py and pytorch_imagenet_resnet50.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import MnistConvNet, ResNet18, ResNet50


def _param_count(tree):
    return sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))


def test_mnist_convnet_shapes():
    model = MnistConvNet()
    variables = model.init(jax.random.key(0), jnp.zeros((2, 28, 28, 1)))
    out = model.apply(variables, jnp.zeros((4, 28, 28, 1)), train=False)
    assert out.shape == (4, 10)
    assert out.dtype == jnp.float32


def test_resnet18_forward():
    model = ResNet18(num_classes=10)
    variables = jax.jit(lambda k, x: model.init(k, x, train=False))(
        jax.random.key(0), jnp.zeros((1, 64, 64, 3)))
    out = jax.jit(lambda v, x: model.apply(v, x, train=False))(
        variables, jnp.zeros((2, 64, 64, 3)))
    assert out.shape == (2, 10)


def test_resnet50_param_count():
    """ResNet-50 ImageNet has ~25.56M params (torchvision parity)."""
    model = ResNet50(num_classes=1000)
    variables = jax.eval_shape(  # the count is in the shapes
        lambda k, x: model.init(k, x, train=False), jax.random.key(0),
        jnp.zeros((1, 32, 32, 3)))
    n = _param_count(variables["params"])
    assert 25.4e6 < n < 25.7e6, f"param count {n}"


def test_resnet50_train_mode_updates_batch_stats():
    model = ResNet50(num_classes=10, dtype=jnp.float32)
    variables = jax.jit(lambda k, x: model.init(k, x, train=False))(
        jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
    x = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3), jnp.float32)
    out, new_state = jax.jit(lambda v, x: model.apply(
        v, x, train=True, mutable=["batch_stats"]))(variables, x)
    assert out.shape == (2, 10)
    # batch stats must actually move
    old = jax.tree_util.tree_leaves(variables["batch_stats"])
    new = jax.tree_util.tree_leaves(new_state["batch_stats"])
    assert any(not np.allclose(a, b) for a, b in zip(old, new))


def test_bert_base_param_count_and_forward():
    """BERT-Base is ~110M params: 86M encoder + 23.4M tied embeddings
    (the LM head shares the embedding matrix, as published)."""
    from horovod_tpu.models import BertBase
    model = BertBase(max_len=64, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 30522, (2, 16)))
    variables = jax.jit(model.init)(jax.random.key(0), tokens)
    n = _param_count(variables["params"])
    assert 105e6 < n < 115e6, f"param count {n}"
    logits = jax.jit(model.apply)(variables, tokens)
    assert logits.shape == (2, 16, 30522)
    assert logits.dtype == jnp.float32


def test_bert_flash_attention_variant():
    """use_flash=True routes attention through the Pallas kernel with the
    same projection geometry; a flash model trains (grads finite, loss
    differentiable) and its forward stays finite."""
    import optax
    from horovod_tpu.models.transformer import BertEncoder

    rs = np.random.RandomState(0)
    tokens = jnp.asarray(rs.randint(0, 97, (2, 16)))
    model = BertEncoder(vocab=97, layers=2, hidden=32, heads=4, mlp_dim=64,
                        max_len=16, dtype=jnp.float32, use_flash=True)
    variables = jax.jit(model.init)(jax.random.key(0), tokens)
    logits = jax.jit(model.apply)(variables, tokens)
    assert logits.shape == (2, 16, 97)
    assert np.isfinite(np.asarray(logits)).all()

    labels = jnp.asarray(rs.randint(0, 97, (2, 16)))

    def loss_fn(params):
        lg = model.apply({"params": params}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            lg, labels).mean()

    grads = jax.jit(jax.grad(loss_fn))(variables["params"])
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    assert sum(float(jnp.abs(g).sum()) for g in flat) > 0


def test_bert_trains_under_dp_step(dp_mesh):
    """A tiny encoder trains (loss drops) through the fused+compressed DP
    step — the in-jit path the BERT benchmark exercises."""
    import optax
    from horovod_tpu.jax.compression import Compression
    from horovod_tpu.models.transformer import BertEncoder
    from horovod_tpu.parallel import dp

    model = BertEncoder(vocab=97, layers=2, hidden=32, heads=4, mlp_dim=64,
                        max_len=16, dtype=jnp.float32)
    rs = np.random.RandomState(0)
    tokens = jnp.asarray(rs.randint(0, 97, (8, 16)))
    params = model.init(jax.random.key(0), tokens)["params"]
    opt = optax.adamw(3e-3)

    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["tokens"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["labels"]).mean()
        return loss, {}

    step = dp.make_train_step(loss_fn, opt, dp_mesh, donate=False,
                              compression=Compression.bf16)
    batch = {
        "tokens": dp.shard_batch(jnp.asarray(rs.randint(0, 97, (16, 16))),
                                 dp_mesh),
        "labels": dp.shard_batch(jnp.asarray(rs.randint(0, 97, (16, 16))),
                                 dp_mesh),
    }
    p = dp.replicate(params, dp_mesh)
    s = dp.replicate(opt.init(params), dp_mesh)
    losses = []
    for i in range(12):
        out = step(p, s, batch, jax.random.key(i))
        p, s = out.params, out.opt_state
        losses.append(float(out.loss))
    assert losses[-1] < losses[0] * 0.8, losses


@pytest.mark.parametrize("use_flash", [False, True], ids=["dot", "flash"])
def test_gpt_decoder_is_causal(use_flash):
    """A future-token perturbation must not change earlier positions'
    logits — both attention paths enforce causality."""
    from horovod_tpu.models import GptDecoder

    model = GptDecoder(vocab=97, layers=2, hidden=32, heads=4, mlp_dim=64,
                       max_len=16, dtype=jnp.float32, use_flash=use_flash)
    rs = np.random.RandomState(0)
    tokens = jnp.asarray(rs.randint(0, 97, (2, 16)))
    variables = model.init(jax.random.key(0), tokens)
    base = model.apply(variables, tokens)
    perturbed = tokens.at[:, -1].set((tokens[:, -1] + 1) % 97)
    out = model.apply(variables, perturbed)
    np.testing.assert_allclose(np.asarray(out[:, :-1]),
                               np.asarray(base[:, :-1]), rtol=1e-5,
                               atol=1e-6)
    assert not np.allclose(np.asarray(out[:, -1]), np.asarray(base[:, -1]))


def test_gpt_trains_under_dp_step(dp_mesh):
    import optax
    from horovod_tpu.models import GptDecoder
    from horovod_tpu.parallel import dp

    model = GptDecoder(vocab=97, layers=2, hidden=32, heads=4, mlp_dim=64,
                       max_len=16, dtype=jnp.float32, use_flash=True)
    rs = np.random.RandomState(0)
    tokens = jnp.asarray(rs.randint(0, 97, (8, 16)))
    params = model.init(jax.random.key(0), tokens)["params"]
    opt = optax.adamw(3e-3)

    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["tokens"])
        # next-token prediction: shift by one
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], batch["tokens"][:, 1:]).mean()
        return loss, {}

    step = dp.make_train_step(loss_fn, opt, dp_mesh, donate=False)
    batch = {"tokens": dp.shard_batch(
        jnp.asarray(rs.randint(0, 97, (16, 16))), dp_mesh)}
    p = dp.replicate(params, dp_mesh)
    s = dp.replicate(opt.init(params), dp_mesh)
    losses = []
    for i in range(6):
        out = step(p, s, batch, jax.random.key(i))
        p, s = out.params, out.opt_state
        losses.append(float(out.loss))
    assert losses[-1] < losses[0], losses
