"""The plain float32 references against the repo's flax models, in float32
on the CPU at a tiny size: the same architecture, so the same loss to
rounding and the same gradients. (On the chip the program runs in bf16 and
is held to the tolerance the configuration file states.)"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bench_paths
from harness import spec as spec_lib


def build(name, traffic, **over):
    config = json.load(open(os.path.join(
        bench_paths.BENCH, "configs", f"{name}.json")))
    config.update(over)
    module = spec_lib.load_module(spec_lib.BENCH / "configs" / f"{name}.py")
    return module, module.build(config, traffic)


def relative_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("seq", [128, 2048], ids=["one_block", "two_blocks"])
def test_gpt2_reference_matches_the_flax_decoder_in_float32(seq):
    """At 2048 the reference takes attention in two query blocks."""
    from horovod_tpu.models import GptSmall
    _, job = build("gpt2-small", {"seq_len": seq, "per_chip_batch": 1},
                   n_layer=2)
    model = GptSmall(dtype=jnp.float32, max_len=max(1024, seq)) \
        .clone(layers=2)
    batch = job.make_batch(jax.random.key(1), 1)
    params = jax.jit(model.init)(jax.random.key(0), batch["tokens"])["params"]

    def flax_loss(p):
        logits = model.apply({"params": p}, batch["tokens"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["labels"]).mean()
    loss, grads = jax.jit(jax.value_and_grad(flax_loss))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: job.reference_loss(p, None, batch)))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    for block in ("EncoderBlock_0", "EncoderBlock_1"):
        # a bias on every key moves each query's scores by one constant,
        # which the softmax drops: that gradient is zero but for rounding
        del errors[block]["FlashSelfAttention_0"]["key"]["bias"]
    worst = max(jax.tree_util.tree_leaves(errors))
    assert worst < 1e-3, errors


def test_gpt2_job_is_at_the_published_widths():
    _, job = build("gpt2-small", {"seq_len": 1024, "per_chip_batch": 16})
    assert job.facts == {"layers": 12, "hidden": 768, "heads": 12,
                         "mlp": 3072, "vocab": 50257, "positions": 1024,
                         "seq_len": 1024, "attention": "flash"}
    from harness import flops, kernels
    from horovod_tpu.ops.flash_attention import flash_min_seq
    assert job.flash_call == (16, 1024, 12, 64, True)
    assert job.flash_layers == 12  # 36 flash kernels in the step
    assert kernels.required(job) == dict.fromkeys(flops.FLASH_PRODUCTS, 12)
    _, short = build("gpt2-small", {"seq_len": 512, "per_chip_batch": 32})
    # the job follows the program's router: no flash shapes below its
    # threshold, and then the compiled step is asked for no kernel
    assert (short.flash_call is None) == (512 < flash_min_seq())
    assert short.flash_call is None and short.flash_layers == 0
    assert kernels.required(short) == {}
    _, long = build("gpt2-small", {"seq_len": 8192, "per_chip_batch": 2})
    assert long.facts["positions"] == 8192


def test_resnet50_reference_matches_the_flax_model_in_float32():
    from horovod_tpu.models import ResNet50
    module, job = build("resnet50", {"image_size": 64, "per_chip_batch": 8})
    model = ResNet50(num_classes=1000, dtype=jnp.float32,
                     param_dtype=jnp.float32, input_layout="NHWC",
                     pad_stem_to=8)
    batch = job.make_batch(jax.random.key(1), 8)
    variables = jax.jit(lambda k: model.init(
        k, jnp.zeros((8, 64, 64, 3)), train=True))(jax.random.key(0))
    params = module.open_residual_branches(variables["params"])
    zero_scales = [leaf for path, leaf in
                   jax.tree_util.tree_flatten_with_path(params)[0]
                   if path[-1].key == "scale" and not np.asarray(leaf).any()]
    assert not zero_scales  # every residual branch takes part in the check

    def flax_loss(p):
        logits, _ = model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]},
            batch["image"], train=True, mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["label"]).mean()
    loss, grads = jax.jit(jax.value_and_grad(flax_loss))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: job.reference_loss(p, None, batch)))(params)
    # the forward pass agrees to rounding: a wrong stride, padding or
    # normalisation would move the loss in its second digit
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    # the stem's padded rows get no gradient from the reference
    grads["conv_init"]["kernel"] = grads["conv_init"]["kernel"][:, :, :3]
    want_grads["conv_init"]["kernel"] = \
        want_grads["conv_init"]["kernel"][:, :, :3]
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    for leaf in job.check_leaves:  # the leaves compared on the chip
        e = errors
        for k in leaf:
            e = e[k]
        assert e < 1e-3, (leaf, e)
    # below the head the gradient on noise images is a small difference of
    # large terms: float32 against float32 differs by percents (PERF.md)
    assert max(jax.tree_util.tree_leaves(errors)) < 0.2, errors
