"""ResNet-50 v1.5: the job the program trains, and its plain float32 reference.

``build(config, traffic)`` returns a ``harness.job.Job``. The job's half is
what a user writes (the repo's flax model under ``bench.py``'s policy, SGD
with momentum); the reference's half is this file's own: convolutions,
BatchNorm in training mode, pooling and the head in plain ``jax.numpy`` /
``jax.lax`` and float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax

from harness import flops
from harness.job import Job, Tolerance

# bf16 keeps 8 significand bits (2**-9 a rounding). The loss at random weights
# is log(1000) plus a mean of small terms, and 2**-8 holds it. Gradients are
# compared at the head only. The images are uniform noise, as in the
# reference's synthetic benchmark: after the stem the images of a batch are
# nearly the same, BatchNorm removes what they share, and a gradient below
# the head is a small difference of large per-image terms. On the stem's
# kernel flax in float32 already differs from this file's float32 by 1.4%
# (fast variance against two-pass) and bf16 by 45% (CPU, 224 px, PR 22), so
# no tolerance there could tell a fault from arithmetic. The head's gradient
# is features^T (softmax - onehot): it carries the forward error of all 53
# convolutions, 0.8% in bf16, and 8 x 2**-8 holds it; fp8 would be 16 times
# off. tests/benchmark compares every leaf in float32 on the CPU.
TOLERANCE = Tolerance(
    loss_rtol=2.0 ** -8, grad_rel_l2=8 * 2.0 ** -8,
    reason="bf16 activations against float32 through 53 convolutions and "
           "BatchNorms: 2**-8 on the loss, 8 x 2**-8 relative L2 on the "
           "head's gradient (gradients below the head are ill-conditioned "
           "on noise images); fp8 would be 16 times off")
# what a zero BatchNorm scale is set to for the reference check
OPEN_SCALE = 0.25


def build(config: dict, traffic: dict) -> Job:
    from horovod_tpu.models import ResNet50

    px = int(traffic["image_size"])
    classes = int(config["num_classes"])
    stages = tuple(config["stage_sizes"])
    if stages != flops.RESNET50_STAGES or config["num_filters"] != 64:
        raise ValueError("the configuration is not ResNet-50")
    model = ResNet50(num_classes=classes, dtype=jnp.bfloat16,
                     param_dtype=jnp.float32, input_layout="NHWC",
                     pad_stem_to=8)
    opt = config["optimizer"]
    optimizer = optax.sgd(opt["learning_rate"], momentum=opt["momentum"])

    def init(key):
        variables = model.init(key, jnp.zeros((8, px, px, 3), jnp.bfloat16),
                               train=True)
        return variables["params"], variables["batch_stats"]

    def loss_fn(params, model_state, batch, rng):
        logits, new_state = model.apply(
            {"params": params, "batch_stats": model_state},
            batch["image"], train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["label"]).mean()
        return loss, (new_state["batch_stats"], {})

    def make_batch(key, n):
        k_image, k_label = jax.random.split(key)
        return {"image": jax.random.uniform(k_image, (n, px, px, 3),
                                            jnp.bfloat16),
                "label": jax.random.randint(k_label, (n,), 0, classes,
                                            jnp.int32)}

    return Job(
        unit="images", items_per_example=1, stateful=True, init=init,
        loss_fn=loss_fn, optimizer=optimizer, make_batch=make_batch,
        model_flops_per_item=flops.resnet50_train_flops_per_image(
            px, classes),
        reference_loss=functools.partial(reference_loss, stages=stages),
        check_leaves=(("head", "kernel"), ("head", "bias")),
        sample_examples=int(traffic.get("reference_examples", 8)),
        tolerance=TOLERANCE,
        check_params=open_residual_branches,
        facts={"image_size": px, "classes": classes,
               "stage_sizes": list(stages)})


def open_residual_branches(params):
    """The parameters the reference check runs on: every BatchNorm scale
    that starts at zero (the last of each block) set to ``OPEN_SCALE``. At
    its initial value each residual branch is multiplied by zero, forward
    and backward, and a check there would not see the 48 convolutions
    inside. A quarter and not one: sixteen unnormalised additions of
    full-size branches make the gradients explode (the stem's grows 40-fold)
    and the comparison chaotic."""
    def opened(path, leaf):
        if path[-1].key == "scale":
            return jnp.where(leaf == 0, jnp.full_like(leaf, OPEN_SCALE), leaf)
        return leaf
    return jax.tree_util.tree_map_with_path(opened, params)


# -- the plain reference ------------------------------------------------------

def _conv(x, kernel, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def _batch_norm(x, p, eps=1e-5):
    """Training mode: the batch's own mean and biased variance."""
    mean = x.mean((0, 1, 2))
    var = ((x - mean) ** 2).mean((0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _bottleneck(x, p, stride):
    y = jax.nn.relu(_batch_norm(_conv(x, p["Conv_0"]["kernel"]),
                                p["BatchNorm_0"]))
    y = jax.nn.relu(_batch_norm(_conv(y, p["Conv_1"]["kernel"], stride),
                                p["BatchNorm_1"]))
    y = _batch_norm(_conv(y, p["Conv_2"]["kernel"]), p["BatchNorm_2"])
    if "conv_proj" in p:
        x = _batch_norm(_conv(x, p["conv_proj"]["kernel"], stride),
                        p["norm_proj"])
    return jax.nn.relu(x + y)


def reference_loss(params, model_state, batch, *, stages):
    """Mean cross-entropy of ResNet-50 v1.5 in training mode, float32
    throughout. The stem runs on the image's own 3 channels: the rows of its
    kernel that meet the program's zero padding take no part."""
    del model_state  # training-mode BatchNorm does not read running statistics
    x = batch["image"].astype(jnp.float32)
    stem = params["conv_init"]["kernel"][:, :, :x.shape[-1], :]
    x = _conv(x, stem, 2, [(3, 3), (3, 3)])
    x = jax.nn.relu(_batch_norm(x, params["bn_init"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    index = 0
    for stage, blocks in enumerate(stages):
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            x = jax.checkpoint(_bottleneck, static_argnums=2)(
                x, params[f"BottleneckBlock_{index}"], stride)
            index += 1
    x = x.mean((1, 2))
    logits = jnp.dot(x, params["head"]["kernel"],
                     precision=jax.lax.Precision.HIGHEST) \
        + params["head"]["bias"]
    picked = jnp.take_along_axis(logits, batch["label"][:, None], axis=-1)
    return (jax.nn.logsumexp(logits, axis=-1) - picked[:, 0]).mean()
