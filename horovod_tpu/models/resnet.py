"""ResNet family (v1.5) — the benchmark flagship.

Parity target: the reference benchmarks ResNet-50/101 data-parallel training
(reference: docs/benchmarks.rst:9-43, examples/pytorch/
pytorch_imagenet_resnet50.py, examples/pytorch/pytorch_synthetic_benchmark.py).
This is a from-scratch flax.linen implementation with an EXPLICIT TPU
mixed-precision policy instead of a single dtype knob:

- ``dtype`` (default fp32; the benchmark's configuration passes bf16):
  conv/matmul compute dtype — what rides the MXU.
- ``param_dtype`` (fp32): master weights, BN scale/bias AND the BN running
  statistics. flax additionally force-float32s the batch-statistics
  *reduction* itself (``_compute_stats(force_float32_reductions=True)``), so
  with bf16 activations the mean/var accumulation never happens in bf16 —
  the recipe the conv path's numerics depend on, pinned by
  tests/test_profiler.py.
- layout: NHWC is the TPU-native conv layout (channels on the 128-wide
  lane dimension). ``input_layout="NCHW"`` transposes PyTorch-style inputs
  once at entry instead of letting every conv do it implicitly.
- ``pad_stem_to``: zero-pads the 3-channel image to a lane-friendlier
  channel count (e.g. 8) before the 7x7 stem conv. Zero input channels
  contribute exactly zero to the conv output, so the function is unchanged
  (the stem filter just grows dead input slices) while the conv's innermost
  contraction stops being a 3-deep tail that misaligns the (8,128) tiling.
  Off by default: it changes the param tree shape (checkpoints).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any


def pad_channels_to_multiple(x: jnp.ndarray, multiple: int) -> jnp.ndarray:
    """Zero-pad the trailing (channel) dim up to a multiple. Exact for convs:
    zero channels contribute nothing to any output element."""
    if multiple <= 1:
        return x
    c = x.shape[-1]
    pad = (-c) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths)


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with projection shortcut (v1.5: stride on
    the 3x3)."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        # Zero-init the last BN scale of each block: standard large-batch
        # ResNet recipe (matches the reference example's --use-adasum-era
        # training setups).
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNetBlock(nn.Module):
    """Two 3x3 convs (ResNet-18/34)."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1),
                                 self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.float32        # compute dtype (conv/matmul/BN outputs)
    param_dtype: Any = jnp.float32  # master weights + BN scale/bias/stats
    input_layout: str = "NHWC"      # or "NCHW" (transposed once at entry)
    pad_stem_to: int = 0            # 0 = off; e.g. 8 pads RGB 3 -> 8 lanes

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        if x.ndim != 4:
            raise ValueError(f"expected a rank-4 image batch, got {x.shape}")
        if self.input_layout == "NCHW":
            x = jnp.transpose(x, (0, 2, 3, 1))
        elif self.input_layout != "NHWC":
            raise ValueError(f"input_layout must be NHWC or NCHW, got "
                             f"{self.input_layout!r}")
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype,
                                 param_dtype=self.param_dtype)
        # BN computes its *output* in the model dtype (bf16 on TPU); flax
        # accumulates the batch statistics in float32 regardless
        # (force_float32_reductions) and stores running stats + scale/bias
        # in param_dtype (fp32) — the standard TPU recipe. An all-fp32 BN
        # output path would force casts + 2x HBM bytes around every one of
        # the ~53 normalizations and costs ~25% of step time on v5e.
        norm = functools.partial(nn.BatchNorm, use_running_average=not train,
                                 momentum=0.9, epsilon=1e-5,
                                 dtype=self.dtype,
                                 param_dtype=self.param_dtype)
        x = x.astype(self.dtype)
        if self.pad_stem_to > 1:
            x = pad_channels_to_multiple(x, self.pad_stem_to)
        x = conv(self.num_filters, (7, 7), (2, 2),
                 padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, block_size in enumerate(self.stage_sizes):
            for j in range(block_size):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(self.num_filters * 2 ** i,
                                   strides=strides, conv=conv, norm=norm,
                                   act=nn.relu)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32,
                     param_dtype=self.param_dtype, name="head")(x)
        return x.astype(jnp.float32)


ResNet18 = functools.partial(ResNet, stage_sizes=[2, 2, 2, 2],
                             block_cls=ResNetBlock)
ResNet34 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=ResNetBlock)
ResNet50 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=BottleneckBlock)
ResNet101 = functools.partial(ResNet, stage_sizes=[3, 4, 23, 3],
                              block_cls=BottleneckBlock)
ResNet152 = functools.partial(ResNet, stage_sizes=[3, 8, 36, 3],
                              block_cls=BottleneckBlock)
