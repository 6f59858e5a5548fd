"""How a Pallas kernel under ``ops/`` is called, and the few values every
kernel module writes its kernels with.

One rule (:func:`on_this_platform`): a ``pl.pallas_call`` sits in a function
under ``jax.jit`` whose ``interpret`` is a static keyword, and the rule asks
``lax.platform_dependent`` for it twice, Mosaic where the program is lowered
for a TPU and interpret mode anywhere else (the CPU of tier-1). Two things
follow from the one writing. The choice is made by the platform *lowered
for*, not by ``jax.default_backend()`` where the call is traced: a compile
for a described chip (``benchmark/compile_check.py``,
``tests/test_tpu_compile*.py``) holds the real kernels with nothing patched.
And the ``jax.jit`` caches the traced call by its shapes and static values,
so a kernel's body is traced once a signature a process, not once a layer a
trace of the step (``PERF.md`` §5's compile-log table prices what that was).
Only the branch of the platform lowered for is lowered; both are traced,
once each.

The jitted function's own name must not end in ``_kernel``: a kernel is known
in the compiled text and in a device trace by the ``*_kernel`` function its
body was traced through (the Mosaic bytecode carries the frames).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() NaN-free

NT = (((1,), (1,)), ((), ()))   # a @ b^T
NN = (((1,), (0,)), ((), ()))   # a @ b
TN = (((0,), (0,)), ((), ()))   # a^T @ b


def dot(a, b, dims):
    # Matmuls run in the input dtype (bf16 rides the fast MXU path; fp32
    # inputs keep full precision) and accumulate in fp32 via
    # preferred_element_type — casting inputs up to fp32 would force 3-pass
    # fp32 MXU matmuls and ~30% more step time.
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def scalar_spec():
    """A small whole array the kernel reads as scalars (SMEM)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


# a grid (batch, group or channel tile, sequential axis): the last in order
GRID_ORDER = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def on_this_platform(call, *args, interpret: Optional[bool] = None):
    """``call(*args, interpret=...)``: the kernel compiled for the TPU where
    the program is lowered for one, in interpret mode anywhere else, decided
    by the platform lowered for. ``call`` is a jitted function with
    ``interpret`` among its static keywords (a ``functools.partial`` over
    its other static values). A bool ``interpret`` forces one way, for the
    callers that are handed one (``flash_attention``'s keyword)."""
    if interpret is not None:
        return call(*args, interpret=interpret)
    return lax.platform_dependent(
        *args, tpu=functools.partial(call, interpret=False),
        default=functools.partial(call, interpret=True))
