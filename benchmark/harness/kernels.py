"""The kernels a cell's metrics read, and whether the compiled step holds
them.

``correct`` asks what the step computes (the float32 reference, the loss
after warm-up, the shards' mean) and not how many ``tpu_custom_call``s it
compiled to. One thing about the text it still asks: a cell whose job names
flash shapes (``Job.flash_call``) reports ``flash_*_roofline`` from the
kernels of ``flops.FLASH_PRODUCTS``, so each of those names is in the step at
least ``Job.flash_layers`` times; a step that silently took XLA attention
would otherwise report rooflines of kernels that never ran. And the other
way: a job that names no flash shapes says the router takes XLA attention,
so none of those names is in its step; one that took the kernels all the
same would run another program than the job's FLOPs and rooflines describe.
More calls than asked, kernels of other names and the compiler's own
``ragged-dot-*`` calls neither pass nor fail it. The same table feeds
``harness/roofline.py``.
"""

from __future__ import annotations

from collections import Counter

from harness import flops


def inventory(hlo) -> dict:
    """{name: count} of every ``tpu_custom_call`` of the step, by
    ``HloIndex.kernel_name``: a Pallas kernel under its function's name, the
    compiler's grouped matmuls as ``ragged-dot-none`` /
    ``ragged-dot-metadata``."""
    return dict(sorted(Counter(
        hlo.kernel_name(ins) for ins in hlo.kernels()).items()))


def required(job) -> dict:
    """{name: least count} of the kernels this job's metrics read; empty
    where the job names no flash shapes."""
    if job.flash_call is None:
        return {}
    return {name: job.flash_layers for name in flops.FLASH_PRODUCTS}


def missing(job, hlo) -> dict:
    """{name: [found, required]} for each required kernel the step holds too
    few of; empty where the step holds them all."""
    found = inventory(hlo)
    return {name: [found.get(name, 0), least]
            for name, least in required(job).items()
            if found.get(name, 0) < least}


def unasked(job, hlo) -> dict:
    """{name: found} for each flash kernel in a step whose job names no
    flash shapes; empty where the job names them, or the step holds none."""
    if job.flash_call is not None:
        return {}
    found = inventory(hlo)
    return {name: found[name] for name in flops.FLASH_PRODUCTS
            if name in found}
