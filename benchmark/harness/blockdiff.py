"""The block-diffusion kernels of ``ops/flash_attention.py``: their names,
what a call needs, and what a step's calls took.

``blockdiff_attention`` runs the clean stream's queries under
``block_mask=(G, "le")`` and the noised stream's under ``(G, "lt")``, both
over the clean stream's keys, as kernel functions of their own names
(``_fwd_blockdiff_kernel``, ``_bwd_dq_blockdiff_kernel``,
``_bwd_dkv_blockdiff_kernel``), the roles of ``flops.FLASH_PRODUCTS``' three
under a causal edge rounded to blocks of ``G`` positions. The two calls of a
role share one name, so a call is costed at their mean: ``seq^2 / 2`` pairs a
head (``seq (seq + G) / 2`` under ``"le"``, ``seq (seq - G) / 2`` under
``"lt"``), and the bytes of a causal call of that role (each call reads its
queries, the clean keys and values once, and writes its output once: the
keys are read once a call, which is twice a layer, because the two streams'
queries are two calls). ``Job.facts["blockdiff_call"]`` states the shapes:
batch, seq, heads, head_dim, G.

A kernel's cost is multiplied by **the number of times it ran** in the
traced stretch, counted on the device's own line and not in the compiled
text: right whether the layers are written out or one scanned body that the
text holds once; a block recomputed in backward runs its forward kernel twice
a step and is costed twice, so a share of the roofline stays what the kernel
achieves.

Where the step holds no such kernel (the parent's programs, every other
configuration) or the job states no ``blockdiff_call``, every reader returns
None and the metric is left out.
"""

from __future__ import annotations

from harness import flops, trace_reduce

# block-diffusion kernel -> the causal kernel whose products and arrays it has
BLOCKDIFF_KERNELS = {"_fwd_blockdiff_kernel": "_fwd_kernel",
                     "_bwd_dq_blockdiff_kernel": "_bwd_dq_kernel",
                     "_bwd_dkv_blockdiff_kernel": "_bwd_dkv_kernel"}
BLOCK_VISITS = "hvd_flash_block_visits"
BLOCKDIFF_KINDS = ("blockdiff_interior", "blockdiff_diagonal",
                   "blockdiff_skipped", "blockdiff_noised_keys")
NEVER_LOADED = ("blockdiff_skipped", "blockdiff_noised_keys")


def call_pairs(seq: int, block: int, edge: str) -> int:
    """(query, key) pairs of one head under ``b(k) <= b(q)`` (``"le"``) or
    ``b(k) < b(q)`` (``"lt"``), ``b(i) = i // block``."""
    blocks = seq // block
    return block * block * blocks * (blocks + (1 if edge == "le" else -1)) \
        // 2


def blockdiff_kernel_cost(kernel: str, batch: int, seq: int, heads: int,
                          head_dim: int, block: int,
                          dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) one call of ``kernel`` needs, the mean of its
    ``"le"`` and its ``"lt"`` call: the role's products of
    ``flops.FLASH_PRODUCTS`` x 2 x head_dim over the pairs; the bytes of
    the causal call of that role."""
    role = BLOCKDIFF_KERNELS[kernel]
    pairs = batch * heads * (call_pairs(seq, block, "le")
                             + call_pairs(seq, block, "lt")) / 2
    return (flops.FLASH_PRODUCTS[role] * 2 * head_dim * pairs,
            flops.flash_kernel_cost(role, batch, seq, heads, head_dim, True,
                                    dtype_bytes)[1])


def _kernel_of(run):
    def kernel(span):
        ins = run.hlo.get(span.name)
        if ins is None or not run.hlo.is_kernel(ins):
            return None
        name = run.hlo.kernel_name(ins)
        return name if name in BLOCKDIFF_KERNELS else None
    return kernel


def runs_and_seconds(trace, run):
    """{kernel: (times it ran, its device seconds)} over the traced stretch,
    averaged over the chips; None without a device plane or without a
    block-diffusion kernel in the trace."""
    if trace is None or not trace.devices:
        return None
    kernel = _kernel_of(run)
    lo, hi = trace_reduce.stretch(trace)
    found = {}
    for device in trace.devices:
        for span, seconds in trace_reduce.self_seconds(device.ops):
            if span.end <= lo or span.start >= hi:
                continue
            name = kernel(span)
            if name is not None:
                ran, spent = found.get(name, (0, 0.0))
                found[name] = (ran + 1, spent + seconds)
    chips = len(trace.devices)
    return {name: (ran / chips, spent / chips)
            for name, (ran, spent) in found.items()} or None


def share(trace, run, names):
    """100 x the least seconds the chip's peaks allow the traced calls of
    the kernels ``names`` (each kernel's cost x the times it ran) over
    their measured device seconds."""
    call = run.job.facts.get("blockdiff_call")
    found = runs_and_seconds(trace, run)
    if call is None or found is None:
        return None
    least = took = 0.0
    for name in names:
        if name not in found:
            continue
        ran, spent = found[name]
        least += ran * flops.roofline_seconds(
            *blockdiff_kernel_cost(name, *call), run.peaks)[0]
        took += spent
    return 100.0 * least / took if took else None


def time_share(trace, run):
    """Device time of the block-diffusion kernels over the device's busy
    time."""
    if trace is None or not trace.devices:
        return None
    kernel = _kernel_of(run)
    seconds = trace_reduce.op_seconds_by(
        trace, lambda span: "blockdiff" if kernel(span) else "other")
    busy = sum(seconds.values())
    if not busy or "blockdiff" not in seconds:
        return None
    return 100.0 * seconds["blockdiff"] / busy


def blocks_skipped_share(trace, run):
    """Of the tiles of the ``[2 seq, 2 seq]`` grids of the block-diffusion
    calls traced in this process (the program's counter
    ``hvd_flash_block_visits`` under its ``blockdiff_*`` kinds, at trace
    time: ``flash_attention.blockdiff_block_plan`` x batch x heads), 100 x
    those no kernel ever loads: wholly past the rounded causal edge, or in
    the two quadrants whose keys are the noised stream's. A ratio of counts
    that every trace of a call adds to alike. None where the program counts
    no such call, or on a trace without a device plane (a rehearsal's result
    line keeps its set of metrics)."""
    if trace is None or not trace.devices:
        return None
    from horovod_tpu import metrics
    snapshot = metrics.get_registry().snapshot()
    counts = {kind: metrics.snapshot_value(snapshot, BLOCK_VISITS, kind=kind)
              for kind in BLOCKDIFF_KINDS}
    total = sum(v for v in counts.values() if v)
    if not total:
        return None
    return 100.0 * sum(counts[k] or 0.0 for k in NEVER_LOADED) / total
