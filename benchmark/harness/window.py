"""The window kernels of ``ops/flash_attention.py``: their names, what a
call needs, and what a step's calls took.

A windowed ``flash_attention(..., window=W)`` compiles to kernel functions
of their own names (``_fwd_window_kernel``, ``_bwd_dq_window_kernel``,
``_bwd_dkv_window_kernel``), the roles of ``flops.FLASH_PRODUCTS``' three
under a mask of ``0 <= q_pos - k_pos < W``. ``harness/roofline.flash_share``
costs every call of the causal names at ``Job.flash_call``'s pair count and
multiplies by ``Job.flash_layers``; here a call costs the window's pairs
(``Job.facts["window_call"]``: batch, seq, heads, head_dim, window) and a
kernel's cost is multiplied by **the number of its calls the compiled step
holds** (``harness/kernels.inventory``), so a block recomputed in backward,
which runs its forward kernel twice a step, is costed twice and its share of
the roofline stays what the kernel achieves.

Where the step holds no window kernel (the parent's programs, every other
configuration) or the job states no ``window_call``, every reader returns
None and the metric is left out.
"""

from __future__ import annotations

from harness import flops, kernels, roofline, trace_reduce

# window kernel -> the causal kernel whose products and arrays it has
WINDOW_KERNELS = {"_fwd_window_kernel": "_fwd_kernel",
                  "_bwd_dq_window_kernel": "_bwd_dq_kernel",
                  "_bwd_dkv_window_kernel": "_bwd_dkv_kernel"}
BLOCK_VISITS = "hvd_flash_block_visits"
WINDOW_KINDS = ("window_interior", "window_diagonal", "window_edge",
                "window_skipped", "window_skipped_behind")
NEVER_LOADED = ("window_skipped", "window_skipped_behind")


def window_pairs(seq: int, window: int) -> int:
    """(query, key) pairs with ``0 <= q - k < window`` in one sequence: the
    first ``window`` queries see 1 .. window keys, every later one
    ``window``."""
    if window >= seq:
        return flops.attended_pairs(seq, True)
    return window * (window + 1) // 2 + (seq - window) * window


def window_kernel_cost(kernel: str, batch: int, seq: int, heads: int,
                       head_dim: int, window: int,
                       dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) one call of the window ``kernel`` needs: its
    role's products of ``flops.FLASH_PRODUCTS`` x 2 x head_dim over the
    window's pairs; the bytes of the causal call of that role (every array
    read and written once: a window skips blocks, it reads no row twice)."""
    role = WINDOW_KERNELS[kernel]
    pairs = batch * heads * window_pairs(seq, window)
    return (flops.FLASH_PRODUCTS[role] * 2 * head_dim * pairs,
            flops.flash_kernel_cost(role, batch, seq, heads, head_dim, True,
                                    dtype_bytes)[1])


def _kernel_of(run):
    def kernel(span):
        ins = run.hlo.get(span.name)
        if ins is None or not run.hlo.is_kernel(ins):
            return None
        name = run.hlo.kernel_name(ins)
        return name if name in WINDOW_KERNELS else None
    return kernel


def seconds_per_step(trace, run):
    """{window kernel: its device seconds in one step}; None without a
    device plane, a step run, or a window kernel in the trace."""
    if trace is None or not trace.devices:
        return None
    seconds = trace_reduce.op_seconds_by(trace, _kernel_of(run))
    steps = roofline.steps_traced(trace, run)
    if not seconds or not steps:
        return None
    return {name: spent / steps for name, spent in seconds.items()}


def share(trace, run, names):
    """100 x the least seconds the chip's peaks allow one step's calls of
    the window kernels ``names`` (each kernel's cost x the calls of it the
    compiled step holds) over their measured device seconds in one step."""
    call = run.job.facts.get("window_call")
    spent = seconds_per_step(trace, run)
    if call is None or spent is None:
        return None
    calls = kernels.inventory(run.hlo)
    least = took = 0.0
    for name in names:
        if name not in spent:
            continue
        least += calls[name] * flops.roofline_seconds(
            *window_kernel_cost(name, *call), run.peaks)[0]
        took += spent[name]
    return 100.0 * least / took if took else None


def time_share(trace, run):
    """Device time of the window kernels over the device's busy time."""
    if trace is None or not trace.devices:
        return None
    kernel = _kernel_of(run)
    seconds = trace_reduce.op_seconds_by(
        trace, lambda span: "window" if kernel(span) else "other")
    busy = sum(seconds.values())
    if not busy or "window" not in seconds:
        return None
    return 100.0 * seconds["window"] / busy


def blocks_skipped_share(trace, run):
    """Of the blocks of the window calls traced in this process (the
    program's counter ``hvd_flash_block_visits`` under its ``window_*``
    kinds, at trace time: ``flash_attention.block_plan`` x batch x heads),
    100 x those never loaded: wholly in the future or wholly behind the
    window. A ratio of counts that every trace of a call adds to alike.
    None where the program counts no window call, or on a trace without a
    device plane (a rehearsal's result line keeps its set of metrics)."""
    if trace is None or not trace.devices:
        return None
    from horovod_tpu import metrics
    snapshot = metrics.get_registry().snapshot()
    counts = {kind: metrics.snapshot_value(snapshot, BLOCK_VISITS, kind=kind)
              for kind in WINDOW_KINDS}
    total = sum(v for v in counts.values() if v)
    if not total:
        return None
    return 100.0 * sum(counts[k] or 0.0 for k in NEVER_LOADED) / total
