"""Kernel rooflines and collective time per step: what several per-layer
readers share."""

from __future__ import annotations

import re
import statistics

from harness import flops, trace_reduce

FLASH_KERNELS = tuple(flops.FLASH_PRODUCTS)  # the one table of their names


def steps_traced(trace, run) -> int:
    """Runs of the step program on one chip in the traced stretch."""
    return len(trace_reduce.step_runs(trace.devices[0], run.program))


def flash_share(trace, run, kernels):
    """100 x least seconds for one step's calls of ``kernels`` (by the
    chip's peaks) over their measured device seconds in one step. None
    where the step runs no such kernel."""
    if trace is None or not trace.devices or run.job.flash_call is None:
        return None

    def kernel(span):
        ins = run.hlo.get(span.name)
        if ins is None or not run.hlo.is_kernel(ins):
            return None
        name = run.hlo.kernel_name(ins)
        return name if name in kernels else None
    seconds = trace_reduce.op_seconds_by(trace, kernel)
    steps = steps_traced(trace, run)
    if not seconds or not steps:
        return None
    least = 0.0
    for name in seconds:
        cost = flops.flash_kernel_cost(name, *run.job.flash_call)
        least += run.job.flash_layers * \
            flops.roofline_seconds(*cost, run.peaks)[0]
    return 100.0 * least / (sum(seconds.values()) / steps)


def flash_bound(run) -> dict:
    """Which peak bounds each kernel at this cell's shapes."""
    if run.job.flash_call is None:
        return {}
    return {name: flops.roofline_seconds(
        *flops.flash_kernel_cost(name, *run.job.flash_call), run.peaks)[1]
        for name in FLASH_KERNELS}


def collective_seconds_per_step(trace, run):
    """(seconds a step spends with a collective in flight, the part of it
    with no compute operation running), averaged over the chips. None on a
    trace without devices; (0, 0) where the step has no collective."""
    if trace is None or not trace.devices:
        return None
    hlo = run.hlo

    def is_collective(name):
        ins = hlo.get(name)
        return ins is not None and hlo.is_collective(ins)

    def pair_of(name):
        # the -done half names its -start as its operand
        ins = hlo.get(name)
        if ins.opcode.endswith("-start"):
            return ins.name
        if ins.opcode.endswith("-done"):
            operand = re.search(r"%([\w.\-]+)\)*\s*(?:,|$)",
                                ins.attributes.split("(", 1)[1])
            return operand.group(1) if operand else ins.name
        return None

    in_flight, bare = [], []
    for device in trace.devices:
        ops = trace_reduce.inside_steps(device, run.program)
        scoped = trace_reduce.DeviceTrace(device.ordinal, ops, device.modules)
        collective = trace_reduce.collective_intervals(
            scoped, is_collective, pair_of)
        compute = [(s.start, s.end) for s in ops if not is_collective(s.name)]
        steps = len(trace_reduce.step_runs(device, run.program))
        if not steps:
            return None
        in_flight.append(trace_reduce.total(collective) / steps / 1e9)
        bare.append(trace_reduce.total(
            trace_reduce.exposed(collective, compute)) / steps / 1e9)
    return statistics.fmean(in_flight), statistics.fmean(bare)
