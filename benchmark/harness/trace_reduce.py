"""From a profiler trace to numbers: the reduction the yardstick owns.

``load()`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with
``jax.profiler.ProfileData`` and nothing else, into plain spans: the
operations and the programs each chip ran, and the benchmark's own host
spans (``bench.block``, ``bench.dispatch``, ``bench.sync``). Everything
after that is interval arithmetic on those spans, checked on hand-built
traces in ``tests/benchmark``. All times are nanoseconds on the trace's
clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from dataclasses import dataclass, field
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
# the ops line names an event by the instruction's whole text:
# "%fusion.12 = bf16[...] fusion(...)"; the instruction's name is what the
# compiled text indexes it by
INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")
# the innermost span labels a gap; a gap no span covers fell between blocks
HOST_PRIORITY = ("bench.sync", "bench.dispatch", "bench.block")
BETWEEN_BLOCKS = "between blocks"


class Span(NamedTuple):
    name: str
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


@dataclass
class DeviceTrace:
    ordinal: int
    ops: list = field(default_factory=list)      # XLA Ops line
    modules: list = field(default_factory=list)  # XLA Modules line


@dataclass
class Trace:
    devices: list = field(default_factory=list)
    host: list = field(default_factory=list)     # bench.* spans


# -- reading -------------------------------------------------------------------

def newest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def instruction_name(event_name: str) -> str:
    found = INSTRUCTION.match(event_name)
    return found.group(1) if found else event_name


def from_profile(profile) -> Trace:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    trace = Trace()
    for plane in profile.planes:
        device = DEVICE_PLANE.match(plane.name)
        if device:
            dev = DeviceTrace(int(device.group(1)))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops.extend(
                        Span(instruction_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns) for e in line.events)
                elif line.name == MODULES_LINE:
                    dev.modules.extend(
                        Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
            dev.ops.sort(key=lambda s: (s.start, -s.end))
            dev.modules.sort(key=lambda s: s.start)
            trace.devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.host.extend(
                    Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(HOST_PREFIX))
    trace.devices.sort(key=lambda d: d.ordinal)
    trace.host.sort(key=lambda s: s.start)
    return trace


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


# -- interval arithmetic -------------------------------------------------------

def union(intervals) -> list:
    """Sorted, disjoint (start, end) pairs covering the same points."""
    merged = []
    for start, end in sorted((s, e) for s, e in intervals if e > s):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(intervals, cover) -> list:
    """The parts of ``intervals`` that ``cover`` does not cover."""
    cover = union(cover)
    out = []
    for start, end in union(intervals):
        at = start
        for c_start, c_end in cover:
            if c_end <= at:
                continue
            if c_start >= end:
                break
            if c_start > at:
                out.append((at, c_start))
            at = max(at, c_end)
        if at < end:
            out.append((at, end))
    return out


def gaps(intervals, lo: float, hi: float) -> list:
    """What is left of [lo, hi] outside ``intervals``."""
    return subtract([(lo, hi)], intervals)


def self_seconds(ops) -> list:
    """(span, seconds not covered by a span nested inside it) for each
    operation: a ``while`` or a ``call`` holds the operations of its body."""
    ops = sorted(ops, key=lambda s: (s.start, -s.end))
    out, stack = [], []  # stack of [span, children's ns]

    def close(until):
        while stack and stack[-1][0].end <= until:
            span, inner = stack.pop()
            out.append((span, (span.end - span.start - inner) / 1e9))
            if stack:
                stack[-1][1] += span.end - span.start
    for span in ops:
        close(span.start)
        stack.append([span, 0.0])
    close(float("inf"))
    return out


# -- the traced stretch --------------------------------------------------------

def stretch(trace: Trace) -> tuple:
    """(start, end) of the traced stretch: from the first ``bench.block``'s
    start to the last one's end, on the host's clock, which the device
    planes share."""
    blocks = [s for s in trace.host if s.name == "bench.block"]
    if not blocks:
        raise ValueError("the trace holds no bench.block span: it is not "
                         "the benchmark's")
    return blocks[0].start, max(s.end for s in blocks)


def busy(device: DeviceTrace, lo: float, hi: float) -> list:
    """The union of the device's operation intervals inside [lo, hi]."""
    return clip(union((s.start, s.end) for s in device.ops), lo, hi)


def busy_and_window_seconds(trace: Trace) -> tuple:
    """(seconds an operation ran, averaged over the chips; seconds of the
    traced stretch)."""
    lo, hi = stretch(trace)
    per_chip = [total(busy(d, lo, hi)) for d in trace.devices]
    return statistics.fmean(per_chip) / 1e9, (hi - lo) / 1e9


def intersect(intervals, cover) -> list:
    """The parts of ``intervals`` that ``cover`` covers."""
    return subtract(intervals, subtract(intervals, cover))


def idle_by_host_span(trace: Trace) -> dict:
    """{label: idle seconds, averaged over the chips}: every idle moment of
    the traced stretch under the innermost ``bench.*`` span of the host that
    covers it, or ``between blocks`` where none does."""
    lo, hi = stretch(trace)
    out = {}
    for device in trace.devices:
        idle = gaps(busy(device, lo, hi), lo, hi)
        for name in HOST_PRIORITY:
            spans = [(s.start, s.end) for s in trace.host if s.name == name]
            part = intersect(idle, spans)
            idle = subtract(idle, spans)
            if part:
                out[name] = out.get(name, 0.0) + total(part) / 1e9
        if idle:
            out[BETWEEN_BLOCKS] = out.get(BETWEEN_BLOCKS, 0.0) + \
                total(idle) / 1e9
    return {k: v / len(trace.devices) for k, v in out.items()}


# -- the step program ----------------------------------------------------------

def step_runs(device: DeviceTrace, program: str) -> list:
    """The runs of the step program on this chip: the events of the modules
    line whose name starts with the compiled module's name."""
    return [s for s in device.modules if s.name.startswith(program)]


def median_step_seconds(trace: Trace, program: str) -> float:
    """Median device time of the step program, over chips and runs."""
    runs = [s.seconds for d in trace.devices for s in step_runs(d, program)]
    if not runs:
        raise ValueError(f"no run of {program!r} on the modules lines")
    return statistics.median(runs)


def launch_gaps_seconds(trace: Trace, program: str, block_steps: int) -> list:
    """Gaps on the device between two runs of the step program inside a
    block: every ``block_steps``-th gap spans a wait of the host and is left
    out."""
    out = []
    for device in trace.devices:
        runs = step_runs(device, program)
        for i in range(len(runs) - 1):
            if (i + 1) % block_steps:
                out.append(max(0.0, runs[i + 1].start - runs[i].end) / 1e9)
    return out


def inside_steps(device: DeviceTrace, program: str) -> list:
    """The chip's operations that ran inside a run of the step program."""
    runs = step_runs(device, program)  # sorted by start, disjoint
    starts = [r.start for r in runs]
    out = []
    for op in device.ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.end <= runs[i].end:
            out.append(op)
    return out


def op_seconds_by(trace: Trace, key) -> dict:
    """{key(span): self seconds, averaged over the chips} over every
    operation of the traced stretch. ``key`` may return None to skip."""
    lo, hi = stretch(trace)
    out = {}
    for device in trace.devices:
        for span, seconds in self_seconds(device.ops):
            if span.end <= lo or span.start >= hi:
                continue
            k = key(span)
            if k is not None:
                out[k] = out.get(k, 0.0) + seconds
    return {k: v / len(trace.devices) for k, v in out.items()}


# -- collectives ---------------------------------------------------------------

def collective_intervals(device: DeviceTrace, is_collective,
                         pair_of=None) -> list:
    """The intervals in which a collective is in flight on this chip. A
    plain collective is its own span. An asynchronous one is in flight from
    its ``-start``'s start to its ``-done``'s end: ``pair_of(name)`` gives a
    key shared by the two halves, or None for a plain one."""
    out, open_starts = [], {}
    for op in device.ops:
        if not is_collective(op.name):
            continue
        key = pair_of(op.name) if pair_of else None
        if key is None:
            out.append((op.start, op.end))
        elif key not in open_starts:
            open_starts[key] = op.start
        else:
            out.append((open_starts.pop(key), op.end))
    return union(out)


def exposed(collective, compute) -> list:
    """The parts of the collective intervals in which no compute operation
    runs on that chip."""
    return subtract(collective, compute)
