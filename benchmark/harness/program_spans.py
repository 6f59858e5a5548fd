"""The program's own host spans, on the profiler's clock.

``metrics.timed_step`` (the wrapper around every step a user gets from
``dp.make_*train_step``) writes an ``hvd.step`` span around all it does for
one call, with the call's number as ``step_num``, and inside it an
``hvd.step.dispatch`` span around the wrapped jit call. ``jax.profiler``
puts them into the same ``.xplane.pb`` as the device planes: one clock.

``trace_reduce.from_profile`` keeps the ``bench.*`` spans only and
``harness.job.Run`` carries no path, so this file finds the profile itself:
the newest one under ``benchmark/.trace/`` whose ``bench.*`` spans are the
reduced trace's own. A profile of a program from before the spans holds no
``hvd.*`` span: ``load_for`` then returns an empty list and the readers
return ``None``.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
from pathlib import Path
from typing import NamedTuple

from harness import trace_reduce
from harness.phases import PerTrace

ROOT = Path(__file__).resolve().parents[1] / ".trace"
PREFIX = "hvd."
STEP = "hvd.step"
DISPATCH = "hvd.step.dispatch"
BLOCK = "bench.block"           # the benchmark's own span, one a block
# the innermost span labels a gap, as trace_reduce.HOST_PRIORITY does
PRIORITY = (DISPATCH, STEP)
OUTSIDE = "outside hvd.step"


class ProgramSpan(NamedTuple):
    name: str
    start: float
    end: float
    step_num: int | None = None

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


def host_events(profile, prefix):
    """The events of the host planes whose name starts with ``prefix``: the
    device planes, most of a profile, are not walked."""
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefix):
                        yield e


def from_profile(profile) -> list:
    """The ``hvd.*`` spans of the host planes of a
    ``jax.profiler.ProfileData``, by start."""
    out = []
    for e in host_events(profile, PREFIX):
        number = dict(e.stats).get("step_num") if e.name == STEP else None
        out.append(ProgramSpan(e.name, e.start_ns, e.start_ns + e.duration_ns,
                               None if number is None else int(number)))
    return sorted(out, key=lambda s: (s.start, -s.end))


def profiles(root) -> list:
    """Every profile under ``root``/<workload>/, newest first."""
    found = glob.glob(os.path.join(
        str(root), "*", "plugins", "profile", "*", "*.xplane.pb"))
    return sorted(found, key=os.path.getmtime, reverse=True)


def first_block(trace):
    """Start of the trace's first ``bench.block`` span, or None."""
    return min((s.start for s in trace.host if s.name == BLOCK),
               default=None)


_LOADED = PerTrace()


def load_for(trace, root=ROOT) -> list:
    """The ``hvd.*`` host spans of the profile ``trace`` was reduced from:
    the newest ``.xplane.pb`` under ``root``/*/ whose first ``bench.block``
    starts where ``trace``'s does (two runs side by side never read each
    other's file). Empty where no profile matches. Kept for the trace."""
    def find():
        from jax.profiler import ProfileData
        want = first_block(trace)
        for path in profiles(root) if want is not None else ():
            profile = ProfileData.from_file(path)
            if min((e.start_ns for e in host_events(profile, BLOCK)),
                   default=None) == want:
                return from_profile(profile)
        return []
    return _LOADED.get(trace, find)


def steps_in_stretch(trace, spans) -> list:
    """The ``hvd.step`` spans that lie inside the traced stretch."""
    lo, hi = trace_reduce.stretch(trace)
    return [s for s in spans
            if s.name == STEP and s.start >= lo and s.end <= hi]


def wrapper_self_seconds(trace, spans) -> list:
    """For each ``hvd.step`` span of the traced stretch, its duration less
    the ``hvd.step.dispatch`` span it contains: what the wrapper itself
    costs a call (attributor, registry, flight marks, the two spans)."""
    dispatches = [s for s in spans if s.name == DISPATCH]
    out = []
    for step in steps_in_stretch(trace, spans):
        held = sum(d.end - d.start for d in dispatches
                   if inside(d, [step]))
        out.append((step.end - step.start - held) / 1e9)
    return out


def split_by(intervals, cover) -> tuple:
    """(the parts of ``intervals`` that ``cover`` covers, the parts it does
    not), both sorted and disjoint, in one sweep over the two: a chip's
    idle gaps are tens of thousands, and ``trace_reduce.intersect`` takes
    their number squared."""
    cover = trace_reduce.union(cover)
    inside, outside, at = [], [], 0
    for start, end in trace_reduce.union(intervals):
        while at < len(cover) and cover[at][1] <= start:
            at += 1
        here = at
        while start < end:
            if here == len(cover) or cover[here][0] >= end:
                outside.append((start, end))
                break
            c_start, c_end = cover[here]
            if c_start > start:
                outside.append((start, c_start))
            inside.append((max(start, c_start), min(end, c_end)))
            start, here = min(end, c_end), here + 1
    return inside, outside


def idle_by_program_span(trace, spans) -> dict:
    """``trace_reduce.idle_by_host_span``'s arithmetic with the program's
    spans: {label: idle seconds, averaged over the chips}, every idle moment
    of the traced stretch under the innermost ``hvd.*`` span that covers
    it, or ``outside hvd.step``."""
    lo, hi = trace_reduce.stretch(trace)
    out = {}
    for device in trace.devices:
        idle = trace_reduce.gaps(trace_reduce.busy(device, lo, hi), lo, hi)
        for name in PRIORITY:
            part, idle = split_by(
                idle, [(s.start, s.end) for s in spans if s.name == name])
            if part:
                out[name] = out.get(name, 0.0) + \
                    trace_reduce.total(part) / 1e9
        if idle:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + \
                trace_reduce.total(idle) / 1e9
    return {k: v / len(trace.devices) for k, v in out.items()}


_SAID = PerTrace()


def spans_for(trace) -> list:
    """``load_for`` for a reader: empty where the trace has no device plane
    (the CPU's rehearsal) or the program writes no span. The first use in a
    run prints the earlier line ``program_spans``."""
    if trace is None or not trace.devices:
        return []
    spans = load_for(trace)
    if spans:
        _SAID.get(trace, lambda: print(json.dumps(
            {"program_spans": facts(trace, spans)}), flush=True))
    return spans


def inside(span, covers) -> bool:
    return any(c.start <= span.start and span.end <= c.end for c in covers)


def facts(trace, spans) -> dict:
    """What the traced stretch holds of the program's spans: one ``hvd.step``
    a step with rising ``step_num``, each holding one dispatch span and lying
    inside the benchmark's own ``bench.dispatch`` span of that step."""
    steps = steps_in_stretch(trace, spans)
    numbers = [s.step_num for s in steps]
    dispatches = [s for s in spans if s.name == DISPATCH and
                  inside(s, steps)]
    bench = [s for s in trace.host if s.name == "bench.dispatch"]
    own = sorted(wrapper_self_seconds(trace, spans))
    return {
        STEP: len(steps), DISPATCH: len(dispatches),
        "step_num_first_last": numbers[:1] + numbers[-1:],
        "step_nums_rise_by_one": numbers == list(range(
            numbers[0], numbers[0] + len(numbers))) if numbers else None,
        "inside_a_bench_dispatch": sum(inside(s, bench) for s in steps),
        "wrapper_self_ms_min_median_max": [
            1e3 * x for x in (own[0], statistics.median(own), own[-1])]
        if own else None,
        "dispatch_ms_median": 1e3 * statistics.median(
            s.seconds for s in dispatches) if dispatches else None,
        "idle_by_program_span_s": idle_by_program_span(trace, spans)}
