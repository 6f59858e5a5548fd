"""The step's own phases as device time: what the program's ``phase_*``
scopes say about each operation of the trace.

``parallel/dp.py`` and ``parallel/zero.py`` write the parts of the training
step under ``jax.named_scope("phase_<name>")`` (``profiler/annotate.py``), and
the compiler keeps the scope in each instruction's ``op_name``:

    jit(_local_step)/shard_map/phase_grad_exchange/hvd_allreduce_average/psum
    jit(_local_step)/phase_forward_backward/transpose(jvp(GptDecoder))/EncoderBlock_3/Dense_0/dot_general

A backward instruction carries ``transpose(`` (a ``custom_vjp``'s backward,
the flash kernels', as
``phase_forward_backward/transpose(phase_forward_backward)/jvp(...)``); flax
writes the module path.
The compiler also makes operations that carry no scope (``copy-start``,
``slice-done``, its own custom calls and loop fusions, the in-place
``dynamic-update-slice`` of a packed buffer). Such an operation **inherits**
the phase (and direction) of the latest earlier operation of the same step
run on that chip that has one; before the first it counts as forward. The
seconds so inherited are kept apart, so a reader can say how much of a
phase's number rests on the rule.

The compiler fuses across the phases' borders: a weight gradient's matmul
may take the optimizer's update of that weight into its epilogue. A fusion
counts under its own ``op_name`` (its root's), whole; the seconds of fusions
whose bodies also hold instructions of another phase are printed apart
(``fused_across_ms``), so a reader sees how much of one phase rides in
another's number.

Where the step's text holds no ``phase_*`` scope at all (a program from
before the scopes) there is nothing to read: ``seconds_per_step`` returns
``None`` and the metrics are left out.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass, field

from harness import trace_reduce

PHASE = re.compile(r"phase_([a-z_]+)")
SPLIT = "forward_backward"          # reported as "forward" and "backward"
FORWARD, BACKWARD = "forward", "backward"
LOSS_BLOCK = "loss"                 # under the phase, outside the model
# the model's scope as jax's transforms print it: jvp(GptDecoder)
_MODEL = re.compile(r"^(?:\w+\()*([A-Z]\w*)\)*$")
_INDEX = re.compile(r"_\d+(?=$|[./])")
BLOCK_DEPTH = 2                     # EncoderBlock/FlashSelfAttention
BLOCKS_PRINTED = 12


def phase_of(ins):
    """The ``phase_<name>`` the instruction was traced under, else None."""
    found = PHASE.search(ins.op_name) if ins is not None else None
    return found.group(1) if found else None


def direction(ins) -> str:
    """``backward`` for what the transposition of the forward pass made."""
    return BACKWARD if "transpose(" in ins.op_name else FORWARD


def block_of(ins):
    """The flax module path below the model, indices stripped
    (``EncoderBlock/FlashSelfAttention``, ``Embed.attend``), the model's own
    name for what it does outside any submodule, ``loss`` for what lies
    under ``phase_forward_backward`` outside the model. None outside that
    phase."""
    if phase_of(ins) != SPLIT:
        return None
    # a custom_vjp's backward (the flash kernels) is written under
    # phase_forward_backward/transpose(phase_forward_backward)/jvp(Model)/...
    below = ins.op_name.rsplit("phase_" + SPLIT, 1)[1].split("/")[1:]
    model = _MODEL.match(below[0]) if below else None
    if not model:
        return LOSS_BLOCK
    modules = []
    for part in below[1:]:
        if not part[:1].isupper() or len(modules) == BLOCK_DEPTH:
            break
        modules.append(_INDEX.sub("", part))
    return "/".join(modules) or model.group(1)


def label(phase, way) -> str:
    return way if phase == SPLIT else phase


def has_phases(hlo) -> bool:
    return any(PHASE.search(i.op_name) for i in hlo.instructions.values())


@dataclass
class Reduction:
    """Device seconds of the traced stretch's step runs, by phase."""
    # label -> seconds a step, averaged over the chips
    seconds: dict = field(default_factory=dict)
    # the part of ``seconds`` the inheritance rule assigned
    inherited: dict = field(default_factory=dict)
    # block -> [forward s, backward s]
    blocks: dict = field(default_factory=dict)
    # "backward+optimizer_update" -> s in fusions that hold both
    fused_across: dict = field(default_factory=dict)
    busy: float = 0.0   # union of the operations inside step runs, a step
    steps: int = 0      # step runs on one chip

    @property
    def total(self) -> float:
        return sum(self.seconds.values())


class PerTrace:
    """What was made for the latest trace asked about: a run has one trace
    and several readers, each of which asks."""

    def __init__(self):
        self.trace = self.value = None

    def get(self, trace, make):
        if self.trace is not trace:
            self.value, self.trace = make(), trace
        return self.value


def guests(hlo, ins) -> tuple:
    """The other phases whose instructions a fusion's body holds."""
    own = phase_of(ins)
    inside = {phase_of(i) for body in ins.calls
              for i in hlo.bodies.get(body, ())}
    return tuple(sorted(inside - {own, None}))


def reduce(trace, hlo, program) -> Reduction:
    out = Reduction()
    guests_of = {}  # instruction name -> guests(), looked up once
    chips = len(trace.devices)
    for device in trace.devices:
        runs = trace_reduce.step_runs(device, program)
        out.steps = max(out.steps, len(runs))
        if not runs:
            continue
        share = 1.0 / (len(runs) * chips)
        ops = trace_reduce.inside_steps(device, program)
        out.busy += share * trace_reduce.total(trace_reduce.union(
            (s.start, s.end) for s in ops)) / 1e9
        timed = sorted(trace_reduce.self_seconds(ops),
                       key=lambda pair: (pair[0].start, -pair[0].end))
        run, latest = 0, (SPLIT, FORWARD)
        for span, seconds in timed:
            while run + 1 < len(runs) and span.start >= runs[run].end:
                # the next step run begins afresh
                run, latest = run + 1, (SPLIT, FORWARD)
            ins = hlo.get(span.name)
            phase = phase_of(ins)
            if phase is None:
                phase, way = latest
                name = label(phase, way)
                out.inherited[name] = out.inherited.get(name, 0.0) + \
                    share * seconds
            else:
                way = direction(ins)
                latest = (phase, way)
                name = label(phase, way)
                block = block_of(ins)
                if block is not None:
                    pair = out.blocks.setdefault(block, [0.0, 0.0])
                    pair[way == BACKWARD] += share * seconds
                if ins.name not in guests_of:
                    guests_of[ins.name] = guests(hlo, ins)
                if guests_of[ins.name]:
                    key = "+".join((name,) + guests_of[ins.name])
                    out.fused_across[key] = out.fused_across.get(
                        key, 0.0) + share * seconds
            out.seconds[name] = out.seconds.get(name, 0.0) + share * seconds
    return out


_REDUCED = PerTrace()


def reduced(trace, run):
    """The reduction of this run's trace, made once; None where there is no
    device plane or the step's text names no phase. The first use prints the
    earlier line ``phases_ms``."""
    if trace is None or not trace.devices:
        return None

    def make():
        if not has_phases(run.hlo):
            return None
        found = reduce(trace, run.hlo, run.program)
        if not found.steps:
            return None
        say(found)
        return found
    return _REDUCED.get(trace, make)


def say(found: Reduction):
    def ms(table):
        return {k: 1e3 * v for k, v in sorted(table.items())}
    largest = sorted(found.blocks.items(), key=lambda kv: -sum(kv[1]))
    print(json.dumps({
        "phases_ms": ms(found.seconds), "inherited_ms": ms(found.inherited),
        "fused_across_ms": ms(found.fused_across),
        "phases_total_ms": 1e3 * found.total,
        "busy_in_steps_ms": 1e3 * found.busy, "steps_traced": found.steps,
        "blocks_ms": [[block, 1e3 * fwd, 1e3 * bwd]
                      for block, (fwd, bwd) in largest[:BLOCKS_PRINTED]]}),
        flush=True)


def seconds_per_step(trace, run):
    """{phase, or ``forward``/``backward`` for ``forward_backward``: device
    self seconds a step}, averaged over the chips and the step runs of the
    traced stretch. The parts add up to the busy time inside step runs."""
    found = reduced(trace, run)
    return None if found is None else dict(found.seconds)


def phase_ms(trace, run, name):
    """One phase in milliseconds a step; 0 where the step has the scopes
    and ran nothing under this one."""
    seconds = seconds_per_step(trace, run)
    return None if seconds is None else 1e3 * seconds.get(name, 0.0)


def inherited_share(trace, run):
    """100 x the busy time inside step runs whose phase was inherited over
    all of it."""
    found = reduced(trace, run)
    if found is None or not found.total:
        return None
    return 100.0 * sum(found.inherited.values()) / found.total
