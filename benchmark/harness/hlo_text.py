"""What the compiled step's text says: instructions by name, their kind,
their scope, the kernels and the collectives.

The device trace names each event after an HLO instruction (``fusion.12``,
``all-reduce-start.1``). The text of the same executable says what each is:
a fusion around a convolution, a Pallas kernel, a collective and the
``hvd_*`` scope it was written under. Nothing here depends on the profiler's
own statistics.
"""

from __future__ import annotations

import base64
import binascii
import math
import re
from typing import NamedTuple, Optional

DTYPE_BYTES = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
               "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
               "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
               "c64": 8, "c128": 16}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
MXU_OPCODES = ("convolution", "dot")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_ARRAY = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")
_SCOPE = re.compile(r"(hvd_[A-Za-z0-9_]+)")


class Instruction(NamedTuple):
    name: str
    opcode: str
    shape: str          # the result's shape, as printed
    computation: str
    calls: tuple        # computations it calls (a fusion's body, ...)
    op_name: str        # metadata: the jax scopes it was traced under
    attributes: str     # the rest of the line


def _split_shape(rest: str) -> tuple:
    """(shape, remainder) of what follows ``=``: a tuple shape is balanced
    parentheses, an array shape runs to the first space."""
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += c == "("
            depth -= c == ")"
            if depth == 0:
                return rest[:i + 1], rest[i + 1:].lstrip()
    shape, _, remainder = rest.partition(" ")
    return shape, remainder


def shape_bytes(shape: str) -> int:
    """Bytes of every array in a printed shape (a tuple's are summed)."""
    total = 0
    for dtype, dims in _ARRAY.findall(shape):
        if dtype in DTYPE_BYTES:
            total += DTYPE_BYTES[dtype] * math.prod(
                int(d) for d in dims.split(",") if d)
    return total


class HloIndex:
    def __init__(self, text: str):
        self.instructions = {}
        self.bodies = {}
        self._kernel_names_cache = None
        module = re.match(r"HloModule\s+([\w.\-]+)", text)
        self.module = module.group(1) if module else ""
        computation = None
        for line in text.splitlines():
            head = _COMPUTATION.match(line)
            if head and "=" not in line.split("(")[0]:
                computation = head.group(1)
                self.bodies[computation] = []
                continue
            if line.startswith("}"):
                computation = None
                continue
            found = _INSTRUCTION.match(line) if computation else None
            if not found:
                continue
            shape, rest = _split_shape(found.group(2))
            opcode = rest.split("(", 1)[0].strip()
            op_name = re.search(r'op_name="([^"]*)"', rest)
            calls = tuple(re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", rest))
            instruction = Instruction(
                found.group(1), opcode, shape, computation, calls,
                op_name.group(1) if op_name else "", rest)
            self.instructions[instruction.name] = instruction
            self.bodies[computation].append(instruction)

    def get(self, event_name: str) -> Optional[Instruction]:
        """The instruction a trace event is named after (the trace may
        print a leading ``%``)."""
        return self.instructions.get(event_name.lstrip("%"))

    # -- kinds ---------------------------------------------------------------

    def is_kernel(self, ins: Instruction) -> bool:
        return ins.opcode == "custom-call" and \
            'custom_call_target="tpu_custom_call"' in ins.attributes

    def kernels(self) -> list:
        return [i for i in self.instructions.values() if self.is_kernel(i)]

    def kernel_name(self, ins: Instruction) -> str:
        """The kernel function's name (``_fwd_kernel``). The text holds each
        kernel as Mosaic bytecode, whose strings name the functions it was
        traced from; helpers cached from an earlier trace carry that one's
        names too, so of the ``*_kernel`` names in a body the one found in
        the fewest bodies of the module is the kernel's own. Falls back to
        the instruction's last scope, and for a call the compiler made
        itself, whose ``op_name`` is one word (``ragged-dot-none``), to
        that word: the same for every such call of the step."""
        names = self._kernel_names()
        own = names.get(ins.name, ())
        if not own:
            if "/" in ins.op_name:
                return ins.op_name.rsplit("/", 2)[-2]
            return ins.op_name or re.sub(r"\.\d+$", "", ins.name)
        spread = {}
        for found in names.values():
            for name in set(found):
                spread[name] = spread.get(name, 0) + 1
        return min(own, key=lambda name: (spread[name], own.index(name)))

    def _kernel_names(self) -> dict:
        if self._kernel_names_cache is None:
            self._kernel_names_cache = {}
            for ins in self.kernels():
                body = re.search(r'"body":"([^"]+)"', ins.attributes)
                try:
                    raw = base64.b64decode(body.group(1)) if body else b""
                except (binascii.Error, ValueError):
                    raw = b""
                self._kernel_names_cache[ins.name] = [
                    n.decode() for n in re.findall(rb"\b\w*_kernel\b", raw)]
        return self._kernel_names_cache

    def is_collective(self, ins: Instruction) -> bool:
        return ins.opcode.removesuffix("-start").removesuffix("-done") \
            in COLLECTIVES

    def collectives(self) -> list:
        """Each collective once: the plain instruction, or the ``-start`` of
        an asynchronous pair."""
        return [i for i in self.instructions.values()
                if self.is_collective(i) and not i.opcode.endswith("-done")]

    def is_mxu(self, ins: Instruction) -> bool:
        """A convolution or dot, alone or inside a fusion (the TPU compiler
        turns most dots into convolutions)."""
        if ins.opcode in MXU_OPCODES:
            return True
        return ins.opcode == "fusion" and any(
            inner.opcode in MXU_OPCODES
            for body in ins.calls for inner in self.bodies.get(body, ()))

    def category(self, ins: Instruction) -> str:
        if self.is_kernel(ins):
            return "pallas kernel"
        if self.is_collective(ins):
            return "collective"
        if self.is_mxu(ins):
            return "convolution/dot fusion"
        if ins.opcode == "fusion":
            kind = re.search(r"kind=k(\w+)", ins.attributes)
            return f"{kind.group(1).lower() if kind else 'other'} fusion"
        return ins.opcode

    def scope(self, ins: Instruction) -> Optional[str]:
        """The ``hvd_*`` named scope the instruction was traced under."""
        found = _SCOPE.search(ins.op_name)
        return found.group(1) if found else None

    # -- counts that repeat exactly -------------------------------------------

    def collective_payload(self) -> dict:
        """{opcode: [count, bytes]} over the step's collectives: the bytes of
        each result, which for an all-reduce are the bytes reduced."""
        out = {}
        for ins in self.collectives():
            kind = ins.opcode.removesuffix("-start")
            shape = ins.shape
            if ins.opcode.endswith("-start") and shape.startswith("("):
                # an async start's result is (operands, results, ...): take
                # the results' half
                arrays = _ARRAY.findall(shape)
                half = arrays[len(arrays) // 2:] if len(arrays) > 1 else arrays
                shape = " ".join(f"{d}[{dims}]" for d, dims in half)
            entry = out.setdefault(kind, [0, 0])
            entry[0] += 1
            entry[1] += shape_bytes(shape)
        return out
