"""Instruction bundles of a compiled TPU kernel's loops, with no chip.

The TPU compiler that is installed beside JAX compiles for a described
device (``tests/test_tpu_compile.py``) and, asked through
``LIBTPU_INIT_ARGS``, writes every program's final VLIW bundles as text.
A Pallas kernel's time on the v5e follows the bundles of its loop bodies
(``PERF.md`` §6, PR 27: 0.79-0.96 ns a bundle over eleven versions of the
flash kernels), so counting them ranks two versions of a kernel before
either has seen the chip, and the operations in them say what fills the
loop: register spills (``vld``/``vst`` of ``_spill`` slots), MXU pushes,
pops, cross-lane work.

    LIBTPU_INIT_ARGS="$(python -m horovod_tpu.profiler.kernel_bundles --flags DIR)" \\
        JAX_PLATFORMS=cpu python my_compile_for_a_described_v5e.py
    python -m horovod_tpu.profiler.kernel_bundles DIR

A count is not a time: it ranks versions of one kernel and is never written
under the name of a device metric.
"""

from __future__ import annotations

import argparse
import collections
import re
from pathlib import Path
from typing import Dict, List, NamedTuple

# "  0x1a7 LB: >> { ins ;; ins }": address, an optional control-target key
# (LB = loop body), one '>' a loop level, the bundle's instructions
_BUNDLE = re.compile(r"\s*(?:0x)?[0-9a-f]+\s+([A-Z]{2})?:\s*(>*)\s*\{(.*)\}")
_OPCODE = re.compile(r"=\s*([a-z]\w*)")


def dump_flags(directory) -> str:
    """``LIBTPU_INIT_ARGS`` that make the TPU compiler write its final
    bundles under ``directory``. Set before JAX loads the library."""
    return f"--xla_jf_dump_to={directory} --xla_jf_dump_llo_text=true"


class Loop(NamedTuple):
    """One region of a program: ``depth`` 0 is straight-line code, 1 a loop
    (of a Pallas kernel: one grid step), 2 a loop inside it; ``index``
    counts the loops of that depth in program order."""
    depth: int
    index: int
    bundles: int
    ops: Dict[str, int]   # opcode stem -> count; spills as vld_spill/vst_spill


def loops(text: str) -> List[Loop]:
    """The loops of one ``*final_bundles.txt``."""
    index: Dict[int, int] = collections.defaultdict(int)
    found: Dict[tuple, list] = {}
    for line in text.splitlines():
        m = _BUNDLE.match(line)
        if not m:
            continue
        key, depth = m.group(1), len(m.group(2))
        if key == "LB" and depth:
            index[depth] += 1
        region = found.setdefault((depth, index[depth] if depth else 0),
                                  [0, collections.Counter()])
        region[0] += 1
        for ins in m.group(3).split(";;"):
            op = _OPCODE.search(ins)
            if not op:
                continue
            stem = op.group(1)
            if stem in ("vld", "vst") and "_spill" in ins:
                stem += "_spill"
            region[1][stem] += 1
    return [Loop(depth, i, n, dict(ops))
            for (depth, i), (n, ops) in sorted(found.items())]


def programs(directory) -> List[Path]:
    """The final bundle files under a dump directory, largest first: a
    kernel dwarfs the copies and element-wise programs around it."""
    files = [p for p in Path(directory).glob("*final_bundles.txt")
             if "schedule-analysis" not in p.name]
    return sorted(files, key=lambda p: -p.stat().st_size)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("directory")
    ap.add_argument("--flags", action="store_true",
                    help="print the LIBTPU_INIT_ARGS that dump to DIRECTORY")
    ap.add_argument("--top", type=int, default=3, help="programs to show")
    args = ap.parse_args(argv)
    if args.flags:
        print(dump_flags(args.directory))
        return 0
    for path in programs(args.directory)[:args.top]:
        print(path.name)
        for loop in loops(path.read_text(errors="replace")):
            top = sorted(loop.ops.items(), key=lambda kv: -kv[1])[:10]
            print(f"  depth {loop.depth} #{loop.index}: {loop.bundles} "
                  "bundles  " + " ".join(f"{k}={v}" for k, v in top))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
