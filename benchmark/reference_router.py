#!/usr/bin/env python3
"""A fourth reading beside ``reference_control.py``'s two and
``reference_forced.py``'s: can a cell's ``correct`` see a router computed
below the float32 its configuration states?

    python3 benchmark/reference_router.py --workload <name> --seeds 11,12,13

For each seed ``Cell.check_reference`` runs on ``router_control_job(job)`` of
the configuration's module: the float32 reference with the routers' logits
alone one precision lower, in the program's place, and nothing else lowered.
One line a seed with ``compared`` as the result line of a run has it, and a
last line with the smallest reading beside each limit and whether any seed
came out not ``ok``: where none did, the cell's limits do not hold the
router's precision, and a test on the CPU has to. Needs the chip at the real
size; ``--rehearse`` runs the files' tiny sizes here.
"""

from __future__ import annotations

import argparse

import reference_control
import run as bench  # benchmark/run.py: puts the checkout on sys.path
from harness import spec as spec_lib


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True,
                        help="comma-separated whole numbers")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    args.seed = seeds[0]
    cell = bench.Cell(args)
    module = spec_lib.load_module(spec_lib.config(
        cell.spec, cell.cell["config"], args.rehearse)[1])
    if not hasattr(module, "router_control_job"):
        bench.fail(f"{cell.cell['config']}.py has no router_control_job(job)")
    job, found = module.router_control_job(cell.job), []
    for seed in seeds:
        compared, ok = reference_control.readings(cell, job, seed)
        found.append((compared, ok))
        bench.say(reading="router", seed=seed, ok=ok, compared=compared)
    bench.say(workload=args.workload, seeds=seeds, rehearse=args.rehearse,
              limits={k: v[1] for k, v in found[0][0].items()},
              router_smallest={k: min(c[k][0] for c, _ in found)
                               for k in found[0][0]},
              router_largest={k: max(c[k][0] for c, _ in found)
                              for k in found[0][0]},
              router_none_ok=not any(ok for _, ok in found))


if __name__ == "__main__":
    main()
