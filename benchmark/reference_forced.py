#!/usr/bin/env python3
"""A third reading beside ``reference_control.py``'s two, for a cell whose
sound reading is laid to routers' near-ties: the program against the float32
reference with every (token, slot) sent to the same expert on both sides.

    python3 benchmark/reference_forced.py --workload <name> --seeds 11,12,13

For each seed ``Cell.check_reference`` runs on
``forced_choices_job(job, sample)`` of the configuration's module: the
reference's own choices on the seed's sample sit in the state both sides
route from, and nothing else differs from a run's check. One line a seed with
``compared`` as the result line of a run has it, and a last line with the
largest reading beside each limit: what is left of a class's sound reading
here is rounding, the rest of it was the choices. Needs the chip at the real
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
    if not hasattr(module, "forced_choices_job"):
        bench.fail(f"{cell.cell['config']}.py has no "
                   "forced_choices_job(job, sample)")
    job, found = cell.job, []
    for seed in seeds:
        # the sample check_reference will cut from this seed's batch
        cell.key_params, cell.key_batch = bench.jax.random.split(
            bench.jax.random.key(seed))
        sample = bench.jax.tree_util.tree_map(
            lambda x: x[:job.sample_examples], cell.global_batch())
        compared, ok = reference_control.readings(
            cell, module.forced_choices_job(job, sample), seed)
        found.append(compared)
        bench.say(reading="forced", seed=seed, ok=ok, compared=compared)
    bench.say(workload=args.workload, seeds=seeds, rehearse=args.rehearse,
              limits={k: v[1] for k, v in found[0].items()},
              forced_largest={k: max(c[k][0] for c in found)
                              for k in found[0]})


if __name__ == "__main__":
    main()
