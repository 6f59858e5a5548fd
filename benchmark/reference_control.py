#!/usr/bin/env python3
"""Both readings a cell's ``Tolerance`` is set from, through ``run.py``'s own
comparison, in one process.

    python3 benchmark/reference_control.py --workload <name> --seeds 11,12,13

For each seed ``Cell.check_reference`` runs twice: on the cell's job (the
program against the float32 reference: sound, has to be ``ok``), and on
``control_job(job)`` of the configuration's module (the reference computed one
precision below the one the configuration states, in the program's place: has
to come out NOT ``ok`` by one of the cell's limits). One line a reading with
``compared`` as the result line of a run has it, and a last line with the
largest sound and the smallest control reading beside each limit. A limit
lies between the two. Needs the chip at the real size; ``--rehearse`` runs
the files' tiny sizes here, where the readings say nothing about the limits.

``check_reference`` builds its programs anew each call, so a seed after the
first loads them from the persistent compile cache: give the process a cache
that holds them all.
"""

from __future__ import annotations

import argparse

import run as bench  # benchmark/run.py: puts the checkout on sys.path
from harness import spec as spec_lib


def readings(cell, job, seed):
    """``compared`` and ``ok`` of one reference check of ``job``."""
    cell.job, cell.compared, cell.checks = job, {}, {}
    cell.key_params, cell.key_batch = bench.jax.random.split(
        bench.jax.random.key(seed))
    cell.check_reference(cell.global_batch())
    return dict(cell.compared), cell.checks["reference"]


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
    if not hasattr(module, "control_job"):
        bench.fail(f"{cell.cell['config']}.py has no control_job(job)")
    jobs = {"sound": cell.job, "control": module.control_job(cell.job)}
    found = {name: [] for name in jobs}
    for seed in seeds:
        for name, job in jobs.items():
            before = dict(cell.setup_marks)
            compared, ok = readings(cell, job, seed)
            found[name].append((compared, ok))
            bench.say(reading=name, seed=seed, ok=ok, compared=compared,
                      seconds={k: round(v - before.get(k, 0.0), 3)
                               for k, v in cell.setup_marks.items()},
                      compile_s=cell.log.seconds, cache_hits=cell.log.hits,
                      cache_misses=cell.log.misses)
    limits = {k: v[1] for k, v in found["sound"][0][0].items()}
    bench.say(
        workload=args.workload, seeds=seeds, rehearse=args.rehearse,
        limits=limits,
        sound_largest={k: max(c[k][0] for c, _ in found["sound"])
                       for k in limits},
        control_smallest={k: min(c[k][0] for c, _ in found["control"])
                          for k in limits},
        sound_all_ok=all(ok for _, ok in found["sound"]),
        control_none_ok=not any(ok for _, ok in found["control"]))


if __name__ == "__main__":
    main()
