#!/usr/bin/env python3
"""Run cells several times, each run a process of its own, and summarise.

    python3 benchmark/measure.py --workload gpt2s-t512 --runs 6 --seconds 20 \
        --seed0 100 [--traced] [--out chiprun_out/measure]

What a builder runs on the chip to set or check a bound: ``--runs`` runs of
``benchmark/run.py`` with ``--trace 0`` and seeds ``seed0, seed0+1, ...``,
then with ``--traced`` one run with ``--trace 1``. This process never
touches JAX, so each run has the chips to itself. Every run's output goes to
``<out>/<workload>.<seed>.log``, its result line to ``<out>/results.jsonl``,
and for each metric the median and the spread (the distance between the
quartiles over the median) are printed at the end.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from harness.timing import quartiles  # noqa: E402  (imports jax, opens no chip)


def one_run(workload, seed, seconds, trace, out, extra=()):
    command = [sys.executable, str(HERE / "run.py"), "--workload", workload,
               "--seed", str(seed), "--seconds", str(seconds),
               "--trace", str(trace), *extra]
    t0 = time.perf_counter()
    done = subprocess.run(command, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    tag = f"{workload}.{seed}" + (".traced" if trace else "")
    (out / f"{tag}.log").write_text(done.stdout + "\n--- stderr ---\n" +
                                    done.stderr[-20000:])
    lines = done.stdout.strip().splitlines()
    result = None
    if done.returncode == 0 and lines:
        result = json.loads(lines[-1])
    record = {"workload": workload, "seed": seed, "trace": trace,
              "rc": done.returncode, "wall_s": round(wall, 2),
              "result": result}
    with open(out / "results.jsonl", "a") as f:
        f.write(json.dumps(record) + "\n")
    brief = {k: v["value"] for k, v in (result or {}).get(
        "metrics", {}).items()}
    print(json.dumps({"run": tag, "rc": done.returncode,
                      "wall_s": round(wall, 1),
                      "correct": (result or {}).get("correct"), **brief}),
          flush=True)
    if done.returncode:
        print(done.stderr[-3000:], flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--traced", action="store_true")
    ap.add_argument("--keep-trace", action="store_true")
    ap.add_argument("--out", default="chiprun_out/measure")
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    for workload in args.workload:
        values = {}
        for i in range(args.runs):
            result = one_run(workload, args.seed0 + i, args.seconds, 0, out)
            if result is None:
                sys.exit(f"measure.py: run {i} of {workload} failed; "
                         "stopping here")
            for name, m in (result or {}).get("metrics", {}).items():
                values.setdefault(name, []).append(m["value"])
        for name, series in values.items():
            q1, median, q3 = quartiles(series)
            # the first run of a cell in a checkout compiles: its set-up is
            # recorded apart
            rest = series[1:] if name == "setup_s" and len(series) > 1 \
                else series
            r1, rmed, r3 = quartiles(rest)
            print(json.dumps({
                "summary": workload, "metric": name, "runs": len(series),
                "median": rmed, "spread": (r3 - r1) / rmed,
                "first": series[0], "values": series}), flush=True)
        if args.traced:
            extra = ["--keep-trace", str(out)] if args.keep_trace else []
            one_run(workload, args.seed0 + args.runs, args.seconds, 1, out,
                    extra)


if __name__ == "__main__":
    main()
