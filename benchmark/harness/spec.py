"""``BENCHMARK.json`` and the files it names.

Whatever belongs to one configuration, one traffic mix or one per-layer
metric is a file of its own, found here by its name: a later PR adds files
and appends entries, and edits nothing that is there.

    configs/<config>.json   sizes, source, dtype policy, optimizer
    configs/<config>.py     build(config, traffic) -> harness.job.Job
    traffic/<mix>.json      batch, lengths, chips, step keyword arguments
    layer_metrics/<m>.py    read(trace, run) -> number or None
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]   # the benchmark's directory
ROOT = BENCH.parent                            # the checkout


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names does not hold together."""


def load(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def _named(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SpecError(f"BENCHMARK.json has no {what} named {name!r} "
                    f"(it has {[e['name'] for e in entries]})")


def workload(spec: dict, name: str) -> dict:
    return _named(spec["workloads"], name, "workload")


def with_rehearsal(data: dict, rehearse: bool) -> dict:
    """The file's values, with its ``rehearse`` block laid over them for the
    sandbox's tiny run."""
    out = {k: v for k, v in data.items() if k != "rehearse"}
    if rehearse:
        out.update(data.get("rehearse", {}))
    return out


def config(spec: dict, name: str, rehearse: bool = False):
    """(values, path of the builder module) of a configuration."""
    entry = _named(spec["configs"], name, "configuration")
    path = ROOT / entry["file"]
    return (with_rehearsal(json.loads(path.read_text()), rehearse),
            path.with_suffix(".py"))


def traffic(name: str, rehearse: bool = False) -> dict:
    path = BENCH / "traffic" / f"{name}.json"
    if not path.exists():
        raise SpecError(f"no traffic mix {path.relative_to(ROOT)}")
    return with_rehearsal(json.loads(path.read_text()), rehearse)


def load_module(path: Path):
    """Import one file by its path: names with a hyphen are fine."""
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in path.stem)
    module_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def metrics(spec: dict, kind: str, workload_name: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this workload reports:
    those that name it under ``workloads``, and those that name none."""
    return [m for m in spec[kind]
            if workload_name in m.get("workloads", [workload_name])]


def layer_reader(name: str):
    """The reader of a per-layer metric. A name may carry a suffix after a
    dot (``mfu_device.images``): one reader then serves several entries of
    ``BENCHMARK.json`` that move different end-to-end metrics."""
    path = BENCH / "layer_metrics" / f"{name.split('.')[0]}.py"
    if not path.exists():
        raise SpecError(f"no reader {path.relative_to(ROOT)} for the "
                        f"per-layer metric {name!r}")
    return load_module(path).read
