"""Profiling / performance-accounting subsystem.

The reference's perf methodology is timeline-driven (HOROVOD_TIMELINE,
reference: horovod/common/timeline.cc, docs/timeline.rst): you can't fix what
you can't attribute. This package is the TPU-native version of that story,
split into layers (FLOP counts, peaks and MFU are the benchmark's:
``benchmark/harness/flops.py`` and ``peaks.json``):

- :mod:`~horovod_tpu.profiler.annotate` — ``jax.named_scope`` wrapping for
  in-jit collectives (shows up as HLO op metadata in device traces) and
  ``jax.profiler.TraceAnnotation`` wrapping for host-side engine negotiation
  (shows up in the JAX host trace). jax-optional: the annotations degrade to
  no-ops so the torch/TF frontends can import this without pulling in JAX.
- :mod:`~horovod_tpu.profiler.trace_merge` — the bridge that merges the C++
  engine timeline (engine/src/timeline.cc, Chrome-trace JSON) with a JAX
  profiler trace into ONE Perfetto-loadable view: engine negotiation lanes
  beside device activity.

Import is lazy (PEP 562) so ``horovod_tpu.profiler.annotate`` stays usable
from jax-free processes.
"""

from __future__ import annotations

_SUBMODULE_EXPORTS = {
    # annotate
    "collective_scope": "annotate",
    "host_annotation": "annotate",
    # trace_merge
    "load_engine_timeline": "trace_merge",
    "find_jax_trace": "trace_merge",
    "merge_traces": "trace_merge",
    # flight (post-mortem analyzer over flight-recorder dumps)
    "load_dumps": "flight",
    "analyze_flight_dumps": "flight",
}

__all__ = sorted(_SUBMODULE_EXPORTS) + [
    "annotate", "flight", "trace_merge",
]


def __getattr__(name):
    import importlib
    if name in ("annotate", "flight", "trace_merge"):
        return importlib.import_module(f"{__name__}.{name}")
    mod = _SUBMODULE_EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(f"{__name__}.{mod}"), name)
