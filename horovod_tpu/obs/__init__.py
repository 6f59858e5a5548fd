"""Live observability: step-time attribution and the ``hvd-top`` view.

The third layer of the observability stack, and the one that finally
*consumes* the signals the other two produce:

- the PR-3 **monitoring** layer (``horovod_tpu/metrics``) counts and
  exports — counters, gauges, histograms, the per-worker ``/metrics``
  endpoint;
- the PR-5 **post-mortem** layer (flight recorder + analyzer) explains
  failures after the fact;
- this **attribution** layer answers "where did my step go" while the job
  is alive: per-step compute / exposed-comm / negotiation-stall / host
  decomposition (:mod:`horovod_tpu.obs.attribution`), rolling step-time
  anomaly detection with automatic flight dumps, and the ``hvd-top``
  cluster view (:mod:`horovod_tpu.obs.top`).
"""

from __future__ import annotations

from horovod_tpu.obs.attribution import (  # noqa: F401
    StepAttributor,
    attribute,
    decompose_rank,
    get_attributor,
    step_windows,
)
