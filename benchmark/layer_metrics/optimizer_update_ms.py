"""Step compiler: device self time a step under ``phase_optimizer_update``
(``optimizer.update`` and ``optax.apply_updates``). None where the step names
no phase."""

from harness import phases


def read(trace, run):
    return phases.phase_ms(trace, run, "optimizer_update")
