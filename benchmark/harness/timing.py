"""The timing protocol, the same in every cell.

After warm-up the window runs *blocks* of ``block_steps`` steps dispatched
back to back, then waits for the block's last output, as a training loop
that reads its loss every few steps does. A block's rate is its items over
its host-clock seconds; the metric is the median of the block rates. The
losses of each block stay on the device until the window is over.
"""

from __future__ import annotations

import contextlib
import statistics
import time
from dataclasses import dataclass, field

import jax


@dataclass
class Window:
    """What one timed stretch measured."""
    block_seconds: list = field(default_factory=list)
    dispatch_seconds: list = field(default_factory=list)  # one a step call
    losses: list = field(default_factory=list)            # device scalars
    programs_compiled: int = 0
    started: float = 0.0   # host clock (perf_counter) of the first block
    ended: float = 0.0

    @property
    def steps(self) -> int:
        return len(self.dispatch_seconds)


def quartiles(values) -> tuple:
    """(first quartile, median, third quartile)."""
    values = sorted(values)
    if len(values) == 1:
        return values[0], values[0], values[0]
    q = statistics.quantiles(values, n=4, method="inclusive")
    return q[0], q[1], q[2]


def run_block(call, state, block_steps: int, window: Window,
              annotate=None):
    """One block: ``block_steps`` dispatches, then one wait. ``call(state)``
    dispatches a step and returns ``(new_state, loss)``. ``annotate(name)``
    gives a context manager for the traced run's host spans."""
    span = annotate or (lambda name: contextlib.nullcontext())
    with span("bench.block"):
        t0 = time.perf_counter()
        loss = None
        for _ in range(block_steps):
            with span("bench.dispatch"):
                d0 = time.perf_counter()
                state, loss = call(state)
                window.dispatch_seconds.append(time.perf_counter() - d0)
            window.losses.append(loss)
        with span("bench.sync"):
            jax.block_until_ready(loss)
        t1 = time.perf_counter()
    window.block_seconds.append(t1 - t0)
    return state


def run_window(call, state, seconds: float, block_steps: int, compile_log,
               min_blocks: int = 3) -> tuple:
    """Blocks until ``seconds`` have passed (and at least ``min_blocks``).
    Returns ``(state, Window)``."""
    window = Window()
    programs_before = compile_log.programs
    window.started = time.perf_counter()
    deadline = window.started + seconds
    while time.perf_counter() < deadline or \
            len(window.block_seconds) < min_blocks:
        state = run_block(call, state, block_steps, window)
    window.ended = time.perf_counter()
    window.programs_compiled = compile_log.programs - programs_before
    return state, window


def block_rates(window: Window, items_per_step_per_chip: float,
                block_steps: int) -> list:
    """Items per second per chip of each block: every chip of the mesh
    takes ``items_per_step_per_chip`` items through each step, so the mesh's
    size does not enter."""
    return [items_per_step_per_chip * block_steps / s
            for s in window.block_seconds]
