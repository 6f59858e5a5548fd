"""The timing protocol's arithmetic: blocks, rates per chip, quartiles."""

import pytest

import bench_paths  # noqa: F401
from harness import timing


class FakeLog:
    programs = 0


def test_block_rates_are_per_chip_whatever_the_mesh():
    """Every chip takes its own batch through each step: 16 x 1024 tokens a
    chip in 5 steps of 0.15 s are 109227 tokens a second on each chip, on
    one chip or on four. (PR 22's first four-chip runs divided by the chips
    once more and read 23% scaling where the device trace said 94%.)"""
    window = timing.Window(block_seconds=[0.75, 1.5])
    assert timing.block_rates(window, 16 * 1024, 5) == pytest.approx(
        [16 * 1024 * 5 / 0.75, 16 * 1024 * 5 / 1.5])


def test_run_window_counts_steps_blocks_and_dispatches():
    calls = []

    def call(state):
        calls.append(state)
        return state + 1, 0.5  # (new state, "loss")
    state, window = timing.run_window(call, 0, seconds=0.0, block_steps=5,
                                      compile_log=FakeLog(), min_blocks=3)
    assert state == 15 and calls == list(range(15))
    assert len(window.block_seconds) == 3 and window.steps == 15
    assert len(window.losses) == 15 and window.programs_compiled == 0
    assert window.ended - window.started >= sum(window.block_seconds) * 0.99


def test_a_program_compiled_in_the_window_is_counted():
    log = FakeLog()

    def call(state):
        log.programs += 1
        return state, 0.0
    _, window = timing.run_window(call, 0, 0.0, 2, log, min_blocks=1)
    assert window.programs_compiled == 2


@pytest.mark.parametrize("values,want", [
    ([3.0], (3.0, 3.0, 3.0)),
    ([1.0, 2.0, 3.0, 4.0, 5.0], (2.0, 3.0, 4.0)),
    ([4.0, 1.0, 3.0, 2.0], (1.75, 2.5, 3.25)),
])
def test_quartiles(values, want):
    assert timing.quartiles(values) == pytest.approx(want)
