"""Kernels: of a window call's grid of blocks, the share the kernels never
load (wholly in the future or wholly behind the window), from the program's
counter ``hvd_flash_block_visits`` (harness/window.py). None where the
program counts no window call."""

from harness import window


def read(trace, run):
    return window.blocks_skipped_share(trace, run)
