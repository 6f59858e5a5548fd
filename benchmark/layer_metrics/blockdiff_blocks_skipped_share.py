"""Kernels: of the [2L, 2L] grid of tiles of a block-diffusion pass, the
share no kernel ever loads (past the rounded causal edge, or under the noised
stream's keys), from the program's counter ``hvd_flash_block_visits``
(harness/blockdiff.py). None where the program counts no such call."""

from harness import blockdiff


def read(trace, run):
    return blockdiff.blocks_skipped_share(trace, run)
