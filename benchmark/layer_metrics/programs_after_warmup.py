"""Step compiler: programs built inside the timed windows. Must read 0: a
step that recompiles is not in steady state."""


def read(trace, run):
    return float(run.programs_after_warmup)
