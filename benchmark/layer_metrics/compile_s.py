"""Step compiler: seconds XLA spent building programs or loading them from
the persistent cache, summed over the run (``jax.monitoring``)."""


def read(trace, run):
    return run.compile_s
