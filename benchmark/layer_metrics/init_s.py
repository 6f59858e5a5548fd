"""Entry and mesh: process start to ``hvd.mesh()`` (import, TPU start-up,
``hvd.init()``), on the host's clock."""


def read(trace, run):
    return run.init_s
