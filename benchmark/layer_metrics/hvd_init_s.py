"""Entry and mesh: the program's own share of ``init_s``: the span
``hvd.import`` (the package's import) plus the span ``hvd.init`` of the
program's compile log; the rest of ``init_s`` is Python's and JAX's imports
and the runtime's start. None where the program keeps no such span."""

from harness import program_compile_log


def read(trace, run):
    found = program_compile_log.report(trace)
    own = found["program_spans"] if found else {}
    if "hvd.init" not in own:
        return None
    return own.get("hvd.import", 0.0) + own["hvd.init"]
