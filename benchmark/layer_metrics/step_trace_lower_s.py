"""Step compiler: self seconds of the ``hvd.compile.trace`` and
``hvd.compile.lower`` spans of the step functions (what ``dp._jit_step``
jitted) and of all they enclose, each nested function counted once: the
Python side of building the step programs, which ``compile_s`` does not
count. None where the program keeps no compile log."""

from harness import program_compile_log


def read(trace, run):
    found = program_compile_log.report(trace)
    return found["step"]["trace_lower_s"] if found else None
