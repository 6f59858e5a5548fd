"""Step compiler: how often JAX traced a step function at top level in the
run (``hvd.compile.trace`` spans of what ``dp._jit_step`` jitted that no
other trace encloses). One a step program is the least; the jit's look-up
of a trace made ahead of time counts as one more. None where the program
keeps no compile log."""

from harness import program_compile_log


def read(trace, run):
    found = program_compile_log.report(trace)
    return float(found["step"]["traces"]) if found else None
