"""Step compiler: programs compiled inside a step call that was not its
wrapper's first: the program's counter ``hvd_step_recompiles_total``, over
all frameworks. Must read 0, as ``programs_after_warmup`` does from
outside. None where the program has no such counter."""

from harness import program_compile_log


def read(trace, run):
    if program_compile_log.report(trace) is None:
        return None
    value = program_compile_log.recompiles()
    return None if value is None else float(value)
