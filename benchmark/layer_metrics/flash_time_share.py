"""Kernels: device time of the flash kernels (the names of
``flops.FLASH_PRODUCTS``) over the device's busy time. Reads 0 where the
router takes XLA attention; the compiler's ``ragged-dot`` calls and any
other ``tpu_custom_call`` are no part of it."""

from harness import flops, trace_reduce


def read(trace, run):
    if trace is None or not trace.devices:
        return None

    def kind(span):
        ins = run.hlo.get(span.name)
        flash = ins is not None and run.hlo.is_kernel(ins) and \
            run.hlo.kernel_name(ins) in flops.FLASH_PRODUCTS
        return "flash" if flash else "other"
    seconds = trace_reduce.op_seconds_by(trace, kind)
    busy = sum(seconds.values())
    return 100.0 * seconds.get("flash", 0.0) / busy if busy else None
