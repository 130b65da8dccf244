"""A kernel's share of its roofline: the least time the chip needs for the
kernel's work in the traced window (``work.KERNEL_WORK[work]``, each call
bound by its flops over the bf16 peak or its bytes over HBM bandwidth,
whichever is larger), over the device time of the ops whose names match
``kernels``, both summed over the devices. Nothing is read where no such
op ran."""
from __future__ import annotations

from fastbench import trace, work as work_mod


def read(ctx, kernels: str, work: str):
    spent = trace.kernel_s(ctx.trace, kernels)
    if spent <= 0.0:
        return None
    calls = work_mod.KERNEL_WORK[work](ctx.config, ctx.shapes,
                                       ctx.shapes.get("dap", 1))
    least, bound = work_mod.least_time(calls, ctx.peak)
    return 100.0 * least * ctx.units / spent, f"bound: {bound}"
