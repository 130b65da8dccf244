"""``roofline.py``'s share of a kernel's roofline, with the work of the
extra-MSA stack's calls counted in (``work_xmsa.KERNEL_WORK[calls]``).
Nothing is read where no op matching ``kernels`` ran."""
from __future__ import annotations

from fastbench import trace, work, work_xmsa


def read(ctx, kernels: str, calls: str):
    spent = trace.kernel_s(ctx.trace, kernels)
    if spent <= 0.0:
        return None
    work_calls = work_xmsa.KERNEL_WORK[calls](ctx.config, ctx.shapes,
                                              ctx.shapes.get("dap", 1))
    least, bound = work.least_time(work_calls, ctx.peak)
    return 100.0 * least * ctx.units / spent, f"bound: {bound}"
