"""Model FLOP utilization of a fold with the extra-MSA stack: its flops
per unit, counted from shapes (``work_xmsa.MODEL_FLOPS[flops]``, the extra
stack's global attention as what it computes), times the units in the
traced window, over the window's length times the chips times the bf16
peak."""
from __future__ import annotations

from fastbench import work_xmsa


def read(ctx, flops: str):
    done = work_xmsa.MODEL_FLOPS[flops](ctx.config, ctx.shapes) * ctx.units
    peak = ctx.trace.window_s * ctx.chips * ctx.peak["bf16_flops_per_s"]
    return 100.0 * done / peak, "bound: compute (bf16 peak)"
