"""Model FLOP utilization: the model's flops per unit, counted from shapes
(``work.MODEL_FLOPS[model_flops]``, no recompute), times the units in the
traced window, over the window's length times the chips times the bf16
peak."""
from __future__ import annotations

from fastbench import work


def read(ctx, model_flops: str):
    flops = work.MODEL_FLOPS[model_flops](ctx.config, ctx.shapes) * ctx.units
    peak = ctx.trace.window_s * ctx.chips * ctx.peak["bf16_flops_per_s"]
    return 100.0 * flops / peak, "bound: compute (bf16 peak)"
