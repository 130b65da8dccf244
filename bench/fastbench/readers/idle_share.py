"""Share of the traced window in which no op ran on the device's
TensorCore, averaged over the devices."""
from __future__ import annotations

from fastbench import trace


def read(ctx):
    t = ctx.trace
    return 100.0 * (1.0 - trace.mean_busy_s(t) / t.window_s), ""
