"""Per-layer metric readers, found by the name a metric file gives. Each
module has ``read(ctx, **params)``, which returns ``(value, note)`` or
``None`` where the trace holds nothing for it to read; ``ctx`` is a
``readers.Context``."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Context:
    trace: object      # fastbench.trace.Trace of the traced window
    units: int         # train steps or folds in the traced window
    chips: int
    peak: dict         # fastbench.peaks entry of the device
    config: dict       # the configuration file
    shapes: dict       # the traffic file (n_res, n_seq, batch, dap)
