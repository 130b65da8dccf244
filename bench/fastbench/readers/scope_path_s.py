"""Device seconds per unit of the ops whose program scope path holds the
named scope (``alphafold.extra_msa_stack``, ...) as a whole component, as a
mean over the devices. Read from the scope paths that the cell's mode, named
by the metric file, kept from its traced window (``traced_scopes()`` of
``fastbench.modes.<mode>``: the harness's ``Trace`` carries no scopes),
clipped to the trace's window as its ops are; nothing is read where the
mode kept none or no op carries the scope."""
from __future__ import annotations

import importlib
import re

from fastbench import scopes as scopes_mod


def read(ctx, scope: str, mode: str):
    kept = importlib.import_module("fastbench.modes." + mode).traced_scopes()
    if not kept:
        return None
    within = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/)]|$)")
    clipped = scopes_mod.clip(kept, ctx.trace.window)
    total = sum(e - s for evs in clipped.values() for s, e, p in evs
                if within.search(p))
    if total <= 0:
        return None
    return total * 1e-9 / len(clipped) / ctx.units, ""
