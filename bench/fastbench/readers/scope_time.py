"""Device seconds per unit of the ops whose kernel family
(``fastbench.scopes.family``) is in ``families``, or that are in no family
when ``families`` is None, as a mean over the devices. Read from the
trace's ``scopes`` ({device: [(start, end, scope path)]}, clipped like its
ops); nothing is read where no op carries a program scope."""
from __future__ import annotations

from fastbench import scopes as scopes_mod


def read(ctx, families):
    scopes = getattr(ctx.trace, "scopes", None) or {}
    if not any(scopes_mod.has_scope(p) for evs in scopes.values()
               for _, _, p in evs):
        return None
    return scopes_mod.seconds(scopes, families) / ctx.units, ""
