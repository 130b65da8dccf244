"""Milliseconds per unit in which a collective was in flight on a device
and nothing else ran there, averaged over the devices. Nothing is read
where no collective ran."""
from __future__ import annotations

from fastbench import trace


def read(ctx):
    t = ctx.trace
    if not any(trace.COLLECTIVE.match(iv[2]) for d in t.devices
               for iv in t.ops[d] + t.async_ops[d]):
        return None
    exposed = sum(trace.exposed_collective_s(t, d) for d in t.devices)
    return 1e3 * exposed / len(t.devices) / ctx.units, ""
