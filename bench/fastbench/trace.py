"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

What a TPU trace holds, as recorded on a v5e with JAX 0.9: one plane per
chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per HLO
instruction that ran on the TensorCore, named by the instruction's text
(``%flash_attention_pallas.12 = bf16[...] custom-call(...)``: a Pallas
kernel is a custom call named after its ``pallas_call``), and whose line
``Async XLA Ops`` holds the asynchronous copies and collectives from start
to done. A ``while`` loop is one event spanning the ops of its body, which
follow it on the same line. The host plane ``/host:CPU`` has the Python thread, on which the
benchmark's ``jax.profiler.TraceAnnotation`` spans (``bench.*``) lie on the
same clock as the device events.

Everything is clipped to the benchmark's ``bench.window`` span.
"""
from __future__ import annotations

import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(r"^(all-to-all|all-gather|all-reduce|"
                        r"collective-permute|reduce-scatter)")
_OP = re.compile(r"^%?([^\s=]+)")
_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """``%layer_norm_pallas.384 = f32[...] custom-call(...)`` ->
    ``layer_norm_pallas``."""
    m = _OP.match(event_name)
    return _SUFFIX.sub("", m.group(1)) if m else event_name


@dataclasses.dataclass
class Trace:
    """Intervals in ns on the trace's clock. ``ops[d]``: (start, end, name)
    of device d's TensorCore ops; ``async_ops[d]``: the same for its
    asynchronous ops; ``host``: (start, end, name) of the bench spans."""

    window: tuple
    ops: dict
    async_ops: dict
    host: list

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def devices(self) -> list:
        return sorted(self.ops)


def _clip(events, lo, hi):
    out = []
    for s, e, n in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, n))
    return out


def load(path: str, device_ids=None) -> Trace:
    """The trace at ``path``, keeping the devices in ``device_ids`` (all
    when None)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, async_ops, host = {}, {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and (device_ids is None or int(m.group(1)) in device_ids):
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE):
                    evs = [(e.start_ns, e.start_ns + e.duration_ns,
                            op_name(e.name)) for e in line.events]
                    (ops if line.name == OPS_LINE else async_ops)[dev] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    return from_events(ops, async_ops, host)


def leaves(events) -> list:
    """The events that contain no other event. Control flow (``while``)
    shows as one event spanning its body's ops; it is left out, so that
    time is counted once, by the ops that ran."""
    events = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    container = [False] * len(events)
    stack = []
    for i, (s, e, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            container[stack[-1]] = True
        stack.append(i)
    return [ev for ev, c in zip(events, container) if not c]


def from_events(ops: dict, async_ops: dict, host: list) -> Trace:
    """A Trace from raw events, clipped to the ``bench.window`` span, with
    container ops (``leaves``) left out of the TensorCore's ops."""
    windows = [(s, e) for s, e, n in host if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    if not ops:
        raise ValueError("the trace holds no TPU device plane")
    return Trace(window=(lo, hi),
                 ops={d: _clip(leaves(v), lo, hi) for d, v in ops.items()},
                 async_ops={d: _clip(async_ops.get(d, []), lo, hi)
                            for d in ops},
                 host=sorted(_clip(host, lo, hi)))


def union(intervals) -> list:
    """Merged, sorted (start, end) pairs."""
    out = []
    for s, e in sorted((iv[0], iv[1]) for iv in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def subtract(a, b) -> float:
    """Length of merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def busy_s(t: Trace, dev) -> float:
    """Seconds in which an op ran on device ``dev``'s TensorCore."""
    return length(union(t.ops[dev])) * 1e-9


def mean_busy_s(t: Trace) -> float:
    return sum(busy_s(t, d) for d in t.devices) / len(t.devices)


def kernel_s(t: Trace, pattern: str) -> float:
    """Device seconds of the ops whose name matches ``pattern``, summed over
    the devices."""
    rx = re.compile(pattern)
    return sum(e - s for d in t.devices for s, e, n in t.ops[d]
               if rx.search(n)) * 1e-9


def exposed_collective_s(t: Trace, dev) -> float:
    """Seconds in which a collective (synchronous, or asynchronous from
    start to done) was in flight on ``dev`` and no other op ran there."""
    coll = union([iv for iv in t.ops[dev] + t.async_ops[dev]
                  if COLLECTIVE.match(iv[2])])
    compute = union([iv for iv in t.ops[dev] if not COLLECTIVE.match(iv[2])])
    return subtract(coll, compute) * 1e-9


def top_ops(t: Trace, n: int = 10) -> list:
    """[[op name, device seconds summed over devices]] of the n largest."""
    tot = {}
    for d in t.devices:
        for s, e, name in t.ops[d]:
            tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(t: Trace, n: int = 10) -> list:
    """[[what the host was doing, seconds]] of the n longest gaps between
    device ops in the window (device 0's, or the first device's), each named
    by the innermost bench span that covers its middle."""
    dev = t.devices[0]
    busy = union(t.ops[dev])
    edges = [t.window[0]] + [x for iv in busy for x in iv] + [t.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        cover = [h for h in t.host if h[0] <= mid <= h[1]]
        name = min(cover, key=lambda h: h[1] - h[0])[2] if cover else \
            "outside any bench span"
        out.append([name, (e - s) * 1e-9])
    return out
