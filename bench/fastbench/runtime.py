"""What every mode shares: host spans on the profiler's clock, a counter of
compilations, the closed-loop window, the traced window and the device's
memory peak."""
from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def span(name: str):
    """A host span, ``bench.<name>``, written into the profiler's trace when
    one is being taken (a no-op otherwise)."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


class CompileCounter:
    """Counts backend compilations (cache reads included) from the moment
    it is armed."""

    def __init__(self):
        import jax

        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if self.armed and event == COMPILE_EVENT:
            self.count += 1


def closed_loop(dispatch, finish, seconds: float):
    """Units back to back, one always queued behind the one running, until
    ``seconds`` have passed; the unit in flight then finishes. ``dispatch(k)``
    enqueues unit k and returns its handle, ``finish(handle)`` waits for it.
    Returns (window seconds, units completed)."""
    t0 = time.perf_counter()
    with span("dispatch"):
        pending = dispatch(0)
    k = 1
    while True:
        with span("dispatch"):
            nxt = dispatch(k)
        with span("fetch"):
            finish(pending)
        k += 1
        if time.perf_counter() - t0 >= seconds:
            with span("fetch"):
                finish(nxt)
            break
        pending = nxt
    return time.perf_counter() - t0, k


def traced(dispatch, finish, units: int):
    """``units`` units back to back under the profiler. Returns the path of
    the ``.xplane.pb`` it wrote and a cleanup callable."""
    import jax

    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(tdir)
    try:
        with span("window"):
            handles = []
            for k in range(units):
                with span("dispatch"):
                    handles.append(dispatch(k))
            for h in handles:
                with span("fetch"):
                    finish(h)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    return files[0], lambda: shutil.rmtree(tdir, ignore_errors=True)


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``, as the runtime's
    allocator reports it (its whole report goes to standard error)."""
    import sys

    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"bench: memory_stats device {d.id}: {stats}", file=sys.stderr)
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def free(*trees) -> None:
    """Delete every device buffer in the trees now, not when Python gets to
    it."""
    import jax

    for t in trees:
        for x in jax.tree.leaves(t):
            if isinstance(x, jax.Array) and not x.is_deleted():
                x.delete()

