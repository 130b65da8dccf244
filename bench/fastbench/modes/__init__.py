"""What a cell does, by the ``mode`` its traffic file names: ``train``
(optimizer steps) or ``fold`` (inference with recycling). Each module has
``run(ctx) -> Outcome``."""
from __future__ import annotations

import dataclasses
from typing import Callable


@dataclasses.dataclass
class RunContext:
    seed: int
    seconds: float
    trace: bool
    cell: object             # manifest.Cell
    devices: list            # the devices the cell may use
    t0: float                # time.perf_counter() at process start
    counter: object          # runtime.CompileCounter


@dataclasses.dataclass
class Outcome:
    setup_s: float
    attempted: int                       # units in the window
    failed: int                          # units that gave no finite result
    numbers: dict                        # the numbers compared, by name
    memory_peak_bytes: int
    per_unit_s: float | None = None      # window / units (untraced runs)
    trace_file: str | None = None        # traced runs
    traced_units: int = 0
    cleanup: Callable = lambda: None
