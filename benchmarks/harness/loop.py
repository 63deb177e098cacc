"""The closed loop and its statistics."""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional


@dataclass
class Execute:
    """One execute of the window, as the client saw it."""
    wall_s: float
    result: Any = None            # what the system returned; None if it raised
    error: Optional[str] = None


@dataclass
class Window:
    elapsed_s: float = 0.0
    executes: List[Execute] = field(default_factory=list)

    @property
    def completed(self) -> List[Execute]:
        return [e for e in self.executes if e.error is None]


def nearest_rank(values: List[float], pct: float) -> float:
    """Nearest-rank percentile: the smallest value with at least pct % of
    the sample at or below it (the largest, for a small sample)."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(pct / 100.0 * len(ordered)) - 1)]


def closed_loop(execute_once: Callable[[], Any], seconds: float,
                annotate: Callable[[str], Any],
                observe: Optional[Callable[[Execute], None]] = None
                ) -> Window:
    """One client, back to back, until `seconds` have passed; the execute in
    flight then is finished and counted.  `annotate(name)` is a context
    manager that marks the host's timeline for the profiler; `observe` sees
    each execute after its clock has stopped (the traced run's readers)."""
    win = Window()
    start = time.perf_counter()
    while True:
        with annotate("execute"):
            t0 = time.perf_counter()
            try:
                ex = Execute(0.0, result=execute_once())
            except Exception as e:   # the loop must go on and count it
                ex = Execute(0.0, error=f"{type(e).__name__}: {e}")
            t1 = time.perf_counter()
        ex.wall_s = t1 - t0
        win.executes.append(ex)
        if observe is not None:
            observe(ex)
        if t1 - start >= seconds:
            win.elapsed_s = t1 - start
            return win
