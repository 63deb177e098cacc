"""Per-layer metrics: each is a file `benchmarks/layer_metrics/<name>.json`
that names a source and what to read from it; the readers are here, one per
source.  A reader that finds nothing to read returns None, and the metric is
left out of the result's line."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


@dataclass
class Observed:
    """What the traced window left behind for the readers."""
    executes: int = 0
    # per completed execute: {span name: total ns} from the program's spans
    spans: List[Dict[str, float]] = field(default_factory=list)
    # per completed execute: {field: number} from the program's query record
    records: List[Dict[str, float]] = field(default_factory=list)
    # CompileLog snapshot after the window minus the one before it
    compiles: Dict[str, float] = field(default_factory=dict)
    trace: Dict[str, Any] = field(default_factory=dict)   # xtrace.reduce
    memory_peak_bytes: Optional[int] = None
    algorithmic_bytes: Optional[int] = None   # per execute, read + written
    hbm_bytes_per_s: Optional[float] = None   # one chip's published peak
    chips: int = 1


def _span(spec, ob: Observed):
    """Mean milliseconds per execute inside spans of one name."""
    found = [s[spec["span"]] for s in ob.spans if spec["span"] in s]
    if not found:
        return None
    return sum(found) / len(found) / 1e6


def _record(spec, ob: Observed):
    """Sum over the window's executes of the record's named fields."""
    if not ob.records:
        return None
    return sum(r.get(f, 0) for r in ob.records for f in spec["fields"])


def _compile_log(spec, ob: Observed):
    return ob.compiles.get(spec["field"])


def _device_s_per_execute(ob: Observed):
    if not ob.trace or not ob.executes:
        return None
    return ob.trace["busiest_busy_s"] / ob.executes


def _trace(spec, ob: Observed):
    per_execute = _device_s_per_execute(ob)
    if per_execute is None:
        return None
    what = spec["read"]
    if what == "device_ms_per_execute":
        return per_execute * 1e3
    if what == "idle_pct":
        return 100.0 * (1.0 - ob.trace["busiest_busy_s"]
                        / ob.trace["window_s"])
    if what == "hbm_roofline_pct":
        # the least seconds the chips could take for the query's
        # algorithmic bytes (bandwidth-bound: a scan-join-aggregate does a
        # few operations a byte), over the seconds the device was busy
        if not ob.algorithmic_bytes or not ob.hbm_bytes_per_s:
            return None
        least_s = ob.algorithmic_bytes / (ob.hbm_bytes_per_s * ob.chips)
        return 100.0 * least_s / per_execute
    raise ValueError(f"unknown trace reading {what!r}")


def _memory(spec, ob: Observed):
    if ob.memory_peak_bytes is None:
        return None
    return ob.memory_peak_bytes / 1e9


READERS = {"span": _span, "record": _record, "compile_log": _compile_log,
           "trace": _trace, "memory": _memory}


def read_metric(spec: Dict[str, Any], ob: Observed) -> Optional[float]:
    return READERS[spec["source"]](spec, ob)
