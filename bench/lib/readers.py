"""Arithmetic the per-layer metric readers share.

A reader gets the run's record: `spans` (harness spans, host clock),
`counters` (the program's byte and pass counters over the window, and
`units`: the epochs or requests the cell's end-to-end rate is per), `trace`
(the reduced profiler trace of the traced part, or None), `traced` (the
algorithm's work in that part: its SpMM passes as (n_rows, n_cols, nnz, F)
and its model operations) and `peaks`. A reader that finds nothing to read
returns None, and the harness leaves the metric out.
"""
from __future__ import annotations

from typing import Optional

from bench.lib import work
from bench.lib.peaks import Peaks


def _traced(record: dict):
    trace, traced = record.get("trace"), record.get("traced")
    if not trace or not traced or trace["window_s"] <= 0:
        return None, None
    return trace, traced


def kernel_roofline(record: dict, kernel: str) -> Optional[float]:
    """% of the kernel's roofline: the least time the traced passes need
    on this chip over the kernel's summed device time."""
    trace, traced = _traced(record)
    if trace is None or trace["kernel_s"].get(kernel, 0.0) <= 0:
        return None
    peaks = Peaks(**record["peaks"])
    least = sum(work.spmm_min_seconds(*p, peaks)[0]
                for p in traced["passes"])
    return 100.0 * least / trace["kernel_s"][kernel]


def kernel_busy_share(record: dict, kernel: str) -> Optional[float]:
    trace, _ = _traced(record)
    if trace is None or trace["kernel_s"].get(kernel, 0.0) <= 0:
        return None
    return 100.0 * trace["kernel_s"][kernel] / trace["window_s"]


def idle_share(record: dict) -> Optional[float]:
    trace, _ = _traced(record)
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def mfu(record: dict) -> Optional[float]:
    """% of the chip's peak: the model operations of the traced part over
    its length."""
    trace, traced = _traced(record)
    if trace is None:
        return None
    peaks = Peaks(**record["peaks"])
    return (100.0 * traced["model_flops"] / trace["window_s"]
            / peaks.flops_per_s)


def per_unit(record: dict, counter: str, unit: str,
             scale: float = 1.0) -> Optional[float]:
    c = record.get("counters", {})
    if not c.get(unit):
        return None
    return c[counter] / c[unit] * scale
