"""What one run observed, in the form the metric readers read.

A runner fills a `Run`: set-up time, the wall time of every unit of work
(a round, or a chunk of requests) in the measured window, counters of the
work done, the device trace's reduction in a traced run, and the checks
that decide `correct`.  Every metric, end-to-end or per-layer, is a small
reader of this record (`metrics/<name>.py`).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct while value <= limit."""
    name: str
    value: float
    limit: Optional[float]

    @property
    def ok(self) -> bool:
        return (self.limit is not None and math.isfinite(self.value)
                and self.value <= self.limit)


@dataclasses.dataclass
class Run:
    cell: Any                       # spec.Cell
    dims: Dict[str, Any]            # the reference's sizes of the model
    device: Dict[str, Any]          # platform, kind, count
    peaks: Dict[str, float]
    setup_s: float = 0.0
    window_s: float = 0.0           # host wall time of the measured window
    unit_s: List[float] = dataclasses.field(default_factory=list)
    work: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace: Optional[Any] = None     # tracing.Summary of a traced run
    traced: Dict[str, float] = dataclasses.field(default_factory=dict)
    hlo_texts: List[str] = dataclasses.field(default_factory=list)
    checks: List[Check] = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def kind(self) -> str:
        return self.cell.kind

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks) \
            and self.failed == 0
