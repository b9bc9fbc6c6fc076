"""What a run records, for the metric readers and the reference."""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class SolveRecord:
    """One ``plan.solve`` call of the window."""
    wall_s: float            # host clock around plan.solve: b in, x out
    device_s: float          # the program's ICCGReport.solve_seconds
    iterations: int
    status: str
    b: np.ndarray
    x: np.ndarray


@dataclasses.dataclass
class Run:
    """Everything one run measured; metric readers read only this."""
    workload: str
    config: dict
    traffic: dict
    device_kind: str
    n_devices: int
    seconds: float
    setup_s: float = 0.0
    plan_build_s: float = 0.0
    window_s: float = 0.0
    solves: list = dataclasses.field(default_factory=list)
    trace: object = None     # bench.trace.Summary of the traced window

    def answers(self):
        """(b, x, status) of every answer due in the window."""
        return [(s.b, s.x, s.status) for s in self.solves]


def span(name: str):
    """A host span in the profiler's trace (next to no cost without one)."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)
