"""Named host intervals: timed for a report, marked for the profiler.

``span(name, seconds)`` times the block it wraps into ``seconds[name]``
and opens a ``jax.profiler.TraceAnnotation`` of the same name around it,
so the interval a report records is the one a profiler trace shows, on
the trace's own clock.  Without a running profiler the annotation costs
about a microsecond.
"""
from __future__ import annotations

import contextlib
import time

from jax.profiler import TraceAnnotation

#: the three host intervals of a solve, b in to x out
SOLVE_EMBED = "repro.solve.embed"       # validate, permute, embed, transfer in
SOLVE_PCG = "repro.solve.pcg"           # dispatch the PCG, block until ready
SOLVE_EXTRACT = "repro.solve.extract"   # transfer out, extract, un-permute


@contextlib.contextmanager
def span(name: str, seconds: dict):
    """Time the block into ``seconds[name]`` under a trace annotation."""
    with TraceAnnotation(name):
        t0 = time.perf_counter()
        yield
        seconds[name] = time.perf_counter() - t0
