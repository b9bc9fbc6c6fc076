"""Closed loop of one client: back-to-back ``plan.solve`` calls.

Traffic parameters: ``rhs`` (the law of each right-hand side) and
``chain``: false solves one-shot right-hand sides, true feeds each
solve's answer back as the next right-hand side (implicit time
stepping, u_{k+1} = A^-1 u_k, with u_0 drawn from the seed).

One-shot right-hand sides come from a set of ``rhs_set`` vectors drawn
once from ``rhs_set_seed``; the run's seed draws the order in which the
window takes them (a new order for each pass over the set).  PCG's
iteration count depends on the right-hand side, so right-hand sides
drawn afresh from each seed would change the work from run to run; the
same set in another order keeps it fixed while the seed still decides
the inputs.

The window's clock runs only inside ``plan.solve`` calls (right-hand side
in on the host, solution out on the host); drawing the next right-hand
side lies outside it.  Solves run whole: the last one that starts inside
the window finishes, and the window ends there.
"""
from __future__ import annotations

import time

import numpy as np

from bench.drivers.common import draw, plan_knobs, rhs_dtype
from bench.records import SolveRecord, span


class Driver:
    def __init__(self, a, cfg: dict, traffic: dict):
        self.a, self.cfg, self.traffic = a, cfg, traffic
        self.n = a.shape[0]
        self.dtype = rhs_dtype(cfg)
        self.plan = None

    def setup(self) -> float:
        """Build the plan and compile its solve; returns the plan's own
        set-up seconds."""
        from repro.core import build_plan
        self.plan = build_plan(self.a, **plan_knobs(self.cfg))
        # a zero right-hand side runs the same compiled PCG and stops
        # after one preconditioner apply
        self._solve(np.zeros(self.n, dtype=self.dtype))
        return self.plan.timings.total

    def _solve(self, b):
        return self.plan.solve(b, rtol=self.cfg["rtol"],
                               maxiter=self.cfg["maxiter"])

    def prepare(self, seed: int, seconds: float) -> None:
        self.rng = np.random.default_rng(seed)
        if self.traffic["chain"]:
            self.b0 = draw(self.rng, self.traffic["rhs"], self.n, self.dtype)
            return
        set_rng = np.random.default_rng(self.traffic["rhs_set_seed"])
        self.rhs_set = [draw(set_rng, self.traffic["rhs"], self.n, self.dtype)
                        for _ in range(self.traffic["rhs_set"])]
        self.order = []
        self.b0 = self._next_rhs()

    def _next_rhs(self):
        if not self.order:
            self.order = list(self.rng.permutation(len(self.rhs_set)))
        return self.rhs_set[self.order.pop(0)]

    def window(self, seconds: float, run) -> None:
        b, clock = self.b0, 0.0
        while clock < seconds:
            t0 = time.perf_counter()
            with span("bench.solve"):
                rep = self._solve(b)
            wall = time.perf_counter() - t0
            clock += wall
            run.solves.append(SolveRecord(
                wall_s=wall, device_s=rep.solve_seconds,
                iterations=rep.result.iterations, status=rep.result.status,
                b=b, x=rep.x))
            with span("bench.rhs"):
                b = rep.x if self.traffic["chain"] else self._next_rhs()
        run.window_s = clock

    def release(self) -> None:
        self.plan = None
