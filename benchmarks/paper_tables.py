"""Benchmarks reproducing the paper's tables/figures.

Table 5.2  -> iterations_table():     #iterations MC / BMC / HBMC
Table 5.3  -> trisolve_table():       sparse-triangular-solver + SpMV timing
              (CPU-host analogue of the paper's per-node timings; TPU
              times come from the chip benchmark in ``bench/``)
Fig  5.1   -> convergence_overlay():  BMC vs HBMC residual histories
§5.2.1     -> lane_occupancy_table(): vector-lane utilization (the SIMD-
              instruction-percentage analogue)
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core import (block_multicolor_ordering, build_preconditioner,
                        hbmc_from_bmc, ic0, pad_system_hbmc, solve_iccg,
                        solve_iccg_batched)
from repro.core.matrices import PAPER_PROBLEMS, PAPER_SHIFTS, paper_problem
from repro.core.sell import pack_sell, pack_ell

BS, W = 8, 8          # block size / lane width used across tables
RTOL = 1e-7           # paper's convergence criterion


def _problems(scale):
    out = []
    for name in PAPER_PROBLEMS:
        a, desc = paper_problem(name, scale=scale)
        rng = np.random.default_rng(42)
        b = rng.normal(size=a.shape[0])
        out.append((name, a, b, PAPER_SHIFTS.get(name, 0.0)))
    return out


def iterations_table(scale="small"):
    rows = []
    for name, a, b, shift in _problems(scale):
        its = {}
        for m in ("mc", "bmc", "hbmc"):
            rep = solve_iccg(a, b, method=m, block_size=BS, w=W, shift=shift,
                             rtol=RTOL)
            its[m] = rep.result.iterations
        assert its["bmc"] == its["hbmc"], \
            f"equivalence violated on {name}: {its}"
        rows.append((name, a.shape[0], its["mc"], its["bmc"], its["hbmc"]))
    return rows


def trisolve_table(scale="small", reps=5):
    """Per-application timing of the triangular solve + SpMV variants."""
    rows = []
    for name, a, b, shift in _problems(scale):
        timings = {}
        for m in ("mc", "bmc", "hbmc"):
            rep = solve_iccg(a, b, method=m, block_size=BS, w=W, shift=shift,
                             rtol=RTOL, maxiter=30)   # fixed 30 iterations
            # per-iteration solver time (PCG = 1 precond + 1 spmv + O(n))
            timings[m] = rep.solve_seconds / max(rep.result.iterations, 1)
        rows.append((name, a.shape[0],
                     timings["mc"] * 1e6, timings["bmc"] * 1e6,
                     timings["hbmc"] * 1e6))
    return rows


def spmv_padding_table(scale="small"):
    """SELL-w padding overhead (the paper's Audikw_1 discussion, §5.2.2)."""
    rows = []
    for name, a, b, shift in _problems(scale):
        sm = pack_sell(a, W)
        cols, vals = pack_ell(a)
        ell_padded = vals.size
        rows.append((name, a.nnz,
                     sm.padded_nnz / a.nnz,      # SELL overhead factor
                     ell_padded / a.nnz))        # ELL (CRS-gather) overhead
    return rows


def convergence_overlay(name="g3_circuit", scale="small"):
    a, _ = paper_problem(name, scale=scale)
    b = np.random.default_rng(42).normal(size=a.shape[0])
    r1 = solve_iccg(a, b, method="bmc", block_size=BS, w=W, rtol=RTOL,
                    record_history=True)
    r2 = solve_iccg(a, b, method="hbmc", block_size=BS, w=W, rtol=RTOL,
                    record_history=True)
    h1, h2 = r1.result.history, r2.result.history
    m = ~np.isnan(h1) & ~np.isnan(h2)
    return h1[m], h2[m], float(np.max(np.abs(h1[m] - h2[m])))


def backend_table(scale="small", reps=3):
    """Per-apply preconditioner timing: XLA substitution vs Pallas kernel.

    NOTE: off-TPU the Pallas kernel runs in *interpret* mode, so its numbers
    here measure semantics and dispatch overhead, not TPU performance — the
    comparison that matters on hardware is re-run with ``interpret=False``.
    """
    rows = []
    for name, a, b, shift in _problems(scale):
        bmc = block_multicolor_ordering(a, BS)
        hb = hbmc_from_bmc(bmc, W)
        a_hb, b_hb = pad_system_hbmc(a, b, hb)
        l = ic0(a_hb, shift=shift)
        r = jnp.asarray(b_hb)
        timings = {}
        for backend in ("xla", "pallas"):
            pre = build_preconditioner(l, hb, backend=backend)
            pre(r).block_until_ready()          # compile + warm cache
            t0 = time.perf_counter()
            for _ in range(reps):
                pre(r).block_until_ready()
            timings[backend] = (time.perf_counter() - t0) / reps
        rows.append((name, a.shape[0], timings["xla"] * 1e6,
                     timings["pallas"] * 1e6))
    return rows


def batched_throughput_table(scale="small", batch=8, maxiter=40):
    """Per-RHS PCG-loop time: B sequential single-RHS runs vs one batched
    multi-RHS run (per-RHS convergence masking).  The batched loop runs
    max(iterations) rounds total instead of sum(iterations).

    Only ``solve_seconds`` is compared — host setup (ordering + IC(0) +
    packing) is identical for both paths, so charging B setups to the
    sequential side would inflate the speedup.  ``solve_seconds`` still
    includes per-call trace/dispatch of the while_loop (each solve builds
    fresh closures), which the batched side pays once and the sequential
    side pays B times; that amortization is a real benefit of batching but
    means the ratio is wall-clock, not pure device-loop throughput."""
    rows = []
    for name, a, b, shift in _problems(scale):
        rng = np.random.default_rng(7)
        bb = rng.normal(size=(a.shape[0], batch))
        # warm the compile caches with one throwaway solve of each shape
        solve_iccg(a, bb[:, 0], method="hbmc", block_size=BS, w=W,
                   shift=shift, maxiter=maxiter)
        solve_iccg_batched(a, bb, method="hbmc", block_size=BS, w=W,
                           shift=shift, maxiter=maxiter)
        single = [solve_iccg(a, bb[:, j], method="hbmc", block_size=BS, w=W,
                             shift=shift, maxiter=maxiter)
                  for j in range(batch)]
        t_single = sum(s.solve_seconds for s in single)
        rep_b = solve_iccg_batched(a, bb, method="hbmc", block_size=BS, w=W,
                                   shift=shift, maxiter=maxiter)
        t_batched = rep_b.solve_seconds
        # batched == single iteration counts is expected but float-sequence
        # dependent; warn (don't abort the whole run) if a backend diverges
        if any(int(s.result.iterations) != int(it)
               for s, it in zip(single, rep_b.result.iterations)):
            print(f"WARNING: {name}: batched iterations "
                  f"{list(rep_b.result.iterations)} != single "
                  f"{[s.result.iterations for s in single]}")
        rows.append((name, a.shape[0], batch,
                     t_single / batch * 1e6,        # us per RHS, sequential
                     t_batched / batch * 1e6,       # us per RHS, batched
                     t_single / max(t_batched, 1e-12)))
    return rows


def lane_occupancy_table(scale="small"):
    """HBMC rounds use w parallel lanes (occupancy ~1); BMC's in-block loop
    is sequential = 1/w of the lanes — the paper's 99.7% vs 12.7% packed-
    instruction measurement, reconstructed structurally."""
    rows = []
    for name, a, b, shift in _problems(scale):
        rep_h = solve_iccg(a, b, method="hbmc", block_size=BS, w=W,
                           shift=shift, maxiter=1)
        rows.append((name, rep_h.lane_occupancy, 1.0 / W,
                     rep_h.n_colors, rep_h.n_rounds))
    return rows
