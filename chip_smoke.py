#!/usr/bin/env python3
"""Smoke run of the HBMC-ICCG solver on one TPU chip, at the paper's sizes.

    python chip_smoke.py            # one chip: every single-device phase
    python chip_smoke.py --mesh4    # four chips: the mesh plan vs one chip

One process holds the chip and runs the solver's main path through the
entry points a user calls (``build_plan`` -> ``SolverPlan`` ->
``SolverService``) on the Thermal2 analogue at Thermal2's row count
(1108 x 1108 grid, n = 1,227,664) and the Parabolic_fem analogue at
Parabolic_fem's (725 x 725, n = 525,625).  Each phase prints one line
(sizes, iterations, residuals, wall times, the device) and checks its
answers on the host in f64: the true residual ||b - A x|| / ||b||, status
CONVERGED, and iteration-count identities between plans that must agree.
Any failed check raises; nothing is caught.  The last line of stdout is a
JSON object naming the device.

Phases without ``--mesh4``:

  one-shot   f64 (the default dtype) at rtol 1e-7 and f32 at rtol 1e-6,
             HBMC with the default backends; BMC must take as many
             iterations as HBMC in f64 (the paper's Table 5.2 identity)
  batched    ``plan.solve_batched`` with B = 8 on the f32 plan; every
             column's count next to its single-RHS count
  pallas     the f32 system with the Pallas trisolve and SELL SpMV
             kernels compiled (never interpreted); iterations within one
             of the XLA plan's
  service    ``SolverService`` over a two-entry ``PlanCache`` with slab
             width 8: 16 requests on both systems, including a value
             change of the Parabolic_fem matrix (a refactor)

``--mesh4`` builds the f32 Thermal2 plan with ``mesh=`` over four chips
and compares it with a one-chip plan padded to the same lane multiple:
equal iteration counts, solutions equal to f32 tolerance.

Exits non-zero, printing no result, where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

THERMAL2_GRID = 1108         # 1108^2 = 1,227,664 rows (Thermal2: 1,228,045)
PARABOLIC_FEM_GRID = 725     # 725^2 = 525,625 rows (Parabolic_fem: 525,825)
SEED = 0
BATCH = 8
SERVICE_REQUESTS = 16

RTOL_F64, RTOL_F32 = 1e-7, 1e-6
# true-residual bounds checked on the host in f64: the recursive residual
# of PCG drifts from the true one by rounding, so f64 gets a factor of 2
# over its rtol and f32 an order of magnitude
TRUE_RES_F64, TRUE_RES_F32 = 2e-7, 1e-5


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def true_residual(a, x, b) -> float:
    """||b - A x|| / ||b|| on the host in f64."""
    b = np.asarray(b, dtype=np.float64)
    r = b - a @ np.asarray(x, dtype=np.float64)
    return float(np.linalg.norm(r) / np.linalg.norm(b))


def _log(dev: str, phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{dev}] {phase}: {body}", flush=True)


def _timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


def _solve_checked(dev, phase, plan, a, b, rtol, bound, **extra):
    rep, secs = _timed(plan.solve, b, rtol=rtol)
    res = rep.result
    tres = true_residual(a, rep.x, b)
    _log(dev, phase, n=plan.n, rounds=plan.n_rounds, iters=res.iterations,
         status=res.status, relres=res.relres, true_res=tres,
         setup_s=round(plan.timings.total, 3), solve_s=round(secs, 3),
         **extra)
    check(res.status == "CONVERGED", f"{phase}: status {res.status}")
    check(tres <= bound, f"{phase}: true residual {tres} > {bound}")
    return rep


def phase_one_shot(dev, a, b):
    """f64 HBMC/BMC at rtol 1e-7, f32 HBMC at rtol 1e-6."""
    import jax.numpy as jnp
    from repro.core import build_plan

    hb64 = build_plan(a)
    r_hb = _solve_checked(dev, "one-shot f64 hbmc", hb64, a, b, RTOL_F64,
                          TRUE_RES_F64, dtype="f64")
    del hb64
    bm64 = build_plan(a, method="bmc")
    r_bm = _solve_checked(dev, "one-shot f64 bmc", bm64, a, b, RTOL_F64,
                          TRUE_RES_F64, dtype="f64")
    del bm64
    check(r_hb.result.iterations == r_bm.result.iterations,
          f"BMC ({r_bm.result.iterations}) and HBMC "
          f"({r_hb.result.iterations}) iteration counts differ")
    hb32 = build_plan(a, dtype=jnp.float32)
    b32 = b.astype(np.float32)
    r32 = _solve_checked(dev, "one-shot f32 hbmc", hb32, a, b32, RTOL_F32,
                         TRUE_RES_F32, dtype="f32")
    _, warm = _timed(hb32.solve, b32, rtol=RTOL_F32)
    _log(dev, "one-shot f32 hbmc warm", solve_s=round(warm, 3))
    return hb32, r32


def phase_batched(dev, a, plan):
    """B = 8 columns in one PCG loop; each as its own single-RHS solve."""
    from repro.core.iccg import status_name

    rng = np.random.default_rng(SEED + 1)
    bb = rng.normal(size=(a.shape[0], BATCH)).astype(np.float32)
    rep, secs = _timed(plan.solve_batched, bb, rtol=RTOL_F32)
    res = rep.result
    singles = [plan.solve(bb[:, j], rtol=RTOL_F32).result.iterations
               for j in range(BATCH)]
    tres = [true_residual(a, rep.x[:, j], bb[:, j]) for j in range(BATCH)]
    names = [status_name(s) for s in np.asarray(res.status)]
    _log(dev, "batched f32", B=BATCH, iters=list(map(int, res.iterations)),
         single_iters=singles, max_true_res=max(tres), solve_s=round(secs, 3))
    check(all(s == "CONVERGED" for s in names), f"batched statuses {names}")
    check(max(tres) <= TRUE_RES_F32,
          f"batched true residual {max(tres)} > {TRUE_RES_F32}")


def phase_pallas(dev, a, b32, xla_iters, interpret=False):
    """The same f32 system through both Pallas kernel families."""
    import jax.numpy as jnp
    from repro.core import build_plan

    plan = build_plan(a, dtype=jnp.float32, backend="pallas",
                      spmv_format="sell", spmv_backend="pallas",
                      interpret=interpret)
    rep = _solve_checked(dev, "pallas f32 hbmc", plan, a, b32, RTOL_F32,
                         TRUE_RES_F32, xla_iters=xla_iters)
    check(abs(rep.result.iterations - xla_iters) <= 1,
          f"pallas iterations {rep.result.iterations} vs xla {xla_iters}")


def phase_service(dev, a_th, g_parabolic):
    """16 requests over two patterns, one value change (a refactor)."""
    import jax.numpy as jnp
    from repro.core.matrices import parabolic_fem_analogue
    from repro.serve import PlanCache, SolverService

    a_pf = parabolic_fem_analogue(g_parabolic)
    a_pf2 = parabolic_fem_analogue(g_parabolic, dt=0.5)   # same pattern
    svc = SolverService(PlanCache(capacity=2), slab_width=BATCH,
                        rtol=RTOL_F32, dtype=jnp.float32)
    rng = np.random.default_rng(SEED + 2)
    mats = ([a_th] * 6) + ([a_pf] * 5) + ([a_pf2] * 5)
    assert len(mats) == SERVICE_REQUESTS
    reqs = {}
    t0 = time.perf_counter()
    for m in mats:
        b = rng.normal(size=m.shape[0]).astype(np.float32)
        reqs[svc.submit(m, b)] = (m, b)
    done = svc.drain()
    secs = time.perf_counter() - t0
    check(len(done) == SERVICE_REQUESTS,
          f"service completed {len(done)} of {SERVICE_REQUESTS}")
    worst, plan_status = 0.0, {}
    for c in done:
        m, b = reqs[c.rid]
        check(c.status == "CONVERGED", f"request {c.rid}: {c.status}")
        tres = true_residual(m, c.x, b)
        worst = max(worst, tres)
        check(tres <= TRUE_RES_F32,
              f"request {c.rid}: true residual {tres} > {TRUE_RES_F32}")
        plan_status[c.plan_status] = plan_status.get(c.plan_status, 0) + 1
    st = svc.cache.stats
    _log(dev, "service f32", requests=len(done), n=[a_th.shape[0],
         a_pf.shape[0]], iters=sorted(c.iterations for c in done),
         max_true_res=worst, plan_status=plan_status,
         cache_misses=st.misses, cache_refactors=st.refactors,
         cache_hits=st.hits, wall_s=round(secs, 3))
    check(st.refactors >= 1, "the value change did not refactor")


def phase_mesh4(dev, a, b):
    """The f32 Thermal2 plan sharded over four chips vs one chip."""
    import jax
    import jax.numpy as jnp
    from repro.core import build_plan

    n_dev = 4
    check(len(jax.devices()) >= n_dev,
          f"--mesh4 needs {n_dev} chips, found {len(jax.devices())}")
    mesh = jax.make_mesh((n_dev,), ("data",))
    b32 = b.astype(np.float32)
    meshed = build_plan(a, dtype=jnp.float32, mesh=mesh)
    r_m = _solve_checked(dev, "mesh4 f32 hbmc", meshed, a, b32, RTOL_F32,
                         TRUE_RES_F32, chips=n_dev)
    single = build_plan(a, dtype=jnp.float32, lane_multiple=n_dev)
    r_1 = _solve_checked(dev, "one-chip f32 hbmc lane_multiple=4", single,
                         a, b32, RTOL_F32, TRUE_RES_F32, chips=1)
    diff = float(np.linalg.norm(r_m.x - r_1.x) / np.linalg.norm(r_1.x))
    _log(dev, "mesh4 vs one chip", iters=[r_m.result.iterations,
                                         r_1.result.iterations],
         rel_diff=diff)
    check(r_m.result.iterations == r_1.result.iterations,
          "mesh and one-chip iteration counts differ")
    check(diff <= 1e-5, f"mesh and one-chip solutions differ by {diff}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh4", action="store_true",
                    help="run only the four-chip mesh plan and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_enable_x64", True)
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform is {d0.platform!r})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.core.matrices import thermal2_analogue

    cache_dir = enable_compile_cache()
    dev = f"{d0.platform} {d0.device_kind} x{len(devices)}"
    a, gen_s = _timed(thermal2_analogue, THERMAL2_GRID)
    b = np.random.default_rng(SEED).normal(size=a.shape[0])
    _log(dev, "system thermal2", n=a.shape[0], nnz=a.nnz,
         generate_s=round(gen_s, 3), compile_cache=cache_dir)
    if args.mesh4:
        phase_mesh4(dev, a, b)
    else:
        plan32, r32 = phase_one_shot(dev, a, b)
        phase_batched(dev, a, plan32)
        del plan32
        phase_pallas(dev, a, b.astype(np.float32), r32.result.iterations)
        phase_service(dev, a, PARABOLIC_FEM_GRID)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
