"""The plain reference that decides ``correct``.

A solve's answer says ``A x = b``.  The reference checks what it says:
the true relative residual ``||b - A x|| / ||b||``, computed on the host in
float64 with scipy from the benchmark's own copy of ``A`` (``bench/
configs/<config>.py``), for every answer due in the window.  It imports
nothing of the program and takes nothing the program made but the
answer.

Two numbers are compared, each with its limit from the configuration's
``limits``:

* ``true_res_max``: the largest true residual over the answers checked.
  Where the configuration's source states the tolerance as one on the
  true residual (poisson2d: pcg's ``tol``), the limit is that tolerance.
  Elsewhere it is a small multiple of the configured ``rtol``, set from
  readings of the program and of its control (PERF.md);
* ``unconverged``: answers that did not come back CONVERGED, or never
  came (exact: limit 0).

A wrong permutation or inverse, a wrong SpMV, or an answer altered on
its way out gives a true residual far over the limit.  A stopping test looser
than the configured ``rtol`` gives one near the looser tolerance, over
the limit once it is more than the limit's multiple of ``rtol``.  A
factor or sweep that breaks the preconditioner keeps PCG from
converging; one that stays SPD but is wrong only costs iterations, which
``pcg_iterations`` and the solve time show.
"""
from __future__ import annotations

import numpy as np

CONVERGED = "CONVERGED"


def true_residual(a, x, b) -> float:
    """||b - A x|| / ||b|| in float64 on the host."""
    b = np.asarray(b, dtype=np.float64)
    r = b - a @ np.asarray(x, dtype=np.float64)
    return float(np.linalg.norm(r) / np.linalg.norm(b))


def judge(a, answers, limits: dict) -> dict:
    """Compared numbers of one run, each as {"value", "limit"}.

    ``answers`` holds (b, x, status) per answer due in the window; x is
    None for one that never came.
    """
    missing = sum(x is None or status != CONVERGED
                  for _, x, status in answers) if answers else 1
    res = [true_residual(a, x, b) for b, x, _ in answers if x is not None]
    worst = max(res, default=0.0)
    if not all(np.isfinite(res)):
        worst = np.inf
    return {"true_res_max": {"value": worst,
                             "limit": float(limits["true_res_max"])},
            "unconverged": {"value": missing, "limit": 0}}


def is_correct(checks: dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
