"""End-to-end behaviour test: the paper's solver pipeline, exercised
through its public entry point."""
import numpy as np

from repro.core import solve_iccg
from repro.core.matrices import paper_problem


def test_paper_pipeline_end_to_end():
    """ordering -> IC(0) -> packed trisolve -> PCG -> correct solution,
    with the HBMC == BMC equivalence holding."""
    a, _ = paper_problem("thermal2", scale="tiny")
    b = np.random.default_rng(0).normal(size=a.shape[0])
    bmc = solve_iccg(a, b, method="bmc", block_size=8, w=4)
    hbmc = solve_iccg(a, b, method="hbmc", block_size=8, w=4)
    assert bmc.result.iterations == hbmc.result.iterations
    assert hbmc.result.converged
    r = a @ hbmc.x - b
    assert np.linalg.norm(r) / np.linalg.norm(b) < 1e-6

