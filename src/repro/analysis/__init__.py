"""Static analysis: schedule races, contracts, numerics and data movement.

Proves a plan is race-free and contract-conforming *before* it dispatches:

  ``schedule``       dependency-DAG race detector over rounds, packed
                     trisolve tables and the IC(0) step schedule, with
                     machine-readable ``Violation`` witnesses
  ``contracts``      jaxpr linter with per-lowering-path primitive budgets
  ``kernel_checks``  static Pallas kernel checks (grid/BlockSpec
                     divisibility, gather index bounds, VMEM footprint)
  ``dtype_flow``     jaxpr dtype-propagation linter proving each lowering
                     path's ``PrecisionContract`` (no silent
                     promotion/demotion, pinned accumulation dtypes)
  ``collectives``    optimized-HLO collective-structure proofs (one tiled
                     all-gather per color round on a mesh, nothing else)
  ``traffic``        static bytes-per-iteration model cross-checked
                     against HLO-measured slice bytes, plus the
                     ``bench-gate`` snapshot regression gate
  ``hlo``            the shared optimized-HLO parser the above build on

``build_plan(a, validate="cheap"|"full"|"deep")`` runs the detector at
setup; ``python -m repro.analysis`` audits matrices/orderings/plans from
the CLI, and ``python -m repro.analysis bench-gate`` gates fresh bench
runs against the committed ``BENCH_*.json`` snapshots.
"""
from .collectives import (FORBIDDEN_COLLECTIVES, assert_plan_collectives,
                          check_collective_structure,
                          check_plan_collectives, collective_bodies,
                          optimized_hlo)
from .contracts import (DISTRIBUTED_APPLY, FULL_PALLAS_ITERATION,
                        PALLAS_SPMV, PRECONDITIONED_ITERATION,
                        ROUND_MAJOR_APPLY, ContractError, PrimitiveBudget,
                        assert_budget, count_primitive, format_eqn_path,
                        iter_eqns, lint, primitive_counts, primitives,
                        retraces)
from .dtype_flow import (PrecisionContract, assert_plan_dtype_flow,
                         check_plan_dtype_flow, contract_for_plan,
                         lint_dtype_flow)
from .hlo import CollectiveStats, parse_collectives
from .kernel_checks import (VMEM_BUDGET_BYTES, assert_plan_kernels,
                            check_plan_kernels, check_sell_spmv,
                            check_trisolve_fused, sell_spmv_vmem_bytes,
                            trisolve_fused_vmem_bytes)
from .schedule import (VALIDATE_MODES, ScheduleError, Violation,
                       assert_plan_valid, check_fused_tables,
                       check_ic0_structure, check_reversed_rounds,
                       check_rounds, check_step_tables, validate_plan)
from .traffic import (TrafficReport, TrafficTerm, assert_plan_traffic,
                      bench_gate, check_plan_traffic, compare_traffic,
                      measured_slice_bytes, traffic_report)

__all__ = [
    "DISTRIBUTED_APPLY", "FULL_PALLAS_ITERATION", "PALLAS_SPMV",
    "PRECONDITIONED_ITERATION", "ROUND_MAJOR_APPLY", "ContractError",
    "PrimitiveBudget", "assert_budget", "count_primitive",
    "format_eqn_path", "iter_eqns", "lint", "primitive_counts",
    "primitives", "retraces",
    "PrecisionContract", "assert_plan_dtype_flow", "check_plan_dtype_flow",
    "contract_for_plan", "lint_dtype_flow",
    "FORBIDDEN_COLLECTIVES", "assert_plan_collectives",
    "check_collective_structure", "check_plan_collectives",
    "collective_bodies", "optimized_hlo",
    "TrafficReport", "TrafficTerm", "assert_plan_traffic", "bench_gate",
    "check_plan_traffic", "compare_traffic", "measured_slice_bytes",
    "traffic_report",
    "CollectiveStats", "parse_collectives",
    "VMEM_BUDGET_BYTES", "assert_plan_kernels", "check_plan_kernels",
    "check_sell_spmv", "check_trisolve_fused", "sell_spmv_vmem_bytes",
    "trisolve_fused_vmem_bytes",
    "VALIDATE_MODES", "ScheduleError", "Violation", "assert_plan_valid",
    "check_fused_tables", "check_ic0_structure", "check_reversed_rounds",
    "check_rounds", "check_step_tables", "validate_plan",
]
