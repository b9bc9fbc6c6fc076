"""Static traffic model + bench regression gate.

Li (arXiv:1710.04985) argues the end-to-end ICCG win is decided by
bytes-per-iteration; this module makes that quantity a *checked* number
instead of a believed one.

**Static model.**  Every byte the hot loop moves is determined by the
plan's packed table shapes: the fused 2S-step sweep streams its per-step
table slices (cols/vals/dinv) plus four R-vectors of state per step, the
SpMV gathers one x value per packed slot, and the PCG vector work streams
a fixed number of m-vectors per iteration.  :func:`traffic_report`
computes those terms, the per-iteration FLOPs, and the resulting
arithmetic intensity.

**Cross-check.**  The slice-family ops of an optimized module
(``dynamic-slice`` / ``gather`` / ``slice`` results,
``dynamic-update-slice`` updates) keep their exact shapes through XLA
fusion, so summing their bytes with while-loop trip multiplication
reproduces a physical table-streaming model exactly — unlike whole-module
heuristics, which are dominated by fusion-boundary modeling choices.
:func:`check_plan_traffic` compiles the apply and SpMV, extracts that
measurement, and fails with a ``Violation`` witness naming the term if
the static model drifts beyond tolerance (default 10%) — e.g. if table
padding silently inflates, or a lowering change starts re-streaming a
table.

**Bench gate.**  :func:`bench_gate` compares two benchmark snapshots
(committed ``benchmarks/BENCH_*.json`` vs a fresh run) metric-by-metric:
time-like metrics may not regress beyond tolerance, throughput-like
metrics may not drop, iteration counts may not grow.  Wired to
``python -m repro.analysis bench-gate`` and the CI analysis job.
"""
from __future__ import annotations

import dataclasses

from . import hlo
from .schedule import ScheduleError, Violation


@dataclasses.dataclass(frozen=True)
class TrafficTerm:
    """One byte stream of the hot loop.  ``measured_bytes`` is filled by
    the HLO slice-extraction cross-check where the lowering exposes it
    (None = static-only term)."""
    name: str
    static_bytes: float
    measured_bytes: float | None = None
    detail: str = ""

    @property
    def relative_error(self) -> float | None:
        if self.measured_bytes is None or self.measured_bytes == 0:
            return None
        return abs(self.static_bytes - self.measured_bytes) \
            / self.measured_bytes


@dataclasses.dataclass(frozen=True)
class TrafficReport:
    """Per-iteration data movement of one plan, term by term."""
    label: str
    terms: tuple
    iteration_bytes: float      # static bytes per PCG iteration
    iteration_flops: float      # static FLOPs per PCG iteration

    @property
    def arithmetic_intensity(self) -> float:
        return self.iteration_flops / self.iteration_bytes \
            if self.iteration_bytes else 0.0


#: m-vector streams per PCG iteration outside apply/SpMV: two dot
#: pairings (4), three axpy-likes (9), one residual norm (1)
VECTOR_STREAMS_PER_ITERATION = 14


def measured_slice_bytes(text: str) -> float:
    """Sum of slice-family result bytes in an optimized module, with
    while-loop trip multiplication — the physically-pinned subset of HBM
    traffic (table slices, gathers, state updates)."""
    comps = hlo.parse_module(text)
    entry = hlo.entry_name(text, comps)
    memo: dict = {}

    def cost(name: str) -> float:
        if name in memo:
            return memo[name]
        comp = comps.get(name)
        if comp is None:
            return 0.0
        memo[name] = 0.0        # break cycles defensively
        total = 0.0
        for op in comp.ops:
            if op.kind.endswith("-done"):
                continue
            base = hlo.base_kind(op.kind)
            if base == "while":
                trip = hlo.trip_count(op, comps)
                total += trip * sum(cost(c)
                                    for c in hlo.called_comps(op.rest))
            elif base in ("fusion", "call", "conditional", "async-start"):
                total += sum(cost(c) for c in hlo.called_comps(op.rest))
            elif base in hlo.SLICE_OPS:
                total += op.bytes
            elif base == "dynamic-update-slice":
                upd = hlo._arg_op(op, comp, 1)
                total += upd.bytes if upd is not None else op.bytes
        memo[name] = total
        return total

    return cost(entry)


def _apply_static_bytes(plan) -> tuple[float, float, str]:
    """(sliced, whole, detail) bytes of one round-major apply, from the
    table shapes.

    Per step of a segment half the sweep slices: cols (R*K int32) + vals
    (R*K item) + dinv (R item) + the right-hand-side read (q forward, y
    backward), the y gather (R*K item) and the y update write — exactly
    the slice-family ops the optimized HLO exposes, so static == measured
    when nothing leaks.  At K = 1 the gather and its fill mask slice the
    cols apart, twice a step.  A segment of one round is a loop of one
    trip, which XLA on the CPU inlines: its step reads its tables whole,
    with no slice, so they are the static-only ``whole`` bytes.
    """
    t = plan._precond.tables
    item = plan._np_dtype.itemsize
    sliced = whole = 0.0
    for h in t.fwd + t.bwd:
        n, k, r = h.cols.shape
        cols = r * k * h.cols.dtype.itemsize
        tables = cols + r * k * item + r * item
        state = r * k * item + 2 * r * item
        if n == 1:
            sliced, whole = sliced + state, whole + tables
        else:
            sliced += n * (tables + state + (cols if k == 1 else 0))
    return sliced, whole, (f"2S={2 * t.n_steps} steps in {2 * t.n_segments}"
                           f" halves (K, R per half: "
                           f"{[h.cols.shape[1:] for h in t.fwd + t.bwd]}, "
                           f"{item}B items)")


def _spmv_gather_bytes(plan) -> tuple[float, str]:
    """The x[cols] gather of the packed SpMV: one item per packed slot,
    plus the ``[:n]`` trim when SELL pads the rows to whole slices.
    (The vals/cols streams are consumed straight from parameters — no
    slice op — so they are static-only terms.)"""
    import numpy as np
    slots = int(np.prod(plan._spmv_vals.shape))
    item = plan._np_dtype.itemsize
    total = float(slots * item)
    detail = f"{slots} packed slots x {item}B ({plan.spmv_format})"
    if plan.spmv_format == "sell":
        rows = plan._spmv_vals.shape[0] * plan._spmv_vals.shape[-1]
        if rows != plan._spmv_n:
            total += plan._spmv_n * item
            detail += f" + [:{plan._spmv_n}] trim of {rows} padded rows"
    return total, detail


def traffic_report(plan, measure: bool = True) -> TrafficReport:
    """Static per-iteration traffic of a plan, with the HLO cross-check
    filled in where the lowering exposes it (round-major XLA paths on a
    single device; pallas kernels and mesh lowerings are static-only)."""
    import numpy as np

    if plan.layout != "round_major":
        raise ValueError("traffic model requires layout='round_major' "
                         "(the native PCG layout); index-layout plans "
                         "have no fused-sweep stream to model")
    item = plan._np_dtype.itemsize
    m = plan.slab_m
    t = plan._precond.tables
    table_slots = sum(h.cols.size for h in t.fwd + t.bwd)
    slots = int(np.prod(plan._spmv_vals.shape))

    apply_static, apply_whole, apply_detail = _apply_static_bytes(plan)
    gather_static, gather_detail = _spmv_gather_bytes(plan)
    apply_measured = gather_measured = None
    measurable = (measure and plan.mesh is None
                  and plan.backend == "xla" and plan.spmv_backend == "xla")
    if measurable:
        import jax
        import jax.numpy as jnp

        from repro.core.plan import _make_spmv
        pre = plan._precond
        q = jnp.zeros((m,), dtype=plan.dtype)
        apply_measured = measured_slice_bytes(
            jax.jit(lambda x: pre(x)).lower(q).compile().as_text())
        spmv = _make_spmv(plan.spmv_format, plan._spmv_n, plan._spmv_vals,
                          plan._spmv_cols, False,
                          spmv_backend=plan.spmv_backend,
                          interpret=plan.interpret)
        gather_measured = measured_slice_bytes(
            jax.jit(spmv).lower(q).compile().as_text())

    # x random reads are the gather term; the streamed remainder is the
    # vals/cols parameters and the y result write
    spmv_stream = float(slots * (item + plan._spmv_cols.dtype.itemsize)
                        + m * item)
    vector_stream = float(VECTOR_STREAMS_PER_ITERATION * m * item)
    terms = (
        TrafficTerm("apply", apply_static, apply_measured, apply_detail),
        TrafficTerm("apply/tables", apply_whole, None,
                    "tables of one-round segments, read whole"),
        TrafficTerm("spmv/gather", gather_static, gather_measured,
                    gather_detail),
        TrafficTerm("spmv/stream", spmv_stream, None,
                    "vals + cols parameter streams + y write"),
        TrafficTerm("vector", vector_stream, None,
                    f"{VECTOR_STREAMS_PER_ITERATION} m-vector streams"),
    )
    # FLOPs: 2 MACs per packed slot (SpMV), 2 per table slot + diag scale
    # (sweep), ~10 per row of vector work
    flops = float(2 * slots + 2 * table_slots + 2 * m + 10 * m)
    total = float(sum(x.static_bytes for x in terms))
    return TrafficReport(
        label=f"{plan.layout}/{plan.backend}/{plan.spmv_format}",
        terms=terms, iteration_bytes=total, iteration_flops=flops)


def compare_traffic(terms, tolerance: float = 0.10,
                    where: str = "traffic") -> list[Violation]:
    """Static-vs-measured witnesses for every cross-checked term."""
    out = []
    for term in terms:
        rel = term.relative_error
        if rel is not None and rel > tolerance:
            out.append(Violation(
                kind="traffic-model-mismatch", where=where,
                detail=f"term {term.name}: static "
                       f"{term.static_bytes:.0f} B vs HLO-measured "
                       f"{term.measured_bytes:.0f} B "
                       f"({100 * rel:.1f}% > {100 * tolerance:.0f}% "
                       f"tolerance; {term.detail})"))
    return out


def check_plan_traffic(plan, tolerance: float = 0.10) -> list[Violation]:
    """Compile the plan's apply + SpMV and prove the static traffic model
    matches the HLO-measured slice bytes within ``tolerance``."""
    report = traffic_report(plan, measure=True)
    return compare_traffic(report.terms, tolerance)


def assert_plan_traffic(plan, tolerance: float = 0.10,
                        context: str = "") -> None:
    violations = check_plan_traffic(plan, tolerance)
    if violations:
        raise ScheduleError(violations, context=context)


# ---------------------------------------------------------------------------
# Bench regression gate over committed BENCH_*.json snapshots.
# ---------------------------------------------------------------------------

#: record fields that identify a list entry (used as the metric path
#: segment so records match structurally, not positionally)
_ID_KEYS = ("problem", "layout", "backend", "spmv_backend", "method",
            "scheduler", "stage", "component", "name", "kind", "B",
            "slab_width", "width", "devices", "n")
_LOWER_SUFFIX = ("_us", "_ms", "_s", "_seconds")
_LOWER_SUBSTR = ("latency", "time", "p50", "p90", "p99")
_HIGHER_SUBSTR = ("per_s", "per_sec", "throughput", "speedup", "hit_rate")
#: iteration-count slack: counts are near-deterministic, but smoke-scale
#: reruns may wiggle by an iteration
_ITER_SLACK = 1.05


def _flatten_metrics(node, prefix: str = "", out: dict | None = None
                     ) -> dict:
    if out is None:
        out = {}
    if isinstance(node, dict):
        for k in sorted(node):
            key = f"{prefix}.{k}" if prefix else str(k)
            _flatten_metrics(node[k], key, out)
    elif isinstance(node, list):
        for i, v in enumerate(node):
            seg = f"[{i}]"
            if isinstance(v, dict):
                ids = [f"{k}={v[k]}" for k in _ID_KEYS
                       if isinstance(v.get(k), (str, int, float))]
                if ids:
                    seg = "[" + ",".join(ids) + "]"
            _flatten_metrics(v, prefix + seg, out)
    elif isinstance(node, bool):
        pass
    elif isinstance(node, (int, float)):
        out[prefix] = float(node)
    return out


def _direction(path: str) -> str | None:
    leaf = path.rsplit(".", 1)[-1].rsplit("]", 1)[-1].lstrip(".")
    if leaf in ("iterations", "iters") or leaf.endswith("_iterations"):
        return "iters"
    # higher-is-better first: "rhs_per_s" must not match the _s suffix
    if any(s in leaf for s in _HIGHER_SUBSTR):
        return "higher"
    if leaf in ("us", "s", "ms") \
            or any(leaf.endswith(s) for s in _LOWER_SUFFIX) \
            or any(s in leaf for s in _LOWER_SUBSTR):
        return "lower"
    return None


def bench_gate(baseline: dict, candidate: dict, tolerance: float = 0.5,
               where: str = "bench-gate") -> list[Violation]:
    """Gate ``candidate`` bench results against a ``baseline`` snapshot.

    Every gateable baseline metric must exist in the candidate (schema
    drift is a failure, not a silent skip) and stay within tolerance in
    its metric's good direction: time-like ``<= base * (1 + tol)``,
    throughput-like ``>= base / (1 + tol)``, iteration counts may not
    grow beyond a fixed 5% determinism slack.  Returns witnesses naming
    the exact metric path; empty = gate passed.
    """
    base = _flatten_metrics(baseline)
    cand = _flatten_metrics(candidate)
    out: list[Violation] = []
    gated = 0
    for path, bv in base.items():
        d = _direction(path)
        if d is None:
            continue
        if path not in cand:
            out.append(Violation(
                kind="missing-metric", where=where,
                detail=f"{path}: present in baseline, absent in "
                       f"candidate (schema drift?)"))
            continue
        cv = cand[path]
        gated += 1
        if d == "iters":
            if cv > bv * _ITER_SLACK + 0.5:
                out.append(Violation(
                    kind="iteration-regression", where=where,
                    detail=f"{path}: {cv:g} iterations vs baseline "
                           f"{bv:g} — convergence regressed"))
        elif bv <= 0:
            continue            # zero baselines carry no gateable ratio
        elif d == "lower" and cv > bv * (1.0 + tolerance):
            out.append(Violation(
                kind="perf-regression", where=where,
                detail=f"{path}: {cv:.4g} vs baseline {bv:.4g} "
                       f"(+{100 * (cv / bv - 1):.0f}% > "
                       f"{100 * tolerance:.0f}% tolerance)"))
        elif d == "higher" and cv < bv / (1.0 + tolerance):
            out.append(Violation(
                kind="perf-regression", where=where,
                detail=f"{path}: {cv:.4g} vs baseline {bv:.4g} "
                       f"(-{100 * (1 - cv / bv):.0f}% > "
                       f"{100 * tolerance:.0f}% tolerance)"))
    if gated == 0 and not out:
        out.append(Violation(
            kind="no-metrics", where=where,
            detail="baseline snapshot exposes no gateable metrics — the "
                   "gate would pass vacuously"))
    return out
