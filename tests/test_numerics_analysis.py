"""Numerics & performance contract tier: dtype flow, collectives, traffic.

Mirrors ``src/repro/analysis``'s PR-9 analyzers with mutation evidence:

  1. **dtype flow** — every lowering path of a plan proves its precision
     contract clean, and an injected silent demotion / wrong-accumulator /
     stray dtype is pinned to the exact jaxpr eqn;
  2. **collectives** — the structural proof accepts the one-tiled-gather-
     per-round sweep shape and pins every doctored HLO mutation (extra
     gather, forbidden all-reduce, wrong trip count, untiled gather) to
     the exact op; single-device plans lower collective-free;
  3. **traffic** — the static bytes-per-iteration model matches the
     HLO-measured slice bytes within tolerance, and an inflated table
     term is witnessed by name;
  4. **bench gate** — every committed ``BENCH_*.json`` self-gates clean,
     and a doctored snapshot fails naming the exact metric path.
"""
import copy
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from repro.analysis import (VALIDATE_MODES, PrecisionContract, ScheduleError,
                            bench_gate, check_collective_structure,
                            check_plan_collectives, check_plan_dtype_flow,
                            check_plan_traffic, collective_bodies,
                            compare_traffic, contract_for_plan,
                            lint_dtype_flow, traffic_report, validate_plan)
from repro.analysis.__main__ import main as analysis_main
from repro.core import build_plan
from repro.core.matrices import laplace_2d
from repro.serve.solver import PlanCache

REPO = Path(__file__).resolve().parents[1]
BENCH_DIR = REPO / "benchmarks"


# ---------------------------------------------------------------------------
# 1. Dtype flow: clean paths prove clean, injected defects are pinned.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ("hbmc", "natural"))
def test_plan_dtype_flow_proves_clean(method):
    plan = build_plan(laplace_2d(13, 11), method=method, validate="off")
    assert check_plan_dtype_flow(plan) == []


def test_f32_plan_dtype_flow_clean():
    """Weak-typed literal normalization (f64 python floats entering an f32
    plan) is the legitimate jax idiom, not a silent demotion."""
    plan = build_plan(laplace_2d(13, 11), method="hbmc",
                      dtype=jnp.float32, validate="off")
    assert contract_for_plan(plan).vector == "float32"
    assert check_plan_dtype_flow(plan) == []


def test_pallas_plan_dtype_flow_clean():
    plan = build_plan(laplace_2d(10, 8), method="hbmc", block_size=8, w=4,
                      spmv_format="sell", backend="pallas",
                      spmv_backend="pallas", interpret=True, validate="off")
    assert check_plan_dtype_flow(plan) == []


def test_injected_demotion_pinned_to_exact_eqn():
    plan = build_plan(laplace_2d(13, 11), method="hbmc", validate="off")
    contract = contract_for_plan(plan)
    pre = plan._precond
    leaky = lambda q: pre(q.astype(jnp.float32).astype(jnp.float64))  # noqa: E731
    q = jnp.zeros((plan.slab_m,), dtype=plan.dtype)
    vio = lint_dtype_flow(leaky, q, contract=contract, where="mutated")
    demo = [v for v in vio if v.kind == "silent-demotion"]
    assert demo, [str(v) for v in vio]
    # the witness names the offending eqn and the exact dtype pair
    assert "convert_element_type#" in demo[0].detail
    assert "float64 -> float32" in demo[0].detail
    # the round trip back up is a (distinct) silent promotion
    assert any(v.kind == "silent-promotion" for v in vio)


def test_allowlisted_convert_passes():
    """A future mixed-precision plan lands behind this allowlist: the same
    convert pair stops being a witness once the contract names it."""
    plan = build_plan(laplace_2d(13, 11), method="hbmc", validate="off")
    contract = dataclasses.replace(
        contract_for_plan(plan),
        allowed_converts=(("float64", "float32"), ("float32", "float64")))
    pre = plan._precond
    leaky = lambda q: pre(q.astype(jnp.float32).astype(jnp.float64))  # noqa: E731
    q = jnp.zeros((plan.slab_m,), dtype=plan.dtype)
    assert lint_dtype_flow(leaky, q, contract=contract, where="allow") == []


def test_wrong_accumulator_dtype_is_witnessed():
    contract = PrecisionContract(name="f64-accum", vector="float64",
                                 accum="float64", tables="float64")
    x = jnp.zeros((8,), jnp.float32)
    vio = lint_dtype_flow(lambda v: jnp.dot(v, v), x, contract=contract,
                          where="dot")
    assert any(v.kind == "accum-dtype" and "dot" in v.detail
               for v in vio), [str(v) for v in vio]


def test_stray_dtype_is_witnessed():
    contract = PrecisionContract(name="f64-only", vector="float64",
                                 accum="float64", tables="float64")
    x = jnp.zeros((8,), jnp.float16)
    vio = lint_dtype_flow(jnp.sin, x, contract=contract, where="stray")
    assert any(v.kind == "stray-dtype" and "float16" in v.detail
               for v in vio), [str(v) for v in vio]


def test_validate_deep_gates_build_and_cache():
    assert "deep" in VALIDATE_MODES
    a = laplace_2d(9, 8)
    plan = build_plan(a, method="hbmc", validate="deep")
    assert plan.validate == "deep"
    assert validate_plan(plan, "deep") == []
    cache = PlanCache(capacity=1, validate="deep")
    _, status = cache.get(a, method="hbmc")
    assert status == "miss" and len(cache) == 1


# ---------------------------------------------------------------------------
# 2. Collective structure: synthetic-HLO mutations pinned, plans proven.
# ---------------------------------------------------------------------------

# the sweep shape the linter must accept for one segment of S = 3 rounds:
# a forward and a backward while body, trip S each, one tiled all-gather
# per body (4 participants: f64[2] operand -> f64[8] result)
GOOD_HLO = """\
HloModule sweep_test

%cond (carg: (f64[8])) -> pred[] {
  %ca = (f64[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

%loop_body (barg: (f64[8])) -> (f64[8]) {
  %ba = (f64[8]{0}) parameter(0)
  %x = f64[8]{0} get-tuple-element(%ba), index=0
  %src = f64[2]{0} dynamic-slice(%x, %x), dynamic_slice_sizes={2}
  %ag = f64[8]{0} all-gather(%src), replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %r = (f64[8]{0}) tuple(%ag)
}

%back_body (carg2: (f64[8])) -> (f64[8]) {
  %ba2 = (f64[8]{0}) parameter(0)
  %x2 = f64[8]{0} get-tuple-element(%ba2), index=0
  %src2 = f64[2]{0} dynamic-slice(%x2, %x2), dynamic_slice_sizes={2}
  %agb = f64[8]{0} all-gather(%src2), replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %r2 = (f64[8]{0}) tuple(%agb)
}

ENTRY %main (p: f64[8]) -> f64[8] {
  %p1 = f64[8]{0} parameter(0)
  %t = (f64[8]{0}) tuple(%p1)
  %w = (f64[8]{0}) while(%t), condition=%cond, body=%loop_body, backend_config={"known_trip_count":{"n":"3"}}
  %w2 = (f64[8]{0}) while(%w), condition=%cond, body=%back_body, backend_config={"known_trip_count":{"n":"3"}}
  ROOT %out = f64[8]{0} get-tuple-element(%w2), index=0
}
"""

EXTRA_GATHER_LINE = ("  %ag2 = f64[8]{0} all-gather(%src), "
                     "replica_groups={{0,1,2,3}}, dimensions={0}\n")


def test_good_sweep_structure_is_accepted():
    assert check_collective_structure(GOOD_HLO, n_rounds=3) == []
    bodies, counts = collective_bodies(GOOD_HLO)
    assert counts == {"all-gather": 2}
    assert [(b.comp, b.trip) for b in bodies] == [("loop_body", 3),
                                                  ("back_body", 3)]


def test_extra_gather_per_round_is_pinned():
    text = GOOD_HLO.replace("  ROOT %r =", EXTRA_GATHER_LINE + "  ROOT %r =")
    vio = check_collective_structure(text, n_rounds=3)
    extra = [v for v in vio if v.kind == "extra-collective"]
    assert extra, [str(v) for v in vio]
    assert "loop_body" in extra[0].detail and "ag2" in extra[0].detail


# a one-round segment's two steps, inlined into the entry (XLA removes a
# loop of one trip), one tiled all-gather each
INLINE_STEPS = (
    "  %s1 = f64[2]{0} slice(%p1), slice={[0:2]}\n"
    "  %i1 = f64[8]{0} all-gather(%s1), replica_groups={{0,1,2,3}}, "
    "dimensions={0}\n"
    "  %i2 = f64[8]{0} all-gather(%s1), replica_groups={{0,1,2,3}}, "
    "dimensions={0}\n")


@pytest.mark.parametrize("inline, n_rounds, ok", [
    (True, [3, 1], True),       # the one-round segment's steps, inline
    (False, [3, 1], False),     # ... missing
    (True, 3, False),           # inline gathers of no segment
    (True, [3, 1, 1], False),   # one one-round segment short
    ("loops", [3, 1], True),    # ... or its steps as loops of one trip
])
def test_one_round_segments_gather_inline(inline, n_rounds, ok):
    text = (GOOD_HLO.replace("  ROOT %out =", INLINE_STEPS + "  ROOT %out =")
            if inline is True else GOOD_HLO)
    if inline == "loops":
        # the second segment's two loops of one trip, over the same bodies
        text = GOOD_HLO + GOOD_HLO[GOOD_HLO.index("%cond"):
                                   GOOD_HLO.index("ENTRY")].replace(
            "%cond", "%cond1").replace("_body", "_body1").replace(
            "%ag", "%ag1")
        text = text.replace(
            "  ROOT %out = f64[8]{0} get-tuple-element(%w2), index=0",
            '  %w3 = (f64[8]{0}) while(%w2), condition=%cond1, '
            'body=%loop_body1, backend_config={"known_trip_count":'
            '{"n":"1"}}\n'
            '  %w4 = (f64[8]{0}) while(%w3), condition=%cond1, '
            'body=%back_body1, backend_config={"known_trip_count":'
            '{"n":"1"}}\n'
            "  ROOT %out = f64[8]{0} get-tuple-element(%w4), index=0")
        assert text.count(" while(") == 4
    vio = check_collective_structure(text, n_rounds=n_rounds)
    assert (vio == []) == ok, [str(v) for v in vio]


def test_only_the_pcg_body_holds_the_inlined_steps():
    """``inline_gathers`` lets exactly one while body hold that many
    gathers beside its own one; every other body still holds one."""
    one = GOOD_HLO.replace("  ROOT %r =", EXTRA_GATHER_LINE + "  ROOT %r =")
    assert check_collective_structure(one, inline_gathers=1) == []
    assert check_collective_structure(one, inline_gathers=2) != []
    assert check_collective_structure(one) != []
    second = EXTRA_GATHER_LINE.replace("%ag2", "%agb2").replace("%src",
                                                                "%src2")
    both = one.replace("  ROOT %r2 =", second + "  ROOT %r2 =")
    vio = check_collective_structure(both, inline_gathers=1)
    assert [v.kind for v in vio] == ["extra-collective"], \
        [str(v) for v in vio]


def test_forbidden_all_reduce_is_pinned():
    text = GOOD_HLO.replace("all-gather", "all-reduce")
    vio = check_collective_structure(text, n_rounds=3)
    kinds = {v.kind for v in vio}
    assert "forbidden-collective" in kinds, [str(v) for v in vio]
    # with its gather gone, the sweep also lost its per-round exchange
    assert "missing-collective" in kinds


def test_wrong_trip_count_is_pinned():
    text = GOOD_HLO.replace('"n":"3"', '"n":"4"', 1)
    vio = check_collective_structure(text, n_rounds=3)
    assert any(v.kind == "trip-count-mismatch" and v.round == 4
               and "2S = 6" in v.detail for v in vio), [str(v) for v in vio]
    # two segments of 3 and 4 rounds are a different sweep
    assert check_collective_structure(text, n_rounds=[3, 4]) != []


def test_untiled_gather_is_pinned():
    # result grows to f64[16] = 128 B, but 4 participants x 16 B = 64 B
    text = GOOD_HLO.replace("%ag = f64[8]{0} all-gather",
                            "%ag = f64[16]{0} all-gather")
    vio = check_collective_structure(text)
    assert any(v.kind == "untiled-all-gather" and "ag" in v.detail
               for v in vio), [str(v) for v in vio]


def test_single_device_plan_lowers_collective_free():
    plan = build_plan(laplace_2d(13, 11), method="hbmc", validate="off")
    assert check_plan_collectives(plan) == []


def test_mesh_plan_collective_proof_subprocess():
    """The full mesh proof (one tiled all-gather per round, 2S trips, no
    reductions) needs >1 device, so it runs in a forced-host-device
    subprocess — the same configuration the CI analysis job uses."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--problems", "laplace2d",
         "--methods", "hbmc", "--collectives"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "all 1 audits clean" in out.stdout


# ---------------------------------------------------------------------------
# 3. Traffic model: static == measured, inflation witnessed by term.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spmv_format", ("ell", "sell"))
def test_traffic_static_matches_measured(spmv_format):
    plan = build_plan(laplace_2d(13, 11), method="hbmc",
                      spmv_format=spmv_format, validate="off")
    rep = traffic_report(plan)
    by_name = {t.name: t for t in rep.terms}
    for name in ("apply", "spmv/gather"):
        term = by_name[name]
        assert term.measured_bytes is not None
        assert term.relative_error < 0.01, (name, term)
    assert check_plan_traffic(plan) == []
    assert rep.iteration_bytes > 0 and rep.arithmetic_intensity > 0


def test_traffic_inflation_is_pinned_to_term():
    plan = build_plan(laplace_2d(13, 11), method="hbmc", validate="off")
    rep = traffic_report(plan)
    doctored = tuple(
        dataclasses.replace(t, static_bytes=t.static_bytes * 1.3)
        if t.name == "apply" else t for t in rep.terms)
    vio = compare_traffic(doctored)
    assert [v.kind for v in vio] == ["traffic-model-mismatch"]
    assert "term apply" in vio[0].detail, vio[0].detail


def test_traffic_requires_round_major():
    plan = build_plan(laplace_2d(9, 8), method="mc", layout="index",
                      validate="off")
    with pytest.raises(ValueError, match="round_major"):
        traffic_report(plan)


# ---------------------------------------------------------------------------
# 4. Bench gate: committed snapshots self-gate, doctored ones fail.
# ---------------------------------------------------------------------------

def _snapshot(name="BENCH_trisolve.json"):
    return json.loads((BENCH_DIR / name).read_text())


def test_bench_gate_self_passes_on_every_snapshot():
    snaps = sorted(BENCH_DIR.glob("BENCH_*.json"))
    assert snaps, "no committed bench snapshots found"
    for path in snaps:
        doc = json.loads(path.read_text())
        assert bench_gate(doc, doc) == [], path.name


def test_bench_gate_catches_doctored_regression():
    base = _snapshot()
    cand = copy.deepcopy(base)
    rec = cand["results"][0]
    rec["apply_us"] *= 3.0
    vio = bench_gate(base, cand)
    assert len(vio) == 1 and vio[0].kind == "perf-regression"
    # the witness names the exact metric path, id keys included
    assert "apply_us" in vio[0].detail
    assert str(rec["problem"]) in vio[0].detail


def test_bench_gate_catches_iteration_growth():
    base = _snapshot()
    cand = copy.deepcopy(base)
    cand["results"][0]["iterations"] += 10
    vio = bench_gate(base, cand)
    assert any(v.kind == "iteration-regression" and "iterations" in v.detail
               for v in vio), [str(v) for v in vio]


def test_bench_gate_schema_drift_is_a_failure():
    base = _snapshot()
    cand = copy.deepcopy(base)
    del cand["results"][0]["solve_us"]
    vio = bench_gate(base, cand)
    assert any(v.kind == "missing-metric" and "solve_us" in v.detail
               for v in vio)


def test_bench_gate_throughput_direction():
    base = {"schema": "t/v1", "rhs_per_s": 100.0}
    assert bench_gate(base, {"schema": "t/v1", "rhs_per_s": 90.0}) == []
    vio = bench_gate(base, {"schema": "t/v1", "rhs_per_s": 50.0})
    assert vio and vio[0].kind == "perf-regression"


def test_bench_gate_refuses_vacuous_pass():
    vio = bench_gate({"foo": 1}, {"foo": 1})
    assert vio and vio[0].kind == "no-metrics"


def test_bench_gate_cli_smoke_and_doctored(tmp_path, capsys):
    rc = analysis_main(["bench-gate", "--smoke",
                        "--baseline-dir", str(BENCH_DIR)])
    out = capsys.readouterr().out
    assert rc == 0 and "gate(s) passed" in out

    cand = _snapshot()
    cand["results"][0]["apply_us"] *= 3.0
    cpath = tmp_path / "cand.json"
    cpath.write_text(json.dumps(cand))
    wpath = tmp_path / "witness.json"
    rc = analysis_main(["bench-gate", "--baseline-dir", str(BENCH_DIR),
                        "--candidate", str(cpath),
                        "--witness-json", str(wpath)])
    capsys.readouterr()
    assert rc == 1
    witnesses = json.loads(wpath.read_text())
    assert any("apply_us" in w["detail"] for w in witnesses)


def test_audit_cli_runs_new_linters(capsys):
    rc = analysis_main(["--problems", "laplace2d", "--methods", "hbmc",
                        "--validate", "deep", "--dtype-flow", "--traffic"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "all 1 audits clean" in out


def test_deep_admission_rejects_contract_breaker():
    """A plan whose precision contract cannot hold (its own dtype absent
    from the allowed set) is refused at deep validation with dtype-flow
    witnesses — the same path PlanCache admission takes."""
    plan = build_plan(laplace_2d(9, 8), method="hbmc", validate="off")
    bad = PrecisionContract(name="impossible", vector="float32",
                            accum="float32", tables="float32")
    vio = check_plan_dtype_flow(plan, contract=bad)
    assert vio and all(v.kind in ("stray-dtype", "accum-dtype",
                                  "silent-demotion", "silent-promotion")
                       for v in vio)
    with pytest.raises(ScheduleError):
        from repro.analysis import assert_plan_dtype_flow
        assert_plan_dtype_flow(plan, contract=bad, context="impossible")
