"""Program scopes on the PCG's device work and spans on the solve's host path.

The scopes name every op of the compiled PCG loop as SpMV, sweep or
vector work (the ``op_name`` metadata of the compiled HLO, through which
a device trace's ops are named); the spans time the host path into the
report and mark it in a profiler trace under the same names.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_plan
from repro.core.iccg import PCG_SCOPES, SWEEP_SCOPE
from repro.core.matrices import laplace_2d
from repro.core.timing import SOLVE_EMBED, SOLVE_EXTRACT, SOLVE_PCG, span

SCOPE = re.compile(r"(?:^|/)(pcg\.[a-z_]+)(?=/|$)")
# instructions the compiler makes for its own bookkeeping carry no
# metadata: tuples, their elements, constants, layout copies, and fusions
# it builds from its own rewrites (a reduction split into reduce-windows)
COMPILER_MADE = {"parameter", "get-tuple-element", "tuple", "constant",
                 "copy", "bitcast", "fusion"}


@pytest.fixture(scope="module")
def plan():
    # one width, cut where K parts: three segments
    return build_plan(laplace_2d(16, 16), dtype=jnp.float64)


@pytest.fixture(scope="module")
def segmented_plan():
    # its colors' rounds differ in width, and K parts inside each color:
    # eleven segments, five of them one round long
    return build_plan(laplace_2d(20, 20), block_size=8, w=4,
                      dtype=jnp.float64)


def _computations(hlo: str) -> dict:
    """computation name -> [(opcode, op_name or None, line)]."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
            continue
        m = re.match(r"^\s*(?:ROOT )?%\S+ = (.*)$", line)
        if m and cur is not None:
            opcode = re.search(r"\s([a-z][a-z\-]*)\(", " " + m.group(1))
            op = re.search(r'op_name="([^"]*)"', line)
            cur.append((opcode.group(1), op.group(1) if op else None, line))
    return comps


def _scope(op_name):
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


def _body(comps, instr_line):
    return comps[re.search(r"body=%([^,\s]+)", instr_line).group(1)]


@pytest.mark.parametrize("which", ["plan", "segmented_plan"])
@pytest.mark.parametrize("batched", [False, True])
def test_every_op_of_the_compiled_pcg_loop_has_a_scope(request, which,
                                                       batched):
    plan = request.getfixturevalue(which)
    assert plan.n_segments == (3 if which == "plan" else 11)
    fn = plan._pcg_fn(batched, 1e-6, 50, False)
    b = jnp.zeros((plan.slab_m,) + ((2,) if batched else ()))
    hlo = fn.lower(plan._precond.tables, plan._spmv_vals, plan._spmv_cols,
                   b).compile().as_text()
    comps = _computations(hlo)
    entry = next(v for k, v in comps.items() if k.startswith("main"))
    loops = [ln for opc, op, ln in entry
             if opc == "while" and op.endswith("pcg.vector/while")]
    assert len(loops) == 1
    body = _body(comps, loops[0])
    scopes = set()
    for opcode, op_name, line in body:
        if op_name is None:
            assert opcode in COMPILER_MADE, line
            continue
        assert _scope(op_name) in PCG_SCOPES, line
        scopes.add(_scope(op_name))
    assert scopes == set(PCG_SCOPES)
    # the sweep is a forward and a backward loop per segment inside the
    # PCG loop, each wholly a sweep; XLA inlines a loop of one trip, so a
    # one-round segment's steps run in the PCG loop's body
    sweeps = [ln for opc, op, ln in body
              if opc == "while" and _scope(op) == SWEEP_SCOPE]
    assert len(sweeps) == 2 * sum(n > 1 for n, _ in
                                  plan._precond.tables.segments)
    for loop in sweeps:
        inner = [_scope(op) for _, op, _ in _body(comps, loop) if op]
        assert inner and set(inner) == {SWEEP_SCOPE}


def test_the_slab_pcg_carries_the_same_scopes(plan):
    fn = plan._slab_fn(1e-6, 50, 4)
    hlo = fn.lower(plan._precond.tables, plan._spmv_vals, plan._spmv_cols,
                   plan.new_slab_state(2)).compile().as_text()
    found = {_scope(op) for ops in _computations(hlo).values()
             for _, op, _ in ops if op}
    assert set(PCG_SCOPES) <= found


def test_span_times_its_block_and_keeps_the_name():
    seconds = {}
    with span("repro.test.outer", seconds):
        with span("repro.test.inner", seconds):
            pass
    assert set(seconds) == {"repro.test.outer", "repro.test.inner"}
    assert 0 <= seconds["repro.test.inner"] <= seconds["repro.test.outer"]


@pytest.mark.parametrize("path", ["solve", "solve_batched", "solve_slab"])
def test_reports_carry_the_host_path(plan, path):
    rng = np.random.default_rng(4)
    b = rng.standard_normal(plan.n)
    if path == "solve_batched":
        rep = plan.solve_batched(b[:, None], rtol=1e-8)
    else:
        rep = getattr(plan, path)(b, rtol=1e-8)
    assert rep.embed_seconds > 0 and rep.extract_seconds > 0
    assert rep.solve_seconds > 0
    # setup_seconds keeps its meaning: the host work before the PCG
    assert rep.setup_seconds == rep.embed_seconds


def test_solve_spans_appear_in_a_profiler_trace_in_order(plan, tmp_path):
    from jax.profiler import ProfileData
    b = np.ones(plan.n)
    plan.solve(b, rtol=1e-8)                    # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    rep = plan.solve(b, rtol=1e-8)
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro.solve."):
                    spans[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    assert set(spans) == {SOLVE_EMBED, SOLVE_PCG, SOLVE_EXTRACT}
    assert (spans[SOLVE_EMBED][1] <= spans[SOLVE_PCG][0]
            and spans[SOLVE_PCG][1] <= spans[SOLVE_EXTRACT][0])
    # the report's interval lies inside the annotation of the same name
    pcg_ns = spans[SOLVE_PCG][1] - spans[SOLVE_PCG][0]
    assert rep.solve_seconds * 1e9 <= pcg_ns * 1.01 + 1e4
