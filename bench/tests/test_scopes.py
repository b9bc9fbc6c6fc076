"""The scope reduction on synthetic traces (no chip, no libtpu)."""
import pytest

from bench import breakdown, scopes as S, trace as T

MS = 1_000_000
D0 = "/device:TPU:0"
SWEEP = "jit(run)/pcg.vector/while/body/pcg.sweep/jit(fused_solve)/while"


def _scoped(ops, spans, scope_names, program=(), loops=()):
    return S.ScopedTrace(trace=T.Trace(devices={D0: ops}, host_spans=spans),
                         scopes=scope_names, program_spans=list(program),
                         loops=frozenset(loops))


# the synthetic traces of bench/tests/test_trace.py, without scopes
EXISTING = [
    ({D0: [("%while.3 = (f32[8]) while(...)", 10 * MS, 60 * MS),
           ("%fusion.1 = f32[8] fusion(...)", 10 * MS, 30 * MS),
           ("%fusion.2 = f32[8] fusion(...)", 35 * MS, 40 * MS),
           ("%fusion.7 = f32[8] fusion(...)", 50 * MS, 60 * MS),
           ("%copy.4 = f32[8] copy(...)", 90 * MS, 120 * MS)]},
     [("bench.window", 0, 100 * MS)]),
    ({D0: [("fusion", 5 * MS, 45 * MS), ("fusion", 80 * MS, 100 * MS)]},
     [("bench.window", 0, 100 * MS), ("bench.solve", 0, 50 * MS),
      ("bench.rhs", 50 * MS, 70 * MS)]),
    ({D0: [("fusion", 0, 4 * MS), ("all-gather.3", 4 * MS, 8 * MS)],
      "/device:TPU:1": [("fusion", 0, 2 * MS)]},
     [("bench.window", 0, 10 * MS)]),
]


@pytest.mark.parametrize("devices,spans", EXISTING)
def test_base_numbers_are_bench_traces_own(devices, spans):
    tr = T.Trace(devices=devices, host_spans=spans)
    s = S.reduce(S.ScopedTrace(trace=tr, scopes={}, program_spans=[]))
    assert s.base == T.reduce(tr)
    # no scopes: plain kinds, as bench.trace names them
    assert s.device_ops == T.reduce(tr).device_ops
    assert s.calls == {} and s.scope_s == {}


def test_scope_of_takes_the_innermost_program_scope():
    assert S.scope_of(SWEEP + "/body/closed_call/add") == "pcg.sweep"
    assert S.scope_of("jit(run)/pcg.vector/while/cond/lt") == "pcg.vector"
    assert S.scope_of("jit(run)/while/body/add") is None
    assert S.scope_of("jit(run)/xpcg.vectorx/add") is None


def test_ops_are_named_by_scope_and_kind_and_unscoped_ops_inherit():
    ops = [("while.5", 10 * MS, 50 * MS),
           ("fusion.1", 10 * MS, 20 * MS),
           ("copy.2", 20 * MS, 30 * MS),         # no scope: inherits sweep
           ("fusion.1", 40 * MS, 50 * MS),
           ("fusion.9", 60 * MS, 70 * MS),
           ("copy.3", 80 * MS, 90 * MS)]          # no scope, no parent
    names = {"while.5": SWEEP, "fusion.1": SWEEP + "/body/closed_call/mul",
             "fusion.9": "jit(run)/pcg.vector/while/body/dot"}
    scoped = {k: S.scope_of(v) for k, v in names.items()}
    s = S.reduce(_scoped(ops, [("bench.window", 0, 100 * MS)], scoped))
    got = dict(s.device_ops)
    assert got["pcg.sweep/fusion"] == pytest.approx(0.020)
    assert got["pcg.sweep/copy"] == pytest.approx(0.010)
    assert got["pcg.sweep/while"] == pytest.approx(0.010)    # its own gap
    assert got["pcg.vector/fusion"] == pytest.approx(0.010)
    assert got["copy"] == pytest.approx(0.010)
    assert s.scope_s == {"pcg.sweep": pytest.approx(0.030),
                         "pcg.vector": pytest.approx(0.010)}
    # the share counts ops with a scope of their own: not the copies
    assert s.scoped_share == pytest.approx(60.0)
    # idle inside the sweep's while (30-40) is the device's loop
    gaps = dict(s.idle_gaps)
    assert gaps[S.LOOP] == pytest.approx(0.010)
    assert gaps[S.NO_SPAN] == pytest.approx(0.040)


SCOPES = {"while.5": "pcg.sweep", "while.8": "pcg.sweep", "s": "pcg.sweep",
          "v": "pcg.vector", "m": "pcg.spmv"}
LOOPS = {"while.5"}          # while.8: a loop the compiler made (emulation)


def _iteration(t0):
    """One PCG iteration's ops from t0 (ms): vector and SpMV work, split
    into interleaved runs, then a 10 ms sweep loop of two steps."""
    return [("v", t0 * MS, (t0 + 1) * MS),
            ("m", (t0 + 1) * MS, (t0 + 4) * MS),
            ("v", (t0 + 4) * MS, (t0 + 5) * MS),
            ("m", (t0 + 5) * MS, int((t0 + 5.5) * MS)),
            ("s", int((t0 + 5.5) * MS), (t0 + 6) * MS),   # a sweep reshape
            ("v", (t0 + 6) * MS, int((t0 + 6.5) * MS)),
            ("while.8", int((t0 + 6.5) * MS), int((t0 + 6.6) * MS)),
            ("while.5", (t0 + 7) * MS, (t0 + 17) * MS),    # the sweep loop
            ("s", (t0 + 7) * MS, (t0 + 11) * MS),
            ("s", (t0 + 12) * MS, (t0 + 17) * MS)]


def test_calls_are_anchored_on_the_sweeps_loops():
    ops = ([("while.5", -5 * MS, 2 * MS)]    # a sweep cut by the start
           + _iteration(2) + _iteration(19) + _iteration(90))  # 97-107 cut
    s = S.reduce(_scoped(ops, [("bench.window", 0, 100 * MS)], SCOPES,
                         loops=LOOPS))
    assert s.calls["pcg.sweep"] == [pytest.approx(0.010)] * 2
    # between the two whole sweep loops: one SpMV call in two runs, and
    # the vector work of one iteration; the first iteration's ops follow
    # no whole sweep, the third's precede none
    assert s.calls["pcg.spmv"] == [pytest.approx(0.0035)]
    assert s.calls["pcg.vector"] == [pytest.approx(0.0025)]
    assert S.median_call_s(s, "pcg.sweep") == pytest.approx(0.010)


def test_idle_in_a_while_goes_to_the_loop_before_any_host_span():
    ops = [("%while.1 = () while(...)", 10 * MS, 40 * MS),
           ("fusion.1", 10 * MS, 20 * MS), ("fusion.2", 30 * MS, 40 * MS),
           ("fusion.3", 60 * MS, 100 * MS)]
    spans = [("bench.window", 0, 100 * MS), ("bench.solve", 0, 100 * MS)]
    program = [("repro.solve.pcg", 5 * MS, 100 * MS)]
    s = S.reduce(_scoped(ops, spans, {}, program))
    gaps = dict(s.idle_gaps)
    assert gaps[S.LOOP] == pytest.approx(0.010)              # 20-30
    assert gaps["repro.solve.pcg"] == pytest.approx(0.025)   # 5-10, 40-60
    assert gaps["bench.solve"] == pytest.approx(0.005)       # 0-5
    assert S.NO_SPAN not in gaps


def test_the_innermost_program_span_wins_then_the_innermost_bench_span():
    ops = [("fusion", 90 * MS, 100 * MS)]
    spans = [("bench.window", 0, 100 * MS), ("bench.solve", 0, 60 * MS),
             ("bench.inner", 10 * MS, 20 * MS)]
    program = [("repro.solve.outer", 30 * MS, 60 * MS),
               ("repro.solve.embed", 40 * MS, 50 * MS)]
    s = S.reduce(_scoped(ops, spans, {}, program))
    gaps = dict(s.idle_gaps)
    assert gaps["bench.solve"] == pytest.approx(0.020)        # 0-10, 20-30
    assert gaps["bench.inner"] == pytest.approx(0.010)
    assert gaps["repro.solve.outer"] == pytest.approx(0.020)  # 30-40, 50-60
    assert gaps["repro.solve.embed"] == pytest.approx(0.010)
    assert gaps[S.NO_SPAN] == pytest.approx(0.030)            # 60-90
    assert s.host_spans == {"repro.solve.embed": [1, pytest.approx(0.01)],
                            "repro.solve.outer": [1, pytest.approx(0.03)]}


def _readings(summary, steps=4):
    cfg = {"n": 9, "nnz": 33, "dtype": "float32"}
    return breakdown.readings(summary, cfg, "TPU v5 lite", 1, steps)


def test_a_trace_without_scopes_gives_no_readings():
    ops = [("fusion.1", 10 * MS, 20 * MS), ("fusion.2", 30 * MS, 40 * MS)]
    s = S.reduce(_scoped(ops, [("bench.window", 0, 50 * MS)], {}))
    assert all(v is None for v in _readings(s).values())


def test_readings_from_whole_calls():
    ops = _iteration(0) + _iteration(17) + _iteration(34)
    program = [("repro.solve.embed", 0, 1 * MS)]
    s = S.reduce(_scoped(ops, [("bench.window", 0, 51 * MS)], SCOPES,
                         program, LOOPS))
    r = _readings(s, steps=4)
    assert r["sweep_step_us"] == pytest.approx(2500.0)       # 10 ms / 4
    sweep_b = 2 * (21 * 4 + 12 * 4)                          # n 9, nnz 33
    assert r["sweep_roofline"] == pytest.approx(
        100 * sweep_b / (819e9 * 0.010))
    assert r["spmv_roofline"] == pytest.approx(
        100 * (sweep_b / 2) / (819e9 * 0.0035))
    assert r["embed_ms_traced"] == pytest.approx(1.0)
    assert r["extract_ms_traced"] is None


def test_a_trace_that_lost_events_is_flagged_and_read_as_none(capsys):
    names = {"while.5": "pcg.sweep", "s": "pcg.sweep"}
    ops = [("while.5", 0, 10 * MS), ("s", 0, 10 * MS),
           ("while.5", 20 * MS, 30 * MS), ("s", 20 * MS, 30 * MS)]
    window = ("bench.window", 0, 100 * MS)
    # the host sits in a solve span the profiler never closed
    lost = S.reduce(_scoped(ops, [window], names, loops=LOOPS))
    assert lost.lost_events
    assert "lost events" in capsys.readouterr().err
    assert S.median_call_s(lost, "pcg.sweep") is None
    # a recorded solve span covers the quiet stretch
    assert S.reduce(_scoped(ops, [window, ("bench.solve", 0, 90 * MS)],
                            names, loops=LOOPS)).lost_events
    # the device is quiet because the host is elsewhere: not a loss
    idle = S.reduce(_scoped(ops, [window, ("bench.solve", 0, 30 * MS),
                                  ("bench.rhs", 30 * MS, 98 * MS)], names,
                            loops=LOOPS))
    assert not idle.lost_events
    assert S.median_call_s(idle, "pcg.sweep") == pytest.approx(0.010)
    # the base numbers stay whatever the flag
    assert lost.base == T.reduce(T.Trace(devices={D0: ops},
                                         host_spans=[window]))


def test_hlo_text_names_the_ops():
    text = """
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(run)/pcg.vector/while/body/pcg.spmv/dot" stack_frame_id=3}
  ROOT %while.2 = (f32[8]) while(%t), body=%b, metadata={op_name="SWEEP"}
  %copy.1 = f32[8]{0} copy(%fusion.3)
  %add.4 = f32[8]{0} add(%a, %b), metadata={op_name="jit(f)/add"}
  %while.7 = (f64[8]) while(%u), body=%c, metadata={op_name="SWEEP/body/dot_general"}
""".replace("SWEEP", SWEEP)
    assert S.hlo_scopes([text]) == {"fusion.3": ("pcg.spmv", False),
                                    "while.2": ("pcg.sweep", True),
                                    "while.7": ("pcg.sweep", False)}


def test_read_matches_bench_trace_on_a_recorded_trace(tmp_path):
    """A trace recorded on the CPU: the same events as bench.trace reads,
    and the program's spans beside them."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("repro.solve.pcg"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = T.find_xplane(tmp_path)
    st = S.read(path, {})
    assert st.trace == T.read_xplane(path)
    assert [n for n, _, _ in st.program_spans] == ["repro.solve.pcg"]
