"""The trace reduction on synthetic traces (no chip, no libtpu)."""
import pytest

from bench import trace as T

MS = 1_000_000


def _trace(devices, spans):
    return T.Trace(devices=devices, host_spans=spans)


def test_busy_counts_leaf_ops_only():
    window = ("bench.window", 0, 100 * MS)
    ops = [("%while.3 = (f32[8]) while(...)", 10 * MS, 60 * MS),
           ("%fusion.1 = f32[8] fusion(...)", 10 * MS, 30 * MS),
           ("%fusion.2 = f32[8] fusion(...)", 35 * MS, 40 * MS),
           ("%fusion.7 = f32[8] fusion(...)", 50 * MS, 60 * MS),
           ("%copy.4 = f32[8] copy(...)", 90 * MS, 120 * MS)]  # clipped
    tr = T.Trace(devices={"/device:TPU:0": ops}, host_spans=[window])
    s = T.reduce(tr)
    assert s.window_s == pytest.approx(0.1)
    # leaves: 10-30, 35-40, 50-60, 90-100; the while's gaps are idle
    assert s.busy_s == pytest.approx(0.045)
    assert s.idle_share == pytest.approx(55.0)
    ops_s = dict(s.device_ops)
    assert s.device_ops[0] == ["fusion", pytest.approx(0.035)]
    assert ops_s["while"] == pytest.approx(0.015)   # its own gaps
    assert ops_s["copy"] == pytest.approx(0.010)    # inside the window
    assert s.collective_share == 0.0


def test_op_kind_drops_the_instruction_number():
    assert T.op_kind("%dynamic-slice_reduce_fusion.14 = f32[7991] "
                     "fusion(...)") == "dynamic-slice_reduce_fusion"
    assert T.op_kind("%all-gather.3 = f32[8] all-gather(...)") == \
        "all-gather"


def test_idle_gaps_split_over_the_host_spans_they_overlap():
    spans = [("bench.window", 0, 100 * MS),
             ("bench.solve", 0, 50 * MS),
             ("bench.rhs", 50 * MS, 70 * MS)]
    ops = [("fusion", 5 * MS, 45 * MS), ("fusion", 80 * MS, 100 * MS)]
    s = T.reduce(_trace({"/device:TPU:0": ops}, spans))
    gaps = dict(s.idle_gaps)
    assert gaps["bench.solve"] == pytest.approx(0.010)   # 0-5, 45-50
    assert gaps["bench.rhs"] == pytest.approx(0.020)     # 50-70
    assert gaps["no bench span"] == pytest.approx(0.010)  # 70-80
    assert s.idle_gaps[0][0] == "bench.rhs"


def test_devices_are_averaged_and_collectives_counted_on_device_0():
    spans = [("bench.window", 0, 10 * MS)]
    d0 = [("fusion", 0, 4 * MS), ("all-gather.3", 4 * MS, 8 * MS)]
    d1 = [("fusion", 0, 2 * MS)]
    s = T.reduce(_trace({"/device:TPU:0": d0, "/device:TPU:1": d1}, spans))
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx(0.005)
    assert s.idle_share == pytest.approx(50.0)
    assert s.collective_share == pytest.approx(50.0)


def test_no_window_or_no_device_work_is_an_error():
    with pytest.raises(ValueError):
        T.reduce(_trace({"/device:TPU:0": [("f", 0, 1)]}, []))
    with pytest.raises(ValueError):
        T.reduce(_trace({}, [("bench.window", 0, 10)]))


def test_read_xplane_finds_host_spans_of_a_recorded_trace(tmp_path):
    """A trace recorded here on the CPU: the harness's host spans come
    back; the CPU is no device, so nothing counts as device work."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.solve"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.read_xplane(T.find_xplane(tmp_path))
    names = [n for n, _, _ in tr.host_spans]
    assert T.WINDOW_SPAN in names and "bench.solve" in names
    assert tr.devices == {}
