"""The hpcg3d configuration, its cell and the sweep-occupancy reader, on
the CPU."""
import types

import numpy as np
import pytest

from bench import run as harness, spec
from bench.readings import CONTROL
from bench.records import Run
from bench.tests.small import drive, small_config

CELL = "hpcg3d.oneshot"


def occupancy_reader():
    return spec.load_module(spec.reader_path("sweep_gather_occupancy"),
                            "bench_metric_sweep_gather_occupancy")


def test_configuration_sizes_match_its_generator():
    cell = spec.resolve(CELL)
    a = cell.matrix_module.matrix(cell.config)
    m = cell.config["matrix"]["m"]
    assert a.shape[0] == cell.config["n"] == m ** 3
    assert a.nnz == cell.config["nnz"] == (3 * m - 2) ** 3
    assert np.all(a.diagonal() == 26.0)
    assert cell.config["dtype"] == "float64"


def test_cell_reports_the_sweep_occupancy_when_traced():
    names = {m.name for m in spec.resolve(CELL).reported(True)}
    assert {"sweep_gather_occupancy", "iter_roofline",
            "idle_share.solve"} <= names
    step = {m.name for m in spec.resolve("heat2d.timestep").reported(True)}
    assert "sweep_gather_occupancy.step" in step


def test_reader_reads_none_without_the_counters(monkeypatch):
    """A program whose plans carry no counters (the parent of this
    metric) reads None and does not raise."""
    reader = occupancy_reader()
    assert reader.occupancy(types.SimpleNamespace()) is None
    import repro.core
    monkeypatch.setattr(repro.core, "build_plan",
                        lambda a, **kw: types.SimpleNamespace())
    cell = spec.resolve(CELL)
    run = Run(workload=CELL, config=small_config(cell, 12),
              traffic=cell.traffic, device_kind="cpu", n_devices=1,
              seconds=0.1)
    assert reader.read(run) is None


def test_reader_reads_a_hand_count():
    """m = 12: segments (rounds, lanes, fwd K, bwd K) (32, 18, 4, 26),
    (32, 12, 22, 22), (64, 9, 26, 8), (32, 6, 26, 4) gather
    17,280 + 16,896 + 19,584 + 5,760 = 59,520 values an apply, of which
    the 39,304 - 1,728 = 37,576 off-diagonal entries are live."""
    cell = spec.resolve(CELL)
    harness.enable_precision(cell.config)
    run = Run(workload=CELL, config=small_config(cell, 12),
              traffic=cell.traffic, device_kind="cpu", n_devices=1,
              seconds=0.1)
    assert occupancy_reader().read(run) == pytest.approx(
        100.0 * 37_576 / 59_520)


def test_a_small_run_is_correct():
    out, run = drive(CELL, m=20)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert {"solve_s", "setup_s"} <= set(out["metrics"])


def test_float32_control_at_the_configured_tolerance_is_not_correct():
    """The program one precision below at rtol 1e-7, at the cell's own
    grid: its true residual stalls above the 2e-7 limit (3.4e-7 to
    3.5e-7 against 8.2e-8 to 9.0e-8 in float64 on the CPU)."""
    cell = spec.resolve(CELL)
    m = cell.config["matrix"]["m"]
    out, _ = drive(CELL, m=m, dtype=CONTROL[cell.config["dtype"]])
    assert out["correct"] is False
    assert out["checks"]["true_res_max"]["value"] > \
        out["checks"]["true_res_max"]["limit"]
