"""The harness finds every file by name and refuses to measure off chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec
from bench.tests.small import drive

WORKLOADS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_workload_resolves_its_files(workload):
    cell = spec.resolve(workload)
    assert hasattr(cell.matrix_module, "matrix")
    assert hasattr(cell.driver_module, "Driver")
    names = {m.name for m in cell.reported(trace=False)}
    assert "setup_s" in names and len(names) >= 2
    assert cell.reported(trace=True), "no per-layer metric"
    for m in cell.metrics:
        assert callable(m.reader.read)


def test_benchmark_file_keeps_to_its_shape():
    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for c in bench["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        assert set(c) == {"name", "source", "file", "reduced", "why"}


def test_a_configuration_added_as_files_is_picked_up(tmp_path):
    """A new configuration and cell need new files and entries only."""
    pkg = tmp_path / "bench"
    shutil.copytree(spec.PACKAGE, pkg,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.load_benchmark()
    cfg = json.loads((pkg / "configs" / "heat2d.json").read_text())
    cfg["matrix"]["m"] = 30
    (pkg / "configs" / "tiny_heat.json").write_text(json.dumps(cfg))
    (pkg / "configs" / "tiny_heat.py").write_text(
        "from bench import matrices\n\n\ndef matrix(cfg):\n"
        "    return matrices.heat_step_2d(cfg['matrix']['m'], 0.5)\n")
    bench["configs"].append({"name": "tiny_heat", "source": "x",
                             "file": "bench/configs/tiny_heat.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_heat.timestep",
                               "config": "tiny_heat",
                               "traffic": "timestep", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "heat2d.timestep" in m.get("workloads", []):
            m["workloads"].append("tiny_heat.timestep")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.resolve("tiny_heat.timestep", package=pkg)
    assert cell.config["matrix"]["m"] == 30
    assert cell.matrix_module.matrix(cell.config).shape == (900, 900)
    assert {m.name for m in cell.reported(False)} == {"solve_s.step",
                                                      "setup_s"}


def _run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "heat2d.timestep", "--seed", "3", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_off_the_chip_it_exits_nonzero_and_prints_no_result():
    p = _run_cli(spec.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_it_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and bench/: no result."""
    shutil.copytree(spec.PACKAGE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload", WORKLOADS)
def test_a_small_run_is_correct_and_reports_its_metrics(workload):
    out, run = drive(workload)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert "setup_s" in out["metrics"]
    assert len(out["metrics"]) >= 2
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name,reader", [
    ("idle_share.step", "idle_share.py"),
    ("iter_roofline.step", "iter_roofline.py"),
    ("solve_s.step", "solve_s.py"),
    ("setup_s", "setup_s.py"),
])
def test_a_split_metric_falls_back_to_its_quantitys_reader(name, reader):
    assert spec.reader_path(name).name == reader


def test_a_metric_without_a_reader_is_an_error():
    with pytest.raises(FileNotFoundError):
        spec.reader_path("no_such_metric.step")


def test_traced_window_stops_the_profiler_mid_solve(tmp_path):
    """The profiler stops after its seconds, in the middle of a solve;
    that solve completes, is recorded and checks out."""
    import copy

    from bench import reference, run as harness
    from bench.records import Run
    from bench.tests.small import small_config

    cell = spec.resolve("poisson2d.oneshot")
    cfg = small_config(cell, m=64)
    harness.enable_precision(cfg)
    a = cell.matrix_module.matrix(cfg)
    driver = cell.driver_module.Driver(a.copy(), cfg, cell.traffic)
    driver.setup()
    driver.prepare(5, 0.01)
    run = Run(workload=cell.name, config=copy.deepcopy(cfg),
              traffic=cell.traffic, device_kind="cpu", n_devices=1,
              seconds=0.01)
    harness.traced_window(driver, run, 0.01, str(tmp_path))
    assert len(run.solves) == 1 and run.window_s > 0.01
    assert list(tmp_path.rglob("*.xplane.pb"))
    checks = reference.judge(a, run.answers(), cfg["limits"])
    assert reference.is_correct(checks), checks
