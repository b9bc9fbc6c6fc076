"""Resolve a cell of ``BENCHMARK.json`` to its files, by name alone.

A cell names a configuration and a traffic mix; each lives in files of
its own that are found from the name, so a later change adds a cell, a
configuration, a traffic mix or a metric by adding files and entries:

    bench/configs/<config>.json    sizes, plan settings, precision, limits
    bench/configs/<config>.py      ``matrix(cfg)``: the system A, seeded
    bench/traffic/<traffic>.json   parameters; ``driver`` names the driver
    bench/drivers/<driver>.py      ``Driver``: one general driver per kind
    bench/metrics/<metric>.py      ``read(run)``: one reader per quantity;
                                   ``<name>.<cells>`` falls back to
                                   ``<name>.py``, so a quantity split by the
                                   end-to-end metric it moves has one reader
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

PACKAGE = Path(__file__).resolve().parent
ROOT = PACKAGE.parent


def load_module(path: Path, name: str):
    """Import one file as a module (names may hold dots, as metrics do)."""
    if not path.is_file():
        raise FileNotFoundError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    reader: object          # module with ``read(run) -> float | None``
    end_to_end: bool
    workloads: list | None  # None: every cell that reports what it moves


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    matrix_module: object
    driver_module: object
    metrics: list           # every metric this cell reports, both kinds

    def reported(self, trace: bool) -> list:
        return [m for m in self.metrics if m.end_to_end != trace]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def reader_path(name: str, package: Path = PACKAGE) -> Path:
    """``metrics/<name>.py``, else that of ``name`` less its last
    ``.``-suffix, and so on: ``idle_share.step`` is read by
    ``idle_share.py`` unless ``idle_share.step.py`` exists."""
    parts = name.split(".")
    for k in range(len(parts), 0, -1):
        path = package / "metrics" / (".".join(parts[:k]) + ".py")
        if path.is_file():
            return path
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{package / 'metrics'}")


def _metric(entry: dict, end_to_end: bool, package: Path) -> Metric:
    reader = load_module(reader_path(entry["name"], package),
                         f"bench_metric_{entry['name']}")
    return Metric(name=entry["name"], unit=entry["unit"],
                  better=entry["better"], source=entry["source"],
                  reader=reader, end_to_end=end_to_end,
                  workloads=entry.get("workloads"))


def resolve(workload: str, bench: dict | None = None,
            package: Path = PACKAGE) -> Cell:
    """The cell named ``workload`` with every file it needs loaded."""
    bench = load_benchmark(package.parent) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    name, config, traffic = workload, w["config"], w["traffic"]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == config)
    root = package.parent
    with open(root / cfg_entry["file"]) as f:
        config_data = json.load(f)
    with open(package / "traffic" / f"{traffic}.json") as f:
        traffic_data = json.load(f)
    matrix_module = load_module(package / "configs" / f"{config}.py",
                                f"bench_config_{config}")
    driver_module = load_module(
        package / "drivers" / f"{traffic_data['driver']}.py",
        f"bench_driver_{traffic_data['driver']}")

    e2e = [_metric(m, True, package) for m in bench["end_to_end"]]
    e2e = [m for m in e2e if m.workloads is None or name in m.workloads]
    e2e_names = {m.name for m in e2e}
    layer = []
    for entry in bench["per_layer"]:
        wl = entry.get("workloads")
        if (name in wl) if wl is not None else entry["moves"] in e2e_names:
            layer.append(_metric(entry, False, package))
    return Cell(name=name, chips=int(w["chips"]), config=config_data,
                traffic=traffic_data, matrix_module=matrix_module,
                driver_module=driver_module, metrics=e2e + layer)
