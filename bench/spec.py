"""Find a cell's pieces by name: ``BENCHMARK.json`` names the cell, its
configuration file and traffic mix; ``bench/traffic/<traffic>.json`` holds
the mix and ``bench/metrics/<metric>.py`` the reader of each metric. Adding
a cell, a mix or a metric adds files and entries; no file here changes."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    chips: int
    config_path: Path
    traffic_path: Path
    end_to_end: list           # metric entries of BENCHMARK.json
    per_layer: list


def benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def traffic_path(name: str, root: Path = ROOT) -> Path:
    return root / "bench" / "traffic" / f"{name}.json"


def cell(name: str, root: Path = ROOT) -> Cell:
    bm = benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in bm["configs"]}[w["config"]]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]
    return Cell(name=name, chips=w["chips"], config_path=root / conf["file"],
                traffic_path=traffic_path(w["traffic"], root),
                end_to_end=[m for m in bm["end_to_end"] if applies(m)],
                per_layer=[m for m in bm["per_layer"] if applies(m)])


def reader(metric: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
