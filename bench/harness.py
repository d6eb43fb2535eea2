"""Find everything a cell needs by the names in ``BENCHMARK.json``.

Nothing here names a configuration, a traffic mix, an algorithm or a
metric.  A cell's entry names its configuration and its mix; the files
are found as

    bench/configs/<config>.json       sizes, algorithm, precision, limits
    bench/traffic/<traffic>.json      the mix (``bench/traffic.py``)
    bench/algos/<algo>.py             inputs and calls of the system
    bench/reference/<algo>.py         its plain reference
    bench/work/<algo>.py              its algorithmic work per round
    bench/metrics/<metric>.py         one reader per per-layer metric

so a later change adds a cell, a mix, an algorithm or a metric as new
files and new entries, and edits none that exist.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import NamedTuple

from bench import traffic as traffic_mod

BENCH = Path(__file__).resolve().parent


class Cell(NamedTuple):
    workload: dict        # the BENCHMARK.json entry
    config: dict          # the configuration file, with its "name"
    traffic: traffic_mod.Traffic
    end_to_end: list      # metric entries this cell reports
    per_layer: list


def load_benchmark(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _reports(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def find_cell(bench: dict, workload: str, bench_dir: Path = BENCH) -> Cell:
    """The cell called ``workload``, with its files read."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = json.loads((bench_dir.parent / entry["file"]).read_text())
    config["name"] = entry["name"]
    traffic = traffic_mod.load(bench_dir / "traffic" / f"{w['traffic']}.json",
                               config["n"])
    return Cell(w, config, traffic,
                [m for m in bench["end_to_end"] if _reports(m, workload)],
                [m for m in bench["per_layer"] if _reports(m, workload)])


def _module(kind: str, algo: str) -> ModuleType:
    return importlib.import_module(f"bench.{kind}.{algo}")


def algo(config: dict) -> ModuleType:
    return _module("algos", config["algo"])


def reference(config: dict) -> ModuleType:
    return _module("reference", config["algo"])


def work(config: dict) -> ModuleType:
    return _module("work", config["algo"])


def metric_reader(name: str, bench_dir: Path = BENCH):
    """The ``read`` function of ``bench/metrics/<name>.py`` (the name may
    hold dots, so the file is loaded by its path)."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
