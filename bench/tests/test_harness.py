"""The harness finds a cell's files by the names in BENCHMARK.json, so a
later change adds a configuration, a mix and a metric as new files."""

import json
import shutil
import subprocess
import sys

import pytest

from bench import harness

from conftest import ROOT


def _dummy_tree(tmp_path):
    """A checkout holding a new configuration, mix and metric, added as
    files and entries beside the committed ones."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bdir = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(ROOT / "bench" / sub, bdir / sub)
    cfg = json.loads((bdir / "configs" / "mp-knn500k-p100.json").read_text())
    cfg["n"] = 1234
    (bdir / "configs" / "dummy-cfg.json").write_text(json.dumps(cfg))
    (bdir / "traffic" / "dummy-mix.json").write_text(json.dumps(dict(
        conditions=dict(drop_prob=0.5), batch_share=0.5, rounds=6,
        record_every=3)))
    (bdir / "metrics" / "dummy.metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"].append(dict(name="dummy-cfg", source="x",
                                 file="bench/configs/dummy-cfg.json",
                                 reduced=[], why="x"))
    bench["workloads"].append(dict(name="dummy-cell", config="dummy-cfg",
                                   traffic="dummy-mix", chips=1, why="x"))
    bench["per_layer"].append(dict(name="dummy.metric", unit="ms",
                                   better="lower", source="program_span",
                                   layer="scan", moves="events_per_s",
                                   workloads=["dummy-cell"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_finds_added_config_mix_and_metric(tmp_path):
    root = _dummy_tree(tmp_path)
    cell = harness.find_cell(harness.load_benchmark(root), "dummy-cell",
                             bench_dir=root / "bench")
    assert cell.config["name"] == "dummy-cfg" and cell.config["n"] == 1234
    assert cell.traffic.batch == 617 and cell.traffic.rounds == 6
    assert cell.traffic.conditions["drop_prob"] == 0.5
    assert cell.traffic.conditions["stale_prob"] == 0.0
    names = [m["name"] for m in cell.per_layer]
    assert "dummy.metric" in names and "op_ms_per_round.admm_primal" \
        not in names
    read = harness.metric_reader("dummy.metric", bench_dir=root / "bench")
    assert read(None) == 42.0
    assert harness.algo(cell.config).SCAN_MODULE == "_scenario_scan"


def test_committed_cells_resolve():
    bench = harness.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = harness.find_cell(bench, w["name"])
        assert cell.config["algo"] in ("mp", "cl")
        assert {m["name"] for m in cell.end_to_end} == {"events_per_s",
                                                        "setup_s"}
        for m in cell.per_layer:
            harness.metric_reader(m["name"])
        harness.reference(cell.config), harness.work(cell.config)


def test_unknown_condition_is_refused(tmp_path):
    from bench import traffic
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(dict(conditions=dict(dropprob=0.1),
                                 batch_share=0.1, rounds=4, record_every=2)))
    with pytest.raises(ValueError):
        traffic.load(p, 100)


def test_large_seeds_are_hashed_not_truncated():
    from bench import traffic
    assert traffic.call_seed(5, 0) != traffic.call_seed(5 + 2 ** 32, 0)
    assert 0 <= traffic.call_seed(2 ** 40, 3) < 2 ** 31


def test_refuses_to_run_without_the_system(tmp_path):
    """In a directory holding only BENCHMARK.json and bench/, the run
    exits non-zero and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "mp-knn500k-lossy10-r100", "--seed", "1",
                        "--seconds", "1"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_refuses_to_run_on_the_cpu():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "cl-knn100k-churn5-r100", "--seed", "1",
                        "--seconds", "1"], cwd=ROOT, capture_output=True,
                       text=True, timeout=120,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0 and p.stdout.strip() == ""
