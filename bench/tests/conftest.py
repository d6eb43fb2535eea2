"""Benchmark tests: run on the host CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They sit outside the repository's ``testpaths``; the graph cache of every
test goes to a temporary directory.
"""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def _graph_cache(tmp_path, monkeypatch):
    from bench import graph
    monkeypatch.setattr(graph, "CACHE", tmp_path / "cache")
