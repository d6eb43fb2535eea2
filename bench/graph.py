"""The deployment's graph, made by the benchmark and cached in the checkout.

The rule is the symmetrised k-nearest-neighbour graph over uniform random
points in the unit square that ``repro.simulate.random_geometric_topology``
also implements (a coarse grid, each point's k nearest searched in its 3x3
cell block).  The benchmark keeps its own copy so that the reference reads
an edge list the program never made; the program receives the same edges
through its public table constructor.

Both forms are cached under ``bench/cache/<config>/`` on first use:
``graph.npz`` (CSR of sorted neighbour ids + the spatial half of each
point) for the reference, ``tables.npz`` (the program's padded tables) for
the system under test.  A later run of the cell loads them.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import List, NamedTuple

import numpy as np

CACHE = Path(__file__).resolve().parent / "cache"


class Graph(NamedTuple):
    """Undirected graph as CSR: row i's neighbours are
    ``indices[indptr[i]:indptr[i + 1]]``, sorted ascending."""

    indptr: np.ndarray    # (n + 1,) int64
    indices: np.ndarray   # (2E,) int32
    groups: np.ndarray    # (n,) int32 spatial half (x >= 0.5)

    @property
    def n(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def degree(self) -> np.ndarray:
        return np.diff(self.indptr).astype(np.int32)


def knn_graph(n: int, k: int, seed: int) -> Graph:
    """Symmetrised kNN graph over ``n`` uniform points (O(n k) work)."""
    rng = np.random.default_rng(seed)
    pts = rng.uniform(size=(n, 2))
    g = max(1, int(np.sqrt(n / max(4 * k, 1))))
    cell = np.minimum((pts * g).astype(np.int64), g - 1)
    cid = cell[:, 0] * g + cell[:, 1]
    order = np.argsort(cid, kind="stable")
    starts = np.searchsorted(cid[order], np.arange(g * g))
    ends = np.searchsorted(cid[order], np.arange(g * g), side="right")
    src: List[np.ndarray] = []
    dst: List[np.ndarray] = []
    for cx in range(g):
        for cy in range(g):
            mine = order[starts[cx * g + cy]:ends[cx * g + cy]]
            if len(mine) == 0:
                continue
            cand = np.concatenate([
                order[starts[x * g + y]:ends[x * g + y]]
                for x in (cx - 1, cx, cx + 1) for y in (cy - 1, cy, cy + 1)
                if 0 <= x < g and 0 <= y < g])
            d2 = ((pts[mine][:, None, :] - pts[cand][None, :, :]) ** 2).sum(-1)
            d2[cand[None, :] == mine[:, None]] = np.inf
            kk = min(k, len(cand) - 1)
            if kk <= 0:
                raise ValueError("grid too coarse for this n and k")
            sel = np.argpartition(d2, kk - 1, axis=1)[:, :kk]
            src.append(np.repeat(mine, kk))
            dst.append(cand[sel].ravel())
    a = np.concatenate(src + dst)
    b = np.concatenate(dst + src)
    pairs = np.unique(np.stack([a, b], axis=1), axis=0)   # sorted, deduped
    deg = np.bincount(pairs[:, 0], minlength=n)
    if (deg == 0).any():
        raise ValueError("isolated point")
    indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int64)
    groups = (pts[:, 0] >= 0.5).astype(np.int32)
    return Graph(indptr, pairs[:, 1].astype(np.int32), groups)


def _save(path: Path, **arrays):
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + f".tmp{os.getpid()}.npz")
    np.savez(tmp, **arrays)
    os.replace(tmp, path)


def load_graph(config: dict) -> Graph:
    """The configuration's graph, from the cache or built and cached."""
    path = CACHE / config["name"] / "graph.npz"
    if path.exists():
        with np.load(path) as z:
            return Graph(z["indptr"], z["indices"], z["groups"])
    gr = knn_graph(config["n"], config["k"], config["graph_seed"])
    _save(path, **gr._asdict())
    return gr


def load_topology(config: dict, graph: Graph):
    """The program's ``SparseTopology`` of ``graph``, built through its
    public table constructor once and cached."""
    from repro.core.sparse import NeighborTables, tables_from_adjacency
    from repro.simulate import SparseTopology

    path = CACHE / config["name"] / "tables.npz"
    if path.exists():
        with np.load(path) as z:
            tabs = NeighborTables(*(z[f] for f in NeighborTables._fields))
    else:
        rows = np.split(graph.indices.astype(np.int64), graph.indptr[1:-1])
        tabs = tables_from_adjacency(rows, [np.ones(len(r)) for r in rows])
        _save(path, **tabs._asdict())
    return SparseTopology(tabs, graph.groups)
