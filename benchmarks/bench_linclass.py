"""Paper Fig. 3 (left/middle): collaborative linear classification.

Left: test accuracy of solitary / consensus / MP / CL vs feature dimension p.
Middle: accuracy vs local training-set size at p=50.
Claims C5 (CL > MP > solitary >> consensus) and C6 (CL equalizes accuracy
across training sizes).
"""

from __future__ import annotations

import numpy as np

from repro.core import (closed_form, solitary_gd, confidences_from_counts,
                        consensus_model, sync_admm)
from repro.data import linear_classification_problem, accuracy


def evaluate(n=100, p=50, seed=0, alpha=0.8, mu=0.05, rho=1.0,
             admm_steps=60):
    g, train, test, targets = linear_classification_problem(n=n, p=p,
                                                            seed=seed)
    sol = np.asarray(solitary_gd(train, "hinge", steps=250))
    conf = np.asarray(confidences_from_counts(train.counts))
    mp = np.asarray(closed_form(g, sol, conf, alpha))
    cons = np.tile(np.asarray(consensus_model(train, "hinge", steps=300)),
                   (n, 1))
    cl = np.asarray(sync_admm(g, train, mu, rho, "hinge", steps=admm_steps,
                              k_steps=12, lr=0.05, theta_sol=sol
                              ).theta_hist[-1])
    out = {}
    for name, th in (("solitary", sol), ("consensus", cons), ("mp", mp),
                     ("cl", cl)):
        out[name] = accuracy(th, test)
    counts = np.asarray(train.counts)
    return out, counts


def run_dim_sweep(p_values=(2, 20, 50, 100), n=100, n_instances=3, seed=0,
                  admm_steps=60):
    rows = []
    for p in p_values:
        accs = {k: [] for k in ("solitary", "consensus", "mp", "cl")}
        for i in range(n_instances):
            out, _ = evaluate(n=n, p=p, seed=seed + 31 * i + p,
                              admm_steps=admm_steps)
            for k, v in out.items():
                accs[k].append(float(np.mean(v)))
        rows.append({"p": p, **{k: float(np.mean(v))
                                for k, v in accs.items()}})
    return rows


def run_size_profile(n=100, p=50, n_instances=3, seed=0, admm_steps=60):
    """Accuracy vs m_i buckets (1-5, 6-10, 11-15, 16-20)."""
    buckets = [(1, 5), (6, 10), (11, 15), (16, 20)]
    sums = {k: np.zeros(len(buckets)) for k in
            ("solitary", "consensus", "mp", "cl")}
    cnts = np.zeros(len(buckets))
    for i in range(n_instances):
        out, counts = evaluate(n=n, p=p, seed=seed + 77 * i,
                               admm_steps=admm_steps)
        for bi, (lo, hi) in enumerate(buckets):
            m = (counts >= lo) & (counts <= hi)
            if m.sum():
                cnts[bi] += 1
                for k in sums:
                    sums[k][bi] += float(np.mean(out[k][m]))
    rows = []
    for bi, (lo, hi) in enumerate(buckets):
        d = max(cnts[bi], 1)
        rows.append({"bucket": f"{lo}-{hi}",
                     **{k: float(sums[k][bi] / d) for k in sums}})
    return rows


def main(fast: bool = True):
    kw = dict(n=40 if fast else 100, n_instances=2 if fast else 10,
              admm_steps=40 if fast else 120)
    rows = run_dim_sweep(p_values=(2, 20, 50) if fast else (2, 20, 50, 100),
                         n=kw["n"], n_instances=kw["n_instances"],
                         admm_steps=kw["admm_steps"])
    for r in rows:
        print(f"linclass_dim,p={r['p']},sol={r['solitary']:.3f},"
              f"cons={r['consensus']:.3f},mp={r['mp']:.3f},cl={r['cl']:.3f}")
    rows2 = run_size_profile(n=kw["n"], n_instances=kw["n_instances"],
                             admm_steps=kw["admm_steps"])
    for r in rows2:
        print(f"linclass_size,m={r['bucket']},sol={r['solitary']:.3f},"
              f"cons={r['consensus']:.3f},mp={r['mp']:.3f},cl={r['cl']:.3f}")
    return rows, rows2


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(fast=False)
