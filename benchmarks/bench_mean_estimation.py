"""Paper Fig. 2 (left/middle): MP with vs without confidence values.

Sweeps the dataset-unbalancedness eps; reports mean L2 error of both
variants and the win ratio in favor of confidence values. Claims C3:
win ratio ~0.5 at eps=0, rising to ~0.85 at eps=1; error of the
with-confidence variant stays ~flat.
"""

from __future__ import annotations

import numpy as np

from repro.core import closed_form, solitary_mean, confidences_from_counts
from repro.data import mean_estimation_problem


def run(eps_values=(0.0, 0.25, 0.5, 0.75, 1.0), n_instances: int = 50,
        n_agents: int = 100, alpha: float = 0.99, seed: int = 0):
    rows = []
    for eps in eps_values:
        errs_c, errs_nc, wins = [], [], []
        for inst in range(n_instances):
            g, data, targets, c_true = mean_estimation_problem(
                n=n_agents, eps=eps, seed=seed + 1000 * inst + int(eps * 17))
            sol = np.asarray(solitary_mean(data))
            conf = np.asarray(confidences_from_counts(data.counts))
            with_c = np.asarray(closed_form(g, sol, conf, alpha))[:, 0]
            no_c = np.asarray(closed_form(g, sol, np.ones(g.n), alpha))[:, 0]
            e_c = float(np.mean((with_c - targets) ** 2))
            e_nc = float(np.mean((no_c - targets) ** 2))
            errs_c.append(e_c)
            errs_nc.append(e_nc)
            if abs(e_c - e_nc) < 1e-12:
                wins.append(0.5)          # tie (balanced data: C == I)
            else:
                wins.append(1.0 if e_c < e_nc else 0.0)
        rows.append({"eps": eps,
                     "l2_with_conf": float(np.mean(errs_c)),
                     "l2_no_conf": float(np.mean(errs_nc)),
                     "win_ratio": float(np.mean(wins))})
    return rows


def main(fast: bool = True):
    rows = run(n_instances=20 if fast else 1000,
               n_agents=100 if fast else 300)
    for r in rows:
        print(f"mean_estimation,eps={r['eps']:.2f},"
              f"l2_conf={r['l2_with_conf']:.4f},"
              f"l2_noconf={r['l2_no_conf']:.4f},win={r['win_ratio']:.2f}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(fast=False)
