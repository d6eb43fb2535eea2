"""Quickstart: the paper's two algorithms in 2 minutes on CPU.

1. Collaborative mean estimation (paper §5.1): solitary models, model
   propagation with confidence values (Prop. 1 + async gossip), and the
   errors of each.
2. Collaborative linear classification (paper §5.2): solitary vs consensus
   vs MP vs CL-ADMM accuracy.
3. Backend dispatch + vmapped sweeps: the same MP iterates under an
   explicit ReproBackend, and a (seed x alpha) grid in one jitted call.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import (closed_form, async_gossip, solitary_mean, solitary_gd,
                        confidences_from_counts, consensus_model, sync_admm,
                        synchronous)
from repro.data import (mean_estimation_problem,
                        linear_classification_problem, accuracy)
from repro.experiments import mean_estimation_trials, run_mp_sweep
from repro.kernels import ReproBackend


def mean_estimation():
    print("== collaborative mean estimation (n=100, eps=1) ==")
    g, data, targets, _ = mean_estimation_problem(n=100, eps=1.0, seed=0)
    sol = np.asarray(solitary_mean(data))
    conf = np.asarray(confidences_from_counts(data.counts))

    err = lambda th: float(np.mean((np.asarray(th)[:, 0] - targets) ** 2))
    star = closed_form(g, sol, conf, alpha=0.99)
    star_noc = closed_form(g, sol, np.ones(g.n), alpha=0.99)
    tr = async_gossip(g, sol, conf, alpha=0.99, steps=4000, record_every=500)

    print(f" solitary models        L2 = {err(sol):.4f}")
    print(f" MP closed form (no c)  L2 = {err(star_noc):.4f}")
    print(f" MP closed form (Prop1) L2 = {err(star):.4f}")
    print(f" MP async gossip        L2 = {err(tr.theta_hist[-1]):.4f} "
          f"after {tr.comms_hist[-1]} pairwise communications "
          f"(converging to the closed form; full curves in benchmarks)")


def linear_classification():
    print("== collaborative linear classification (n=60, p=30) ==")
    g, train, test, _ = linear_classification_problem(n=60, p=30, seed=0)
    sol = np.asarray(solitary_gd(train, "hinge", steps=250))
    conf = np.asarray(confidences_from_counts(train.counts))
    acc = lambda th: float(np.mean(accuracy(np.asarray(th), test)))

    cons = np.tile(np.asarray(consensus_model(train, "hinge")), (g.n, 1))
    mp = closed_form(g, sol, conf, alpha=0.99)
    cl = sync_admm(g, train, mu=0.05, rho=1.0, loss="hinge", steps=40,
                   k_steps=12, lr=0.05, theta_sol=sol).theta_hist[-1]

    print(f" solitary  acc = {acc(sol):.3f}")
    print(f" consensus acc = {acc(cons):.3f}   (Eq. 2 baseline)")
    print(f" MP        acc = {acc(mp):.3f}")
    print(f" CL (ADMM) acc = {acc(cl):.3f}")


def backends_and_sweeps():
    print("== backend dispatch + vmapped sweep ==")
    g, data, targets, _ = mean_estimation_problem(n=60, eps=1.0, seed=0)
    sol = np.asarray(solitary_mean(data))
    conf = np.asarray(confidences_from_counts(data.counts))

    # auto backend: fused XLA on every platform
    auto = synchronous(g, sol, conf, alpha=0.9, steps=300)
    # explicit override: validate the Pallas kernel via interpret mode
    # repro-lint: disable=RPL005  demo opts in to validate the kernel on CPU
    pallas_cpu = ReproBackend.using(mix="pallas", interpret=True)
    kern = synchronous(g, sol, conf, alpha=0.9, steps=300,
                       backend=pallas_cpu)
    print(f" |auto - pallas(interpret)| = "
          f"{float(np.abs(np.asarray(auto) - np.asarray(kern)).max()):.2e}")

    # 8 (seed, alpha) trials as ONE jitted program over the trial axis
    trials = mean_estimation_trials(seeds=range(4), alphas=[0.9, 0.99], n=60)
    res = run_mp_sweep(trials, sweeps=300)
    for a in (0.9, 0.99):
        sel = trials.alpha == np.float32(a)
        print(f" alpha={a}: mean final L2 over {int(sel.sum())} seeds = "
              f"{res.err_hist[sel, -1].mean():.4f}")


if __name__ == "__main__":
    mean_estimation()
    linear_classification()
    backends_and_sweeps()
