"""``correct`` is decided by the plain reference, and it comes out false
when the timed path is broken underneath the harness.

Each case drives the rest of a run (``run.run_cell``, the look for a chip
skipped) at a small size on the host CPU: the committed configurations
and mixes with n cut to 2,000.  The sound program passes; the control,
the reference computed in bfloat16 and put in the program's place, fails;
so does each fault the one-chip cells can have: a scan that returns its
state unchanged, half of each round's wake-ups left out, and an answer
altered where the program produces it.  (The cells run on one chip, so
there is no exchange between chips to leave out.)
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, run
from bench import traffic as tr
from conftest import ROOT

N = 2000
CELLS = {"mp": "mp-knn500k-lossy10-r100", "cl": "cl-knn100k-churn5-r100"}


def _cell(algo):
    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, CELLS[algo])
    config = dict(cell.config, n=N, name=f"test-{algo}")
    traffic = tr.load(ROOT / "bench" / "traffic"
                      / f"{cell.workload['traffic']}.json", N)
    return cell._replace(config=config, traffic=traffic)


def _run(cell, seed=2 ** 33 + 1):
    jax.clear_caches()   # the faults patch code that jitted scans close over
    try:
        return run.run_cell(cell, seed, 0.5, False, jax.devices())
    finally:
        jax.clear_caches()


def _unchanged(monkeypatch, algo):
    from repro.simulate import engines
    if algo == "mp":
        orig = engines._scenario_scan

        def scan(tabs, part_half, rates, theta_sol, *a, **kw):
            counters, (hist, frac) = orig(tabs, part_half, rates, theta_sol,
                                          *a, **kw)
            return counters, (jnp.broadcast_to(theta_sol, hist.shape), frac)
        monkeypatch.setattr(engines, "_scenario_scan", scan)
    else:
        orig = engines._cl_scenario_scan

        def scan(nbr_w, deg_count, D, m_counts, sx, state0, ev, *a, **kw):
            st, hist = orig(nbr_w, deg_count, D, m_counts, sx, state0, ev,
                            *a, **kw)
            return st, jnp.broadcast_to(state0[1], hist.shape)
        monkeypatch.setattr(engines, "_cl_scenario_scan", scan)


def _half_batch(monkeypatch, algo):
    from repro.simulate import scheduler
    orig = scheduler.draw_events

    def draw(*a, **kw):
        ev = orig(*a, **kw)
        keep = jnp.arange(ev.i.shape[0]) < ev.i.shape[0] // 2
        return ev._replace(deliver_ij=ev.deliver_ij & keep,
                           deliver_ji=ev.deliver_ji & keep)
    monkeypatch.setattr(scheduler, "draw_events", draw)


def _altered(monkeypatch, algo):
    from repro.simulate import engines
    name = f"run_{algo}_scenario"
    orig = getattr(engines, name)

    def run_scenario(*a, **kw):
        trace = orig(*a, **kw)
        hist = np.array(trace.theta_hist)
        hist[-1, 7, 3] += 0.5
        return dataclasses.replace(trace, theta_hist=hist)
    monkeypatch.setattr(engines, name, run_scenario)


def _control(monkeypatch, algo):
    """The reference in bfloat16, in the program's place."""
    from bench.reference.events import ref_graph
    real = harness.algo

    class Control:
        def __init__(self, config, traffic, graph, inputs):
            self.args = (ref_graph(graph), inputs, config, traffic)
            self.ref = harness.reference(config)
            self.events = traffic.events_per_call

        def call(self, seed):
            (d, dr, inv), hist = self.ref.run(*self.args, seed,
                                              jnp.bfloat16)
            return dict(events=self.events, delivered=d, dropped=dr,
                        invalid=inv, theta_hist=hist)

    def algo_mod(config):
        mod = real(config)
        return types.SimpleNamespace(make_inputs=mod.make_inputs,
                                     System=Control)
    monkeypatch.setattr(harness, "algo", algo_mod)


@pytest.mark.parametrize("algo", ["mp", "cl"])
def test_sound_program_is_correct(algo):
    res = _run(_cell(algo))
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert res["checks"]["counter_mismatch"]["value"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [_control, _unchanged, _half_batch,
                                   _altered])
@pytest.mark.parametrize("algo", ["mp", "cl"])
def test_broken_path_is_not_correct(monkeypatch, algo, fault):
    fault(monkeypatch, algo)
    res = _run(_cell(algo))
    assert not res["correct"] and res["failed"] > 0, res["checks"]
