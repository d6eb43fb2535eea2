"""The trace reduction, on a hand-made trace whose numbers are worked out
by hand, and on small traces recorded on a TPU v5e."""

import gzip
import json
from pathlib import Path

import pytest

from bench import harness, trace
from conftest import ROOT

DATA = Path(__file__).resolve().parent / "data"


def _write(path, events):
    meta = [
        dict(ph="M", pid=3, name="process_name", args=dict(name="/device:TPU:0")),
        dict(ph="M", pid=3, tid=2, name="thread_name", args=dict(name="XLA Modules")),
        dict(ph="M", pid=3, tid=3, name="thread_name", args=dict(name="XLA Ops")),
        dict(ph="M", pid=9, name="process_name", args=dict(name="/host:CPU")),
    ]
    with gzip.open(path, "wt") as f:
        json.dump(dict(traceEvents=meta + events), f)


def _x(pid, tid, name, ts, dur, **args):
    return dict(ph="X", pid=pid, tid=tid, name=name, ts=ts, dur=dur,
                args=args)


@pytest.fixture
def hand(tmp_path):
    """Window 0-100 us; calls 0-45 and 50-100; scan runs 10-40 and 60-90.
    Ops (leaf): 10-20, 15-30 (overlaps), 35-40, 60-90; the while op
    10-40 encloses the first three and is left out.  Busy: 10-30, 35-40,
    60-90 = 55 us.  Gaps: 0-10 (call 0 before its scan), 30-35 (during
    it), 40-60 split at the calls' edges into 40-45 (call 0 after its
    scan), 45-50 (between calls) and 50-60 (call 1 before its scan), and
    90-100 (call 1 after its scan)."""
    ev = [
        _x(9, 1, "bench/window", 0, 100),
        _x(9, 1, "bench/call", 0, 45), _x(9, 1, "bench/call", 50, 50),
        _x(3, 2, "jit__scenario_scan(1)", 10, 30),
        _x(3, 2, "jit__scenario_scan(1)", 60, 30),
        _x(3, 2, "jit_other(2)", 95, 1),
        _x(3, 3, "while.1", 10, 30, hlo_category="while", tf_op="w"),
        _x(3, 3, "fusion.1", 10, 10, hlo_category="loop fusion",
           tf_op="jit(f)/repro/neighbor_aggregate/xla/dot_general:"),
        _x(3, 3, "fusion.2", 15, 15, hlo_category="loop fusion",
           tf_op="jit(f)/gather:"),
        _x(3, 3, "copy.3", 35, 5, hlo_category="data formatting",
           tf_op="jit(f)/repro/neighbor_aggregate/xla/reduce:"),
        _x(3, 3, "fusion.4", 60, 30, hlo_category="loop fusion",
           tf_op="jit(f)/scatter:"),
    ]
    p = tmp_path / "hand.trace.json.gz"
    _write(p, ev)
    return trace.reduce(p, "_scenario_scan")


def test_busy_window_and_scans_by_hand(hand):
    assert hand.window_s() == pytest.approx(100e-6)
    assert hand.busy_s() == pytest.approx(55e-6)
    assert [(s.start, s.end) for s in hand.scans] == [(10, 40), (60, 90)]
    assert hand.scope_us("repro/neighbor_aggregate/") == 15


def test_gaps_are_named_by_what_the_host_did(hand):
    assert hand.gaps() == [("call 0: before its scan", 10e-6),
                           ("call 1: before its scan", 10e-6),
                           ("call 1: after its scan", 10e-6),
                           ("call 0: during its scan", 5e-6),
                           ("call 0: after its scan", 5e-6),
                           ("between calls", 5e-6)]


def test_readers_by_hand(hand):
    cell = harness.find_cell(harness.load_benchmark(ROOT),
                             "mp-knn500k-lossy10-r100")
    ctx = trace.Context(hand, cell, 18, [dict(delivered=9_000_000)] * 2,
                        harness.work(cell.config), "TPU v5 lite")
    read = harness.metric_reader
    assert ctx.rounds == 200
    assert read("scan_ms_per_round")(ctx) == pytest.approx(60e-3 / 200)
    assert read("host_ms_per_call")(ctx) == pytest.approx((15 + 20) / 2e3)
    assert read("device_idle_share")(ctx) == pytest.approx(45.0)
    assert read("op_ms_per_round.neighbor_aggregate")(ctx) == \
        pytest.approx(15e-3 / 200)
    assert read("op_ms_per_round.admm_primal")(ctx) is None
    mfu = read("round_mfu")(ctx)
    flops, nbytes = harness.work(cell.config).per_round(
        500_000, 50_000, 18, 100, 9_000_000, 100, 25)
    assert mfu["bound"] == "hbm"
    assert mfu["value"] == pytest.approx(
        100 * nbytes / 8.19e11 / (60e-6 / 200))


def test_window_must_be_annotated(tmp_path):
    p = tmp_path / "t.trace.json.gz"
    _write(p, [])
    with pytest.raises(ValueError):
        trace.reduce(p, "_scenario_scan")


@pytest.mark.parametrize("algo", ["mp", "cl"])
def test_recorded_chip_trace(algo):
    """A traced run of the cell at n = 2,000 and 4 rounds a call, recorded
    on a TPU v5e (``data/tiny_<algo>.trace.json.gz``), with the result
    line that run printed (``data/tiny_<algo>.result.json``): the
    reduction finds one scan run per call inside its call's span, each
    dispatch scope of the algorithm, and gives the readings the run gave."""
    res = json.loads((DATA / f"tiny_{algo}.result.json").read_text())
    wl = {"mp": "mp-knn500k-lossy10-r100", "cl": "cl-knn100k-churn5-r100"}
    cell = harness.find_cell(harness.load_benchmark(ROOT), wl[algo])
    module = harness.algo(cell.config).SCAN_MODULE
    red = trace.reduce(DATA / f"tiny_{algo}.trace.json.gz", module)
    assert len(red.scans) == len(red.calls) == res["attempted"]
    for call, scan in zip(red.calls, red.scans):
        assert call.start <= scan.start and scan.end <= call.end
    assert 0 < red.busy_s() <= red.window_s()
    assert red.busy_s() == pytest.approx(res["device"]["busy_s"])
    assert red.window_s() == pytest.approx(res["device"]["window_s"])
    assert red.breakdown() == res["breakdown"]
    cell = cell._replace(traffic=cell.traffic._replace(rounds=4,
                                                       record_every=2))
    ctx = trace.Context(red, cell, 0, [], None, res["device"]["kind"])
    for m in cell.per_layer:
        if m["name"] == "round_mfu":
            continue
        got = harness.metric_reader(m["name"])(ctx)
        assert got == pytest.approx(res["metrics"][m["name"]]["value"])
