"""The readers of the system's own spans and phase scopes, on a hand-made
trace whose numbers are worked out by hand, on one of a program that
writes none, and on small traces recorded on a TPU v5e.

``BENCHMARK.json`` does not list these metrics yet: their entries are in
``data/span_metrics.json``, as a ``benchmark`` change will append them,
and the tests read the cells with those entries appended."""

import gzip
import json
import shutil
from pathlib import Path

import pytest

from bench import harness, spans, trace
from conftest import ROOT

DATA = Path(__file__).resolve().parent / "data"
MP_CELL = "mp-knn500k-lossy10-r100"
NEW = ("host_ms_per_call.prepare", "host_ms_per_call.fetch",
       "transfer_mb_per_call", "compiles_in_window",
       "phase_ms_per_round.draw", "phase_ms_per_round.comm",
       "phase_ms_per_round.update", "phase_ms_per_round.primal",
       "phase_ms_per_round.edge", "phase_ms_per_round.unscoped",
       "idle_unattributed_share")


def _entries():
    return json.loads((DATA / "span_metrics.json").read_text())


def _bench():
    """``BENCHMARK.json`` with the entries of ``data/span_metrics.json``
    appended where it does not list them yet."""
    bench = harness.load_benchmark(ROOT)
    have = {m["name"] for m in bench["per_layer"]}
    bench["per_layer"] += [m for m in _entries() if m["name"] not in have]
    return bench


def _write(path, events):
    meta = [
        dict(ph="M", pid=3, name="process_name", args=dict(name="/device:TPU:0")),
        dict(ph="M", pid=3, tid=2, name="thread_name", args=dict(name="XLA Modules")),
        dict(ph="M", pid=3, tid=3, name="thread_name", args=dict(name="XLA Ops")),
        dict(ph="M", pid=9, name="process_name", args=dict(name="/host:CPU")),
    ]
    path.parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(dict(traceEvents=meta + events), f)


def _x(pid, tid, name, ts, dur, **args):
    return dict(ph="X", pid=pid, tid=tid, name=name, ts=ts, dur=dur,
                args=args)


def _op(name, ts, dur, tf_op, source="", category="loop fusion"):
    return _x(3, 3, name, ts, dur, hlo_category=category, tf_op=tf_op,
              source=source)


def _call(t, compile_event=False):
    """One scenario call starting at ``t``: spans prepare t+2..t+20
    (1000 bytes up), scan t+20..t+22, wait t+22..t+70, fetch t+70..t+85
    (4000 bytes down); the scan executable runs t+25..t+65 with ops init
    t+25..30, draw 30..40, comm 40..50, update 50..55 (the dispatch scope
    nested), an unscoped copy 57..62 and record 62..64; a key split runs
    t+8..10, outside the scan."""
    scan = "jit(_scenario_scan)/while/body/"
    ev = [
        _x(9, 1, "bench/call", t, 90),
        _x(9, 1, "repro/scenario", t + 2, 86, algo="mp", seed=str(t)),
        _x(9, 1, "repro/host/prepare", t + 2, 18, bytes="1000"),
        _x(9, 1, "repro/host/scan", t + 20, 2),
        _x(9, 1, "repro/host/wait", t + 22, 48),
        _x(9, 1, "repro/host/fetch", t + 70, 15, bytes="4000"),
        _x(3, 2, "jit__scenario_scan(1)", t + 25, 40),
        _op("while.1", t + 25, 40, "w", category="while"),
        _op("fusion.0", t + 8, 2, "jit(_threefry_split)/slice:"),
        _op("fusion.1", t + 25, 5, "jit(f)/repro/phase/init/gather:"),
        _op("fusion.2", t + 30, 10, scan + "repro/phase/draw/searchsorted:"),
        _op("fusion.3", t + 40, 10, scan + "repro/phase/comm/scatter:"),
        _op("fusion.4", t + 50, 5, scan + "repro/phase/update/vmap("
            "repro/neighbor_aggregate/xla)/dot_general:"),
        _op("copy.5", t + 57, 5, scan + "copy:", "engines.py:400",
            "data formatting"),
        _op("fusion.6", t + 62, 2, scan + "repro/phase/record/reduce:"),
    ]
    if compile_event:
        ev.append(_x(9, 1, "backend_compile_and_load", t + 20.5, 1))
    return ev


def _ctx(trace_dir, events, rounds=2):
    """Write the trace where the readers look, and the reader context of
    the MP cell at ``rounds`` rounds a call."""
    p = trace_dir / "plugins/profile/1/h.trace.json.gz"
    _write(p, events)
    cell = harness.find_cell(_bench(), MP_CELL)
    cell = cell._replace(traffic=cell.traffic._replace(rounds=rounds))
    red = trace.reduce(p, "_scenario_scan")
    return trace.Context(red, cell, 18, [], harness.work(cell.config),
                         "TPU v5 lite")


@pytest.fixture
def hand(tmp_path, monkeypatch):
    """Window 0-200 us, two calls (at 0 and 100) of two rounds each, one
    compile event inside the first call's scan span."""
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    ev = [_x(9, 1, "bench/window", 0, 200)] + _call(0, True) + _call(100)
    return _ctx(tmp_path, ev)


def test_phases_by_hand(hand):
    """Per round (4 rounds): draw 20 us, comm 20, update 10, record 4,
    unscoped 10 (the copies), init 10, gaps 6 (55-57 and 64-65 in each
    run); with them the run's 80 us are accounted for."""
    read = harness.metric_reader
    assert read("phase_ms_per_round.draw")(hand) == pytest.approx(5e-3)
    assert read("phase_ms_per_round.comm")(hand) == pytest.approx(5e-3)
    assert read("phase_ms_per_round.update")(hand) == pytest.approx(2.5e-3)
    assert spans.phase_ms_per_round(hand, "record") == pytest.approx(1e-3)
    assert read("phase_ms_per_round.primal")(hand) == 0.0
    un = read("phase_ms_per_round.unscoped")(hand)
    assert un == pytest.approx(dict(value=2.5e-3, init=2.5e-3, gaps=1.5e-3))
    phases = sum(spans.phase_ms_per_round(hand, p)
                 for p in ("draw", "comm", "update", "record"))
    assert phases + un["value"] + un["init"] + un["gaps"] == \
        pytest.approx(read("scan_ms_per_round")(hand))
    # the dispatch scope nested in the update phase is still read
    assert read("op_ms_per_round.neighbor_aggregate")(hand) == \
        pytest.approx(2.5e-3)


def test_host_steps_and_bytes_by_hand(hand):
    """Prepare 18 us and fetch 15 us a call; 1000 bytes up and 4000 down a
    call; one compile in the window."""
    read = harness.metric_reader
    assert read("host_ms_per_call.prepare")(hand) == pytest.approx(0.018)
    assert read("host_ms_per_call.fetch")(hand) == pytest.approx(0.015)
    assert read("transfer_mb_per_call")(hand) == pytest.approx(
        dict(value=5e-3, up_mb=1e-3, down_mb=4e-3))
    assert read("compiles_in_window")(hand) == 1


def test_idle_attribution_by_hand(hand):
    """Idle (first chip): 0-8, 10-25, 55-57, 64-108, 110-125, 155-157 and
    164-200 = 122 us.  Under prepare 6+10+6+10 = 32, scan 2+2, wait
    3+2+6+3+2+6 = 22, fetch 15+15; under no step 0-2, 85-102 and 185-200
    = 34 us (before the first prepare, between a fetch and the next
    prepare, after the last fetch)."""
    got = harness.metric_reader("idle_unattributed_share")(hand)
    assert got == pytest.approx(dict(
        value=100 * 34 / 122, idle_ms_per_call_prepare=0.016,
        idle_ms_per_call_scan=0.002, idle_ms_per_call_wait=0.011,
        idle_ms_per_call_fetch=0.015, idle_ms_per_call_unattributed=0.017))


def test_self_time_leaves_out_nested_repro_spans(tmp_path, monkeypatch):
    """A repro span nested in a step is not the step's own time."""
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    ev = [_x(9, 1, "bench/window", 0, 200)] + _call(0) \
        + [_x(9, 1, "repro/host/serve", 5, 4, requests="3")]
    ctx = _ctx(tmp_path, ev)
    assert harness.metric_reader("host_ms_per_call.prepare")(ctx) == \
        pytest.approx(0.014)


def test_a_program_without_spans_reports_nothing(tmp_path, monkeypatch):
    """The trace of a program that writes no spans or phase scopes: every
    new reader but the compile count gives None (the metric is left out),
    and none raises."""
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    ev = [_x(9, 1, "bench/window", 0, 100), _x(9, 1, "bench/call", 0, 90),
          _x(3, 2, "jit__scenario_scan(1)", 10, 30),
          _op("fusion.1", 10, 10, "jit(f)/repro/neighbor_aggregate/xla/x:"),
          _op("fusion.2", 20, 15, "jit(f)/gather:")]
    ctx = _ctx(tmp_path, ev)
    for name in NEW:
        got = harness.metric_reader(name)(ctx)
        assert got == (0 if name == "compiles_in_window" else None), name


def test_a_trace_of_another_window_is_not_read(tmp_path, monkeypatch):
    """The readers read the newest trace only if it is the one the
    context was reduced from."""
    ev = [_x(9, 1, "bench/window", 0, 200)] + _call(0) + _call(100)
    ctx = _ctx(tmp_path / "a", ev)
    _write(tmp_path / "b/plugins/profile/1/h.trace.json.gz",
           [_x(9, 1, "bench/window", 0, 300)] + _call(0))
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path / "b")
    assert harness.metric_reader("host_ms_per_call.prepare")(ctx) is None
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path / "a")
    assert harness.metric_reader("host_ms_per_call.prepare")(ctx) == \
        pytest.approx(0.018)


def test_new_metrics_are_declared():
    """Every new reader has its entry in ``data/span_metrics.json``,
    moving ``events_per_s``, each cell lists the ones it reports, and an
    entry ``BENCHMARK.json`` already lists is the same there."""
    entries = {m["name"]: m for m in _entries()}
    assert set(entries) == set(NEW)
    cells = {w["name"] for w in harness.load_benchmark(ROOT)["workloads"]}
    for name in NEW:
        assert entries[name]["moves"] == "events_per_s"
        assert set(entries[name]["workloads"]) <= cells
        assert (ROOT / "bench" / "metrics" / f"{name}.py").exists()
    for m in harness.load_benchmark(ROOT)["per_layer"]:
        assert entries.get(m["name"], m) == m
    for cell, own in (("mp-knn500k-lossy10-r100", ("comm", "update")),
                      ("cl-knn100k-churn5-r100", ("primal", "edge"))):
        names = {m["name"] for m in
                 harness.find_cell(_bench(), cell).per_layer}
        assert {f"phase_ms_per_round.{p}" for p in own} <= names


@pytest.mark.parametrize("algo", ["mp", "cl"])
def test_recorded_chip_trace_with_spans(algo, tmp_path, monkeypatch):
    """A traced run of the cell at n = 2,000 and 4 rounds a call, recorded
    on a TPU v5e with the system's spans and phase scopes
    (``data/tiny_<algo>_spans.trace.json.gz``), with the result line that
    run printed (``data/tiny_<algo>_spans.result.json``): every scan run
    lies inside one ``repro/scenario`` span and its ``repro/host/scan`` +
    ``wait`` steps, the steps come in their order, the readers give the
    readings the run gave, and the phases account for the scan."""
    res = json.loads((DATA / f"tiny_{algo}_spans.result.json").read_text())
    wl = {"mp": "mp-knn500k-lossy10-r100", "cl": "cl-knn100k-churn5-r100"}
    cell = harness.find_cell(_bench(), wl[algo])
    module = harness.algo(cell.config).SCAN_MODULE
    src = DATA / f"tiny_{algo}_spans.trace.json.gz"
    dst = tmp_path / "plugins/profile/1" / src.name
    dst.parent.mkdir(parents=True)
    shutil.copy(src, dst)
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    red = trace.reduce(dst, module)
    parsed = spans.parse(str(dst))
    calls = spans.scenarios(parsed)
    assert len(calls) == len(red.scans) == res["attempted"]
    order = {"mp": ["prepare", "scan", "wait", "fetch"],
             "cl": ["prepare", "scan", "fetch", "wait", "fetch"]}[algo]
    for call, scan in zip(calls, red.scans):
        assert call.start <= scan.start and scan.end <= call.end
        inside = [s.name[len(spans.HOST):] for s in parsed.host
                  if s.name.startswith(spans.HOST)
                  and call.start <= s.start and s.end <= call.end]
        assert inside == order
    cell = cell._replace(traffic=cell.traffic._replace(rounds=4,
                                                       record_every=2))
    ctx = trace.Context(red, cell, 0, [], None, res["device"]["kind"])
    got = {}
    for m in cell.per_layer:
        if m["name"] == "round_mfu":
            continue
        want = dict(res["metrics"][m["name"]])
        want.pop("unit")
        got[m["name"]] = harness.metric_reader(m["name"])(ctx)
        read = got[m["name"]]
        assert (read if isinstance(read, dict) else dict(value=read)) \
            == pytest.approx(want), m["name"]
    un = got["phase_ms_per_round.unscoped"]
    phases = ("draw", "comm", "update", "record") if algo == "mp" \
        else ("primal", "edge", "record")
    assert sum(spans.phase_ms_per_round(ctx, p) for p in phases) \
        + un["value"] + un["init"] + un["gaps"] == \
        pytest.approx(got["scan_ms_per_round"])
    assert got["compiles_in_window"] == 0
