"""Reduce a profiler trace of the window to the numbers the readers need.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb`` and,
beside it, ``<host>.trace.json.gz``, the same trace converted to the
Chrome trace format.  This reads the latter: ``jax.profiler.ProfileData``
gives the xplane's events with their own statistics only, and a TPU op's
``tf_op`` (its ``jax.named_scope`` path, e.g. ``.../repro/admm_primal/
xla/...``) is a statistic of the op's metadata, which only the converted
trace carries.

What is read (names as a TPU v5e trace has them):

* process ``/device:TPU:<k>``, thread ``XLA Ops``: one event per executed
  HLO op, with ``args.tf_op`` and ``args.hlo_category``.  Ops of category
  ``while``/``conditional``/``call`` enclose their bodies' ops and are left
  out, so the rest are leaf ops that do not overlap;
* the same process, thread ``XLA Modules``: one event per executable run,
  named ``jit_<function>(<fingerprint>)``;
* process ``/host:CPU``: the benchmark's own annotations ``bench/window``
  (the measured window) and ``bench/call`` (one scenario call).

Host and device events share one clock in this file (microseconds).
"""

from __future__ import annotations

import gzip
import json
from pathlib import Path
from typing import List, NamedTuple, Tuple

ENCLOSING = ("while", "conditional", "call")
TOP = 10


class Op(NamedTuple):
    chip: int
    name: str
    start: float    # microseconds
    dur: float
    tf_op: str


class Span(NamedTuple):
    start: float
    end: float


def find(trace_dir: Path) -> Path:
    """The newest ``*.trace.json.gz`` under ``trace_dir``."""
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.trace.json.gz"))
    if not found:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    return found[-1]


def _union(spans: List[Span]) -> List[Span]:
    out: List[Span] = []
    for s in sorted(spans):
        if out and s.start <= out[-1].end:
            out[-1] = Span(out[-1].start, max(out[-1].end, s.end))
        else:
            out.append(s)
    return out


def _clip(spans: List[Span], w: Span) -> List[Span]:
    return [Span(max(s.start, w.start), min(s.end, w.end))
            for s in spans if s.end > w.start and s.start < w.end]


class Reduced:
    """Device ops, executable runs and host spans of one traced window."""

    def __init__(self, ops: List[Op], modules: List[Tuple[int, str, Span]],
                 window: Span, calls: List[Span], scan_module: str):
        self.ops = ops
        self.window = window
        self.calls = calls
        self.chips = sorted({o.chip for o in ops}) or [0]
        prefix = f"jit_{scan_module}("
        # the scan executable's runs on the first chip, one per call
        self.scans = [s for c, name, s in modules
                      if c == self.chips[0] and name.startswith(prefix)
                      and window.start <= s.start <= window.end]

    def busy(self, chip: int) -> List[Span]:
        return _clip(_union([Span(o.start, o.start + o.dur)
                             for o in self.ops if o.chip == chip]),
                     self.window)

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips used."""
        return sum(sum(s.end - s.start for s in self.busy(c))
                   for c in self.chips) / len(self.chips) / 1e6

    def window_s(self) -> float:
        return (self.window.end - self.window.start) / 1e6

    def _window_ops(self) -> List[Op]:
        """Ops of the first chip that started inside the window."""
        return [o for o in self.ops if o.chip == self.chips[0]
                and self.window.start <= o.start <= self.window.end]

    def scope_us(self, scope: str) -> float:
        """Device microseconds of the window's ops under a named scope."""
        return sum(o.dur for o in self._window_ops() if scope in o.tf_op)

    def _host_state(self, t: float) -> str:
        for k, c in enumerate(self.calls):
            if c.start <= t <= c.end:
                scan = next((s for s in self.scans
                             if c.start <= s.start <= c.end), None)
                side = "before" if scan is None or t < scan.start \
                    else "after" if t > scan.end else "during"
                return f"call {k}: {side} its scan"
        return "between calls"

    def gaps(self) -> List[Tuple[str, float]]:
        """Idle gaps of the first chip in the window, split where the host
        changed what it was doing, each piece named by it: inside a call
        before, during or after its scan ran, or between calls."""
        busy = self.busy(self.chips[0])
        edges = [self.window.start] + [x for s in busy for x in s] \
            + [self.window.end]
        marks = sorted({t for c in self.calls for t in c}
                       | {t for s in self.scans for t in s})
        out = []
        for a, b in zip(edges[::2], edges[1::2]):
            cuts = [a] + [t for t in marks if a < t < b] + [b]
            for x, y in zip(cuts, cuts[1:]):
                if y > x:
                    out.append((self._host_state((x + y) / 2),
                                (y - x) / 1e6))
        return sorted(out, key=lambda g: -g[1])

    def breakdown(self) -> dict:
        """The device ops that took most time and the longest idle gaps."""
        by_op: dict = {}
        for o in self._window_ops():
            # the op's name and the innermost part of its scope path
            where = o.tf_op.rstrip(":").split("closed_call/")[-1]
            key = f"{o.name} {where}"
            by_op[key] = by_op.get(key, 0.0) + o.dur / 1e6
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
        return dict(device_ops=[[k, v] for k, v in top],
                    idle_gaps=[[k, v] for k, v in self.gaps()[:TOP]])


def reduce(path: Path, scan_module: str) -> Reduced:
    """Read a ``*.trace.json.gz`` into a :class:`Reduced`."""
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    chip_of = {pid: int(name.rsplit(":", 1)[1]) for pid, name in
               procs.items() if name.startswith("/device:TPU:")}
    ops, modules, windows, calls = [], [], [], []
    for e in events:
        if e.get("ph") != "X":
            continue
        pid = e["pid"]
        span = Span(float(e["ts"]), float(e["ts"]) + float(e["dur"]))
        if pid in chip_of:
            line = threads.get((pid, e["tid"]))
            args = e.get("args", {})
            if line == "XLA Ops" and \
                    args.get("hlo_category") not in ENCLOSING:
                ops.append(Op(chip_of[pid], e["name"], span.start,
                              span.end - span.start, args.get("tf_op", "")))
            elif line == "XLA Modules":
                modules.append((chip_of[pid], e["name"], span))
        elif procs.get(pid) == "/host:CPU":
            if e["name"] == "bench/window":
                windows.append(span)
            elif e["name"] == "bench/call":
                calls.append(span)
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} bench/window spans")
    return Reduced(ops, modules, windows[0], sorted(calls), scan_module)


class Context(NamedTuple):
    """What a per-layer metric reader gets: the reduced trace, the cell,
    the graph's row width, the window's per-call counters, the
    algorithm's work counts and the device's kind."""

    trace: Reduced
    cell: object
    k_max: int
    calls: list
    work: object
    device_kind: str

    @property
    def rounds(self) -> int:
        """Rounds the traced scan runs executed."""
        return len(self.trace.scans) * self.cell.traffic.rounds

    def scan_s_per_round(self) -> float:
        return sum(s.end - s.start for s in self.trace.scans) / 1e6 \
            / max(self.rounds, 1)
