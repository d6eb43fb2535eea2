"""The program's own spans and scopes in the trace of the window.

``bench/trace.py`` reduces the device ops and the benchmark's own spans.
This reads, from the same ``*.trace.json.gz`` (the newest under the trace
directory, found as ``bench.trace.find`` finds it, parsed once per path),
process ``/host:CPU``: the spans the system writes around each scenario
call (``repro/scenario``, and ``repro/host/<step>`` for its steps
``prepare``, ``scan``, ``wait``, ``fetch``, ``serve``, with their args,
e.g. ``bytes``), the benchmark's ``bench/window``, and JAX's own compile
events (``backend_compile...``).  The device ops inside a scan carry
``repro/phase/<phase>/`` in their ``tf_op`` where the system scoped them;
``bench.trace`` already reduces those.

A reader gets ``None`` from :func:`of` where the trace is not the one
its context was reduced from, and empty lists where the system wrote no
such span (a program without them): it then reports nothing.
"""

from __future__ import annotations

import functools
import gzip
import json
from pathlib import Path
from typing import List, NamedTuple, Optional

from bench import trace as trace_mod

TRACE_DIR = Path(__file__).resolve().parent / "cache" / "trace"
HOST = "repro/host/"
SCENARIO = "repro/scenario"
PHASE = "repro/phase/"
COMPILE = "backend_compile"


class HostSpan(NamedTuple):
    name: str
    tid: int
    start: float    # microseconds, the clock of the device ops
    end: float
    args: dict


class Parsed(NamedTuple):
    window: Optional[trace_mod.Span]
    host: List[HostSpan]      # repro/* spans and compile events


@functools.lru_cache(maxsize=4)
def parse(path: str) -> Parsed:
    """Read one ``*.trace.json.gz``."""
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    hosts = {e["pid"] for e in events if e.get("ph") == "M"
             and e.get("name") == "process_name"
             and e["args"]["name"] == "/host:CPU"}
    window, host = None, []
    for e in events:
        if e.get("ph") != "X" or e["pid"] not in hosts:
            continue
        name, start = e["name"], float(e["ts"])
        end = start + float(e["dur"])
        if name == "bench/window":
            window = trace_mod.Span(start, end)
        elif name.startswith(("repro/", COMPILE)):
            host.append(HostSpan(name, e["tid"], start, end,
                                 e.get("args", {})))
    host.sort(key=lambda s: (s.start, -s.end))
    return Parsed(window, host)


def of(ctx, trace_dir: Optional[Path] = None) -> Optional[Parsed]:
    """The parsed newest trace, if it is the one ``ctx.trace`` was
    reduced from (the same ``bench/window``); else None."""
    try:
        path = trace_mod.find(TRACE_DIR if trace_dir is None else trace_dir)
    except FileNotFoundError:
        return None
    parsed = parse(str(path))
    if parsed.window != ctx.trace.window:
        return None
    return parsed


def union(spans: List[trace_mod.Span]) -> List[trace_mod.Span]:
    """The spans merged where they overlap, in order."""
    out: List[trace_mod.Span] = []
    for s in sorted(spans):
        if out and s.start <= out[-1].end:
            out[-1] = trace_mod.Span(out[-1].start, max(out[-1].end, s.end))
        else:
            out.append(s)
    return out


def in_window(spans: List[HostSpan], window) -> List[HostSpan]:
    return [s for s in spans if window.start <= s.start <= window.end]


def scenarios(parsed: Parsed) -> List[HostSpan]:
    """The ``repro/scenario`` spans (one per call) that started in the
    window."""
    return [s for s in in_window(parsed.host, parsed.window)
            if s.name == SCENARIO]


def steps(parsed: Parsed, step: str) -> List[HostSpan]:
    """The window's ``repro/host/<step>`` spans."""
    return [s for s in in_window(parsed.host, parsed.window)
            if s.name == HOST + step]


def self_us(span: HostSpan, parsed: Parsed) -> float:
    """The span's time less that of the ``repro/`` spans nested in it on
    its thread."""
    inner = union([
        trace_mod.Span(s.start, s.end) for s in parsed.host
        if s is not span and s.tid == span.tid
        and s.name.startswith("repro/")
        and span.start <= s.start and s.end <= span.end])
    return (span.end - span.start) - sum(s.end - s.start for s in inner)


def per_call(parsed: Parsed, step: str) -> Optional[float]:
    """Mean self time of a host step per scenario call in the window, in
    ms; None where the system wrote no such spans."""
    calls, found = scenarios(parsed), steps(parsed, step)
    if not calls or not found:
        return None
    return sum(self_us(s, parsed) for s in found) / len(calls) / 1e3


def step_bytes(parsed: Parsed, step: str) -> int:
    """The ``bytes`` args of the window's spans of one host step."""
    return sum(int(s.args.get("bytes", 0)) for s in steps(parsed, step))


def overlap_us(a: List[trace_mod.Span], b: List[trace_mod.Span]) -> float:
    """Microseconds in which some span of ``a`` and some of ``b`` are
    both open."""
    a, b = union(a), union(b)
    total, k = 0.0, 0
    for s in a:
        while k < len(b) and b[k].end <= s.start:
            k += 1
        j = k
        while j < len(b) and b[j].start < s.end:
            total += min(s.end, b[j].end) - max(s.start, b[j].start)
            j += 1
    return total


def idle(ctx) -> List[trace_mod.Span]:
    """The window's device-idle intervals on the first chip."""
    w = ctx.trace.window
    busy = ctx.trace.busy(ctx.trace.chips[0])
    edges = [w.start] + [x for s in busy for x in s] + [w.end]
    return [trace_mod.Span(a, b) for a, b in zip(edges[::2], edges[1::2])
            if b > a]


def phase_ms_per_round(ctx, phase: str) -> Optional[float]:
    """Device time of the window's ops under ``repro/phase/<phase>/``,
    per round, in ms: 0 where no op carries that phase, None where no op
    carries any phase scope (a program without them)."""
    if ctx.rounds == 0 or not any(PHASE in o.tf_op for o in ctx.trace.ops):
        return None
    return ctx.trace.scope_us(f"{PHASE}{phase}/") / 1e3 / ctx.rounds
