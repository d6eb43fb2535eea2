"""Scan: device time of the ops inside the scan executable's runs whose
``tf_op`` holds no ``repro/phase/`` scope, per round, in ms: what XLA adds
at loop level (carry copies, relayouts, the snapshot writes) or fuses
under a name of its own.

Extra fields, per round in ms: ``init``, the ops under
``repro/phase/init/`` (the warm start, once a call), and ``gaps``, the
time inside the scan's runs in which no op ran.  The phases inside the
scan, this value, ``init`` and ``gaps`` add up to ``scan_ms_per_round``."""

import bisect

from bench import spans


def read(ctx):
    tr = ctx.trace
    if ctx.rounds == 0 or not tr.scans \
            or not any(spans.PHASE in o.tf_op for o in tr.ops):
        return None
    runs = sorted(tr.scans)
    starts = [s.start for s in runs]

    def in_scan(t):
        k = bisect.bisect_right(starts, t) - 1
        return k >= 0 and t <= runs[k].end

    ops = [o for o in tr.ops if o.chip == tr.chips[0] and in_scan(o.start)]
    unscoped = sum(o.dur for o in ops if spans.PHASE not in o.tf_op)
    init = sum(o.dur for o in ops if f"{spans.PHASE}init/" in o.tf_op)
    busy = spans.union([spans.trace_mod.Span(o.start, o.start + o.dur)
                        for o in ops])
    gaps = sum(s.end - s.start for s in runs) \
        - sum(s.end - s.start for s in busy)
    per_round = 1e3 * ctx.rounds
    return dict(value=unscoped / per_round, init=init / per_round,
                gaps=gaps / per_round)
