"""Exchange: payload bytes the chips publish to one another per round
(every shard's boundary rows, as the system states them in the
``halo_bytes_per_round`` arg of its ``repro/host/scan`` spans), mean over
the window's calls, in MB (10^6 bytes).  Nothing where the scan spans
carry no such arg."""

from bench import spans


def read(ctx):
    parsed = spans.of(ctx)
    if parsed is None:
        return None
    got = [int(s.args["halo_bytes_per_round"])
           for s in spans.steps(parsed, "scan")
           if "halo_bytes_per_round" in s.args]
    return sum(got) / len(got) / 1e6 if got else None
