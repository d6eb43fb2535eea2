"""Algorithmic work counts against a small case worked out by hand."""

import json
from pathlib import Path

import pytest

from bench.work import cl, mp

# n = 10 users, B = 2 wake-ups a round, k_max = 3, p = 4, 6 deliveries in
# a call of 2 rounds with a snapshot every round: 3 deliveries a round.
CASE = dict(n=10, batch=2, k_max=3, p=4, delivered=6, rounds=2,
            record_every=1)


def test_mp_round_by_hand():
    # comm 3 * 2 * 4 = 24; update 3 * (12 + 3 + 8 + 1) = 72; events
    # 3 * 10 + 8 * 2 = 46; per call (40 + 120 + 40 + 2 * 40) / 2 = 140
    # words: 282 -> 1128 bytes.  FLOPs 3 * (2 * 3 * 4 + 5 * 4) = 132.
    assert mp.per_round(**CASE) == (132.0, 1128.0)


def test_cl_round_by_hand():
    # primal 3 * (48 + 3 + 4 + 2 + 4 + 12) = 219; edge 3 * 48 = 144;
    # events 46; per call (40 + 40 + 5 * 120 + 2 * 40) / 2 = 380
    # words: 789 -> 3156 bytes.  FLOPs 3 * (108 + 12) + 3 * 56 = 528.
    assert cl.per_round(**CASE) == (528.0, 3156.0)


@pytest.mark.parametrize("work", [mp, cl])
def test_work_grows_with_deliveries(work):
    lo = work.per_round(**{**CASE, "delivered": 2})
    hi = work.per_round(**{**CASE, "delivered": 20})
    assert hi[0] > lo[0] and hi[1] > lo[1]


def test_peak_table_names_its_source_and_refuses_unknown_devices():
    from bench.metrics import round_mfu
    table = json.loads(round_mfu.PEAKS.read_text())
    assert "v5e" in table["source"]
    assert round_mfu.peak("TPU v5 lite")["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError):
        round_mfu.peak("cpu")
