"""The one traffic generator: a mix file in ``bench/traffic/`` -> the calls.

A mix is a JSON object:

    conditions    fault process of every call (missing fields take
                  ``DEFAULT_CONDITIONS``; names as the paper's fault model
                  in ``repro.simulate.NetworkConditions``)
    batch_share   wake-ups per round as a share of the configuration's n
    rounds        rounds per call (one call = one independent scenario)
    record_every  rounds between the model snapshots a call returns

The window is a closed loop: one caller makes calls back to back, each
waiting for the previous one.  Every call does the same amount of work
(rounds x batch wake-ups); only its random stream differs, drawn from
``call_seed(seed, index)``.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import NamedTuple

import numpy as np

DEFAULT_CONDITIONS = dict(drop_prob=0.0, stale_prob=0.0, straggler_frac=0.0,
                          straggler_factor=0.1, churn_rate=0.0,
                          partition_start=-1, partition_end=-1)


class Traffic(NamedTuple):
    name: str
    conditions: dict
    batch: int
    rounds: int
    record_every: int

    @property
    def n_records(self) -> int:
        return self.rounds // self.record_every

    @property
    def events_per_call(self) -> int:
        return self.rounds * self.batch


def load(path: Path, n: int) -> Traffic:
    """Read a mix file for a configuration of ``n`` users."""
    mix = json.loads(Path(path).read_text())
    unknown = set(mix["conditions"]) - set(DEFAULT_CONDITIONS)
    if unknown:
        raise ValueError(f"{path}: unknown conditions {sorted(unknown)}")
    rounds, every = int(mix["rounds"]), int(mix["record_every"])
    if rounds < 1 or every < 1 or rounds % every:
        raise ValueError(f"{path}: rounds must be a positive multiple of "
                         f"record_every")
    batch = int(round(mix["batch_share"] * n))
    if batch < 1:
        raise ValueError(f"{path}: batch_share gives no wake-up at n={n}")
    return Traffic(Path(path).stem, {**DEFAULT_CONDITIONS, **mix["conditions"]},
                   batch, rounds, every)


def _seed31(*words: int) -> int:
    """A 31-bit seed from whole numbers of any size (JAX keys take 32 bits,
    so a large ``--seed`` is hashed, never truncated)."""
    return int(np.random.SeedSequence([int(w) % 2 ** 64 for w in words])
               .generate_state(1)[0] >> 1)


def data_seed(seed: int) -> int:
    """Seed of the models and data a run draws once, at set-up."""
    return _seed31(seed, 0)


def call_seed(seed: int, index: int) -> int:
    """Seed of call ``index``'s event stream."""
    return _seed31(seed, 1, index)


def sample_seed(seed: int) -> int:
    """Seed of the choice of calls the reference checks."""
    return _seed31(seed, 2)
