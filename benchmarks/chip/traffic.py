"""The one traffic generator: turns a mix file (``traffic/<name>.json``)
and a seed into an endless stream of requests and, for an open loop,
arrival gaps.

A mix file holds:

- ``loop``: ``"closed"`` (``outstanding`` requests always in the system;
  a completion sends the next) or ``"open"`` (arrivals at ``rate_hz``
  whatever the system does);
- ``steps``: ``[[steps, count], ...]``, the DDIM step counts of one
  block of requests; every block holds exactly these counts, shuffled;
- ``schedule_seed``: the seed of the schedule, that is of the order of
  the step counts and (open loop) of the arrival gaps, which are i.i.d.
  exponential at ``rate_hz``.  The schedule is part of the mix, so every
  run offers the same work at the same times, whatever its seed;
- ``guidance`` and ``precision`` of every request;
- ``ramp_completions`` (closed) or ``ramp_s`` (open): the ramp before the
  window opens, counted in set-up;
- ``drain_quantile`` (open loop, optional): after the window the run
  goes on until the latency and service percentiles up to this one, over
  the requests due in the window, are fixed (``bench.settled``); without
  it, until every one of those requests has finished.

The run's seed draws each request's own seed (its starting noise).
"""
from __future__ import annotations

import json

import numpy as np


def load(path):
    with open(path) as f:
        return json.load(f)


class Stream:
    def __init__(self, mix, seed: int):
        self.mix = mix
        self.rng = np.random.default_rng(seed)
        steps_seed, gaps_seed = np.random.SeedSequence(
            int(mix['schedule_seed'])).spawn(2)
        self._steps_rng = np.random.default_rng(steps_seed)
        self._gaps_rng = np.random.default_rng(gaps_seed)
        self._block = [int(s) for s, n in mix['steps'] for _ in range(int(n))]
        self._steps = []

    def next_request(self):
        """(steps, request seed) of the next request."""
        if not self._steps:
            self._steps = list(self._steps_rng.permutation(self._block))
        return int(self._steps.pop()), int(self.rng.integers(0, 2 ** 31 - 1))

    def next_gap(self) -> float:
        """Seconds from the previous arrival to the next (open loop)."""
        return float(self._gaps_rng.exponential(1.0 / float(self.mix['rate_hz'])))
