"""The open loop's early stop: once ``bench.settled`` says so, the
percentiles read with the unfinished requests counted as infinite are
the ones that the requests' own times give, however late they finish."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_cells  # noqa: E402

bench = tiny_cells.bench


class _Loop:
    def note_starts(self):
        pass


def _records(rng, n):
    recs = []
    for rid in range(n):
        r = bench.Record(rid, 0, 0, float(rng.uniform(0.0, 10.0)))
        r.start = r.due + float(rng.exponential(0.1))
        r.finish = r.start + float(rng.choice([1.0, 2.0, 5.0],
                                                 p=[0.6, 0.35, 0.05]))
        recs.append(r)
    return recs


@pytest.mark.parametrize('q', [50, 90])
@pytest.mark.parametrize('seed', [1, 2, 3])
def test_settled_percentiles_are_final(q, seed):
    rng = np.random.default_rng(seed)
    recs = _records(rng, 200)
    true = [bench.percentile(f(recs), p) for f in (bench.latencies,
                                                   bench.service_times)
            for p in (50, q)]
    close = 10.0
    for now in np.arange(close, close + 6.0, 0.05):
        seen = [bench.Record(r.rid, 0, 0, r.due) for r in recs]
        for s, r in zip(seen, recs):
            s.start = r.start if r.start <= now else None
            s.finish = r.finish if r.finish <= now else None
        if bench.settled(_Loop(), seen, now, q):
            got = [bench.percentile(f(seen), p) for f in (
                bench.latencies, bench.service_times) for p in (50, q)]
            assert got == true
            assert any(s.finish is None for s in seen)   # it stopped early
            return
    pytest.fail('never settled')


def test_without_a_quantile_every_request_is_waited_for():
    r = bench.Record(0, 0, 0, 0.0)
    r.start = 0.0
    assert not bench.settled(_Loop(), [r], 100.0, None)
    r.finish = 1.0
    assert bench.settled(_Loop(), [r], 100.0, None)
