"""The serving generator: the same work for every seed, and no request shed
when a stalled generator catches up with its schedule."""
from __future__ import annotations

import numpy as np
import pytest

from bench.harness import serve_traffic
from bench.tests.cells import small_cell


def gaps(due):
    return np.sort(np.diff(due, prepend=0.0))


@pytest.mark.parametrize("seed", [7, 2**31 + 12345])
def test_every_seed_offers_the_same_arrivals_in_another_order(seed):
    a, rows_a = serve_traffic.arrivals(1, 9600.0, 48.0, 393216, tag=2)
    b, rows_b = serve_traffic.arrivals(seed, 9600.0, 48.0, 393216, tag=2)
    assert a.shape == b.shape == rows_a.shape == (460800,)
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=0, atol=1e-9)
    assert a[-1] == pytest.approx(48.0) and b[-1] == pytest.approx(48.0)
    assert not np.array_equal(a, b) and not np.array_equal(rows_a, rows_b)
    # every held-out row once before any row twice
    assert np.unique(rows_b[:393216]).size == 393216


def test_arrivals_are_drawn_from_the_seed():
    a = serve_traffic.arrivals(2**31 + 3, 1000.0, 2.0, 4096, tag=2)
    b = serve_traffic.arrivals(2**31 + 3, 1000.0, 2.0, 4096, tag=2)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


def test_a_burst_past_the_programs_default_bound_is_not_shed():
    """A generator that stalled submits what fell due all at once: the cell's
    admission bound, above a whole window's arrivals, sheds none of it."""
    cell = small_cell("imagenet-nystrom.serve")
    s = serve_traffic.setup(cell, seed=5)
    try:
        assert s.tier.admission.max_inflight == cell.traffic["max_inflight"]
        assert cell.traffic["max_inflight"] > 9600 * 48
        s.traffic = dict(s.traffic, rate_per_s=1e6)
        w = serve_traffic.offer(s, 0.006, tag=3)  # 6,000 requests due at once
        serve_traffic.wait_answers(s, 60.0, w.due.size)
        assert w.due.size == 6000 and not w.shed.any()
        _, failed = serve_traffic.latencies_ms(s, w, serve_traffic.DRAIN_S)
        assert failed == 0
    finally:
        s.tier.stop()
