"""The load generator's schedules: seeded orders, fixed work per seed."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import loadgen  # noqa: E402


def test_uniform_order_sends_every_graph_once_per_pass():
    rng = np.random.default_rng(5)
    order = loadgen.draw_order({"popularity": "uniform"}, 10, rng, 25)
    assert sorted(order[:10]) == list(range(10))
    assert sorted(order[10:20]) == list(range(10))
    assert len(order) == 25


def test_orders_differ_by_seed_and_repeat_by_seed():
    t = {"popularity": "uniform"}
    a = loadgen.draw_order(t, 50, np.random.default_rng(1), 50)
    b = loadgen.draw_order(t, 50, np.random.default_rng(1), 50)
    c = loadgen.draw_order(t, 50, np.random.default_rng(2), 50)
    assert (a == b).all() and not (a == c).all()


def test_zipf_favours_the_top_rank():
    rng = np.random.default_rng(0)
    order = loadgen.draw_order({"popularity": "zipf:1.2"}, 100, rng, 5000)
    counts = np.bincount(order, minlength=100)
    assert counts.max() > 10 * np.median(counts)


def test_duplicate_share_resends_recent_graphs():
    rng = np.random.default_rng(0)
    order = loadgen.draw_order({"popularity": "uniform",
                                "duplicate_share": 0.9}, 512, rng, 2000)
    assert len(set(order.tolist())) < 400


@pytest.mark.parametrize("traffic", [
    {"arrivals": "poisson", "rate_per_s": 50.0},
    {"arrivals": "onoff", "rate_per_s": 50.0, "on_s": 1.0, "off_s": 3.0},
])
def test_open_loop_work_is_the_same_for_every_seed(traffic):
    runs = [loadgen.arrival_offsets(traffic, 20.0, np.random.default_rng(s))
            for s in (1, 2, 2**31 + 11)]
    for t in runs:
        assert len(t) == 1000
        assert t[0] == 0.0 and (np.diff(t) >= 0).all() and t[-1] < 20.0
    assert not np.allclose(runs[0], runs[1])


def test_onoff_sends_nothing_in_the_pauses():
    t = loadgen.arrival_offsets({"arrivals": "onoff", "rate_per_s": 40.0,
                                 "on_s": 1.0, "off_s": 3.0}, 20.0,
                                np.random.default_rng(3))
    assert ((t % 4.0) < 1.0 + 1e-9).all()
