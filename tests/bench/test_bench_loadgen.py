"""The benchmark's generator: the seed fixes the data and the schedule."""
import numpy as np
import pytest

import benchtiny  # noqa: F401  (puts the harness on the path)
import loadgen
import spec

BIG = 2 ** 31 + 12345  # seeds past 32 signed bits are valid


def _mix(name):
    return spec.traffic(name), spec.cell("stream-ycsb-b").config


@pytest.mark.parametrize("mix", ["ycsb-b", "ycsb-a"])
def test_same_seed_same_schedule(mix):
    traffic, conf = _mix(mix)
    a = loadgen.open_loop(conf, traffic, BIG, 30.0)
    b = loadgen.open_loop(conf, traffic, BIG, 30.0)
    np.testing.assert_array_equal(a.t, b.t)
    np.testing.assert_array_equal(a.insert, b.insert)
    np.testing.assert_array_equal(a.x, b.x)
    np.testing.assert_array_equal(a.y, b.y)
    assert a.kind == b.kind


@pytest.mark.parametrize("mix", ["ycsb-b", "ycsb-a"])
def test_other_seed_other_schedule_same_work(mix):
    traffic, conf = _mix(mix)
    a = loadgen.open_loop(conf, traffic, BIG, 30.0)
    b = loadgen.open_loop(conf, traffic, BIG + 1, 30.0)
    assert not np.array_equal(a.t, b.t)
    assert not np.array_equal(a.x, b.x)
    # the same number of operations of each kind, in another order
    assert len(a.t) == len(b.t) == round(traffic["rate_per_s"] * 30.0)
    assert a.insert.sum() == b.insert.sum() == round(
        len(a.t) * traffic["insert_share"])
    for k in traffic["query_kinds"]:
        assert a.kind.count(k) == b.kind.count(k)
    assert np.all(np.diff(a.t) >= 0) and a.t[-1] < 30.0
    assert np.all(np.isnan(a.y[~a.insert])) and np.all(np.isfinite(a.y[a.insert]))


@pytest.mark.parametrize("mix", ["ycsb-b", "ycsb-a"])
def test_every_seed_same_gaps_and_insert_strata(mix):
    traffic, conf = _mix(mix)
    a = loadgen.open_loop(conf, traffic, BIG, 30.0)
    b = loadgen.open_loop(conf, traffic, 7, 30.0)
    n, block = len(a.t), traffic["block_ops"]
    fixed = loadgen.gaps(traffic["rate_per_s"], block, n)
    np.testing.assert_allclose(fixed.sum(), 30.0)
    for s in (a, b):
        gap = np.diff(np.append(s.t, 30.0))
        for lo in range(0, n, block):
            np.testing.assert_allclose(np.sort(gap[lo:lo + block]),
                                       np.sort(fixed[lo:lo + block]))
        # one insert in each of the equal strata of the operations
        n_ins = int(s.insert.sum())
        edges = np.floor(np.arange(n_ins + 1) * n / n_ins).astype(int)
        assert all(s.insert[lo:hi].sum() == 1
                   for lo, hi in zip(edges[:-1], edges[1:]))
    assert not np.array_equal(a.insert, b.insert)


def test_data_from_seed():
    conf = spec.cell("stream-ycsb-a").config
    x1, y1 = loadgen.observe(conf, loadgen.rng(BIG, "data"), 100)
    x2, y2 = loadgen.observe(conf, loadgen.rng(BIG, "data"), 100)
    x3, _ = loadgen.observe(conf, loadgen.rng(BIG + 1, "data"), 100)
    np.testing.assert_array_equal(x1, x2)
    np.testing.assert_array_equal(y1, y2)
    assert not np.array_equal(x1, x3)
    assert np.all(np.abs(x1) <= 500.0)
    # streams of one seed are independent: the check's places are not the data
    xc, _ = loadgen.observe(conf, loadgen.rng(BIG, "check"), 100)
    assert not np.array_equal(x1, xc)


def test_round_streams_from_seed():
    """A stream keyed by a round's index: the same seed and round give the
    same draws, other rounds and the unkeyed stream others."""
    a = loadgen.rng(BIG, "data", 3).uniform(size=8)
    np.testing.assert_array_equal(a, loadgen.rng(BIG, "data", 3).uniform(size=8))
    for other in (loadgen.rng(BIG, "data", 4), loadgen.rng(BIG, "data"),
                  loadgen.rng(BIG, "probes", 3), loadgen.rng(BIG + 1, "data", 3)):
        assert not np.array_equal(a, other.uniform(size=8))
