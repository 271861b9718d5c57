"""The work counts of one Gibbs iteration, against hand-counted shapes."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import spec  # noqa: E402


def test_gaussian_counts_by_hand():
    # n=10, d=2, K=3: a density is 2*4 + 3*2 = 14 FLOP, against 3 + 2
    # slots; a fold is 2*4 + 2*2 + 1 = 13 FLOP, twice.
    work = spec.load_module("counts", "gaussian").work(10, 2, 3)
    assert work["flops"] == 10 * (5 * 14 + 2 * 13)
    # x read twice (10*2*4 bytes each) + labels and sub-labels written,
    # then read and written again (3 * 2 * 10 * 4)
    assert work["bytes"] == 2 * 80 + 240


def test_multinomial_counts_by_hand():
    work = spec.load_module("counts", "multinomial").work(10, 4, 3)
    # a dot product with log theta is 2*4 FLOP against 3 + 2 slots; two
    # folds of d + 1 = 5
    assert work["flops"] == 10 * (5 * 8 + 2 * 5)
    assert work["bytes"] == 2 * 10 * 4 * 4 + 240


@pytest.mark.parametrize("family", ["gaussian", "multinomial"])
def test_counts_follow_the_sizes_only(family):
    """The count is of the work the iteration needs: it grows with N and
    K_active and reads nothing of how a path implements it."""
    work = spec.load_module("counts", family).work
    one, two = work(1000, 8, 4), work(2000, 8, 4)
    assert two["flops"] == 2 * one["flops"]
    assert two["bytes"] == 2 * one["bytes"]
    assert work(1000, 8, 8)["flops"] > one["flops"]
    assert work(1000, 8, 8)["bytes"] == one["bytes"]


def test_roofline_share_at_the_cells_sizes_is_below_one_hundred():
    """At the Gaussian cell's sizes, least time is bandwidth-bound and a
    device busy for 1 ms per iteration would read about 35%."""
    reader = spec.load_module("metrics", "step_roofline_pct").read
    ctx = {"trace": {"busy_s": 1e-3 * 10, "window_s": 0.02},
           "iters_traced": 10, "chips": 1,
           "work": spec.load_module("counts", "gaussian").work(
               1_000_000, 32, 19.0),
           "peaks": spec.peaks("TPU v5 lite")}
    got = reader(ctx)
    assert got["bound"] == "bytes"
    assert 30.0 < got["value"] < 40.0


def test_gaussian_query_counts_by_hand():
    # 10 rows, d=2, 3 served clusters: a density is 2*4 + 3*2 + 3 = 17 FLOP
    # per cluster; each row reads 2 floats and writes a label, a log
    # predictive density and 5 log posteriors (K_max = 5).
    work = spec.load_module("counts", "gaussian").query_work(10, 2, 3, 5)
    assert work["flops"] == 10 * 3 * 17
    assert work["bytes"] == 10 * (2 * 4 + 4 + 4 + 5 * 4)


def test_query_roofline_at_the_cells_sizes():
    """The serving cell's rows over a second of busy device read a small
    share, bound by bytes; with no trace there is nothing to read."""
    reader = spec.load_module("metrics", "query_roofline").read
    ctx = {"trace": {"busy_s": 1.0, "window_s": 20.0}, "chips": 1,
           "query_work": spec.load_module("counts", "gaussian").query_work(
               1_000_000, 32, 16, 64),
           "peaks": spec.peaks("TPU v5 lite")}
    got = reader(ctx)
    assert got["bound"] == "bytes"
    assert 0.0 < got["value"] < 100.0
    assert reader({}) is None


def test_multinomial_query_counts_by_hand():
    # 10 rows, d=4, 3 served clusters: a dot product with log theta is
    # 2*4 = 8 FLOP and the log-sum-exp 3 more per cluster; each row reads
    # 4 floats and writes a label, a log predictive density and 5 log
    # posteriors (K_max = 5).
    work = spec.load_module("counts", "multinomial").query_work(10, 4, 3, 5)
    assert work["flops"] == 10 * 3 * 11
    assert work["bytes"] == 10 * (4 * 4 + 4 + 4 + 5 * 4)


@pytest.mark.parametrize("family", ["gaussian", "multinomial"])
def test_query_counts_follow_the_sizes_only(family):
    query_work = spec.load_module("counts", family).query_work
    one, two = query_work(1000, 8, 4, 64), query_work(2000, 8, 4, 64)
    assert two["flops"] == 2 * one["flops"]
    assert two["bytes"] == 2 * one["bytes"]
    assert query_work(1000, 8, 8, 64)["flops"] == 2 * one["flops"]
    assert query_work(1000, 8, 8, 64)["bytes"] == one["bytes"]
    assert query_work(1000, 8, 4, 128)["bytes"] > one["bytes"]
