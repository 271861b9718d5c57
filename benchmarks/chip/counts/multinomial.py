"""Work one Gibbs iteration of the multinomial DPMM needs, counted from
the sizes alone (whatever path implements it).

Per point: the log likelihood under each of the K_active clusters and
under its own cluster's 2 sub-clusters (a dot product with log theta,
2 d), and two statistic folds (the sweep's and the split/merge
consistency pass) into (n, summed counts): d + 1 each. Bytes: x (float32
counts) read by both passes; labels and sub-labels (int32) written by
the sweep, then read and written by split/merge.
"""


def work(n: int, d: int, k_active: float) -> dict:
    flops = n * ((k_active + 2) * 2 * d + 2 * (d + 1))
    bytes_ = 2 * n * d * 4 + 3 * 2 * n * 4
    return {"flops": float(flops), "bytes": float(bytes_)}


def query_work(rows: int, d: int, k_active: int, k_max: int) -> dict:
    """Work the assignment engine needs to answer ``rows`` query rows:
    each row's dot product with log theta under the K_active served
    clusters (2 d each) and their log-sum-exp (3 each); bytes: the rows
    read, and each row's label (int32), log predictive density and K_max
    log posteriors (float32) written."""
    flops = rows * k_active * (2 * d + 3)
    bytes_ = rows * (d * 4 + 4 + 4 + k_max * 4)
    return {"flops": float(flops), "bytes": float(bytes_)}
