"""Seeded synthetic data for the benchmark's cells.

A copy of the program's generators (``repro.data.synthetic``), kept here so
that the benchmark's yardstick does not move when the program changes.
``generate`` is the one entry point: a configuration's ``data`` block names
the generator and its parameters.

A data law beyond ``GENERATORS`` is a file of its own,
``generators/<name>.py``, found by the name in ``data["generator"]``. It
defines ``generate(n, d, k, seed, **params)``, returning ``x`` float32
(n, d) and the true labels int32 (n,), the same for the same seed; and,
where a ``served/<family>.py`` builds its model from the law, may define
``mixture(d, k, seed, **params)``, the law's parameters for that seed.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from chipbench import spec


def _gmm_mixture(rng: np.random.Generator, d: int, k: int, sep: float):
    means = rng.normal(0.0, sep, size=(k, d))
    covs = np.zeros((k, d, d))
    for j in range(k):
        q, _ = np.linalg.qr(rng.normal(size=(d, d)))
        eig = rng.uniform(0.3, 1.3, size=(d,))
        covs[j] = (q * eig) @ q.T
    weights = rng.dirichlet(np.full(k, 5.0))
    return means, covs, weights


def gmm_mixture(d: int, k: int, seed: int = 0, sep: float = 6.0) -> dict:
    """The mixture ``generate_gmm`` draws its points from, for the same
    seed: float64 ``means`` (k, d), ``covs`` (k, d, d), ``weights`` (k,)."""
    means, covs, weights = _gmm_mixture(np.random.default_rng(seed), d, k,
                                        sep)
    return {"means": means, "covs": covs, "weights": weights}


def generate_gmm(n: int, d: int, k: int, seed: int = 0,
                 sep: float = 6.0) -> Tuple[np.ndarray, np.ndarray]:
    """Gaussian mixture: means ~ N(0, sep^2 I), covariances with
    eigenvalues in [0.3, 1.3], Dirichlet(5) weights. Returns (x (n, d)
    float32, labels (n,) int32)."""
    rng = np.random.default_rng(seed)
    means, covs, weights = _gmm_mixture(rng, d, k, sep)
    labels = rng.choice(k, size=n, p=weights).astype(np.int32)
    x = np.empty((n, d), np.float32)
    for j in range(k):
        idx = np.nonzero(labels == j)[0]
        if idx.size:
            l_chol = np.linalg.cholesky(covs[j])
            z = rng.normal(size=(idx.size, d))
            x[idx] = (means[j] + z @ l_chol.T).astype(np.float32)
    return x, labels


def generate_mnmm(n: int, d: int, k: int, seed: int = 0,
                  trials: int = 50, concentration: float = 0.2
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Multinomial mixture: each point is a count vector of ``trials``
    draws from its cluster's Dirichlet(concentration) topic."""
    rng = np.random.default_rng(seed)
    thetas = rng.dirichlet(np.full(d, concentration), size=k)
    weights = rng.dirichlet(np.full(k, 5.0))
    labels = rng.choice(k, size=n, p=weights).astype(np.int32)
    x = np.empty((n, d), np.float32)
    for j in range(k):
        idx = np.nonzero(labels == j)[0]
        if idx.size:
            x[idx] = rng.multinomial(trials, thetas[j], size=idx.size)
    return x, labels


GENERATORS = {"gmm": generate_gmm, "mnmm": generate_mnmm}


def generate(data: dict, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(x, true labels) for a configuration's ``data`` block, e.g.
    ``{"generator": "gmm", "n": 1000000, "d": 32, "k": 16, "sep": 6.0}``:
    a generator of ``GENERATORS``, else ``generators/<name>.py`` under
    the benchmark's directory (``spec.HERE``)."""
    name = data["generator"]
    params = {key: v for key, v in data.items() if key != "generator"}
    if name in GENERATORS:
        return GENERATORS[name](seed=seed, **params)
    law = spec.load_module("generators", name, spec.HERE)
    x, labels = law.generate(seed=seed, **params)
    n, d = int(data["n"]), int(data["d"])
    if (x.dtype != np.float32 or x.shape != (n, d)
            or labels.dtype != np.int32 or labels.shape != (n,)):
        raise ValueError(f"generator {name!r} returned x {x.dtype} "
                         f"{x.shape} and labels {labels.dtype} "
                         f"{labels.shape}; the contract is float32 "
                         f"({n}, {d}) and int32 ({n},)")
    return x, labels
