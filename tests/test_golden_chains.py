"""Golden-chain fingerprints: unintended chain drift becomes EXPLICIT.

The repo's parity suites prove invariances *within* a run (tiled ==
resident, fused == three-pass, chains == single-chain fits), but nothing
pins the chain itself: a change like PR 3's ``fold_in`` normalization
silently re-rolled every chain and only a careful reader of CHANGES.md
would know. This suite hashes the labels and full history of a
fixed-seed 30-iteration fit per family on BOTH data planes against
``tests/goldens/chains.json``; any drift fails a dedicated CI job.

When a chain change is *intended* (a key-derivation fix, a new fold
order), regenerate and commit the goldens deliberately:

    PYTHONPATH=src python -m pytest tests/test_golden_chains.py -q \
        --update-goldens

Environment contract: fingerprints are taken on the pinned CI jax
version (0.9.0) with the conftest's 4 virtual CPU devices — that is
the environment the golden job provides. XLA codegen may legitimately
differ across versions, so a JAX upgrade re-records them.
"""
import hashlib
import json
import os

import numpy as np
import pytest

from repro.configs import DPMMConfig
from repro.core.gibbs import STATS_BLOCK
from repro.core.sampler import DPMM
from repro.data.synthetic import generate_gmm, generate_mnmm, generate_pmm

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "goldens",
                           "chains.json")
FAMILIES = ("gaussian", "diag_gaussian", "multinomial", "poisson")
PLANES = ("resident", "tiled")
ITERS = 30


def _data(name):
    if name in ("gaussian", "diag_gaussian"):
        return generate_gmm(2400, 4, 4, seed=0, sep=10.0)[0]
    if name == "poisson":
        return generate_pmm(2400, 4, 4, seed=0)[0]
    return generate_mnmm(2400, 16, 4, seed=0)[0]


def _hash(arr) -> str:
    a = np.ascontiguousarray(np.asarray(arr))
    h = hashlib.sha256()
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(a.tobytes())
    return h.hexdigest()[:16]


def _fingerprint(result) -> dict:
    return {
        "labels": _hash(result.labels),
        "k": int(result.k),
        "history": {k: _hash(v) for k, v in sorted(result.history.items())},
    }


def _fit(family: str, plane: str):
    cfg = DPMMConfig(
        component=family, alpha=10.0, iters=ITERS, k_max=16, burnout=4,
        tile_size=(STATS_BLOCK if plane == "tiled" else None))
    return DPMM(cfg).fit(_data(family))


def test_golden_chains(request):
    """One fixed-seed fit per (family, plane); all 8 fingerprints must
    match the committed goldens bit for bit."""
    update = request.config.getoption("--update-goldens")
    fresh = {}
    for family in FAMILIES:
        for plane in PLANES:
            fresh[f"{family}/{plane}"] = _fingerprint(_fit(family, plane))

    # internal sanity: the two planes are the SAME chain (the tiled-parity
    # contract) — if this trips, the golden diff is a plane bug, not drift
    for family in FAMILIES:
        assert (fresh[f"{family}/resident"] == fresh[f"{family}/tiled"]), (
            f"{family}: resident and tiled fingerprints diverged — "
            "tiled-parity violation, not ordinary chain drift")

    if update:
        os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
        with open(GOLDEN_PATH, "w") as f:
            json.dump(fresh, f, indent=2, sort_keys=True)
            f.write("\n")
        pytest.skip(f"goldens rewritten at {GOLDEN_PATH}; commit the diff")

    assert os.path.exists(GOLDEN_PATH), (
        f"no goldens at {GOLDEN_PATH}; generate with --update-goldens")
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)

    drifted = []
    for key, fp in fresh.items():
        if key not in golden:
            drifted.append(f"{key}: missing from goldens")
            continue
        for field, value in fp.items():
            if golden[key].get(field) != value:
                drifted.append(
                    f"{key}.{field}: golden {golden[key].get(field)!r} "
                    f"!= fresh {value!r}")
    assert not drifted, (
        "golden chain drift — chains changed for the same seed. If "
        "intended (key-derivation/fold-order change), regenerate with "
        "--update-goldens and commit; otherwise find the unintended "
        "float/PRNG change:\n  " + "\n  ".join(drifted))


def test_hash_is_content_sensitive():
    """The fingerprint distinguishes values, dtype, and shape."""
    a = np.arange(6, dtype=np.int32)
    assert _hash(a) == _hash(a.copy())
    assert _hash(a) != _hash(a.astype(np.float32))
    assert _hash(a) != _hash(a.reshape(2, 3))
    b = a.copy()
    b[3] += 1
    assert _hash(a) != _hash(b)
