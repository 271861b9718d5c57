"""Data laws: the built-in generators draw what they always drew, and a
law brought as a file, ``generators/<name>.py``, is found by its name,
held to its contract, and drives a serving cell's and a fit cell's
set-up with nothing else changed."""
import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
from chipbench import datagen, serve, spec  # noqa: E402
from chipbench_tiny import tiny_cell, tiny_serve_cell  # noqa: E402

SEED = 2 ** 33 + 11

# sha256 of x's bytes then the labels' bytes, as the generators drew them
# before a law could be a file
PINNED = {
    "gmm": (dict(n=500, d=4, k=3, sep=6.0),
            "07fc96433ae278e4e0361586a541115e5250401480f0329d6dda32dafeca5c4f"),
    "mnmm": (dict(n=500, d=16, k=3, trials=50, concentration=0.2),
             "a0641594545a3586436ba93d41363fc678d85519095647a1eaceaf3d511d8213"),
}

# A law the benchmark's code has never seen: the Gaussian mixture's points
# with their coordinates reversed, so that its draws differ from gmm's.
LAW = '''
import numpy as np
from chipbench import datagen


def generate(n, d, k, seed, sep):
    x, labels = datagen.generate_gmm(n, d, k, seed, sep)
    return np.ascontiguousarray(x[:, ::-1]), labels


def mixture(d, k, seed, sep):
    return datagen.gmm_mixture(d, k, seed, sep)
'''


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_built_in_generators_draw_as_they_did(name):
    params, digest = PINNED[name]
    x, labels = datagen.generate(dict(params, generator=name), SEED)
    assert x.dtype == np.float32 and x.shape == (params["n"], params["d"])
    assert labels.dtype == np.int32 and labels.shape == (params["n"],)
    got = hashlib.sha256(x.tobytes() + labels.tobytes()).hexdigest()
    assert got == digest


@pytest.fixture
def here(tmp_path, monkeypatch):
    monkeypatch.setattr(spec, "HERE", tmp_path)
    (tmp_path / "generators").mkdir()
    (tmp_path / "generators" / "reversed_gmm.py").write_text(LAW)
    (tmp_path / "generators" / "short.py").write_text(
        "import numpy as np\n\n\ndef generate(n, d, k, seed):\n"
        "    return (np.zeros((n - 1, d), np.float32),\n"
        "            np.zeros((n - 1,), np.int32))\n")
    return tmp_path


def test_a_generator_file_is_found_by_its_name(here):
    data = {"generator": "reversed_gmm", "n": 300, "d": 4, "k": 3,
            "sep": 6.0}
    x, labels = datagen.generate(data, SEED)
    want_x, want_labels = datagen.generate(dict(data, generator="gmm"),
                                           SEED)
    assert np.array_equal(x, want_x[:, ::-1])
    assert np.array_equal(labels, want_labels)
    law = spec.load_module("generators", "reversed_gmm", here)
    assert np.array_equal(law.mixture(4, 3, SEED, 6.0)["means"],
                          datagen.gmm_mixture(4, 3, SEED, 6.0)["means"])


def test_a_generator_file_is_held_to_its_contract(here):
    with pytest.raises(ValueError, match="contract"):
        datagen.generate({"generator": "short", "n": 10, "d": 2, "k": 1},
                         SEED)
    with pytest.raises(FileNotFoundError):
        datagen.generate({"generator": "absent", "n": 10, "d": 2, "k": 1},
                         SEED)


@pytest.fixture
def cells():
    return tiny_serve_cell(), tiny_cell()


def test_cells_set_up_on_a_generator_file(cells, here):
    """A configuration whose ``data.generator`` names a file: the
    serving cell's pool and the fit cell's points are that law's draws,
    through ``serve.prepare`` and ``run.prepare`` as they stand. (The
    cells are read from the benchmark's files before ``here`` stands in
    for its directory.)"""
    cell, fit = cells
    cell["config"] = dict(cell["config"], data=dict(
        cell["config"]["data"], generator="reversed_gmm"))
    cell["mix"] = dict(cell["mix"], pool_rows=2048)
    prep = serve.prepare(cell, SEED)
    data_seed, _ = serve.derive_seeds(SEED)
    want, _ = datagen.generate(dict(cell["config"]["data"], n=2048),
                               data_seed)
    assert np.array_equal(prep["pool"], want)
    assert prep["engine"].query(prep["pool"][:5]).labels.shape == (5,)

    fit["config"]["data"].update(generator="reversed_gmm", n=512)
    fit["mix"] = dict(fit["mix"], burn_in_iters=2)
    prep = run.prepare(fit, SEED)
    data_seed, _ = run.derive_seeds(SEED)
    want, _ = datagen.generate(fit["config"]["data"], data_seed)
    assert np.array_equal(prep["x"], want)
    assert prep["burned"].iter_times_s
