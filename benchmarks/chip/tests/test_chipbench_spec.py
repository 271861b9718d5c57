"""Discovery of cells, configurations, mixes and metric readers by name,
from files alone."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from chipbench import spec  # noqa: E402

ROOT = HERE.parents[1]


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(obj if isinstance(obj, str) else json.dumps(obj))


@pytest.fixture
def tree(tmp_path):
    """A checkout holding one configuration, one mix and one metric that
    the benchmark's code has never seen."""
    here = tmp_path / "bench"
    _write(here / "configs" / "toy.json", {"family": "toy", "data": {}})
    _write(here / "mixes" / "burst.json", {"data_shards": 1})
    _write(here / "metrics" / "toy_share.py",
           "def read(ctx):\n    return ctx.get('toy')\n")
    _write(tmp_path / "BENCHMARK.json", {
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.burst", "config": "toy",
                       "traffic": "burst", "chips": 1}],
        "end_to_end": [{"name": "iter_ms"}, {"name": "setup_s"},
                       {"name": "other", "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "toy_share", "moves": "iter_ms"},
                      {"name": "not_here", "moves": "iter_ms",
                       "workloads": ["elsewhere"]}]})
    return tmp_path, here


def test_a_new_cell_is_found_by_name_from_its_files(tree):
    root, here = tree
    cell = spec.load_cell("toy.burst", root=root, here=here)
    assert cell["config"]["family"] == "toy"
    assert cell["mix"] == {"data_shards": 1}
    assert [m["name"] for m in cell["end_to_end"]] == ["iter_ms", "setup_s"]
    assert [m["name"] for m in cell["per_layer"]] == ["toy_share"]
    reader = spec.load_module("metrics", "toy_share", here=here)
    assert reader.read({"toy": 1.5}) == 1.5
    assert reader.read({}) is None


def test_unknown_names_are_errors(tree):
    root, here = tree
    with pytest.raises(KeyError):
        spec.load_cell("toy.steady", root=root, here=here)
    with pytest.raises(FileNotFoundError):
        spec.load_module("metrics", "absent", here=here)
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name", [
    w["name"] for w in
    json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_every_cell_of_the_benchmark_resolves(name):
    cell = spec.load_cell(name)
    family = cell["config"]["family"]
    assert cell["mix"].get("data_shards", cell["chips"]) == cell["chips"]
    for kind in ("counts", "reference", "served"):
        spec.load_module(kind, family)
    for metric in cell["per_layer"]:
        module = spec.load_module("metrics", metric["name"])
        assert module.read({}) is None      # nothing to read, nothing back
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["mix"]["kind"] in cell["config"]["limits"]
