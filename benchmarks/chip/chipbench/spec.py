"""Discovery of a cell and of everything it names, by name, from files.

``BENCHMARK.json`` (at the checkout's root) lists the cells; a cell names
a configuration (its file is in the ``configs`` entry) and a traffic mix
(``mixes/<traffic>.json``). A per-layer metric ``m`` is read by
``metrics/<m>.py``; a family ``f``'s work per iteration is
``counts/<f>.py``, its plain reference ``reference/<f>.py`` and its
served model ``served/<f>.py``; a data law ``g`` other than the built-in
``gmm`` and ``mnmm`` is ``generators/<g>.py``, defining
``generate(n, d, k, seed, **params) -> (x float32 (n, d), labels int32
(n,))`` and, for a served model to build from, optionally
``mixture(d, k, seed, **params)`` (``chipbench/datagen.py``). Adding any
of them is adding a file and an entry: nothing here changes.

A metric reader's ``read(ctx)`` gets the traced run's context: ``trace``
(the device's busy time and breakdown), ``chips``, ``peaks`` and the
counts of the work, and ``program``, every ``dpmm.*`` span of the
program in the window by name (``programspans.reduce_program``); a
serving run's also ``engine``, the serving engine's readings
(``programspans.engine_readings``).
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Optional

HERE = Path(__file__).resolve().parents[1]      # benchmarks/chip
ROOT = HERE.parents[1]                          # the checkout


def load_module(kind: str, name: str, here: Path = HERE) -> ModuleType:
    """``<here>/<kind>/<name>.py`` as a module."""
    path = here / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str, root: Path = ROOT, here: Optional[Path] = None
              ) -> dict:
    """The cell ``name`` with its configuration, mix and metric entries
    resolved from files."""
    here = here or HERE
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / config_entry["file"]).read_text())
    mix = json.loads((here / "mixes" / f"{cell['traffic']}.json")
                     .read_text())

    def mine(metric: dict, default: bool) -> bool:
        return name in metric["workloads"] if "workloads" in metric \
            else default

    end_to_end = [m for m in bench["end_to_end"] if mine(m, True)]
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if mine(m, m["moves"] in e2e_names)]
    return {"name": name, "chips": int(cell["chips"]), "config": config,
            "config_name": cell["config"], "traffic": cell["traffic"],
            "mix": mix, "end_to_end": end_to_end, "per_layer": per_layer}


def peaks(device_kind: str, here: Path = HERE) -> dict:
    """The chip's published peaks; a device not in the table is an
    error, never a default."""
    table = json.loads((here / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]
