"""Cells at a size the CPU test run can hold: a configuration's family,
sampler settings and limits with the data shrunk, for the tests that
drive a whole run with the timed path broken underneath."""
import contextlib
import json
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]


SIZES = {"gaussian": dict(n=4096, d=4, k=3),
         "multinomial": dict(n=4096, d=16, k=3)}


def tiny_cell(config: str = "gauss_n1m_d32_k16", chips: int = 1) -> dict:
    config = json.loads((HERE / "configs" / f"{config}.json").read_text())
    config["data"].update(SIZES[config["family"]])
    return {"name": "tiny", "chips": chips, "traffic": "tiny",
            "config": config,
            "mix": {"kind": "fit_continuation", "burn_in_iters": 10, "min_window_chunks": 2,
                    "trace_chunks": 2, "data_shards": chips},
            "end_to_end": [{"name": "iter_ms", "unit": "ms"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


@contextlib.contextmanager
def exchange_left_out():
    """The stat fold's cross-chip psum returns each chip's own partial."""
    from unittest import mock
    import repro.core.gibbs as gibbs
    with mock.patch.object(gibbs, "psum_tree", lambda tree, axes: tree):
        yield


def tiny_serve_cell(rate_rps: float = 40.0) -> dict:
    """The serving cell with a pool and a rate the CPU test run holds;
    the model is the configuration's own."""
    import sys
    sys.path.insert(0, str(HERE))
    from chipbench import spec
    cell = spec.load_cell("gauss_n1m_d32_k16.serve_knee80")
    cell["mix"] = dict(cell["mix"], rate_rps=rate_rps, pool_rows=16384,
                       check_requests=8)
    return cell
