"""Guards that keep a run off the chip from passing as a chip run.

 - ``FitResult.sweep_paths`` counts which sweep body each traced program
   ran; a ``use_pallas`` sweep that lands on the jnp reference warns;
 - ``cfg.workers`` refuses to start worker processes on a TPU backend
   (the coordinator already holds the chip);
 - on a TPU, the measured peak is the device's, never host RSS;
 - the entry points' compile-cache rule and their refusal to run Pallas
   in interpret mode;
 - ``chip_smoke.py`` fails, printing no result, without a TPU or without
   the repo next to it.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import DPMMConfig
from repro.core import sampler
from repro.core.family import SweepFallbackWarning
from repro.core.sampler import DPMM
from repro.data.synthetic import generate_gmm
from repro.kernels import ops
from repro.launch import entry

ROOT = Path(__file__).resolve().parents[1]


def _fit(use_pallas, **kw):
    x, _ = generate_gmm(600, 3, 3, seed=0, sep=8.0)
    cfg = DPMMConfig(alpha=10.0, iters=4, k_max=8, burnout=2, log_every=4,
                     use_pallas=use_pallas, **kw)
    return DPMM(cfg).fit(x)


# ---------------------------------------------------------------------------
# which sweep body ran
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("use_pallas", [False, True])
def test_sweep_paths_count_the_body_that_ran(use_pallas):
    r = _fit(use_pallas)
    want = "sweep_fast" if use_pallas else "sweep_ref"
    assert r.sweep_paths.get(want, 0) > 0, r.sweep_paths
    assert set(r.sweep_paths) == {want}, r.sweep_paths


def test_pallas_fallback_to_reference_is_reported(monkeypatch):
    """A use_pallas sweep pushed outside every kernel envelope runs
    sweep_ref — and says so, in a warning and in the count."""
    monkeypatch.setattr(ops, "KERNEL_BLOCK_VMEM_BYTES", 0)
    with pytest.warns(SweepFallbackWarning, match="VMEM envelope"):
        r = _fit(True)
    assert r.sweep_paths.get("sweep_ref", 0) > 0, r.sweep_paths
    assert "sweep_fast" not in r.sweep_paths


def test_sweep_paths_on_the_tiled_plane():
    r = _fit(True, tile_size=1024)
    assert set(r.sweep_paths) == {"sweep_fast"}, r.sweep_paths
    assert r.device_bytes["mesh_devices"] >= 1


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------
def test_workers_refused_on_a_tpu_backend(monkeypatch):
    from repro.dist import ChipHeldError
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x, _ = generate_gmm(256, 2, 2, seed=0)
    cfg = DPMMConfig(iters=2, k_max=8, workers=2)
    with pytest.raises(ChipHeldError, match="shard_map mesh"):
        DPMM(cfg).fit(x)


# ---------------------------------------------------------------------------
# device memory is measured on the device
# ---------------------------------------------------------------------------
class _Device:
    def __init__(self, platform, stats):
        self.platform, self.device_kind, self._stats = (
            platform, f"fake {platform}", stats)

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_measured_peak_never_reports_rss_on_a_tpu(monkeypatch, platform):
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_Device(platform, None)])
    if platform == "tpu":
        with pytest.raises(RuntimeError, match="host RSS"):
            sampler._measured_peak()
    else:
        _, src = sampler._measured_peak()
        assert src.startswith("process_peak_rss")
    monkeypatch.setattr(jax, "local_devices", lambda: [
        _Device(platform, {"peak_bytes_in_use": 123})])
    assert sampler._measured_peak() == (123, "device.memory_stats")


# ---------------------------------------------------------------------------
# entry-point rules
# ---------------------------------------------------------------------------
@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test that sets it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_rule(monkeypatch, tmp_path, cache_config, env_set):
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv(entry.CACHE_ENV, str(tmp_path / "outside"))
        assert entry.configure_compile_cache() == str(tmp_path / "outside")
        assert jax.config.jax_compilation_cache_dir == before
    else:
        monkeypatch.delenv(entry.CACHE_ENV, raising=False)
        path = entry.configure_compile_cache(tmp_path)
        assert path == str(tmp_path / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert entry.configure_compile_cache(tmp_path) == path  # fixed


def test_pallas_entry_points_need_the_chip():
    entry.require_chip_for_pallas(False)
    with pytest.raises(SystemExit, match="interpret mode"):
        entry.require_chip_for_pallas(True)


def _run_smoke(script, cwd, tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_fails_without_chip_or_repo(tmp_path, where):
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    proc = _run_smoke(script, script.parent, tmp_path)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            assert not json.loads(line).get("ok")
