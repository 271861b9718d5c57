"""Start-up rules shared by the entry points (``chip_smoke.py`` and the
``launch/*_dpmm.py`` CLIs) — never applied by library imports, so tests
stay uncached and free to run Pallas in interpret mode.

Compile cache: with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself
and nothing else is set in code; otherwise the cache lives at
``<checkout>/.jax_cache``, a fixed path (the path is part of the cache
key, so a temp-, pid- or time-based directory never hits).

Pallas: on any backend but the TPU the kernels run in interpret mode, the
CPU test path, at Python speed. An entry point asked for Pallas refuses
to start there instead of passing slowly.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def configure_compile_cache(root: Optional[Path] = None) -> str:
    """Apply the cache rule; returns the cache directory in use."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    path = str(Path(root or CHECKOUT) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_chip_for_pallas(use_pallas: bool) -> None:
    """SystemExit when Pallas is asked for off the TPU."""
    import jax
    backend = jax.default_backend()
    if use_pallas and backend != "tpu":
        raise SystemExit(
            f"--use-pallas needs a TPU; the backend is {backend!r}, where "
            "the kernels would run in interpret mode (the CPU test path)")
