"""Elastic multi-process distributed sampling (coordinator/worker shards).

``DPMMConfig.workers=N`` routes ``DPMM.fit`` through this package: a
coordinator process (repro.dist.coordinator) owns ModelState and every
O(K) step, N worker processes (repro.dist.worker) each own a
STATS_BLOCK-aligned row-range shard of x and stream the per-point tile
bodies over it, shipping per-block suff-stat partials back over a
framed, CRC-checked socket protocol (repro.dist.proto).

The package's contract, asserted in tests/test_dist.py and gated in CI:
the distributed chain is **bitwise identical** to the single-process
tiled fit at any worker count, including across worker SIGKILL / hang
failover (row ranges are reassigned to survivors and respawns; the fold
replay order never changes).
"""
from repro.dist.proto import ProtocolError
from repro.dist.coordinator import (ChipHeldError, Coordinator, DistHooks,
                                   fit_distributed)

__all__ = ["ChipHeldError", "Coordinator", "DistHooks", "ProtocolError",
           "fit_distributed"]
