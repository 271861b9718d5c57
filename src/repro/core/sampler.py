"""Top-level distributed DPMM sampler — the paper's `fit` entry point.

Composition per iteration (paper §4.1):
    restricted Gibbs sweep  ->  splits  ->  merges  ->  stats consistency
with splits/merges gated by ``burnout``. Observation models are
``ComponentFamily`` instances looked up from the registry (core/family.py)
by ``cfg.component`` — the sampler never inspects param/stat pytrees
itself.

Two data planes share every sampling body (core/gibbs.py,
core/splitmerge.py — the split is model-side O(K) math vs per-point tile
bodies):

 - **Resident** (``cfg.tile_size is None`` and the source is resident):
   points are device-resident; ``cfg.log_every`` iterations run inside one
   jitted, buffer-donated ``lax.scan`` chunk that carries the
   (ModelState, PointState) pair and collects ``summarize()`` history on
   device, so the host blocks once per chunk — no O(iters) round-trips.
 - **Tiled / out-of-core** (``cfg.tile_size`` set, or a non-resident
   ``DataSource``): only ModelState persists on device. Points stream
   through fixed-size tiles pulled from the ``DataSource``
   (data/source.py) with double-buffered ``jax.device_put``; per-point
   labels live in host arrays and ride along with their tile. Device
   memory is O(K_max + tile), so N is bounded by host storage, not HBM.

Because per-point randomness is counter-based on the *global* point index
and suff-stats fold in fixed STATS_BLOCK-aligned blocks (core/gibbs.py),
the two planes produce bitwise-identical chains — tile size, like shard
count, is a pure performance knob.

**Multi-chain fits** (``fit(..., n_chains=C)``): both drivers carry an
optional leading *chain axis* on the (ModelState, PointState) pair. The C
chains run inside the same jitted chunk via ``jax.lax.map`` over that
axis, sharing ONE device-resident copy of x (the points are closed over,
never duplicated per chain, and in tiled mode each streamed tile is
uploaded once and consumed by every chain) and syncing with the host once
per chunk total — not once per chain. ``lax.map`` (not ``vmap``) is the
batching transform on purpose: it traces the *identical* unbatched chain
body per slice, so chain c of an ``n_chains=C`` fit is **bitwise
identical** to an independent single-chain fit with
``key=fold_in(key(seed), c)`` — vmap's batched reductions reassociate
float additions and break the repo's bitwise-chain contract (measured:
ULP drift in stats by iteration 1). Cross-chain diagnostics ride on the
result: ``FitResult.rhat`` (split-R-hat over history traces),
``FitResult.select_best`` (max posterior ``score``), and per-chain views
via ``FitResult.chain(c)``.

Example (paper §3.4.1 analogue):
    >>> from repro.core.sampler import DPMM
    >>> from repro.configs import DPMMConfig
    >>> model = DPMM(DPMMConfig(alpha=10., iters=100))
    >>> result = model.fit(x)          # x: (N, d) np.ndarray or DataSource
    >>> result.labels, result.k, result.nmi(gt)
    >>> best = model.fit(x, n_chains=4).select_best()   # parallel chains
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import gammaln
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import DPMMConfig
from repro.core import checkpoint as _checkpoint
from repro.core import gibbs, splitmerge
from repro.core.distributed import (data_axes_of, make_data_mesh,
                                    n_data_shards, shard_points, tile_plan)
from repro.core.family import (ComponentFamily, get_family,
                               record_sweep_paths, state_partition_specs)
from repro.core.metrics import ari, nmi
from repro.core.resilience import (DivergenceError, RetryPolicy,
                                   model_health, read_block_checked)
from repro.core.state import ModelState, PointState, grow_model
from repro.data.source import DataSource, as_source

_HIST_KEYS = ("k", "max_cluster", "min_cluster", "score")

# Rollback key stream: fold_in values >= 2**30 are disjoint from both
# per-iteration streams (the sweep folds it in [0, iters), split/merge
# folds -(it+1)), so a recovered chain never collides with the clean one.
_RECOVERY_FOLD = (1 << 30) + 1337


def _recovery_rekey(model: ModelState, n_rollback: int) -> ModelState:
    """Advance the chain key after a divergence rollback: replaying the
    exact (key, it) stream that just diverged would be futile when the
    divergence is state-dependent, so each rollback folds a reserved
    counter into the key. Multi-chain keys advance per chain (vmap over
    the (C,) key axis — integer math, exact)."""
    fold = _RECOVERY_FOLD + n_rollback

    def f(k):
        return jax.random.fold_in(k, fold)
    key = model.key
    return model._replace(key=f(key) if key.ndim == 0 else jax.vmap(f)(key))


class _Recovery:
    """Shared per-fit bookkeeping for auto-checkpointing and divergence
    rollback (both drivers). ``events`` becomes ``FitResult.recoveries``;
    it also collects the tile-read retry events the streaming path
    reports (core/resilience.read_block_checked)."""

    def __init__(self, cfg: DPMMConfig, family_name: str, it_base: int):
        self.cfg = cfg
        self.events: List[dict] = []
        self.n_rollbacks = 0
        self._family = family_name
        self._last_saved = it_base

    def maybe_checkpoint(self, model: ModelState, it_abs: int,
                         force: bool = False) -> None:
        """Save a rotation member when ``checkpoint_every`` iterations
        have passed since the last save (the resident driver calls this
        at chunk boundaries, so saves land on the first boundary past
        each multiple). ``force`` saves the final state regardless of
        cadence (but never duplicates an already-saved iteration)."""
        cfg = self.cfg
        if not (cfg.checkpoint_path and cfg.checkpoint_every):
            return
        due = it_abs - self._last_saved >= cfg.checkpoint_every
        if (force and it_abs > self._last_saved) or due:
            _checkpoint.save_checkpoint(cfg.checkpoint_path, model,
                                        self._family, it_abs,
                                        keep=cfg.checkpoint_keep)
            self._last_saved = it_abs

    def rollback(self, it_abs: int, restored_it: int, detail: str) -> None:
        """Record a divergence rollback; raise once the budget is spent
        (carrying the full event log for the post-mortem)."""
        self.n_rollbacks += 1
        self.events.append({"kind": "divergence_rollback",
                            "iter": int(it_abs),
                            "restored_it": int(restored_it),
                            "rollback": self.n_rollbacks,
                            "detail": detail})
        if self.n_rollbacks > self.cfg.max_recoveries:
            raise DivergenceError(
                f"chain state went non-finite/degenerate at iteration "
                f"{it_abs} and rollback did not recover it within "
                f"max_recoveries={self.cfg.max_recoveries} attempts — "
                "the divergence is persistent (non-finite input data, or "
                "a numerically hostile configuration). See .recoveries "
                "for the event log.", self.events)


def chain_score(model: ModelState, prior, family, alpha: float) -> jax.Array:
    """Collapsed log posterior density of the chain's clustering (up to a
    data-independent constant): the CRP EPPF plus the per-cluster marginal
    likelihoods, ``sum_k [log alpha + lgamma(N_k) + log m(prior, S_k)]``
    over active clusters. O(K) — no per-point input. This is the ranking
    used by ``FitResult.select_best`` and the 'score' history trace R-hat
    diagnoses (inactive slots are masked BEFORE the sum, so their
    unnormalized stats never contribute NaNs)."""
    logm = family.log_marginal(prior, model.stats)
    act = model.active
    occ = jnp.where(act, jnp.maximum(model.stats.n, 1.0), 1.0)
    return (jnp.sum(jnp.where(act, logm, 0.0))
            + model.k_hat.astype(jnp.float32) * jnp.log(jnp.float32(alpha))
            + jnp.sum(jnp.where(act, gammaln(occ), 0.0))
            ).astype(jnp.float32)


def _summaries(model: ModelState, prior, family, alpha: float) -> dict:
    """Per-step history row: the replicated scalar diagnostics plus the
    posterior 'score' trace (chain_score)."""
    s = model.summarize()
    s["score"] = chain_score(model, prior, family, alpha)
    return s


def _chain_keys(key: jax.Array, n_chains: int) -> jax.Array:
    """(C,) per-chain base keys: ``fold_in(key, c)``. vmap over the
    integer chain ids is exact (threefry is integer math), so chain c's
    key is bit-for-bit the key an independent single-chain fit gets from
    ``fold_in(key, c)``."""
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, jnp.arange(n_chains))


def _ceil_pow2(v: int) -> int:
    return 1 << max(0, (int(v) - 1).bit_length())


def _k_compact(k_hat: int, headroom: int, k_slab: int,
               k_block: int) -> Optional[int]:
    """Static compact-slab size for the sparse-K sweep: covers
    ``headroom * k_hat`` live clusters (headroom 1 when K cannot change
    during the pass — tiled sweeps; 2 when splits may double it — resident
    chunks and split/merge folds), rounded up to a power of two so the
    number of distinct compiled shapes is O(log K) per fit. ``None`` when
    the compact slab would not beat the dense one."""
    kc = max(k_block, _ceil_pow2(headroom * max(1, k_hat)))
    return None if kc >= k_slab else kc


def _chain_map(f):
    """lax.map ``f`` over a leading chain axis of every argument — the
    multi-chain batching transform. The mapped body is the *same traced
    jaxpr* as the unbatched one, which is what keeps per-chain results
    bitwise identical to independent single-chain fits (vmap would batch
    the float reductions and reassociate them)."""
    return lambda *args: jax.lax.map(lambda s: f(*s), args)


def _init_local(key, x, valid, *, prior, family, cfg, axes, k_max,
                feat_axis=None) -> Tuple[ModelState, PointState]:
    """Initial state (runs under shard_map), whole shard as one tile."""
    n_local = x.shape[0]
    gidx = gibbs.global_indices(n_local, axes)
    labels = _init_labels(gidx, cfg.init_clusters)
    # first pass for cluster means, then hyperplane sub-label init
    stats0, _ = gibbs.compute_stats(
        family, x, valid, labels, jnp.zeros_like(labels), k_max, axes,
        feat_axis, cfg.use_pallas)
    means0 = family.cluster_means(stats0)
    v0 = splitmerge.hyperplane_vecs(
        jax.random.fold_in(key, 1), k_max, means0.shape[1], x.dtype)
    sublabels = splitmerge.hyperplane_bits(x, labels, means0, v0, feat_axis)
    stats, substats = gibbs.compute_stats(
        family, x, valid, labels, sublabels, k_max, axes, feat_axis,
        cfg.use_pallas)
    return (_init_model(key, stats, substats, prior=prior, family=family,
                        cfg=cfg, k_max=k_max),
            PointState(labels=labels, sublabels=sublabels, valid=valid))


def _init_labels(gidx: jax.Array, init_clusters: int) -> jax.Array:
    return (gidx % jnp.uint32(init_clusters)).astype(jnp.int32)


def _init_model(key, stats, substats, *, prior, family, cfg,
                k_max) -> ModelState:
    """Replicated O(K) half of initialization, given the initial stats."""
    active = jnp.arange(k_max) < cfg.init_clusters
    params = family.expected_params(prior, stats)
    subparams = family.expected_params(prior, substats)
    # strong dtypes: weak-typed leaves would force a second trace/compile of
    # the chunk fn on its own (strongly-typed) output state
    logw = jnp.where(active, -jnp.log(float(cfg.init_clusters)),
                     gibbs.NEG_INF).astype(jnp.float32)
    sublogw = jnp.full((k_max, 2), jnp.log(0.5), dtype=jnp.float32)
    return ModelState(
        key=key, it=jnp.zeros((), jnp.int32), active=active,
        logweights=logw, sub_logweights=sublogw,
        stuck=jnp.zeros((k_max,), jnp.int32), params=params,
        subparams=subparams, stats=stats, substats=substats)


def _move_key(model: ModelState) -> jax.Array:
    """Per-iteration split/merge key (negative fold: disjoint from the
    sweep's fold_in(key, it) stream)."""
    return jax.random.fold_in(model.key, -(model.it + 1))


def _split_merge(model: ModelState, point: PointState, x, *, prior, family,
                 cfg, axes, k_max, feat_axis=None, k_compact=None
                 ) -> Tuple[ModelState, PointState]:
    """Resident split/merge: plan (O(K)), one whole-shard tile, finalize.

    With ``k_compact`` set, the consistency suff-stat fold runs on a
    compact slab sized for the *post-move* active set — splits at most
    double K per move, so ``min(k_max, 2 * k_compact)`` rows suffice —
    and the finalized stats scatter back to the dense slab (bitwise the
    dense fold). A ``lax.cond`` falls back to the dense fold whenever the
    post-move live count outgrew the bound (possible mid-chunk, where
    ``k_compact`` was sized from a chunk-old k_hat)."""
    plan = splitmerge.plan_split_merge(
        _move_key(model), model, prior, family, cfg.alpha,
        cfg.subreset_every)

    def run(comp):
        k_eff = k_max if comp is None else comp.slot_of_compact.shape[0]
        acc = gibbs.empty_substats(family, k_eff, x.shape[-1])
        point2, acc2 = splitmerge.split_merge_tile(
            plan, x, point, acc, family, use_pallas=cfg.use_pallas,
            feat_axis=feat_axis, compaction=comp)
        # consistency pass (paper §4.4: 'processing accepted splits/merges
        # requires updating the sufficient statistics', O(N/G) + one psum)
        stats3, substats3 = gibbs.finalize_substats(family, acc2, axes,
                                                    feat_axis)
        if comp is not None:
            stats3 = gibbs.compact_scatter(comp, k_max, stats3)
            substats3 = gibbs.compact_scatter(comp, k_max, substats3)
        return (model._replace(active=plan.merge.new_active,
                               stuck=plan.stuck, stats=stats3,
                               substats=substats3), point2)

    k_c_sm = None if k_compact is None else min(k_max, 2 * k_compact)
    if k_c_sm is None or k_c_sm >= k_max:
        return run(None)
    comp = gibbs.compaction_plan(plan.merge.new_active, k_c_sm)
    n_new = jnp.sum(plan.merge.new_active.astype(jnp.int32))
    return jax.lax.cond(n_new <= k_c_sm, lambda: run(comp),
                        lambda: run(None))


def dpmm_step(model: ModelState, point: PointState, x, *, prior, family,
              cfg, axes, k_max, feat_axis=None, k_compact=None
              ) -> Tuple[ModelState, PointState]:
    """One full iteration; designed to run under shard_map. ``k_compact``
    (static) turns on active-set compaction for the sweep and the
    split/merge stat fold — O(N * K_active) per-point work instead of
    O(N * k_max), bitwise the dense iteration (core/gibbs.py)."""
    model, point = gibbs.sweep(model, point, x, prior, family, cfg.alpha,
                               axes, use_pallas=cfg.use_pallas,
                               feat_axis=feat_axis, k_compact=k_compact,
                               k_block=cfg.k_block)
    model, point = jax.lax.cond(
        model.it >= cfg.burnout,
        lambda mp: _split_merge(*mp, x, prior=prior, family=family,
                                cfg=cfg, axes=axes, k_max=k_max,
                                feat_axis=feat_axis, k_compact=k_compact),
        lambda mp: mp,
        (model, point))
    return model._replace(it=model.it + 1), point


def _peak_fields(rss_baseline: Optional[int]) -> Dict[str, Any]:
    """The measured-peak entries of ``FitResult.device_bytes``. When the
    measurement is the RSS fallback, also record the high-water *delta*
    over this fit (``peak_rss_delta_bytes``) — the leg-accurate number
    when several fits share one process (a later fit that never exceeds
    an earlier one's peak reports delta 0 and source
    ``process_peak_rss_stale`` instead of silently re-reporting the old
    peak as its own)."""
    peak, src = _measured_peak(rss_baseline)
    fields: Dict[str, Any] = {"peak_bytes_in_use": peak,
                              "peak_bytes_source": src}
    if src.startswith("process_peak_rss") and rss_baseline is not None:
        fields["peak_rss_delta_bytes"] = max(int(peak) - rss_baseline, 0)
    return fields


def _copy_state(state: ModelState) -> ModelState:
    """Fresh buffers for a caller-provided init_state: the resident
    chunk donates its state arguments, and without the copy the FIRST
    chunk would delete the caller's (possibly checkpoint-loaded) arrays
    out from under them — resuming twice from one state would crash."""
    return jax.tree.map(jnp.copy, state)


def _tree_bytes(tree: Any) -> int:
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(tree)
               if hasattr(l, "shape"))


@dataclasses.dataclass
class FitResult:
    """Result of ``DPMM.fit``. With ``n_chains=1`` (default) every field
    is per-run; with C > 1 the state/labels/history carry a leading chain
    axis ((C, ...) state leaves, (C, N) labels, (C, iters) traces), ``k``
    is the best-scoring chain's cluster count, and the cross-chain views
    are ``chain(c)`` / ``select_best()`` / ``rhat(key)``."""
    state: ModelState            # final replicated model-side state
    labels: np.ndarray           # (N,) cluster assignments (unpadded)
    k: int
    history: Dict[str, np.ndarray]
    iter_times_s: List[float]
    # accounting of what the fit kept device-resident (see README
    # 'Memory model'): est_peak_bytes is the analytic per-run peak over
    # persistent device buffers; peak_bytes_in_use is the measured peak —
    # device.memory_stats() where the backend reports it, else the
    # process's peak RSS — with its origin in peak_bytes_source.
    device_bytes: Optional[Dict[str, Any]] = None
    n_chains: int = 1
    # final chain_score per chain: scalar (C=1) or (C,) — the
    # select_best ranking; the full trace is history["score"]
    score: Any = None
    # resilience event log: tile-read retries ('tile_read_fault'),
    # recovered retries ('io_retry'), divergence rollbacks
    # ('divergence_rollback'), and distributed worker failovers
    # ('worker_failover') the fit survived. Empty for a clean fit. NOT
    # part of ``history`` on purpose — the golden-chain fingerprints
    # hash history, and recoveries are operational metadata, not chain
    # state.
    recoveries: List[dict] = dataclasses.field(default_factory=list)
    # distributed-fit metadata (cfg.workers set): worker count, the
    # per-worker shard row ranges, and respawn/reassignment tallies.
    # None for single-process fits.
    dist: Optional[Dict[str, Any]] = None
    # sweep bodies traced for this fit, by path: "sweep_fast" (the Pallas
    # megakernel) / "sweep_ref" (the blocked jnp scan) — one count per
    # compiled program branch, not per iteration (core/family.py). A
    # use_pallas fit whose count shows "sweep_ref" ran the reference.
    # Worker processes of a cfg.workers fit trace their own and are not
    # counted here.
    sweep_paths: Dict[str, int] = dataclasses.field(default_factory=dict)

    def chain(self, c: int) -> "FitResult":
        """Single-chain view of chain ``c`` (bitwise — pure slicing)."""
        if self.n_chains == 1:
            if c != 0:
                raise IndexError(f"single-chain result has no chain {c}")
            return self
        state_c = jax.tree.map(lambda v: v[c], self.state)
        return FitResult(
            state=state_c, labels=self.labels[c],
            k=int(np.asarray(state_c.active).sum()),
            history={k: np.asarray(v[c]) for k, v in self.history.items()},
            iter_times_s=self.iter_times_s,
            device_bytes=self.device_bytes, n_chains=1,
            score=float(np.asarray(self.score)[c]),
            recoveries=self.recoveries, sweep_paths=self.sweep_paths)

    def select_best(self) -> "FitResult":
        """The chain with the highest final posterior ``score``
        (core/sampler.chain_score) — what a practitioner consumes."""
        if self.n_chains == 1:
            return self
        return self.chain(int(np.argmax(np.asarray(self.score))))

    def rhat(self, key: str = "score") -> float:
        """Split-R-hat (Gelman et al.) over the per-chain history traces
        of ``key`` ('score' or 'k' are the useful ones). Values near 1
        mean the chains agree; > ~1.1 means they found different modes —
        run longer or take ``select_best()`` with a grain of salt."""
        if self.n_chains < 2:
            raise ValueError("rhat needs n_chains >= 2")
        trace = np.asarray(self.history[key], np.float64)   # (C, T)
        half = trace.shape[1] // 2
        if half < 2:
            raise ValueError("rhat needs >= 4 recorded iterations")
        x = np.concatenate([trace[:, :half], trace[:, half:2 * half]])
        n = x.shape[1]
        w = x.var(axis=1, ddof=1).mean()
        b = n * x.mean(axis=1).var(ddof=1)
        if w <= 0.0:
            return 1.0 if b <= 0.0 else float("inf")
        return float(np.sqrt(((n - 1) / n * w + b / n) / w))

    def rhats(self) -> Dict[str, float]:
        return {key: self.rhat(key) for key in ("k", "score")}

    def nmi(self, true_labels: np.ndarray, n_true: Optional[int] = None):
        if self.n_chains > 1:
            return self.select_best().nmi(true_labels, n_true)
        n_true = n_true or int(true_labels.max()) + 1
        k_max = int(self.state.active.shape[0])
        return float(nmi(jnp.asarray(true_labels),
                         jnp.asarray(self.labels), n_true, k_max))

    def ari(self, true_labels: np.ndarray, n_true: Optional[int] = None):
        if self.n_chains > 1:
            return self.select_best().ari(true_labels, n_true)
        n_true = n_true or int(true_labels.max()) + 1
        k_max = int(self.state.active.shape[0])
        return float(ari(jnp.asarray(true_labels),
                         jnp.asarray(self.labels), n_true, k_max))


def _rss_peak_bytes() -> Optional[int]:
    """Process-lifetime peak RSS in bytes (``ru_maxrss``), or None where
    unmeasurable (non-POSIX)."""
    try:
        import resource
        return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024
    except Exception:
        return None


def _measured_peak(rss_baseline: Optional[int] = None
                   ) -> Tuple[Optional[int], str]:
    """(peak bytes, source): the backend's ``peak_bytes_in_use`` where
    ``device.memory_stats()`` reports it (TPU/GPU), else the process's
    peak RSS (``ru_maxrss``; on CPU the 'device' IS host memory) — so
    memory claims are measurable everywhere. RSS is a process-lifetime
    high-water mark that includes host-side buffers and cannot be reset
    between fits, so a leg that runs after a larger allocation in the same
    process would silently report that *earlier* peak as its own. Callers
    that measure a leg pass ``rss_baseline`` (``_rss_peak_bytes()`` taken
    at leg start); when the high-water mark did not move during the leg
    the source is reported as ``process_peak_rss_stale`` — the number is a
    ceiling inherited from earlier work, not this leg's footprint.
    """
    device = jax.local_devices()[0]
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if peak is not None:
        return int(peak), "device.memory_stats"
    if device.platform == "tpu":
        # host RSS says nothing about HBM; never report it as device memory
        raise RuntimeError(
            "device.memory_stats() gave no peak_bytes_in_use on a TPU "
            f"({device.device_kind}); refusing to report host RSS as the "
            "fit's device memory")
    rss = _rss_peak_bytes()
    if rss is None:                           # non-POSIX: no measurement
        return None, "unavailable"
    if rss_baseline is not None and rss <= rss_baseline:
        return rss, "process_peak_rss_stale"
    return rss, "process_peak_rss"


class DPMM:
    """Distributed DPMM with sub-cluster splits (paper [1] + this paper)."""

    def __init__(self, cfg: DPMMConfig, mesh: Optional[Mesh] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.family: ComponentFamily = get_family(cfg.component)

    def fit(self, x, iters: Optional[int] = None, verbose: bool = False,
            *, n_chains: int = 1, key: Optional[jax.Array] = None,
            init_state: Optional[ModelState] = None,
            resume: bool = False, dist_hooks: Any = None) -> FitResult:
        """Fit to ``x``: an (N, d) array (resident fast path) or any
        ``DataSource`` (e.g. ``HostTiledSource`` over an np.memmap for
        out-of-core data). ``cfg.tile_size`` forces the tiled plane even
        for resident arrays — chains are bitwise identical either way.

        ``n_chains=C`` runs C parallel MCMC chains inside the same jitted
        chunks, sharing one device copy of x; chain c is bitwise the
        single-chain fit with ``key=fold_in(key, c)`` (see module
        docstring). ``key`` overrides ``jax.random.key(cfg.seed)``.
        ``init_state`` resumes from a checkpointed ``ModelState``
        (core/checkpoint.py) and runs ``iters`` MORE iterations; because
        every per-point quantity is recomputed from the model each sweep
        and all randomness derives from ``(state.key, state.it)``, the
        resumed chain is bitwise the uninterrupted one.

        ``resume=True`` picks up a killed fit from the auto-checkpoint
        rotation at ``cfg.checkpoint_path`` (requires it): the newest
        member that *verifies* (version, CRCs, leaf shapes) is loaded —
        corrupt members fall back through the rotation — and ``iters``
        is treated as the TOTAL iteration target, so the fit runs only
        the remaining ``iters - it_checkpoint`` iterations. With no
        checkpoint on disk yet it is a fresh fit, which is what makes
        blind ``fit(resume=True)`` re-runs idempotent-ish: run, crash,
        rerun until done. Mutually exclusive with ``init_state``.

        ``cfg.workers=N`` routes the fit through the elastic
        multi-process driver (repro.dist): N worker processes each
        stream a row-range shard while this process keeps the model.
        The chain is bitwise identical to the single-process tiled fit
        at any worker count, including across worker failover.
        ``dist_hooks`` (a ``repro.dist.DistHooks``) injects worker-side
        faults / iteration callbacks for chaos tests. Resume and
        init_state compose unchanged — they are resolved here, before
        the driver dispatch.
        """
        source = as_source(x)
        iters = iters if iters is not None else self.cfg.iters
        if n_chains < 1:
            raise ValueError(f"n_chains must be >= 1, got {n_chains}")
        if key is None:
            key = jax.random.key(self.cfg.seed)
        if resume:
            if init_state is not None:
                raise ValueError(
                    "pass either resume=True (load from "
                    "cfg.checkpoint_path) or init_state, not both")
            if not self.cfg.checkpoint_path:
                raise ValueError(
                    "fit(resume=True) needs cfg.checkpoint_path — the "
                    "rotation prefix auto-checkpointing saved to")
            try:
                loaded, fam, _path, it_ckpt = _checkpoint.latest_valid(
                    self.cfg.checkpoint_path)
            except _checkpoint.CheckpointNotFound:
                loaded = None           # nothing saved yet: fresh fit
            if loaded is not None:
                if fam.name != self.family.name:
                    raise ValueError(
                        f"checkpoint at {self.cfg.checkpoint_path} holds "
                        f"a '{fam.name}' model but cfg.component is "
                        f"'{self.family.name}'")
                init_state = loaded
                iters = max(0, iters - it_ckpt)
        if init_state is not None:
            # k_max='auto': the checkpoint's slab size IS the resumed
            # starting capacity, so only the chain axis is validated
            k_chk = (init_state.active.shape[-1]
                     if self.cfg.k_max == "auto" else self.cfg.k_max)
            want = ((n_chains, k_chk) if n_chains > 1 else (k_chk,))
            got = tuple(init_state.active.shape)
            if got != want:
                raise ValueError(
                    f"init_state.active has shape {got}, expected {want} "
                    f"for n_chains={n_chains}, k_max={self.cfg.k_max} — "
                    "checkpoint/config/chain-count mismatch")
        if self.cfg.workers:
            driver = functools.partial(self._fit_distributed,
                                       dist_hooks=dist_hooks)
        elif self.cfg.tile_size is None and source.resident() is not None:
            driver = self._fit_resident
        else:
            driver = self._fit_tiled
        with record_sweep_paths() as paths:
            result = driver(source, iters, verbose, n_chains=n_chains,
                            key=key, init_state=init_state)
        result.sweep_paths = dict(paths)
        return result

    def _fit_distributed(self, source: DataSource, iters: int,
                         verbose: bool, n_chains: int = 1,
                         key: Optional[jax.Array] = None,
                         init_state: Optional[ModelState] = None,
                         dist_hooks: Any = None) -> FitResult:
        """Third fit driver: coordinator/worker shards (repro.dist).
        Lazy import — single-process fits never touch the subprocess /
        socket machinery."""
        if n_chains != 1:
            raise ValueError(
                "cfg.workers does not compose with n_chains > 1 yet: "
                "chain batching rides the tile bodies, which the "
                "distributed driver runs per worker shard. Run one "
                "distributed fit per chain key instead.")
        from repro.dist.coordinator import fit_distributed
        return fit_distributed(self, source, iters, verbose, key=key,
                               init_state=init_state, hooks=dist_hooks)

    def _setup(self, source: DataSource):
        cfg = self.cfg
        family = self.family
        mesh = self.mesh if self.mesh is not None else make_data_mesh()
        axes = data_axes_of(mesh)
        # the prior's data-dependent part is the column mean, computed
        # once by the source's canonical streaming pass — identical for
        # resident and out-of-core modes (data/source.py)
        prior = family.build_prior(cfg, source.column_mean()[None, :])
        want_feat_shard = cfg.shard_features and family.feature_shardable
        feat_axis = ("model" if (want_feat_shard
                                 and "model" in mesh.axis_names)
                     else None)
        kwargs = dict(prior=prior, family=family, cfg=cfg, axes=axes,
                      k_max=cfg.k_max, feat_axis=feat_axis)
        return mesh, axes, feat_axis, kwargs

    # ------------------------------------------------------------------
    # Resident plane: device-resident points, chunked on-device scan
    # ------------------------------------------------------------------
    def _fit_resident(self, source: DataSource, iters: int, verbose: bool,
                      n_chains: int = 1, key: Optional[jax.Array] = None,
                      init_state: Optional[ModelState] = None) -> FitResult:
        cfg = self.cfg
        multi = n_chains > 1
        mesh, axes, feat_axis, kwargs = self._setup(source)
        prior, family = kwargs["prior"], kwargs["family"]
        # slab capacity: fixed k_max, or the 'auto' growth schedule — start
        # small and double at chunk boundaries when the live count crosses
        # half the slab, so k_max is a discovered high-water mark
        auto = cfg.k_max == "auto"
        if init_state is not None:
            k_slab = int(init_state.active.shape[-1])
        elif auto:
            k_slab = min(cfg.k_max_cap, max(8, 2 * cfg.init_clusters))
        else:
            k_slab = cfg.k_max
        k_cap = cfg.k_max_cap if auto else k_slab
        kwargs["k_max"] = k_slab
        x = source.resident()
        n = x.shape[0]
        # non-separable families keep features replicated even when
        # shard_features is requested (family.feature_shardable contract)
        xs, valid = shard_points(mesh, x, feat_axis is not None)
        shard_spec = P(axes)
        x_in_spec = P(axes, feat_axis)
        rep = P()
        model_specs, point_specs = state_partition_specs(self.family,
                                                         shard_spec)
        if multi:
            # chain axis leads every per-point leaf; replicated O(K)
            # leaves keep P() (rank-agnostic)
            point_specs = jax.tree.map(lambda _: P(None, axes), point_specs)
        state_specs = (model_specs, point_specs)

        def init_body(keys, x, valid):
            if multi:
                return jax.lax.map(
                    lambda k: _init_local(k, x, valid, **kwargs), keys)
            return _init_local(keys, x, valid, **kwargs)

        # check_vma=False on every shard_map here: the out_specs mix
        # replicated per-cluster state with sharded labels, which the
        # checker cannot verify across psum/all_gather
        init = jax.jit(jax.shard_map(
            init_body, mesh=mesh,
            in_specs=(rep, x_in_spec, shard_spec), out_specs=state_specs,
            check_vma=False))

        def make_chunk(length: int, k_c: Optional[int]):
            """`length` iterations in one jitted call, history on device.

            The scan carries the (model, point) state pair; per-step
            host-visible output is only the O(1) ``_summaries()`` scalars
            (per chain when C > 1 — the C chains run under ``lax.map``
            INSIDE the scan body, sharing the closed-over x). State
            buffers are donated, so chunk i+1 reuses chunk i's memory.
            ``k_c`` (static) is the compact-slab size for every iteration
            of the chunk; the in-step ``lax.cond`` (core/gibbs.py) falls
            back to the dense slab if mid-chunk splits outgrow it.
            """
            def one(m, p, x):
                m, p = dpmm_step(m, p, x, k_compact=k_c, **kwargs)
                return (m, p), _summaries(m, prior, family, cfg.alpha)

            def run(model, point, x):
                def body(mp, _):
                    if multi:
                        return jax.lax.map(lambda s: one(*s, x), mp)
                    return one(*mp, x)
                return jax.lax.scan(body, (model, point), None,
                                    length=length)
            hist_specs = {k: rep for k in _HIST_KEYS}
            return jax.jit(
                jax.shard_map(run, mesh=mesh,
                              in_specs=(*state_specs, x_in_spec),
                              out_specs=(state_specs, hist_specs),
                              check_vma=False),
                donate_argnums=(0, 1))

        rss0 = _rss_peak_bytes()
        # fresh PointState from the validity mask alone: zeros for labels
        # are fine — every sweep recomputes them from the model. Used on
        # resume (no point in the checkpoint) AND on divergence rollback
        # (the donated chunk consumed the diverged point's buffers).
        mk_point = jax.jit(jax.shard_map(
            lambda v: PointState(
                labels=jnp.zeros(((n_chains,) if multi else ())
                                 + v.shape, jnp.int32),
                sublabels=jnp.zeros(((n_chains,) if multi else ())
                                    + v.shape, jnp.int32),
                valid=(jnp.broadcast_to(v, (n_chains,) + v.shape)
                       if multi else v)),
            mesh=mesh, in_specs=(shard_spec,), out_specs=point_specs,
            check_vma=False))
        if init_state is not None:
            model = jax.device_put(_copy_state(init_state),
                                   NamedSharding(mesh, P()))
            point = mk_point(valid)
            it_base = int(np.asarray(
                jax.device_get(init_state.it)).reshape(-1)[0])
        else:
            keys = _chain_keys(key, n_chains) if multi else key
            model, point = init(keys, xs, valid)
            it_base = 0

        chunk = max(1, cfg.log_every)
        chunk_fns: Dict[Any, Any] = {}
        hist_chunks: List[Dict[str, np.ndarray]] = []
        times: List[float] = []
        done = 0
        # guardrails: the health verdict is a SEPARATE tiny jitted program
        # over the O(K) model state — never fused into the chunk, so the
        # chunk's compiled artifact (and the chain it computes) is bitwise
        # identical with guardrails on or off; the verdict rides the
        # existing per-chunk device_get (zero extra host syncs)
        health_fn = jax.jit(model_health) if cfg.guardrails else None
        rec = _Recovery(cfg, self.family.name, it_base)
        # rollback anchor: device-side copy of the last healthy boundary
        # (model, done, k_slab) — kept on device because typed PRNG keys
        # round-trip poorly and the copy is O(K), not O(N)
        snap = ((jax.tree.map(jnp.copy, model), 0, k_slab)
                if cfg.guardrails else None)
        # last known live cluster count (max over chains) — sizes the next
        # chunk's compact slab and drives the 'auto' growth schedule; the
        # host learns it for free from the chunk history it pulls anyway
        if init_state is not None:
            k0 = int(np.max(np.asarray(
                jax.device_get(init_state.active)).sum(axis=-1)))
        else:
            k0 = cfg.init_clusters
        while done < iters:
            length = min(chunk, iters - done)
            if auto and 2 * k0 > k_slab and k_slab < k_cap:
                while 2 * k0 > k_slab and k_slab < k_cap:
                    k_slab = min(k_cap, 2 * k_slab)
                # chunk-boundary growth: pad the slab, re-replicate, and
                # let the next AOT compile re-donate the grown buffers
                model = jax.device_put(grow_model(model, k_slab),
                                       NamedSharding(mesh, P()))
                kwargs["k_max"] = k_slab
            k_c = (_k_compact(k0, 2, k_slab, cfg.k_block)
                   if cfg.compact else None)
            fkey = (length, k_slab, k_c)
            if fkey not in chunk_fns:
                # AOT-compile outside the timed region so jit compile time
                # (seconds) never contaminates iter_times_s / benchmarks.
                # O(log K) compiles per fit: `log_every` + one trailing
                # remainder length, times the pow2 compact/slab sizes.
                chunk_fns[fkey] = make_chunk(length, k_c).lower(
                    model, point, xs).compile()
            t0 = time.perf_counter()
            (model, point), hist = chunk_fns[fkey](model, point, xs)
            if health_fn is not None:
                # one sync pulls the chunk history AND the health verdict
                hist, healthy = jax.device_get((hist, health_fn(model)))
                healthy = bool(healthy)
            else:
                hist = jax.device_get(hist)   # the one host sync per chunk
                healthy = True
            dt = time.perf_counter() - t0
            if not healthy:
                snap_model, snap_done, snap_slab = snap
                rec.rollback(it_base + done + length, it_base + snap_done,
                             "non-finite/degenerate model state after "
                             "resident chunk")
                # restore the anchor (fresh copy: the anchor itself must
                # survive a possible second rollback), advance the key so
                # the replay takes a different trajectory, rebuild point
                model = _recovery_rekey(
                    jax.tree.map(jnp.copy, snap_model), rec.n_rollbacks)
                done = snap_done
                if k_slab != snap_slab:       # undo post-anchor slab growth
                    k_slab = snap_slab
                    kwargs["k_max"] = k_slab
                point = mk_point(valid)
                k0 = int(np.max(np.asarray(
                    jax.device_get(snap_model.active)).sum(axis=-1)))
                continue                      # failed chunk leaves no
                                              # hist/times rows behind
            times.extend([dt / length] * length)
            hist_chunks.append(hist)
            k0 = int(np.max(np.asarray(hist["k"][-1])))
            done += length
            if cfg.guardrails:
                snap = (jax.tree.map(jnp.copy, model), done, k_slab)
            rec.maybe_checkpoint(model, it_base + done)
            if verbose:
                ks = np.asarray(hist["k"][-1]).reshape(-1).tolist()
                print(f"iter {it_base + done:4d}  "
                      f"K={ks if len(ks) > 1 else ks[0]}  "
                      f"{dt / length * 1e3:.1f} ms/iter")
        rec.maybe_checkpoint(model, it_base + done, force=True)
        history = {
            k: (np.concatenate([h[k] for h in hist_chunks])
                if hist_chunks else np.zeros((0,) + ((n_chains,) if multi
                                                     else ())))
            for k in _HIST_KEYS}
        if multi:
            # (iters, C) per-step stacks -> (C, iters) per-chain traces
            history = {k: np.ascontiguousarray(v.T)
                       for k, v in history.items()}
        labels = np.asarray(jax.device_get(point.labels))[..., :n]
        device_bytes = {
            "mode": "resident",
            "mesh_devices": int(mesh.devices.size),
            "est_peak_bytes": (_tree_bytes(xs) + _tree_bytes(valid)
                               + 2 * _tree_bytes(point)
                               + 2 * _tree_bytes(model)),
            **_peak_fields(rss0),
        }
        return self._result(model, labels, history, times, device_bytes,
                            n_chains, rec.events)

    def _result(self, model: ModelState, labels, history, times,
                device_bytes, n_chains: int,
                recoveries: Optional[List[dict]] = None) -> FitResult:
        """Assemble a FitResult; for C > 1, ``k`` is the best chain's."""
        recoveries = recoveries or []
        if n_chains == 1:
            score = (float(history["score"][-1])
                     if history["score"].size else None)
            return FitResult(state=model, labels=labels,
                             k=int(model.k_hat), history=history,
                             iter_times_s=times, device_bytes=device_bytes,
                             score=score, recoveries=recoveries)
        score = (np.asarray(history["score"][:, -1])
                 if history["score"].size
                 else np.zeros((n_chains,), np.float32))
        best = int(np.argmax(score))
        return FitResult(state=model, labels=labels,
                         k=int(np.asarray(model.active[best]).sum()),
                         history=history, iter_times_s=times,
                         device_bytes=device_bytes, n_chains=n_chains,
                         score=score, recoveries=recoveries)

    # ------------------------------------------------------------------
    # Tiled plane: out-of-core points streamed under a resident ModelState
    # ------------------------------------------------------------------
    def _fit_tiled(self, source: DataSource, iters: int, verbose: bool,
                   n_chains: int = 1, key: Optional[jax.Array] = None,
                   init_state: Optional[ModelState] = None) -> FitResult:
        cfg = self.cfg
        family = self.family
        multi = n_chains > 1
        mesh, axes, feat_axis, kwargs = self._setup(source)
        prior = kwargs["prior"]
        if cfg.k_max == "auto":
            raise ValueError(
                "k_max='auto' requires the resident data plane: the tiled "
                "driver has no scan-chunk boundary to grow the slab at. "
                "Pass an integer k_max for tiled/out-of-core fits.")
        k_max = cfg.k_max
        n, d = source.n, source.d
        shards = n_data_shards(mesh)
        # chain batching: replicated O(K) model math and per-tile bodies
        # lax.map over the leading chain axis (bitwise per chain; see
        # module docstring) — identity when C == 1
        cmap = _chain_map if multi else (lambda f: f)
        cshape = (n_chains,) if multi else ()
        n_local, tiles = tile_plan(n, shards, cfg.tile_size)
        if shards * n_local >= 2 ** 32:
            # >=, not >: at exactly 2**32 rows jnp.uint32(n) wraps to 0 in
            # the tile validity mask, which would silently zero all stats
            raise ValueError(
                f"N={n} ({shards * n_local} rows padded) exceeds the "
                "uint32 global point-index space: counter-based draws "
                "would wrap and silently corrupt the chain. Shard the fit "
                "across processes, or widen kernels/prng counters to "
                "uint64 first.")
        use_pallas = cfg.use_pallas

        model_specs, _ = state_partition_specs(family, P(axes))
        x_spec = P(axes, feat_axis)
        rep = P()

        # ---- the per-shard suff-stat accumulator: leading shard axis ----
        # built at full feature width; feature-sliced fields are sharded
        # over the model axis so each device's local slice matches the
        # local width its stats_from_labels partials produce
        acc_shape = jax.eval_shape(
            lambda: gibbs.empty_substats(family, k_max, d))
        feat_fields = set(family.feature_stat_fields if feat_axis else ())

        def leaf_spec(field, leaf):
            dims = ([None] if multi else []) + [axes] + [None] * leaf.ndim
            if field in feat_fields:
                dims[-1] = feat_axis
            return P(*dims)

        # specs depend only on field name and rank, so ONE spec tree (and
        # sharding tree) serves the dense k_max accumulator and every
        # compact k_c-row accumulator alike
        acc_specs = type(acc_shape)(**{
            f: leaf_spec(f, getattr(acc_shape, f))
            for f in acc_shape._fields})
        acc_shardings = type(acc_shape)(**{
            f: NamedSharding(mesh, getattr(acc_specs, f))
            for f in acc_shape._fields})

        @functools.lru_cache(maxsize=None)
        def zeros_acc_k(k: int):
            shape_k = jax.eval_shape(
                lambda: gibbs.empty_substats(family, k, d))
            return jax.jit(
                lambda: type(shape_k)(**{
                    f: jnp.zeros(cshape + (shards,)
                                 + getattr(shape_k, f).shape, jnp.float32)
                    for f in shape_k._fields}),
                out_shardings=acc_shardings)

        zeros_acc = zeros_acc_k(k_max)

        local = lambda acc: jax.tree.map(lambda v: v[0], acc)
        delocal = lambda acc: jax.tree.map(lambda v: v[None], acc)

        # ---- host-side point state and tile transfer ------------------
        # chain axis (when C > 1) leads the host label arrays and every
        # label tile; x tiles carry NO chain axis — one upload per tile,
        # consumed by all chains
        labels_h = np.zeros(cshape + (shards * n_local,), np.int32)
        sublabels_h = np.zeros(cshape + (shards * n_local,), np.int32)
        x_sharding = NamedSharding(mesh, x_spec)
        lab_spec = P(None, axes) if multi else P(axes)
        i32_sharding = NamedSharding(mesh, lab_spec)

        # every streamed read goes through the bounded retry path
        # (core/resilience.py): transient IOError/short-read/NaN-tile
        # faults re-read (the retried data is identical, so the chain is
        # bitwise untouched); persistent faults raise TileReadError with
        # tile provenance. Retry events land in FitResult.recoveries.
        retry = RetryPolicy(max_retries=cfg.io_retries,
                            backoff_s=cfg.io_backoff_s,
                            guard_nonfinite=cfg.guard_tiles)
        rec = _Recovery(cfg, family.name, 0)    # it_base fixed after init

        def put_x_tile(off: int, length: int):
            rows = np.concatenate(
                [read_block_checked(source, s * n_local + off,
                                    s * n_local + off + length, retry,
                                    on_event=rec.events.append)
                 for s in range(shards)], axis=0)
            return jax.device_put(rows, x_sharding)

        def put_label_tile(host, off: int, length: int):
            rows = np.concatenate(
                [host[..., s * n_local + off:s * n_local + off + length]
                 for s in range(shards)], axis=-1)
            return jax.device_put(rows, i32_sharding)

        def write_back(host, off: int, length: int, tile_out):
            rows = np.asarray(jax.device_get(tile_out))
            for s in range(shards):
                host[..., s * n_local + off:s * n_local + off + length] = (
                    rows[..., s * length:(s + 1) * length])

        def stream(pass_fn, carry, point_pass: bool):
            """Run ``pass_fn`` over all tiles with double-buffered
            device_put: tile i+1's transfer is issued right after tile i's
            compute is dispatched (dispatch is async), so it overlaps."""
            def load(i):
                off, length = tiles[i]
                xt = put_x_tile(off, length)
                pt = (put_label_tile(labels_h, off, length),
                      put_label_tile(sublabels_h, off, length)
                      ) if point_pass else None
                return xt, pt
            buf = load(0)
            for i, (off, length) in enumerate(tiles):
                xt, pt = buf
                out, carry = pass_fn(i, off, length, xt, pt, carry)
                if i + 1 < len(tiles):
                    buf = load(i + 1)       # overlaps the dispatched compute
                if out is not None:
                    lab_t, sub_t = out
                    write_back(labels_h, off, length, lab_t)
                    write_back(sublabels_h, off, length, sub_t)
            return carry

        # ---- jitted bodies (compiled once per distinct tile length) ----
        def tile_point(pt, off, length, x_t):
            lab, sub = pt
            gidx = gibbs.global_indices(n_local, axes, offset=off,
                                        length=length)
            valid = (gidx < jnp.uint32(n)).astype(x_t.dtype)
            return PointState(labels=lab, sublabels=sub, valid=valid), gidx

        def _sweep_tile(model, x_t, lab, sub, off, acc, comp=None):
            point, gidx = tile_point((lab, sub), off, x_t.shape[0], x_t)
            point, a = gibbs.sweep_tile(model, x_t, point, gidx, local(acc),
                                        family, use_pallas=use_pallas,
                                        feat_axis=feat_axis, plan=comp,
                                        k_block=cfg.k_block)
            return (point.labels, point.sublabels), delocal(a)

        def _sm_tile(plan, x_t, lab, sub, off, acc, comp=None):
            point, _ = tile_point((lab, sub), off, x_t.shape[0], x_t)
            point, a = splitmerge.split_merge_tile(
                plan, x_t, point, local(acc), family,
                use_pallas=use_pallas, feat_axis=feat_axis,
                compaction=comp)
            return (point.labels, point.sublabels), delocal(a)

        def _init1_tile(x_t, off, acc):
            gidx = gibbs.global_indices(n_local, axes, offset=off,
                                        length=x_t.shape[0])
            labels = _init_labels(gidx, cfg.init_clusters)
            valid = (gidx < jnp.uint32(n)).astype(x_t.dtype)
            a = gibbs.accumulate_substats(
                family, x_t, valid, labels, jnp.zeros_like(labels), k_max,
                local(acc), use_pallas)
            return (labels, jnp.zeros_like(labels)), delocal(a)

        def _init2_tile(means0, v0, x_t, lab, sub, off, acc):
            point, gidx = tile_point((lab, sub), off, x_t.shape[0], x_t)
            sublabels = splitmerge.hyperplane_bits(x_t, point.labels,
                                                   means0, v0, feat_axis)
            a = gibbs.accumulate_substats(
                family, x_t, point.valid, point.labels, sublabels, k_max,
                local(acc), use_pallas)
            return (point.labels, sublabels), delocal(a)

        def _finalize(acc):
            return gibbs.finalize_substats(family, local(acc), axes,
                                           feat_axis)

        # chain-mapped wrappers: per-chain tile/model bodies are the exact
        # single-chain bodies; x_t and the tile offset are closed over
        # (shared across chains — one upload, C consumers)
        def _sweep_tile_c(model, x_t, lab, sub, off, acc):
            return cmap(lambda m, l, s, a: _sweep_tile(m, x_t, l, s, off,
                                                       a))(model, lab, sub,
                                                           acc)

        def _sm_tile_c(plan, x_t, lab, sub, off, acc):
            return cmap(lambda pl, l, s, a: _sm_tile(pl, x_t, l, s, off,
                                                     a))(plan, lab, sub,
                                                         acc)

        # compacted variants: the per-chain CompactionPlan rides along as
        # a replicated operand; acc is the compact k_c-row accumulator
        def _sweep_tile_comp(model, x_t, lab, sub, off, comp, acc):
            return cmap(lambda m, l, s, c, a: _sweep_tile(
                m, x_t, l, s, off, a, c))(model, lab, sub, comp, acc)

        def _sm_tile_comp(plan, x_t, lab, sub, off, comp, acc):
            return cmap(lambda pl, l, s, c, a: _sm_tile(
                pl, x_t, l, s, off, a, c))(plan, lab, sub, comp, acc)

        def _init1_c(x_t, off, acc):
            return cmap(lambda a: _init1_tile(x_t, off, a))(acc)

        def _init2_c(means0, v0, x_t, lab, sub, off, acc):
            return cmap(lambda mn, v, l, s, a: _init2_tile(
                mn, v, x_t, l, s, off, a))(means0, v0, lab, sub, acc)

        lab_specs = (lab_spec, lab_spec)
        smap = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
        sweep_tile_fn = jax.jit(smap(
            _sweep_tile_c, in_specs=(model_specs, x_spec, *lab_specs, rep,
                                     acc_specs),
            out_specs=(lab_specs, acc_specs)))
        comp_specs = gibbs.CompactionPlan(rep, rep)
        sweep_tile_comp_fn = jax.jit(smap(
            _sweep_tile_comp,
            in_specs=(model_specs, x_spec, *lab_specs, rep, comp_specs,
                      acc_specs),
            out_specs=(lab_specs, acc_specs)))
        sm_tile_fn = None     # built lazily: needs the plan's pytree specs
        sm_tile_comp_fn = None
        finalize_fn = jax.jit(smap(
            cmap(_finalize), in_specs=(acc_specs,), out_specs=(rep, rep)))
        init1_fn = jax.jit(smap(
            _init1_c, in_specs=(x_spec, rep, acc_specs),
            out_specs=(lab_specs, acc_specs)))

        sweep_model_fn = jax.jit(cmap(functools.partial(
            gibbs.sweep_model, prior=prior, family=family,
            alpha=cfg.alpha)))
        plan_fn = jax.jit(cmap(lambda m: splitmerge.plan_split_merge(
            _move_key(m), m, prior, family, cfg.alpha,
            cfg.subreset_every)))
        advance_fn = jax.jit(cmap(
            lambda m: (m._replace(it=m.it + 1),
                       _summaries(m, prior, family, cfg.alpha))))

        rss0 = _rss_peak_bytes()
        keys = _chain_keys(key, n_chains) if multi else key
        if init_state is not None:
            # resume: the model is the whole chain state (labels are
            # recomputed from it every sweep), so the two init passes are
            # skipped and host labels start zeroed
            model = jax.device_put(_copy_state(init_state),
                                   NamedSharding(mesh, P()))
        else:
            # ---- initialization: two streamed passes ------------------
            acc = zeros_acc()
            acc = stream(
                lambda i, off, length, xt, pt, a:
                    init1_fn(xt, np.uint32(off), a),
                acc, point_pass=False)
            stats0, _ = finalize_fn(acc)
            means0 = jax.jit(cmap(family.cluster_means))(stats0)
            v0 = jax.jit(cmap(lambda k: splitmerge.hyperplane_vecs(
                jax.random.fold_in(k, 1), k_max, d, jnp.float32)))(keys)
            _init2 = jax.jit(smap(
                _init2_c, in_specs=(rep, rep, x_spec, *lab_specs, rep,
                                    acc_specs),
                out_specs=(lab_specs, acc_specs)))
            acc = zeros_acc()
            acc = stream(
                lambda i, off, length, xt, pt, a:
                    _init2(means0, v0, xt, *pt, np.uint32(off), a),
                acc, point_pass=True)
            stats, substats = finalize_fn(acc)
            model = jax.jit(cmap(lambda k, s, ss: _init_model(
                k, s, ss, prior=prior, family=family, cfg=cfg,
                k_max=k_max)))(keys, stats, substats)

        # ---- iteration loop: ModelState is the only persistent state ---
        set_stats_fn = jax.jit(cmap(
            lambda m, s, ss: m._replace(stats=s, substats=ss)))
        apply_plan_fn = jax.jit(cmap(
            lambda m, plan, s, ss: m._replace(
                active=plan.merge.new_active, stuck=plan.stuck,
                stats=s, substats=ss)))
        # compacted variants: scatter the finalized compact stats back to
        # the dense slab (pure scatter — bitwise the dense-fold stats)
        set_stats_comp_fn = jax.jit(cmap(
            lambda m, c, s, ss: m._replace(
                stats=gibbs.compact_scatter(c, k_max, s),
                substats=gibbs.compact_scatter(c, k_max, ss))))
        apply_plan_comp_fn = jax.jit(cmap(
            lambda m, plan, c, s, ss: m._replace(
                active=plan.merge.new_active, stuck=plan.stuck,
                stats=gibbs.compact_scatter(c, k_max, s),
                substats=gibbs.compact_scatter(c, k_max, ss))))
        comp_fns: Dict[int, Any] = {}

        def compact_plan_fn(k_c: int):
            if k_c not in comp_fns:
                comp_fns[k_c] = jax.jit(cmap(
                    lambda act: gibbs.compaction_plan(act, k_c)))
            return comp_fns[k_c]

        hist_rows: List[Dict[str, np.ndarray]] = []
        times: List[float] = []
        # persistent device buffers: double-buffered (x + label) tiles
        # (labels carry the chain axis; x is shared), the model (x2:
        # pre/post update), and the suff-stat accumulator
        tile_bytes = max(
            length * (d * 4 + n_chains * 2 * 4) * shards
            for _, length in tiles)
        est_peak = (2 * _tree_bytes(model) + _tree_bytes(zeros_acc())
                    + 2 * tile_bytes)
        # the split/merge gate runs on the TRUE iteration number (resume:
        # model.it > 0), matching the resident driver's model.it cond
        it0 = int(jax.device_get(model.it[0] if multi else model.it))
        rec._last_saved = it0           # checkpoint cadence counts from here
        # exact live cluster count (max over chains): known on host from
        # the per-iteration summary pull, so the tiled compact slab needs
        # no lax.cond fallback — sweeps cannot change K mid-pass, and the
        # split/merge fold is bounded by 2*k (splits at most double K)
        if init_state is not None:
            k0 = int(np.max(np.asarray(
                jax.device_get(init_state.active)).sum(axis=-1)))
        else:
            k0 = cfg.init_clusters
        # guardrails: same contract as the resident driver — separate
        # jitted verdict, pulled with the summary the loop syncs anyway.
        # Rollback restores the last healthy model; the stale host label
        # arrays are harmless (sweeps recompute labels from the model).
        health_fn = jax.jit(model_health) if cfg.guardrails else None
        snap = (jax.tree.map(jnp.copy, model), 0) if cfg.guardrails else None
        it = 0
        while it < iters:
            t0 = time.perf_counter()
            model = sweep_model_fn(model)
            k_c = (_k_compact(k0, 1, k_max, cfg.k_block)
                   if cfg.compact else None)
            if k_c is None:
                acc = stream(
                    lambda i, off, length, xt, pt, a:
                        sweep_tile_fn(model, xt, *pt, np.uint32(off), a),
                    zeros_acc(), point_pass=True)
                model = set_stats_fn(model, *finalize_fn(acc))
            else:
                comp = compact_plan_fn(k_c)(model.active)
                acc = stream(
                    lambda i, off, length, xt, pt, a:
                        sweep_tile_comp_fn(model, xt, *pt, np.uint32(off),
                                           comp, a),
                    zeros_acc_k(k_c)(), point_pass=True)
                model = set_stats_comp_fn(model, comp, *finalize_fn(acc))
            if it0 + it >= cfg.burnout:
                plan = plan_fn(model)
                if sm_tile_fn is None:
                    plan_specs = jax.tree.map(lambda _: rep, plan)
                    sm_tile_fn = jax.jit(smap(
                        _sm_tile_c,
                        in_specs=(plan_specs, x_spec, *lab_specs, rep,
                                  acc_specs),
                        out_specs=(lab_specs, acc_specs)))
                    sm_tile_comp_fn = jax.jit(smap(
                        _sm_tile_comp,
                        in_specs=(plan_specs, x_spec, *lab_specs, rep,
                                  comp_specs, acc_specs),
                        out_specs=(lab_specs, acc_specs)))
                k_c_sm = (_k_compact(k0, 2, k_max, cfg.k_block)
                          if cfg.compact else None)
                if k_c_sm is None:
                    acc = stream(
                        lambda i, off, length, xt, pt, a:
                            sm_tile_fn(plan, xt, *pt, np.uint32(off), a),
                        zeros_acc(), point_pass=True)
                    model = apply_plan_fn(model, plan, *finalize_fn(acc))
                else:
                    comp = compact_plan_fn(k_c_sm)(plan.merge.new_active)
                    acc = stream(
                        lambda i, off, length, xt, pt, a:
                            sm_tile_comp_fn(plan, xt, *pt, np.uint32(off),
                                            comp, a),
                        zeros_acc_k(k_c_sm)(), point_pass=True)
                    model = apply_plan_comp_fn(model, plan, comp,
                                               *finalize_fn(acc))
            model, summary = advance_fn(model)
            if health_fn is not None:
                summary, healthy = jax.device_get(
                    (summary, health_fn(model)))
                healthy = bool(healthy)
            else:
                summary = jax.device_get(summary)
                healthy = True
            if not healthy:
                snap_model, snap_it = snap
                rec.rollback(it0 + it + 1, it0 + snap_it,
                             "non-finite/degenerate model state after "
                             "tiled iteration")
                model = _recovery_rekey(
                    jax.tree.map(jnp.copy, snap_model), rec.n_rollbacks)
                it = snap_it
                k0 = int(np.max(np.asarray(
                    jax.device_get(snap_model.active)).sum(axis=-1)))
                continue            # diverged iteration leaves no rows
            k0 = int(np.max(np.asarray(summary["k"])))
            hist_rows.append(summary)
            times.append(time.perf_counter() - t0)
            it += 1
            if cfg.guardrails:
                snap = (jax.tree.map(jnp.copy, model), it)
            rec.maybe_checkpoint(model, it0 + it)
            if verbose:
                ks = np.asarray(summary["k"]).reshape(-1).tolist()
                print(f"iter {it0 + it:4d}  "
                      f"K={ks if len(ks) > 1 else ks[0]}  "
                      f"{times[-1] * 1e3:.1f} ms/iter")
        rec.maybe_checkpoint(model, it0 + it, force=True)

        history = {
            k: np.asarray([row[k] for row in hist_rows])
            for k in _HIST_KEYS} if hist_rows else {
            k: np.zeros((0,) + cshape) for k in _HIST_KEYS}
        if multi:
            history = {k: np.ascontiguousarray(v.T)
                       for k, v in history.items()}
        device_bytes = {
            "mode": "tiled",
            "mesh_devices": int(mesh.devices.size),
            "tile_size": tiles[0][1],
            "est_peak_bytes": int(est_peak),
            **_peak_fields(rss0),
        }
        return self._result(model, labels_h[..., :n].copy(), history,
                            times, device_bytes, n_chains, rec.events)
