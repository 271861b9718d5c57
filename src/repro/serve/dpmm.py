"""Live DPMM serving: multi-size AOT dispatch, hot swap, online refinement.

The dirichletprocess-style consumption pattern: practitioners don't want
a trace, they want a fitted model they can *query*. A ``DPMMEngine``
wraps a ``ModelState`` (usually ``FitResult.select_best().state`` from a
multi-chain fit, or a checkpoint written by core/checkpoint.py) and
answers batched queries:

 - ``predict(x)``        — hard cluster assignment, argmax_k p(k | x)
 - ``predict_logprobs(x)`` — soft assignment: log p(k | x) over the K_max
   slots (inactive slots are -inf)
 - ``log_predictive(x)`` — log p(x) under the mixture posterior
   (the density ranking used e.g. for outlier scoring)
 - ``sample(x, seed)``   — a posterior *draw* of the assignment, reusing
   the sampler's fused assignment kernels (``family.assign`` — the exact
   Gumbel-argmax path the Gibbs sweep runs, counter-based on the query
   row index)

``query(x)`` composes all of them into one :class:`ServeResult` whose
``to_json()`` is the stable wire schema the CLI (launch/serve_dpmm.py)
emits — the Python API and the shell pipeline agree field for field.

The engine is configured by a :class:`ServeConfig` (validated like
``DPMMConfig``) and is a *live* system, not a frozen checkpoint:

**Multi-size AOT step table.** ``cfg.batch_sizes`` is an ascending
ladder (default 256/2048/8192). Every ladder size is AOT-compiled at
engine build — no query ever pays a trace — and each request routes to
the *smallest covering* step (requests longer than the largest step
consume largest-size chunks first, then one covering tail step:
``plan_route``). A 256-row request therefore runs the 256-row
executable instead of padding to 8192 — that pad was pure wasted
compute, and dropping it is what the latency-percentile bench
(benchmarks/bench_serve.py) records as the ladder's p50 win. Because a
request of n rows runs the exact executable a fixed-``batch_sizes=(b,)``
engine compiles for its covering size b, ragged dispatch is *bitwise*
invisible (tests/test_serve_live.py).

**Hot model swap.** ``engine.swap(path)`` loads a new checkpoint (single
file or rotation prefix — newest member that verifies), health-checks it
(``resilience.model_health``, ``cfg.guardrails``), warms every ladder
step off the serving path, then flips ONE snapshot reference atomically.
Queries read that reference once at entry, so a query issued before the
flip is answered bitwise by the old model and a query after it bitwise
by the new one — never a blend. Compiled steps take the model's compact
params/weights as runtime *operands* (not baked constants) keyed only on
shapes, so a swap that preserves shapes reuses the existing executables:
the flip costs an operand gather, never a compile on the serving path.

**Online refinement** (``cfg.refine``, opt-in). Served query batches are
buffered and ``engine.refine()`` folds them through the real sampler
micro-batch sweep (``gibbs.refine_sweep``: steps (a)-(f) on the batch +
an exponentially decayed suff-stat fold) into a *shadow* ModelState.
Every ``cfg.refine_publish_every`` healthy sweeps the shadow publishes
through the same atomic swap path; ``model_health`` gates every publish
and every swap — a poisoned batch (NaN/Inf stats) is rejected, the
shadow re-anchors to the served model, and a ``refine_rejected`` event
lands in ``engine.events`` instead of a poisoned model in production.
With ``refine=False`` the serving path is bit-for-bit the static
engine's (chain-neutrality, tested).

Mixture weights: ``ModelState.logweights`` are the step-(a) Dirichlet
draw's log pi; the engine renormalizes over *active* slots once per
snapshot so ``predict_logprobs`` is a proper conditional and
``log_predictive`` integrates to 1.

Sparse-K serving: checkpoints carry the full (K_max, ...) slab, but a
fitted model typically has K_active << K_max live clusters. At snapshot
build the params/weights are gathered to a compact slab (K_active
rounded up to a power of two, via ``gibbs.compaction_plan`` — active
slots first, ascending) and every query step runs O(N * K_c) work.
Outputs are unchanged to the bit: the compact logsumexp only drops
exact-zero ``exp(NEG_INF - max)`` terms, hard labels map back through
``slot_of_compact`` (ascending, so first-max tie order is preserved),
and the (N, K_max) soft output is the compact one scattered into a
``NEG_INF`` background — float32 ``NEG_INF - logpred`` rounds to
``NEG_INF`` exactly, which is what the dense step computes for inactive
slots.

**Spans.** ``query`` and the step table mark their phases with
``jax.profiler.TraceAnnotation``, so a profiler trace shows the engine's
host work on the same clock as the device's operations; counts ride as
the span's arguments. With no profiler running a span costs the
annotation object alone (about a microsecond).

 - ``dpmm.serve.query`` (``rows``, ``segments``): the whole request.
 - ``dpmm.serve.validate``: the dtype cast and the finiteness scan.
 - ``dpmm.serve.segment`` (``used``, ``batch``): one ladder step, rows
   answered over rows dispatched; its own time includes starting the
   host copies of the step's outputs.
 - ``dpmm.serve.pad``: the host zero-pad of the segment to its step.
 - ``dpmm.serve.dispatch`` (``bytes``): the compiled step's call, with
   the upload of the padded rows.
 - ``dpmm.serve.copy_back`` (``out``, ``bytes``, ``inflight``): the
   read of one output's copy to the host, at its padded size, after
   every segment has been dispatched and every copy started.
   ``inflight`` counts the request's copies started and not yet read
   when this read begins: all of them on the first read, 1 on the last.
   The first read holds the wait for the steps and the transfers; the
   later reads find their data on the host or nearly so. A separate
   wait before the copies would cost a round trip to the device.
 - ``dpmm.serve.assemble``: the concatenation into the ``ServeResult``.
 - ``dpmm.serve.compile`` (``kind`` ``q``/``s``, ``batch``): a step
   compiled into the table (engine build, swap, publish; never inside a
   query).
"""
from __future__ import annotations

import dataclasses
import threading
import warnings
from typing import Any, Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import DPMMConfig
from repro.core import checkpoint as _checkpoint
from repro.core import gibbs, resilience
from repro.core.family import NEG_INF, ComponentFamily, get_family
from repro.core.state import ModelState
from repro.kernels import prng

_span = jax.profiler.TraceAnnotation


class InvalidQueryError(ValueError):
    """A query batch failed validation (wrong rank/width, or non-finite
    values). Typed so servers can map it to a 4xx instead of treating it
    as an engine fault — a NaN row is a *client* bug, and letting it
    through would silently produce garbage scores (NaN propagates
    through loglik + logsumexp into every answer for that row)."""


class PublishRejected(RuntimeError):
    """A model swap or refinement publish failed the ``model_health``
    gate (non-finite stats/weights, degenerate clusters) and was NOT
    made live. The engine keeps serving the previous model; the event is
    also logged in ``engine.events``."""


# ---------------------------------------------------------------------------
# ServeConfig: the serving surface's one validated configuration object
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Knobs for :class:`DPMMEngine`, mirroring ``DPMMConfig``'s
    validated-``__post_init__`` style (invalid values fail at
    construction, not at first query).

    ``batch_sizes`` — ascending AOT ladder; every size is precompiled
    and each request routes to the smallest covering step.
    ``checkpoint_prefix`` — default source for ``engine.swap()`` (set
    automatically by ``from_checkpoint``).
    ``guardrails`` — run ``model_health`` before any swap/publish goes
    live.
    ``refine*`` — opt-in online refinement: micro-batch Gibbs sweeps
    over buffered query traffic into a shadow model (``refine_batch``
    rows per sweep, at most ``refine_buffer`` rows buffered, suff-stats
    folded as ``decay * old + batch``), published through the swap path
    every ``refine_publish_every`` healthy sweeps. ``refine_cfg``
    carries the sampler hyper-parameters (prior + alpha) — defaults to
    ``DPMMConfig()`` with the engine's component family.
    """
    batch_sizes: Tuple[int, ...] = (256, 2048, 8192)
    validate_queries: bool = True
    use_pallas: bool = False
    seed: int = 0
    checkpoint_prefix: Optional[str] = None
    guardrails: bool = True
    refine: bool = False
    refine_batch: int = 1024
    refine_buffer: int = 32768
    refine_decay: float = 0.9
    refine_publish_every: int = 1
    refine_cfg: Optional[DPMMConfig] = None

    def __post_init__(self):
        sizes = tuple(self.batch_sizes)
        if not sizes:
            raise ValueError("ServeConfig.batch_sizes must name at least "
                             "one AOT step size")
        for b in sizes:
            if isinstance(b, bool) or not isinstance(b, int) or b < 1:
                raise ValueError(
                    f"ServeConfig.batch_sizes entries must be positive "
                    f"ints, got {b!r}")
        if list(sizes) != sorted(set(sizes)):
            raise ValueError(
                f"ServeConfig.batch_sizes must be strictly ascending "
                f"(the routing walks smallest-covering-first), got {sizes}")
        object.__setattr__(self, "batch_sizes", sizes)

        def positive(name, value):
            if (isinstance(value, bool) or not isinstance(value, int)
                    or value <= 0):
                raise ValueError(f"ServeConfig.{name} must be a positive "
                                 f"int, got {value!r}")
        positive("refine_batch", self.refine_batch)
        positive("refine_buffer", self.refine_buffer)
        positive("refine_publish_every", self.refine_publish_every)
        if self.refine_buffer < self.refine_batch:
            raise ValueError(
                f"ServeConfig.refine_buffer ({self.refine_buffer}) must "
                f"hold at least one refine_batch ({self.refine_batch})")
        if not (0.0 <= float(self.refine_decay) < 1.0):
            raise ValueError(
                f"ServeConfig.refine_decay must be in [0, 1) — 1.0 would "
                f"grow stats without bound; got {self.refine_decay!r}")
        if (self.checkpoint_prefix is not None
                and not isinstance(self.checkpoint_prefix, str)):
            raise ValueError(
                f"ServeConfig.checkpoint_prefix must be a path string or "
                f"None, got {type(self.checkpoint_prefix).__name__}")


# ---------------------------------------------------------------------------
# ServeResult: the one result type every query surface composes into
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ServeResult:
    """One request's answers (rows past the query count are stripped).

    ``model_epoch`` identifies the served model generation (bumps on
    every swap/publish) — a client can detect mid-stream model changes
    without comparing floats. ``sampled_labels`` is filled only by
    ``query(..., sample=True)`` / ``engine.sample``.
    ``to_json()`` is the stable wire schema; the CLI emits exactly it.
    """
    labels: np.ndarray          # (N,) int32 hard assignment
    logprobs: np.ndarray        # (N, K_max) float32 log p(k | x)
    log_predictive: np.ndarray  # (N,) float32 log p(x)
    sampled_labels: Optional[np.ndarray]  # (N,) int32, or None
    family: str
    k_max: int
    model_epoch: int

    @property
    def n(self) -> int:
        return int(self.labels.shape[0])

    def cluster_counts(self) -> Dict[int, int]:
        counts = np.bincount(self.labels, minlength=self.k_max)
        return {int(k): int(counts[k]) for k in np.flatnonzero(counts)}

    def to_json(self, include_logprobs: bool = False) -> dict:
        """Stable JSON schema, shared verbatim by launch/serve_dpmm.py.
        ``logprobs`` is opt-in (it is N * K_max floats)."""
        out = {
            "n": self.n,
            "family": self.family,
            "k_max": self.k_max,
            "model_epoch": self.model_epoch,
            "labels": self.labels.tolist(),
            "log_predictive": self.log_predictive.tolist(),
            "sampled_labels": (None if self.sampled_labels is None
                               else self.sampled_labels.tolist()),
            "cluster_counts": {str(k): v
                               for k, v in self.cluster_counts().items()},
        }
        if include_logprobs:
            out["logprobs"] = self.logprobs.tolist()
        return out


# ---------------------------------------------------------------------------
# The AOT step table: executables keyed on shapes, model fed as operands
# ---------------------------------------------------------------------------
class _Operands(NamedTuple):
    """The compact-model operands every serving step consumes. These are
    runtime *arguments* to the compiled steps (never baked constants), so
    two models with the same shapes share executables — a swap/publish
    flips operands, not programs."""
    params: Any               # family params, compact (K_c, ...) slab
    logw: jax.Array           # (K_c,) renormalized log weights
    active: jax.Array         # (K_c,) bool
    slots: jax.Array          # (K_c,) int32 dense slot id of each row


def _query_fn(family: ComponentFamily, k_max: int, use_pallas: bool):
    def step(x, params, logw, active, slots):
        ll = family.loglik(x, params, use_pallas=use_pallas)
        logits = jnp.where(active[None, :], ll + logw[None, :], NEG_INF)
        logpred = jax.scipy.special.logsumexp(logits, axis=-1)
        logprobs = jnp.full((x.shape[0], k_max), NEG_INF, jnp.float32)
        logprobs = logprobs.at[:, slots].set(logits - logpred[:, None])
        return {
            "labels": jnp.take(
                slots, jnp.argmax(logits, axis=-1)).astype(jnp.int32),
            "logprobs": logprobs,
            "log_predictive": logpred,
        }
    return step


def _sample_fn(family: ComponentFamily, use_pallas: bool):
    def step(x, params, logw, active, slots, key_words, offset):
        # the sweep's step (e): argmax_k [loglik + log pi + Gumbel],
        # counter-based on the request row index — the fused
        # assign/assign_fast kernel path, verbatim. ``slots`` keeps the
        # Gumbel counters in dense slot space, so the draw is bitwise
        # the dense engine's AND invariant to how the request was
        # decomposed over ladder steps (counters depend on the row, not
        # the step).
        gidx = offset + jnp.arange(x.shape[0], dtype=jnp.uint32)
        z = family.assign(x, params, logw, active, gidx, key_words,
                          use_pallas=use_pallas,
                          slots=slots.astype(jnp.uint32))
        return jnp.take(slots, z).astype(jnp.int32)
    return step


class _StepTable:
    """Process-wide cache of AOT-compiled serving executables.

    Keyed on everything that determines the *program*: family, feature
    width, dense/compact slab widths, batch size, kernel path. Model
    values are operands, so every engine (and every swapped/published
    model) with the same shapes shares one executable — which is also
    what makes ragged-dispatch parity *bitwise*: the ladder engine and a
    fixed-batch engine literally run the same compiled step.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._compiled: Dict[tuple, Any] = {}

    def _get(self, key, build):
        with self._lock:
            hit = self._compiled.get(key)
            if hit is None:
                with _span("dpmm.serve.compile", kind=key[0], batch=key[3]):
                    hit = self._compiled[key] = build()
            return hit

    @staticmethod
    def _sds(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                           jnp.asarray(a).dtype), tree)

    def query_step(self, family, k_max: int, batch: int, d: int,
                   use_pallas: bool, ops: _Operands):
        key = ("q", family.name, k_max, batch, d, use_pallas,
               ops.slots.shape[0])
        x = jax.ShapeDtypeStruct((batch, d), jnp.float32)
        return self._get(key, lambda: jax.jit(
            _query_fn(family, k_max, use_pallas)
        ).lower(x, *self._sds(tuple(ops))).compile())

    def sample_step(self, family, k_max: int, batch: int, d: int,
                    use_pallas: bool, ops: _Operands):
        key = ("s", family.name, k_max, batch, d, use_pallas,
               ops.slots.shape[0])
        x = jax.ShapeDtypeStruct((batch, d), jnp.float32)
        u32 = jax.ShapeDtypeStruct((2,), jnp.uint32)
        off = jax.ShapeDtypeStruct((), jnp.uint32)
        return self._get(key, lambda: jax.jit(
            _sample_fn(family, use_pallas)
        ).lower(x, *self._sds(tuple(ops)), u32, off).compile())


_TABLE = _StepTable()


# ---------------------------------------------------------------------------
# Served snapshot: ONE immutable object per model generation
# ---------------------------------------------------------------------------
class _Served(NamedTuple):
    """Everything a query needs, bundled so the swap path can flip a
    single reference atomically: a query reads ``engine._served`` once
    at entry and sees exactly one model generation end to end."""
    model: ModelState
    family: ComponentFamily
    epoch: int
    k_max: int
    d: int
    k_active: int
    slots_np: np.ndarray        # (K_c,) dense slot ids, active first
    logweights: jax.Array       # (K_max,) renormalized dense log weights
    ops: _Operands
    steps: Dict[int, Any]       # batch size -> compiled query step
    sample_steps: Dict[int, Any]
    source: str


def _ceil_pow2(v: int) -> int:
    return 1 << max(0, (int(v) - 1).bit_length())


def _build_served(model: ModelState, family: ComponentFamily,
                  cfg: ServeConfig, epoch: int, source: str) -> _Served:
    """Gather the compact operands and warm every ladder step. Runs OFF
    the serving path (engine build, swap, publish) — by the time the
    snapshot is flipped live, every request size is compile-free."""
    if model.active.ndim != 1:
        raise ValueError(
            f"DPMMEngine expects a single-chain ModelState; got active "
            f"shape {tuple(model.active.shape)} — select a chain first "
            "(FitResult.select_best())")
    k_max = int(model.active.shape[0])
    d = int(family.cluster_means(model.stats).shape[-1])

    active = model.active
    logw = jnp.where(active, model.logweights, NEG_INF)
    # renormalize over active slots: p(k) must sum to 1 for the
    # predictive density (the sampler's logweights carry alpha-slot
    # mass that the restricted sweep never uses)
    logw = (logw - jax.scipy.special.logsumexp(
        jnp.where(active, logw, -jnp.inf))).astype(jnp.float32)

    k_active = max(1, int(np.asarray(jax.device_get(active)).sum()))
    # compact width is K_active rounded up to a power of two: pad rows
    # are inactive dense slots (masked to NEG_INF, bitwise inert), and
    # the bucketing means a refinement publish or swap whose live count
    # drifts within the bucket reuses the same executables
    k_c = min(k_max, _ceil_pow2(k_active))
    comp = gibbs.compaction_plan(active, k_c)
    slots = comp.slot_of_compact
    ops = _Operands(params=gibbs.compact_gather(comp, model.params),
                    logw=jnp.take(logw, slots),
                    active=jnp.take(active, slots),
                    slots=slots)
    # fits run under a shard_map mesh and leave NamedSharding on every
    # leaf; the AOT steps are compiled for plain single-device operands,
    # so commit the (tiny, O(K_c)) operand slab to one device here
    ops = jax.device_put(ops, jax.devices()[0])
    steps = {b: _TABLE.query_step(family, k_max, b, d, cfg.use_pallas, ops)
             for b in cfg.batch_sizes}
    samples = {b: _TABLE.sample_step(family, k_max, b, d, cfg.use_pallas,
                                     ops)
               for b in cfg.batch_sizes}
    return _Served(model=model, family=family, epoch=epoch, k_max=k_max,
                   d=d, k_active=k_active,
                   slots_np=np.asarray(jax.device_get(slots)),
                   logweights=logw, ops=ops, steps=steps,
                   sample_steps=samples, source=source)


def _traffic_prior(family: ComponentFamily, cfg: DPMMConfig,
                   model: ModelState):
    """Prior hyper-parameters for refinement sweeps. The fit derived its
    prior from the data column mean; at serve time the data is gone, but
    the count-weighted active cluster means reconstruct exactly
    ``sum_i x_i / N`` from the sufficient statistics."""
    means = family.cluster_means(model.stats)
    w = jnp.where(model.active, model.stats.n, 0.0)
    mean = ((w[:, None] * means).sum(axis=0)
            / jnp.maximum(w.sum(), 1e-6)).astype(jnp.float32)
    return family.build_prior(cfg, mean[None, :])


_LEGACY_KWARGS = ("batch_size", "use_pallas", "seed", "validate_queries")


def _coerce_cfg(cfg: Optional[ServeConfig], legacy: dict,
                where: str) -> ServeConfig:
    """One-release deprecation shim: map the PR-5 loose kwargs onto
    ``ServeConfig`` with a warning. Remove after the next release."""
    if not legacy:
        return cfg if cfg is not None else ServeConfig()
    unknown = sorted(set(legacy) - set(_LEGACY_KWARGS))
    if unknown:
        raise TypeError(f"{where}() got unexpected keyword argument(s) "
                        f"{unknown}")
    if cfg is not None:
        raise TypeError(
            f"{where}() got both a ServeConfig and legacy keyword "
            f"argument(s) {sorted(legacy)} — move them into the "
            "ServeConfig")
    warnings.warn(
        f"{where}({', '.join(sorted(legacy))}=...) is deprecated; pass a "
        "ServeConfig instead (batch_size=N becomes batch_sizes=(N,)). "
        "The keyword shim will be removed next release.",
        DeprecationWarning, stacklevel=3)
    fields: Dict[str, Any] = {}
    if "batch_size" in legacy:
        fields["batch_sizes"] = (int(legacy["batch_size"]),)
    for name in ("use_pallas", "seed", "validate_queries"):
        if name in legacy:
            fields[name] = legacy[name]
    return ServeConfig(**fields)


class DPMMEngine:
    """Live query engine over a fitted ``ModelState``.

    ``DPMMEngine(model, family, cfg)`` / ``DPMMEngine.from_checkpoint(
    path, cfg)`` with a :class:`ServeConfig`; the PR-5 loose kwargs
    (``batch_size=...`` etc.) still work behind a one-release
    ``DeprecationWarning`` shim. ``model`` must be single-chain (no
    leading chain axis) — take ``FitResult.select_best().state`` first.
    """

    def __init__(self, model: ModelState,
                 family: Union[str, ComponentFamily],
                 cfg: Optional[ServeConfig] = None, **legacy):
        self.cfg = _coerce_cfg(cfg, legacy, "DPMMEngine")
        fam = get_family(family) if isinstance(family, str) else family
        self._swap_lock = threading.Lock()   # serializes swap/publish
        self._key_lock = threading.Lock()
        self._key = jax.random.key(self.cfg.seed)
        self.events: List[dict] = []
        self._served = _build_served(model, fam, self.cfg, epoch=0,
                                     source="<memory>")
        # online refinement state (lazy; None until the first refine())
        self._refine_lock = threading.Lock()
        self._traffic: List[np.ndarray] = []
        self._traffic_rows = 0
        self._shadow: Optional[ModelState] = None
        self._refine_fn = None
        self._refine_prior = None
        self._since_publish = 0

    # -- construction ---------------------------------------------------
    @classmethod
    def from_checkpoint(cls, path: str, cfg: Optional[ServeConfig] = None,
                        **legacy) -> "DPMMEngine":
        """Load a core/checkpoint.py npz and build the engine.

        ``path`` may be a single checkpoint file OR an auto-checkpoint
        rotation prefix (``cfg.checkpoint_path`` of a fit with
        ``checkpoint_every`` set): the newest member that verifies
        (version, per-leaf CRC32, shapes) is served — a half-written or
        bit-flipped member falls back through the rotation instead of
        poisoning the engine (``core/checkpoint.resolve_model``). Raises
        ``CheckpointCorrupt`` / ``CheckpointNotFound`` otherwise.
        ``path`` becomes ``cfg.checkpoint_prefix`` (unless already set),
        so a bare ``engine.swap()`` re-reads the same rotation — the
        fit-keeps-checkpointing, engine-keeps-swapping loop.
        """
        cfg = _coerce_cfg(cfg, legacy, "DPMMEngine.from_checkpoint")
        model, family, resolved, _it = _checkpoint.resolve_model(path)
        if cfg.checkpoint_prefix is None:
            cfg = dataclasses.replace(cfg, checkpoint_prefix=path)
        eng = cls(model, family, cfg)
        eng._served = eng._served._replace(source=resolved)
        return eng

    # -- introspection (stable surface; snapshot-backed) ----------------
    @property
    def model(self) -> ModelState:
        return self._served.model

    @property
    def family(self) -> ComponentFamily:
        return self._served.family

    @property
    def epoch(self) -> int:
        """Served model generation; bumps on every swap/publish."""
        return self._served.epoch

    @property
    def k_max(self) -> int:
        return self._served.k_max

    @property
    def k_active(self) -> int:
        return self._served.k_active

    @property
    def d(self) -> int:
        return self._served.d

    @property
    def slots(self) -> np.ndarray:
        return self._served.slots_np

    @property
    def logweights(self) -> jax.Array:
        return self._served.logweights

    @property
    def batch_sizes(self) -> Tuple[int, ...]:
        return self.cfg.batch_sizes

    @property
    def batch_size(self) -> int:
        """Largest ladder step (PR-5 compat: the old single AOT size)."""
        return self.cfg.batch_sizes[-1]

    @property
    def validate_queries(self) -> bool:
        return self.cfg.validate_queries

    # -- routing ---------------------------------------------------------
    def plan_route(self, n: int) -> List[Tuple[int, int, int]]:
        """Ladder routing for an n-row request: ``(start, used,
        batch_size)`` segments. Requests no longer than the largest step
        run as ONE dispatch at the smallest covering size (a 256-row
        request never pays the 8192 pad); longer requests consume
        largest-size chunks, then one covering tail dispatch."""
        sizes = self.cfg.batch_sizes
        big = sizes[-1]
        segs: List[Tuple[int, int, int]] = []
        start = 0
        while n - start > big:
            segs.append((start, big, big))
            start += big
        if n - start > 0:
            rem = n - start
            segs.append((start, rem, next(b for b in sizes if b >= rem)))
        return segs

    # -- query path -------------------------------------------------------
    def _validated(self, x: np.ndarray, d: int) -> np.ndarray:
        x = np.asarray(x, np.float32)
        if x.ndim != 2 or x.shape[1] != d:
            raise InvalidQueryError(f"queries must be (N, {d}), got "
                                    f"{x.shape}")
        if self.cfg.validate_queries and not np.isfinite(x).all():
            bad = np.flatnonzero(~np.isfinite(x).all(axis=1))
            raise InvalidQueryError(
                f"queries contain non-finite values in {bad.size} row(s), "
                f"first at row {int(bad[0])} — NaN/Inf inputs would "
                "produce NaN scores for those rows (pass "
                "ServeConfig(validate_queries=False) to skip this check)")
        return x

    @staticmethod
    def _pad(block: np.ndarray, b: int, d: int) -> np.ndarray:
        if block.shape[0] == b:
            return block
        return np.concatenate(
            [block, np.zeros((b - block.shape[0], d), np.float32)], axis=0)

    def query(self, x: np.ndarray, sample: bool = False,
              seed: Optional[int] = None) -> ServeResult:
        """All answers for (N, d) queries through the AOT step table.
        N = 0 returns empty answers. ``sample=True`` additionally draws
        ``sampled_labels`` (see :meth:`sample`)."""
        with _span("dpmm.serve.query") as request:
            served = self._served          # ONE snapshot for the request
            with _span("dpmm.serve.validate"):
                x = self._validated(x, served.d)
            self._record_traffic(x)
            route = self.plan_route(x.shape[0])
            request.set_metadata(rows=x.shape[0], segments=len(route))
            # every output's copy to the host starts as soon as its step
            # is dispatched, and none is read before all have started:
            # each blocking read costs a round trip to the device, so
            # the request pays one wait, not one per output and segment
            started: List[Tuple[str, jax.Array, int]] = []
            for start, used, b in route:
                with _span("dpmm.serve.segment", used=used, batch=b):
                    with _span("dpmm.serve.pad"):
                        block = self._pad(x[start:start + used], b, served.d)
                    with _span("dpmm.serve.dispatch", bytes=block.nbytes):
                        out = served.steps[b](block, *served.ops)
                    for k, v in out.items():
                        v.copy_to_host_async()
                        started.append((k, v, used))
            outs: Dict[str, list] = {"labels": [], "logprobs": [],
                                     "log_predictive": []}
            for i, (k, v, used) in enumerate(started):
                with _span("dpmm.serve.copy_back", out=k, bytes=v.nbytes,
                           inflight=len(started) - i):
                    outs[k].append(np.asarray(v)[:used])
            sampled = self._sample(served, x, seed) if sample else None
            with _span("dpmm.serve.assemble"):
                empty = not outs["labels"]
                return ServeResult(
                    labels=(np.zeros((0,), np.int32) if empty
                            else np.concatenate(outs["labels"])),
                    logprobs=(np.zeros((0, served.k_max), np.float32)
                              if empty else np.concatenate(outs["logprobs"])),
                    log_predictive=(
                        np.zeros((0,), np.float32) if empty
                        else np.concatenate(outs["log_predictive"])),
                    sampled_labels=sampled,
                    family=served.family.name, k_max=served.k_max,
                    model_epoch=served.epoch)

    def predict(self, x: np.ndarray) -> np.ndarray:
        return self.query(x).labels

    def predict_logprobs(self, x: np.ndarray) -> np.ndarray:
        return self.query(x).logprobs

    def log_predictive(self, x: np.ndarray) -> np.ndarray:
        return self.query(x).log_predictive

    def sample(self, x: np.ndarray,
               seed: Optional[int] = None) -> np.ndarray:
        """Posterior assignment DRAW (not the argmax): the Gibbs sweep's
        Gumbel-argmax assignment over the served components. Each call
        advances the engine key unless ``seed`` pins it. Draws are
        counter-based on the request row index, so they are invariant to
        the ladder decomposition."""
        served = self._served
        x = self._validated(x, served.d)
        self._record_traffic(x)
        return self._sample(served, x, seed)

    def _sample(self, served: _Served, x: np.ndarray,
                seed: Optional[int]) -> np.ndarray:
        if seed is not None:
            key = jax.random.key(seed)
        else:
            with self._key_lock:
                key = self._key
                self._key = jax.random.fold_in(self._key, 1)
        words = prng.key_words(key)
        parts = [np.zeros((0,), np.int32)]
        for start, used, b in self.plan_route(x.shape[0]):
            out = served.sample_steps[b](
                self._pad(x[start:start + used], b, served.d),
                *served.ops, words, np.uint32(start))
            parts.append(np.asarray(jax.device_get(out))[:used])
        return np.concatenate(parts)

    # -- hot model swap ---------------------------------------------------
    def swap(self, path: Optional[str] = None) -> int:
        """Load a checkpoint (file or rotation prefix; defaults to
        ``cfg.checkpoint_prefix``), health-check it, AOT-warm every
        ladder step OFF the serving path, then flip atomically. Queries
        issued before the flip are answered bitwise by the old model,
        after it bitwise by the new one. Returns the new epoch. Raises
        :class:`PublishRejected` (old model keeps serving) if
        ``cfg.guardrails`` and the loaded state is unhealthy."""
        path = path if path is not None else self.cfg.checkpoint_prefix
        if path is None:
            raise ValueError(
                "swap() needs a checkpoint path: pass one or set "
                "ServeConfig.checkpoint_prefix (from_checkpoint sets it)")
        model, family, resolved, it = _checkpoint.resolve_model(path)
        return self._publish(model, family, source=resolved,
                             kind="model_swap", it=it)

    def _publish(self, model: ModelState, family: ComponentFamily,
                 source: str, kind: str, it: Optional[int] = None) -> int:
        """The one path a new model takes to production: health gate,
        off-path warmup, atomic flip, audit event."""
        if self.cfg.guardrails and not bool(jax.device_get(
                jax.jit(resilience.model_health)(model))):
            event = {"kind": f"{kind}_rejected", "source": source,
                     "detail": "model_health gate failed (non-finite "
                               "stats/weights or degenerate cluster)"}
            self.events.append(event)
            raise PublishRejected(
                f"{kind} from {source!r} rejected: model_health gate "
                "failed — the previous model keeps serving")
        with self._swap_lock:
            nxt = _build_served(model, family, self.cfg,
                                epoch=self._served.epoch + 1,
                                source=source)
            self._served = nxt             # THE atomic flip
            # the shadow chain re-anchors on whatever is now live
            self._shadow = None
            self._refine_fn = None
            self._refine_prior = None
            self._since_publish = 0
            self.events.append({"kind": kind, "epoch": nxt.epoch,
                                "source": source,
                                "it": (None if it is None else int(it))})
            return nxt.epoch

    # -- online refinement ------------------------------------------------
    def _record_traffic(self, x: np.ndarray) -> None:
        if not self.cfg.refine or x.shape[0] == 0:
            return
        with self._refine_lock:
            self._traffic.append(np.array(x, np.float32, copy=True))
            self._traffic_rows += x.shape[0]
            while (self._traffic_rows > self.cfg.refine_buffer
                   and len(self._traffic) > 1):
                self._traffic_rows -= self._traffic.pop(0).shape[0]
            if self._traffic_rows > self.cfg.refine_buffer:
                keep = self._traffic[0][-self.cfg.refine_buffer:]
                self._traffic = [keep]
                self._traffic_rows = keep.shape[0]

    def _refine_setup(self, served: _Served):
        """Lazy per-anchor refinement program: prior from the anchor
        model's stats, jitted sweep+health step (prior is an operand, so
        re-anchoring after a swap never re-traces)."""
        if self._refine_prior is None:
            dcfg = self.cfg.refine_cfg
            if dcfg is None:
                dcfg = DPMMConfig(component=served.family.name)
            elif dcfg.component != served.family.name:
                raise ValueError(
                    f"ServeConfig.refine_cfg.component "
                    f"({dcfg.component!r}) does not match the served "
                    f"family ({served.family.name!r})")
            self._refine_prior = _traffic_prior(served.family, dcfg,
                                                served.model)
            fam, cfg = served.family, self.cfg
            alpha = float(dcfg.alpha)

            def run(model, xb, valid, prior):
                m2, labels = gibbs.refine_sweep(
                    model, xb, valid, prior, fam, alpha,
                    decay=cfg.refine_decay, use_pallas=cfg.use_pallas)
                return m2, resilience.model_health(m2), labels
            self._refine_fn = jax.jit(run)
        return self._refine_fn, self._refine_prior

    def refine(self, x: Optional[np.ndarray] = None,
               publish: bool = True) -> dict:
        """Fold buffered query traffic (or an explicit ``x``) into the
        shadow model via micro-batch Gibbs sweeps, publishing every
        ``cfg.refine_publish_every`` healthy sweeps through the atomic
        swap path. Partial tail batches are padded with ``valid=0`` rows
        (stat-inert). An unhealthy sweep re-anchors the shadow to the
        served model and logs ``refine_rejected`` — poison never
        publishes. Returns a summary dict."""
        if not self.cfg.refine:
            raise ValueError("online refinement is disabled: construct "
                             "the engine with ServeConfig(refine=True)")
        served = self._served
        B, d = self.cfg.refine_batch, served.d
        with self._refine_lock:
            if x is not None:
                rows = self._validated_refine(x, d)
            else:
                rows = (np.concatenate(self._traffic)
                        if self._traffic else np.zeros((0, d), np.float32))
                self._traffic, self._traffic_rows = [], 0
        out = {"sweeps": 0, "rows": 0, "rejected": 0, "published": 0,
               "epoch": served.epoch}
        if rows.shape[0] == 0:
            return out
        step, prior = self._refine_setup(served)
        shadow = self._shadow if self._shadow is not None else served.model
        for start in range(0, rows.shape[0], B):
            used = min(B, rows.shape[0] - start)
            xb = self._pad(rows[start:start + used], B, d)
            valid = np.zeros((B,), np.float32)
            valid[:used] = 1.0
            shadow2, ok, _labels = step(shadow, jnp.asarray(xb),
                                        jnp.asarray(valid), prior)
            if not bool(jax.device_get(ok)):
                out["rejected"] += 1
                self.events.append({
                    "kind": "refine_rejected",
                    "rows": [int(start), int(start + used)],
                    "detail": "micro-batch sweep produced an unhealthy "
                              "model (non-finite stats); shadow "
                              "re-anchored to the served model"})
                shadow = self._served.model   # drop the poisoned chain
                self._since_publish = 0
                continue
            shadow = shadow2
            out["sweeps"] += 1
            out["rows"] += used
            self._since_publish += 1
            if publish and self._since_publish >= self.cfg.refine_publish_every:
                out["epoch"] = self._publish(
                    shadow, served.family, source="refine",
                    kind="refine_publish",
                    it=int(np.asarray(jax.device_get(shadow.it))))
                out["published"] += 1
                # _publish reset the anchor; keep sweeping from the
                # just-published chain
                self._shadow = shadow
                self._since_publish = 0
        self._shadow = shadow
        return out

    def _validated_refine(self, x: np.ndarray, d: int) -> np.ndarray:
        x = np.asarray(x, np.float32)
        if x.ndim != 2 or x.shape[1] != d:
            raise InvalidQueryError(
                f"refinement batches must be (N, {d}), got {x.shape}")
        return x
