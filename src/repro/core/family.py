"""ComponentFamily: the one dispatch layer for all likelihood families.

The sampler skeleton (restricted Gibbs + sub-cluster splits/merges) is
observation-model-agnostic — the paper's central modularity claim: 'it can
be easily adapted to other component distributions ... as long as they
belong to an exponential family' (§3.4.3). A ``ComponentFamily`` bundles
everything the skeleton needs from an observation model:

 - conjugate math: ``stats_from_points`` / ``add_stats`` / ``log_marginal``
   / ``sample_posterior`` / ``expected_params`` / ``loglik``,
 - pytree *templates* (``param_struct`` / ``stats_struct``) used to build
   replicated PartitionSpecs without knowing field names,
 - an optional Pallas/accelerated ``loglik_fast`` path (paper §4.2),
 - the fused sweep hot path (paper §4.1e/§4.4 "Kernel #1/#2"): ``assign``
   (step e), ``sub_assign`` (step f, own-cluster only) and
   ``stats_from_labels`` dispatch between streaming Pallas kernels
   (``assign_fast`` / ``assign_pack`` / ``sub_assign_fast`` /
   ``labels_stats_fast``, kernels/assign.py + kernels/suffstats.py) and
   jnp reference fallbacks (``labels_stats_ref``, chunked own-cluster
   gather) — neither path materializes an (N, K, 2) sub-cluster loglik or
   a dense (N, K, 2) responsibility tensor,
 - the ONE-READ sweep (``sweep`` dispatch): steps (e) + (f) + the
   suff-stat fold run while each point block is resident, so a sweep
   reads every tile of x from HBM exactly once. ``sweep_fast`` is the
   per-family Pallas megakernel hook (kernels/sweep.py, packed via the
   modules' ``sweep_pack``); ``sweep_ref`` is the blocked jnp scan — both
   fold stat partials per STATS_BLOCK left-to-right and reproduce the
   three-pass chain bitwise,
 - the feature-sharding contract (DESIGN §10): ``feature_shardable``
   families declare which stats fields carry a feature axis
   (``feature_stat_fields``, all-gathered after the data-axis psum) and how
   to slice their params to a local feature block (``slice_params``), and
 - ``build_prior(cfg, x)``: config + data -> prior hyper-parameters.
   ``DPMM.fit`` passes the (1, d) *column-mean summary row* from the
   ``DataSource`` (computed by one canonical streaming pass so resident
   and out-of-core fits build bitwise-identical priors) — family hooks may
   read ``x.shape[1]`` and ``x.mean(axis=0)`` but must not assume all N
   rows are present.

``core/gibbs.py``, ``core/sampler.py`` and ``core/splitmerge.py`` dispatch
*only* through this interface — no ``hasattr``/``getattr`` probing of
param/stat pytrees anywhere in the sampler.

Registering a new family::

    from repro.core.family import ComponentFamily, register_family
    register_family(ComponentFamily(name="my_family", ...))
    # then DPMMConfig(component="my_family") just works.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import threading
import warnings
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import diag_gaussian, multinomial, niw, poisson
from repro.core.state import ModelState, PointState
from repro.kernels import prng
# the inactive-cluster assignment mask — single-sourced from the fused
# kernels so reference and in-kernel masking can never drift
from repro.kernels.assign import NEG_INF  # noqa: F401  (re-exported)
# granularity of the suff-stat fold (canonical home: kernels/sweep.py;
# core/gibbs.py re-exports it) — the one-read blocked passes below fold
# stat partials per STATS_BLOCK points, left to right in point order
from repro.kernels.sweep import STATS_BLOCK


def _add_tree(a: Any, b: Any) -> Any:
    return jax.tree.map(jnp.add, a, b)


# Which sweep body each traced ``ComponentFamily.sweep`` ran: "sweep_fast"
# (the Pallas megakernel) or "sweep_ref" (the blocked jnp scan). The choice
# is static, so it is made — and counted — when a program is traced; the
# fit drivers open a recorder per fit and put the counts on FitResult.
_RECORDERS = threading.local()


class SweepFallbackWarning(UserWarning):
    """``use_pallas=True`` traced a sweep that runs the jnp reference."""


@contextlib.contextmanager
def record_sweep_paths() -> Iterator[collections.Counter]:
    """Count the sweep bodies traced in this thread while the block runs:
    ``{"sweep_fast": n, "sweep_ref": m}`` (one per traced program branch,
    not per iteration)."""
    stack = getattr(_RECORDERS, "stack", None)
    if stack is None:
        stack = _RECORDERS.stack = []
    counts: collections.Counter = collections.Counter()
    stack.append(counts)
    try:
        yield counts
    finally:
        stack.remove(counts)


def _note_sweep_path(path: str) -> None:
    for counts in getattr(_RECORDERS, "stack", ()):
        counts[path] += 1


def fold_blocked(family: "ComponentFamily", k_max: int, body, x: jax.Array,
                 valid: jax.Array, extras: Tuple, acc,
                 use_pallas: bool = False, label_map=None):
    """Run a per-point ``body`` over fixed STATS_BLOCK point blocks and
    fold each block's sub-cluster stat partial into ``acc`` — the one-read
    pass shape shared by the fused sweep (``ComponentFamily.sweep_ref``)
    and the fused split/merge apply (``splitmerge.split_merge_tile``).

    ``body(x_blk, valid_blk, *extras_blk) -> (labels_blk, sublabels_blk)``
    runs while the block is resident; its labels feed the stat partial
    immediately, so each block of ``x`` is consumed exactly once per pass
    (one ``lax.scan`` body — nothing re-reads x afterwards). Partials are
    added left to right in global point order, per STATS_BLOCK — the exact
    float addition sequence of ``gibbs.accumulate_substats`` — so chains
    stay bitwise identical to the three-pass formulation on every plane,
    tile size, and sharding. Only a shard's ragged tail (< STATS_BLOCK)
    runs outside the scan; it folds last either way.

    ``label_map`` (optional, (k_dense,) int32) re-indexes labels before
    the stat fold only — the returned labels stay in ``body``'s space.
    The active-set compaction uses it to fold a dense-slab relabel pass
    into a compact (k_max = K_active) ``acc``: per-segment sums are
    unchanged (same points, same order), so the scattered-back stats are
    bitwise the dense fold's.
    """
    n = x.shape[0]
    nb, rem = divmod(n, STATS_BLOCK)
    outs = []
    stat_lab = ((lambda lab: lab) if label_map is None
                else (lambda lab: label_map[lab]))
    if nb:
        blk = lambda a: a[:nb * STATS_BLOCK].reshape(
            (nb, STATS_BLOCK) + a.shape[1:])

        def step(a, args):
            xb, vb = args[0], args[1]
            lab, sub = body(xb, vb, *args[2:])
            p = family.stats_from_labels(xb, vb, stat_lab(lab), sub, k_max,
                                         use_pallas=use_pallas)
            return _add_tree(a, p), (lab, sub)

        acc, (labs, subs) = jax.lax.scan(
            step, acc, (blk(x), blk(valid)) + tuple(blk(e) for e in extras))
        outs.append((labs.reshape(-1), subs.reshape(-1)))
    if rem:
        tail = slice(nb * STATS_BLOCK, None)
        xb, vb = x[tail], valid[tail]
        lab, sub = body(xb, vb, *(e[tail] for e in extras))
        p = family.stats_from_labels(xb, vb, stat_lab(lab), sub, k_max,
                                     use_pallas=use_pallas)
        acc = _add_tree(acc, p)
        outs.append((lab, sub))
    if len(outs) == 1:
        labels, sublabels = outs[0]
    else:
        labels = jnp.concatenate([o[0] for o in outs])
        sublabels = jnp.concatenate([o[1] for o in outs])
    return labels, sublabels, acc


def fold_chunked(run, x: jax.Array, valid: jax.Array, gidx: jax.Array, acc):
    """Drive a megakernel ``run(x, valid, gidx) -> (labels, sublabels,
    per-STATS_BLOCK partials)`` over STATS_BLOCK-aligned point chunks,
    folding every partial into ``acc`` left to right in point order.

    That is the add chain of one call over all of x, so the result is
    bitwise the same; but the kernel's per-point-block stat partials,
    which grow with the points of a call (4 GB of them for the Gaussian at
    N=1e6, d=32, K=64), stay within ``ops.SWEEP_PARTIALS_BYTES``. Returns
    None where the kernel refuses the shape.
    """
    from repro.kernels import ops
    n = x.shape[0]
    chunk = ops.sweep_chunk_points(
        sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(acc)))

    def fold(a, partials):
        return jax.lax.scan(lambda a, p: (_add_tree(a, p), None), a,
                            partials)[0]

    if n <= chunk:
        out = run(x, valid, gidx)
        return None if out is None else (out[0], out[1], fold(acc, out[2]))
    spec = lambda a: jax.ShapeDtypeStruct((chunk,) + a.shape[1:], a.dtype)
    if jax.eval_shape(run, spec(x), spec(valid), spec(gidx)) is None:
        return None
    n_chunks, rem = divmod(n, chunk)

    def body(c, carry):
        labels, sublabels, a = carry
        start = c * chunk
        lab, sub, partials = run(*(
            jax.lax.dynamic_slice_in_dim(v, start, chunk)
            for v in (x, valid, gidx)))
        put = lambda full, part: jax.lax.dynamic_update_slice_in_dim(
            full, part, start, 0)
        return put(labels, lab), put(sublabels, sub), fold(a, partials)

    zeros = jnp.zeros((n,), jnp.int32)
    labels, sublabels, acc = jax.lax.fori_loop(0, n_chunks, body,
                                               (zeros, zeros, acc))
    if rem:
        tail = slice(n_chunks * chunk, None)
        lab, sub, partials = run(x[tail], valid[tail], gidx[tail])
        labels = labels.at[tail].set(lab)
        sublabels = sublabels.at[tail].set(sub)
        acc = fold(acc, partials)
    return labels, sublabels, acc


@dataclasses.dataclass(frozen=True)
class ComponentFamily:
    """One observation model behind the fixed sampler interface."""
    name: str
    # pytree templates (placeholder leaves) for building PartitionSpecs
    param_struct: Callable[[], Any]
    stats_struct: Callable[[], Any]
    # conjugate math (see core/niw.py for the reference semantics)
    build_prior: Callable[[Any, Any], Any]          # (cfg, x) -> prior
    empty_stats: Callable[..., Any]                 # (batch_shape, d) -> stats
    stats_from_points: Callable[[jax.Array, jax.Array], Any]
    add_stats: Callable[[Any, Any], Any]
    log_marginal: Callable[[Any, Any], jax.Array]   # (prior, stats) -> (*B,)
    sample_posterior: Callable[[jax.Array, Any, Any], Any]
    expected_params: Callable[[Any, Any], Any]
    loglik_ref: Callable[[jax.Array, Any], jax.Array]  # (x, params) -> (N,*B)
    # label-indexed suff-stats: (x, valid, labels, sublabels, k_max) ->
    # (k_max, 2)-batched sub-cluster stats (cluster stats are the sub fold,
    # core/gibbs.compute_stats). ``_ref`` is the jnp path (segment-sum /
    # one-hot einsum); ``_fast`` the Pallas kernel, returning None when the
    # problem falls outside the kernel's VMEM envelope.
    labels_stats_ref: Callable[..., Any] = None
    labels_stats_fast: Optional[Callable[..., Any]] = None
    # fused assignment (steps e/f). ``assign_pack`` expresses a linear
    # likelihood loglik(x)_b = feats @ w_b + const_b so one shared kernel
    # serves every such family; non-linear families provide dedicated
    # ``assign_fast`` / ``sub_assign_fast`` kernels instead. All return
    # None outside their guard so the caller can fall back.
    assign_pack: Optional[Callable[[jax.Array, Any], Tuple]] = None
    assign_fast: Optional[Callable[..., Optional[jax.Array]]] = None
    sub_assign_fast: Optional[Callable[..., Optional[jax.Array]]] = None
    # one-read fused sweep (steps e + f + stat fold in ONE pass over x,
    # kernels/sweep.py): returns (labels, sublabels, per-STATS_BLOCK stat
    # partials) or None outside the kernel's VMEM envelope; the ``sweep``
    # dispatch method folds the partials and falls back to ``sweep_ref``
    # (the blocked jnp scan) when absent/guarded out.
    sweep_fast: Optional[Callable[..., Optional[Tuple]]] = None
    # optional accelerated loglik (Pallas on TPU; paper §4.2 'Kernel #1/#2')
    loglik_fast: Optional[Callable[[jax.Array, Any], jax.Array]] = None
    # feature-sharding contract (DESIGN §10); shardable families' loglik and
    # stats must be sums over features so local slices psum/gather correctly
    feature_shardable: bool = False
    feature_stat_fields: Tuple[str, ...] = ()
    slice_params: Optional[Callable[[Any, Any, int], Any]] = None
    # stats field holding the first moment (sum x) — cluster means read it
    mean_field: str = "sx"

    def loglik(self, x: jax.Array, params: Any,
               use_pallas: bool = False) -> jax.Array:
        """(N, *B) point log-likelihoods; Pallas fast path when available."""
        if use_pallas and self.loglik_fast is not None:
            return self.loglik_fast(x, params)
        return self.loglik_ref(x, params)

    # -- one-read fused sweep (steps e + f + stat fold, ONE pass over x) --
    def sweep(self, x: jax.Array, valid: jax.Array, params: Any,
              subparams: Any, logw: jax.Array, sublogw: jax.Array,
              active: jax.Array, gidx: jax.Array, key_z: jax.Array,
              key_zb: jax.Array, k_max: int, acc,
              use_pallas: bool = False, feat_axis=None, slots=None,
              k_block: Optional[int] = None
              ) -> Tuple[jax.Array, jax.Array, Any]:
        """Steps (e)+(f)+suff-stat fold with x consumed exactly once.

        Dispatch: the ``sweep_fast`` megakernel (Pallas, kernels/sweep.py)
        when available and inside its VMEM envelope, else ``sweep_ref``
        (one ``lax.scan`` over STATS_BLOCK blocks running assign /
        sub_assign / stats_from_labels while the block is resident). The
        body taken is counted (``record_sweep_paths``); a ``use_pallas``
        sweep that lands on ``sweep_ref`` also warns with the reason. Both
        paths fold stat partials per STATS_BLOCK left-to-right and draw
        noise from the counter-based PRNG, so they produce the same chain
        as the pre-fusion three-pass formulation, bit for bit.

        ``params``/``logw``/... may be a COMPACT slab (K_active rows
        gathered from the dense k_max slab — core/gibbs.py's compaction);
        ``slots`` then carries the (K,) uint32 dense slot ids so the
        Gumbel counters — hence the chain — are bitwise the dense slab's.
        ``k_block`` overrides the streamed cluster-tile size of the
        megakernel. Returns ``(labels, sublabels, acc')`` with labels in
        COMPACT positions (the caller maps them back through the plan).

        ``key_z``/``key_zb``: raw (2,) uint32 key words
        (``prng.key_words``).
        """
        if use_pallas:
            if feat_axis is not None:
                why = "x is feature-sharded"
            elif self.sweep_fast is None:
                why = f"family {self.name!r} has no megakernel"
            else:
                out = fold_chunked(
                    lambda xc, vc, gc: self.sweep_fast(
                        xc, vc, params, subparams, logw, sublogw, active,
                        gc, key_z, key_zb, k_max, slots=slots,
                        k_block=k_block),
                    x, valid, gidx, acc)
                if out is not None:
                    _note_sweep_path("sweep_fast")
                    return out
                why = "the shape is outside the kernel's VMEM envelope"
            warnings.warn(
                f"use_pallas=True sweep of {self.name!r} at x{tuple(x.shape)}"
                f", K={k_max} runs the jnp reference sweep_ref: {why}",
                SweepFallbackWarning, stacklevel=2)
        _note_sweep_path("sweep_ref")
        return self.sweep_ref(x, valid, params, subparams, logw, sublogw,
                              active, gidx, key_z, key_zb, k_max, acc,
                              use_pallas=use_pallas, feat_axis=feat_axis,
                              slots=slots)

    def sweep_ref(self, x: jax.Array, valid: jax.Array, params: Any,
                  subparams: Any, logw: jax.Array, sublogw: jax.Array,
                  active: jax.Array, gidx: jax.Array, key_z: jax.Array,
                  key_zb: jax.Array, k_max: int, acc,
                  use_pallas: bool = False, feat_axis=None, slots=None
                  ) -> Tuple[jax.Array, jax.Array, Any]:
        """Blocked one-read sweep reference: e + f + stat fold per
        STATS_BLOCK block inside one scan body. Per-block math is exactly
        ``assign``/``sub_assign``/``stats_from_labels`` (counter-based
        noise, same op order), so the chain matches the three-pass body
        bitwise while x streams through the scan once. Accepts the same
        compact-slab + ``slots`` calling convention as ``sweep``."""
        def body(xb, vb, gb):
            del vb                      # assignment ignores the pad mask
            lab = self.assign(xb, params, logw, active, gb, key_z,
                              use_pallas=use_pallas, feat_axis=feat_axis,
                              slots=slots)
            sub = self.sub_assign(xb, subparams, sublogw, lab, gb, key_zb,
                                  use_pallas=use_pallas,
                                  feat_axis=feat_axis)
            return lab, sub

        return fold_blocked(self, k_max, body, x, valid, (gidx,), acc,
                            use_pallas=use_pallas)

    # -- fused sweep hot path (steps e/f + suff-stats) --------------------
    def assign(self, x: jax.Array, params: Any, logw: jax.Array,
               active: jax.Array, gidx: jax.Array, key_data: jax.Array,
               use_pallas: bool = False, feat_axis=None,
               slots=None) -> jax.Array:
        """Step (e): z_i = argmax_k [loglik + log pi_k + Gumbel] -> (N,).

        The Gumbel noise is the counter-based Threefry draw of
        kernels/prng.py keyed on (key, global index, cluster) — identical
        bits in the fused kernel and in this reference path, so both
        sample the same chain. The cluster counter is the dense-slab SLOT
        id: ``slots`` (default ``arange(K)``) lets a compacted caller pass
        the gathered ids so compact and dense slabs draw identical noise.
        With ``use_pallas`` the streaming kernel (kernels/assign.py) runs
        the whole step in VMEM tiles and the (N, K) logits/Gumbel matrices
        never exist in HBM; otherwise this reference materializes the
        (N, K) logits once (and nothing else).
        """
        if use_pallas and feat_axis is None:
            fused = self._assign_fused(x, params, logw, active, gidx,
                                       key_data, slots)
            if fused is not None:
                return fused
        ll = (self.loglik_sharded(x, params, feat_axis)
              if feat_axis is not None
              else self.loglik(x, params, use_pallas=use_pallas))
        logits = ll + logw[None, :]
        logits = jnp.where(active[None, :], logits, NEG_INF)
        cid = (jnp.arange(logw.shape[0], dtype=jnp.uint32)
               if slots is None else slots.astype(jnp.uint32))
        logits = logits + prng.gumbel(key_data, gidx[:, None], cid[None, :])
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _assign_fused(self, x, params, logw, active, gidx, key_data,
                      slots=None):
        from repro.kernels import ops
        if self.assign_fast is not None:
            return self.assign_fast(x, params, logw, active, gidx, key_data,
                                    slots)
        if self.assign_pack is not None:
            feats, w, const = self.assign_pack(x, params)
            return ops.assign_linear_pallas(feats, w, const, logw, active,
                                            gidx, key_data, slots)
        return None

    def sub_assign(self, x: jax.Array, subparams: Any, sublogw: jax.Array,
                   labels: jax.Array, gidx: jax.Array, key_data: jax.Array,
                   use_pallas: bool = False, feat_axis=None,
                   chunk: Optional[int] = None) -> jax.Array:
        """Step (f): sub-label under the point's OWN cluster only -> (N,).

        Evaluates the sub-cluster log-likelihood for 2 sub-clusters per
        point instead of all 2K — the O(N K T) -> O(N T) cut. The fused
        kernels gather the (K, 2, ...) sub-params in VMEM; this reference
        gathers them per ``chunk`` points under ``lax.map`` so the largest
        jnp intermediate is (chunk, 2, ...) — never (N, K, 2). ``chunk``
        defaults to a memory-budgeted size (all N at once when the gathered
        params are small — e.g. any linear family or a low-d Gaussian — so
        the scan and its per-step overhead disappear entirely).
        """
        if use_pallas and feat_axis is None:
            fused = self._sub_assign_fused(x, subparams, sublogw, labels,
                                           gidx, key_data)
            if fused is not None:
                return fused
        own = self._own_subloglik(x, subparams, labels, feat_axis, chunk)
        t = own + sublogw[labels]
        cid = jnp.arange(2, dtype=jnp.uint32)
        t = t + prng.gumbel(key_data, gidx[:, None], cid[None, :])
        return jnp.argmax(t, axis=-1).astype(jnp.int32)

    def _sub_assign_fused(self, x, subparams, sublogw, labels, gidx,
                          key_data):
        from repro.kernels import ops
        if self.sub_assign_fast is not None:
            return self.sub_assign_fast(x, subparams, sublogw, labels,
                                        gidx, key_data)
        if self.assign_pack is not None:
            feats, w, const = self.assign_pack(x, subparams)
            return ops.sub_assign_linear_pallas(feats, w, const, sublogw,
                                                labels, gidx, key_data)
        return None

    # cap on the gathered (chunk, 2, ...) sub-params intermediate (floats):
    # 32M floats = 128 MiB — far below the dense (N, K, 2, ...) it replaces
    _SUB_GATHER_BUDGET = 32 * 1024 * 1024

    def _own_subloglik(self, x, subparams, labels, feat_axis,
                       chunk: Optional[int]) -> jax.Array:
        """(N, 2) own-cluster sub-loglik via chunked gather (jnp path)."""
        n = x.shape[0]
        if chunk is None:
            per_point = sum(math.prod(leaf.shape[1:])
                            for leaf in jax.tree_util.tree_leaves(subparams))
            chunk = max(512, self._SUB_GATHER_BUDGET // max(per_point, 1))
        chunk = min(chunk, n)
        pad = (-n) % chunk
        xp = jnp.pad(x, ((0, pad), (0, 0)))
        lp = jnp.pad(labels, (0, pad))
        if feat_axis is not None:
            # x is a feature slice; sub-params are full-d replicated —
            # slice the gathered params to the local block and psum the
            # (N, 2) partials once at the end (O(N) wire bytes, not O(N K))
            blk = jax.lax.axis_index(feat_axis) * x.shape[1]

        def body(args):
            xc, lc = args
            pc = jax.tree.map(lambda p: p[lc], subparams)   # (chunk, 2, ..)
            if feat_axis is not None:
                pc = self.slice_params(pc, blk, x.shape[1])
            one = lambda xi, pi: self.loglik_ref(xi[None], pi)[0]
            return jax.vmap(one)(xc, pc)                     # (chunk, 2)

        if xp.shape[0] == chunk:        # one chunk: no scan wrapper at all
            out = body((xp, lp))[:n]
        else:
            out = jax.lax.map(body, (xp.reshape(-1, chunk, x.shape[1]),
                                     lp.reshape(-1, chunk)))
            out = out.reshape(-1, 2)[:n]
        if feat_axis is not None:
            out = jax.lax.psum(out, feat_axis)
        return out

    def stats_from_labels(self, x: jax.Array, valid: jax.Array,
                          labels: jax.Array, sublabels: jax.Array,
                          k_max: int, use_pallas: bool = False) -> Any:
        """(k_max, 2)-batched sub-cluster stats straight from int labels;
        cluster stats are the fold over the sub axis (gibbs.compute_stats).
        No dense (N, K, 2) responsibility tensor on either path."""
        if use_pallas and self.labels_stats_fast is not None:
            out = self.labels_stats_fast(x, valid, labels, sublabels, k_max)
            if out is not None:
                return out
        if self.labels_stats_ref is not None:
            return self.labels_stats_ref(x, valid, labels, sublabels, k_max)
        # back-compat for user families registered without a label-indexed
        # path: dense (N, 2K) one-hot through stats_from_points (all four
        # built-ins provide labels_stats_ref and never take this branch)
        seg = labels * 2 + sublabels
        r2 = (jax.nn.one_hot(seg, 2 * k_max, dtype=x.dtype)
              * valid.astype(x.dtype)[:, None])
        flat = self.stats_from_points(x, r2)
        return jax.tree.map(
            lambda a: a.reshape((k_max, 2) + a.shape[1:]), flat)

    def loglik_sharded(self, x: jax.Array, params: Any,
                       feat_axis: str) -> jax.Array:
        """Feature-sharded loglik: local params slice + psum over features.

        ``x`` holds this shard's feature block (paper's d=20,000 regime —
        the feature dim never replicates); params are full-d replicated.
        """
        self._require_shardable()
        i = jax.lax.axis_index(feat_axis)
        dl = x.shape[1]
        partial = self.loglik_ref(x, self.slice_params(params, i * dl, dl))
        return jax.lax.psum(partial, feat_axis)

    def gather_feature_stats(self, stats: Any, feat_axis: str) -> Any:
        """All-gather feature-sliced stats fields to full d (still O(K d))."""
        self._require_shardable()
        gather = lambda c: jax.lax.all_gather(c, feat_axis, axis=c.ndim - 1,
                                              tiled=True)
        return stats._replace(**{f: gather(getattr(stats, f))
                                 for f in self.feature_stat_fields})

    def cluster_means(self, stats: Any) -> jax.Array:
        """(*B, d) empirical cluster means from the first-moment field."""
        first = getattr(stats, self.mean_field)
        return first / jnp.maximum(stats.n[..., None], 1.0)

    def _require_shardable(self) -> None:
        if not self.feature_shardable:
            raise ValueError(
                f"component family {self.name!r} is not feature-separable: "
                "its likelihood/stats are not sums over independent "
                "features (e.g. the full-covariance Gaussian Mahalanobis), "
                "so shard_features is unsupported — use a shardable family "
                f"({', '.join(shardable_families())}) for the high-d path")


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, ComponentFamily] = {}


def register_family(family: ComponentFamily) -> ComponentFamily:
    if family.name in _REGISTRY:
        raise ValueError(f"component family {family.name!r} already "
                         "registered")
    if family.feature_shardable and (not family.feature_stat_fields
                                     or family.slice_params is None):
        raise ValueError(f"{family.name!r}: feature_shardable families must "
                         "set feature_stat_fields and slice_params")
    _REGISTRY[family.name] = family
    return family


def get_family(name: str) -> ComponentFamily:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown component family {name!r}; registered: "
                         f"{', '.join(available_families())}") from None


def available_families() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def shardable_families() -> Tuple[str, ...]:
    return tuple(n for n in available_families()
                 if _REGISTRY[n].feature_shardable)


def state_partition_specs(family: ComponentFamily, shard_spec: P
                          ) -> Tuple[ModelState, PointState]:
    """shard_map specs for the (ModelState, PointState) pair: per-point
    state on the data axes, everything per-cluster replicated (paper §4.3:
    only stats/params are global)."""
    rep = P()
    rep_tree = lambda struct: jax.tree.map(lambda _: rep, struct)
    model = ModelState(
        key=rep, it=rep, active=rep, logweights=rep, sub_logweights=rep,
        stuck=rep,
        params=rep_tree(family.param_struct()),
        subparams=rep_tree(family.param_struct()),
        stats=rep_tree(family.stats_struct()),
        substats=rep_tree(family.stats_struct()))
    point = PointState(labels=shard_spec, sublabels=shard_spec,
                       valid=shard_spec)
    return model, point


# ---------------------------------------------------------------------------
# Built-in families
# ---------------------------------------------------------------------------
def _module_family(mod, **kw) -> ComponentFamily:
    kw.setdefault("labels_stats_ref", mod.stats_from_labels)
    if hasattr(mod, "assign_pack"):
        kw.setdefault("assign_pack", mod.assign_pack)
    return ComponentFamily(
        param_struct=mod.param_struct, stats_struct=mod.stats_struct,
        build_prior=mod.build_prior, empty_stats=mod.empty_stats,
        stats_from_points=mod.stats_from_points, add_stats=mod.add_stats,
        log_marginal=mod.log_marginal, sample_posterior=mod.sample_posterior,
        expected_params=mod.expected_params, loglik_ref=mod.loglik, **kw)


def _slice_last(arr: jax.Array, start, size: int) -> jax.Array:
    return jax.lax.dynamic_slice_in_dim(arr, start, size, axis=-1)


def _gauss_loglik_fast(x: jax.Array, params) -> jax.Array:
    # Pallas whitening-matmul kernel; sub-cluster params (K, 2, ...) fall
    # back to the jnp path (the kernel grid is 2-D over clusters)
    if params.mu.ndim != 2:
        return niw.loglik(x, params)
    from repro.kernels import ops
    return ops.gauss_loglik(x, params, True)


def _diag_gauss_loglik_fast(x: jax.Array, params) -> jax.Array:
    if params.mu.ndim != 2:
        return diag_gaussian.loglik(x, params)
    from repro.kernels import ops
    return ops.diag_gauss_loglik(x, params, True)


def _gauss_assign_fast(x, params, logw, active, gidx, key_data, slots=None):
    if params.mu.ndim != 2:
        return None
    from repro.kernels import ops
    return ops.assign_gauss_pallas(x, params.mu, params.chol_prec,
                                   params.logdet_prec, logw, active, gidx,
                                   key_data, slots)


def _gauss_sub_assign_fast(x, subparams, sublogw, labels, gidx, key_data):
    if subparams.mu.ndim != 3:                        # expect (K, 2, d)
        return None
    from repro.kernels import ops
    return ops.sub_assign_gauss_pallas(x, subparams.mu, subparams.chol_prec,
                                       subparams.logdet_prec, sublogw,
                                       labels, gidx, key_data)


def _gauss_labels_stats_fast(x, valid, labels, sublabels, k_max):
    from repro.kernels import ops
    out = ops.suffstats_labels_pallas(x, labels, sublabels, valid, k_max)
    return None if out is None else niw.GaussStats(*out)


def _linear_sweep_fast(mod):
    """One-read megakernel hook for linear-likelihood families: the
    module's ``sweep_pack`` builds the shared feature block once; its
    ``stats_from_moments`` unpacks the folded (nsb, K, 2, d') moment
    partials into the family's stats pytree."""
    def hook(x, valid, params, subparams, logw, sublogw, active, gidx,
             key_z, key_zb, k_max, slots=None, k_block=None):
        from repro.kernels import ops
        feats, w, const, subw, subconst = mod.sweep_pack(x, params,
                                                         subparams)
        out = ops.sweep_linear_pallas(feats, w, const, logw, active, subw,
                                      subconst, sublogw, valid, gidx,
                                      key_z, key_zb, slots,
                                      k_block=k_block or ops.K_BLOCK)
        if out is None:
            return None
        labels, sublabels, n2, sf2 = out
        return labels, sublabels, mod.stats_from_moments(n2, sf2)
    return hook


def _gauss_sweep_fast(x, valid, params, subparams, logw, sublogw, active,
                      gidx, key_z, key_zb, k_max, slots=None, k_block=None):
    if params.mu.ndim != 2 or subparams.mu.ndim != 3:
        return None
    from repro.kernels import ops
    mu, f, ld, smu, sf, sld = niw.sweep_pack(params, subparams)
    out = ops.sweep_gauss_pallas(x, mu, f, ld, logw, active, smu, sf, sld,
                                 sublogw, valid, gidx, key_z, key_zb, slots,
                                 k_block=k_block or ops.K_BLOCK)
    if out is None:
        return None
    labels, sublabels, n2, sx2, sxx2 = out
    return labels, sublabels, niw.stats_from_moments(n2, sx2, sxx2)


def _moments_labels_fast(feats, valid, labels, sublabels, k_max):
    from repro.kernels import ops
    return ops.moments_labels_pallas(feats, labels, sublabels, valid, k_max)


def _mult_labels_stats_fast(x, valid, labels, sublabels, k_max):
    out = _moments_labels_fast(x, valid, labels, sublabels, k_max)
    return None if out is None else multinomial.MultStats(n=out[0],
                                                          counts=out[1])


def _pois_labels_stats_fast(x, valid, labels, sublabels, k_max):
    out = _moments_labels_fast(x, valid, labels, sublabels, k_max)
    return None if out is None else poisson.PoisStats(n=out[0], sx=out[1])


def _diag_labels_stats_fast(x, valid, labels, sublabels, k_max):
    out = _moments_labels_fast(jnp.concatenate([x, x * x], axis=-1),
                               valid, labels, sublabels, k_max)
    if out is None:
        return None
    d = x.shape[-1]
    return diag_gaussian.DiagStats(n=out[0], sx=out[1][..., :d],
                                   sxx=out[1][..., d:])


GAUSSIAN = register_family(_module_family(
    niw, name="gaussian", loglik_fast=_gauss_loglik_fast,
    assign_fast=_gauss_assign_fast, sub_assign_fast=_gauss_sub_assign_fast,
    labels_stats_fast=_gauss_labels_stats_fast,
    sweep_fast=_gauss_sweep_fast,
    feature_shardable=False, mean_field="sx"))

MULTINOMIAL = register_family(_module_family(
    multinomial, name="multinomial",
    labels_stats_fast=_mult_labels_stats_fast,
    sweep_fast=_linear_sweep_fast(multinomial),
    feature_shardable=True, feature_stat_fields=("counts",),
    slice_params=lambda p, s, n: multinomial.MultParams(
        logtheta=_slice_last(p.logtheta, s, n)),
    mean_field="counts"))

POISSON = register_family(_module_family(
    poisson, name="poisson",
    labels_stats_fast=_pois_labels_stats_fast,
    sweep_fast=_linear_sweep_fast(poisson),
    feature_shardable=True, feature_stat_fields=("sx",),
    slice_params=lambda p, s, n: poisson.PoisParams(
        log_rate=_slice_last(p.log_rate, s, n)),
    mean_field="sx"))

DIAG_GAUSSIAN = register_family(_module_family(
    diag_gaussian, name="diag_gaussian",
    loglik_fast=_diag_gauss_loglik_fast,
    labels_stats_fast=_diag_labels_stats_fast,
    sweep_fast=_linear_sweep_fast(diag_gaussian),
    feature_shardable=True, feature_stat_fields=("sx", "sxx"),
    slice_params=lambda p, s, n: diag_gaussian.DiagParams(
        mu=_slice_last(p.mu, s, n), log_prec=_slice_last(p.log_prec, s, n)),
    mean_field="sx"))
