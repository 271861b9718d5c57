"""Coordinator for elastic multi-process distributed sampling.

The third fit driver (``DPMMConfig.workers=N``; dispatched from
``DPMM.fit``): one coordinator process owns ModelState and every O(K)
step — ``sweep_model``, the split/merge plan, ``finalize_substats``,
guardrails, auto-checkpointing — while N spawned worker processes each
own a contiguous, STATS_BLOCK-aligned row range of x behind the
``DataSource`` protocol and run the per-point tile bodies
(repro.dist.worker) on it.

**The bitwise-fold contract.** The single-process tiled driver folds
suff-stats strictly left-to-right over STATS_BLOCK blocks in global
point order, with the accumulator carried across tiles. Workers
therefore ship their substat partials *per block, unfolded*, and the
coordinator replays ``acc += p_block`` here, in fixed global block
order, on the host (same-width IEEE f32 adds — bit-identical to the
device fold). Two consequences, both load-bearing:

 1. the distributed chain is **bitwise identical** to the
    single-process tiled fit (pinned to a 1-device mesh, where the
    cross-shard psum is a no-op and the fold is fully sequential) at
    ANY worker count — worker count is a pure wall-clock knob;
 2. failover is bitwise-neutral by construction: any worker recomputes
    any block to the same bits (per-point randomness is counter-based
    on the global index; ModelState is broadcast losslessly via the
    checkpoint codec), so reassigning a dead worker's range changes
    nothing but wall clock.

**The failure model.** Workers heartbeat every ``worker_heartbeat_s``.
Per WORK item the coordinator arms a ``worker_deadline_s`` deadline.
A dead worker (SIGKILL, crash) surfaces as EOF/heartbeat loss on its
reader thread; a *hung* worker (wedged read, livelock) keeps
heartbeating but misses its deadline and is killed. Either way the
range is requeued to survivors, a ``worker_failover`` event is logged
into ``FitResult.recoveries``, and the slot is respawned (with
``RetryPolicy`` backoff) at most ``cfg.max_worker_retries`` times.
:class:`WorkerLostError` is raised only when work is pending, no worker
survives, and every respawn budget is spent. Shards are stateless —
labels recompute each sweep, ModelState lives here — so recovery needs
no worker-side state at all.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import queue
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.dist import proto

# Bound on spawn -> HELLO -> INIT -> warmup -> READY (covers a cold jax
# import plus every per-phase XLA compile on a loaded CI container; work
# deadlines stay tight because warmup pre-compiles the tile bodies).
READY_TIMEOUT_S = 600.0


class _HandshakeError(RuntimeError):
    """A worker failed to come up (died pre-HELLO, bad id, no READY)."""


@dataclasses.dataclass
class DistHooks:
    """Chaos/observability hooks for tests and benchmarks.

    ``worker_faults`` maps worker slot -> ``FaultInjectingSource``
    kwargs applied to that worker's shard view (respawns inherit them —
    a persistently faulty shard stays faulty). ``on_iteration`` runs on
    the coordinator at the top of every iteration with
    ``(absolute_iter, coordinator)`` — e.g. to SIGKILL a worker pid
    mid-fit."""
    worker_faults: Optional[Dict[int, dict]] = None
    on_iteration: Optional[Callable[[int, "Coordinator"], None]] = None


class _Worker:
    """Slot-side view of one worker process."""

    def __init__(self, slot: int):
        self.slot = slot
        self.proc: Optional[subprocess.Popen] = None
        self.conn: Optional[socket.socket] = None
        self.reader: Optional[threading.Thread] = None
        self.alive = False
        self.item: Optional[Tuple[int, int, int]] = None
        self.deadline: Optional[float] = None
        self.last_seen = 0.0
        self.respawns = 0
        # incarnation counter: bumped on every (re)connect. Reader-thread
        # messages carry the epoch they were read under, so anything a
        # dead incarnation left in the inbox (a buffered result, its own
        # EOF marker) cannot be misattributed to a respawned successor.
        self.epoch = 0

    @property
    def id(self) -> str:
        return f"w{self.slot}"


def shard_ranges(n: int, workers: int, stats_block: int
                 ) -> List[Tuple[int, int, int]]:
    """Static contiguous row ranges, one per worker slot, cut on the
    suff-stat block grid so every block is computed whole by exactly one
    worker: ``[(lo, hi, preferred_slot), ...]`` sorted by ``lo`` (the
    global fold order). Extra workers (more slots than blocks) get no
    range and serve purely as failover capacity."""
    nb = -(-n // stats_block)
    per = -(-nb // workers)
    ranges = []
    for w in range(workers):
        lo = min(w * per * stats_block, n)
        hi = min((w + 1) * per * stats_block, n)
        if lo < hi:
            ranges.append((lo, hi, w))
    return ranges


class Coordinator:
    """Worker-pool plumbing: spawn/handshake, scatter/gather with
    deadlines, failover, bounded respawn. The sampling logic lives in
    :func:`fit_distributed`."""

    def __init__(self, cfg, init_meta: dict, events: List[dict],
                 hooks: Optional[DistHooks] = None):
        self.cfg = cfg
        self.events = events
        self.hooks = hooks or DistHooks()
        self._init_meta = init_meta
        self._inbox: "queue.Queue" = queue.Queue()
        self._cur_phase: Optional[Tuple[dict, dict]] = None
        self.respawns_done = 0
        self.reassignments = 0
        # liveness window on the reader socket: several heartbeats must
        # go missing before an *idle* worker is declared dead
        self._liveness_s = max(10 * cfg.worker_heartbeat_s, 5.0)
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind(("127.0.0.1", 0))
        self._listener.listen(max(cfg.workers * 2, 8))
        self._port = self._listener.getsockname()[1]
        self.workers = [_Worker(s) for s in range(cfg.workers)]
        deadline = time.monotonic() + READY_TIMEOUT_S
        for w in self.workers:
            self._spawn(w)
        # accept in arrival order (workers import jax / warm up in
        # parallel), then confirm READY per slot
        todo = {w.id: w for w in self.workers}
        while todo:
            conn, wid = self._accept_hello(deadline)
            w = todo.pop(wid, None)
            if w is None:
                conn.close()
                continue
            w.conn = conn
            proto.send_msg(conn, "init", self._slot_init_meta(w.slot))
        for w in self.workers:
            self._wait_ready(w, deadline)
            self._online(w)

    # -- spawn / handshake --------------------------------------------------
    def worker_pids(self) -> List[Optional[int]]:
        return [w.proc.pid if w.proc is not None else None
                for w in self.workers]

    def _slot_init_meta(self, slot: int) -> dict:
        meta = dict(self._init_meta)
        faults = (self.hooks.worker_faults or {}).get(slot)
        if faults:
            meta["faults"] = faults
        return meta

    def _spawn(self, w: _Worker) -> None:
        import repro
        env = os.environ.copy()
        # repro is a namespace package (__file__ is None): resolve the
        # import root from __path__ so spawned workers find the same tree
        pkg_root = os.path.dirname(os.path.abspath(
            list(repro.__path__)[0]))
        env["PYTHONPATH"] = (pkg_root + os.pathsep
                             + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
        w.proc = subprocess.Popen(
            [sys.executable, "-m", "repro.dist.worker",
             "--connect", f"127.0.0.1:{self._port}", "--id", w.id],
            env=env)

    def _accept_hello(self, deadline: float) -> Tuple[socket.socket, str]:
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise _HandshakeError("timed out waiting for a worker "
                                      "to connect")
            self._listener.settimeout(min(remaining, 5.0))
            try:
                conn, _addr = self._listener.accept()
            except socket.timeout:
                dead = [w.id for w in self.workers
                        if w.conn is None and w.proc is not None
                        and w.proc.poll() is not None]
                if dead:
                    raise _HandshakeError(
                        f"worker(s) {dead} exited before connecting "
                        "(startup crash)")
                continue
            conn.settimeout(self._liveness_s)
            try:
                kind, meta, _ = proto.recv_msg(conn)
            except (proto.ProtocolError, OSError):
                conn.close()
                continue
            if kind != "hello" or "id" not in meta:
                conn.close()
                continue
            return conn, str(meta["id"])

    def _wait_ready(self, w: _Worker, deadline: float) -> None:
        """Drain heartbeats until READY (warmup runs worker-side)."""
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise _HandshakeError(f"worker {w.id} never became ready")
            w.conn.settimeout(min(remaining, self._liveness_s))
            try:
                kind, meta, _ = proto.recv_msg(w.conn)
            except (proto.ProtocolError, OSError) as e:
                raise _HandshakeError(
                    f"worker {w.id} lost during startup "
                    f"({type(e).__name__}: {e})")
            if kind == "ready":
                return
            if kind == "error":
                raise _HandshakeError(
                    f"worker {w.id} failed during startup: "
                    f"{meta.get('detail', '')}")
            # heartbeats (and anything else) just keep the clock alive

    def _online(self, w: _Worker) -> None:
        w.conn.settimeout(self._liveness_s)
        w.last_seen = time.monotonic()
        w.alive = True
        w.epoch += 1
        w.reader = threading.Thread(target=self._reader,
                                    args=(w, w.conn, w.epoch),
                                    daemon=True)
        w.reader.start()

    def _reader(self, w: _Worker, conn: socket.socket, epoch: int) -> None:
        try:
            while True:
                kind, meta, arrays = proto.recv_msg(conn)
                if epoch == w.epoch:
                    w.last_seen = time.monotonic()
                if kind == "heartbeat":
                    continue
                self._inbox.put((w, epoch, kind, meta, arrays))
        except (proto.ProtocolError, OSError) as e:
            self._inbox.put((w, epoch, "__down__",
                             {"detail": f"{type(e).__name__}: {e}"}, {}))

    def _send(self, w: _Worker, kind: str, meta: Optional[dict] = None,
              arrays: Optional[dict] = None) -> bool:
        try:
            proto.send_msg(w.conn, kind, meta, arrays)
            return True
        except (OSError, proto.ProtocolError):
            return False

    # -- failover -----------------------------------------------------------
    def _lost(self, w: _Worker, detail: str,
              pending: Optional[List] = None) -> None:
        """Declare ``w`` lost: kill the process, requeue its work item,
        log the ``worker_failover`` event, and respawn within budget
        (RetryPolicy backoff). Idempotent per incarnation."""
        if not w.alive:
            return
        from repro.core.resilience import RetryPolicy
        w.alive = False
        item, w.item, w.deadline = w.item, None, None
        if w.proc is not None and w.proc.poll() is None:
            w.proc.kill()               # hung or half-dead: no niceties
        try:
            w.conn.close()
        except OSError:
            pass
        if item is not None and pending is not None:
            pending.append(item)
            self.reassignments += 1
        phase_meta = self._cur_phase[0] if self._cur_phase else {}
        will_respawn = w.respawns < self.cfg.max_worker_retries
        self.events.append({
            "kind": "worker_failover", "worker": w.slot,
            "iter": phase_meta.get("iter"),
            "phase": phase_meta.get("phase"),
            "rows": [int(item[0]), int(item[1])] if item else None,
            "respawn": will_respawn, "detail": detail})
        policy = RetryPolicy(max_retries=self.cfg.max_worker_retries,
                             backoff_s=self.cfg.io_backoff_s)
        t_stall = time.monotonic()
        while w.respawns < policy.max_retries:
            w.respawns += 1
            delay = policy.backoff_s * policy.backoff_mult ** (
                w.respawns - 1)
            if delay > 0:
                time.sleep(delay)
            try:
                self._respawn(w)
                self.respawns_done += 1
                break
            except _HandshakeError as e:
                self.events.append({
                    "kind": "worker_failover", "worker": w.slot,
                    "iter": phase_meta.get("iter"),
                    "phase": phase_meta.get("phase"), "rows": None,
                    "respawn": w.respawns < policy.max_retries,
                    "detail": f"respawn attempt {w.respawns} failed: {e}"})
        # else: budget spent — the slot stays dead; survivors absorb it.
        # The respawn handshake blocked the gather loop (worker warmup),
        # so credit the stall to every other in-flight deadline: those
        # workers' *compute* budget must not shrink because a peer died.
        stall = time.monotonic() - t_stall
        for o in self.workers:
            if o.alive and o.deadline is not None:
                o.deadline += stall

    def _respawn(self, w: _Worker) -> None:
        self._spawn(w)
        deadline = time.monotonic() + READY_TIMEOUT_S
        conn, wid = self._accept_hello(deadline)
        if wid != w.id:
            conn.close()
            raise _HandshakeError(
                f"respawned worker announced id {wid!r}, want {w.id!r}")
        w.conn = conn
        proto.send_msg(conn, "init", self._slot_init_meta(w.slot))
        self._wait_ready(w, deadline)
        if self._cur_phase is not None:
            proto.send_msg(conn, "phase", *self._cur_phase)
        self._online(w)

    # -- phase scatter/gather -----------------------------------------------
    def set_phase(self, meta: dict, arrays: dict) -> None:
        self._cur_phase = (meta, arrays)
        for w in self.workers:
            if w.alive and not self._send(w, "phase", meta, arrays):
                self._lost(w, "phase broadcast failed (connection lost)")

    def run_phase(self, meta: dict, arrays: dict,
                  items: List[Tuple[int, int, int]],
                  item_arrays: Optional[Callable[[int, int], dict]] = None
                  ) -> Dict[int, Tuple[dict, dict]]:
        """Broadcast the phase, scatter one WORK per row range, gather
        RESULTs with deadline/failover handling; returns ``{lo: (meta,
        arrays)}`` for every item. Raises :class:`WorkerLostError` when
        work remains and no worker can take it."""
        from repro.core.resilience import WorkerLostError
        self.set_phase(meta, arrays)
        pending = list(items)
        results: Dict[int, Tuple[dict, dict]] = {}
        while len(results) < len(items):
            self._assign(pending, item_arrays)
            if (len(results) < len(items)
                    and not any(w.alive for w in self.workers)):
                raise WorkerLostError(
                    f"distributed {meta.get('phase')} pass stalled: "
                    f"{len(items) - len(results)} row range(s) "
                    "unprocessed, no live workers, and every "
                    f"max_worker_retries={self.cfg.max_worker_retries} "
                    "respawn budget is spent. See .recoveries for the "
                    "failover log.", self.events)
            try:
                w, epoch, kind, m, arrs = self._inbox.get(timeout=0.05)
            except queue.Empty:
                pass
            else:
                if not w.alive or epoch != w.epoch:
                    pass            # stale message from a dead incarnation
                elif kind == "result":
                    if w.item is not None and int(m["lo"]) == w.item[0]:
                        results[int(m["lo"])] = (m, arrs)
                        w.item, w.deadline = None, None
                elif kind == "error":
                    self._lost(w, f"worker error: {m.get('detail', '')}",
                               pending)
                elif kind == "__down__":
                    self._lost(w, m.get("detail", "connection lost"),
                               pending)
            now = time.monotonic()
            for w in self.workers:
                if not w.alive:
                    continue
                if w.item is not None and now > w.deadline:
                    self._lost(w, f"work deadline "
                                  f"({self.cfg.worker_deadline_s}s) missed "
                                  f"for rows [{w.item[0]}, {w.item[1]}) — "
                                  "worker hung", pending)
                elif (w.item is None
                      and now - w.last_seen > self._liveness_s):
                    self._lost(w, "heartbeat lost while idle", pending)
        return results

    def _assign(self, pending: List,
                item_arrays: Optional[Callable[[int, int], dict]]) -> None:
        for w in self.workers:
            if not pending:
                return
            if not w.alive or w.item is not None:
                continue
            idx = next((i for i, it in enumerate(pending)
                        if it[2] == w.slot), 0)
            item = pending.pop(idx)
            lo, hi, _pref = item
            arrs = item_arrays(lo, hi) if item_arrays else {}
            if self._send(w, "work", {"lo": int(lo), "hi": int(hi)}, arrs):
                w.item = item
                w.deadline = time.monotonic() + self.cfg.worker_deadline_s
            else:
                pending.append(item)
                self._lost(w, "work send failed (connection lost)",
                           pending)

    # -- teardown -----------------------------------------------------------
    def shutdown(self) -> None:
        for w in self.workers:
            if w.conn is not None:
                try:
                    proto.send_msg(w.conn, "shutdown")
                except (OSError, proto.ProtocolError):
                    pass
        for w in self.workers:
            if w.proc is not None:
                try:
                    w.proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    w.proc.kill()
                    w.proc.wait()
            if w.conn is not None:
                try:
                    w.conn.close()
                except OSError:
                    pass
            w.alive = False
        self._listener.close()


# ---------------------------------------------------------------------------
# The distributed fit driver (called from DPMM.fit via cfg.workers)
# ---------------------------------------------------------------------------
def _materialize(source) -> Tuple[str, Optional[str]]:
    """Resolve the .npy file workers will memmap: the source's own
    backing file when it has one, else a temp dump (returned as the
    cleanup path). Fault-injecting wrappers are unwrapped — worker-side
    faults are injected via DistHooks, not smuggled through the dump."""
    from repro.data.faults import FaultInjectingSource
    src = source
    while isinstance(src, FaultInjectingSource):
        src = src._inner
    backing = getattr(src, "_x", None)
    fname = getattr(backing, "filename", None)
    if fname and str(fname).endswith(".npy"):
        return str(fname), None
    x = src.resident()
    if x is None:
        x = np.concatenate([src.read_block(s, min(s + 65_536, src.n))
                            for s in range(0, src.n, 65_536)], axis=0)
    fd, path = tempfile.mkstemp(suffix=".npy", prefix="dpmm-dist-")
    os.close(fd)
    np.save(path, np.ascontiguousarray(
        np.asarray(x, np.float32)))
    return path, path


class ChipHeldError(RuntimeError):
    """``cfg.workers`` asked for worker processes on a TPU backend."""


def fit_distributed(dpmm, source, iters: int, verbose: bool, *,
                    key=None, init_state=None,
                    hooks: Optional[DistHooks] = None):
    """Mirror of ``DPMM._fit_tiled``'s model-side loop with the tile
    streams replaced by coordinator phases. See the module docstring for
    the bitwise and failure contracts."""
    import jax
    import jax.numpy as jnp

    if jax.default_backend() == "tpu":
        # a TPU belongs to one process: this coordinator already holds it,
        # so every JAX-importing worker would fail or hang waiting for it
        raise ChipHeldError(
            "cfg.workers starts worker processes that each need the "
            "accelerator, but this process already holds the TPU. To use "
            "several chips, leave cfg.workers unset and pass an "
            "in-process shard_map mesh: DPMM(cfg, "
            "mesh=make_data_mesh(jax.device_count())).")
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import checkpoint, gibbs, splitmerge
    from repro.core.distributed import (data_axes_of, make_data_mesh,
                                        n_data_shards)
    from repro.core.family import state_partition_specs
    from repro.core.sampler import (_Recovery, _copy_state, _init_model,
                                    _k_compact, _move_key, _peak_fields,
                                    _recovery_rekey, _rss_peak_bytes,
                                    _summaries, _tree_bytes, model_health)

    cfg = dpmm.cfg
    family = dpmm.family
    if dpmm.mesh is not None and n_data_shards(dpmm.mesh) > 1:
        raise ValueError(
            "cfg.workers does not compose with a multi-device local mesh "
            "yet: worker shards replace local data sharding (the "
            "distributed fold is pinned to the 1-device layout)")
    SB = gibbs.STATS_BLOCK
    mesh = make_data_mesh(1)
    axes = data_axes_of(mesh)
    n, d = source.n, source.d
    if n >= 2 ** 32:
        raise ValueError(
            f"N={n} exceeds the uint32 global point-index space: "
            "counter-based draws would wrap and silently corrupt the "
            "chain")
    k_max = cfg.k_max
    prior = family.build_prior(cfg, source.column_mean()[None, :])
    rec = _Recovery(cfg, family.name, 0)
    rss0 = _rss_peak_bytes()
    if key is None:
        key = jax.random.key(cfg.seed)

    # ---- coordinator-side jitted constructions (identical jaxprs to
    # _fit_tiled at shards=1, n_chains=1 — same executables, same bits) --
    model_specs, _ = state_partition_specs(family, P(axes))
    rep = P()
    acc_shape = jax.eval_shape(
        lambda: gibbs.empty_substats(family, k_max, d))
    acc_specs = type(acc_shape)(**{
        f: P(*([axes] + [None] * getattr(acc_shape, f).ndim))
        for f in acc_shape._fields})
    acc_shardings = type(acc_shape)(**{
        f: NamedSharding(mesh, getattr(acc_specs, f))
        for f in acc_shape._fields})
    local = lambda acc: jax.tree.map(lambda v: v[0], acc)
    smap = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
    finalize_fn = jax.jit(smap(
        lambda acc: gibbs.finalize_substats(family, local(acc), axes,
                                            None),
        in_specs=(acc_specs,), out_specs=(rep, rep)))
    sweep_model_fn = jax.jit(functools.partial(
        gibbs.sweep_model, prior=prior, family=family, alpha=cfg.alpha))
    plan_fn = jax.jit(lambda m: splitmerge.plan_split_merge(
        _move_key(m), m, prior, family, cfg.alpha, cfg.subreset_every))
    advance_fn = jax.jit(
        lambda m: (m._replace(it=m.it + 1),
                   _summaries(m, prior, family, cfg.alpha)))
    set_stats_fn = jax.jit(
        lambda m, s, ss: m._replace(stats=s, substats=ss))
    apply_plan_fn = jax.jit(
        lambda m, plan, s, ss: m._replace(
            active=plan.merge.new_active, stuck=plan.stuck,
            stats=s, substats=ss))
    set_stats_comp_fn = jax.jit(
        lambda m, c, s, ss: m._replace(
            stats=gibbs.compact_scatter(c, k_max, s),
            substats=gibbs.compact_scatter(c, k_max, ss)))
    apply_plan_comp_fn = jax.jit(
        lambda m, plan, c, s, ss: m._replace(
            active=plan.merge.new_active, stuck=plan.stuck,
            stats=gibbs.compact_scatter(c, k_max, s),
            substats=gibbs.compact_scatter(c, k_max, ss)))
    comp_fns: Dict[int, Any] = {}

    def compact_plan_fn(k_c: int):
        if k_c not in comp_fns:
            comp_fns[k_c] = jax.jit(
                lambda act: gibbs.compaction_plan(act, k_c))
        return comp_fns[k_c]

    @functools.lru_cache(maxsize=None)
    def acc_template(k: int):
        shape_k = jax.eval_shape(
            lambda: gibbs.empty_substats(family, k, d))
        return [(getattr(shape_k, f).shape,
                 np.dtype(getattr(shape_k, f).dtype))
                for f in shape_k._fields], type(shape_k)

    # ---- shard layout + worker pool -----------------------------------
    it0 = int(jax.device_get(init_state.it)) if init_state is not None \
        else 0
    if init_state is not None:
        k0 = int(np.asarray(jax.device_get(init_state.active)).sum())
    else:
        k0 = cfg.init_clusters
    warm_k = {"sweep_k": [], "sm_k": [],
              "init": init_state is None,
              "sm": it0 + iters > cfg.burnout}
    if cfg.compact:
        kc = _k_compact(k0, 1, k_max, cfg.k_block)
        if kc is not None:
            warm_k["sweep_k"].append(int(kc))
        kc = _k_compact(k0, 2, k_max, cfg.k_block)
        if kc is not None:
            warm_k["sm_k"].append(int(kc))
    ranges = shard_ranges(n, cfg.workers, SB)
    data_path, tmp_path = _materialize(source)
    init_meta = {"cfg": dataclasses.asdict(cfg), "data_path": data_path,
                 "heartbeat_s": cfg.worker_heartbeat_s, "warm": warm_k}
    labels_h = np.zeros(n, np.int32)
    sublabels_h = np.zeros(n, np.int32)
    coord = Coordinator(cfg, init_meta, rec.events, hooks)

    def run_pass(phase: str, k_c: Optional[int], phase_arrays: dict,
                 need_labels: bool, iter_tag: int):
        """One scatter/gather pass + the host-side bitwise fold replay;
        returns ``finalize_fn``'s (stats, substats)."""
        meta = {"phase": phase, "iter": int(iter_tag),
                "k_c": None if k_c is None else int(k_c)}
        item_arrays = ((lambda lo, hi: {"labels": labels_h[lo:hi],
                                        "sublabels": sublabels_h[lo:hi]})
                       if need_labels else None)
        results = coord.run_phase(meta, phase_arrays, ranges, item_arrays)
        k_eff = k_max if k_c is None else k_c
        leaf_shapes, acc_type = acc_template(k_eff)
        acc_leaves = [np.zeros(shape, dtype)
                      for shape, dtype in leaf_shapes]
        for lo, hi, _pref in ranges:          # sorted: global fold order
            m, arrs = results[lo]
            labels_h[lo:hi] = arrs["labels"]
            sublabels_h[lo:hi] = arrs["sublabels"]
            for e in m.get("io_events", []):
                rec.events.append(dict(e, worker=m.get("worker")))
            nb = -(-(hi - lo) // SB)
            for i, (shape, _dt) in enumerate(leaf_shapes):
                part = arrs.get(f"p{i}")
                if part is None or part.shape != (nb,) + shape:
                    raise proto.ProtocolError(
                        f"worker partial p{i} for rows [{lo}, {hi}) has "
                        f"shape {None if part is None else part.shape}, "
                        f"want {(nb,) + shape} — shard out of sync")
            # the replayed fold: += in global block order, host-side
            # same-dtype IEEE adds — bit-identical to the device fold
            for b in range(nb):
                for i in range(len(acc_leaves)):
                    np.add(acc_leaves[i], arrs[f"p{i}"][b],
                           out=acc_leaves[i])
        acc = acc_type(**{
            f: leaf[None] for f, leaf in zip(acc_type._fields, acc_leaves)})
        return finalize_fn(jax.device_put(acc, acc_shardings))

    try:
        # ---- init / resume -------------------------------------------
        if init_state is not None:
            model = jax.device_put(_copy_state(init_state),
                                   NamedSharding(mesh, P()))
        else:
            stats0, _ = run_pass("init1", None, {}, False, it0)
            means0 = jax.jit(family.cluster_means)(stats0)
            v0 = jax.jit(lambda k: splitmerge.hyperplane_vecs(
                jax.random.fold_in(k, 1), k_max, d, jnp.float32))(key)
            stats, substats = run_pass(
                "init2", None, {"means0": np.asarray(means0),
                                "v0": np.asarray(v0)}, True, it0)
            model = jax.jit(lambda k, s, ss: _init_model(
                k, s, ss, prior=prior, family=family, cfg=cfg,
                k_max=k_max))(key, stats, substats)

        rec._last_saved = it0
        est_peak = 2 * _tree_bytes(model) + sum(
            int(np.prod(s)) * dt.itemsize
            for s, dt in acc_template(k_max)[0])
        health_fn = jax.jit(model_health) if cfg.guardrails else None
        snap = (jax.tree.map(jnp.copy, model), 0) if cfg.guardrails \
            else None
        hist_rows: List[Dict[str, np.ndarray]] = []
        times: List[float] = []
        it = 0
        while it < iters:
            t0 = time.perf_counter()
            if coord.hooks.on_iteration is not None:
                coord.hooks.on_iteration(it0 + it, coord)
            model = sweep_model_fn(model)
            k_c = (_k_compact(k0, 1, k_max, cfg.k_block)
                   if cfg.compact else None)
            model_blob = np.frombuffer(
                checkpoint.dumps_model(model, family.name), np.uint8)
            if k_c is None:
                stats_ss = run_pass("sweep", None, {"model": model_blob},
                                    False, it0 + it)
                model = set_stats_fn(model, *stats_ss)
            else:
                comp = compact_plan_fn(k_c)(model.active)
                stats_ss = run_pass(
                    "sweep", k_c,
                    {"model": model_blob,
                     "comp0": np.asarray(comp.slot_of_compact),
                     "comp1": np.asarray(comp.compact_of_slot)},
                    False, it0 + it)
                model = set_stats_comp_fn(model, comp, *stats_ss)
            if it0 + it >= cfg.burnout:
                plan = plan_fn(model)
                plan_arrays = proto.pack_tree(plan, "plan")
                k_c_sm = (_k_compact(k0, 2, k_max, cfg.k_block)
                          if cfg.compact else None)
                if k_c_sm is None:
                    stats_ss = run_pass("sm", None, plan_arrays, True,
                                        it0 + it)
                    model = apply_plan_fn(model, plan, *stats_ss)
                else:
                    comp = compact_plan_fn(k_c_sm)(plan.merge.new_active)
                    stats_ss = run_pass(
                        "sm", k_c_sm,
                        dict(plan_arrays,
                             comp0=np.asarray(comp.slot_of_compact),
                             comp1=np.asarray(comp.compact_of_slot)),
                        True, it0 + it)
                    model = apply_plan_comp_fn(model, plan, comp,
                                               *stats_ss)
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
                             "distributed iteration")
                model = _recovery_rekey(
                    jax.tree.map(jnp.copy, snap_model), rec.n_rollbacks)
                it = snap_it
                k0 = int(np.asarray(
                    jax.device_get(snap_model.active)).sum())
                continue
            k0 = int(np.max(np.asarray(summary["k"])))
            hist_rows.append(summary)
            times.append(time.perf_counter() - t0)
            it += 1
            if cfg.guardrails:
                snap = (jax.tree.map(jnp.copy, model), it)
            rec.maybe_checkpoint(model, it0 + it)
            if verbose:
                print(f"iter {it0 + it:4d}  K={summary['k']}  "
                      f"{times[-1] * 1e3:.1f} ms/iter  "
                      f"[{sum(1 for w in coord.workers if w.alive)}"
                      f"/{cfg.workers} workers]")
        rec.maybe_checkpoint(model, it0 + it, force=True)
    finally:
        coord.shutdown()
        if tmp_path is not None:
            try:
                os.unlink(tmp_path)
            except OSError:
                pass

    from repro.core.sampler import _HIST_KEYS
    history = {
        k: np.asarray([row[k] for row in hist_rows])
        for k in _HIST_KEYS} if hist_rows else {
        k: np.zeros((0,)) for k in _HIST_KEYS}
    device_bytes = {
        "mode": "distributed",
        "workers": cfg.workers,
        "est_peak_bytes": int(est_peak),
        **_peak_fields(rss0),
    }
    result = dpmm._result(model, labels_h.copy(), history, times,
                          device_bytes, 1, rec.events)
    result.dist = {
        "workers": cfg.workers,
        "shard_ranges": [[int(lo), int(hi)] for lo, hi, _ in ranges],
        "respawns": coord.respawns_done,
        "reassignments": coord.reassignments,
    }
    return result
