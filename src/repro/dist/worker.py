"""Worker shard process for the elastic multi-process sampler.

A worker owns nothing but a row-range view of x behind the existing
``DataSource`` protocol (memmap via ``HostTiledSource.from_npy``) and a
socket to the coordinator. It is **stateless by design**: ModelState
lives on the coordinator, per-point labels are recomputed every sweep,
and each WORK message names an explicit row range — so a SIGKILL'd
worker's range can be re-streamed by any survivor (or a respawn) with a
bitwise-identical result.

Per WORK message the worker streams its range in STATS_BLOCK-aligned
read chunks (through ``read_block_checked``, so transient I/O faults
retry locally and the recovery events ride back to the coordinator's
``FitResult.recoveries``) and runs the phase's tile body **one
suff-stat block at a time**, shipping the per-block substat partials
unfolded. That per-block granularity is the bitwise contract: the
coordinator replays ``acc += p_block`` in fixed global block order, so
the fold's float-addition order is identical to the single-process
tiled driver no matter how many workers exist or which worker computed
which block (core/gibbs.py STATS_BLOCK fold).

The tile bodies here are the *same closure constructions* as
``DPMM._fit_tiled`` pinned to a 1-device mesh (the distributed driver's
mesh — see repro.dist.coordinator), at tile length == STATS_BLOCK. Tile
size is already proven bitwise-neutral repo-wide (tests/test_tiled_parity),
and at the comparison tile size the per-block programs are structurally
identical, so worker compute is bit-for-bit the single-process compute.

A daemon thread heartbeats every ``worker_heartbeat_s`` so the
coordinator can tell a *hung* worker (beats flowing, work deadline
missed) from a *dead* one (EOF). The worker exits when the coordinator
closes the socket or sends ``shutdown``.

Run as: ``python -m repro.dist.worker --connect 127.0.0.1:PORT --id w0``
"""
from __future__ import annotations

import argparse
import socket
import sys
import threading
import traceback
from typing import Dict, List, Optional

import numpy as np

from repro.dist import proto


def plan_template(k_max: int, d: int):
    """Structural ``SplitMergePlan`` dummy: correct leaf dtypes/shapes for
    wire unpacking (proto.unpack_tree) and for tracing the split/merge
    tile body during warmup. Values are never meaningful."""
    import jax.numpy as jnp
    from repro.core.splitmerge import (MergeDecision, SplitDecision,
                                       SplitMergePlan)
    b = jnp.zeros((k_max,), jnp.bool_)
    i = jnp.zeros((k_max,), jnp.int32)
    f = jnp.zeros((k_max, d), jnp.float32)
    return SplitMergePlan(
        split=SplitDecision(accept=b, dest=i, new_active=b),
        merge=MergeDecision(merged=b, into=i, side=i, new_active=b),
        means_split=f, means_merge=f, vecs_split=f, vecs_reset=f,
        reset=b, stuck=i)


class WorkerRuntime:
    """Shard-local compute: the tiled driver's per-tile jitted bodies on
    a 1-device mesh, invoked one STATS_BLOCK at a time."""

    def __init__(self, meta: dict, arrays: Dict[str, np.ndarray]):
        # jax imports live here (not module top) so `--help` and the
        # protocol layer stay import-light
        import functools
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.configs import DPMMConfig
        from repro.core import gibbs, splitmerge
        from repro.core.distributed import (data_axes_of, make_data_mesh,
                                            tile_plan)
        from repro.core.family import get_family, state_partition_specs
        from repro.core.resilience import RetryPolicy, read_block_checked
        from repro.core.sampler import _init_labels
        from repro.core.state import PointState
        from repro.data.faults import FaultInjectingSource
        from repro.data.source import HostTiledSource

        self._gibbs = gibbs
        self._read_block_checked = read_block_checked
        self.STATS_BLOCK = gibbs.STATS_BLOCK

        cfg = DPMMConfig(**meta["cfg"])
        self.cfg = cfg
        family = get_family(cfg.component)
        self.family = family
        src = HostTiledSource.from_npy(meta["data_path"])
        faults = meta.get("faults")
        if faults:
            fa = dict(faults)
            if fa.get("schedule"):
                # JSON round-trip stringifies the call-index keys
                fa["schedule"] = {int(k): v
                                  for k, v in fa["schedule"].items()}
            src = FaultInjectingSource(src, **fa)
        self.source = src
        self.n, self.d = src.n, src.d
        k_max = cfg.k_max
        self.k_max = k_max
        n = self.n
        d = self.d

        mesh = make_data_mesh(1)
        axes = data_axes_of(mesh)
        prior = family.build_prior(cfg, src.column_mean()[None, :])
        n_local, tiles = tile_plan(n, 1, cfg.tile_size)
        self.n_local = n_local
        # read-chunk size: the tile plan's (STATS_BLOCK-aligned) tile
        self.chunk = max(self.STATS_BLOCK,
                         -(-tiles[0][1] // self.STATS_BLOCK)
                         * self.STATS_BLOCK)
        use_pallas = cfg.use_pallas
        feat_axis = None                    # shard_features gated off

        # ---- jitted tile bodies: the _fit_tiled constructions at
        # shards=1, n_chains=1 (cmap identity) --------------------------
        model_specs, _ = state_partition_specs(family, P(axes))
        x_spec = P(axes, feat_axis)
        rep = P()
        acc_shape = jax.eval_shape(
            lambda: gibbs.empty_substats(family, k_max, d))
        acc_specs = type(acc_shape)(**{
            f: P(*([axes] + [None] * getattr(acc_shape, f).ndim))
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
                    f: jnp.zeros((1,) + getattr(shape_k, f).shape,
                                 jnp.float32)
                    for f in shape_k._fields}),
                out_shardings=acc_shardings)

        self._zeros_acc_k = zeros_acc_k
        local = lambda acc: jax.tree.map(lambda v: v[0], acc)
        delocal = lambda acc: jax.tree.map(lambda v: v[None], acc)

        def tile_point(pt, off, length, x_t):
            lab, sub = pt
            gidx = gibbs.global_indices(n_local, axes, offset=off,
                                        length=length)
            valid = (gidx < jnp.uint32(n)).astype(x_t.dtype)
            return PointState(labels=lab, sublabels=sub, valid=valid), gidx

        def _sweep_tile(model, x_t, lab, sub, off, acc, comp=None):
            point, gidx = tile_point((lab, sub), off, x_t.shape[0], x_t)
            point, a = gibbs.sweep_tile(model, x_t, point, gidx,
                                        local(acc), family,
                                        use_pallas=use_pallas,
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

        def _sweep_tile_c(model, x_t, lab, sub, off, acc):
            return _sweep_tile(model, x_t, lab, sub, off, acc)

        def _sm_tile_c(plan, x_t, lab, sub, off, acc):
            return _sm_tile(plan, x_t, lab, sub, off, acc)

        def _sweep_tile_comp(model, x_t, lab, sub, off, comp, acc):
            return _sweep_tile(model, x_t, lab, sub, off, acc, comp)

        def _sm_tile_comp(plan, x_t, lab, sub, off, comp, acc):
            return _sm_tile(plan, x_t, lab, sub, off, acc, comp)

        lab_spec = P(axes)
        lab_specs = (lab_spec, lab_spec)
        smap = functools.partial(jax.shard_map, mesh=mesh,
                                 check_vma=False)
        self.sweep_tile_fn = jax.jit(smap(
            _sweep_tile_c, in_specs=(model_specs, x_spec, *lab_specs, rep,
                                     acc_specs),
            out_specs=(lab_specs, acc_specs)))
        comp_specs = gibbs.CompactionPlan(rep, rep)
        self.sweep_tile_comp_fn = jax.jit(smap(
            _sweep_tile_comp,
            in_specs=(model_specs, x_spec, *lab_specs, rep, comp_specs,
                      acc_specs),
            out_specs=(lab_specs, acc_specs)))
        self.plan_tpl = plan_template(k_max, d)
        plan_specs = jax.tree.map(lambda _: rep, self.plan_tpl)
        self.sm_tile_fn = jax.jit(smap(
            _sm_tile_c,
            in_specs=(plan_specs, x_spec, *lab_specs, rep, acc_specs),
            out_specs=(lab_specs, acc_specs)))
        self.sm_tile_comp_fn = jax.jit(smap(
            _sm_tile_comp,
            in_specs=(plan_specs, x_spec, *lab_specs, rep, comp_specs,
                      acc_specs),
            out_specs=(lab_specs, acc_specs)))
        self.init1_fn = jax.jit(smap(
            _init1_tile, in_specs=(x_spec, rep, acc_specs),
            out_specs=(lab_specs, acc_specs)))
        self.init2_fn = jax.jit(smap(
            _init2_tile, in_specs=(rep, rep, x_spec, *lab_specs, rep,
                                   acc_specs),
            out_specs=(lab_specs, acc_specs)))

        self.x_sharding = NamedSharding(mesh, x_spec)
        self.i32_sharding = NamedSharding(mesh, lab_spec)
        self._device_put = jax.device_put
        self._tree_leaves = jax.tree_util.tree_leaves
        self.retry = RetryPolicy(max_retries=cfg.io_retries,
                                 backoff_s=cfg.io_backoff_s,
                                 guard_nonfinite=cfg.guard_tiles)
        # phase context (set by PHASE messages)
        self._phase: Optional[str] = None
        self._model = None
        self._plan = None
        self._comp = None
        self._k_eff = k_max
        self._means0 = None
        self._v0 = None
        self._warm_meta = meta.get("warm") or {}

    # -- phase / work handling ---------------------------------------------
    def set_phase(self, meta: dict, arrays: Dict[str, np.ndarray]) -> None:
        from repro.core import checkpoint, gibbs
        phase = meta["phase"]
        self._phase = phase
        k_c = meta.get("k_c")
        self._k_eff = int(k_c) if k_c is not None else self.k_max
        if "comp0" in arrays:
            self._comp = gibbs.CompactionPlan(arrays["comp0"],
                                              arrays["comp1"])
        else:
            self._comp = None
        if phase == "sweep":
            self._model, _ = checkpoint.loads_model(
                arrays["model"].tobytes())
        elif phase == "sm":
            self._plan = proto.unpack_tree(self.plan_tpl, arrays, "plan")
        elif phase == "init2":
            self._means0 = arrays["means0"]
            self._v0 = arrays["v0"]
        elif phase != "init1":
            raise proto.ProtocolError(f"unknown phase {phase!r}")

    def _block(self, x_rows: np.ndarray, off: int,
               lab: np.ndarray, sub: np.ndarray):
        """One suff-stat block through the current phase's tile body;
        returns host (labels, sublabels, partial leaves) with the shard
        axis stripped."""
        x_t = self._device_put(x_rows, self.x_sharding)
        lab_t = self._device_put(lab, self.i32_sharding)
        sub_t = self._device_put(sub, self.i32_sharding)
        off_u = np.uint32(off)
        zeros = self._zeros_acc_k(self._k_eff)()
        if self._phase == "init1":
            (lab_o, sub_o), acc = self.init1_fn(x_t, off_u, zeros)
        elif self._phase == "init2":
            (lab_o, sub_o), acc = self.init2_fn(
                self._means0, self._v0, x_t, lab_t, sub_t, off_u, zeros)
        elif self._phase == "sweep":
            if self._comp is None:
                (lab_o, sub_o), acc = self.sweep_tile_fn(
                    self._model, x_t, lab_t, sub_t, off_u, zeros)
            else:
                (lab_o, sub_o), acc = self.sweep_tile_comp_fn(
                    self._model, x_t, lab_t, sub_t, off_u, self._comp,
                    zeros)
        elif self._phase == "sm":
            if self._comp is None:
                (lab_o, sub_o), acc = self.sm_tile_fn(
                    self._plan, x_t, lab_t, sub_t, off_u, zeros)
            else:
                (lab_o, sub_o), acc = self.sm_tile_comp_fn(
                    self._plan, x_t, lab_t, sub_t, off_u, self._comp,
                    zeros)
        else:
            raise proto.ProtocolError(
                f"WORK before PHASE (phase={self._phase!r})")
        return (np.asarray(lab_o), np.asarray(sub_o),
                [np.asarray(l)[0] for l in self._tree_leaves(acc)])

    def process(self, meta: dict, arrays: Dict[str, np.ndarray]):
        """Run the current phase over rows [lo, hi); returns the RESULT
        (meta, arrays): updated labels, stacked per-block partials, and
        any local I/O recovery events."""
        lo, hi = int(meta["lo"]), int(meta["hi"])
        SB = self.STATS_BLOCK
        labels = arrays.get("labels")
        sublabels = arrays.get("sublabels")
        if labels is None:
            # sweeps reassign labels from the model — inputs are unused
            # (the same contract that lets resume start from zeros)
            labels = np.zeros(hi - lo, np.int32)
            sublabels = np.zeros(hi - lo, np.int32)
        io_events: List[dict] = []
        lab_out = np.empty(hi - lo, np.int32)
        sub_out = np.empty(hi - lo, np.int32)
        parts: List[List[np.ndarray]] = []
        for c0 in range(lo, hi, self.chunk):
            c1 = min(c0 + self.chunk, hi)
            rows = self._read_block_checked(self.source, c0, c1,
                                            self.retry,
                                            on_event=io_events.append)
            for b0 in range(c0, c1, SB):
                b1 = min(b0 + SB, c1)
                lab_o, sub_o, p = self._block(
                    rows[b0 - c0:b1 - c0], b0,
                    labels[b0 - lo:b1 - lo], sublabels[b0 - lo:b1 - lo])
                lab_out[b0 - lo:b1 - lo] = lab_o
                sub_out[b0 - lo:b1 - lo] = sub_o
                parts.append(p)
        out_arrays = {"labels": lab_out, "sublabels": sub_out}
        for i in range(len(parts[0])):
            out_arrays[f"p{i}"] = np.stack([p[i] for p in parts])
        return ({"lo": lo, "hi": hi, "phase": self._phase,
                 "io_events": io_events}, out_arrays)

    # -- warmup -------------------------------------------------------------
    def warmup(self) -> None:
        """Pre-compile every (phase, tile length, k_eff) variant this fit
        can hit, so WORK deadlines bound *compute*, not XLA compilation —
        a hung read is then distinguishable from a cold jit cache."""
        import jax
        import jax.numpy as jnp
        from repro.core import gibbs
        from repro.core.sampler import _init_model

        wm = self._warm_meta
        SB = self.STATS_BLOCK
        lengths = sorted({min(SB, self.n)}
                         | ({self.n % SB} if self.n % SB else set()))
        substats = gibbs.empty_substats(self.family, self.k_max, self.d)
        stats = jax.tree.map(lambda a: jnp.sum(a, axis=1), substats)
        cfg = self.cfg
        prior = self.family.build_prior(
            cfg, self.source.column_mean()[None, :])
        model = _init_model(jax.random.key(0), stats, substats,
                            prior=prior, family=self.family, cfg=cfg,
                            k_max=self.k_max)
        plan = self.plan_tpl
        comps = {None: None}
        for k_c in set((wm.get("sweep_k") or [])
                       + (wm.get("sm_k") or [])):
            comps[int(k_c)] = gibbs.compaction_plan(model.active,
                                                    int(k_c))
        off_u = np.uint32(0)
        for length in lengths:
            x1 = np.ones((length, self.d), np.float32)
            lab = np.zeros((length,), np.int32)
            if wm.get("init", True):
                self.init1_fn(x1, off_u, self._zeros_acc_k(self.k_max)())
                self.init2_fn(np.zeros((self.k_max, self.d), np.float32),
                              np.ones((self.k_max, self.d), np.float32),
                              x1, lab, lab, off_u,
                              self._zeros_acc_k(self.k_max)())
            for k_c in [None] + [int(k) for k in (wm.get("sweep_k") or [])]:
                if k_c is None:
                    self.sweep_tile_fn(model, x1, lab, lab, off_u,
                                       self._zeros_acc_k(self.k_max)())
                else:
                    self.sweep_tile_comp_fn(model, x1, lab, lab, off_u,
                                            comps[k_c],
                                            self._zeros_acc_k(k_c)())
            if wm.get("sm", True):
                for k_c in [None] + [int(k)
                                     for k in (wm.get("sm_k") or [])]:
                    if k_c is None:
                        self.sm_tile_fn(plan, x1, lab, lab, off_u,
                                        self._zeros_acc_k(self.k_max)())
                    else:
                        self.sm_tile_comp_fn(plan, x1, lab, lab, off_u,
                                             comps[k_c],
                                             self._zeros_acc_k(k_c)())


# ---------------------------------------------------------------------------
# Process entry: HELLO -> INIT -> warmup -> READY -> {PHASE | WORK}* loop
# ---------------------------------------------------------------------------
def _heartbeat_loop(sock, lock, interval: float,
                    stop: threading.Event) -> None:
    while not stop.wait(interval):
        try:
            proto.send_msg(sock, "heartbeat", lock=lock)
        except OSError:
            return                      # coordinator gone; main loop exits


def run_worker(sock, worker_id: str) -> int:
    lock = threading.Lock()
    stop = threading.Event()
    hb = None
    try:
        proto.send_msg(sock, "hello", {"id": worker_id}, lock=lock)
        kind, meta, arrays = proto.recv_msg(sock)
        if kind != "init":
            raise proto.ProtocolError(f"expected init, got {kind!r}")
        hb = threading.Thread(
            target=_heartbeat_loop,
            args=(sock, lock, float(meta.get("heartbeat_s", 0.5)), stop),
            daemon=True)
        hb.start()
        rt = WorkerRuntime(meta, arrays)
        rt.warmup()
        proto.send_msg(sock, "ready", {"id": worker_id}, lock=lock)
        while True:
            kind, meta, arrays = proto.recv_msg(sock)
            if kind == "phase":
                rt.set_phase(meta, arrays)
            elif kind == "work":
                out_meta, out_arrays = rt.process(meta, arrays)
                out_meta["worker"] = worker_id
                proto.send_msg(sock, "result", out_meta, out_arrays,
                               lock=lock)
            elif kind == "shutdown":
                return 0
            # unknown kinds are ignored (forward compatibility)
    except (proto.ProtocolError, OSError):
        # coordinator died or the stream broke — nothing to clean up
        # (shards are stateless); exit nonzero so ps tells the story
        return 1
    except Exception:
        # compute-side failure (e.g. TileReadError past the retry
        # budget): tell the coordinator why before dying, so the
        # failover event — and a possible WorkerLostError — carry it
        try:
            proto.send_msg(sock, "error",
                           {"id": worker_id,
                            "detail": traceback.format_exc(limit=5)},
                           lock=lock)
        except OSError:
            pass
        return 2
    finally:
        stop.set()
        try:
            sock.close()
        except OSError:
            pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="repro.dist worker shard (spawned by the coordinator)")
    ap.add_argument("--connect", required=True,
                    help="coordinator host:port")
    ap.add_argument("--id", default="w?", help="worker slot id")
    args = ap.parse_args(argv)
    host, port = args.connect.rsplit(":", 1)
    sock = socket.create_connection((host, int(port)), timeout=60)
    sock.settimeout(None)
    return run_worker(sock, args.id)


if __name__ == "__main__":
    sys.exit(main())
