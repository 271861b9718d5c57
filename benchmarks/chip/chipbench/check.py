"""The comparison that decides a run's ``correct``.

What the timed window produced (the final model state and the last
chunk's labels and sub-labels) is compared with the family's plain
reference (``reference/<family>.py``), over every point:

- ``iters_short``: iterations the window was asked for and did not run.
- ``stray_labels``: points labelled to an inactive slot, or with a
  sub-label outside {0, 1}.
- ``stats_gap`` / ``substats_gap``: the cluster and sub-cluster
  statistics the program holds against the reference's fold of the
  final labels (the sweep's fold, its cross-chip psum and split/merge's
  consistency pass). Per field and slot, the largest absolute difference
  over the reference's largest entry of that slot, or of the median
  occupied slot where that is larger.
- ``label_gap`` / ``sublabel_gap``: for the points of clusters the last
  split/merge move left alone (``stuck > 0``: not split, merged, born or
  reset), how far below the reference's log-sum-exp the drawn label's
  (sub-label's) log posterior lies, at the parameters the last sweep used.
  A correct sampler draws a label of posterior probability below e^-t
  with probability under K e^-t per point.

The control replaces the program's statistics by the reference's fold in
bfloat16 and its drawn parameters by the same values held in bfloat16
(``control_outputs``).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

# The program keeps an inactive slot's log weight at -1e30; anything at or
# below this marks a slot the sweep could not assign to.
ZERO_WEIGHT = -1e29
BLOCK = 8192


def program_outputs(state: Any, point: Any, hist_len: int, ref) -> dict:
    """Host copies of what the timed path produced: the final
    ``ModelState`` and the last chunk's ``PointState``."""
    f32 = lambda a: np.asarray(a, np.float32)
    return {
        "labels": np.asarray(point.labels).reshape(-1),
        "sublabels": np.asarray(point.sublabels).reshape(-1),
        "active": np.asarray(state.active), "stuck": np.asarray(state.stuck),
        "it": int(np.asarray(state.it)), "hist_len": int(hist_len),
        "logw": f32(state.logweights), "sublogw": f32(state.sub_logweights),
        "params": ref.read_params(state.params),
        "subparams": ref.read_params(state.subparams),
        "stats": ref.read_stats(state.stats),
        "substats": ref.read_stats(state.substats),
    }


def _segments(out: dict, n: int):
    """Labels, sub-labels and fold segments of the n points. Every one of
    the n points counts, whatever validity mask the program carries: the
    program pads only beyond n."""
    k = out["active"].shape[0]
    lab, sub = out["labels"][:n], out["sublabels"][:n]
    valid = np.ones((n,), bool)
    stray = int(np.sum(valid & ~out["active"][np.clip(lab, 0, k - 1)])
                + np.sum(valid & (sub != 0) & (sub != 1)))
    lab = np.clip(lab, 0, k - 1).astype(np.int32)
    sub = np.clip(sub, 0, 1).astype(np.int32)
    seg = np.where(valid, 2 * lab + sub, 2 * k).astype(np.int32)
    return lab, sub, valid, seg, stray


def _blocked(a: np.ndarray, fill) -> np.ndarray:
    pad = (-a.shape[0]) % BLOCK
    if pad:
        a = np.concatenate([a, np.full((pad,) + a.shape[1:], fill, a.dtype)])
    return a.reshape((-1, BLOCK) + a.shape[1:])


def reference_fold(ref, xb: jax.Array, segb: jax.Array, n_seg: int,
                   dtype) -> Dict[str, np.ndarray]:
    """The reference's (n_seg, ...) statistics over all blocks, summed in
    ``dtype`` block after block."""
    def body(acc, blk):
        part = ref.fold(blk[0], blk[1], n_seg, dtype)
        return jax.tree.map(jnp.add, acc, part), None
    zero = jax.eval_shape(lambda: ref.fold(xb[0], segb[0], n_seg, dtype))
    zero = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), zero)
    run = jax.jit(lambda xb, segb: jax.lax.scan(body, zero, (xb, segb))[0])
    return {f: np.asarray(v.astype(jnp.float32))
            for f, v in run(xb, segb).items()}


def rel_gap(prog: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
            occupied: np.ndarray) -> float:
    """Largest per-slot, per-field |prog - want| over the slot's largest
    reference entry, or the median occupied slot's where that is larger."""
    worst = 0.0
    for f, w in want.items():
        s = w.shape[0]
        w = w.reshape(s, -1).astype(np.float64)
        p = prog[f].reshape(s, -1).astype(np.float64)
        num = np.max(np.abs(p - w), axis=1)
        scale = np.max(np.abs(w), axis=1)
        med = np.median(scale[occupied]) if occupied.any() else 1.0
        worst = max(worst, float(np.max(num / np.maximum(scale, med))))
    return worst


def _gaps(ref, xb, labb, subb, maskb, out: dict):
    """(label_gap, sublabel_gap): the largest, over the masked points, of
    logsumexp minus the drawn label's (sub-label's) log posterior."""
    k = out["active"].shape[0]
    logw = jnp.asarray(out["logw"])
    live = logw > ZERO_WEIGHT
    params = jax.tree.map(jnp.asarray, out["params"])
    logdet = jnp.asarray(ref.log_dets(out["params"]), jnp.float32)
    sub_params = {key: jnp.asarray(v.reshape((2 * k,) + v.shape[2:]))
                  for key, v in out["subparams"].items()}
    sub_logdet = jnp.asarray(ref.log_dets(
        {key: v.reshape((2 * k,) + v.shape[2:])
         for key, v in out["subparams"].items()}), jnp.float32)
    sublogw = jnp.asarray(out["sublogw"]).reshape(2 * k)

    def body(carry, blk):
        x, lab, sub, mask = blk
        lp = ref.logp(x, logw, params, logdet)
        lp = jnp.where(live[None, :], lp, -jnp.inf)
        chosen = jnp.take_along_axis(lp, lab[:, None], axis=1)[:, 0]
        gap = jax.nn.logsumexp(lp, axis=1) - chosen
        slp = ref.logp(x, sublogw, sub_params, sub_logdet)
        own = jnp.stack([2 * lab, 2 * lab + 1], axis=1)
        slp = jnp.take_along_axis(slp, own, axis=1)
        schosen = jnp.take_along_axis(slp, sub[:, None], axis=1)[:, 0]
        sgap = jax.nn.logsumexp(slp, axis=1) - schosen
        big = lambda g: jnp.max(jnp.where(mask, g, 0.0))
        return (jnp.maximum(carry[0], big(gap)),
                jnp.maximum(carry[1], big(sgap))), None

    zero = (jnp.float32(0.0), jnp.float32(0.0))
    run = jax.jit(lambda *b: jax.lax.scan(body, zero, b)[0])
    g, sg = run(xb, labb, subb, maskb)
    return float(g), float(sg)


def evaluate(ref, x: np.ndarray, out: dict, config: dict,
             iters: int, it_start: int) -> Dict[str, float]:
    """The numbers compared, for the program's outputs ``out``."""
    n = x.shape[0]
    k = out["active"].shape[0]
    lab, sub, valid, seg, stray = _segments(out, n)
    xb = jax.device_put(_blocked(x, 0.0))
    segb = jax.device_put(_blocked(seg, 2 * k))
    sub_ref = reference_fold(ref, xb, segb, 2 * k, jnp.float32)
    sub_ref = {f: v.reshape((k, 2) + v.shape[1:]) for f, v in sub_ref.items()}
    stats_ref = {f: v.astype(np.float64).sum(axis=1)
                 for f, v in sub_ref.items()}
    occupied = stats_ref["n"] > 0
    untouched = out["active"] & (out["stuck"] > 0)
    mask = valid & untouched[lab]
    gap, sgap = _gaps(ref, xb, jax.device_put(_blocked(lab, 0)),
                      jax.device_put(_blocked(sub, 0)),
                      jax.device_put(_blocked(mask, False)), out)
    ran = min(out["it"] - it_start, out["hist_len"])
    return {
        "iters_short": float(iters - ran),
        "stray_labels": float(stray),
        "stats_gap": rel_gap(out["stats"], stats_ref, occupied),
        "substats_gap": rel_gap(
            {f: v.reshape((2 * k,) + v.shape[2:])
             for f, v in out["substats"].items()},
            {f: v.reshape((2 * k,) + v.shape[2:])
             for f, v in sub_ref.items()},
            (sub_ref["n"] > 0).reshape(-1)),
        "label_gap": gap,
        "sublabel_gap": sgap,
        "checked_points": float(mask.sum()),
    }


def control_outputs(ref, x: np.ndarray, out: dict) -> dict:
    """``out`` with the reference in bfloat16 in the program's place: the
    statistics folded in bfloat16 from the same labels, the drawn weights
    and parameters held in bfloat16."""
    n = x.shape[0]
    k = out["active"].shape[0]
    _, _, _, seg, _ = _segments(out, n)
    sub = reference_fold(ref, jax.device_put(_blocked(x, 0.0)),
                         jax.device_put(_blocked(seg, 2 * k)), 2 * k,
                         jnp.bfloat16)
    sub = {f: v.reshape((k, 2) + v.shape[1:]) for f, v in sub.items()}
    bf = lambda a: np.asarray(jnp.asarray(a).astype(jnp.bfloat16)
                              .astype(jnp.float32))
    return dict(out,
                stats={f: v.sum(axis=1) for f, v in sub.items()},
                substats=sub,
                logw=bf(out["logw"]), sublogw=bf(out["sublogw"]),
                params=ref.round_params(out["params"], jnp.bfloat16),
                subparams=ref.round_params(out["subparams"], jnp.bfloat16))


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """[(name, value, limit, ok)] for every limited number, in order."""
    rows = []
    for name, limit in limits.items():
        value = numbers[name]
        rows.append((name, value, limit,
                     bool(np.isfinite(value) and value <= limit)))
    return rows


def result_line(cell: dict, rows, attempted: int, failed: int, devices,
                memory: int, context, values: Dict[str, float]) -> dict:
    """The run's result object: with a trace ``context`` its per-layer
    metrics (each ``metrics/<name>.py`` reader's reading, left out where
    it finds nothing), else its end-to-end ``values``; then the compared
    numbers (``checks``, last) and the verdict ``rows`` (``_rows``, for
    the caller to print and drop)."""
    from chipbench import spec
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory}
    line = {"correct": all(ok for *_, ok in rows), "attempted": attempted,
            "failed": failed, "device": device}
    units = {m["name"]: m["unit"]
             for m in cell["end_to_end"] + cell["per_layer"]}
    if context is not None:
        device["busy_s"] = context["trace"]["busy_s"]
        device["window_s"] = context["trace"]["window_s"]
        metrics = {}
        for m in cell["per_layer"]:
            got = spec.load_module("metrics", m["name"]).read(context)
            if got is None:
                continue
            entry = got if isinstance(got, dict) else {"value": got}
            metrics[m["name"]] = dict(entry, unit=units[m["name"]])
        line["metrics"] = metrics
        line["breakdown"] = context["trace"]["breakdown"]
    else:
        line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                       "unit": units[m["name"]]}
                           for m in cell["end_to_end"]}
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit, _ in rows}
    line["_rows"] = rows
    return line
