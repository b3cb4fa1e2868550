"""The comparison that decides ``correct``.

Every request answered ``ok`` in the window is judged after the window has
closed, against the benchmark's own exact reference (``reference.py``) and
float64 distances computed on the host.  The numbers:

* ``bad_answers``: answers with an id outside the corpus, a missing slot, a
  repeated id, or distances that do not ascend;
* ``dist_err``: the largest gap between a served squared distance and the
  float64 squared distance of the id it was served with, as a share of
  the two squared norms it is computed from (|q|^2 + |x|^2), so that it
  measures the arithmetic and not how closely the terms cancel;
* ``rank_gap`` (exact engines): the largest relative amount by which the
  j-th served neighbour (float64, sorted) lies farther than the j-th exact
  one.

* ``recall_at_10``: the mean share of the exact top-k found, over the
  answered requests.  It is an end-to-end metric; an approximate engine's
  configuration also holds it to a floor, the one number here that the
  traversal decides (valid ids with their true distances that miss the
  neighbours pass every other check).

A limit is a ceiling, ``{"max": x}`` or a bare number, or a floor,
``{"min": x}``.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference import dist64, exact_topk, row_shards

#: served distances that descend by less than this share are ties
ASCEND_RTOL = 1e-5


def gather_rows(X, ids: np.ndarray, chunk: int = 8192) -> np.ndarray:
    """Rows ``X[ids]`` of a device corpus (``ids`` in range), copied to the
    host in chunks; of a row-sharded corpus, each row gathered from the
    shard that holds it, on that shard's device."""
    import jax

    flat = ids.reshape(-1)
    out = np.empty((flat.size, X.shape[1]), np.float32)
    for first, Xs in row_shards(X):
        at = np.flatnonzero((flat >= first) & (flat < first + Xs.shape[0]))
        for s in range(0, at.size, chunk):
            part = jax.device_put(flat[at[s:s + chunk]] - first, Xs.sharding)
            out[at[s:s + chunk]] = np.asarray(Xs[part])
    return out.reshape(ids.shape + (X.shape[1],))


def judge(X, pool: np.ndarray, rows: np.ndarray, idx: np.ndarray,
          dist: np.ndarray, *, k: int, checks: dict,
          reference=exact_topk) -> dict:
    """Judge the served answers ``idx``/``dist`` (m, k) of pool rows
    ``rows`` (m,).  ``checks`` maps each compared number's name to its
    limit.  Returns {"checks": {name: {"value", "limit"}}, "correct",
    "recall_at_10"}."""
    m = len(rows)
    n = X.shape[0]
    if m == 0:
        return {"checks": {"answered": {"value": 0, "limit": 1, "is": "min"}},
                "correct": False, "recall_at_10": None}
    uniq, inv = np.unique(rows, return_inverse=True)
    Qu = pool[uniq]
    _, ref_u = reference(Qu, X, k)
    ref_i = ref_u[inv]
    Q = pool[rows]

    in_range = (idx >= 0) & (idx < n)
    srt = np.sort(idx, axis=1)
    dup = np.any(srt[:, 1:] == srt[:, :-1], axis=1)
    desc = np.any(dist[:, 1:] < dist[:, :-1] * (1 - ASCEND_RTOL), axis=1)
    bad = ~in_range.all(axis=1) | dup | desc | ~np.isfinite(dist).all(axis=1)
    safe = np.where(in_range, idx, 0)

    rows_served = gather_rows(X, safe)
    d_served = dist64(rows_served, Q)
    d_ref = dist64(gather_rows(X, ref_i), Q)
    good = ~bad
    scale = (np.sum(Q.astype(np.float64) ** 2, axis=1)[:, None]
             + np.sum(rows_served.astype(np.float64) ** 2, axis=2))
    err = np.abs(dist.astype(np.float64) ** 2 - d_served ** 2) / scale
    dist_err = float(np.max(err[good])) if good.any() else float("inf")
    gap = (np.sort(d_served, axis=1) - np.sort(d_ref, axis=1)) / np.maximum(
        np.sort(d_ref, axis=1), 1e-12)
    rank_gap = float(max(0.0, np.max(gap[good]))) if good.any() else float("inf")
    hits = [len(set(a) & set(b)) for a, b in zip(idx.tolist(), ref_i.tolist())]
    recall = float(np.mean(hits)) / k
    values = {"bad_answers": int(bad.sum()), "dist_err": dist_err,
              "rank_gap": rank_gap, "recall_at_10": recall}
    out, correct = {}, True
    for name, lim in checks.items():
        kind, bound = next(iter(lim.items())) if isinstance(lim, dict) \
            else ("max", lim)
        out[name] = {"value": values[name], "limit": bound, "is": kind}
        correct &= (values[name] >= bound if kind == "min"
                    else values[name] <= bound)
    return {"checks": out, "correct": bool(correct), "recall_at_10": recall}
