"""Vantage-point trees for q-metric / infinity-metric search (paper App. C/D).

Build (host, numpy)
-------------------
``build_vptree`` follows Algorithm 1 literally: random (or max-spread)
vantage, radius = median of distances, ties assigned to the OUTSIDE set
(paper (5)/(16)).  The tree is stored as flat arrays — ``vantage[i]`` is the
dataset index of node i's vantage point, ``mu[i]`` its radius, ``left/right``
child node ids (-1 = none) — so the search phase is pure gather arithmetic.

Search (device, JAX) — DESIGN.md §3.2
-------------------------------------
* ``descend_infty``: the Theorem-1 path.  In an infinity-metric space the
  prune conditions (inf-CI)/(inf-CO) are complementary, so each query visits
  exactly one node per level; the whole batch advances in lockstep with one
  gather + one batched distance per level (fori_loop over depth).  Total
  comparisons per query = root-to-leaf path length <= tree depth.
* ``search_best_first``: Algorithm 2 (finite q) with its backtracking
  semantics — a while_loop with an explicit fixed-capacity DFS stack, a
  top-k result buffer and a ``max_comparisons`` budget.  Budget >= n
  reproduces the exact search; smaller budgets give the approximate
  speed/recall trade-off swept in the benchmarks.
* ``search_beam``: level-synchronous beam traversal over a FLATTENED tree
  (``flatten_vptree``: level-order internal nodes + contiguous leaf buckets
  of ``leaf_size`` points, corpus rows re-laid-out bucket-major).  Per
  level the whole (B queries x W beam) frontier of vantage distances is one
  batched distance computation, the q-CI/q-CO prune rules run vectorized
  as child lower bounds against the running tau, and the top-W children
  per query survive; reached leaf buckets accumulate into a fixed-capacity
  buffer and are scanned with the ``core/scan`` running-merge discipline —
  the whole search is ONE jitted dispatch per query batch (DESIGN.md §15).

Both searches accept either raw vectors (distances evaluated on the fly with
any registered metric) or precomputed query->dataset distance rows (used for
the canonical-projection experiments where d_q(x_o, x) comes from
``project_with_queries``).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics as metrics_lib
from repro.core import telemetry as telem
from repro.kernels import bestfirst

INF = jnp.inf


class VPTree(NamedTuple):
    """Flat array representation of a VP tree (device-friendly)."""

    vantage: jax.Array  # (num_nodes,) int32 — dataset index of vantage point
    mu: jax.Array  # (num_nodes,) float32 — node radius
    left: jax.Array  # (num_nodes,) int32 — inside child node id or -1
    right: jax.Array  # (num_nodes,) int32 — outside child node id or -1
    depth: int  # static python int

    @property
    def num_nodes(self) -> int:
        return int(self.vantage.shape[0])


# ---------------------------------------------------------------------------
# host-side numpy distance rows (build-time only)
# ---------------------------------------------------------------------------

def _np_dist_rows(X: np.ndarray, i: int, idxs: np.ndarray, metric: str) -> np.ndarray:
    x = X[i]
    Y = X[idxs]
    if metric == "euclidean":
        return np.sqrt(np.maximum(((Y - x) ** 2).sum(-1), 0.0))
    if metric == "sqeuclidean":
        return ((Y - x) ** 2).sum(-1)
    if metric == "manhattan":
        return np.abs(Y - x).sum(-1)
    if metric == "chebyshev":
        return np.abs(Y - x).max(-1)
    if metric == "cosine":
        nx = max(float(np.linalg.norm(x)), 1e-12)
        ny = np.maximum(np.linalg.norm(Y, axis=-1), 1e-12)
        return 1.0 - (Y @ x) / (ny * nx)
    if metric == "correlation":
        xc = x - x.mean()
        Yc = Y - Y.mean(-1, keepdims=True)
        nx = max(float(np.linalg.norm(xc)), 1e-12)
        ny = np.maximum(np.linalg.norm(Yc, axis=-1), 1e-12)
        return 1.0 - (Yc @ xc) / (ny * nx)
    if metric == "jaccard":
        xb = x > 0
        Yb = Y > 0
        inter = (Yb & xb).sum(-1)
        union = (Yb | xb).sum(-1)
        return 1.0 - inter / np.maximum(union, 1)
    if metric == "dot":
        return -(Y @ x)
    raise KeyError(metric)


# ---------------------------------------------------------------------------
# build (Algorithm 1)
# ---------------------------------------------------------------------------

def build_vptree(
    X: Optional[np.ndarray] = None,
    *,
    D: Optional[np.ndarray] = None,
    metric: str = "euclidean",
    seed: int = 0,
    select: str = "random",
) -> VPTree:
    """Recursive median-split construction (Algorithm 1).

    Either ``X`` (vectors + metric) or ``D`` (precomputed (n, n) dissimilarity
    matrix, e.g. a canonical projection) must be given.  ``select='spread'``
    uses the Yianilos variance heuristic over a distance sample (Remark 2).
    """
    if (X is None) == (D is None):
        raise ValueError("exactly one of X / D must be provided")
    n = (X.shape[0] if X is not None else D.shape[0])
    if n == 0:
        raise ValueError("empty dataset")
    rng = np.random.default_rng(seed)

    def dist_rows(i: int, idxs: np.ndarray) -> np.ndarray:
        if D is not None:
            return np.asarray(D)[i, idxs]
        return _np_dist_rows(np.asarray(X), i, idxs, metric)

    vantage: list[int] = []
    mu: list[float] = []
    left: list[int] = []
    right: list[int] = []

    def new_node() -> int:
        vantage.append(-1)
        mu.append(0.0)
        left.append(-1)
        right.append(-1)
        return len(vantage) - 1

    max_depth = 0

    # Iterative DFS to avoid Python recursion limits on unbalanced trees.
    root = new_node()
    stack: list[tuple[int, np.ndarray, int]] = [(root, np.arange(n), 0)]
    while stack:
        node, idxs, d_level = stack.pop()
        max_depth = max(max_depth, d_level)
        if select == "spread" and len(idxs) > 2:
            cand = idxs[rng.choice(len(idxs), size=min(8, len(idxs)), replace=False)]
            probe = idxs[rng.choice(len(idxs), size=min(32, len(idxs)), replace=False)]
            spreads = [float(np.var(dist_rows(int(c), probe))) for c in cand]
            v = int(cand[int(np.argmax(spreads))])
        else:
            v = int(idxs[rng.integers(len(idxs))])
        rest = idxs[idxs != v]
        vantage[node] = v
        if rest.size == 0:
            continue
        dists = dist_rows(v, rest)
        m = float(np.median(dists))
        mu[node] = m
        inside = rest[dists < m]
        outside = rest[dists >= m]  # ties -> outside (paper (5))
        if inside.size:
            c = new_node()
            left[node] = c
            stack.append((c, inside, d_level + 1))
        if outside.size:
            c = new_node()
            right[node] = c
            stack.append((c, outside, d_level + 1))

    return VPTree(
        vantage=jnp.asarray(vantage, jnp.int32),
        mu=jnp.asarray(mu, jnp.float32),
        left=jnp.asarray(left, jnp.int32),
        right=jnp.asarray(right, jnp.int32),
        depth=max_depth + 1,
    )


# ---------------------------------------------------------------------------
# distance evaluation during search
# ---------------------------------------------------------------------------

def _make_dist(X: Optional[jax.Array], metric: str):
    """Returns f(q_repr, j) -> distance.

    If ``X`` is given, ``q_repr`` is a query vector; otherwise ``q_repr`` is a
    precomputed (n,) row of query->dataset dissimilarities and the evaluation
    is a single gather (canonical-projection search mode).
    """
    if X is None:
        def f(q_row: jax.Array, j: jax.Array) -> jax.Array:
            return q_row[j]
        return f
    pair = metrics_lib.pair_fn(metric)

    def f(q_vec: jax.Array, j: jax.Array) -> jax.Array:
        return pair(q_vec, X[j])

    return f


# ---------------------------------------------------------------------------
# infinity-metric descent (Theorem 1)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("metric", "depth"))
@telem.stage_scope("traversal")
def _descend_impl(tree_arrays, X, queries, metric: str, depth: int):
    vantage, mu, left, right = tree_arrays
    dist = _make_dist(X, metric)

    def per_query(qr):
        def body(_, st):
            node, best_d, best_i, comps = st
            valid = node >= 0
            j = vantage[jnp.maximum(node, 0)]
            d = dist(qr, j)
            better = valid & (d < best_d)
            best_d = jnp.where(better, d, best_d)
            best_i = jnp.where(better, j, best_i)
            comps = comps + valid.astype(jnp.int32)
            go_left = d < mu[jnp.maximum(node, 0)]
            nxt = jnp.where(go_left, left[jnp.maximum(node, 0)], right[jnp.maximum(node, 0)])
            node = jnp.where(valid, nxt, node)
            return node, best_d, best_i, comps

        init = (jnp.int32(0), jnp.float32(INF), jnp.int32(-1), jnp.int32(0))
        _, bd, bi, c = jax.lax.fori_loop(0, depth, body, init)
        return bi, bd, c

    return jax.vmap(per_query)(queries)


def descend_infty(
    tree: VPTree,
    queries: jax.Array,
    *,
    X: Optional[jax.Array] = None,
    metric: str = "euclidean",
):
    """Single-path descent (Algorithm 3 / Theorem 1).

    ``queries`` is (B, d) vectors when ``X`` is given, else (B, n) precomputed
    distance rows.  Returns (best_idx (B,), best_dist (B,), comparisons (B,)).
    Comparisons <= tree depth by construction.
    """
    return _descend_impl(
        (tree.vantage, tree.mu, tree.left, tree.right), X, queries, metric, tree.depth
    )


# ---------------------------------------------------------------------------
# finite-q best-first search (Algorithm 2) with comparison budget
# ---------------------------------------------------------------------------

def _topk_insert(kd: jax.Array, ki: jax.Array, d: jax.Array, j: jax.Array):
    """Insert candidate (d, j) into the ascending (k,) buffer (kd, ki).

    The candidate lands after every entry it does not beat, which is where
    a stable sort of the k + 1 entries puts it, so ties keep their order;
    the last entry drops off.  A +inf (or NaN) candidate lands past the end
    and changes nothing.  Compare, select and a one-slot shift: no sort and
    no data-dependent gather over the buffer.
    """
    slot = jnp.arange(kd.shape[-1])
    pos = jnp.sum(~(d < kd))

    def put(buf, x):
        shifted = jnp.concatenate([x[None], buf[:-1]])
        return jnp.where(slot < pos, buf, jnp.where(slot == pos, x, shifted))

    return put(kd, d), put(ki, j)


@functools.partial(
    jax.jit, static_argnames=("metric", "q", "k", "stack_cap")
)
@telem.stage_scope("traversal")
def _best_first_impl(
    tree_arrays, X, queries, max_comparisons, metric: str, q: float, k: int,
    stack_cap: int, valid=None,
):
    # ``max_comparisons`` is a TRACED int32 scalar: it only gates the
    # while_loop condition, so different budgets (notably the per-shard
    # remainder split in core/index) share one compiled program.
    # ``valid`` (n,) bool masks ACCEPTANCE only (filtered search): every
    # vantage distance is still evaluated — navigation and pruning need it
    # — and still counts against the budget, but non-passing points never
    # enter the top-k buffer.  tau then upper-bounds the k-th best PASSING
    # distance, which is >= the unfiltered tau, so pruning only weakens:
    # conservative, never wrong (the subset argument of DESIGN.md §12).
    vantage, mu, left, right = tree_arrays
    dist = _make_dist(X, metric)
    q_inf = math.isinf(q)

    def per_query(qr):
        def cond(st):
            stack, sp, kd, ki, comps, trunc = st
            return (sp > 0) & (comps < max_comparisons)

        def body(st):
            stack, sp, kd, ki, comps, trunc = st
            node = stack[sp - 1]
            sp = sp - 1
            j = vantage[node]
            d = dist(qr, j)
            comps = comps + 1
            # sorted top-k insert; filtered-out vantages insert as
            # (+inf, -1), which falls off the end — a no-op
            if valid is None:
                ins_d, ins_i = d, j
            else:
                ok = valid[j]
                ins_d = jnp.where(ok, d, INF)
                ins_i = jnp.where(ok, j, -1)
            kd, ki = _topk_insert(kd, ki, ins_d, ins_i)
            tau = kd[k - 1]

            m = mu[node]
            lc, rc = left[node], right[node]
            if q_inf:
                # (inf-CI)/(inf-CO): complementary once tau <= d holds.
                prune_out = jnp.maximum(d, tau) < m
                prune_in = jnp.maximum(m, tau) <= d
            else:
                # powered conditions in a normalized domain: overflow-safe and
                # conservative (underflow can only disable pruning, never
                # prune a branch that may hold the NN).
                s = jnp.maximum(jnp.maximum(d, m), jnp.where(jnp.isfinite(tau), tau, 0.0))
                s = jnp.maximum(s, 1e-30)
                dq = (d / s) ** q
                mq = (m / s) ** q
                tq = jnp.where(jnp.isfinite(tau), (tau / s) ** q, INF)
                prune_out = dq + tq < mq  # (q-CI): only inside can hold NN
                prune_in = mq + tq <= dq  # (q-CO): only outside can hold NN

            # DFS order: push the deferred far child first, near child last.
            push_left = (lc >= 0) & ~prune_in
            push_right = (rc >= 0) & ~prune_out
            near_left = d < m  # visit the side containing the query first
            first = jnp.where(near_left, rc, lc)      # deferred
            first_ok = jnp.where(near_left, push_right, push_left)
            second = jnp.where(near_left, lc, rc)     # visited next
            second_ok = jnp.where(near_left, push_left, push_right)

            # guarded pushes: ``.at[sp].set`` CLAMPS an out-of-bounds sp
            # under jit, which would silently overwrite the top stack slot
            # and corrupt the DFS frontier.  A push past the cap is dropped
            # instead and surfaced through the ``truncated`` flag.
            room1 = sp < stack_cap
            do1 = first_ok & room1
            stack = jnp.where(do1, stack.at[sp].set(first), stack)
            sp = sp + do1.astype(jnp.int32)
            room2 = sp < stack_cap
            do2 = second_ok & room2
            stack = jnp.where(do2, stack.at[sp].set(second), stack)
            sp = sp + do2.astype(jnp.int32)
            trunc = trunc | (first_ok & ~room1) | (second_ok & ~room2)
            return stack, sp, kd, ki, comps, trunc

        stack0 = jnp.zeros((stack_cap,), jnp.int32)
        init = (
            stack0,
            jnp.int32(1),
            jnp.full((k,), INF, jnp.float32),
            jnp.full((k,), -1, jnp.int32),
            jnp.int32(0),
            jnp.asarray(False),
        )
        _, _, kd, ki, comps, trunc = jax.lax.while_loop(cond, body, init)
        return ki, kd, comps, trunc

    return jax.vmap(per_query)(queries)


def search_best_first(
    tree: VPTree,
    queries: jax.Array,
    *,
    q: float,
    k: int = 1,
    X: Optional[jax.Array] = None,
    metric: str = "euclidean",
    max_comparisons: Optional[int] = None,
    valid: Optional[jax.Array] = None,
    with_truncated: bool = False,
    kernel_view=None,
):
    """Algorithm 2: best-first q-metric VP search with top-k results.

    With ``max_comparisons >= num_nodes`` this is the paper's exact search
    (returns the true NN w.r.t. the supplied dissimilarity if it satisfies
    the q-triangle inequality).  Smaller budgets truncate the DFS frontier —
    the approximate regime used for speed/recall sweeps.
    ``valid`` (n,) bool restricts the RESULTS to passing dataset points
    (filtered search): traversal still evaluates — and counts — every
    vantage distance, but only passing points can enter the top-k.
    Returns (idx (B, k), dist (B, k), comparisons (B,)); with
    ``with_truncated=True`` a fourth (B,) bool reports queries whose DFS
    stack hit its capacity (a dropped push — the default cap of
    ``2*depth+8`` never trips, since a binary DFS holds at most depth+1
    deferred nodes, but callers overriding the cap can detect it).
    On a TPU, with vectors ``X`` under the Euclidean metric and no
    ``valid``, the loop runs as one kernel (``kernels/bestfirst``);
    ``kernel_view``, where given, is its ``view`` of (tree, X), kept by the
    caller so it is not rebuilt on every call.
    """
    budget = tree.num_nodes if max_comparisons is None else max_comparisons
    cap = 2 * tree.depth + 8
    arrays = (tree.vantage, tree.mu, tree.left, tree.right)
    budget = jnp.asarray(budget, jnp.int32)  # traced: int AND tracer budgets work
    if bestfirst.applies(X, metric, valid):
        # on a TPU: the same loop as one kernel, not ~30 launches per step
        ki, kd, comps, trunc = bestfirst.best_first(
            arrays, X, queries, budget, q=float(q), k=int(k),
            stack_cap=int(cap), tv=kernel_view)
    else:
        ki, kd, comps, trunc = _best_first_impl(
            arrays,
            X,
            queries,
            budget,
            metric,
            float(q),
            int(k),
            int(cap),
            None if valid is None else jnp.asarray(valid, bool),
        )
    if with_truncated:
        return ki, kd, comps, trunc
    return ki, kd, comps


# ---------------------------------------------------------------------------
# flattened tree + level-synchronous beam search (DESIGN.md §15)
# ---------------------------------------------------------------------------

class FlatVPTree(NamedTuple):
    """Level-order flattening of a ``VPTree`` with bucketed leaves.

    Internal node ``i`` (BFS order, root = 0) has its vantage point laid out
    at ROW ``i`` of the permuted corpus; bucket members follow, contiguous
    and bucket-major.  ``perm`` maps layout rows back to original dataset
    ids (``perm[row] = original id``), so ``Zf = Z[perm]`` is the search
    corpus and every gather during traversal is row-local.

    Child pointers encode three cases in one int32: ``>= 0`` internal child
    node id, ``-1`` no child, ``<= -2`` leaf bucket ``b`` as ``-(b + 2)``.
    All arrays are pad-safe for the ShardedIndex stacker (int pads -1,
    float pads +inf): a padded node is unreachable because only real nodes
    are ever pointed to and the root is always real.
    """

    mu: jax.Array  # (N,) float32 — node radius
    child_in: jax.Array  # (N,) int32 — inside child (see encoding above)
    child_out: jax.Array  # (N,) int32 — outside child
    rad_in: jax.Array  # (N,) f32 — max dist vantage->inside subtree (or inf)
    rad_out: jax.Array  # (N,) f32 — max dist vantage->outside subtree (or inf)
    bucket_rows: jax.Array  # (num_buckets, leaf_size) int32 layout rows, -1 pad
    centroids: Optional[jax.Array]  # (num_buckets, dim) f32 bucket means
    perm: jax.Array  # (n,) int32 — layout row -> original dataset id
    depth: int  # static: number of BFS levels (root level included)
    leaf_size: int  # static: bucket capacity L

    @property
    def num_nodes(self) -> int:
        return int(self.mu.shape[0])

    @property
    def num_buckets(self) -> int:
        return int(self.bucket_rows.shape[0])


def flatten_vptree(
    tree: VPTree,
    *,
    leaf_size: int = 16,
    Z: Optional[np.ndarray] = None,
    metric: str = "euclidean",
) -> FlatVPTree:
    """Build-time flattening pass (host): collapse every subtree holding at
    most ``leaf_size`` points into one contiguous leaf bucket, renumber the
    surviving internal nodes level-order (BFS), and emit the bucket-major
    corpus permutation.  The root never collapses, so ``num_nodes >= 1``
    and the beam always has a level-0 frontier to start from.

    When ``Z`` (the points the tree was built over, original-id indexed) is
    given, per-child subtree radii ``rad_in`` / ``rad_out`` — the max
    distance from a node's vantage to any point of its inside / outside
    subtree — are precomputed for the beam's triangle bounds
    (``d - rad >= 0`` lower-bounds the distance to every subtree point).
    Without ``Z`` the radii are +inf and the beam falls back to the
    mu-margin bounds alone."""
    van = np.asarray(tree.vantage)
    mu_a = np.asarray(tree.mu)
    left = np.asarray(tree.left)
    right = np.asarray(tree.right)
    nn = van.shape[0]
    L = int(leaf_size)
    if L < 1:
        raise ValueError(f"leaf_size must be >= 1, got {leaf_size}")

    # subtree point counts — children are appended after their parent during
    # the build DFS, so a reverse-id sweep sees every child before its parent
    size = np.ones(nn, np.int64)
    for i in range(nn - 1, -1, -1):
        for c in (left[i], right[i]):
            if c >= 0:
                size[i] += size[c]
    collapse = size <= L
    collapse[0] = False

    # BFS over surviving internal nodes: new id = visit order, level-ordered
    order: list[int] = []
    newid: dict[int, int] = {}
    levels: list[int] = []
    queue: list[tuple[int, int]] = [(0, 0)]
    head = 0
    while head < len(queue):
        o, lvl = queue[head]
        head += 1
        newid[o] = len(order)
        order.append(o)
        levels.append(lvl)
        for c in (left[o], right[o]):
            if c >= 0 and not collapse[c]:
                queue.append((int(c), lvl + 1))
    N = len(order)
    depth = levels[-1] + 1

    def subtree_points(r: int) -> list[int]:
        out, st = [], [r]
        while st:
            x = st.pop()
            out.append(int(van[x]))
            for c in (left[x], right[x]):
                if c >= 0:
                    st.append(int(c))
        return out

    child_in = np.full(N, -1, np.int32)
    child_out = np.full(N, -1, np.int32)
    rad_in = np.full(N, np.inf, np.float32)
    rad_out = np.full(N, np.inf, np.float32)
    Za = None if Z is None else np.asarray(Z)
    buckets: list[list[int]] = []
    for o in order:  # BFS order => bucket ids in encounter order
        ni = newid[o]
        for arr, rad, c in (
            (child_in, rad_in, left[o]),
            (child_out, rad_out, right[o]),
        ):
            if c < 0:
                continue
            members = subtree_points(int(c))
            if Za is not None:
                rad[ni] = float(
                    _np_dist_rows(
                        Za, int(van[o]), np.asarray(members, np.int64), metric
                    ).max()
                )
            if collapse[c]:
                arr[ni] = -(len(buckets) + 2)
                buckets.append(members)
            else:
                arr[ni] = newid[int(c)]

    # layout: rows 0..N-1 are the internal vantages (row == node id), then
    # bucket members, contiguous per bucket
    perm = [int(van[o]) for o in order]
    bucket_rows = np.full((max(len(buckets), 1), L), -1, np.int32)
    centroids = None
    if Za is not None:
        centroids = np.zeros((max(len(buckets), 1), Za.shape[1]), np.float32)
    row = N
    for b, members in enumerate(buckets):
        bucket_rows[b, : len(members)] = np.arange(
            row, row + len(members), dtype=np.int32
        )
        if centroids is not None:
            centroids[b] = Za[members].mean(0)
        perm.extend(members)
        row += len(members)
    assert len(perm) == nn, f"layout covers {len(perm)} of {nn} points"

    return FlatVPTree(
        mu=jnp.asarray(mu_a[order], jnp.float32),
        child_in=jnp.asarray(child_in),
        child_out=jnp.asarray(child_out),
        rad_in=jnp.asarray(rad_in),
        rad_out=jnp.asarray(rad_out),
        bucket_rows=jnp.asarray(bucket_rows),
        centroids=None if centroids is None else jnp.asarray(centroids),
        perm=jnp.asarray(perm, jnp.int32),
        depth=depth,
        leaf_size=L,
    )


def _pow2floor(x: int) -> int:
    return 1 << (max(int(x), 1).bit_length() - 1)


def _hofloor(x: int) -> int:
    """Largest half-octave value (2^j or 3 * 2^(j-1)) <= x — twice the
    granularity of pow2 bucketing at the same O(log) jit-key count."""
    p = _pow2floor(x)
    return p + p // 2 if x >= p + p // 2 else p


def beam_plan(
    max_comparisons: Optional[int],
    *,
    depth: int,
    leaf_size: int,
    num_nodes: int,
    num_buckets: int,
    k: int,
) -> tuple[int, int]:
    """Map a per-query comparison budget onto the beam's two static knobs.

    Returns ``(beam_width W, bucket_cap Bcap)``.  Cost accounting is EXACT,
    not the naive ``W * depth``: level l of a binary tree holds at most
    ``min(2^l, W)`` alive frontier slots, so a full-width beam over a small
    tree costs ~``num_nodes`` vantage evaluations — far less than
    ``W * depth`` — and every reached bucket adds one centroid evaluation
    (at most ``2 * vant`` and at most ``num_buckets``).  Whatever the
    traversal estimate leaves funds bucket rows.  W is power-of-two and
    Bcap half-octave (1, 2, 3, 4, 6, 8, 12, ...) bucketed — the static-knob
    discipline keeping budget sweeps at O(log) compiled programs.  With no
    budget the plan covers the whole tree (exact-regime default).
    """
    from repro.core.scan import pow2ceil

    levels = max(int(depth), 1)
    L = max(int(leaf_size), 1)
    nb = max(int(num_buckets), 1)
    full = num_nodes + nb + nb * L
    budget = full if max_comparisons is None else max(int(max_comparisons), 1)

    def traversal_cost(w: int) -> int:
        vant = sum(min(1 << min(lvl, 62), w) for lvl in range(levels))
        vant = min(vant, max(num_nodes, 1))
        return vant + min(2 * vant, nb)  # + centroid evaluations

    # widest affordable beam (wide frontiers are cheap under the exact
    # accounting), leaving at least half the budget for bucket rows
    W = min(64, pow2ceil(max(num_nodes, 1)))
    while W > 1 and traversal_cost(W) > budget // 2:
        W //= 2
    rem = max(budget - traversal_cost(W), L)
    # full coverage must mean FULL: only half-octave-bucket when the budget
    # actually forces dropping buckets
    Bcap = nb if rem // L >= nb else _hofloor(rem // L)
    # floor: enough bucket rows to fill k results even under tiny budgets
    need = -(-int(k) // L)
    return W, min(max(Bcap, pow2ceil(need)), nb)


@functools.partial(
    jax.jit,
    static_argnames=("metric", "q", "k", "beam_width", "bucket_cap", "depth"),
)
@telem.stage_scope("traversal")
def _beam_impl(
    flat_arrays, X, queries, metric: str, q: float, k: int, beam_width: int,
    bucket_cap: int, depth: int, valid=None, codes=None, scales=None,
):
    # One fused program for the whole batch: ``depth`` level steps, each a
    # batched (B, W) vantage-distance evaluation + vectorized q-CI/q-CO
    # pruning + top-W frontier selection, then ``bucket_cap`` leaf-bucket
    # scans through the core/scan running-merge discipline.  ``valid`` (n,)
    # bool (ORIGINAL ids) masks acceptance only — every evaluated distance
    # still counts, exactly like ``_best_first_impl``.  ``codes``/``scales``
    # switch the bucket scans to int8 rows (1 byte/dim read); traversal
    # stays f32 because navigation errors compound down the tree.
    (mu, child_in, child_out, rad_in, rad_out, bucket_rows, perm,
     centroids) = flat_arrays
    W, Bcap, K = beam_width, bucket_cap, k
    L = bucket_rows.shape[1]
    q_inf = math.isinf(q)
    pair = None if X is None else metrics_lib.pair_fn(metric)

    def vantage_dists(qr, nid):
        if X is None:
            return qr[perm[nid]]
        return jax.vmap(lambda v: pair(qr, v))(X[nid])

    def bucket_dists(qr, rows):
        if X is None:
            return qr[perm[rows]]
        if codes is not None:
            V = codes[rows].astype(jnp.float32) * scales[None, :]
            return jax.vmap(lambda v: pair(qr, v))(V)
        return jax.vmap(lambda v: pair(qr, v))(X[rows])

    def merge(best_d, best_i, ds, is_):
        cd = jnp.concatenate([best_d, ds])
        ci = jnp.concatenate([best_i, is_])
        neg, pos = jax.lax.top_k(-cd, K)
        return -neg, ci[pos]

    def per_query(qr):
        # comparison accounting is carried as THREE stage counters —
        # traversal (frontier vantage evals), centroid ranking, bucket rows
        # — threaded out of the jitted program as extra scalar outputs, the
        # no-host-callback route the telemetry layer reads (DESIGN.md §16).
        # Their sum is the engine-reported ``comparisons``.
        def level(_, st):
            frontier, flb, best_d, best_i, buf, bufp, c_trav, c_cent = st
            alive = frontier >= 0
            nid = jnp.maximum(frontier, 0)
            d = jnp.where(alive, vantage_dists(qr, nid), INF)
            c_trav = c_trav + jnp.sum(alive).astype(jnp.int32)
            # the vantages are dataset points: merge them (acceptance-masked)
            # before pruning, mirroring best_first's insert-then-prune order
            vid = perm[nid]
            acc = alive if valid is None else alive & valid[vid]
            best_d, best_i = merge(
                best_d, best_i,
                jnp.where(acc, d, INF), jnp.where(acc, vid, -1),
            )
            tau = best_d[K - 1]

            # q-CI / q-CO keep conditions — the EXACT mirror of
            # _best_first_impl's prune rules (paper semantics + parity
            # with the reference oracle)
            m = mu[nid]
            if q_inf:
                keep_in_c = ~(jnp.maximum(m, tau) <= d)
                keep_out_c = ~(jnp.maximum(d, tau) < m)
            else:
                s = jnp.maximum(jnp.maximum(d, m),
                                jnp.where(jnp.isfinite(tau), tau, 0.0))
                s = jnp.maximum(s, 1e-30)
                dq = (d / s) ** q
                mq = (m / s) ** q
                tq = jnp.where(jnp.isfinite(tau), (tau / s) ** q, INF)
                keep_in_c = ~(mq + tq <= dq)
                keep_out_c = ~(dq + tq < mq)

            cin, cout = child_in[nid], child_out[nid]
            ptr = jnp.concatenate([cin, cout])
            keep = jnp.concatenate(
                [alive & (cin != -1) & keep_in_c,
                 alive & (cout != -1) & keep_out_c]
            )
            # beam priority: (accumulated path bound, parent-vantage
            # distance) lexicographic.  The per-child 1-triangle bounds
            # max(d-m, 0) / max(m-d, 0) are sound for ANY q >= 1 (a
            # q-metric also satisfies the ordinary triangle inequality,
            # (a^q + b^q)^(1/q) <= a + b) — and, unlike the q-powered
            # bounds, they remain meaningful when the searched values are
            # Euclidean embedding distances that only approximate a
            # q-metric (the engine's reality, DESIGN.md §15).  The bound is
            # accumulated down the path (max with the parent's bound, the
            # monotone priority of a best-first queue): a child whose own
            # margin is zero still inherits every ancestor violation, so
            # exactly one root-leaf path per query scores 0 and the beam
            # discriminates at every level instead of only the last one.
            # The parent distance breaks the remaining lb == 0 ties toward
            # cells the query sits deep in.  The precomputed subtree radii
            # tighten both sides (``d - rad`` lower-bounds the distance to
            # every point of that child, and rad_in <= mu by construction);
            # radii are +inf when the flatten pass had no points, where the
            # max reduces back to the mu margins alone.
            rin = jnp.where(jnp.isfinite(rad_in[nid]), rad_in[nid], m)
            rout = rad_out[nid]
            lb = jnp.concatenate([
                jnp.maximum(d - rin, 0.0),
                jnp.maximum(jnp.maximum(m - d, d - rout), 0.0),
            ])
            bound = jnp.maximum(jnp.concatenate([flb, flb]), lb)
            prio = jnp.where(
                keep, bound * 1024.0 + jnp.concatenate([d, d]), INF
            )

            # reached leaf buckets: running top-Bcap merge by priority, so
            # overflow (the budget's Bcap) drops the GLOBALLY least
            # promising buckets, not merely the latest level's.  A bucket
            # has exactly one parent, so no id appears twice.  Buckets are
            # ranked by query->centroid distance when centroids are
            # available (vector mode): a min-distance bound barely
            # separates buckets in high dimension — some point of almost
            # every cell is close-ish — while the EXPECTED distance (the
            # IVF coarse-quantizer signal) tracks where the neighbors
            # actually are.  Each centroid evaluation is a real distance
            # computation and is counted in ``comparisons``.
            is_bucket = keep & (ptr <= -2)
            if centroids is not None:
                bidx = jnp.where(is_bucket, -(ptr + 2), 0)
                dcent = jax.vmap(lambda c: pair(qr, c))(centroids[bidx])
                bprio = jnp.where(is_bucket, dcent, INF)
                c_cent = c_cent + jnp.sum(is_bucket).astype(jnp.int32)
            else:
                bprio = jnp.where(is_bucket, prio, INF)
            cat_p = jnp.concatenate([bufp, bprio])
            cat_b = jnp.concatenate([buf, -(ptr + 2)])
            bneg, bpos = jax.lax.top_k(-cat_p, Bcap)
            bufp = -bneg
            buf = jnp.where(jnp.isfinite(bufp), cat_b[bpos], -1)

            # next frontier: the W most promising surviving internal
            # children (smallest priority), inheriting their path bounds
            is_node = keep & (ptr >= 0)
            neg, pos = jax.lax.top_k(-jnp.where(is_node, prio, INF), W)
            sel = jnp.isfinite(-neg)
            frontier = jnp.where(sel, ptr[pos], -1)
            flb = jnp.where(sel, bound[pos], 0.0)
            return frontier, flb, best_d, best_i, buf, bufp, c_trav, c_cent

        def bucket_scan(buf, best_d, best_i):
            # one fused scan over every selected bucket: gather the
            # (Bcap * L) member rows, evaluate all distances in one batched
            # computation (MXU-shaped in vector mode) and fold them into
            # the running best with a single top-k merge — buckets are
            # disjoint and never contain vantage rows, so no id repeats
            rows = jnp.where(
                (buf >= 0)[:, None], bucket_rows[jnp.maximum(buf, 0)], -1
            ).reshape(-1)
            rvalid = rows >= 0
            rsafe = jnp.maximum(rows, 0)
            d = jnp.where(rvalid, bucket_dists(qr, rsafe), INF)
            oid = perm[rsafe]
            c_buck = jnp.sum(rvalid).astype(jnp.int32)
            acc = rvalid if valid is None else rvalid & valid[oid]
            best_d, best_i = merge(
                best_d, best_i, jnp.where(acc, d, INF), jnp.where(acc, oid, -1)
            )
            return best_d, best_i, c_buck

        frontier0 = jnp.full((W,), -1, jnp.int32).at[0].set(0)
        init = (
            frontier0,
            jnp.zeros((W,), jnp.float32),
            jnp.full((K,), INF, jnp.float32),
            jnp.full((K,), -1, jnp.int32),
            jnp.full((Bcap,), -1, jnp.int32),
            jnp.full((Bcap,), INF, jnp.float32),
            jnp.int32(0),
            jnp.int32(0),
        )
        frontier, _, best_d, best_i, buf, _, c_trav, c_cent = jax.lax.fori_loop(
            0, depth, level, init
        )
        best_d, best_i, c_buck = bucket_scan(buf, best_d, best_i)
        return best_i, best_d, c_trav + c_cent + c_buck, c_trav, c_cent, c_buck

    return jax.vmap(per_query)(queries)


def search_beam(
    flat: FlatVPTree,
    queries: jax.Array,
    *,
    q: float,
    k: int = 1,
    X: Optional[jax.Array] = None,
    metric: str = "euclidean",
    max_comparisons: Optional[int] = None,
    beam_width: Optional[int] = None,
    bucket_cap: Optional[int] = None,
    valid: Optional[jax.Array] = None,
    codes: Optional[jax.Array] = None,
    scales: Optional[jax.Array] = None,
    with_stages: bool = False,
):
    """Level-synchronous beam search over a flattened VP tree — ONE jitted
    dispatch for the whole query batch (DESIGN.md §15).

    ``X`` is the LAYOUT-ORDERED corpus (``Z[flat.perm]``), not the original
    row order; with ``X=None`` each query is a precomputed (n,) distance row
    indexed by ORIGINAL dataset id (the canonical-projection mode shared
    with ``search_best_first``).  ``codes``/``scales`` (int8 codes of the
    layout-ordered corpus + per-dim scales) switch bucket scans to the
    1-byte/dim quantized read.  ``max_comparisons`` is a PLANNING input: it
    is mapped onto the static (beam_width, bucket_cap) knobs by
    ``beam_plan`` (explicit knobs win), and the returned per-query
    comparison counts — frontier evaluations plus scanned bucket rows —
    respect ``W * depth + Bcap * leaf_size``.

    At ``beam_width >= num_nodes`` and ``bucket_cap >= num_buckets`` no
    viable child is ever dropped, so (on a dissimilarity satisfying the
    q-triangle inequality) the result is exact — the same guarantee as
    best-first at full budget.  Returns (idx (B, k), dist (B, k),
    comparisons (B,)) with idx in ORIGINAL dataset ids.

    ``with_stages=True`` appends a fourth element: a dict of per-query
    (B,) int32 stage counters ``{"traversal", "centroid_rank",
    "bucket_scan"}`` whose elementwise sum equals ``comparisons`` — the
    jit-threaded accounting the telemetry layer records (DESIGN.md §16).
    """
    if codes is not None and X is None:
        raise ValueError("quantized bucket scan requires vector mode (X)")
    W0, B0 = beam_plan(
        max_comparisons, depth=flat.depth, leaf_size=flat.leaf_size,
        num_nodes=flat.num_nodes, num_buckets=flat.num_buckets, k=k,
    )
    W = int(beam_width) if beam_width is not None else W0
    Bcap = int(bucket_cap) if bucket_cap is not None else B0
    idx, dist, comps, c_trav, c_cent, c_buck = _beam_impl(
        (flat.mu, flat.child_in, flat.child_out, flat.rad_in, flat.rad_out,
         flat.bucket_rows, flat.perm,
         flat.centroids if X is not None else None),
        X,
        queries,
        metric,
        float(q),
        int(k),
        max(1, W),
        max(1, min(Bcap, flat.num_buckets)),
        flat.depth,
        None if valid is None else jnp.asarray(valid, bool),
        codes,
        None if scales is None else scales,
    )
    if with_stages:
        stages = {"traversal": c_trav, "centroid_rank": c_cent,
                  "bucket_scan": c_buck}
        return idx, dist, comps, stages
    return idx, dist, comps


# ---------------------------------------------------------------------------
# reference search (host, exact recursion) — oracle for tests
# ---------------------------------------------------------------------------

def search_reference(
    tree: VPTree,
    q_row_or_vec: np.ndarray,
    *,
    q: float,
    X: Optional[np.ndarray] = None,
    metric: str = "euclidean",
) -> tuple[int, float, int]:
    """Literal recursive Algorithm 2/3 in numpy (1 query, k=1)."""
    vantage = np.asarray(tree.vantage)
    mu = np.asarray(tree.mu)
    left = np.asarray(tree.left)
    right = np.asarray(tree.right)

    if X is None:
        def dist(j: int) -> float:
            return float(q_row_or_vec[j])
    else:
        Xq = np.concatenate([np.asarray(X), np.asarray(q_row_or_vec)[None]], axis=0)

        def dist(j: int) -> float:
            return float(_np_dist_rows(Xq, Xq.shape[0] - 1, np.asarray([j]), metric)[0])

    best = [-1, math.inf, 0]  # idx, tau, comparisons

    def visit(node: int) -> None:
        if node < 0:
            return
        j = int(vantage[node])
        d = dist(j)
        best[2] += 1
        if d < best[1]:
            best[1] = d
            best[0] = j
        tau = best[1]
        m = float(mu[node])
        if math.isinf(q):
            if d < m:
                visit(int(left[node]))
                if not max(d, tau) < m:  # unreachable: complementary conditions
                    visit(int(right[node]))
            else:
                visit(int(right[node]))
            return
        s = max(d, m, 0.0 if math.isinf(tau) else tau, 1e-30)
        dq, mq = (d / s) ** q, (m / s) ** q
        tq = math.inf if math.isinf(tau) else (tau / s) ** q
        if dq + tq < mq:
            visit(int(left[node]))
        elif mq + tq <= dq:
            visit(int(right[node]))
        else:
            if d < m:
                visit(int(left[node]))
                visit(int(right[node]))
            else:
                visit(int(right[node]))
                visit(int(left[node]))

    import sys

    old = sys.getrecursionlimit()
    sys.setrecursionlimit(max(old, tree.num_nodes + 100))
    try:
        visit(0)
    finally:
        sys.setrecursionlimit(old)
    return best[0], best[1], best[2]
