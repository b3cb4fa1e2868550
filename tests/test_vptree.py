"""VP trees: Algorithm 1 build, Theorem 1 descent, Algorithm 2 best-first."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import metrics, qmetric, vptree


def _data(n=80, d=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    D = np.array(metrics.pairwise(jnp.asarray(X), jnp.asarray(X)))
    np.fill_diagonal(D, 0.0)
    return X, jnp.asarray((D + D.T) / 2)


def test_build_invariants():
    X, D = _data()
    tree = vptree.build_vptree(X, metric="euclidean", seed=0)
    assert tree.num_nodes == X.shape[0]  # every point is a vantage exactly once
    v = np.sort(np.asarray(tree.vantage))
    assert (v == np.arange(X.shape[0])).all()
    # children are valid node ids
    for c in (np.asarray(tree.left), np.asarray(tree.right)):
        assert ((c == -1) | ((c >= 0) & (c < tree.num_nodes))).all()


def test_theorem1_descent_depth_bound_and_exactness():
    """On an ultrametric space, dataset-point queries find themselves in
    <= depth comparisons (Theorem 1)."""
    X, D = _data(100, seed=1)
    Dinf = qmetric.canonical_projection(D, math.inf)
    tree = vptree.build_vptree(D=np.asarray(Dinf), seed=0)
    # queries ARE dataset rows of the ultrametric -> exact self-match
    rows = Dinf[:16]
    bi, bd, comps = vptree.descend_infty(tree, rows)
    assert (np.asarray(comps) <= tree.depth).all()
    assert np.allclose(np.asarray(bd), 0.0, atol=1e-6)
    assert (np.asarray(bi) == np.arange(16)).all()


def test_descent_close_to_log2n():
    """Fig. 2/10: mean comparisons stay near log2(n)."""
    X, D = _data(128, seed=2)
    Dinf = qmetric.canonical_projection(D, math.inf)
    tree = vptree.build_vptree(D=np.asarray(Dinf), seed=0)
    _, _, comps = vptree.descend_infty(tree, Dinf[:64])
    assert float(np.mean(np.asarray(comps))) <= 3.0 * math.log2(128)


def test_best_first_exact_against_brute_force():
    """Algorithm 2 with full budget returns the true NN for a q-metric."""
    X, D = _data(90, seed=3)
    for q in (2.0, 8.0):
        Dq = qmetric.canonical_projection(D, q)
        tree = vptree.build_vptree(D=np.asarray(Dq), seed=1)
        rng = np.random.default_rng(4)
        Qv = rng.normal(size=(10, X.shape[1])).astype(np.float32)
        rows = metrics.pairwise(jnp.asarray(Qv), jnp.asarray(X))
        Eq = qmetric.project_with_queries(D, rows, q)
        ki, kd, comps = vptree.search_best_first(tree, Eq, q=q, k=1)
        assert (np.asarray(ki)[:, 0] == np.argmin(np.asarray(Eq), axis=1)).all()


def test_best_first_matches_reference_recursion():
    X, D = _data(60, seed=5)
    q = 2.0
    Dq = qmetric.canonical_projection(D, q)
    tree = vptree.build_vptree(D=np.asarray(Dq), seed=2)
    rng = np.random.default_rng(6)
    Qv = rng.normal(size=(5, X.shape[1])).astype(np.float32)
    rows = metrics.pairwise(jnp.asarray(Qv), jnp.asarray(X))
    Eq = np.asarray(qmetric.project_with_queries(D, rows, q))
    ki, kd, comps = vptree.search_best_first(tree, jnp.asarray(Eq), q=q, k=1)
    for b in range(5):
        ridx, rd, rc = vptree.search_reference(tree, Eq[b], q=q)
        assert int(ki[b, 0]) == ridx
        assert int(comps[b]) == rc, "comparison counts must match Algorithm 2"


def test_knn_and_budget():
    X, D = _data(120, seed=7)
    tree = vptree.build_vptree(X, metric="euclidean", seed=3)
    rng = np.random.default_rng(8)
    Qv = jnp.asarray(rng.normal(size=(6, X.shape[1])).astype(np.float32))
    ki, kd, comps = vptree.search_best_first(
        tree, Qv, q=1.0, k=5, X=jnp.asarray(X), metric="euclidean"
    )
    # exact 5-NN vs brute force (euclidean is a 1-metric -> exact)
    Dq = np.array(metrics.pairwise(Qv, jnp.asarray(X)))
    ref = np.argsort(Dq, axis=1)[:, :5]
    assert (np.sort(np.asarray(ki), axis=1) == np.sort(ref, axis=1)).all()
    # budgeted search visits no more than the budget
    _, _, comps_b = vptree.search_best_first(
        tree, Qv, q=1.0, k=1, X=jnp.asarray(X), metric="euclidean",
        max_comparisons=17,
    )
    assert (np.asarray(comps_b) <= 17).all()


def test_fewer_comparisons_with_larger_q():
    """(C1): monotone-ish decrease of comparisons in q (mean over queries)."""
    X, D = _data(150, seed=9)
    rng = np.random.default_rng(10)
    Qv = rng.normal(size=(20, X.shape[1])).astype(np.float32)
    rows = metrics.pairwise(jnp.asarray(Qv), jnp.asarray(X))
    means = []
    for q in [1.0, 4.0, 16.0]:
        Dq = qmetric.canonical_projection(D, q)
        tree = vptree.build_vptree(D=np.asarray(Dq), seed=4)
        Eq = qmetric.project_with_queries(D, rows, q)
        _, _, comps = vptree.search_best_first(tree, Eq, q=q, k=1)
        means.append(float(np.mean(np.asarray(comps))))
    assert means[-1] < means[0], means


# ---------------------------------------------------------------------------
# select="spread" (Yianilos variance heuristic, Remark 2)
# ---------------------------------------------------------------------------

def test_build_spread_invariants_and_exact_search():
    """Spread-selected vantage points must keep Algorithm 1's invariants and
    exact-search behavior (euclidean is a 1-metric -> full-budget best-first
    is exact)."""
    X, D = _data(90, seed=11)
    tree = vptree.build_vptree(X, metric="euclidean", seed=0, select="spread")
    assert tree.num_nodes == X.shape[0]
    v = np.sort(np.asarray(tree.vantage))
    assert (v == np.arange(X.shape[0])).all()  # every point a vantage once
    for c in (np.asarray(tree.left), np.asarray(tree.right)):
        assert ((c == -1) | ((c >= 0) & (c < tree.num_nodes))).all()
    rng = np.random.default_rng(12)
    Qv = jnp.asarray(rng.normal(size=(8, X.shape[1])).astype(np.float32))
    ki, kd, comps = vptree.search_best_first(
        tree, Qv, q=1.0, k=3, X=jnp.asarray(X), metric="euclidean"
    )
    ref = np.argsort(np.array(metrics.pairwise(Qv, jnp.asarray(X))), axis=1)[:, :3]
    assert (np.sort(np.asarray(ki), axis=1) == np.sort(ref, axis=1)).all()


def test_build_spread_differs_from_random_but_same_contract():
    """The heuristic actually changes vantage choices (it isn't a silent
    fall-through to random) while preserving the node-count contract."""
    X, D = _data(120, seed=13)
    t_rand = vptree.build_vptree(X, metric="euclidean", seed=5, select="random")
    t_spread = vptree.build_vptree(X, metric="euclidean", seed=5, select="spread")
    assert t_rand.num_nodes == t_spread.num_nodes == X.shape[0]
    assert (np.asarray(t_rand.vantage) != np.asarray(t_spread.vantage)).any()


# ---------------------------------------------------------------------------
# precomputed-D build + search (canonical-projection mode)
# ---------------------------------------------------------------------------

def test_spread_build_on_precomputed_projection_descend_exact():
    """select='spread' over a precomputed canonical projection D_inf: the
    Theorem-1 descent must still find dataset-row queries exactly within
    depth comparisons."""
    X, D = _data(100, seed=14)
    Dinf = qmetric.canonical_projection(D, math.inf)
    tree = vptree.build_vptree(D=np.asarray(Dinf), seed=3, select="spread")
    rows = Dinf[:12]
    bi, bd, comps = vptree.descend_infty(tree, rows)
    assert (np.asarray(comps) <= tree.depth).all()
    assert np.allclose(np.asarray(bd), 0.0, atol=1e-6)
    assert (np.asarray(bi) == np.arange(12)).all()


def test_precomputed_D_search_matches_reference_on_spread_tree():
    """Best-first over query->dataset projection rows (X=None) must agree
    with the literal recursive reference, including comparison counts."""
    X, D = _data(60, seed=15)
    q = 4.0
    Dq = qmetric.canonical_projection(D, q)
    tree = vptree.build_vptree(D=np.asarray(Dq), seed=4, select="spread")
    rng = np.random.default_rng(16)
    Qv = rng.normal(size=(5, X.shape[1])).astype(np.float32)
    rows = metrics.pairwise(jnp.asarray(Qv), jnp.asarray(X))
    Eq = np.asarray(qmetric.project_with_queries(D, rows, q))
    ki, kd, comps = vptree.search_best_first(tree, jnp.asarray(Eq), q=q, k=1)
    assert (np.asarray(ki)[:, 0] == np.argmin(Eq, axis=1)).all()
    for b in range(5):
        ridx, rd, rc = vptree.search_reference(tree, Eq[b], q=q)
        assert int(ki[b, 0]) == ridx
        assert int(comps[b]) == rc


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), n=st.integers(10, 60))
def test_property_descend_comparisons_bounded_by_depth(seed, n):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 4)).astype(np.float32)
    D = np.array(metrics.pairwise(jnp.asarray(X), jnp.asarray(X)))
    np.fill_diagonal(D, 0.0)
    Dinf = qmetric.canonical_projection(jnp.asarray(D), math.inf)
    tree = vptree.build_vptree(D=np.asarray(Dinf), seed=seed)
    _, _, comps = vptree.descend_infty(tree, Dinf[: min(8, n)])
    assert (np.asarray(comps) <= tree.depth).all()


# ---------------------------------------------------------------------------
# DFS stack guard (fixed-capacity stack must not silently corrupt on deep
# unbalanced trees — pushes are bounds-checked and surfaced as `truncated`)
# ---------------------------------------------------------------------------

def test_deep_unbalanced_tree_stack_guard_default_cap():
    """All-duplicate points build a maximally unbalanced chain (every split
    sends the whole remainder outside).  The default stack cap must absorb
    it: correct result, truncated=False."""
    n = 40
    X = np.zeros((n, 4), np.float32)  # all identical -> depth-n right chain
    tree = vptree.build_vptree(X, metric="euclidean", seed=0)
    assert tree.depth >= n - 1  # the pathological shape actually happened
    Q = jnp.zeros((3, 4), jnp.float32)
    ki, kd, comps, trunc = vptree.search_best_first(
        tree, Q, q=2.0, k=1, X=jnp.asarray(X), metric="euclidean",
        with_truncated=True,
    )
    assert np.allclose(np.asarray(kd), 0.0, atol=1e-6)
    assert not np.asarray(trunc).any()


def test_stack_overflow_is_flagged_not_silent():
    """With a deliberately tiny stack, overflow must raise the truncated
    flag instead of clamping `stack.at[sp]` onto a live slot."""
    X, _ = _data(64, seed=21)
    tree = vptree.build_vptree(X, metric="euclidean", seed=7)
    rng = np.random.default_rng(22)
    Q = jnp.asarray(rng.normal(size=(8, X.shape[1])).astype(np.float32))
    ki, kd, comps, trunc = vptree._best_first_impl(
        (tree.vantage, tree.mu, tree.left, tree.right),
        jnp.asarray(X),
        Q,
        jnp.asarray(tree.num_nodes, jnp.int32),
        "euclidean",
        2.0,
        1,
        1,  # stack_cap=1: any branch with two viable children overflows
        None,
    )
    assert np.asarray(trunc).any()
    # results remain well-formed even when truncated
    assert (np.asarray(ki)[:, 0] >= 0).all()


def test_with_truncated_flag_api_default_false():
    X, _ = _data(50, seed=23)
    tree = vptree.build_vptree(X, metric="euclidean", seed=8)
    Q = jnp.asarray(np.random.default_rng(24).normal(size=(4, X.shape[1]))
                    .astype(np.float32))
    out3 = vptree.search_best_first(tree, Q, q=2.0, k=2, X=jnp.asarray(X))
    assert len(out3) == 3
    out4 = vptree.search_best_first(
        tree, Q, q=2.0, k=2, X=jnp.asarray(X), with_truncated=True)
    assert len(out4) == 4 and not np.asarray(out4[3]).any()


# ---------------------------------------------------------------------------
# sorted top-k insert of the best-first loop: same buffer as a stable sort
# ---------------------------------------------------------------------------

def _stable_insert(kd, ki, d, j):
    """Reference: stable argsort of the k + 1 entries, keep the first k."""
    cd = np.append(kd, np.float32(d))
    ci = np.append(ki, np.int32(j))
    order = np.argsort(cd, kind="stable")[: kd.shape[0]]
    return cd[order], ci[order]


@pytest.mark.parametrize("k", [1, 2, 10, 512])
def test_topk_insert_matches_stable_argsort(k):
    """Tie-heavy sorted buffers, part filled (+inf, -1), and +inf inserts:
    the shift-insert returns the stable argsort's buffer, bit for bit."""
    rng = np.random.default_rng(k)
    trials = 64
    kd = np.full((trials, k), np.inf, np.float32)
    ki = np.full((trials, k), -1, np.int32)
    for t in range(trials):
        m = int(rng.integers(0, k + 1))
        kd[t, :m] = np.sort(rng.integers(0, 4, m)).astype(np.float32)
        ki[t, :m] = rng.integers(0, 10_000, m)
    d = rng.integers(0, 5, trials).astype(np.float32)
    j = rng.integers(0, 10_000, trials).astype(np.int32)
    d[::4], j[::4] = np.inf, -1  # filtered-out candidates
    got_d, got_i = jax.jit(jax.vmap(vptree._topk_insert))(
        jnp.asarray(kd), jnp.asarray(ki), jnp.asarray(d), jnp.asarray(j))
    for t in range(trials):
        ref_d, ref_i = _stable_insert(kd[t], ki[t], d[t], j[t])
        np.testing.assert_array_equal(np.asarray(got_d[t]), ref_d)
        np.testing.assert_array_equal(np.asarray(got_i[t]), ref_i)


def _np_best_first(tree, row, q, k, valid=None):
    """Algorithm 2 in numpy, step for step as ``_best_first_impl`` (explicit
    DFS stack, float32 prune rules), with a stable-argsort top-k buffer."""
    vantage, mu, left, right = (np.asarray(a) for a in
                                (tree.vantage, tree.mu, tree.left, tree.right))
    f32 = np.float32
    kd = np.full(k, np.inf, f32)
    ki = np.full(k, -1, np.int32)
    stack, comps = [0], 0
    while stack:
        node = stack.pop()
        j = int(vantage[node])
        d = f32(row[j])
        comps += 1
        ok = valid is None or bool(valid[j])
        kd, ki = _stable_insert(kd, ki, d if ok else np.inf, j if ok else -1)
        tau, m = kd[k - 1], f32(mu[node])
        if math.isinf(q):
            prune_out = max(d, tau) < m
            prune_in = max(m, tau) <= d
        else:
            s = max(d, m, tau if np.isfinite(tau) else f32(0), f32(1e-30))
            dq, mq = (d / s) ** f32(q), (m / s) ** f32(q)
            tq = (tau / s) ** f32(q) if np.isfinite(tau) else f32(np.inf)
            prune_out = dq + tq < mq
            prune_in = mq + tq <= dq
        push_left = left[node] >= 0 and not prune_in
        push_right = right[node] >= 0 and not prune_out
        if d < m:
            pushes = [(right[node], push_right), (left[node], push_left)]
        else:
            pushes = [(left[node], push_left), (right[node], push_right)]
        stack += [int(c) for c, ok_c in pushes if ok_c]
    return ki, kd, comps


@pytest.mark.parametrize("filtered", [False, True])
@pytest.mark.parametrize("q", [math.inf, 2.0])
def test_best_first_k512_matches_numpy_stable_argsort(q, filtered):
    """k = 512 on a tie-heavy integer corpus (L1 over {0..3}^6): ids,
    distances and comparisons equal numpy Algorithm 2 with a stable-argsort
    buffer, with and without a ``valid`` mask."""
    rng = np.random.default_rng(31)
    C = rng.integers(0, 4, size=(1500, 6))
    Qi = rng.integers(0, 4, size=(6, 6))
    D = np.abs(C[:, None, :] - C[None, :, :]).sum(-1).astype(np.float32)
    rows = np.abs(Qi[:, None, :] - C[None, :, :]).sum(-1).astype(np.float32)
    tree = vptree.build_vptree(D=D, seed=5)
    valid = rng.random(1500) < 0.6 if filtered else None
    ki, kd, comps = vptree.search_best_first(
        tree, jnp.asarray(rows), q=q, k=512,
        valid=None if valid is None else jnp.asarray(valid))
    for b in range(rows.shape[0]):
        ref_i, ref_d, ref_c = _np_best_first(tree, rows[b], q, 512, valid)
        np.testing.assert_array_equal(np.asarray(ki[b]), ref_i)
        np.testing.assert_array_equal(np.asarray(kd[b]), ref_d)
        assert int(comps[b]) == ref_c


def test_best_first_loop_has_no_sort_or_buffer_gather():
    """The lowered best-first program at k = 512 holds no sort and no gather
    with k + 1 elements per query: the top-k insert is compare and select."""
    X, _ = _data(64, seed=25)
    tree = vptree.build_vptree(X, metric="euclidean", seed=9)
    B, k = 4, 512
    txt = vptree._best_first_impl.lower(
        (tree.vantage, tree.mu, tree.left, tree.right), jnp.asarray(X),
        jnp.asarray(X[:B]), jnp.int32(64), metric="euclidean", q=math.inf,
        k=k, stack_cap=2 * tree.depth + 8).as_text()
    assert "stablehlo.sort" not in txt
    gathers = re.findall(r"stablehlo\.gather.*-> tensor<([0-9x]+)x[a-z0-9]+>",
                         txt)
    assert gathers, "no gather found: the lowered text changed form"
    for shape in gathers:
        dims = [int(s) for s in shape.split("x")]
        assert not (dims[0] == B and math.prod(dims[1:]) == k + 1), shape


# ---------------------------------------------------------------------------
# the best-first kernel (kernels/bestfirst): the XLA loop's answers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q", [math.inf, 2.0])
@pytest.mark.parametrize("d,B,k", [(6, 8, 512), (5, 3, 10), (96, 1, 130)])
def test_best_first_kernel_matches_xla_loop(q, d, B, k):
    """Interpreted, the kernel returns the XLA loop's ids, distances,
    comparisons and truncation flags, bit for bit, on a tie-heavy integer
    corpus (exact sums): a budget that cuts the search, one that does not,
    and none at all.  Widths of 5, 6 and 96 pad to segments of 8 and 128."""
    from repro.kernels.bestfirst import best_first_pallas, best_first_ref, view

    rng = np.random.default_rng(d * 100 + B)
    X = rng.integers(0, 4, size=(900, d)).astype(np.float32)
    Q = jnp.asarray(rng.integers(0, 4, size=(B, d)).astype(np.float32))
    tree = vptree.build_vptree(X, metric="euclidean", seed=7)
    arrays = (tree.vantage, tree.mu, tree.left, tree.right)
    cap = 2 * tree.depth + 8
    tv = view(arrays, jnp.asarray(X))
    for budget in (0, 150, 900):
        ref = best_first_ref(arrays, jnp.asarray(X), Q, jnp.int32(budget),
                             q=q, k=k, stack_cap=cap)
        got = best_first_pallas(tv, Q, jnp.int32(budget), q=q, k=k,
                                stack_cap=cap, interpret=True)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_best_first_kernel_flags_a_full_stack_like_the_loop():
    """A stack cap below the tree's depth drops pushes: the kernel flags
    the same queries and returns the same partial search as the loop."""
    from repro.kernels.bestfirst import best_first_pallas, best_first_ref, view

    rng = np.random.default_rng(3)
    X = rng.normal(size=(600, 4)).astype(np.float32)
    Q = jnp.asarray(rng.normal(size=(4, 4)).astype(np.float32))
    tree = vptree.build_vptree(X, metric="euclidean", seed=1)
    arrays = (tree.vantage, tree.mu, tree.left, tree.right)
    ref = best_first_ref(arrays, jnp.asarray(X), Q, jnp.int32(600), q=2.0,
                         k=5, stack_cap=3)
    got = best_first_pallas(view(arrays, jnp.asarray(X)), Q, jnp.int32(600),
                            q=2.0, k=5, stack_cap=3, interpret=True)
    assert np.asarray(ref[3]).any()
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(ref[3]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(ref[2]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))


def test_best_first_kernel_applies_only_on_a_tpu_without_a_mask():
    from repro.kernels import bestfirst

    X = jnp.zeros((16, 32), jnp.float32)
    on_tpu = jax.default_backend() == "tpu"
    assert bestfirst.applies(X, "euclidean", None) == on_tpu
    assert not bestfirst.applies(X, "manhattan", None)
    assert not bestfirst.applies(X, "euclidean", jnp.ones(16, bool))
    assert not bestfirst.applies(None, "euclidean", None)
    assert not bestfirst.applies(jnp.zeros((16, 129)), "euclidean", None)
