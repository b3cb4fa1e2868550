"""ANN baselines the paper compares against (§5.1, App. F.7) — in JAX.

* ``brute_force`` / ``BruteIndex`` — exact blocked top-k (the ground-truth
                      oracle).
* ``IVFFlat``       — k-means coarse quantizer + probed exact scoring
                      (FAISS IVF-Flat semantics).
* ``IVFPQ``         — IVF + product quantization with ADC lookup tables
                      (Jégou et al. 2011).
* ``NSWGraph``      — greedy beam search over a kNN graph (the navigable-
                      small-world core of HNSW, single layer).

All searches are jit-compiled with static shapes (clusters padded to the max
list length; beam frontiers fixed-width) — the TPU-idiomatic formulation of
the same algorithms.

Every searcher implements the ``core/index`` protocol: it registers under a
string key, builds from one config mapping, returns a ``SearchResult`` whose
``comparisons`` field counts original-space distance evaluations (the
paper's implementation-agnostic cost metric), reports ``memory_bytes()``,
and exposes ``shard_state``/``shard_search`` so ``ShardedIndex`` can run it
data-parallel over corpus shards.  The pre-registry entry points (keyword
arguments like ``nprobe=4``) keep working unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import index as index_lib
from repro.core import knn_graph as knn_lib
from repro.core import metrics as metrics_lib
from repro.core import quant as quant_lib
from repro.core import scan as scan_lib
from repro.core import telemetry as telem
from repro.core.index import SearchResult


# ---------------------------------------------------------------------------
# brute force
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "metric", "block", "impl"))
@telem.stage_scope("scan")
def brute_force(
    X: jax.Array, Q: jax.Array, *, k: int = 1, metric: str = "euclidean",
    block: int = 0, impl: str = "jnp", valid: Optional[jax.Array] = None,
) -> SearchResult:
    """Exact search. Returns SearchResult (idx (B,k), dist (B,k), comps (B,)).

    Streams over X through ``core/scan`` — the (B, n) score matrix is never
    materialized, so ground truth stays computable when n no longer fits.
    ``valid`` (n,) bool restricts candidates (filtered search): the scan
    masks non-passing rows to +inf, so the answer is bit-identical to a
    brute scan over the pre-filtered sub-corpus (same per-pair distance
    arithmetic, same ascending-index tie order), and comparisons count the
    passing rows actually scored."""
    dists, idx = scan_lib.topk_scan(
        Q, X, k=k, metric=metric, impl=impl,
        block=block or scan_lib.DEFAULT_BLOCK, valid=valid,
    )
    if valid is None:
        comps = jnp.full((Q.shape[0],), X.shape[0], jnp.int32)
    else:
        comps = jnp.broadcast_to(
            jnp.sum(valid).astype(jnp.int32), (Q.shape[0],)
        )
    return SearchResult(idx, dists, comps)


@functools.partial(
    jax.jit, static_argnames=("k", "K", "metric", "block", "impl")
)
@telem.stage_scope("scan")
def _brute_quant_search(
    Q, codes, scales, sqnorms, X, *, k, K, metric, block, impl, valid=None,
) -> SearchResult:
    """Quantized two-stage brute scan: first pass over int8 codes keeps the
    ``K = quant.shortlist_width(k, n)`` best, the shortlist is re-scored
    exactly in f32 (``topk_candidates``) and the best k survive.  The full
    corpus is read at 1 byte/dim; f32 rows are touched only for the K
    shortlisted candidates.  Comparisons count both stages: n code scores
    (sum of the mask under a filter) + K exact re-scores."""
    qd, qpos = scan_lib.topk_scan_quant(
        Q, codes, scales, k=K, metric=metric, impl=impl,
        block=block or scan_lib.DEFAULT_BLOCK, valid=valid, sqnorms=sqnorms,
    )
    idx, dists = jax.vmap(
        lambda q, c: scan_lib.topk_candidates(q, c, X, k=k, metric=metric)
    )(Q, qpos)
    if valid is None:
        scanned = jnp.int32(codes.shape[0])
    else:
        scanned = jnp.sum(valid).astype(jnp.int32)
    comps = jnp.broadcast_to(scanned + K, (Q.shape[0],))
    return SearchResult(idx.astype(jnp.int32), dists, comps)


@index_lib.register_index("brute")
@dataclasses.dataclass
class BruteIndex:
    """The exact oracle behind the uniform contract (budget is ignored —
    a brute scan always pays n comparisons per query).  With a ``quant``
    store attached (the registry's ``quant`` cfg key) the scan becomes the
    quantized two-stage: int8 first pass, exact f32 rerank of the pow2
    shortlist — recall >= 0.99 at a quarter of the scanned bytes."""

    X: jax.Array
    metric: str = "euclidean"
    impl: str = "jnp"
    block: int = 0
    search_defaults: dict = dataclasses.field(default_factory=dict)
    quant: Optional[quant_lib.QuantStore] = None

    #: ShardedIndex may hand this engine per-shard code slices
    shard_supports_quant = True

    @classmethod
    def build(
        cls, X: jax.Array, *, metric: str = "euclidean", impl: str = "jnp",
        block: int = 0,
    ) -> "BruteIndex":
        return cls(X=jnp.asarray(X, jnp.float32), metric=metric, impl=impl, block=block)

    def search(self, Q: jax.Array, k: int = 1, *, budget: Optional[int] = None,
               filter=None) -> SearchResult:
        from repro.core import filter as filter_lib

        filter = index_lib.resolve(filter, self.search_defaults, "filter")
        mask = filter_lib.resolve_mask(
            filter, getattr(self, "attrs", None), self.X.shape[0]
        )
        Q = jnp.asarray(Q, jnp.float32)
        k = int(k)
        with telem.span("scan", engine="brute"):
            if self.quant is not None:
                codes, scales, sqnorms = self.quant.device_view()
                return _brute_quant_search(
                    Q, codes, scales, sqnorms, self.X, k=k,
                    K=quant_lib.shortlist_width(k, self.X.shape[0]),
                    metric=self.metric, block=self.block, impl=self.impl,
                    valid=mask,
                )
            return brute_force(
                self.X, Q, k=k, metric=self.metric,
                block=self.block, impl=self.impl, valid=mask,
            )

    def memory_bytes(self) -> int:
        return index_lib.pytree_nbytes(self.X) + index_lib.side_store_bytes(self)

    # -------------------------------------------------------------- snapshot
    def snapshot_state(self):
        return {"X": self.X}, {
            "metric": self.metric, "impl": self.impl, "block": self.block,
            "search_defaults": self.search_defaults,
        }

    @classmethod
    def from_snapshot(cls, arrays, statics) -> "BruteIndex":
        return cls(
            X=jnp.asarray(arrays["X"], jnp.float32), metric=statics["metric"],
            impl=statics["impl"], block=int(statics["block"]),
            search_defaults=dict(statics.get("search_defaults") or {}),
        )

    # -------------------------------------------------------------- sharding
    def shard_state(self):
        return {"X": self.X}, {"metric": self.metric, "impl": self.impl, "block": self.block}

    @classmethod
    def shard_search(cls, state, Q, *, k, budget, static, valid=None,
                     quant=None):
        if quant is not None:
            codes, scales, sqnorms = quant
            res = _brute_quant_search(
                Q, codes, scales, sqnorms, state["X"], k=k,
                K=quant_lib.shortlist_width(k, state["X"].shape[0]),
                metric=static["metric"], block=static["block"],
                impl=static["impl"], valid=valid,
            )
        else:
            res = brute_force(
                state["X"], Q, k=k, metric=static["metric"],
                block=static["block"], impl=static["impl"], valid=valid,
            )
        return res.idx, res.dist, res.comparisons


# ---------------------------------------------------------------------------
# k-means (shared by IVF variants)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_clusters", "iters", "metric"))
def kmeans(
    X: jax.Array, *, num_clusters: int, iters: int = 10, metric: str = "sqeuclidean",
    seed: int = 0,
):
    """Lloyd's algorithm; returns (centroids (C, d), assignment (n,))."""
    n = X.shape[0]
    key = jax.random.PRNGKey(seed)
    init_idx = jax.random.choice(key, n, (num_clusters,), replace=False)
    cents = X[init_idx]

    def body(_, cents):
        D = metrics_lib.pairwise(X, cents, metric=metric)
        assign = jnp.argmin(D, axis=1)
        one_hot = jax.nn.one_hot(assign, num_clusters, dtype=X.dtype)
        sums = one_hot.T @ X
        counts = jnp.sum(one_hot, axis=0)[:, None]
        new = sums / jnp.maximum(counts, 1.0)
        return jnp.where(counts > 0, new, cents)

    cents = jax.lax.fori_loop(0, iters, body, cents)
    assign = jnp.argmin(metrics_lib.pairwise(X, cents, metric=metric), axis=1)
    return cents, assign


def _build_lists(assign: np.ndarray, num_clusters: int) -> tuple[np.ndarray, np.ndarray]:
    """Padded inverted lists: (C, Lmax) member indices (-1 pad) + lengths."""
    lists = [np.where(assign == c)[0] for c in range(num_clusters)]
    lmax = max(1, max(len(l) for l in lists))
    padded = np.full((num_clusters, lmax), -1, np.int32)
    lens = np.zeros((num_clusters,), np.int32)
    for c, l in enumerate(lists):
        padded[c, : len(l)] = l
        lens[c] = len(l)
    return padded, lens


def _resolve_nprobe(
    nprobe: Optional[int], budget: Optional[int], *, n: int, num_clusters: int,
    default: int = 4,
) -> int:
    """The one IVF probe policy (instance AND shard paths, Flat AND PQ):
    explicit nprobe wins; else a comparison budget converts via "probing one
    list costs ~n/C scored candidates" -> nprobe = clamp(budget·C/n, 1, C);
    else ``default``.  Always clamped to [1, C]."""
    if nprobe is None and budget is not None:
        per_list = max(1, -(-n // num_clusters))
        nprobe = int(budget) // per_list
    if nprobe is None:
        nprobe = default
    return max(1, min(num_clusters, int(nprobe)))


# ---------------------------------------------------------------------------
# IVF-Flat
# ---------------------------------------------------------------------------

@index_lib.register_index("ivf_flat")
@dataclasses.dataclass
class IVFFlat:
    """k-means coarse quantizer + probed exact scoring (FAISS IVF-Flat
    semantics); nprobe trades recall for comparisons.  With a ``quant``
    store attached, probed members are first scored on int8 codes and only
    the pow2 shortlist is re-scored in f32 (IVFFlat -> IVF-SQ8, roughly)."""

    X: jax.Array
    centroids: jax.Array
    lists: jax.Array  # (C, Lmax) int32, -1 padded
    list_lens: jax.Array
    metric: str
    search_defaults: dict = dataclasses.field(default_factory=dict)
    quant: Optional[quant_lib.QuantStore] = None

    #: ShardedIndex may hand this engine per-shard code slices
    shard_supports_quant = True

    @classmethod
    def build(
        cls, X: jax.Array, *, num_clusters: int = 64, iters: int = 10,
        metric: str = "euclidean", seed: int = 0,
    ) -> "IVFFlat":
        X = jnp.asarray(X, jnp.float32)
        cents, assign = kmeans(X, num_clusters=num_clusters, iters=iters, seed=seed)
        lists, lens = _build_lists(np.asarray(assign), num_clusters)
        return cls(X=X, centroids=cents, lists=jnp.asarray(lists),
                   list_lens=jnp.asarray(lens), metric=metric)

    def search(
        self, Q: jax.Array, k: int = 1, *, nprobe: Optional[int] = None,
        budget: Optional[int] = None, filter=None,
    ) -> SearchResult:
        from repro.core import filter as filter_lib

        nprobe = _resolve_nprobe(
            index_lib.resolve(nprobe, self.search_defaults, "nprobe"),
            index_lib.resolve(budget, self.search_defaults, "budget"),
            n=self.X.shape[0], num_clusters=self.centroids.shape[0],
        )
        filter = index_lib.resolve(filter, self.search_defaults, "filter")
        mask = filter_lib.resolve_mask(
            filter, getattr(self, "attrs", None), self.X.shape[0]
        )
        idx, dist, comps = _ivf_flat_search(
            self.X, self.centroids, self.lists, self.list_lens,
            jnp.asarray(Q, jnp.float32), k=int(k), nprobe=nprobe,
            metric=self.metric, valid=mask, quant=self._quant_view(),
        )
        return SearchResult(idx, dist, comps)

    def _quant_view(self):
        if self.quant is None:
            return None
        codes, scales, _ = self.quant.device_view()
        return codes, scales

    def memory_bytes(self) -> int:
        return index_lib.pytree_nbytes(
            (self.X, self.centroids, self.lists, self.list_lens)
        ) + index_lib.side_store_bytes(self)

    # -------------------------------------------------------------- snapshot
    def snapshot_state(self):
        return (
            {"X": self.X, "centroids": self.centroids, "lists": self.lists,
             "list_lens": self.list_lens},
            {"metric": self.metric, "search_defaults": self.search_defaults},
        )

    @classmethod
    def from_snapshot(cls, arrays, statics) -> "IVFFlat":
        return cls(
            X=jnp.asarray(arrays["X"], jnp.float32),
            centroids=jnp.asarray(arrays["centroids"], jnp.float32),
            lists=jnp.asarray(arrays["lists"], jnp.int32),
            list_lens=jnp.asarray(arrays["list_lens"], jnp.int32),
            metric=statics["metric"],
            search_defaults=dict(statics.get("search_defaults") or {}),
        )

    # -------------------------------------------------------------- sharding
    def shard_state(self):
        sd = self.search_defaults or {}
        static = {"metric": self.metric, "nprobe": sd.get("nprobe"),
                  "budget": sd.get("budget")}
        return (
            {"X": self.X, "centroids": self.centroids, "lists": self.lists,
             "list_lens": self.list_lens},
            static,
        )

    @classmethod
    def shard_search(cls, state, Q, *, k, budget, static, valid=None,
                     quant=None):
        nprobe = _resolve_nprobe(
            static.get("nprobe"), budget if budget is not None else static.get("budget"),
            n=state["X"].shape[0], num_clusters=state["centroids"].shape[0],
        )
        return _ivf_flat_search(
            state["X"], state["centroids"], state["lists"], state["list_lens"],
            Q, k=k, nprobe=nprobe, metric=static["metric"], valid=valid,
            quant=None if quant is None else quant[:2],
        )


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "metric"))
def _ivf_flat_search(X, cents, lists, lens, Q, *, k, nprobe, metric, valid=None,
                     quant=None):
    B = Q.shape[0]
    Dc = metrics_lib.pairwise(Q, cents, metric=metric)
    _, probe = jax.lax.top_k(-Dc, nprobe)  # (B, nprobe)
    cand = lists[probe].reshape(B, -1)  # (B, nprobe * Lmax)
    if valid is not None:
        # filtered search: non-passing members become -1 padding BEFORE the
        # scan, so mask composition is filter ∧ list-validity and the
        # comparison count below only pays for rows actually scored
        cand = jnp.where(valid[jnp.maximum(cand, 0)] & (cand >= 0), cand, -1)
    ok = cand >= 0
    # quantized probing: gathered members score on int8 codes first, then
    # only the pow2 shortlist touches f32 rows (the rerank-width rule);
    # both stages land in the comparison count.  When the width already
    # covers every gathered candidate the code pass could not shrink
    # anything — skip it (same guard as the infinity rerank prefilter).
    K = 0
    if quant is not None:
        w = quant_lib.shortlist_width(k, X.shape[0])
        if w < int(cand.shape[1]):
            K = w

    def per_query(q, c, v):
        nv = jnp.sum(v).astype(jnp.int32)
        if K:
            codes, scales = quant
            c, _ = scan_lib.quant_candidates(
                q, c, codes, scales, k=K, metric=metric
            )
            nv = nv + K
        # probed-list scoring routes through the scan engine; the padded
        # slots are masked inside the merge
        idx, d = scan_lib.topk_candidates(q, c, X, k=k, metric=metric)
        return idx, d, nv

    idx, dist, comps = jax.vmap(per_query)(Q, cand, ok)
    return idx.astype(jnp.int32), dist, comps


# ---------------------------------------------------------------------------
# IVF-PQ (ADC)
# ---------------------------------------------------------------------------

@index_lib.register_index("ivf_pq")
@dataclasses.dataclass
class IVFPQ:
    """IVF + product quantization with ADC lookup tables (Jégou et al.
    2011); optional exact rerank of the ADC shortlist."""

    X: jax.Array
    centroids: jax.Array  # coarse (C, d)
    codebooks: jax.Array  # (M, 256sub, dsub)
    codes: jax.Array  # (n, M) uint8-as-int32 PQ codes of residuals
    lists: jax.Array
    list_lens: jax.Array
    metric: str
    search_defaults: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def build(
        cls, X: jax.Array, *, num_clusters: int = 64, M: int = 8, ksub: int = 32,
        iters: int = 10, metric: str = "euclidean", seed: int = 0,
    ) -> "IVFPQ":
        """PQ on residuals (x - coarse centroid), M subspaces, ksub centroids
        per subspace (<= 256)."""
        X = jnp.asarray(X, jnp.float32)
        n, d = X.shape
        assert d % M == 0, (d, M)
        dsub = d // M
        cents, assign = kmeans(X, num_clusters=num_clusters, iters=iters, seed=seed)
        resid = X - cents[assign]
        sub = resid.reshape(n, M, dsub)
        books, codes = [], []
        for m in range(M):
            cb, cd = kmeans(sub[:, m], num_clusters=ksub, iters=iters, seed=seed + m + 1)
            books.append(cb)
            codes.append(cd)
        lists, lens = _build_lists(np.asarray(assign), num_clusters)
        return cls(
            X=X, centroids=cents, codebooks=jnp.stack(books),
            codes=jnp.stack(codes, axis=1).astype(jnp.int32),
            lists=jnp.asarray(lists), list_lens=jnp.asarray(lens), metric=metric,
        )

    def search(
        self, Q: jax.Array, k: int = 1, *, nprobe: Optional[int] = None,
        rerank: Optional[int] = None, budget: Optional[int] = None,
        filter=None,
    ) -> SearchResult:
        from repro.core import filter as filter_lib

        nprobe = _resolve_nprobe(
            index_lib.resolve(nprobe, self.search_defaults, "nprobe"),
            index_lib.resolve(budget, self.search_defaults, "budget"),
            n=self.X.shape[0], num_clusters=self.centroids.shape[0],
        )
        rerank = int(index_lib.resolve(rerank, self.search_defaults, "rerank", 0))
        filter = index_lib.resolve(filter, self.search_defaults, "filter")
        mask = filter_lib.resolve_mask(
            filter, getattr(self, "attrs", None), self.X.shape[0]
        )
        idx, dist, comps = _ivf_pq_search(
            self.X, self.centroids, self.codebooks, self.codes, self.lists,
            jnp.asarray(Q, jnp.float32), k=int(k), nprobe=nprobe, rerank=rerank,
            metric=self.metric, valid=mask,
        )
        return SearchResult(idx, dist, comps)

    def memory_bytes(self) -> int:
        return index_lib.pytree_nbytes(
            (self.X, self.centroids, self.codebooks, self.codes, self.lists, self.list_lens)
        ) + index_lib.side_store_bytes(self)

    # -------------------------------------------------------------- snapshot
    def snapshot_state(self):
        return (
            {"X": self.X, "centroids": self.centroids, "codebooks": self.codebooks,
             "codes": self.codes, "lists": self.lists, "list_lens": self.list_lens},
            {"metric": self.metric, "search_defaults": self.search_defaults},
        )

    @classmethod
    def from_snapshot(cls, arrays, statics) -> "IVFPQ":
        return cls(
            X=jnp.asarray(arrays["X"], jnp.float32),
            centroids=jnp.asarray(arrays["centroids"], jnp.float32),
            codebooks=jnp.asarray(arrays["codebooks"], jnp.float32),
            codes=jnp.asarray(arrays["codes"], jnp.int32),
            lists=jnp.asarray(arrays["lists"], jnp.int32),
            list_lens=jnp.asarray(arrays["list_lens"], jnp.int32),
            metric=statics["metric"],
            search_defaults=dict(statics.get("search_defaults") or {}),
        )

    # -------------------------------------------------------------- sharding
    def shard_state(self):
        sd = self.search_defaults or {}
        static = {"metric": self.metric, "nprobe": sd.get("nprobe"),
                  "rerank": int(sd.get("rerank") or 0), "budget": sd.get("budget")}
        return (
            {"X": self.X, "centroids": self.centroids, "codebooks": self.codebooks,
             "codes": self.codes, "lists": self.lists, "list_lens": self.list_lens},
            static,
        )

    @classmethod
    def shard_search(cls, state, Q, *, k, budget, static, valid=None):
        nprobe = _resolve_nprobe(
            static.get("nprobe"), budget if budget is not None else static.get("budget"),
            n=state["X"].shape[0], num_clusters=state["centroids"].shape[0],
        )
        return _ivf_pq_search(
            state["X"], state["centroids"], state["codebooks"], state["codes"],
            state["lists"], Q, k=k, nprobe=nprobe,
            rerank=int(static.get("rerank") or 0), metric=static["metric"],
            valid=valid,
        )


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "rerank", "metric"))
def _ivf_pq_search(X, cents, books, codes, lists, Q, *, k, nprobe, rerank, metric,
                   valid=None):
    """ADC: per (query, probed cluster) LUT of query-residual -> subspace
    centroid sq-distances; candidate distance = sum of LUT entries."""
    B, d = Q.shape
    M, ksub, dsub = books.shape
    if valid is not None:
        # filtered search: drop non-passing members to -1 padding at the
        # source, so ADC scoring, the comparison count and the rerank
        # shortlist all see only passing rows
        lists = jnp.where(valid[jnp.maximum(lists, 0)] & (lists >= 0), lists, -1)
    Dc = metrics_lib.pairwise(Q, cents, metric="sqeuclidean")
    _, probe = jax.lax.top_k(-Dc, nprobe)  # (B, nprobe)

    def per_query(q, probes):
        def per_cluster(c):
            r = (q - cents[c]).reshape(M, dsub)  # query residual
            # LUT (M, ksub): ||r_m - codebook[m, j]||^2
            lut = jnp.sum((r[:, None, :] - books) ** 2, axis=-1)
            members = lists[c]  # (Lmax,)
            mcodes = codes[jnp.maximum(members, 0)]  # (Lmax, M)
            adc = jnp.sum(lut[jnp.arange(M)[None, :], mcodes], axis=-1)
            adc = jnp.where(members >= 0, adc, jnp.inf)
            return members, adc

        mem, adc = jax.vmap(per_cluster)(probes)  # (nprobe, Lmax)
        mem = mem.reshape(-1)
        adc = adc.reshape(-1)
        kk = max(k, rerank)
        neg, pos = jax.lax.top_k(-adc, kk)
        cand = mem[pos]
        comps = jnp.sum(jnp.isfinite(adc)).astype(jnp.int32)
        if rerank:
            # exact re-scoring of the ADC shortlist via the scan engine
            idx2, dex = scan_lib.topk_candidates(q, cand, X, k=k, metric=metric)
            return idx2, dex, comps
        return cand[:k], -neg[:k], comps

    idx, dist, comps = jax.vmap(per_query)(Q, probe)
    return idx.astype(jnp.int32), dist, comps


# ---------------------------------------------------------------------------
# NSW graph beam search
# ---------------------------------------------------------------------------

@index_lib.register_index("nsw")
@dataclasses.dataclass
class NSWGraph:
    """Greedy beam search over a kNN graph with random long-range links
    (the navigable-small-world core of HNSW, single layer)."""

    X: jax.Array
    neighbors: jax.Array  # (n, deg) int32
    metric: str
    entry: int
    search_defaults: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def build(
        cls, X: jax.Array, *, degree: int = 16, random_links: int = 4,
        metric: str = "euclidean", seed: int = 0,
    ) -> "NSWGraph":
        """kNN edges + a few random long-range links per node — the
        small-world shortcut that lets greedy search hop between clusters
        (HNSW gets this from its upper layers)."""
        X = jnp.asarray(X, jnp.float32)
        idx, _ = knn_lib.knn_graph(X, k=degree, metric=metric)
        rng = np.random.default_rng(seed)
        if random_links > 0:
            extra = rng.integers(0, X.shape[0], size=(X.shape[0], random_links))
            idx = jnp.concatenate([idx, jnp.asarray(extra, jnp.int32)], axis=1)
        return cls(X=X, neighbors=idx, metric=metric, entry=int(rng.integers(X.shape[0])))

    def search(
        self, Q: jax.Array, k: int = 1, *, ef: Optional[int] = None,
        max_steps: Optional[int] = None, budget: Optional[int] = None,
        filter=None,
    ) -> SearchResult:
        from repro.core import filter as filter_lib

        ef, max_steps = self._resolve_beam(
            int(k),
            index_lib.resolve(ef, self.search_defaults, "ef"),
            index_lib.resolve(max_steps, self.search_defaults, "max_steps"),
            index_lib.resolve(budget, self.search_defaults, "budget"),
            deg=self.neighbors.shape[1],
        )
        filter = index_lib.resolve(filter, self.search_defaults, "filter")
        mask = filter_lib.resolve_mask(
            filter, getattr(self, "attrs", None), self.X.shape[0]
        )
        idx, dist, comps = _nsw_search(
            self.X, self.neighbors, jnp.asarray(Q, jnp.float32),
            jnp.int32(self.entry), k=int(k), ef=ef, max_steps=max_steps,
            metric=self.metric, valid=mask,
        )
        return SearchResult(idx, dist, comps)

    @staticmethod
    def _resolve_beam(k, ef, max_steps, budget, *, deg) -> tuple[int, int]:
        """The one beam policy (instance AND shard paths): explicit knobs
        win; else a budget converts via "each expansion scores <= deg fresh
        neighbors" -> max_steps = budget/deg."""
        ef = 32 if ef is None else int(ef)
        if max_steps is None and budget is not None:
            max_steps = max(1, int(budget) // max(1, deg))
        return max(ef, int(k)), int(max_steps if max_steps is not None else 64)

    def memory_bytes(self) -> int:
        return index_lib.pytree_nbytes(
            (self.X, self.neighbors)
        ) + index_lib.side_store_bytes(self)

    # -------------------------------------------------------------- snapshot
    def snapshot_state(self):
        return (
            {"X": self.X, "neighbors": self.neighbors},
            {"metric": self.metric, "entry": int(self.entry),
             "search_defaults": self.search_defaults},
        )

    @classmethod
    def from_snapshot(cls, arrays, statics) -> "NSWGraph":
        return cls(
            X=jnp.asarray(arrays["X"], jnp.float32),
            neighbors=jnp.asarray(arrays["neighbors"], jnp.int32),
            metric=statics["metric"], entry=int(statics["entry"]),
            search_defaults=dict(statics.get("search_defaults") or {}),
        )

    # -------------------------------------------------------------- sharding
    def shard_state(self):
        sd = self.search_defaults or {}
        static = {"metric": self.metric, "ef": sd.get("ef"),
                  "max_steps": sd.get("max_steps"), "budget": sd.get("budget")}
        return (
            {"X": self.X, "neighbors": self.neighbors,
             "entry": jnp.int32(self.entry)},
            static,
        )

    @classmethod
    def shard_search(cls, state, Q, *, k, budget, static, valid=None):
        ef, max_steps = cls._resolve_beam(
            k, static.get("ef"), static.get("max_steps"),
            budget if budget is not None else static.get("budget"),
            deg=state["neighbors"].shape[1],
        )
        return _nsw_search(
            state["X"], state["neighbors"], Q, state["entry"], k=k,
            ef=ef, max_steps=max_steps, metric=static["metric"], valid=valid,
        )


@functools.partial(jax.jit, static_argnames=("k", "ef", "max_steps", "metric"))
def _nsw_search(X, neighbors, Q, entry, *, k, ef, max_steps, metric, valid=None):
    """Greedy best-first beam (HNSW layer-0 semantics, fixed iteration count).

    Frontier = ef best visited nodes; each step expands the best unexpanded
    node's neighbor list.  Visited set is a dense (n,) bool row per query —
    fine at benchmark scale, and fully vectorized on TPU.  ``entry`` is a
    traced int32 scalar so per-shard entry points ride along as data.

    ``valid`` (n,) bool gives filtered-graph-search semantics: the beam
    NAVIGATES over every node — restricting the graph itself to passing
    nodes would disconnect it under narrow filters — while a separate
    result buffer collects the best passing nodes seen.  Each node's
    distance is evaluated exactly once (the visited set), so a node enters
    the result buffer at most once and comps counts every evaluation
    regardless of whether the node passes.
    """
    n, deg = neighbors.shape
    pair = metrics_lib.pair_fn(metric)
    entry = entry.astype(jnp.int32)

    def per_query(q):
        d0 = pair(q, X[entry])
        cand_i = jnp.full((ef,), -1, jnp.int32).at[0].set(entry)
        cand_d = jnp.full((ef,), jnp.inf, jnp.float32).at[0].set(d0)
        expanded = jnp.zeros((ef,), bool)
        visited = jnp.zeros((n,), bool).at[entry].set(True)
        comps = jnp.int32(1)
        if valid is None:
            res_i = res_d = None
        else:  # passing-node result buffer, seeded with the entry if it passes
            res_i = jnp.where(valid[entry], cand_i, -1)
            res_d = jnp.where(valid[entry], cand_d, jnp.inf)

        def cond(st):
            cand_i, cand_d, expanded, visited, comps, t, *_ = st
            has_unexpanded = jnp.any((cand_i >= 0) & ~expanded)
            return has_unexpanded & (t < max_steps)

        def body(st):
            cand_i, cand_d, expanded, visited, comps, t, *res = st
            d_mask = jnp.where((cand_i >= 0) & ~expanded, cand_d, jnp.inf)
            b = jnp.argmin(d_mask)
            node = cand_i[b]
            expanded = expanded.at[b].set(True)
            nbrs = neighbors[jnp.maximum(node, 0)]  # (deg,)
            fresh = ~visited[nbrs]
            # a neighbor row can list the same node twice (a random long
            # link duplicating a kNN edge): only the FIRST occurrence is
            # fresh, else the duplicate enters the frontier/result twice,
            # double-counts comps, and can evict a true neighbor.  deg is
            # small, so the O(deg^2) first-occurrence mask is free.
            pos = jnp.arange(deg)
            earlier_dup = jnp.any(
                (nbrs[None, :] == nbrs[:, None]) & (pos[None, :] < pos[:, None]),
                axis=1,
            )
            fresh = fresh & ~earlier_dup
            visited = visited.at[nbrs].set(True)
            nd = jax.vmap(lambda j: pair(q, X[j]))(nbrs)
            nd = jnp.where(fresh, nd, jnp.inf)
            comps = comps + jnp.sum(fresh).astype(jnp.int32)
            if valid is not None:
                # fresh AND passing neighbors join the result buffer (their
                # one-and-only distance evaluation happened just above)
                res_i, res_d = res
                rd = jnp.concatenate(
                    [res_d, jnp.where(valid[nbrs], nd, jnp.inf)]
                )
                ri = jnp.concatenate([res_i, nbrs])
                keep = jnp.argsort(rd)[:ef]
                res = (ri[keep], rd[keep])
            # merge into frontier: keep ef best, preserving expansion flags
            all_i = jnp.concatenate([cand_i, nbrs])
            all_d = jnp.concatenate([cand_d, nd])
            all_e = jnp.concatenate([expanded, jnp.zeros((deg,), bool)])
            order = jnp.argsort(all_d)[:ef]
            return (all_i[order], all_d[order], all_e[order], visited, comps,
                    t + 1, *res)

        init = (cand_i, cand_d, expanded, visited, comps, jnp.int32(0))
        if valid is not None:
            init = init + (res_i, res_d)
        out = jax.lax.while_loop(cond, body, init)
        if valid is None:
            cand_i, cand_d = out[0], out[1]
        else:  # answers come from the passing-node buffer, not the frontier
            cand_i, cand_d = out[6], out[7]
            cand_i = jnp.where(jnp.isinf(cand_d), -1, cand_i)
        return cand_i[:k], cand_d[:k], out[4]

    return jax.vmap(per_query)(Q)
