"""The kernel's oracle: the XLA loop of ``core/vptree``, Euclidean, no mask."""
from __future__ import annotations


def best_first_ref(tree_arrays, X, queries, max_comparisons, *, q: float,
                   k: int, stack_cap: int):
    from repro.core import vptree  # vptree imports this package

    return vptree._best_first_impl(tree_arrays, X, queries, max_comparisons,
                                   "euclidean", q, k, stack_cap)
