"""Public wrapper of the best-first kernel: where it applies, and the call.

It applies on a TPU, to vectors under the Euclidean metric with at most 128
features and no ``valid`` mask: the infinity engine's traversal of its Phi
embedding.  Elsewhere ``core/vptree`` runs its XLA loop, which is also what
the kernel is tested against.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.core import telemetry as telem
from repro.kernels._compat import default_interpret
from repro.kernels.bestfirst.bestfirst import LANES, View, best_first_pallas, view


def applies(X: Optional[jax.Array], metric: str,
            valid: Optional[jax.Array]) -> bool:
    return (not default_interpret() and X is not None
            and metric == "euclidean" and valid is None
            and X.shape[-1] <= LANES)


@telem.stage_scope("traversal")
def best_first(tree_arrays, X, queries, max_comparisons, *, q: float, k: int,
               stack_cap: int, tv: Optional[View] = None):
    """``tv``: ``view(tree_arrays, X)`` where the caller keeps it, else it
    is built here, a relayout of the tree and corpus on every call."""
    if tv is None:
        tv = view(tree_arrays, X)
    return best_first_pallas(tv, queries, max_comparisons, q=q, k=k,
                             stack_cap=stack_cap,
                             interpret=default_interpret())
