"""Jit'd public wrapper for the embedding-bag kernel, plus the pure-jnp
segment-sum formulation used by the sharded recsys models (the kernel is the
single-device fast path; the jnp path composes with shard_map)."""
from __future__ import annotations

import jax

from repro.kernels._compat import default_interpret
from repro.kernels.bag.bag import embedding_bag_pallas


def embedding_bag(
    table: jax.Array,
    ids: jax.Array,
    weights: jax.Array | None = None,
    *,
    combine: str = "sum",
    impl: str = "pallas",
) -> jax.Array:
    if impl == "pallas":
        return embedding_bag_pallas(
            table, ids, weights, combine=combine, interpret=default_interpret()
        )
    from repro.kernels.bag.ref import embedding_bag_ref

    return embedding_bag_ref(table, ids, weights, combine=combine)
