"""Pallas TPU kernels for the compute hot spots (DESIGN.md §4).

qpath — (min, combine) semiring matmul driving the canonical projection.
pdist — tiled pairwise distance matrices (MXU cross-term + fused epilogue).
bag   — embedding-bag gather/reduce with scalar-prefetched indices.
bestfirst — the best-first VP-tree traversal (Algorithm 2), one call a batch.

Each subpackage: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper, backend-resolved interpret flag), ref.py (pure-jnp oracle).
"""
