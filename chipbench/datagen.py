"""Seeded corpus and query generators, made on the device.

``manifold`` follows ``manifold`` of the program's ``data/synthetic.py``
(a low-dimensional clustered latent through a fixed random two-layer
decoder, plus small ambient noise), drawn with ``jax.random`` so that a
10M x 96 corpus is made on the chip in one jitted call instead of on the
host.  The decoder is fixed across seeds; the latents, cluster labels and
noise come from the run's seed.  Rows are made in blocks written in
place, so the call's peak is the corpus plus one block.

A corpus may be row-sharded over S devices (``make(..., mesh=
corpus_mesh(n, S, chips))``): each device then makes only its own rows,
``n / S`` of them, under ``shard_map``, so its peak is its shard plus one
block.  The block
size stays that of the whole corpus and block ``b`` is still drawn from
``fold_in(key, b)``, so row ``r`` has the same bits however many shards
there are.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: the decoder's key: fixed, so every seed draws from the same manifold
DECODER_SEED = 99
HIDDEN = 64
#: rows made per step of the generator's loop
BLOCK_ROWS = 131072
#: the name of the corpus mesh's one axis
AXIS = "data"


def key_from_seed(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative integer seed.  ``jax.random.key``
    keeps only the low 32 bits of a large seed; a SeedSequence keeps all of
    them.  ``stream`` separates independent draws of one run."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def _block_rows(n: int) -> int:
    """The largest divisor of ``n`` that is at most ``BLOCK_ROWS``."""
    for m in range(-(-n // BLOCK_ROWS), n + 1):
        if n % m == 0:
            return n // m
    return n


def _manifold_rows(key, *, n: int, rows: int, first=None, d: int,
                   latent: int, num_clusters: int, noise: float) -> jax.Array:
    """``rows`` rows of an ``n``-row corpus, from block ``first`` on (the
    whole corpus from block 0 where ``first`` is None)."""
    wk = jax.random.split(jax.random.key(DECODER_SEED), 3)
    means = jax.random.normal(wk[0], (num_clusters, latent), jnp.float32)
    w1 = jax.random.normal(wk[1], (latent, HIDDEN), jnp.float32) / np.sqrt(latent)
    w2 = jax.random.normal(wk[2], (HIDDEN, d), jnp.float32) / np.sqrt(HIDDEN)
    bn = _block_rows(n)

    def block(b):
        kc, kz, ke = jax.random.split(jax.random.fold_in(key, b), 3)
        lab = jax.random.randint(kc, (bn,), 0, num_clusters)
        z = means[lab] + 0.5 * jax.random.normal(kz, (bn, latent), jnp.float32)
        x = jnp.tanh(z @ w1) @ w2
        return x + noise * jax.random.normal(ke, (bn, d), jnp.float32)

    def body(b, out):
        blk = block(b if first is None else first + b)
        return jax.lax.dynamic_update_slice_in_dim(out, blk, b * bn, 0)

    return jax.lax.fori_loop(0, rows // bn, body,
                             jnp.zeros((rows, d), jnp.float32))


@functools.partial(jax.jit, static_argnames=("n", "d", "latent",
                                             "num_clusters", "noise"))
def manifold(key, *, n: int, d: int = 96, latent: int = 12,
             num_clusters: int = 20, noise: float = 0.02) -> jax.Array:
    """(n, d) float32 rows on the manifold, on the default device."""
    return _manifold_rows(key, n=n, rows=n, d=d, latent=latent,
                          num_clusters=num_clusters, noise=noise)


@functools.partial(jax.jit, static_argnames=("mesh", "n", "d", "latent",
                                             "num_clusters", "noise"))
def manifold_sharded(key, *, mesh, n: int, d: int = 96, latent: int = 12,
                     num_clusters: int = 20, noise: float = 0.02) -> jax.Array:
    """The rows of ``manifold``, row-sharded over ``mesh``'s one axis: each
    device makes its own ``n / S`` rows."""
    from jax.sharding import PartitionSpec as P

    rows = n // mesh.shape[AXIS]
    nblocks = rows // _block_rows(n)

    def shard(key):
        first = jax.lax.axis_index(AXIS) * nblocks
        return _manifold_rows(key, n=n, rows=rows, first=first, d=d,
                              latent=latent, num_clusters=num_clusters,
                              noise=noise)

    return jax.shard_map(shard, mesh=mesh, in_specs=P(), out_specs=P(AXIS),
                         check_vma=False)(key)


GENERATORS = {"manifold": manifold}
SHARDED = {"manifold": manifold_sharded}


def corpus_mesh(n: int, shards: int, chips: int):
    """The ``(AXIS,)`` mesh over the first ``shards`` devices for an
    ``n``-row corpus; raises where the layout cannot hold it."""
    from jax.sharding import Mesh

    devs = jax.devices()
    if shards < 1 or n % shards:
        raise ValueError(f"{n} corpus rows do not divide into {shards} shards")
    if shards > min(chips, len(devs)):
        raise ValueError(f"{shards} shards need {shards} devices; the cell "
                         f"has {chips} chip(s) and JAX reports {len(devs)}")
    bn = _block_rows(n)
    if (n // shards) % bn:
        raise ValueError(
            f"a shard of {n // shards} rows ({n} over {shards}) is not a whole "
            f"number of the generator's {bn}-row blocks")
    return Mesh(np.asarray(devs[:shards]), (AXIS,))


def make(name: str, seed: int, stream: int, n: int, *, mesh=None,
         **kw) -> jax.Array:
    """``n`` rows of generator ``name`` from ``(seed, stream)``: on the
    default device, or row-sharded over ``mesh`` (``corpus_mesh``)."""
    key = key_from_seed(seed, stream)
    if mesh is None:
        return GENERATORS[name](key, n=n, **kw)
    return SHARDED[name](key, mesh=mesh, n=n, **kw)
