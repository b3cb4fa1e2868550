"""Seeded corpus and query generators, made on the device.

``manifold`` follows ``manifold`` of the program's ``data/synthetic.py``
(a low-dimensional clustered latent through a fixed random two-layer
decoder, plus small ambient noise), drawn with ``jax.random`` so that a
10M x 96 corpus is made on the chip in one jitted call instead of on the
host.  The decoder is fixed across seeds; the latents, cluster labels and
noise come from the run's seed.  Rows are made in blocks written in
place, so the call's peak is the corpus plus one block.
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


def key_from_seed(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative integer seed.  ``jax.random.key``
    keeps only the low 32 bits of a large seed; a SeedSequence keeps all of
    them.  ``stream`` separates independent draws of one run."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def _block_rows(n: int, cap: int = BLOCK_ROWS) -> int:
    """The largest divisor of ``n`` that is at most ``cap``."""
    for m in range(-(-n // cap), n + 1):
        if n % m == 0:
            return n // m
    return n


@functools.partial(jax.jit, static_argnames=("n", "d", "latent",
                                             "num_clusters", "noise"))
def manifold(key, *, n: int, d: int = 96, latent: int = 12,
             num_clusters: int = 20, noise: float = 0.02) -> jax.Array:
    """(n, d) float32 rows on the manifold, on the default device."""
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
        return jax.lax.dynamic_update_slice_in_dim(out, block(b), b * bn, 0)

    return jax.lax.fori_loop(0, n // bn, body, jnp.zeros((n, d), jnp.float32))


GENERATORS = {"manifold": manifold}


def make(name: str, seed: int, stream: int, n: int, **kw) -> jax.Array:
    """``n`` rows of generator ``name`` from ``(seed, stream)``."""
    return GENERATORS[name](key_from_seed(seed, stream), n=n, **kw)

