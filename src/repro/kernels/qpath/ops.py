"""Jit'd public wrapper for the q-path semiring matmul.

On a TPU the kernel compiles through Mosaic; on any other backend it runs in
interpret mode.  The flag is resolved from the backend on every call, so
callers never pass it.
"""
from __future__ import annotations

import jax

from repro.kernels._compat import default_interpret
from repro.kernels.qpath.qpath import qpath_matmul_pallas


def qpath_matmul(A: jax.Array, B: jax.Array, *, mode: str = "minmax") -> jax.Array:
    return qpath_matmul_pallas(A, B, mode=mode, interpret=default_interpret())
