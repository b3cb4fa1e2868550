"""Policy shared by every kernel package: the kernels run compiled on TPU
and interpreted elsewhere, decided here so the rule lives in one place.
"""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Kernels compile through Mosaic on TPU, interpret everywhere else.
    Called per kernel call, never at import: asking for the backend starts
    it, and a process must be able to import the kernels before it does."""
    return jax.default_backend() != "tpu"
