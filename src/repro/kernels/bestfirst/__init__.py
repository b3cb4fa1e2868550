"""Best-first VP-tree traversal as one TPU kernel (Algorithm 2)."""
from repro.kernels.bestfirst.bestfirst import View, best_first_pallas, view  # noqa: F401
from repro.kernels.bestfirst.ops import applies, best_first  # noqa: F401
from repro.kernels.bestfirst.ref import best_first_ref  # noqa: F401
