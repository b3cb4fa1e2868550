"""The control, and the faults planted under the timed path.

The control is the reference put in the program's place, one precision
lower.

The configurations state float32 with matmuls at ``Precision.HIGHEST``;
the nearest precision below is ``HIGH``, three bf16 passes.  ``high_dot``
computes it explicitly (each float32 operand split into a bf16 high part
and a bf16 low part, the three larger products summed in float32), so the
control reads the same on the chip and on a CPU, where XLA ignores the
precision flag.  ``install`` swaps the server's engine for an exact scan
in that precision; everything else in the run (runtime, batching, padding,
the comparison) stays as it is.

The faults break the approximate engine's traversal and leave every
answer a valid id with its true distance, so that only the recall floor
can catch them: ``negated_embedding`` embeds each query as the negation of
its Phi image, which steers the traversal away from its neighbours;
``cut_budget`` gives the traversal a sixteenth of the configured budget.

  python chipbench/control.py --workload deep10m-brute.steady \
      --seeds 5,6,7 --seconds 5 [--fault control]

prints the compared numbers of each seed, one JSON line each.  The
benchmark's own runs never run it.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench.reference import exact_topk  # noqa: E402


def _split(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def high_dot(a, b):
    """``a @ b.T`` in three bf16 passes, accumulated in float32."""
    ah, al = _split(a)
    bh, bl = _split(b)
    f = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    return f(ah, bh.T) + (f(ah, bl.T) + f(al, bh.T))


class HighScan:
    """An engine with the server's ``search`` contract: exact top-k in
    three bf16 passes."""

    def __init__(self, X):
        self.X = X

    def search(self, Q, k=10, *, budget=None, filter=None, **kw):
        dist, idx = exact_topk(np.asarray(Q), self.X, int(k), dot=high_dot)
        comps = np.full((Q.shape[0],), self.X.shape[0], np.int32)
        return idx, dist, comps


#: the control needs no engine build: the server is made over the brute
#: engine, whose index is then replaced
CONFIG_OVERRIDE = {"engine": "brute", "engine_cfg": {}}


def install(system) -> None:
    system.server.index = HighScan(system.server.corpus)


def negate_embedding(system) -> None:
    """Phi's last layer negated on the built index: queries embed to the
    far side of the tree; the corpus embedding is left as built."""
    index = system.server.index
    params = dict(index.phi_params)
    last = {key: -v for key, v in params["layers"][-1].items()}
    params["layers"] = list(params["layers"][:-1]) + [last]
    object.__setattr__(index, "phi_params", params)


#: the share of the configured budget that ``cut_budget`` leaves
BUDGET_CUT = 16


def cut_budget(system) -> None:
    """Every search gets a sixteenth of the budget it was asked for."""
    index = system.server.index
    inner = index.search

    def search(Q, k=1, **kw):
        for key in ("budget", "max_comparisons"):
            if kw.get(key) is not None:
                kw[key] = max(1, int(kw[key]) // BUDGET_CUT)
        return inner(Q, k, **kw)

    object.__setattr__(index, "search", search)


#: name -> (program hook, configuration override)
FAULTS = {
    "control": (install, CONFIG_OVERRIDE),
    "negated_embedding": (negate_embedding, None),
    "cut_budget": (cut_budget, None),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), default="control")
    args = ap.parse_args(argv)
    hook, override = FAULTS[args.fault]

    from chipbench import harness

    for s in [int(x) for x in args.seeds.split(",") if x]:
        res = harness.run_cell(ROOT, args.workload, s, args.seconds, False,
                               t_process=time.monotonic(),
                               program_hook=hook, config_override=override)
        print(json.dumps({"fault": args.fault, "seed": s,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "answered": res["load"]["outcomes"].get("ok", 0),
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
