"""Run one cell of the chip benchmark once, on the machine it starts on.

  python chipbench/run.py --workload deep1m-inf.steady --seed 7 \
      --seconds 20 --trace 0

Makes the cell's corpus and query pool from ``--seed`` on the device,
builds the system from the cell's configuration, warms every batch shape
the traffic can form, drives the traffic for ``--seconds`` and judges every
answer against the benchmark's exact reference.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``; the compared numbers come last, under ``checks``, and again
as the last lines of standard error.  Without a TPU it exits non-zero and
prints no result.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
