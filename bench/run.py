"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for. It needs a TPU: on any other platform, or with fewer
chips than the cell asks for, it exits nonzero and prints no result.
Set-up (imports, compile-cache loading, the warm-up pass of the cell's
own traffic) is ``setup_s``; then the window runs for ``--seconds``.
With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
a slice of the window. The last line of standard output is the result
object; the numbers of the correctness check, each beside its limit,
are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: no program under {ROOT}/src/repro; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import harness
    try:
        cell = harness.find_cell(args.workload)
        line = harness.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), t_start=T_START)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
