"""Readings that set a configuration's limits: the check's numbers for the
program on many seeds and for the control on a few, in one process and
one set-up, on the chip.

    python3 bench/tools/readings.py --workload paper4_edap_campaign \
        --seconds 10 --seeds 11 12 13 ... --control-seeds 11 12 13

Each seed runs one short window of the cell's own traffic and holds every
answer to the reference (the program's reading). For a control seed the
same answers are then replaced by the reference computed at the next
precision down (bfloat16) and checked again (the control's reading). The
lower reading of a number is the largest over the program's seeds; its
upper reading the smallest over the control's.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax.numpy as jnp
    import ml_dtypes

    import generator
    import harness
    from correct import Reference, check_units, substitute
    cell = harness.find_cell(args.workload)
    harness._devices(int(cell.entry["chips"]), True)
    from repro.api import enable_persistent_cache
    enable_persistent_cache()
    drive = cell.driver
    system = drive.System(cell.config, os.path.join(harness.OUT, cell.name))
    ref_cfg = cell.config["reference"]
    ref = Reference(ref_cfg)
    control = Reference(ref_cfg, cost_dtype=ml_dtypes.bfloat16,
                        acc_dtype=jnp.bfloat16)
    limits = cell.config["limits"]
    try:
        drive.warm(system, cell.mix, args.seeds[0])
        print(json.dumps({"setup_s": time.perf_counter() - T_START}),
              flush=True)
        for seed in args.seeds:
            w = drive.run(system, cell.mix, seed, args.seconds,
                          generator.Tracer(None))
            row = {"seed": seed, "units": len(w.units)}
            v = check_units(ref, w.units, limits)
            row["program"] = {k: c["value"] for k, c in v["checks"].items()}
            row["where"] = v["where"]
            if seed in args.control_seeds:
                vc = check_units(ref, substitute(control, w.units), limits)
                row["control"] = {k: c["value"]
                                  for k, c in vc["checks"].items()}
            print(json.dumps(row), flush=True)
    finally:
        system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
