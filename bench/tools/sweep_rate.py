"""Find the highest request rate a service cell sustains: one set-up, then
open-loop windows at rising rates, in one process on the chip.

    python3 bench/tools/sweep_rate.py --workload paper4_edap_service \
        --seed 7 --seconds 51 --rates 0.75 1 1.25 1.5 2

Every rate offers the same arrival pattern, scaled to its
rate. For each rate it prints the requests offered and completed, p50/p90
latency from the due time, and how the backlog moved:

* ``open_at_close`` — requests due in the window whose answers had not
  reached the client when it closed;
* ``drain_s`` — from the window's close to the last answer;
* ``latency_slope`` — least-squares slope of latency against due time,
  seconds of latency gained per second of window;
* ``backlog_trend`` — median latency of the window's last third over
  its first third.

A rate is sustained when the backlog does not grow through the window:
the slope stays near 0 and a request or two are open at the close, as
at a low rate. Past the knee the queue grows all through the run: the
slope, the open requests and the drain grow with the window's length.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import numpy as np

    import generator
    import harness
    cell = harness.find_cell(args.workload)
    harness._devices(int(cell.entry["chips"]), True)
    from repro.api import enable_persistent_cache
    enable_persistent_cache()
    drive = cell.driver
    system = drive.System(cell.config, os.path.join(harness.OUT, cell.name))
    try:
        drive.warm(system, cell.mix, args.seed)
        print(json.dumps({"setup_s": time.perf_counter() - T_START}),
              flush=True)
        for rate in args.rates:
            mix = dict(cell.mix, rate_per_s=rate)
            w = drive.run(system, mix, args.seed, args.seconds,
                          generator.Tracer(None))
            due = np.asarray([u.due - w.t0 for u in w.units])
            lat = np.asarray([u.latency if u.status == "completed"
                              else np.inf for u in w.units])
            done = np.isfinite(lat)
            close = w.t0 + args.seconds
            third = max(len(lat) // 3, 1)
            a, b = w.extra["service_before"], w.extra["service_after"]
            print(json.dumps({
                "rate_per_s": rate, "offered": len(lat),
                "completed": int(done.sum()),
                "p50_s": float(np.percentile(lat, 50)),
                "p90_s": float(np.percentile(lat, 90)),
                "open_at_close": int(sum(u.end > close for u in w.units)),
                "drain_s": w.t1 - close,
                "latency_slope": (float(np.polyfit(due[done], lat[done], 1)[0])
                                  if done.sum() > 1 else None),
                "backlog_trend": (float(np.median(lat[-third:]))
                                  / float(np.median(lat[:third]))),
                "requests_per_batch": ((b["completed"] - a["completed"])
                                       / max(b["batches"] - a["batches"], 1)),
                "generator_late_s": w.lateness_s}), flush=True)
    finally:
        system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
