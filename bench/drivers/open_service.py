"""Open loop over one ``CodesignService``: requests reach it on a schedule,
whether or not earlier ones are done (independent users sharing one
accelerator). Each request runs the configuration's scenario with one
seed of its own, so no request hits the result cache.

Every time is the benchmark's own clock: a request is due at its
arrival time, and it ends when a waiter thread of the client, blocked on
``result`` since the submit, has its answer in hand.

Mix parameters: ``rate_per_s`` (arrivals, see
``generator.arrival_times``), ``warm_batches`` (batch sizes submitted at
once during set-up, one per lane tier the window's batches can reach),
``trace_seconds`` (the traced slice, from ``TRACE_START_SHARE`` of the
window on), ``drain_s`` (how long after the window's close an answer may
still come).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from generator import (TRACE_START_SHARE, Unit, Window, arrival_times,
                       scenario_of, unit_seed)

ENTRY = "service"


class System:
    """One ``CodesignService`` with the configuration's deployment
    settings."""

    def __init__(self, config: Dict, out_root: str):
        from repro.api import CodesignService
        dep = config.get("deployment", {})
        self.scenario = scenario_of(config)
        self.svc = CodesignService(
            out_dir=out_root, write=True, force=True,
            window_s=float(dep.get("window_s", 0.05)),
            max_batch=int(dep.get("max_batch", 64))).start()

    def submit(self, seed: int) -> str:
        from repro.api import SearchRequest
        return self.svc.submit(SearchRequest(scenario=self.scenario,
                                             seed=seed, n_seeds=1))

    def result(self, rid: str, timeout: float):
        return self.svc.result(rid, timeout=timeout)

    def counters(self) -> Dict:
        return self.svc.stats().asdict()

    def close(self) -> None:
        self.svc.close(drain=False)


def _await(system: System, rid: str, u: Unit, until: float) -> None:
    """Block on one request until it is terminal or ``until`` passes,
    and stamp when its answer reached the client."""
    try:
        resp = system.result(rid, timeout=max(until - time.perf_counter(),
                                              0.0))
    except TimeoutError:
        u.status, u.error = "missing", "not done by the end of the drain"
        return
    u.end = time.perf_counter()
    u.status = resp.status
    if resp.status == "completed":
        u.results = [resp.result]
    else:
        u.error = resp.error or resp.status


def run(system: System, mix: Dict, seed: int, seconds: float,
        tracer) -> Window:
    due = arrival_times(float(mix["rate_per_s"]), seconds)
    trace_from = TRACE_START_SHARE * seconds
    trace_len = float(mix.get("trace_seconds", 8.0))
    units = [Unit(index=k, seeds=[unit_seed(seed, k, 1)])
             for k in range(len(due))]
    waiters: List[threading.Thread] = []
    stats0 = system.counters()
    t0 = time.perf_counter()
    until = t0 + seconds + float(mix.get("drain_s", 60.0))
    lateness = 0.0
    for k, u in enumerate(units):
        u.due = t0 + due[k]
        while True:
            now = time.perf_counter()
            if tracer.dir is not None:
                if not tracer.on and now - t0 >= trace_from:
                    tracer.start()
                elif tracer.on and now - t0 >= trace_from + trace_len:
                    tracer.stop()
            if now >= u.due:
                break
            time.sleep(min(u.due - now, 0.005))
        u.start = time.perf_counter()
        lateness = max(lateness, u.start - u.due)
        try:
            rid = system.submit(u.seeds[0])
        except Exception as e:
            u.status, u.error = "failed", repr(e)
            continue
        w = threading.Thread(target=_await, args=(system, rid, u, until),
                             name=f"bench-await-{k}", daemon=True)
        w.start()
        waiters.append(w)
    tracer.stop()
    for w in waiters:
        w.join()
    stats1 = system.counters()
    done = [u.end for u in units if u.status == "completed"]
    t1 = max(done) if done else t0 + seconds
    traced = [u for u in units
              if tracer.t[1] and tracer.t[0] <= u.start <= tracer.t[1]]
    return Window(units=units, t0=t0, t1=t1, traced=traced,
                  lateness_s=lateness,
                  extra={"service_before": stats0,
                         "service_after": stats1})


def warm(system: System, mix: Dict, seed: int) -> None:
    """Submit each warm batch size at once and wait for it, so every lane
    tier the window's batches reach is compiled (or loaded) in set-up."""
    k = 10 ** 5
    for size in mix.get("warm_batches", [1]):
        rids = []
        for _ in range(int(size)):
            rids.append(system.submit(unit_seed(seed, k, 1)))
            k += 1
        for rid in rids:
            resp = system.result(rid, timeout=1200.0)
            if resp.status != "completed":
                raise RuntimeError(f"warm-up request {rid}: {resp.status} "
                                   f"{resp.error}")


def describe(window: Window) -> Dict:
    after: Optional[Dict] = window.extra.get("service_after")
    return {"service": dict(after or {})}
