"""Closed loop over ``run_campaign``: one client calls it back to back,
each call waiting for the previous one (a user running the experiments
CLI again and again), forced (no result-cache hit) and writing its
artifacts as the CLI does.

Mix parameters: ``warmup_units`` (campaigns in set-up), ``trace_units``
(whole campaigns inside the traced slice, from the window's second on).
"""
from __future__ import annotations

import os
import time
from typing import Dict, List

from generator import Unit, Window, scenario_of, unit_seed

ENTRY = "campaign"


class System:
    """``run_campaign`` over the configuration's scenario; each campaign
    writes under its own directory of ``out_root``."""

    def __init__(self, config: Dict, out_root: str):
        self.scenarios = [scenario_of(config)]
        self.n_seeds = int(config["n_seeds"])
        self.out_root = out_root

    def campaign(self, seed: int, index: int) -> Unit:
        """The ``index``-th campaign of a run, timed by the client."""
        from repro.api import run_campaign
        first = unit_seed(seed, index, self.n_seeds)
        u = Unit(index=index, seeds=[first + j for j in range(self.n_seeds)])
        u.out_dir = os.path.join(self.out_root, f"campaign_{index:04d}")
        u.due = u.start = time.perf_counter()
        try:
            u.results, u.stats = run_campaign(
                self.scenarios, out_dir=u.out_dir, force=True, seed=first,
                n_seeds=self.n_seeds, write=True)
            u.status = "completed"
        except Exception as e:  # the run goes on; the unit counts as failed
            u.status, u.error = "failed", repr(e)
        u.end = time.perf_counter()
        return u

    def close(self) -> None:
        pass


def warm(system: System, mix: Dict, seed: int) -> None:
    for i in range(int(mix.get("warmup_units", 1))):
        u = system.campaign(seed, -1 - i)
        if u.status != "completed":
            raise RuntimeError(f"warm-up campaign failed: {u.error}")


def run(system: System, mix: Dict, seed: int, seconds: float,
        tracer) -> Window:
    units: List[Unit] = []
    traced: List[Unit] = []
    n_trace = int(mix.get("trace_units", 2))
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        # the traced slice holds whole campaigns, from the second on
        if i == 1:
            tracer.start()
        u = system.campaign(seed, 1 + i)
        units.append(u)
        if tracer.on:
            traced.append(u)
            if len(traced) >= n_trace:
                tracer.stop()
        i += 1
    tracer.stop()
    return Window(units=units, t0=t0, t1=units[-1].end, traced=traced)


def describe(window: Window) -> Dict:
    done = [u for u in window.units if u.status == "completed"]
    return {"campaigns": {
        "n": len(done),
        "wall_s": [round(u.end - u.start, 4) for u in done],
        "kernel_cache": [u.stats["kernel_cache"] for u in done[:3]],
        "buckets": done[0].stats["buckets"] if done else []}}
