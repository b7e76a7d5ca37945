"""The traffic generator: what every way of offering load shares.

A traffic mix (``traffic/<mix>.json``) is data. Its ``driver`` names the
way load is offered, a module ``drivers/<driver>.py`` found by name; the
rest of the mix are that driver's parameters. A driver module holds:

* ``ENTRY`` — the public entry point it drives (``"campaign"`` or
  ``"service"``), which the metric readers test;
* ``System(config, out_root)`` — the system under test built from a
  configuration, with a ``close()``;
* ``warm(system, mix, seed)`` — set-up's pass of the cell's own traffic;
* ``run(system, mix, seed, seconds, tracer) -> Window`` — the window;
* ``describe(window) -> dict`` — what the run logs before its result.

A new mix for an existing driver is one JSON file; a new way of offering
load is one more driver module, with no edit of a file that is there.
This module holds the helpers the drivers share: seeds, arrival times,
the unit and window records, the tracer and the scenario a configuration
runs.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import math
import os
import shutil
import time
from typing import Dict, List, Optional

import numpy as np

# seeds handed to the program stay inside a signed 32-bit range
SEED_RANGE = 2 ** 31
# the fixed shuffle of the open loop's inter-arrival gaps
ORDER_SEED = 20260415
# a timed traced slice starts this far into the window
TRACE_START_SHARE = 0.3


class BenchError(RuntimeError):
    """The benchmark cannot run this cell here."""


@functools.lru_cache(maxsize=None)
def load_driver(name: str, bench_dir: str):
    """The driver module ``drivers/<name>.py`` under ``bench_dir``; one
    module object per file and process."""
    path = os.path.join(bench_dir, "drivers", name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"traffic driver {name!r} has no module at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_driver_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def unit_seed(seed: int, index: int, stride: int) -> int:
    """First program seed of the ``index``-th unit of work of a run."""
    return (seed * 10 ** 4 + stride * index) % SEED_RANGE


def arrival_times(rate_per_s: float, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of an open loop: n =
    round(rate * seconds) requests whose n - 1 gaps are the exponential
    quantiles at ``rate`` in one fixed shuffled order, scaled to end at
    ``seconds``; the first is due at 0. The schedule takes nothing from
    the run's seed: in an open loop the order of the gaps is where the
    bursts fall, and so the tail latency, so every run offers the same
    arrivals and the seed changes only what each request asks for."""
    n = max(int(round(rate_per_s * seconds)), 1)
    if n == 1:
        return np.zeros(1)
    q = (np.arange(n - 1) + 0.5) / (n - 1)
    gaps = np.random.default_rng(ORDER_SEED).permutation(
        -np.log1p(-q) / rate_per_s)
    t = np.concatenate([[0.0], np.cumsum(gaps)])
    return t * (seconds / t[-1])


def scenario_of(config: Dict):
    """The registry scenario at the configuration's budget, after
    checking that it states the semantics the reference holds it to."""
    from repro.api import Budget, get_scenario
    sc = get_scenario(config["scenario"])
    ref = config["reference"]
    stated = {"mem": sc.mem, "workloads": list(sc.workloads),
              "objective": sc.objective, "n_calib": sc.n_calib,
              "calib_k": sc.calib_k,
              "specific_baselines": sc.specific_baselines}
    for k, v in stated.items():
        if k in ref and ref[k] != v:
            raise BenchError(f"scenario {sc.name!r} has {k}={v!r}, the "
                             f"configuration states {ref[k]!r}")
    budget = Budget(n_seeds=int(config["n_seeds"]), **config["budget"])
    return dataclasses.replace(sc, budget=budget)


@dataclasses.dataclass
class Unit:
    """One unit of offered work: a campaign or a request. Every time is
    the benchmark's own ``perf_counter``."""
    index: int
    seeds: List[int]
    due: float = 0.0            # when it was due
    start: float = 0.0          # when it was handed to the program
    end: float = math.inf       # when its answer was in the client's hands
    status: str = "pending"
    results: List[Dict] = dataclasses.field(default_factory=list)
    stats: Optional[Dict] = None
    out_dir: str = ""
    error: str = ""

    @property
    def latency(self) -> float:
        return self.end - self.due


@dataclasses.dataclass
class Window:
    """What the generator saw during the measured window."""
    units: List[Unit]
    t0: float
    t1: float
    traced: List[Unit]
    lateness_s: float = 0.0
    extra: Dict = dataclasses.field(default_factory=dict)


class Tracer:
    """Starts and stops the profiler around a slice of the window; a
    no-op when the run is not traced."""

    def __init__(self, trace_dir: Optional[str]):
        self.dir = trace_dir
        self.t = [0.0, 0.0]
        self.on = False
        self._span = None

    def start(self) -> None:
        if self.dir is None or self.on or self.t[1]:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation("bench_traced_slice")
        self._span.__enter__()
        self.on = True
        self.t[0] = time.perf_counter()

    def stop(self) -> None:
        if not self.on:
            return
        import jax
        self.t[1] = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False
