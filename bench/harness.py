"""Runs one cell of ``BENCHMARK.json``: set-up, the measured window, the
check against the reference, and the metrics.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name in ``BENCHMARK.json``:

* ``configs/<config>.json`` — the deployment: the registry scenario it
  runs, budget, seeds, deployment settings, the reference's statement of
  the same semantics, and the limits of the check;
* ``traffic/<mix>.json`` — a mix: the ``driver`` that offers its load
  (``drivers/<driver>.py``, see ``generator.py``) and its parameters;
* ``metrics/<metric>.py`` — a reader ``read(run) -> float | None`` of
  one metric from a ``RunRecord``; ``None`` leaves the metric out.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

from generator import BenchError, Tracer, load_driver

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "out")

# compile-time events of JAX's monitoring, read by the jit_ms metrics
JIT_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    entry: Dict
    config: Dict
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: str
    driver: object                   # the mix's drivers/<driver>.py


def find_cell(name: str, bench: Optional[Dict] = None,
              bench_dir: str = BENCH) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics, each
    found by name under ``bench_dir``."""
    if bench is None:
        bench = load_json(os.path.join(os.path.dirname(bench_dir),
                                       "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    entry = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = load_json(os.path.join(os.path.dirname(bench_dir), cfg["file"]))
    mix = load_json(os.path.join(bench_dir, "traffic",
                                 entry["traffic"] + ".json"))
    return Cell(name=name, entry=entry, config=config, mix=mix,
                end_to_end=[m for m in bench["end_to_end"]
                            if applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if applies(m, name)],
                bench_dir=bench_dir,
                driver=load_driver(mix["driver"], bench_dir))


def load_reader(name: str, bench_dir: str = BENCH) -> Callable:
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class JitListener:
    """Collects JAX's compile-time events (perf_counter at report, event,
    seconds) while registered."""

    def __init__(self):
        self.events: List = []

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event in JIT_EVENTS:
            self.events.append((time.perf_counter(), event, duration))

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self)

    def between(self, t0: float, t1: float) -> List:
        return [e for e in self.events if t0 <= e[0] <= t1]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunRecord:
    """What a metric reader sees."""
    cell: Cell
    device_kind: str
    setup_s: float
    window: object                   # generator.Window
    jit_events: List                 # (t, event, seconds) in the window
    trace: Optional[object] = None   # trace_reduce.Summary of the slice

    @property
    def entry(self) -> str:
        return self.cell.driver.ENTRY

    @property
    def completed(self) -> List:
        return [u for u in self.window.units if u.status == "completed"]

    def idle_pct(self) -> Optional[float]:
        """Percent of the traced slice in which no operation ran on the
        device, averaged over the cell's devices."""
        if self.trace is None:
            return None
        return 100.0 * (1.0 - self.trace.mean_busy_s / self.trace.window_s)

    def jit_ms_per_unit(self) -> Optional[float]:
        """Milliseconds per completed unit that JAX spent inside the
        window tracing, lowering, compiling or loading programs."""
        if not self.completed:
            return None
        return 1e3 * sum(e[2] for e in self.jit_events) / len(self.completed)


def _devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise BenchError(f"the benchmark needs a TPU; JAX found platform "
                         f"{d.platform!r} ({d.device_kind})")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips; JAX sees "
                         f"{len(devs)} {d.platform} device(s)")
    return devs[:chips]


def _peak_memory(devs) -> int:
    peaks = []
    for d in devs:
        try:
            peaks.append(int((d.memory_stats() or {})
                             .get("peak_bytes_in_use", 0)))
        except (AttributeError, RuntimeError, ValueError):
            peaks.append(0)
    return max(peaks) if peaks else 0


def _finite(x: float) -> float:
    return x if math.isfinite(x) else 1.0e300


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, require_tpu: bool = True,
             log: Callable[[str], None] = print) -> Dict:
    """One run of one cell; returns the result line's object."""
    import trace_reduce
    from correct import Reference, check_units

    t_start = time.perf_counter() if t_start is None else t_start
    devs = _devices(int(cell.entry["chips"]), require_tpu)
    from repro.api import enable_persistent_cache
    enable_persistent_cache()

    out_root = os.path.join(OUT, cell.name)
    shutil.rmtree(out_root, ignore_errors=True)
    os.makedirs(out_root, exist_ok=True)
    drive = cell.driver
    system = drive.System(cell.config, out_root)
    tracer = Tracer(os.path.join(out_root, "trace") if trace else None)
    try:
        drive.warm(system, cell.mix, seed)
        setup_s = time.perf_counter() - t_start
        with JitListener() as jit:
            window = drive.run(system, cell.mix, seed, seconds, tracer)
        memory_peak = _peak_memory(devs)
    finally:
        tracer.stop()
        system.close()

    summary = None
    if trace:
        summary = trace_reduce.Summary(trace_reduce.load(tracer.dir))
    run = RunRecord(cell=cell, device_kind=devs[0].device_kind,
                    setup_s=setup_s, window=window,
                    jit_events=jit.between(window.t0, window.t1),
                    trace=summary)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(m["name"], cell.bench_dir)(run)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    units = window.units
    failed = sum(1 for u in units if u.status != "completed")
    dev = f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}"
    log(json.dumps({"window": {
        "device": dev, "units": len(units), "failed": failed,
        "seconds": window.t1 - window.t0,
        "generator_late_s": window.lateness_s,
        "compile_events": _count(run.jit_events),
        "statuses": _count([(0, u.status) for u in units]),
        "errors": sorted({u.error[:200] for u in units if u.error})[:3]}}))
    log(json.dumps(dict(drive.describe(window), device=dev), default=str))

    verdict = check_units(Reference(cell.config["reference"]), units,
                          cell.config["limits"])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    line = {"correct": verdict["correct"], "attempted": len(units),
            "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean_busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = {"device_ops": summary.top_ops(10),
                             "idle_gaps": summary.idle_gaps(10)}
    line["checks"] = {k: {"value": _finite(c["value"]), "limit": c["limit"]}
                      for k, c in verdict["checks"].items()}
    line["_where"] = verdict["where"]
    return line


def _count(events) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for e in events:
        out[e[1]] = out.get(e[1], 0) + 1
    return out


def emit(line: Dict, out=sys.stdout, err=sys.stderr) -> None:
    """Print the result: where a check's worst reading came from and each
    number compared beside its limit on standard error (last there), and
    the result object as the last line of standard output."""
    where = line.pop("_where", {})
    for k, w in where.items():
        print(f"worst {k}: {w}", file=err)
    for k, c in line["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})", file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)
