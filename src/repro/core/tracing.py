"""Tracing, in both of its meanings here: trace-safety markers for
compiled code, and host spans that time the program's layers.

**Trace safety.** Every closure that executes INSIDE a compiled search
region — scorer closures (core.scoring.build_scorer), GA/NSGA-II/
baseline generation steps, the device sampler, the workload builder —
must stay pure traced JAX: no host syncs (``.item()``, ``float()``/
``int()`` on traced values), no per-trace ``np.*`` work, no wall-clock
or Python RNG, no printing, no global mutation. ``@traced_closure``
marks such a function so the static-analysis suite (``python -m
repro.analysis``, rule R001) audits its body; at runtime it is a
zero-cost annotation — the function is returned unchanged.

The registry is keyed by (module, qualname), so closures rebuilt per
``build_scorer`` call overwrite their slot instead of accumulating:
at most one instance per marked site is ever pinned.

**Host spans.** ``span(name, **ids)`` times one piece of host work
where it happens: it opens a ``jax.profiler.TraceAnnotation`` (a host
event on the profiler's clock, the one the device's ``XLA Ops`` are on,
carrying ``ids`` as its stats) and measures its own ``perf_counter``
duration, which the caller adds to the stats it already keeps
(``run_campaign``'s stats, ``ServiceStats``). With no profiler running
a span costs one annotation and two clock reads; there is no registry,
exporter or switch — the profiler's buffer is the store. A span never
goes inside a traced closure (R001 reports one): it would time tracing,
not execution. Device work is named by ``jax.named_scope`` instead.

This module imports no jax or numpy at load time on purpose (``span``
imports jax when called): it sits below everything in core/ and must
never create an import cycle.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Tuple

#: (module, qualname) -> the most recently constructed marked closure.
TRACED_REGISTRY: Dict[Tuple[str, str], Callable] = {}


def traced_closure(fn: Callable) -> Callable:
    """Mark ``fn`` as a traced-pure closure (see module docstring).

    Purely declarative: sets ``__traced_closure__`` and records the
    function in :data:`TRACED_REGISTRY`, then returns ``fn`` unchanged
    (no wrapper, no call overhead inside the trace).
    """
    fn.__traced_closure__ = True
    TRACED_REGISTRY[(fn.__module__, fn.__qualname__)] = fn
    return fn


def traced_sites() -> Tuple[Tuple[str, str], ...]:
    """Sorted (module, qualname) keys of every registered marked site
    (the jaxpr audit and tests enumerate these)."""
    return tuple(sorted(TRACED_REGISTRY))


class span:
    """Context manager timing one named piece of host work (see module
    docstring). ``seconds`` holds the duration once the block exits::

        with span("campaign.plan", n_scenarios=3) as sp:
            jobs = plan_campaign(...)
        stats["plan_s"] = sp.seconds
    """
    __slots__ = ("_annotation", "_t0", "seconds")

    def __init__(self, name: str, **ids):
        import jax
        self._annotation = jax.profiler.TraceAnnotation(name, **ids)
        self._t0 = 0.0
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
