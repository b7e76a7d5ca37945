"""Unified scorer construction: ONE entry point for every search path.

Before this module the scorer constructors were scattered across
layers — ``experiments.runner.make_scorer`` (host tuple),
``experiments.runner.make_traced_scorer`` (traced closures),
``core.nonideal.make_accuracy_model`` (the accuracy component), and
``core.distributed.make_sharded_scorer`` (population-sharded scoring,
which silently lacked the accuracy objective). ``build_scorer`` is now
the single constructor behind all of them:

    scorer = build_scorer(space, ScorerSpec(objective, workloads=wa),
                          budget=scenario.budget,
                          calib=Calib(n_calib, calib_k),
                          backend=scenario.backend)

It returns a ``Scorer`` — the traced closures the compiled search
engines consume (``score`` / ``score_w`` / per-workload restriction /
``score_vec`` for NSGA-II), plus the host-facing jitted/sharded
``score_host`` and ``evaluator``, plus the provenance fields
(``backend``, ``calib``, ``budget``) result caches key on. The old
names (runner.make_scorer, runner.make_traced_scorer,
distributed.make_sharded_scorer) are gone: they survive only as
ImportError stubs naming this module, pinned in
tests/test_scoring.py.

``backend`` selects the accuracy model's crossbar-GEMM route
declaratively (nonideal.BACKENDS: 'auto' | 'pallas' | 'ref' | 'jnp')
instead of an ad-hoc use-kernel flag: 'pallas' is the fused
gather/noise/GEMM/ADC kernel of kernels/imc_fused.py, 'ref' its
pure-jnp oracle, 'jnp' the original einsum path, and 'auto' resolves
per jax platform. The resolved backend is recorded on the Scorer and
in the scenario result-cache key.

Population sharding: with more than one visible device (or an explicit
``mesh``) the single-objective ``score_host`` shards the population
axis over the mesh 'data' axis — *including* accuracy-aware
(``edap_acc``) objectives, whose model is pure JAX and partitions like
the cost model (this closes the ROADMAP's "edap_acc is still
local-device only" gap).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import nonideal
from .cost_model import (HWConstants, evaluate_population,
                         evaluate_population_joint)
from .nonideal import resolve_backend
from .objectives import (INFEASIBLE_PENALTY, MultiObjective, Objective,
                         per_workload_scores)
from .search_space import SearchSpace
from .tracing import traced_closure
from .workloads import WorkloadArrays


@dataclasses.dataclass(frozen=True)
class Calib:
    """Calibration fidelity of the non-ideality accuracy model
    (§IV-H): rows and reduction depth of the calibration GEMMs. Part
    of the scenario result-cache key."""
    n_calib: int = 32
    calib_k: int = 256

    def asdict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class ScorerSpec:
    """What to score: the objective plus exactly one workload source —
    packed ``workloads`` tensors, or a traced ``builder``
    (core.workloads.WorkloadBuilder) for joint genome-slice
    co-search."""
    objective: Union[Objective, MultiObjective]
    workloads: Optional[WorkloadArrays] = None
    builder: Optional[Any] = None
    constants: HWConstants = HWConstants()


@dataclasses.dataclass(frozen=True)
class Scorer:
    """Every scoring surface of one (space, spec, calib, backend)
    configuration.

    Traced closures (consumed INSIDE the compiled search region — no
    jit wrappers, no host round-trips): ``score``/``feasible`` see the
    whole workload set; ``score_w``/``feasible_w`` restrict to one
    workload column ``w`` (a traced index), matching a single-workload
    pack bit-for-bit for EVERY objective kind
    (core.objectives.per_workload_scores), so the specific-baseline
    fan-out never needs a host-loop fallback. ``accuracy`` is the
    batched (P, W) non-ideality model for accuracy-aware objectives,
    None otherwise. Multi-objective specs populate ``score_vec`` — the
    (P, n) -> (P, D) score matrix the NSGA-II kernel non-dominated
    sorts inside the scan; ``score`` then restricts to the first
    component.

    ``design`` gathers, in one trace, every number a finished search
    reports of its designs: the CostMetrics fields, the per-workload
    EDAP, the first objective component's aggregated EDAP (the post-hoc
    front's axis), ``score`` and, with an accuracy model, ``accuracy``
    (experiments.runner.design_table compiles it per content key).

    Host-facing: ``score_host`` is jitted and, on multi-device
    runtimes, population-sharded over the mesh 'data' axis (with
    transparent padding to the device count); ``evaluator`` is the
    jitted CostMetrics function (capacity filters, final metrics).

    Provenance: ``backend`` (resolved), ``calib``, ``budget`` ride
    along for result-cache keys.
    """
    score: Callable                 # (P, n) -> (P,)
    feasible: Callable              # (P, n) -> (P,) bool
    score_w: Callable               # ((P, n), w) -> (P,)
    feasible_w: Callable            # ((P, n), w) -> (P,) bool
    metrics: Callable               # (P, n) -> CostMetrics
    accuracy: Optional[Callable] = None   # (P, n) -> (P, W)
    score_vec: Optional[Callable] = None  # (P, n) -> (P, D), MO only
    design: Optional[Callable] = None     # (R, n) -> {name: (R, ...)}
    score_host: Optional[Callable] = None
    evaluator: Optional[Callable] = None
    backend: str = "jnp"
    calib: Calib = Calib()
    budget: Optional[Any] = None


def sharded_score_fn(score: Callable, mesh: Mesh, axis: str = "data"):
    """jit ``score`` with the population axis sharded over ``axis``.

    The cost/accuracy models are elementwise over the population, so
    sharding is communication-free until the caller reduces; GSPMD
    partitions the whole evaluation from the in_shardings constraint.
    P must divide the axis size (callers pad otherwise). The returned
    callable exposes ``lowerable`` / ``in_sharding`` for the
    production-mesh dry-run's .lower().compile() check."""
    pop_sharding = NamedSharding(mesh, PartitionSpec(axis, None))
    out_sharding = NamedSharding(mesh, PartitionSpec(axis))
    fn = jax.jit(score, in_shardings=pop_sharding,
                 out_shardings=out_sharding)

    def score_fn(genomes):
        return fn(genomes)

    score_fn.lowerable = fn  # expose for dry-run .lower().compile()
    score_fn.in_sharding = pop_sharding
    return score_fn


def build_scorer(space: SearchSpace, spec: ScorerSpec, *,
                 budget: Optional[Any] = None, calib: Calib = Calib(),
                 backend: str = "auto",
                 mesh: Optional[Mesh] = None) -> Scorer:
    """THE scorer constructor (see module docstring).

    ``mesh`` overrides the automatic multi-device population sharding
    of ``score_host`` (None: shard iff more than one device is
    visible). The traced closures are mesh-independent — the batched
    search engines shard at the *search* axis instead
    (core.distributed.compile_batched_search)."""
    objective = spec.objective
    backend = resolve_backend(backend)
    table = jnp.asarray(space.value_table())
    is_mo = isinstance(objective, MultiObjective)
    kinds = objective.kinds if is_mo else (objective.kind,)
    components = objective.components if is_mo else (objective,)
    first = components[0]

    needs_acc = (any(k in ("edap_acc", "acc_loss") for k in kinds)
                 or any(o.min_accuracy > 0.0 for o in components))
    acc_fn = None
    if needs_acc:
        acc_fn = nonideal.make_accuracy_model(
            space, spec.workloads if spec.builder is None else None,
            builder=spec.builder, n_calib=calib.n_calib,
            calib_k=calib.calib_k, backend=backend)

    if spec.builder is not None:
        @traced_closure
        def metrics(genomes):
            return evaluate_population_joint(space, spec.builder, genomes,
                                             spec.constants, table)
    else:
        @traced_closure
        def metrics(genomes):
            return evaluate_population(space, spec.workloads, genomes,
                                       spec.constants, table)

    @traced_closure
    def score_full(genomes):
        m = metrics(genomes)
        if acc_fn is None:
            return objective(m)
        return objective(m, accuracy=acc_fn(genomes))

    if is_mo:
        score_vec = score_full

        @traced_closure
        def score(genomes):
            return score_full(genomes)[:, 0]
    else:
        score_vec = None
        score = score_full

    @traced_closure
    def feasible(genomes):
        return metrics(genomes).feasible

    @traced_closure
    def feasible_w(genomes, w):
        return metrics(genomes).feasible_w[:, w]

    @traced_closure
    def score_w(genomes, w):
        m = metrics(genomes)
        acc = acc_fn(genomes) if acc_fn is not None else None
        s = per_workload_scores(m, first.kind, accuracy=acc)[:, w]
        bad = (~m.feasible_w[:, w]) | (m.area >
                                       first.area_constraint)
        if first.min_accuracy > 0.0:
            bad = bad | (acc[:, w] < first.min_accuracy)
        return jnp.where(bad, INFEASIBLE_PENALTY, s)

    edap_first = Objective("edap", first.aggregation,
                           first.area_constraint)

    @traced_closure
    def design(genomes):
        m = metrics(genomes)
        table = {"energy": m.energy, "latency": m.latency,
                 "area": m.area, "feasible": m.feasible,
                 "feasible_w": m.feasible_w, "cost": m.cost,
                 "edap": per_workload_scores(m, "edap"),
                 "edap_agg": edap_first(m), "score": score(genomes)}
        if acc_fn is not None:
            table["accuracy"] = acc_fn(genomes)
        return table

    evaluator = jax.jit(metrics)
    n_dev = jax.device_count()
    if mesh is None and n_dev > 1:
        mesh = jax.make_mesh((n_dev,), ("data",))
    if mesh is not None and not is_mo:
        n_shards = mesh.devices.size
        sharded = sharded_score_fn(score, mesh)

        def score_host(genomes):
            genomes = jnp.asarray(genomes)
            P = genomes.shape[0]
            pad = (-P) % n_shards
            if pad:
                genomes = jnp.concatenate(
                    [genomes, jnp.repeat(genomes[:1], pad, axis=0)],
                    axis=0)
            return sharded(genomes)[:P]
    else:
        score_host = jax.jit(score)

    return Scorer(score=score, feasible=feasible, score_w=score_w,
                  feasible_w=feasible_w, metrics=metrics,
                  accuracy=acc_fn, score_vec=score_vec, design=design,
                  score_host=score_host, evaluator=evaluator,
                  backend=backend, calib=calib, budget=budget)
