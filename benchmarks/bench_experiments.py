"""Benchmarks for the experiment-runner hot path, and the CI perf gate.

Cells:
  experiments_eval_hot     — steady-state batched population evaluation
                             through core.scoring.build_scorer (the
                             per-generation device computation): us/call
                             and design-evaluations/s at the benchmark
                             population scale, PAPER_4 and PAPER_9.
  experiments_search_loop  — the tentpole metric: one full smoke-budget
                             joint search, scan-compiled (one device
                             call, zero per-generation host syncs) vs
                             the reference host-driven loop. Steady
                             state (compile excluded). The
                             scan-vs-host speedup is the number the CI
                             perf gate pins (benchmarks/baseline.json).
  experiments_multiseed    — S independent seeds as ONE vmapped device
                             call vs S sequential scan searches.
  experiments_baselines_scan — the Table 3 baseline engine: one
                             scan-compiled (µ+λ)-ES search
                             (core/baselines.py) vs the host-driven
                             per-iteration reference loop on the
                             §III-C1 reduced-space landscape; the
                             scan-vs-host speedup is gated like the
                             GA/NSGA cells.
  experiments_nsga_scan    — the multi-objective tentpole: one full
                             smoke-budget NSGA-II search (non-dominated
                             sorting, crowding, tournament and
                             environmental selection inside ONE
                             compiled lax.scan — zero per-generation
                             host syncs) vs the host-loop reference
                             (core.nsga.run_nsga_loop, one Python
                             round-trip per generation). The
                             scan-vs-host speedup is gated like the
                             single-objective search cell.
  experiments_accuracy_scored — §IV-H hot path: the batched
                             non-ideality accuracy model vs the
                             retained host per-genome loop at
                             population scale (gated speedup), plus
                             the scan-compiled edap_acc smoke search.
  experiments_imc_fused    — the fused IMC fast path: the accuracy
                             model routed through the fused
                             gather/noise/GEMM/ADC evaluator
                             (kernels/imc_fused.py, 'ref' backend on
                             CPU) vs the retained host per-genome loop
                             (gated speedup, the fused-path analogue
                             of accuracy_model_speedup_x).
  experiments_nsga_dominance — the tiled Deb dominance-count build
                             (core.nsga.dominance_matrix_tiled,
                             O(tile·N·D) live memory) vs the one-shot
                             (N, N, D) broadcast at N=4096, D=8
                             (gated speedup; ranks are bit-identical,
                             tests/test_nsga.py).
  experiments_joint_eval   — the joint co-search hot path: the traced
                             workload builder + cost model evaluating
                             a population of (hardware, architecture)
                             genomes in one device call vs dispatching
                             the same jitted evaluator per design
                             (gated batching speedup).
  experiments_smoke_run    — wall time of a full tiny scenario
                             (search + specific-baseline fan-out +
                             report), write=False so only compute is
                             measured.
  experiments_campaign_throughput — the campaign-engine gate: a fleet
                             of shape-identical scenarios run
                             sequentially (one retrace + compile each,
                             the pre-campaign ``run --all`` cost) vs
                             the campaign engine (one shape-bucketed
                             mega-batched compile + dispatch), both
                             cold-started; plus the warm re-run
                             against the persistent XLA compile cache.
                             The cold sequential/campaign speedup is
                             gated (campaign_throughput).
  experiments_service_throughput — the co-design service gate: the
                             same request fleet submitted one
                             run_campaign call at a time vs
                             concurrently through CodesignService's
                             micro-batch window (one plan, one
                             bucket compile), both cold. The
                             speedup is gated (service_throughput);
                             sustained requests/sec comes from the
                             service stats surface.

CLI (the CI bench job):
  PYTHONPATH=src python -m benchmarks.bench_experiments \
      --smoke --out bench_result.json
writes the metrics as JSON for benchmarks/check_regression.py.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import (ScorerSpec, build_scorer, get_scenario,
                       joint_search, make_objective, pack,
                       run_scenario)
from repro.core import (FOUR_PHASES, phase_schedule, random_genomes,
                        search_kernel)

from .common import Bench

# metric registry for the perf gate: name -> (higher_is_better, gated)
_METRICS: Dict[str, Dict] = {}


def _metric(name: str, value: float, higher_is_better: bool,
            gated: bool) -> None:
    _METRICS[name] = {"value": float(value),
                      "higher_is_better": higher_is_better,
                      "gated": gated}


def experiments_eval_hot(pop: int = 512, iters: int = 30) -> None:
    for name in ("rram_small_set", "rram_large_set"):
        sc = get_scenario(name)
        space = sc.space()
        wa = pack(sc.resolve_workloads())
        score_fn = build_scorer(
            space, ScorerSpec(make_objective(sc.objective),
                              workloads=wa)).score_host
        g = random_genomes(jax.random.PRNGKey(0), space, pop)
        score_fn(g).block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            s = score_fn(g)
        s.block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        Bench.record(f"experiments_eval_hot_{name}", dt,
                     f"pop{pop}_W{wa.n_workloads}_"
                     f"{pop / dt:.0f}designs_per_s")
        _metric(f"eval_hot_{name}_s", dt, higher_is_better=False,
                gated=False)


def experiments_search_loop(iters: int = 8) -> None:
    """Scan-compiled search vs host-driven loop at the smoke budget.

    Both run the identical algorithm (Hamming init + 4-phase GA) on the
    rram_smoke scenario; steady state — jits warmed before timing.
    """
    sc = get_scenario("rram_smoke")
    b = sc.budget
    space = sc.space()
    wa = pack(sc.resolve_workloads())
    obj = make_objective(sc.objective)
    traced = build_scorer(space, ScorerSpec(obj, workloads=wa))
    host_score, evaluator = traced.score_host, traced.evaluator

    def cap(g):
        return np.asarray(evaluator(jnp.asarray(g)).feasible)

    cards = jnp.asarray(space.cardinalities.astype(np.float32))
    schedule = jnp.asarray(phase_schedule(FOUR_PHASES, b.generations))
    kern = jax.jit(functools.partial(
        search_kernel, cards=cards, schedule=schedule,
        score_fn=traced.score, feasible_fn=traced.feasible,
        p_h=b.p_h, p_e=b.p_e, p_ga=b.p_ga))

    key = jax.random.PRNGKey(0)
    jax.block_until_ready(kern(key))  # compile
    t0 = time.perf_counter()
    for i in range(iters):
        out = kern(jax.random.PRNGKey(i))
    jax.block_until_ready(out)
    t_scan = (time.perf_counter() - t0) / iters

    run_host = functools.partial(
        joint_search, space=space, score_fn=host_score, p_h=b.p_h,
        p_e=b.p_e, p_ga=b.p_ga, generations_per_phase=b.generations,
        capacity_filter=cap, use_scan=False)
    run_host(jax.random.PRNGKey(0))  # warm the step/score jits
    t0 = time.perf_counter()
    for i in range(iters):
        run_host(jax.random.PRNGKey(i))
    t_host = (time.perf_counter() - t0) / iters

    speedup = t_host / t_scan
    Bench.record("experiments_search_scan", t_scan,
                 f"smoke_T{schedule.shape[0]}gen")
    Bench.record("experiments_search_hostloop", t_host,
                 f"scan_speedup_{speedup:.1f}x")
    _metric("search_loop_scan_s", t_scan, higher_is_better=False,
            gated=False)
    _metric("search_loop_host_s", t_host, higher_is_better=False,
            gated=False)
    _metric("search_scan_speedup_x", speedup, higher_is_better=True,
            gated=True)


def experiments_multiseed(n_seeds: int = 4, iters: int = 4) -> None:
    """S seeds in one vmapped device call vs S sequential scan calls."""
    sc = get_scenario("rram_smoke")
    b = sc.budget
    space = sc.space()
    wa = pack(sc.resolve_workloads())
    traced = build_scorer(space,
                          ScorerSpec(make_objective(sc.objective),
                                     workloads=wa))
    cards = jnp.asarray(space.cardinalities.astype(np.float32))
    schedule = jnp.asarray(phase_schedule(FOUR_PHASES, b.generations))

    def one(key):
        return search_kernel(key, cards, schedule, traced.score,
                             traced.feasible, p_h=b.p_h, p_e=b.p_e,
                             p_ga=b.p_ga)

    batched = jax.jit(jax.vmap(one))
    single = jax.jit(one)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(n_seeds)])
    jax.block_until_ready(batched(keys))
    jax.block_until_ready(single(keys[0]))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = batched(keys)
    jax.block_until_ready(out)
    t_batch = (time.perf_counter() - t0) / iters
    t0 = time.perf_counter()
    for _ in range(iters):
        for i in range(n_seeds):
            out = single(keys[i])
    jax.block_until_ready(out)
    t_seq = (time.perf_counter() - t0) / iters
    Bench.record("experiments_multiseed_batched", t_batch,
                 f"S{n_seeds}_vs_seq_{t_seq / t_batch:.2f}x")
    _metric("multiseed_batched_s", t_batch, higher_is_better=False,
            gated=False)
    _metric("multiseed_batch_speedup_x", t_seq / t_batch,
            higher_is_better=True, gated=False)


def experiments_nsga_scan(iters: int = 8) -> None:
    """Scan-compiled NSGA-II vs the host-driven generation loop at the
    smoke budget, on the rram_tech_cost_mo scenario's EDAP × cost
    objective pair. Equal work on both sides: the same initial
    population feeds the jitted ``nsga_scan`` and ``run_nsga_loop``,
    so the gated speedup isolates exactly the per-generation host
    round-trips the scan removes (the identical generation math —
    tests/test_nsga.py pins the trajectories). Steady state — jits
    warmed before timing."""
    from repro.core import random_genomes as rand_g, run_nsga_loop
    from repro.core.nsga import nsga_scan
    from repro.experiments import SMOKE_BUDGET

    sc = get_scenario("rram_tech_cost_mo")
    b = SMOKE_BUDGET
    space = sc.space()
    wa = pack(sc.resolve_workloads())
    traced = build_scorer(space,
                          ScorerSpec(make_objective(sc.objective),
                                     workloads=wa))
    cards = jnp.asarray(space.cardinalities.astype(np.float32))
    schedule = jnp.asarray(phase_schedule(FOUR_PHASES, b.generations))
    init = rand_g(jax.random.PRNGKey(0), space, b.p_ga)
    kern = jax.jit(functools.partial(
        nsga_scan, cards=cards, schedule=schedule,
        score_vec=traced.score_vec))

    jax.block_until_ready(kern(jax.random.PRNGKey(0), init))  # compile
    t0 = time.perf_counter()
    for i in range(iters):
        out = kern(jax.random.PRNGKey(i), init)
    jax.block_until_ready(out)
    t_scan = (time.perf_counter() - t0) / iters

    run_loop = functools.partial(run_nsga_loop, space=space,
                                 score_vec=traced.score_vec,
                                 init_pop=init, phases=FOUR_PHASES,
                                 generations_per_phase=b.generations)
    run_loop(jax.random.PRNGKey(0))  # warm the cached step jit
    t0 = time.perf_counter()
    for i in range(iters):
        run_loop(jax.random.PRNGKey(i))
    t_host = (time.perf_counter() - t0) / iters

    speedup = t_host / t_scan
    Bench.record("experiments_nsga_scan", t_scan,
                 f"smoke_T{schedule.shape[0]}gen_D2")
    Bench.record("experiments_nsga_hostloop", t_host,
                 f"nsga_scan_speedup_{speedup:.1f}x")
    _metric("nsga_scan_s", t_scan, higher_is_better=False, gated=False)
    _metric("nsga_host_s", t_host, higher_is_better=False, gated=False)
    _metric("nsga_scan_speedup_x", speedup, higher_is_better=True,
            gated=True)


def experiments_accuracy_scored(pop: int = 64, host_pop: int = 8,
                                iters: int = 5) -> None:
    """Accuracy-scored search hot path (§IV-H): the batched (vmapped,
    jit-compiled) non-ideality accuracy model vs the retained host
    per-genome loop (accuracy_proxy_host) at population scale, plus
    the steady-state scan-compiled edap_acc smoke search.

    The gated metric is the dimensionless device-vs-host-loop speedup
    of one population evaluation — the factor that let edap_acc move
    inside the compiled search. Host time is measured on a small
    genome subset and scaled linearly (the loop is embarrassingly
    per-genome)."""
    from repro.core import nonideal

    sc = get_scenario("rram_accuracy")
    space = sc.space()
    wls = sc.resolve_workloads()
    model = jax.jit(nonideal.make_accuracy_model(space, wls))
    g = random_genomes(jax.random.PRNGKey(0), space, pop)
    model(g).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = model(g)
    out.block_until_ready()
    t_dev = (time.perf_counter() - t0) / iters
    gh = np.asarray(g[:host_pop])
    # warm every per-rows jit shape so the timed pass is steady state
    # (matching the device side, whose compile is excluded above)
    nonideal.accuracy_proxy_host(space, gh, wls)
    t0 = time.perf_counter()
    nonideal.accuracy_proxy_host(space, gh, wls)
    t_host = (time.perf_counter() - t0) * (pop / host_pop)
    speedup = t_host / t_dev
    Bench.record("experiments_accuracy_model", t_dev,
                 f"pop{pop}_host_loop_{speedup:.0f}x")
    _metric("accuracy_model_batched_s", t_dev, higher_is_better=False,
            gated=False)
    _metric("accuracy_model_speedup_x", speedup, higher_is_better=True,
            gated=True)

    # full smoke-budget edap_acc search, scan-compiled (steady state)
    smoke = get_scenario("rram_smoke")
    b = smoke.budget
    wa = pack(wls)
    traced = build_scorer(space,
                          ScorerSpec(make_objective(sc.objective),
                                     workloads=wa))
    cards = jnp.asarray(space.cardinalities.astype(np.float32))
    schedule = jnp.asarray(phase_schedule(FOUR_PHASES, b.generations))
    kern = jax.jit(functools.partial(
        search_kernel, cards=cards, schedule=schedule,
        score_fn=traced.score, feasible_fn=traced.feasible,
        p_h=b.p_h, p_e=b.p_e, p_ga=b.p_ga))
    jax.block_until_ready(kern(jax.random.PRNGKey(0)))
    t0 = time.perf_counter()
    for i in range(max(1, iters // 2)):
        out = kern(jax.random.PRNGKey(i))
    jax.block_until_ready(out)
    t_search = (time.perf_counter() - t0) / max(1, iters // 2)
    Bench.record("experiments_accuracy_search", t_search,
                 f"smoke_T{schedule.shape[0]}gen_edap_acc")
    _metric("accuracy_search_scan_s", t_search, higher_is_better=False,
            gated=False)


def experiments_imc_fused(pop: int = 64, host_pop: int = 8,
                          iters: int = 5) -> None:
    """The fused IMC fast path (kernels/imc_fused.py): the accuracy
    model routed through the single-pass gather + conductance-noise +
    crossbar-tiled bit-plane GEMM + per-tile ADC evaluator, vs the
    retained host per-genome loop. The 'ref' backend is the fused
    dataflow in pure jnp — what the Pallas kernel computes, minus the
    interpret-mode overhead that would dominate a CPU timing; on an
    accelerator the 'pallas' route lowers the same pass. Host time is
    measured on a small genome subset and scaled linearly (the loop is
    embarrassingly per-genome). Gated like accuracy_model_speedup_x."""
    from repro.core import nonideal

    sc = get_scenario("rram_accuracy")
    space = sc.space()
    wls = sc.resolve_workloads()
    model = jax.jit(nonideal.make_accuracy_model(space, wls,
                                                 backend="ref"))
    g = random_genomes(jax.random.PRNGKey(0), space, pop)
    model(g).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = model(g)
    out.block_until_ready()
    t_dev = (time.perf_counter() - t0) / iters
    gh = np.asarray(g[:host_pop])
    nonideal.accuracy_proxy_host(space, gh, wls)  # warm per-rows jits
    t0 = time.perf_counter()
    nonideal.accuracy_proxy_host(space, gh, wls)
    t_host = (time.perf_counter() - t0) * (pop / host_pop)
    speedup = t_host / t_dev
    Bench.record("experiments_imc_fused", t_dev,
                 f"pop{pop}_host_loop_{speedup:.0f}x")
    _metric("imc_fused_batched_s", t_dev, higher_is_better=False,
            gated=False)
    _metric("imc_fused_speedup_x", speedup, higher_is_better=True,
            gated=True)


def experiments_nsga_dominance(n: int = 4096, d: int = 8,
                               iters: int = 5) -> None:
    """Tiled Deb dominance build (lax.scan over fixed row blocks,
    peak intermediate O(tile·N·D)) vs the one-shot broadcast (peak
    O(N²·D) if unfused), on a tie-heavy integer grid at N=4096, D=8.
    Both produce identical matrices (tests/test_nsga.py pins the
    ranks bit-for-bit). XLA's CPU fusion already keeps the broadcast
    from materializing N²·D, so the honest expectation here is
    *parity*, not a speedup: the tiled kernel buys the bounded memory
    envelope (what lets P_GA=1000+ populations rank under vmap) and
    must not cost wall-clock for it. The gated metric is the
    dimensionless broadcast/tiled time ratio, pinned near 1.0 — it
    trips if the scan path ever becomes significantly slower than the
    broadcast it replaces."""
    from repro.core.nsga import dominance_matrix, dominance_matrix_tiled

    rng = np.random.default_rng(0)
    F = jnp.asarray(rng.integers(0, 16, (n, d)).astype(np.float32))

    dom_tiled = jax.jit(lambda s: dominance_matrix_tiled(s))
    dom_full = jax.jit(lambda s: dominance_matrix(s))
    dom_tiled(F).block_until_ready()  # compile
    dom_full(F).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = dom_tiled(F)
    out.block_until_ready()
    t_tiled = (time.perf_counter() - t0) / iters
    t0 = time.perf_counter()
    for _ in range(iters):
        out = dom_full(F)
    out.block_until_ready()
    t_full = (time.perf_counter() - t0) / iters
    ratio = t_full / t_tiled
    Bench.record("experiments_nsga_dominance", t_tiled,
                 f"N{n}_D{d}_broadcast_{ratio:.2f}x")
    _metric("nsga_dominance_tiled_s", t_tiled, higher_is_better=False,
            gated=False)
    _metric("nsga_dominance_tiled", ratio, higher_is_better=True,
            gated=True)


def experiments_baselines_scan(iters: int = 12, pop: int = 24,
                               timed: int = 8) -> None:
    """Table 3 baseline engine: one scan-compiled (µ+λ)-ES search vs
    the host-driven per-iteration loop (core.baselines.run_baseline_
    loop — the same init/step closures, one Python round-trip per
    iteration), on the §III-C1 reduced-space EDAP landscape. Equal
    work both sides, steady state (jits warmed before timing); the
    gated metric is the dimensionless scan-vs-host speedup, like the
    GA/NSGA cells."""
    from repro.core import pack as pack_w, reduced_rram_space
    from repro.core import get_workload_set, PAPER_4
    from repro.core.baselines import baseline_search, run_baseline_loop
    from repro.experiments import make_landscape_scorer

    space = reduced_rram_space()
    wa = pack_w(get_workload_set(PAPER_4))
    score = make_landscape_scorer(space, wa, make_objective("edap:mean"))

    kw = dict(algorithm="es", pop=pop, iters=iters)
    baseline_search(jax.random.PRNGKey(0), space, score, **kw)  # compile
    t0 = time.perf_counter()
    for i in range(timed):
        out = baseline_search(jax.random.PRNGKey(i), space, score, **kw)
    t_scan = (time.perf_counter() - t0) / timed

    run_baseline_loop(jax.random.PRNGKey(0), space, score, **kw)  # warm
    t0 = time.perf_counter()
    for i in range(timed):
        out = run_baseline_loop(jax.random.PRNGKey(i), space, score,
                                **kw)
    t_host = (time.perf_counter() - t0) / timed
    del out

    speedup = t_host / t_scan
    Bench.record("experiments_baselines_scan", t_scan,
                 f"es_pop{pop}_T{iters}")
    Bench.record("experiments_baselines_hostloop", t_host,
                 f"baselines_scan_speedup_{speedup:.1f}x")
    _metric("baselines_scan_s", t_scan, higher_is_better=False,
            gated=False)
    _metric("baselines_host_s", t_host, higher_is_better=False,
            gated=False)
    _metric("baselines_scan_speedup_x", speedup, higher_is_better=True,
            gated=True)


def experiments_joint_eval(pop: int = 64, iters: int = 5) -> None:
    """Joint co-search hot path: the traced workload builder + cost
    model evaluating a whole population's (hardware, architecture)
    genomes in ONE device call, vs dispatching the same jitted
    evaluator once per design (the host-driven pattern a
    non-vectorized builder forces — identical math, P batch-1 calls).
    The gated metric is the dimensionless batching speedup."""
    from repro.core import get_space, joint_space, make_joint_evaluator
    from repro.core.workloads import make_workload_builder, resnet_family

    fam = resnet_family()
    space = joint_space(get_space("rram"), [fam])
    builder = make_workload_builder(space, [fam])
    ev = make_joint_evaluator(space, builder)
    g = random_genomes(jax.random.PRNGKey(0), space, pop)

    jax.block_until_ready(ev(g))          # compile (P,)
    jax.block_until_ready(ev(g[:1]))      # compile (1,)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = ev(g)
    jax.block_until_ready(out)
    t_batch = (time.perf_counter() - t0) / iters

    t0 = time.perf_counter()
    for _ in range(iters):
        for i in range(pop):
            out = ev(g[i:i + 1])
    jax.block_until_ready(out)
    t_per_design = (time.perf_counter() - t0) / iters

    speedup = t_per_design / t_batch
    Bench.record("experiments_joint_eval", t_batch,
                 f"pop{pop}_arch{fam.n_combos}_"
                 f"per_design_{speedup:.1f}x")
    _metric("joint_eval_batched_s", t_batch, higher_is_better=False,
            gated=False)
    _metric("joint_eval_speedup_x", speedup, higher_is_better=True,
            gated=True)


def experiments_smoke_run() -> None:
    t0 = time.perf_counter()
    res = run_scenario(get_scenario("rram_smoke"), write=False)
    dt = time.perf_counter() - t0
    Bench.record("experiments_smoke_run", dt,
                 f"gap_{res['gap']['mean_pct']:.1f}pct")
    _metric("smoke_run_s", dt, higher_is_better=False, gated=False)


def experiments_campaign_throughput(n_clones: int = 6) -> None:
    """Campaign engine vs sequential execution of a scenario fleet.

    The fleet is ``n_clones`` shape-identical scenarios (distinct
    names, same space/workloads/budget — the rram_smoke config).
    Sequentially each scenario builds its own scorer and re-traces +
    re-compiles its search kernel; the campaign engine content-keys
    one Scorer, buckets all fleet lanes into one compiled
    mega-batched device call per lane flavor (generalized lanes and
    specific-baseline lanes dispatch separately), and
    pipelines drains against dispatches. Both sides start cold (jit
    caches + kernel cache cleared), so the speedup measures exactly
    what ``run --all`` pays today: per-scenario retrace/compile.

    A third timing re-runs the campaign against the persistent XLA
    compilation cache it just filled (in-process jit caches cleared
    again): the nightly-CI steady state, where even the one bucket
    compile is served from disk.
    """
    import dataclasses
    import tempfile

    from repro.core.distributed import kernel_cache_clear
    from repro.experiments import enable_persistent_cache, run_campaign

    base = get_scenario("rram_smoke")
    clones = [dataclasses.replace(base, name=f"rram_smoke_clone{i}")
              for i in range(n_clones)]

    kernel_cache_clear()
    jax.clear_caches()
    t0 = time.perf_counter()
    for sc in clones:
        run_scenario(sc, write=False)
    t_seq = time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as cache_dir:
        prior = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        try:
            enable_persistent_cache()
            kernel_cache_clear()
            jax.clear_caches()
            t0 = time.perf_counter()
            _, stats = run_campaign(clones, write=False)
            t_camp = time.perf_counter() - t0

            kernel_cache_clear()
            jax.clear_caches()
            t0 = time.perf_counter()
            _, stats_warm = run_campaign(clones, write=False)
            t_warm = time.perf_counter() - t0
        finally:
            if prior is None:
                del os.environ["JAX_COMPILATION_CACHE_DIR"]
            else:
                os.environ["JAX_COMPILATION_CACHE_DIR"] = prior
            jax.config.update("jax_compilation_cache_dir", prior)

    speedup = t_seq / t_camp
    pc = stats_warm["persistent_cache"]
    Bench.record("experiments_campaign_sequential", t_seq,
                 f"{n_clones}scen_cold")
    Bench.record("experiments_campaign_batched", t_camp,
                 f"{stats['n_buckets']}bucket_"
                 f"{stats['lanes_total']}lane")
    Bench.record("experiments_campaign_warm", t_warm,
                 f"sig_hits{pc['signature_hits']}")
    Bench.record("experiments_campaign_speedup", speedup,
                 f"{speedup:.1f}x")
    _metric("campaign_sequential_s", t_seq, higher_is_better=False,
            gated=False)
    _metric("campaign_batched_s", t_camp, higher_is_better=False,
            gated=False)
    _metric("campaign_warm_s", t_warm, higher_is_better=False,
            gated=False)
    _metric("campaign_throughput", speedup, higher_is_better=True,
            gated=True)
    _metric("campaign_scenarios_per_sec", stats["scenarios_per_sec"],
            higher_is_better=True, gated=False)
    # compile-cache effectiveness on the warm pass: every bucket
    # signature must re-hit the on-disk index (1.0 = all hits)
    hits = pc["signature_hits"]
    total = hits + pc["signature_misses"]
    _metric("campaign_cache_hit_rate", hits / max(total, 1),
            higher_is_better=True, gated=False)


def experiments_service_throughput(n_requests: int = 6) -> None:
    """CodesignService vs one-at-a-time run_campaign requests.

    ``n_requests`` shape-identical scenario requests (distinct names,
    the rram_smoke config) are first executed the way a client without
    the service would: one ``run_campaign([sc])`` call per request,
    each paying its own plan + compile. Then the same requests are
    submitted concurrently to a CodesignService, whose micro-batch
    window collects them into one campaign plan — one shape bucket,
    one mega-batched compile — before dispatch. Each baseline request
    starts cold (jit caches + kernel cache cleared per call: a client
    invocation is its own process), the service once, so the gated
    speedup measures the batching + amortization a long-lived
    request loop actually delivers, and
    ``service_requests_per_sec`` reports the sustained rate from the
    service's own stats surface.
    """
    import dataclasses

    from repro.core.distributed import kernel_cache_clear
    from repro.experiments import run_campaign
    from repro.serve.codesign import CodesignService
    from repro.api import SearchRequest

    base = get_scenario("rram_smoke")
    clones = [dataclasses.replace(base, name=f"rram_smoke_req{i}")
              for i in range(n_requests)]

    t_seq = 0.0
    for sc in clones:
        # each one-at-a-time request is its own client invocation: a
        # fresh process with nothing compiled (the pre-service cost)
        kernel_cache_clear()
        jax.clear_caches()
        t0 = time.perf_counter()
        run_campaign([sc], write=False)
        t_seq += time.perf_counter() - t0

    kernel_cache_clear()
    jax.clear_caches()
    with CodesignService(write=False, autostart=False, window_s=0.05,
                         max_batch=n_requests) as svc:
        t0 = time.perf_counter()
        rids = [svc.submit(SearchRequest(sc)) for sc in clones]
        svc.start()
        responses = [svc.result(rid, timeout=1800) for rid in rids]
        t_svc = time.perf_counter() - t0
        stats = svc.stats()
    assert all(r.status == "completed" for r in responses), \
        [r.status for r in responses]

    speedup = t_seq / t_svc
    Bench.record("experiments_service_sequential", t_seq,
                 f"{n_requests}req_cold")
    Bench.record("experiments_service_batched", t_svc,
                 f"{stats.batches}batch_{stats.buckets}bucket_"
                 f"{stats.lanes_total}lane")
    Bench.record("experiments_service_speedup", speedup,
                 f"{speedup:.1f}x")
    _metric("service_sequential_s", t_seq, higher_is_better=False,
            gated=False)
    _metric("service_batched_s", t_svc, higher_is_better=False,
            gated=False)
    _metric("service_throughput", speedup, higher_is_better=True,
            gated=True)
    _metric("service_requests_per_sec", stats.requests_per_sec,
            higher_is_better=True, gated=False)
    _metric("service_bucket_occupancy", stats.bucket_occupancy,
            higher_is_better=True, gated=False)


_SMOKE_CELLS = (
    "experiments_search_loop",
    "experiments_multiseed",
    "experiments_nsga_scan",
    "experiments_nsga_dominance",
    "experiments_baselines_scan",
    "experiments_accuracy_scored",
    "experiments_imc_fused",
    "experiments_joint_eval",
    "experiments_smoke_run",
    "experiments_campaign_throughput",
    "experiments_service_throughput",
)

_ALL_CELLS = ("experiments_eval_hot",) + _SMOKE_CELLS


def _run_cells(names) -> list:
    """Run each cell isolated: one failing cell doesn't lose the
    others' metrics (multi-cell regressions stay diagnosable in one
    run). Returns the failed cell names."""
    import traceback

    failed = []
    for name in names:
        try:
            globals()[name]()
        except Exception:
            traceback.print_exc()
            failed.append(name)
    return failed


def experiments_runner() -> None:
    failed = _run_cells(_ALL_CELLS)
    if failed:
        raise RuntimeError(f"bench cells failed: {', '.join(failed)}")


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.bench_experiments",
        description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: skip the large eval-hot cells, keep "
                         "the search-loop gate metrics fast")
    ap.add_argument("--out", default=None,
                    help="write metrics JSON (bench_result.json)")
    args = ap.parse_args(argv)
    failed = _run_cells(_SMOKE_CELLS if args.smoke else _ALL_CELLS)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"metrics": _METRICS}, f, indent=1, sort_keys=True)
        print(f"-> {args.out}")
    if failed:
        print(f"{len(failed)} cell(s) failed: {', '.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
