"""Four-phase genetic algorithm with optimized sampling (paper §III-C2).

Operators: simulated binary crossover (SBX) + polynomial mutation
[Deb et al.], applied on a real-coded relaxation of the discrete genome
(index -> (idx + 0.5)/cardinality in (0,1), decode by floor), exactly
the pymoo-style treatment the paper uses. Phase schedule = Table 4.

The search engine is **device-resident**: the whole multi-phase run —
every generation of every phase — is folded into a single
``jax.lax.scan`` over a static-length schedule of (pc, eta_c, pm,
eta_m) rows, so one search is ONE compiled computation with zero host
transfers between generations (``ga_scan``/``search_kernel``). The
kernel is traceable, which makes independent searches a ``vmap`` axis:
``batched_joint_search`` runs S seeds (or, in the experiment runner, S
seeds x W workload-specific baselines) in one device call — the
TPU-native replacement for the paper's 64-core process pool
(DESIGN.md §3). ``run_ga_loop`` keeps the original host-driven loop as
the reference implementation; tests/test_genetic.py pins scan-vs-loop
equivalence.

Scorer contract: ``score_fn`` maps (P, n) int32 genomes to (P,) f32
scores (lower = better, +inf penalties for infeasible designs) and
must be pure traceable JAX — that is the *whole* contract, so scorers
that fold in the batched non-ideality accuracy model (objective kind
``edap_acc``) or the technology fabrication cost (``edap_cost``)
compile into the same lax.scan as the plain EDAP evaluator
(core.scoring.build_scorer builds all of them). Stochastic
models must derive their randomness from genome *content* (e.g.
fold_in on the genome's flat index, core.nonideal.genome_flat_index),
never from a side-channel key: the scan re-scores populations every
generation, and a design's score must be a pure function of the design
for elitism and best-so-far tracking to be meaningful.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .search_space import SearchSpace
# The compiled-kernel cache is shared by every search engine (GA,
# NSGA-II, the Table 3 baseline optimizers) and lives with the other
# compilation/distribution machinery in core.distributed.
from .distributed import cached_compile as _cached_jit
from .tracing import traced_closure
from . import sampling


@dataclasses.dataclass(frozen=True)
class Phase:
    name: str
    pc: float      # crossover probability
    eta_c: float   # crossover distribution index
    pm: float      # mutation probability (per gene)
    eta_m: float   # mutation distribution index


# Paper Table 4.
FOUR_PHASES: Tuple[Phase, ...] = (
    Phase("exploration", 1.0, 3.0, 1.0, 3.0),
    Phase("transition", 0.9, 7.0, 0.5, 7.0),
    Phase("convergence", 1.0, 15.0, 0.2, 15.0),
    Phase("fine-tuning", 1.0, 25.0, 0.05, 25.0),
)
# Traditional non-modified GA [44]: one phase, stock parameters.
PLAIN_PHASE = Phase("plain", 0.9, 15.0, 0.1, 20.0)

N_ELITE = 2


def phase_schedule(phases: Sequence[Phase],
                   generations_per_phase: int) -> np.ndarray:
    """Static-length scanned schedule: one (pc, eta_c, pm, eta_m) row
    per generation, phases expanded in order — the array the GA scan
    consumes instead of a host-side phase loop."""
    rows = [[p.pc, p.eta_c, p.pm, p.eta_m]
            for p in phases for _ in range(generations_per_phase)]
    return np.asarray(rows, np.float32)


@traced_closure
def _to_real(pop: jax.Array, cards: jax.Array) -> jax.Array:
    return (pop.astype(jnp.float32) + 0.5) / cards[None, :]


@traced_closure
def _to_index(x: jax.Array, cards: jax.Array) -> jax.Array:
    idx = jnp.floor(jnp.clip(x, 0.0, 1.0 - 1e-6) * cards[None, :])
    return idx.astype(jnp.int32)


@traced_closure
def _sbx(key: jax.Array, x1: jax.Array, x2: jax.Array, pc: jax.Array,
         eta: jax.Array) -> Tuple[jax.Array, jax.Array]:
    k_u, k_cross, k_gene = jax.random.split(key, 3)
    u = jax.random.uniform(k_u, x1.shape)
    beta = jnp.where(
        u <= 0.5,
        (2.0 * u) ** (1.0 / (eta + 1.0)),
        (1.0 / (2.0 * (1.0 - u))) ** (1.0 / (eta + 1.0)),
    )
    c1 = 0.5 * ((1 + beta) * x1 + (1 - beta) * x2)
    c2 = 0.5 * ((1 - beta) * x1 + (1 + beta) * x2)
    do_pair = jax.random.bernoulli(k_cross, pc, (x1.shape[0], 1))
    do_gene = jax.random.bernoulli(k_gene, 0.5, x1.shape)
    m = do_pair & do_gene
    return jnp.where(m, c1, x1), jnp.where(m, c2, x2)


@traced_closure
def _poly_mutate(key: jax.Array, x: jax.Array, pm: jax.Array,
                 eta: jax.Array,
                 cards: jax.Array | None = None) -> jax.Array:
    """Polynomial mutation; with ``cards``, a selected gene moves at
    least one discrete index step. High eta otherwise yields deltas far
    below the index granularity (e.g. |delta| < 1/3 for a 3-value
    parameter ~87% of the time at eta=20), silently neutering mutation
    on the floor-decoded genome and stalling low-pm phases."""
    k_u, k_m = jax.random.split(key)
    u = jax.random.uniform(k_u, x.shape)
    delta = jnp.where(
        u < 0.5,
        (2.0 * u) ** (1.0 / (eta + 1.0)) - 1.0,
        1.0 - (2.0 * (1.0 - u)) ** (1.0 / (eta + 1.0)),
    )
    if cards is not None:
        step = 1.0 / cards[None, :]
        delta = jnp.where(delta < 0.0, jnp.minimum(delta, -step),
                          jnp.maximum(delta, step))
    mask = jax.random.bernoulli(k_m, pm, x.shape)
    return jnp.clip(x + jnp.where(mask, delta, 0.0), 0.0, 1.0 - 1e-6)


@traced_closure
def _generation_step(key: jax.Array, pop: jax.Array, scores: jax.Array,
                     cards: jax.Array, pc: jax.Array, eta_c: jax.Array,
                     pm: jax.Array, eta_m: jax.Array) -> jax.Array:
    """One GA generation: sort, tournament-select, SBX, mutate, elitism.

    The phase parameters are traced (not static), so all phases share
    one compilation and the whole schedule can ride a lax.scan."""
    P = pop.shape[0]
    order = jnp.argsort(scores)
    pop_sorted = pop[order]

    k_t, k_x, k_m = jax.random.split(key, 3)
    n_child = P - N_ELITE
    n_pairs = (n_child + 1) // 2
    # binary tournament on ranks (pop_sorted is rank-ordered: lower = better)
    idx = jax.random.randint(k_t, (2, 2 * n_pairs), 0, P)
    winners = jnp.minimum(idx[0], idx[1])
    parents = _to_real(pop_sorted[winners], cards)
    x1, x2 = parents[:n_pairs], parents[n_pairs:]
    c1, c2 = _sbx(k_x, x1, x2, pc, eta_c)
    children = jnp.concatenate([c1, c2], axis=0)[:n_child]
    children = _poly_mutate(k_m, children, pm, eta_m, cards)
    new_pop = jnp.concatenate(
        [pop_sorted[:N_ELITE], _to_index(children, cards)], axis=0)
    return new_pop


_generation_step_jit = jax.jit(_generation_step)


@traced_closure
def ga_scan(key: jax.Array, init_pop: jax.Array, cards: jax.Array,
            schedule: jax.Array, score_fn: Callable[[jax.Array], jax.Array],
            active: Optional[jax.Array] = None) -> Tuple[jax.Array, ...]:
    """Traceable multi-phase GA: the whole schedule in one lax.scan.

    ``score_fn`` must be traceable (pure JAX). Returns device arrays
    (best_genome, best_score, history (T+1,), pop_sorted, scores_sorted)
    — no host transfer happens here; callers materialize once at the
    end of the full search computation.

    ``active`` is an optional (T,) bool mask over schedule rows; rows
    with ``active[t] == False`` leave the carry (population, best, PRNG
    key) untouched, so a schedule padded to T' > T rows with a
    ``[True]*T + [False]*(T'-T)`` mask produces bit-identical results
    to the unpadded run: history rows T..T'-1 repeat row T-1 and the
    appended final entry equals the unpadded one (see
    experiments/campaign.py's shape bucketing).
    """
    def step(carry, params):
        key, pop, best_g, best_s = carry
        scores = score_fn(pop)
        i = jnp.argmin(scores)
        s = scores[i]
        better = s < best_s
        best_s = jnp.where(better, s, best_s)
        best_g = jnp.where(better, pop[i], best_g)
        key, k = jax.random.split(key)
        pop = _generation_step(k, pop, scores, cards,
                               params[0], params[1], params[2], params[3])
        return (key, pop, best_g, best_s), best_s

    body = jax.named_scope("ga_step")(step)

    @jax.named_scope("ga_step")
    def body_masked(carry, xs):
        params, act = xs
        key, pop, best_g, best_s = carry
        (key2, pop2, best_g2, best_s2), _ = step(
            (key, pop, best_g, best_s), params)
        key = jnp.where(act, key2, key)
        pop = jnp.where(act, pop2, pop)
        best_g = jnp.where(act, best_g2, best_g)
        best_s = jnp.where(act, best_s2, best_s)
        return (key, pop, best_g, best_s), best_s

    best0 = jnp.array(jnp.inf, jnp.float32)
    carry = (key, init_pop, init_pop[0], best0)
    if active is None:
        (key, pop, best_g, best_s), hist = jax.lax.scan(
            body, carry, schedule)
    else:
        (key, pop, best_g, best_s), hist = jax.lax.scan(
            body_masked, carry, (schedule, active))
    scores = score_fn(pop)
    order = jnp.argsort(scores)
    pop, scores = pop[order], scores[order]
    better = scores[0] < best_s
    best_s = jnp.where(better, scores[0], best_s)
    best_g = jnp.where(better, pop[0], best_g)
    hist = jnp.concatenate([hist, best_s[None]])
    return best_g, best_s, hist, pop, scores


@traced_closure
def search_kernel(key: jax.Array, cards: jax.Array, schedule: jax.Array,
                  score_fn: Callable[[jax.Array], jax.Array],
                  feasible_fn: Optional[Callable] = None, *,
                  p_h: int, p_e: int, p_ga: int,
                  hamming_sampling: bool = True,
                  oversample: int = 4,
                  active: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, ...]:
    """Traceable Algorithm 1: device-resident sampling + scanned GA.

    Capacity filtering happens *inside* the compiled region via the
    traceable ``feasible_fn`` (sampling.sample_initial_device masks
    infeasible candidates out of the Hamming selection). vmap over
    ``key`` (and any axis score_fn closes over) to batch independent
    searches into one device call.
    """
    key, k_s = jax.random.split(key)
    if hamming_sampling:
        c2 = sampling.sample_initial_device(k_s, cards, p_h, p_e,
                                            feasible_fn=feasible_fn,
                                            oversample=oversample)
        scores = score_fn(c2)
        init = c2[jnp.argsort(scores)[:p_ga]]
    elif feasible_fn is None:
        init = sampling.uniform_genomes(k_s, cards, p_ga)
    else:
        pool = sampling.sample_initial_device(k_s, cards, p_h, p_ga,
                                              feasible_fn=feasible_fn,
                                              oversample=oversample)
        init = pool[:p_ga]
    return ga_scan(key, init, cards, schedule, score_fn, active=active)


class SearchResult(NamedTuple):
    best_genome: np.ndarray
    best_score: float
    history: np.ndarray          # (total_generations,) best-so-far score
    population: np.ndarray       # final population (sorted by score)
    scores: np.ndarray           # final population scores (sorted)
    wall_time_s: float
    sampling_time_s: float


class MultiSearchResult(NamedTuple):
    """S independent searches executed as one batched device call.

    Every array carries a leading seed axis; ``seed_result(i)`` slices
    one seed out as a plain SearchResult, ``best()`` the winner.
    """
    best_genomes: np.ndarray     # (S, n_params)
    best_scores: np.ndarray      # (S,)
    histories: np.ndarray        # (S, T+1)
    populations: np.ndarray      # (S, P, n_params), sorted per seed
    scores: np.ndarray           # (S, P), sorted per seed
    wall_time_s: float
    sampling_time_s: float

    @property
    def n_seeds(self) -> int:
        return int(self.best_scores.shape[0])

    def seed_result(self, i: int) -> SearchResult:
        return SearchResult(
            best_genome=self.best_genomes[i],
            best_score=float(self.best_scores[i]),
            history=self.histories[i],
            population=self.populations[i], scores=self.scores[i],
            wall_time_s=self.wall_time_s,
            sampling_time_s=self.sampling_time_s)

    def best(self) -> SearchResult:
        return self.seed_result(int(np.argmin(self.best_scores)))


def run_ga_loop(key: jax.Array, space: SearchSpace,
                score_fn: Callable[[jax.Array], jax.Array],
                init_pop: jax.Array, phases: Sequence[Phase],
                generations_per_phase: int) -> SearchResult:
    """Reference host-driven GA loop (pre-scan implementation).

    One Python round-trip per generation: argmin + float sync + key
    split on host. Kept as the equivalence oracle for ``ga_scan`` and
    as the measured baseline in benchmarks/bench_experiments.py.
    """
    t0 = time.perf_counter()
    cards = jnp.asarray(space.cardinalities.astype(np.float32))
    pop = init_pop
    best_g, best_s = None, np.inf
    hist: List[float] = []
    for phase in phases:
        pc = jnp.float32(phase.pc)
        eta_c = jnp.float32(phase.eta_c)
        pm = jnp.float32(phase.pm)
        eta_m = jnp.float32(phase.eta_m)
        for _ in range(generations_per_phase):
            scores = score_fn(pop)
            i = int(jnp.argmin(scores))
            s = float(scores[i])
            if s < best_s:
                best_s, best_g = s, np.asarray(pop[i])
            hist.append(best_s)
            key, k = jax.random.split(key)
            pop = _generation_step_jit(k, pop, scores, cards, pc, eta_c,
                                       pm, eta_m)
    scores = np.asarray(score_fn(pop))
    order = np.argsort(scores, kind="stable")
    i = order[0]
    if scores[i] < best_s:
        best_s, best_g = float(scores[i]), np.asarray(pop)[i]
    hist.append(best_s)
    return SearchResult(best_genome=best_g, best_score=best_s,
                        history=np.asarray(hist),
                        population=np.asarray(pop)[order],
                        scores=scores[order],
                        wall_time_s=time.perf_counter() - t0,
                        sampling_time_s=0.0)


def run_ga(key: jax.Array, space: SearchSpace,
           score_fn: Callable[[jax.Array], jax.Array],
           init_pop: jax.Array, phases: Sequence[Phase],
           generations_per_phase: int,
           use_scan: bool = True) -> SearchResult:
    """Run the (multi-phase) GA from an initial population.

    Default: one jit-compiled lax.scan over the whole phase schedule
    (zero host syncs between generations). ``use_scan=False`` runs the
    reference host-driven loop.
    """
    if not use_scan:
        return run_ga_loop(key, space, score_fn, init_pop, phases,
                           generations_per_phase)
    t0 = time.perf_counter()
    cards = jnp.asarray(space.cardinalities.astype(np.float32))
    schedule = jnp.asarray(phase_schedule(phases, generations_per_phase))
    fn = _cached_jit(
        ("ga_scan", id(score_fn)),
        lambda: jax.jit(functools.partial(ga_scan, score_fn=score_fn)),
        score_fn)
    best_g, best_s, hist, pop, scores = fn(key, init_pop, cards, schedule)
    return SearchResult(best_genome=np.asarray(best_g),
                        best_score=float(best_s),
                        history=np.asarray(hist),
                        population=np.asarray(pop),
                        scores=np.asarray(scores),
                        wall_time_s=time.perf_counter() - t0,
                        sampling_time_s=0.0)


def batched_joint_search(keys: jax.Array, space: SearchSpace,
                         score_fn: Callable[[jax.Array], jax.Array],
                         p_h: int = 1000, p_e: int = 500, p_ga: int = 40,
                         generations_per_phase: int = 10,
                         phases: Sequence[Phase] = FOUR_PHASES,
                         feasible_fn: Optional[Callable] = None,
                         hamming_sampling: bool = True,
                         oversample: int = 4,
                         mesh=None) -> MultiSearchResult:
    """Algorithm 1, S seeds in one compiled device computation.

    ``keys``: (S, key) PRNG keys, one independent search each; the
    whole batch — sampling, capacity masking, scoring, every GA
    generation — is one jit(vmap(search_kernel)) call. ``score_fn`` and
    ``feasible_fn`` must be traceable (pure JAX; the jitted evaluator
    closures qualify). With ``mesh``, the seed axis is sharded over the
    mesh's 'data' axis (core.distributed.compile_batched_search).
    """
    t0 = time.perf_counter()
    cards = jnp.asarray(space.cardinalities.astype(np.float32))
    schedule = jnp.asarray(phase_schedule(phases, generations_per_phase))

    # schedule + active mask ride along as runtime lane data (not
    # closed-over constants): the compiled kernel is then the exact
    # computation the campaign engine's bucketed lanes run, so
    # bucketed and sequential executions stay bit-identical — baking
    # the schedule lets XLA constant-fold reductions differently and
    # drift by ULPs.
    def one(key, sched, active):
        return search_kernel(key, cards, sched, score_fn, feasible_fn,
                             p_h=p_h, p_e=p_e, p_ga=p_ga,
                             hamming_sampling=hamming_sampling,
                             oversample=oversample, active=active)

    from .distributed import compile_batched_search
    fn = _cached_jit(
        ("batched", id(space), id(score_fn), id(feasible_fn), id(mesh),
         p_h, p_e, p_ga, generations_per_phase, tuple(phases),
         hamming_sampling, oversample),
        lambda: compile_batched_search(one, mesh=mesh),
        space, score_fn, feasible_fn, mesh)
    S = keys.shape[0]
    scheds = jnp.broadcast_to(schedule, (S,) + schedule.shape)
    actives = jnp.ones((S, schedule.shape[0]), bool)
    best_g, best_s, hist, pops, scores = fn(keys, scheds, actives)
    return MultiSearchResult(
        best_genomes=np.asarray(best_g), best_scores=np.asarray(best_s),
        histories=np.asarray(hist), populations=np.asarray(pops),
        scores=np.asarray(scores),
        wall_time_s=time.perf_counter() - t0, sampling_time_s=0.0)


def joint_search(key: jax.Array, space: SearchSpace,
                 score_fn: Callable[[jax.Array], jax.Array],
                 p_h: int = 1000, p_e: int = 500, p_ga: int = 40,
                 generations_per_phase: int = 10,
                 phases: Sequence[Phase] = FOUR_PHASES,
                 capacity_filter=None,
                 hamming_sampling: bool = True,
                 feasible_fn: Optional[Callable] = None,
                 use_scan: bool = True) -> SearchResult:
    """Algorithm 1: optimized sampling + four-phase GA.

    Three execution modes:
      * device-resident (default when the capacity constraint is absent
        or given as a *traceable* ``feasible_fn``): sampling, capacity
        masking and the whole GA run as ONE compiled computation;
      * host-sampled (a host-side ``capacity_filter`` is given):
        sampling keeps the paper's host rejection loop, the GA still
        runs as one scan;
      * reference (``use_scan=False``): the original host-driven loop.

    hamming_sampling=False gives the 'non-modified GA with enhanced
    sampling' ablation its counterfactual (random init of size p_ga).
    """
    if use_scan and capacity_filter is None:
        res = batched_joint_search(
            key[None], space, score_fn, p_h=p_h, p_e=p_e, p_ga=p_ga,
            generations_per_phase=generations_per_phase, phases=phases,
            feasible_fn=feasible_fn,
            hamming_sampling=hamming_sampling).seed_result(0)
        return res
    t0 = time.perf_counter()
    key, k_s = jax.random.split(key)
    if hamming_sampling:
        c2 = sampling.sample_initial(k_s, space, p_h, p_e,
                                     capacity_filter=capacity_filter)
        scores = np.asarray(score_fn(c2))
        order = np.argsort(scores, kind="stable")
        init = jnp.asarray(np.asarray(c2)[order[:p_ga]])
    else:
        if capacity_filter is None:
            init = sampling.random_genomes(k_s, space, p_ga)
        else:
            pool = sampling.sample_initial(k_s, space, p_h, p_ga,
                                           capacity_filter=capacity_filter)
            init = pool[:p_ga]
    t_sample = time.perf_counter() - t0
    res = run_ga(key, space, score_fn, init, phases, generations_per_phase,
                 use_scan=use_scan)
    return res._replace(sampling_time_s=t_sample,
                        wall_time_s=res.wall_time_s + t_sample)


def random_search(key: jax.Array, space: SearchSpace,
                  score_fn: Callable[[jax.Array], jax.Array],
                  n_evals: int = 684, batch: int = 200,
                  capacity_filter=None) -> SearchResult:
    """Random-search baseline: evaluate ``n_evals`` uniform genomes.

    The default budget matches joint_search's evaluation count at the
    reduced scale (P_H + P_GA * 4 phases * G = 300 + 24*16 = 684) so
    scenario comparisons are budget-fair. History is best-so-far per
    batch. Infeasible designs are masked to +inf rather than dropped,
    keeping batch shapes static (one jit compilation for all batches).
    """
    t0 = time.perf_counter()
    best_g, best_s = None, np.inf
    hist: List[float] = []
    pop = scores = None
    remaining = n_evals
    while remaining > 0:
        n = min(batch, remaining)
        remaining -= n
        key, k = jax.random.split(key)
        g = sampling.random_genomes(k, space, n)
        s = np.asarray(score_fn(g))
        if capacity_filter is not None:
            s = np.where(np.asarray(capacity_filter(g)), s, np.inf)
        i = int(np.argmin(s))
        if s[i] < best_s:
            best_s, best_g = float(s[i]), np.asarray(g)[i]
        hist.append(best_s)
        pop, scores = np.asarray(g), s
    if best_g is None:  # every sample infeasible: still return a genome
        i = int(np.argmin(scores))
        best_g, best_s = pop[i], float(scores[i])
    order = np.argsort(scores)
    return SearchResult(best_genome=best_g, best_score=best_s,
                        history=np.asarray(hist),
                        population=pop[order], scores=scores[order],
                        wall_time_s=time.perf_counter() - t0,
                        sampling_time_s=0.0)


def plain_ga_search(key: jax.Array, space: SearchSpace,
                    score_fn: Callable[[jax.Array], jax.Array],
                    p_ga: int = 40, total_generations: int = 40,
                    capacity_filter=None,
                    feasible_fn: Optional[Callable] = None,
                    use_scan: bool = True) -> SearchResult:
    """Traditional non-modified GA [44]: random init, single phase.

    Runs total_generations (= 4 phases * G for an equal budget)."""
    return joint_search(key, space, score_fn, p_h=max(4 * p_ga, 200),
                        p_e=p_ga, p_ga=p_ga,
                        generations_per_phase=total_generations,
                        phases=(PLAIN_PHASE,),
                        capacity_filter=capacity_filter,
                        feasible_fn=feasible_fn,
                        hamming_sampling=False, use_scan=use_scan)
