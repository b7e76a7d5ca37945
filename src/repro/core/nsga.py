"""Device-resident NSGA-II: true multi-objective search in one scan.

The §IV-I EDAP × fabrication-cost trade-off front was previously
reproduced *post hoc*: the single-objective GA visited designs under a
scalarized objective and the runner filtered its final populations
through ``core.pareto.pareto_front`` afterwards — which under-covers
the front exactly where single-objective pressure never visits. This
module searches the front *directly* with an NSGA-II
[Deb et al., TEVC 2002] sibling of the scan-compiled GA
(core/genetic.py):

  * **fast non-dominated sorting** — Deb dominance counts + rank
    peeling via ``lax.while_loop``: each iteration assigns the current
    zero-dominator front and subtracts its dominance contributions,
    exactly the Deb counting algorithm, fully traceable. Above
    DOMINANCE_TILE_THRESHOLD the dominance matrix builds in fixed-size
    row blocks (``dominance_matrix_tiled``: a lax.scan over tiles, peak
    float memory O(tile·N·D) instead of the (N, N, D) broadcast) so
    paper-scale P_GA=1000+ populations fit; the broadcast
    ``dominance_matrix`` is kept as the equivalence oracle and ranks
    are bit-identical on either path;
  * **crowding distance** — per objective, a rank-segmented
    ``lexsort`` (sort by rank, then objective value) with
    ``segment_min/max`` normalization; front boundaries get +inf;
  * **binary tournament by (rank, crowding)** — lower rank wins, ties
    break on larger crowding;
  * **environmental selection** — parents + children (2P) sorted by
    ``lexsort((-crowding, rank))``, best P survive.

All of it lives inside the same jit-compiled ``lax.scan`` body as the
single-objective GA — ``nsga_scan`` consumes the identical static
(pc, eta_c, pm, eta_m) phase schedule and reuses genetic.py's SBX /
polynomial-mutation operators and sampling.sample_initial_device's
in-region capacity masking, so one multi-objective search is ONE device
computation with zero per-generation host syncs, and independent
searches batch along a ``vmap`` axis (``batched_nsga_search``, sharded
over the mesh by core.distributed.compile_batched_search).

Scorer contract: ``score_vec`` maps (P, n) int32 genomes to a (P, D)
float32 matrix (every column: lower = better, INFEASIBLE_PENALTY for
infeasible designs — finite, so dominance comparisons stay valid).
objectives.MultiObjective and the TracedScorer of experiments/runner.py
build such closures for any pair of objective kinds.

``run_nsga_loop`` keeps a host-driven per-generation loop (same RNG
stream, same jitted generation step) as the equivalence oracle —
tests/test_nsga.py pins scan-vs-loop trajectories, and
benchmarks/bench_experiments.py gates the scan-vs-loop speedup in CI.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .genetic import (FOUR_PHASES, Phase, _cached_jit, _poly_mutate, _sbx,
                      _to_index, _to_real, phase_schedule)
from .search_space import SearchSpace
from .tracing import traced_closure
from . import sampling


# ---------------------------------------------------------------------------
# fast non-dominated sorting + crowding (traceable)
# ---------------------------------------------------------------------------

@traced_closure
def dominance_matrix(scores: jax.Array) -> jax.Array:
    """(N, D) minimize-all score matrix -> (N, N) bool: [i, j] is True
    iff design i dominates design j (i <= j everywhere, i < j
    somewhere). Duplicates do not dominate each other — the same
    convention as core.pareto.pareto_front.

    One (N, N, D) broadcast — the memory hot spot that gates
    paper-scale populations; kept as the equivalence oracle for
    ``dominance_matrix_tiled`` (tests/test_nsga.py pins elementwise
    equality, so ranks are bit-identical on either path)."""
    le = jnp.all(scores[:, None, :] <= scores[None, :, :], axis=-1)
    lt = jnp.any(scores[:, None, :] < scores[None, :, :], axis=-1)
    return le & lt


# Row-block size of the tiled dominance build, and the population size
# above which nondominated_rank switches to it automatically. 256 rows
# keeps each (tile, N, D) comparison block ~a few MB at paper-scale
# 2P = 2000-4000 populations while amortizing the scan step overhead.
DOMINANCE_TILE = 256
DOMINANCE_TILE_THRESHOLD = 512


@traced_closure
def dominance_matrix_tiled(scores: jax.Array,
                           tile: int = DOMINANCE_TILE) -> jax.Array:
    """``dominance_matrix`` computed in fixed-size row blocks.

    A ``lax.scan`` over ceil(N / tile) row tiles compares each (tile, D)
    block against all N columns, so the float broadcast peak is
    O(tile·N·D) instead of O(N²·D); only the (N, N) bool matrix (which
    the rank peeling needs anyway) is materialized. Elementwise
    comparisons are exact, so the result equals ``dominance_matrix``
    bit-for-bit — and on CPU the smaller working set makes the build
    ~2x faster at N >= 4096 on top of the memory win."""
    n, d = scores.shape
    if n <= tile:
        return dominance_matrix(scores)
    pad = (-n) % tile
    blocks = jnp.pad(scores, ((0, pad), (0, 0))).reshape(-1, tile, d)

    def row_block(_, block):
        le = jnp.all(block[:, None, :] <= scores[None, :, :], axis=-1)
        lt = jnp.any(block[:, None, :] < scores[None, :, :], axis=-1)
        return None, le & lt

    _, dom = jax.lax.scan(row_block, None, blocks)
    return dom.reshape(-1, n)[:n]


@traced_closure
@jax.named_scope("nsga_rank")
def nondominated_rank(scores: jax.Array,
                      tile: Optional[int] = None) -> jax.Array:
    """(N, D) scores -> (N,) int32 non-domination ranks (0 = front).

    Deb's counting sort, traceable: dominator counts from the dominance
    matrix, then rank peeling in a ``lax.while_loop`` — every
    iteration assigns the current zero-dominator front rank r and
    subtracts that front's dominance contributions. Terminates in at
    most N iterations (a finite strict partial order always has a
    non-dominated element), so the loop is vmap/scan-safe.

    ``tile=None`` picks the dominance build automatically: the row-
    tiled path (O(tile·N·D) peak memory) above
    DOMINANCE_TILE_THRESHOLD, the plain broadcast below it. Pass
    ``tile=0`` to force the broadcast or an explicit block size to
    force tiling; ranks are bit-identical either way."""
    n = scores.shape[0]
    if tile is None:
        tile = DOMINANCE_TILE if n >= DOMINANCE_TILE_THRESHOLD else 0
    dom = dominance_matrix_tiled(scores, tile) if tile \
        else dominance_matrix(scores)
    counts = jnp.sum(dom, axis=0).astype(jnp.int32)
    ranks0 = jnp.full((n,), -1, jnp.int32)

    def cond(state):
        _, _, ranks = state
        return jnp.any(ranks < 0)

    def body(state):
        r, counts, ranks = state
        front = (ranks < 0) & (counts == 0)
        ranks = jnp.where(front, r, ranks)
        dec = jnp.sum(jnp.where(front[:, None], dom, False), axis=0)
        # assigned members drop to -1 so they never re-enter the front
        counts = jnp.where(front, -1, counts - dec.astype(jnp.int32))
        return r + 1, counts, ranks

    _, _, ranks = jax.lax.while_loop(cond, body,
                                     (jnp.int32(0), counts, ranks0))
    return ranks


@traced_closure
def crowding_distance(scores: jax.Array, ranks: jax.Array) -> jax.Array:
    """(N, D) scores + (N,) ranks -> (N,) crowding distances.

    Within each rank-front and each objective, sort by value; the two
    boundary designs get +inf, interior designs the normalized gap to
    their sorted neighbours (Deb's crowding). Vectorized: one
    ``lexsort((value, rank))`` per objective puts every front
    contiguous in sorted order, ``segment_min/max`` over the front
    segments give the normalization span, and contributions scatter
    back by the sort permutation. D is static and small, so the Python
    loop over objectives unrolls into the trace."""
    n, d = scores.shape
    total = jnp.zeros((n,), scores.dtype)
    for j in range(d):
        f = scores[:, j]
        order = jnp.lexsort((f, ranks))           # rank, then value
        f_s, r_s = f[order], ranks[order]
        new_seg = jnp.concatenate(
            [jnp.ones((1,), bool), r_s[1:] != r_s[:-1]])
        seg = jnp.cumsum(new_seg) - 1             # front id in sort order
        fmin = jax.ops.segment_min(f_s, seg, num_segments=n)
        fmax = jax.ops.segment_max(f_s, seg, num_segments=n)
        span = (fmax - fmin)[seg]
        first = new_seg
        last = jnp.concatenate(
            [r_s[1:] != r_s[:-1], jnp.ones((1,), bool)])
        prev = jnp.concatenate([f_s[:1], f_s[:-1]])
        nxt = jnp.concatenate([f_s[1:], f_s[-1:]])
        gap = (nxt - prev) / jnp.where(span > 0, span, 1.0)
        contrib = jnp.where(first | last, jnp.inf, gap)
        total = total.at[order].add(contrib)
    return total


@traced_closure
def crowded_order(ranks: jax.Array, crowd: jax.Array) -> jax.Array:
    """Permutation sorting by (rank asc, crowding desc) — NSGA-II's
    total preference order (environmental selection and final report
    ordering)."""
    return jnp.lexsort((-crowd, ranks))


@traced_closure
def tournament_select(key: jax.Array, ranks: jax.Array, crowd: jax.Array,
                      n_winners: int) -> jax.Array:
    """Binary tournament by (rank, crowding): (n_winners,) indices."""
    n = ranks.shape[0]
    idx = jax.random.randint(key, (2, n_winners), 0, n)
    a, b = idx[0], idx[1]
    a_wins = (ranks[a] < ranks[b]) | ((ranks[a] == ranks[b])
                                      & (crowd[a] > crowd[b]))
    return jnp.where(a_wins, a, b)


# ---------------------------------------------------------------------------
# the scanned NSGA-II generation
# ---------------------------------------------------------------------------

@traced_closure
def _nsga_generation(key: jax.Array, pop: jax.Array, scores: jax.Array,
                     cards: jax.Array, pc: jax.Array, eta_c: jax.Array,
                     pm: jax.Array, eta_m: jax.Array,
                     score_vec: Callable[[jax.Array], jax.Array],
                     ) -> Tuple[jax.Array, jax.Array]:
    """One NSGA-II generation: tournament-select by (rank, crowding),
    SBX + polynomial mutation (genetic.py's operators, traced phase
    params), then (mu + lambda) environmental selection over parents +
    children. Carries the parent score matrix so each generation scores
    only the P children."""
    P = pop.shape[0]
    ranks = nondominated_rank(scores)
    crowd = crowding_distance(scores, ranks)
    k_t, k_x, k_m = jax.random.split(key, 3)
    n_pairs = (P + 1) // 2
    winners = tournament_select(k_t, ranks, crowd, 2 * n_pairs)
    parents = _to_real(pop[winners], cards)
    x1, x2 = parents[:n_pairs], parents[n_pairs:]
    c1, c2 = _sbx(k_x, x1, x2, pc, eta_c)
    children = jnp.concatenate([c1, c2], axis=0)[:P]
    children = _to_index(
        _poly_mutate(k_m, children, pm, eta_m, cards), cards)
    comb = jnp.concatenate([pop, children], axis=0)
    comb_scores = jnp.concatenate([scores, score_vec(children)], axis=0)
    r2 = nondominated_rank(comb_scores)
    c2d = crowding_distance(comb_scores, r2)
    sel = crowded_order(r2, c2d)[:P]
    return comb[sel], comb_scores[sel]


@traced_closure
def nsga_scan(key: jax.Array, init_pop: jax.Array, cards: jax.Array,
              schedule: jax.Array,
              score_vec: Callable[[jax.Array], jax.Array],
              active: Optional[jax.Array] = None) -> Tuple[jax.Array, ...]:
    """Traceable multi-phase NSGA-II: the whole schedule in one
    lax.scan.

    Returns device arrays (pop, scores, ranks, history): the final
    population sorted by (rank, crowding desc), its (P, D) score
    matrix, its ranks, and the (T+1, D) best-so-far *ideal point*
    (per-objective minimum over everything evaluated) — the
    multi-objective analogue of the GA's best-so-far history, monotone
    non-increasing per column.

    ``active`` is an optional (T,) bool mask over schedule rows; rows
    with ``active[t] == False`` leave the carry untouched, so a
    schedule padded with trailing inactive rows is bit-identical to
    the unpadded run once the history is sliced back to (T+1, D)."""
    scores0 = score_vec(init_pop)
    ideal0 = jnp.min(scores0, axis=0)

    def body(carry, params):
        key, pop, scores, ideal = carry
        key, k = jax.random.split(key)
        pop, scores = _nsga_generation(k, pop, scores, cards, params[0],
                                       params[1], params[2], params[3],
                                       score_vec)
        ideal = jnp.minimum(ideal, jnp.min(scores, axis=0))
        return (key, pop, scores, ideal), ideal

    def body_masked(carry, xs):
        params, act = xs
        key, pop, scores, ideal = carry
        (key2, pop2, scores2, ideal2), _ = body(
            (key, pop, scores, ideal), params)
        key = jnp.where(act, key2, key)
        pop = jnp.where(act, pop2, pop)
        scores = jnp.where(act, scores2, scores)
        ideal = jnp.where(act, ideal2, ideal)
        return (key, pop, scores, ideal), ideal

    carry = (key, init_pop, scores0, ideal0)
    if active is None:
        (key, pop, scores, ideal), hist = jax.lax.scan(
            body, carry, schedule)
    else:
        (key, pop, scores, ideal), hist = jax.lax.scan(
            body_masked, carry, (schedule, active))
    ranks = nondominated_rank(scores)
    crowd = crowding_distance(scores, ranks)
    order = crowded_order(ranks, crowd)
    pop, scores, ranks = pop[order], scores[order], ranks[order]
    hist = jnp.concatenate([ideal0[None], hist], axis=0)
    return pop, scores, ranks, hist


@traced_closure
def nsga_search_kernel(key: jax.Array, cards: jax.Array,
                       schedule: jax.Array,
                       score_vec: Callable[[jax.Array], jax.Array],
                       feasible_fn: Optional[Callable] = None, *,
                       p_h: int, p_e: int, p_ga: int,
                       hamming_sampling: bool = True,
                       oversample: int = 4,
                       active: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, ...]:
    """Traceable Algorithm 1 with a multi-objective tail: the same
    device-resident sampling as genetic.search_kernel (capacity masking
    inside the compiled region), but the P_E Hamming-diverse pool seeds
    the NSGA-II population by (rank, crowding) instead of by scalar
    score. vmap over ``key`` to batch independent searches."""
    key, k_s = jax.random.split(key)
    if hamming_sampling:
        pool = sampling.sample_initial_device(k_s, cards, p_h, p_e,
                                              feasible_fn=feasible_fn,
                                              oversample=oversample)
        s = score_vec(pool)
        r = nondominated_rank(s)
        c = crowding_distance(s, r)
        init = pool[crowded_order(r, c)[:p_ga]]
    elif feasible_fn is None:
        init = sampling.uniform_genomes(k_s, cards, p_ga)
    else:
        pool = sampling.sample_initial_device(k_s, cards, p_h, p_ga,
                                              feasible_fn=feasible_fn,
                                              oversample=oversample)
        init = pool[:p_ga]
    return nsga_scan(key, init, cards, schedule, score_vec, active=active)


# ---------------------------------------------------------------------------
# host-facing results + entry points
# ---------------------------------------------------------------------------

class MOSearchResult(NamedTuple):
    """One NSGA-II search, materialized on host.

    ``population``/``scores``/``ranks`` are sorted by (rank, crowding
    desc), so the searched front is the ``ranks == 0`` prefix.
    ``history`` is the (T+1, D) ideal-point trajectory."""
    population: np.ndarray       # (P, n_params)
    scores: np.ndarray           # (P, D)
    ranks: np.ndarray            # (P,)
    history: np.ndarray          # (T+1, D)
    wall_time_s: float

    def front(self) -> Tuple[np.ndarray, np.ndarray]:
        """(genomes, scores) of the rank-0 (non-dominated) designs."""
        m = self.ranks == 0
        return self.population[m], self.scores[m]


class MultiMOSearchResult(NamedTuple):
    """S independent NSGA-II searches executed as one batched call."""
    populations: np.ndarray      # (S, P, n_params)
    scores: np.ndarray           # (S, P, D)
    ranks: np.ndarray            # (S, P)
    histories: np.ndarray        # (S, T+1, D)
    wall_time_s: float

    @property
    def n_seeds(self) -> int:
        return int(self.populations.shape[0])

    def seed_result(self, i: int) -> MOSearchResult:
        return MOSearchResult(population=self.populations[i],
                              scores=self.scores[i], ranks=self.ranks[i],
                              history=self.histories[i],
                              wall_time_s=self.wall_time_s)

    def union_front(self) -> Tuple[np.ndarray, np.ndarray]:
        """Global searched front: the per-seed rank-0 designs pooled
        and re-filtered to the non-dominated subset (deduplicated).

        Equal, as a set of points, to running pareto_front over *all*
        final-population candidates: any globally non-dominated design
        is rank-0 within its own seed (so it is in the pool), and a
        pool point dominated by any candidate is — by transitivity
        through that candidate's own rank-0 dominators — dominated
        inside the pool too. tests/test_nsga.py pins this."""
        from .pareto import pareto_front
        genomes = self.populations.reshape(-1, self.populations.shape[-1])
        scores = self.scores.reshape(-1, self.scores.shape[-1])
        mask = self.ranks.reshape(-1) == 0
        genomes, scores = genomes[mask], scores[mask]
        uniq, j = np.unique(genomes, axis=0, return_index=True)
        scores = scores[j]
        idx = pareto_front(scores)
        return uniq[idx], scores[idx]


def run_nsga_loop(key: jax.Array, space: SearchSpace,
                  score_vec: Callable[[jax.Array], jax.Array],
                  init_pop: jax.Array, phases: Sequence[Phase],
                  generations_per_phase: int) -> MOSearchResult:
    """Reference host-driven NSGA-II loop (one Python round-trip per
    generation, same RNG stream and jitted generation step as the
    scan). The equivalence oracle for ``nsga_scan`` and the measured
    baseline of the ``nsga_scan`` benchmark cell."""
    t0 = time.perf_counter()
    cards = jnp.asarray(space.cardinalities.astype(np.float32))
    step = _cached_jit(
        ("nsga_loop_step", id(score_vec)),
        lambda: jax.jit(functools.partial(_nsga_generation,
                                          score_vec=score_vec)),
        score_vec)
    schedule = phase_schedule(phases, generations_per_phase)
    pop = init_pop
    scores = score_vec(pop)
    ideal = np.asarray(jnp.min(scores, axis=0))
    hist = [ideal]
    for row in schedule:
        key, k = jax.random.split(key)
        pop, scores = step(k, pop, scores, cards,
                           jnp.float32(row[0]), jnp.float32(row[1]),
                           jnp.float32(row[2]), jnp.float32(row[3]))
        ideal = np.minimum(ideal, np.asarray(jnp.min(scores, axis=0)))
        hist.append(ideal)
    ranks = nondominated_rank(scores)
    crowd = crowding_distance(scores, ranks)
    order = np.asarray(crowded_order(ranks, crowd))
    return MOSearchResult(population=np.asarray(pop)[order],
                          scores=np.asarray(scores)[order],
                          ranks=np.asarray(ranks)[order],
                          history=np.stack(hist),
                          wall_time_s=time.perf_counter() - t0)


def batched_nsga_search(keys: jax.Array, space: SearchSpace,
                        score_vec: Callable[[jax.Array], jax.Array],
                        p_h: int = 1000, p_e: int = 500, p_ga: int = 40,
                        generations_per_phase: int = 10,
                        phases: Sequence[Phase] = FOUR_PHASES,
                        feasible_fn: Optional[Callable] = None,
                        hamming_sampling: bool = True,
                        oversample: int = 4,
                        mesh=None) -> MultiMOSearchResult:
    """S independent NSGA-II searches in one compiled device call.

    Mirrors genetic.batched_joint_search: jit(vmap(nsga_search_kernel))
    over the (S, key) batch, compiled kernels cached per (scorer,
    budget), the search axis sharded over the mesh 'data' axis when
    given (core.distributed.compile_batched_search)."""
    t0 = time.perf_counter()
    cards = jnp.asarray(space.cardinalities.astype(np.float32))
    schedule = jnp.asarray(phase_schedule(phases, generations_per_phase))

    # schedule + active as runtime lane data, exactly like
    # genetic.batched_joint_search: the compiled kernel matches the
    # campaign engine's bucketed lanes bit for bit
    def one(key, sched, active):
        return nsga_search_kernel(key, cards, sched, score_vec,
                                  feasible_fn, p_h=p_h, p_e=p_e,
                                  p_ga=p_ga,
                                  hamming_sampling=hamming_sampling,
                                  oversample=oversample, active=active)

    from .distributed import compile_batched_search
    fn = _cached_jit(
        ("nsga_batched", id(space), id(score_vec), id(feasible_fn),
         id(mesh), p_h, p_e, p_ga, generations_per_phase, tuple(phases),
         hamming_sampling, oversample),
        lambda: compile_batched_search(one, mesh=mesh),
        space, score_vec, feasible_fn, mesh)
    S = keys.shape[0]
    scheds = jnp.broadcast_to(schedule, (S,) + schedule.shape)
    actives = jnp.ones((S, schedule.shape[0]), bool)
    pops, scores, ranks, hists = fn(keys, scheds, actives)
    return MultiMOSearchResult(
        populations=np.asarray(pops), scores=np.asarray(scores),
        ranks=np.asarray(ranks), histories=np.asarray(hists),
        wall_time_s=time.perf_counter() - t0)


def nsga_search(key: jax.Array, space: SearchSpace,
                score_vec: Callable[[jax.Array], jax.Array],
                p_h: int = 1000, p_e: int = 500, p_ga: int = 40,
                generations_per_phase: int = 10,
                phases: Sequence[Phase] = FOUR_PHASES,
                feasible_fn: Optional[Callable] = None,
                hamming_sampling: bool = True) -> MOSearchResult:
    """One NSGA-II search (a single-seed batched call)."""
    res = batched_nsga_search(
        key[None], space, score_vec, p_h=p_h, p_e=p_e, p_ga=p_ga,
        generations_per_phase=generations_per_phase, phases=phases,
        feasible_fn=feasible_fn, hamming_sampling=hamming_sampling)
    return res.seed_result(0)
