"""Hamming-distance-based initial sampling (paper §III-C2, Eqs. 1-2).

Three steps, exactly as the paper:
  1. randomly sample P_H candidate genomes from the space (RRAM: reject
     designs that cannot hold the largest workload);
  2. greedily select the P_E most mutually distant candidates under
     Hamming distance (max-min greedy, seeded with the first candidate);
  3. evaluate those and keep the best P_GA as the GA's initial
     population (done by the caller / genetic.py).

The greedy max-min selection runs on-device with lax.fori_loop:
maintain d_min(X, C2) for every candidate and add argmax(d_min) each
iteration — O(P_E · P_H · n_params).

Two entry points:
  * ``sample_initial``        — host-orchestrated (the paper's rejection
                                loop for the capacity filter);
  * ``sample_initial_device`` — fully traceable (scan/vmap-safe): a
                                statically oversampled pool is
                                capacity-masked *inside* the compiled
                                region, so the device-resident search
                                kernel (genetic.search_kernel) never
                                leaves the device for sampling.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .search_space import SearchSpace
from .tracing import traced_closure


@traced_closure
def uniform_genomes(key: jax.Array, cards: jax.Array, n: int) -> jax.Array:
    """Traceable uniform genomes from a cardinality array:
    (n, n_params) int32 of value indices."""
    u = jax.random.uniform(key, (n, cards.shape[0]))
    return jnp.floor(u * cards[None, :].astype(jnp.float32)).astype(
        jnp.int32)


def random_genomes(key: jax.Array, space: SearchSpace, n: int) -> jax.Array:
    """Uniform random genomes: (n, n_params) int32 of value indices."""
    return uniform_genomes(key, jnp.asarray(space.cardinalities), n)


@traced_closure
@jax.named_scope("hamming_select")
def hamming_select(candidates: jax.Array, n_select: int,
                   n_valid: Optional[jax.Array] = None) -> jax.Array:
    """Greedy max-min Hamming-distance subset selection.

    candidates: (P_H, n) int32. Returns (n_select, n) int32.

    ``n_valid`` (traced scalar) restricts selection to the candidate
    *prefix* [0, n_valid): entries past it are treated as already taken
    and only reappear (as duplicates of the seed) once every valid
    candidate is exhausted — the capacity-masked device path orders
    feasible candidates first and passes the feasible count here.
    """
    P_H = candidates.shape[0]
    n_select = min(n_select, P_H)

    def dist_to(idx):
        return jnp.sum(candidates != candidates[idx][None, :], axis=1)

    selected = jnp.zeros((n_select,), jnp.int32)
    d_min = dist_to(0)
    # first candidate seeds the set (paper: C2 = {c_1-1})
    taken = jnp.zeros((P_H,), bool).at[0].set(True)
    if n_valid is not None:
        taken = taken | (jnp.arange(P_H) >= n_valid)

    def body(i, state):
        selected, d_min, taken = state
        masked = jnp.where(taken, -1, d_min)
        nxt = jnp.argmax(masked).astype(jnp.int32)
        selected = selected.at[i].set(nxt)
        d_min = jnp.minimum(d_min, dist_to(nxt))
        taken = taken.at[nxt].set(True)
        return selected, d_min, taken

    selected, _, _ = jax.lax.fori_loop(1, n_select, body,
                                       (selected, d_min, taken))
    return candidates[selected]


@traced_closure
def sample_initial_device(key: jax.Array, cards: jax.Array, p_h: int,
                          p_e: int,
                          feasible_fn: Optional[Callable] = None,
                          oversample: int = 4) -> jax.Array:
    """Traceable ``sample_initial``: capacity masking inside the
    compiled region (scan/vmap-safe — static shapes, no host syncs).

    Without a filter this is bit-identical to the host path: P_H
    uniform genomes -> greedy Hamming selection. With ``feasible_fn``
    (traceable (N, n) -> (N,) bool), a statically oversampled pool is
    sorted feasible-first (stable, preserving draw order) and the
    selection is confined to the feasible prefix; if fewer than P_E
    candidates are feasible the set is padded with duplicates of the
    seed rather than with infeasible designs.
    """
    if feasible_fn is None:
        return hamming_select(uniform_genomes(key, cards, p_h), p_e)
    pool = uniform_genomes(key, cards, p_h * oversample)
    ok = feasible_fn(pool)
    order = jnp.argsort(~ok)          # stable: feasible first, draw order
    cands = pool[order[:p_h]]
    n_valid = jnp.minimum(jnp.sum(ok), p_h)
    return hamming_select(cands, p_e, n_valid=n_valid)


def sample_initial(key: jax.Array, space: SearchSpace, p_h: int, p_e: int,
                   capacity_filter=None, max_tries: int = 20) -> jax.Array:
    """P_H random (feasibility-filtered) -> P_E Hamming-diverse genomes.

    capacity_filter: optional fn(genomes (N, n)) -> (N,) bool keeping
    designs that can hold the largest workload (RRAM weight-stationary
    case in Algorithm 1). Host-orchestrated rejection loop; the
    device-resident search path uses ``sample_initial_device`` instead.
    """
    if capacity_filter is None:
        cands = random_genomes(key, space, p_h)
    else:
        pool = []
        total = 0
        for t in range(max_tries):
            key, k = jax.random.split(key)
            g = random_genomes(k, space, p_h)
            keep = np.asarray(capacity_filter(g))
            g = np.asarray(g)[keep]
            pool.append(g)
            total += g.shape[0]
            if total >= p_h:
                break
        cands = jnp.asarray(np.concatenate(pool, axis=0))
        if cands.shape[0] < 2:
            raise RuntimeError(
                "capacity filter rejected (almost) all sampled designs — "
                "the largest workload does not fit anywhere in this space")
        cands = cands[:p_h]
    return hamming_select(cands, p_e)
