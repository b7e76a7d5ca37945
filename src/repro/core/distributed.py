"""Distributed population evaluation (DESIGN.md §3).

The paper parallelizes design evaluation over 64 CPU cores with a
process pool; the TPU-native equivalent shards the jit'd cost model
across the device mesh. Two granularities:

  * population-axis sharding of one evaluation call — now built by
    ``core.scoring.build_scorer`` / ``scoring.sharded_score_fn`` (the
    host-driven search paths and the dry-run's "paper's technique"
    cell); ``make_sharded_scorer`` below is the deprecated wrapper;
  * ``compile_batched_search`` — shard the *search* axis: a
    device-resident search kernel (core.genetic.search_kernel,
    core.nsga.nsga_search_kernel, core.baselines.baseline_kernel) is
    vmapped over independent searches (seeds, workload-specific
    baselines, Table 3 algorithm fan-outs) and each device runs whole
    searches locally, which is communication-free end to end.

``cached_compile`` is the shared compiled-kernel cache all three
search engines register their jitted kernels in, so re-running the
same search setup never re-traces a whole scanned search.

Used by launch/search.py, experiments/runner.py, and exercised
(lower + compile) by the production-mesh dry-run.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Compiled search kernels cached per (closure identity, static knobs):
# re-running the same search setup (e.g. a host loop re-driving one
# seed, or the Table 3 runner re-dispatching an algorithm) must not
# re-trace the whole scanned search. Values pin the closures so id()
# keys stay valid. LRU-bounded: a long campaign cycling through many
# scenario/bucket shapes would otherwise pin every compiled executable
# (and the scorer closures passed as refs) for the process lifetime.
KERNEL_CACHE_MAXSIZE = 128
_KERNEL_CACHE: "OrderedDict[object, tuple]" = OrderedDict()
_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def cached_compile(key, builder: Callable, *refs):
    """Return (building once) the compiled callable registered under
    ``key``; ``refs`` keep the closures the key's id() components point
    at alive for the entry's lifetime. Least-recently-used entries are
    evicted past ``KERNEL_CACHE_MAXSIZE`` (an evicted kernel is merely
    re-traced on next use — and usually re-hits the persistent XLA
    compilation cache, see experiments/campaign.py)."""
    entry = _KERNEL_CACHE.get(key)
    if entry is None:
        _CACHE_STATS["misses"] += 1
        entry = (builder(), refs)
        _KERNEL_CACHE[key] = entry
        while len(_KERNEL_CACHE) > KERNEL_CACHE_MAXSIZE:
            _KERNEL_CACHE.popitem(last=False)
            _CACHE_STATS["evictions"] += 1
    else:
        _CACHE_STATS["hits"] += 1
        _KERNEL_CACHE.move_to_end(key)
    return entry[0]


def is_cached(key) -> bool:
    """Whether ``cached_compile`` holds a kernel under ``key`` (no
    counter moves)."""
    return key in _KERNEL_CACHE


def kernel_cache_stats() -> dict:
    """Snapshot of the in-process kernel cache counters + current size."""
    return dict(_CACHE_STATS, size=len(_KERNEL_CACHE))


def kernel_cache_clear() -> None:
    """Drop every cached kernel and zero the counters (tests/benches)."""
    _KERNEL_CACHE.clear()
    for k in _CACHE_STATS:
        _CACHE_STATS[k] = 0


def make_sharded_scorer(*_args, **_kwargs):
    """Removed (was a DeprecationWarning wrapper). Build the scorer
    with the mesh and shard its traced closure::

        sc = build_scorer(space, ScorerSpec(objective, workloads=wl),
                          mesh=mesh)
        sharded = sharded_score_fn(sc.score, mesh)
    """
    raise ImportError(
        "distributed.make_sharded_scorer was removed; use "
        "core.scoring.build_scorer(space, ScorerSpec(objective, "
        "workloads=wl), mesh=mesh) with scoring.sharded_score_fn "
        "(or import both from repro.api)")


def compile_batched_search(search_one: Callable, mesh: Optional[Mesh] = None,
                           axis: str = "data", *,
                           donate: bool = False) -> Callable:
    """jit(vmap(search_one)): S independent searches as one computation.

    ``search_one`` is a traceable kernel ``key -> pytree of arrays``
    (core.genetic.search_kernel closed over its schedule/scorer); the
    returned callable maps a (S, key) batch to the stacked results.
    With a ``mesh``, the search axis is sharded over ``axis`` by
    ``shard_map``: every device runs S/axis_size whole searches with
    zero inter-device communication (searches are independent by
    construction), so the partitioner never splits a search, nor a
    Pallas kernel inside one. The axis size must then divide S; callers
    fall back to mesh=None otherwise (see experiments/runner._search_mesh
    and the campaign's device-rounded lane tiers).

    ``donate=True`` donates every input buffer (lane keys, padded
    schedules, masks) to the computation — callers must pass freshly
    built arrays and not reuse them. Worth it off-CPU at paper-scale
    populations; on CPU XLA typically declines the donation (and logs
    warnings), so the campaign engine only asks off-CPU.
    """
    fn = jax.vmap(search_one)
    kw = {}
    if donate:
        import inspect
        n_args = len(inspect.signature(search_one).parameters)
        kw["donate_argnums"] = tuple(range(n_args))
    if mesh is None:
        return jax.jit(fn, **kw)
    sh = NamedSharding(mesh, P(axis))
    # check_vma=False: the scanned searches build their carries from
    # unsharded constants, which the varying-axis check rejects, though
    # every lane's search is local to its device by construction
    local = jax.shard_map(fn, mesh=mesh, in_specs=P(axis),
                          out_specs=P(axis), check_vma=False)
    return jax.jit(local, in_shardings=sh, out_shardings=sh, **kw)
