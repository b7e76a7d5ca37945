"""Campaign execution engine: a set of scenario runs as ONE
schedulable workload.

``run --all`` (and the nightly CI job) used to execute ~25 registered
scenarios strictly sequentially: every scenario re-traced and
re-compiled its search kernel even when it shared (space, populations,
schedule shape, algorithm, objective arity, backend) with a neighbor,
and the runner blocked on host transfers + report rendering between
device calls. This module turns the scenario list into buckets of
shape-identical searches and executes each bucket as one batched
device call:

* **shape bucketing** — every run is canonicalized to a bucket
  signature (scorer content key, engine kind, populations, generation
  tier, Hamming/feasibility flags, workload-dispatch flag). Generation
  counts pad up to powers-of-two-ish tiers with trailing rows masked
  *inside* the scan (the ``active`` mask of core.genetic.ga_scan /
  core.nsga.nsga_scan / core.baselines.baseline_scan) — pinned
  bit-identical to the unpadded run (tests/test_campaign.py).
  Populations stay exact in the signature: unlike masked generations,
  a padded population changes PRNG draw *shapes* (threefry counters
  are laid out per output element), so trajectories would diverge —
  padding there would be score-plausible but not run-identical, and
  the engine refuses to trade reproducibility for fewer compiles.
* **mega-batching** — all same-bucket lanes run as one
  ``compile_batched_search`` call per lane flavor: scenario × seeds
  for the generalized search, and scenario × seeds × workloads for
  the specific baselines (the same trick runner.run_specific_fanout
  plays). The two flavors dispatch through *separate* kernels built
  from the exact closures the sequential path compiles
  (``traced.score`` vs ``traced.score_w``) — merging them into one
  ``jnp.where(w < 0, ...)`` kernel would let XLA fuse the generalized
  evaluation differently and drift by ULPs. Per-lane schedules and
  masks are runtime data, so one compiled kernel serves every
  scenario in the bucket; the lane axis itself pads to tiers
  (replicated lane 0, sliced off on drain) so bucket batches of
  nearby sizes reuse one executable shape.
* **persistent compilation cache** — ``enable_persistent_cache`` wires
  jax's on-disk compilation cache at ``JAX_COMPILATION_CACHE_DIR`` or,
  unset, a fixed in-checkout directory (so repeated CLI invocations and
  nightly CI skip XLA compile entirely) plus a small JSON index keyed
  by bucket signature whose hit/miss counters surface in the campaign
  stats.
* **async pipelining** — jax dispatch is asynchronous: buckets are
  dispatched ``window`` deep before the oldest is drained, so host
  work (result finalization, JSON/markdown rendering) overlaps device
  compute, and each drain materializes arrays once.

Scenario semantics are untouched: per-lane PRNG keys, schedules and
scorers are exactly the sequential path's, and result finalization is
the shared runner.finalize_result — result JSONs are byte-identical
to ``run_scenario``'s modulo timing fields. ``random`` and
``alg_compare`` scenarios (host-driven / own-schema paths) fall back
to the sequential runner inside the campaign.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import (MultiMOSearchResult, MultiSearchResult, nonideal,
                    search_kernel)
from ..core.distributed import (cached_compile, compile_batched_search,
                                is_cached, kernel_cache_stats)
from ..core.nsga import nsga_search_kernel
from ..core.scoring import Scorer
from ..core.tracing import span
from . import report, runner
from .scenarios import Scenario

# Generation/lane tier ladders: powers of two densified with 3*2^k so
# padding waste stays under ~33% (typically well under 20%). Distinct
# (T, B) pairs that round to the same tiers share one compiled kernel.
GEN_TIERS = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128)
LANE_TIERS = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192,
              256)


def _tier(n: int, tiers: Sequence[int], step: int) -> int:
    for t in tiers:
        if n <= t:
            return t
    return ((n + step - 1) // step) * step


def gen_tier(t: int) -> int:
    """Smallest schedule-row tier >= t (multiples of 64 past the
    ladder)."""
    return _tier(t, GEN_TIERS, 64)


def lane_tier(b: int) -> int:
    """Smallest batch-lane tier >= b (multiples of 128 past the
    ladder), rounded up to a multiple of the device count so that every
    bucket shards over all devices (runner._search_mesh shards only a
    lane count the device count divides)."""
    n_dev = jax.device_count()
    return -(-_tier(b, LANE_TIERS, 128) // n_dev) * n_dev


def scorer_key(scenario: Scenario) -> Tuple:
    """Content key of a scenario's Scorer: two scenarios with equal
    keys build arithmetically identical scorers (same space, workload
    set, objective, calibration fidelity and resolved backend), so the
    campaign builds one Scorer — and one jitted evaluator — for e.g. a
    scenario and its ``_plain`` / ``_random`` registry variants."""
    return (scenario.mem, scenario.reduced_space, scenario.tech_variable,
            scenario.workload_source, tuple(scenario.workloads),
            scenario.seq, scenario.objective, scenario.min_accuracy,
            scenario.n_calib, scenario.calib_k,
            nonideal.resolve_backend(scenario.backend))


@dataclasses.dataclass
class CampaignJob:
    """One scenario run inside a campaign."""
    scenario: Scenario
    seeds: List[int]
    kind: str                    # "bucket" | "fallback" | "cached"
    t0: float = 0.0
    setup: Optional[runner.ScenarioSetup] = None
    traced: Optional[Scorer] = None
    # bucket-kind shape info (GA engines; NSGA-II reuses p_*/sched)
    engine: str = "ga"           # "ga" | "nsga"
    sched: Optional[np.ndarray] = None
    p_h: int = 0
    p_e: int = 0
    hamming: bool = True
    wants_spec: bool = False
    result: Optional[Dict] = None
    error: Optional[str] = None  # set when a degraded retry also fails

    @property
    def n_workloads(self) -> int:
        return len(self.setup.workloads)

    @property
    def n_spec(self) -> int:
        return (len(self.seeds) * self.n_workloads if self.wants_spec
                else 0)

    @property
    def n_lanes(self) -> int:
        return len(self.seeds) + self.n_spec

    def bucket_key(self) -> Tuple:
        sc = self.scenario
        return (self.engine, scorer_key(sc), self.p_h, self.p_e,
                sc.budget.p_ga, self.hamming, sc.mem == "rram",
                gen_tier(self.sched.shape[0]))


def _job_shape(job: CampaignJob) -> None:
    """Fill the job's kernel-shape fields — the exact populations and
    schedule the sequential path (run_search_batched /
    run_mo_search_batched / _specific_budget) would use."""
    from ..core import FOUR_PHASES, PLAIN_PHASE, phase_schedule
    sc, b = job.scenario, job.scenario.budget
    if sc.algorithm == "plain":
        job.sched = np.asarray(
            phase_schedule((PLAIN_PHASE,), b.total_generations))
        job.p_h, job.p_e = max(4 * b.p_ga, 200), b.p_ga
        job.hamming = False
    else:
        job.sched = np.asarray(phase_schedule(FOUR_PHASES, b.generations))
        job.p_h, job.p_e = b.p_h, b.p_e
        job.hamming = True


def plan_campaign(scenarios: Sequence[Scenario],
                  out_dir: str = runner.DEFAULT_OUT_DIR,
                  force: bool = False, seed: Optional[int] = None,
                  n_seeds: Optional[int] = None,
                  write: bool = True) -> List[CampaignJob]:
    """Scenario list -> jobs, with shared Scorers resolved.

    Scenarios whose result cache already matches become ``cached``
    jobs; ``random``/``alg_compare`` algorithms and multi-objective
    non-fourphase combinations become ``fallback`` jobs (executed by
    the sequential runner); everything else gets a bucket signature.
    """
    scorers: Dict[Tuple, Tuple[runner.ScenarioSetup, Scorer]] = {}
    jobs: List[CampaignJob] = []
    for sc in scenarios:
        s0 = sc.seed if seed is None else seed
        ns = sc.budget.n_seeds if n_seeds is None else n_seeds
        seeds = [s0 + j for j in range(ns)]
        job = CampaignJob(scenario=sc, seeds=seeds, kind="bucket",
                          t0=time.perf_counter())
        if write and not force:
            cached = runner.load_cached_result(sc, out_dir, s0, ns)
            if cached is not None:
                job.kind, job.result = "cached", cached
                jobs.append(job)
                continue
        if sc.algorithm in ("random", "alg_compare"):
            job.kind = "fallback"
            jobs.append(job)
            continue
        key = scorer_key(sc)
        if key not in scorers:
            st = runner.setup_scenario(sc)
            scorers[key] = (st, runner.build_scenario_scorer(sc, st))
        job.setup, job.traced = scorers[key]
        if job.setup.is_mo:
            if sc.algorithm != "fourphase":
                job.kind = "fallback"
                jobs.append(job)
                continue
            job.engine = "nsga"
        job.wants_spec = (sc.specific_baselines
                          and job.n_workloads > 1
                          and not job.setup.is_mo)
        _job_shape(job)
        jobs.append(job)
    return jobs


# ---------------------------------------------------------------------------
# bucket kernels
# ---------------------------------------------------------------------------


def _build_bucket_kernel(key: Tuple, traced: Scorer, space, mesh,
                         part: str = "main") -> object:
    """The bucket's compiled callable: jit(vmap(search lane)). Every
    lane carries (PRNG key, padded schedule, active mask — plus a
    workload index on the specific part) as runtime data; the
    scorer/populations/tier are static.

    The generalized (``part="main"``) and specific-baseline
    (``part="spec"``) lanes compile as SEPARATE kernels built from the
    exact closures the sequential path uses — ``traced.score`` vs
    ``traced.score_w`` (runner.run_specific_fanout's construction).
    Merging them into one ``jnp.where(w < 0, ...)`` kernel is tempting
    (XLA CSE shares the evaluation) but lets the compiler fuse the
    generalized reduction differently than the sequential build and
    drift by ULPs — byte-identity to ``run --sequential`` is part of
    the engine's contract.

    The compiled module is named after ``lane``. The name is part of the
    persistent compilation cache's key, and the named scopes inside are
    not (the key strips debug info): renaming it when the scopes change
    keeps a cache from serving an executable whose op metadata lacks
    the scopes that ``bench/scopes.py`` reads.
    """
    engine, _, p_h, p_e, p_ga, hamming, rram, _ = key
    cards = jnp.asarray(space.cardinalities.astype(np.float32))
    donate = jax.default_backend() != "cpu"

    if engine == "nsga":
        def lane(k, schedule, active):
            fe = traced.feasible if rram else None
            return nsga_search_kernel(
                k, cards, schedule, traced.score_vec, fe, p_h=p_h,
                p_e=p_e, p_ga=p_ga, hamming_sampling=hamming,
                active=active)
    elif part == "spec":
        def lane(k, w, schedule, active):
            def sc(g):
                return traced.score_w(g, w)
            fe = None
            if rram:
                def fe(g):
                    return traced.feasible_w(g, w)
            return search_kernel(k, cards, schedule, sc, fe, p_h=p_h,
                                 p_e=p_e, p_ga=p_ga,
                                 hamming_sampling=hamming, active=active)
    else:
        def lane(k, schedule, active):
            fe = traced.feasible if rram else None
            return search_kernel(k, cards, schedule, traced.score, fe,
                                 p_h=p_h, p_e=p_e, p_ga=p_ga,
                                 hamming_sampling=hamming, active=active)
    return compile_batched_search(lane, mesh=mesh, donate=donate)


class _Bucket:
    """Same-signature jobs packed onto one vmapped lane axis per lane
    flavor (generalized "main" lanes; specific-baseline "spec"
    lanes)."""

    def __init__(self, key: Tuple):
        self.key = key
        self.jobs: List[CampaignJob] = []
        self.offsets: List[Tuple[int, int]] = []   # (main, spec)
        self.n_main = 0
        self.n_spec = 0
        self.outs = None
        self.spec_outs = None
        self.dispatch_s = 0.0
        self.wait_s = 0.0
        self.finalize_s = 0.0
        self.drain_s = 0.0

    def add(self, job: CampaignJob) -> None:
        self.offsets.append((self.n_main, self.n_spec))
        self.jobs.append(job)
        self.n_main += len(job.seeds)
        self.n_spec += job.n_spec

    @property
    def n_lanes(self) -> int:
        return self.n_main + self.n_spec

    @property
    def lanes_padded_to(self) -> int:
        return (lane_tier(self.n_main)
                + (lane_tier(self.n_spec) if self.n_spec else 0))

    @property
    def tier(self) -> int:
        return self.key[7]

    def signature(self) -> str:
        """Stable hash of the bucket signature + padded lane counts
        (the persistent-index key; lane counts are part of the
        compiled shapes)."""
        raw = repr((self.key, lane_tier(self.n_main),
                    lane_tier(self.n_spec) if self.n_spec else 0))
        return hashlib.sha256(raw.encode()).hexdigest()[:16]

    def _padded_sched(self, job: CampaignJob):
        T = job.sched.shape[0]
        pad = np.concatenate(
            [job.sched, np.tile(job.sched[-1:], (self.tier - T, 1))])
        act = np.zeros((self.tier,), bool)
        act[:T] = True
        return pad, act

    @staticmethod
    def _pad_lanes(cols: List[list], n: int, tier: int) -> Tuple:
        """Replicate lane 0 up to the tier so nearby batch sizes share
        one executable shape; sliced off on drain."""
        return tuple(c + c[:1] * (tier - n) for c in cols)

    def _main_arrays(self) -> Tuple[np.ndarray, ...]:
        keys, scheds, actives = [], [], []
        for job in self.jobs:
            pad, act = self._padded_sched(job)
            keys += [jax.random.PRNGKey(s) for s in job.seeds]
            scheds += [pad] * len(job.seeds)
            actives += [act] * len(job.seeds)
        keys, scheds, actives = self._pad_lanes(
            [keys, scheds, actives], self.n_main,
            lane_tier(self.n_main))
        return (np.stack([np.asarray(k) for k in keys]),
                np.stack(scheds), np.stack(actives))

    def _spec_arrays(self) -> Tuple[np.ndarray, ...]:
        keys, ws, scheds, actives = [], [], [], []
        for job in self.jobs:
            if not job.wants_spec:
                continue
            pad, act = self._padded_sched(job)
            W = job.n_workloads
            lane_keys = [jax.random.PRNGKey(s + 1000 + i)
                         for s in job.seeds for i in range(W)]
            keys += lane_keys
            ws += [i for _ in job.seeds for i in range(W)]
            scheds += [pad] * len(lane_keys)
            actives += [act] * len(lane_keys)
        keys, ws, scheds, actives = self._pad_lanes(
            [keys, ws, scheds, actives], self.n_spec,
            lane_tier(self.n_spec))
        return (np.stack([np.asarray(k) for k in keys]),
                np.asarray(ws, np.int32), np.stack(scheds),
                np.stack(actives))

    def _kernel_key(self, part: str, n_lanes: int) -> Tuple:
        """(kernel-cache key, mesh) of one lane flavor's kernel."""
        b = lane_tier(n_lanes)
        mesh = runner._search_mesh(b)
        return (("campaign", self.key, part, b,
                 mesh.devices.size if mesh is not None else 0), mesh)

    def _kernel(self, key: Tuple, mesh, part: str) -> object:
        job = self.jobs[0]
        return cached_compile(
            key,
            lambda: _build_bucket_kernel(self.key, job.traced,
                                         job.setup.space, mesh, part),
            job.traced)

    def dispatch(self) -> None:
        """Trace/compile (cached) + enqueue the device call(s). Returns
        with the result arrays still in flight (jax async dispatch)."""
        parts = [("main", self.n_main, self._main_arrays)]
        if self.n_spec:
            parts.append(("spec", self.n_spec, self._spec_arrays))
        keys = [self._kernel_key(part, n) for part, n, _ in parts]
        bucket = self.signature()
        hit = all(is_cached(key) for key, _ in keys)
        outs = []
        with span("campaign.dispatch", bucket=bucket,
                  kernel_cache="hit" if hit else "miss") as sp:
            for (part, _, lane_arrays), (key, mesh) in zip(parts, keys):
                kern = self._kernel(key, mesh, part)
                with span("campaign.lane_arrays", bucket=bucket,
                          part=part):
                    arrays = lane_arrays()
                outs.append(kern(*[jnp.asarray(a) for a in arrays]))
        self.outs = outs[0]
        if self.n_spec:
            self.spec_outs = outs[1]
        self.dispatch_s = sp.seconds

    def drain(self, out_dir: str, write: bool,
              specific_fanout: bool) -> None:
        """Materialize the bucket's arrays (blocks) and finalize every
        job's result dict + artifacts."""
        t0 = time.perf_counter()
        bucket = self.signature()
        with span("campaign.device_wait", bucket=bucket) as sp:
            outs = [np.asarray(o) for o in self.outs]
            spec_outs = ([np.asarray(o) for o in self.spec_outs]
                         if self.spec_outs is not None else None)
        self.outs = self.spec_outs = None
        self.wait_s = sp.seconds
        for job, (mo, so) in zip(self.jobs, self.offsets):
            with span("campaign.finalize", scenario=job.scenario.name,
                      bucket=bucket) as sp:
                job.result = self._finalize(job, mo, so, outs, spec_outs,
                                            out_dir, write,
                                            specific_fanout)
            self.finalize_s += sp.seconds
        self.drain_s = time.perf_counter() - t0

    def _finalize(self, job: CampaignJob, mo: int, so: int, outs,
                  spec_outs, out_dir: str, write: bool,
                  specific_fanout: bool) -> Dict:
        """One job's lanes of the drained arrays -> its result dict
        (+ artifacts) through runner.finalize_result."""
        share = self.wait_s * job.n_lanes / max(self.n_lanes, 1)
        S, T = len(job.seeds), job.sched.shape[0]
        sl = slice(mo, mo + S)
        spec = None
        if job.engine == "nsga":
            pop, scores, ranks, hist = outs
            res = MultiMOSearchResult(
                populations=pop[sl], scores=scores[sl],
                ranks=ranks[sl], histories=hist[sl][:, :T + 1],
                wall_time_s=share)
        else:
            best_g, best_s, hist, pops, pscores = outs
            res = MultiSearchResult(
                best_genomes=best_g[sl], best_scores=best_s[sl],
                histories=np.concatenate(
                    [hist[sl][:, :T], hist[sl][:, -1:]], axis=1),
                populations=pops[sl], scores=pscores[sl],
                wall_time_s=share, sampling_time_s=0.0)
            if job.wants_spec:
                W = job.n_workloads
                ss = slice(so, so + S * W)
                genomes = spec_outs[0][ss].reshape(S, W, -1)
                with span("runner.design_metrics",
                          scenario=job.scenario.name):
                    edap = runner.specific_edap(job.traced, genomes)
                spec = {
                    "genomes": genomes,
                    "best_scores": spec_outs[1][ss].reshape(S, W),
                    "edap": edap,
                }
        return runner.finalize_result(
            job.scenario, job.setup, job.traced, res, job.seeds,
            spec=spec, specific_fanout=specific_fanout,
            out_dir=out_dir, write=write, t0=job.t0)


def bucket_jobs(jobs: Sequence[CampaignJob]
                ) -> "OrderedDict[Tuple, _Bucket]":
    """Group the plan's bucket-kind jobs by bucket signature, in first-
    appearance order (cached/fallback jobs are skipped — they never
    touch a bucket kernel)."""
    buckets: "OrderedDict[Tuple, _Bucket]" = OrderedDict()
    for job in jobs:
        if job.kind != "bucket":
            continue
        bk = job.bucket_key()
        if bk not in buckets:
            buckets[bk] = _Bucket(bk)
        buckets[bk].add(job)
    return buckets


def _run_bucket_sequential(bucket: _Bucket, out_dir: str, write: bool,
                           specific_fanout: bool, cause: str) -> None:
    """Degraded path: execute every job of a failed bucket through the
    sequential runner (per-scenario compile + dispatch). One job
    failing does not sink its bucket-mates; it records ``job.error``
    and leaves ``job.result`` None for the caller to surface."""
    import traceback
    for job in bucket.jobs:
        if job.result is not None:
            continue
        try:
            job.result = runner.run_scenario(
                job.scenario, out_dir=out_dir, force=True,
                seed=job.seeds[0], write=write,
                n_seeds=len(job.seeds), specific_fanout=specific_fanout)
        except Exception:
            job.error = (f"bucket degraded ({cause}); sequential retry "
                         f"failed:\n{traceback.format_exc(limit=8)}")


def execute_buckets(buckets: Sequence[_Bucket],
                    out_dir: str = runner.DEFAULT_OUT_DIR, *,
                    write: bool = True, specific_fanout: bool = True,
                    window: int = 2, on_drained=None,
                    degrade_sequential: bool = False) -> int:
    """Dispatch + drain a planned bucket sequence with async
    pipelining: buckets are dispatched ``window`` deep before the
    oldest drains, so host-side result finalization overlaps device
    compute. Shared by run_campaign and serve.codesign.CodesignService.

    ``on_drained(bucket)`` fires after each bucket's jobs carry their
    results (the service streams progress / completes futures from
    it). With ``degrade_sequential`` a bucket whose kernel fails to
    compile (or whose drain raises) falls back to per-scenario
    sequential execution instead of sinking the run; returns the
    number of buckets degraded (0 when all mega-batched calls held).
    """
    degraded = 0
    inflight: List[_Bucket] = []

    def _drain(bucket: _Bucket) -> None:
        nonlocal degraded
        try:
            bucket.drain(out_dir, write, specific_fanout)
        except Exception as e:
            if not degrade_sequential:
                raise
            _run_bucket_sequential(bucket, out_dir, write,
                                   specific_fanout, repr(e))
            degraded += 1
        if on_drained is not None:
            on_drained(bucket)

    for bucket in buckets:
        try:
            bucket.dispatch()
        except Exception as e:
            if not degrade_sequential:
                raise
            _run_bucket_sequential(bucket, out_dir, write,
                                   specific_fanout, repr(e))
            degraded += 1
            if on_drained is not None:
                on_drained(bucket)
            continue
        inflight.append(bucket)
        while len(inflight) > max(window, 1):
            _drain(inflight.pop(0))
    while inflight:
        _drain(inflight.pop(0))
    return degraded


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

_INDEX_NAME = "campaign_index.json"

# <checkout>/.jax_compile_cache: derived from this package's location
# (src/repro/experiments/), so it is the same path on every run and a
# cache written by one run is found by the next.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))),
    ".jax_compile_cache")


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else the
    fixed in-checkout ``DEFAULT_CACHE_DIR``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable_persistent_cache() -> str:
    """Point jax's on-disk compilation cache at ``compile_cache_dir()``
    (created if missing) with thresholds dropped to cache every search
    kernel. Called once by each entry point (experiments CLI, service
    launcher, chip smoke); ``run_campaign`` keeps its bucket-signature
    index next to the cache whenever one is enabled. Returns the
    index's path."""
    cache_dir = compile_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return os.path.join(cache_dir, _INDEX_NAME)


def _cache_entries(cache_dir: Optional[str]) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(1 for n in os.listdir(cache_dir) if n != _INDEX_NAME)


def _load_index(path: str) -> Dict:
    if os.path.exists(path):
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            pass
    return {}


# ---------------------------------------------------------------------------
# the campaign loop
# ---------------------------------------------------------------------------


def run_campaign(scenarios: Sequence[Scenario],
                 out_dir: str = runner.DEFAULT_OUT_DIR,
                 force: bool = False, seed: Optional[int] = None,
                 n_seeds: Optional[int] = None, write: bool = True,
                 window: int = 2,
                 specific_fanout: bool = True,
                 ) -> Tuple[List[Dict], Dict]:
    """Execute a scenario set through the campaign engine.

    Returns (results in input order, campaign stats). ``window`` is
    the pipelining depth: how many buckets may be in flight before the
    oldest is drained. The bucket-signature index lives in the
    persistent compilation cache's directory when an entry point has
    enabled one (``enable_persistent_cache``). Stats are written to
    ``<out_dir>/campaign_stats.json`` when ``write``.
    """
    t_start = time.perf_counter()
    cache_dir = jax.config.jax_compilation_cache_dir
    index_path = os.path.join(cache_dir, _INDEX_NAME) if cache_dir else None
    entries_before = _cache_entries(cache_dir)
    kstats0 = kernel_cache_stats()

    with span("campaign.plan", n_scenarios=len(scenarios)) as sp:
        jobs = plan_campaign(scenarios, out_dir=out_dir, force=force,
                             seed=seed, n_seeds=n_seeds, write=write)
    plan_s = sp.seconds
    buckets = bucket_jobs(jobs)

    index = _load_index(index_path) if index_path else {}
    sig_hits = sig_misses = 0
    for bucket in buckets.values():
        sig = bucket.signature()
        if sig in index:
            sig_hits += 1
        else:
            sig_misses += 1
        index[sig] = {"lanes": bucket.lanes_padded_to,
                      "scenarios": [j.scenario.name
                                    for j in bucket.jobs]}
    execute_buckets(buckets.values(), out_dir, write=write,
                    specific_fanout=specific_fanout, window=window)

    # host-driven schemas (random search, Table 3) run sequentially
    # after the bucketed fleet — they were never device-hot paths
    for job in jobs:
        if job.kind == "fallback":
            job.result = runner.run_scenario(
                job.scenario, out_dir=out_dir, force=force, seed=seed,
                write=write, n_seeds=n_seeds,
                specific_fanout=specific_fanout)

    if index_path:
        with open(index_path, "w") as f:
            json.dump(index, f, indent=1, sort_keys=True)

    kstats1 = kernel_cache_stats()
    wall = time.perf_counter() - t_start
    n_executed = sum(1 for j in jobs if j.kind != "cached")
    stats = {
        "n_scenarios": len(jobs),
        "n_cached": sum(1 for j in jobs if j.kind == "cached"),
        "n_fallback": sum(1 for j in jobs if j.kind == "fallback"),
        "n_bucketed": sum(1 for j in jobs if j.kind == "bucket"),
        "n_buckets": len(buckets),
        "lanes_total": sum(b.n_lanes for b in buckets.values()),
        "lanes_padded": sum(b.lanes_padded_to - b.n_lanes
                            for b in buckets.values()),
        "wall_time_s": wall,
        "plan_s": plan_s,
        "scenarios_per_sec": (n_executed / wall if wall > 0
                              else float("inf")),
        "kernel_cache": {
            k: kstats1[k] - kstats0.get(k, 0)
            for k in ("hits", "misses", "evictions")},
        "persistent_cache": {
            "enabled": bool(cache_dir),
            "dir": cache_dir,
            "entries_before": entries_before,
            "entries_after": _cache_entries(cache_dir),
            "signature_hits": sig_hits,
            "signature_misses": sig_misses,
        },
        "buckets": [
            {"signature": b.signature(),
             "engine": b.key[0],
             "gen_tier": b.tier,
             "lanes": b.n_lanes,
             "lanes_padded_to": b.lanes_padded_to,
             "scenarios": [j.scenario.name for j in b.jobs],
             "dispatch_s": b.dispatch_s,
             "wait_s": b.wait_s,
             "finalize_s": b.finalize_s,
             "drain_s": b.drain_s}
            for b in buckets.values()],
    }
    if write:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "campaign_stats.json"),
                  "w") as f:
            json.dump(stats, f, indent=1, sort_keys=True, default=float)
    return [j.result for j in jobs], stats
