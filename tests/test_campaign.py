"""Campaign execution engine (experiments/campaign.py).

The load-bearing guarantees:

  * generation padding with the ``active`` mask is BIT-identical to
    the unpadded run for every engine (GA, NSGA-II, baseline
    optimizers) — deterministic sweep always, hypothesis property
    when installed;
  * the campaign engine's result JSONs match the sequential runner's
    byte-for-byte modulo timing fields;
  * the in-process kernel cache is LRU-bounded with live counters;
  * the result cache is schema-versioned (stale entries recompute).
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, distributed, genetic, nsga
from repro.core.objectives import make_objective
from repro.core.scoring import ScorerSpec, build_scorer
from repro.core.search_space import sram_space
from repro.core.workloads import get_workload_set, pack
from repro.experiments import campaign, report, runner
from repro.experiments.scenarios import Budget, Scenario

TINY_BUDGET = Budget(p_h=16, p_e=8, p_ga=6, generations=1)

TINY = Scenario(name="tiny_campaign", mem="sram",
                workloads=("alexnet", "resnet18"),
                algorithm="fourphase", budget=TINY_BUDGET)
TINY_PLAIN = dataclasses.replace(TINY, name="tiny_campaign_plain",
                                 algorithm="plain")
TINY_MO = dataclasses.replace(TINY, name="tiny_campaign_mo",
                              objective="edap:mean+cost",
                              specific_baselines=False)
TINY_B = dataclasses.replace(TINY, name="tiny_campaign_b")

TIMING_FIELDS = {"wall_time_s", "search_wall_time_s",
                 "sampling_time_s"}


def _strip(d):
    return {k: v for k, v in d.items() if k not in TIMING_FIELDS}


@pytest.fixture(scope="module")
def space_scorer():
    space = sram_space()
    wa = pack(get_workload_set(["alexnet", "resnet18"]))
    sc = build_scorer(space, ScorerSpec(make_objective("edap:mean"),
                                        workloads=wa))
    mo = build_scorer(space,
                      ScorerSpec(make_objective("edap:mean+cost"),
                                 workloads=wa))
    return space, sc, mo


# ---------------------------------------------------------------------------
# shape tiers
# ---------------------------------------------------------------------------


def test_tiers_cover_and_bound():
    for n in list(range(1, 140)) + [200, 300, 1000]:
        for fn in (campaign.gen_tier, campaign.lane_tier):
            t = fn(n)
            assert t >= n
            # padding waste is bounded (< 50% everywhere on the ladder)
            assert t < 2 * n or n == 1


def test_lane_tiers_round_to_device_count(monkeypatch):
    """On a multi-device runtime every bucket's lane count is a multiple
    of the device count, so no bucket silently runs on one device."""
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    for n in range(1, 300):
        t = campaign.lane_tier(n)
        assert t >= n and t % 4 == 0


def test_tiers_monotone():
    gens = [campaign.gen_tier(n) for n in range(1, 200)]
    lanes = [campaign.lane_tier(n) for n in range(1, 300)]
    assert gens == sorted(gens)
    assert lanes == sorted(lanes)


# ---------------------------------------------------------------------------
# padding equivalence: bit-identical, every engine
# ---------------------------------------------------------------------------


def _padded(sched, tier):
    T = sched.shape[0]
    pad = jnp.concatenate([sched, jnp.tile(sched[-1:], (tier - T, 1))])
    act = jnp.asarray([True] * T + [False] * (tier - T))
    return pad, act


@pytest.mark.parametrize("pad_to", [5, 8])
def test_ga_padding_bit_identical(space_scorer, pad_to):
    space, sc, _ = space_scorer
    cards = jnp.asarray(space.cardinalities.astype(np.float32))
    sched = genetic.phase_schedule(genetic.FOUR_PHASES, 1)  # T=4
    key = jax.random.PRNGKey(0)
    kw = dict(p_h=16, p_e=8, p_ga=6)
    ref = genetic.search_kernel(key, cards, sched, sc.score, None, **kw)
    pad, act = _padded(sched, pad_to)
    got = genetic.search_kernel(key, cards, pad, sc.score, None,
                                active=act, **kw)
    T = sched.shape[0]
    for r, g in zip(ref[:2], got[:2]):  # best genome, best score
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    hist = np.concatenate([np.asarray(got[2])[:T],
                           np.asarray(got[2])[-1:]])
    np.testing.assert_array_equal(np.asarray(ref[2]), hist)
    np.testing.assert_array_equal(np.asarray(ref[3]), np.asarray(got[3]))
    np.testing.assert_array_equal(np.asarray(ref[4]), np.asarray(got[4]))


def test_nsga_padding_bit_identical(space_scorer):
    space, _, mo = space_scorer
    cards = jnp.asarray(space.cardinalities.astype(np.float32))
    sched = genetic.phase_schedule(genetic.FOUR_PHASES, 1)
    key = jax.random.PRNGKey(3)
    kw = dict(p_h=16, p_e=8, p_ga=6)
    ref = nsga.nsga_search_kernel(key, cards, sched, mo.score_vec,
                                  None, **kw)
    pad, act = _padded(sched, 6)
    got = nsga.nsga_search_kernel(key, cards, pad, mo.score_vec, None,
                                  active=act, **kw)
    T = sched.shape[0]
    for r, g in zip(ref[:3], got[:3]):  # pop, scores, ranks
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    np.testing.assert_array_equal(np.asarray(ref[3]),
                                  np.asarray(got[3])[:T + 1])


@pytest.mark.parametrize("alg", ["es", "pso"])
def test_baseline_padding_bit_identical(space_scorer, alg):
    space, sc, _ = space_scorer
    cards = jnp.asarray(space.cardinalities.astype(np.float32))
    key = jax.random.PRNGKey(7)
    ref = baselines.baseline_kernel(key, cards, sc.score,
                                    algorithm=alg, pop=8, iters=3)
    act = jnp.asarray([True] * 3 + [False] * 3)
    got = baselines.baseline_kernel(key, cards, sc.score,
                                    algorithm=alg, pop=8, iters=6,
                                    active=act)
    np.testing.assert_array_equal(np.asarray(ref[0]),
                                  np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(ref[1]),
                                  np.asarray(got[1]))
    np.testing.assert_array_equal(np.asarray(ref[2]),
                                  np.asarray(got[2])[:4])


def test_padding_property_hypothesis(space_scorer):
    """Property form: ANY (T, tier) pair slices back bit-identical."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    space, sc, _ = space_scorer
    cards = jnp.asarray(space.cardinalities.astype(np.float32))

    @settings(max_examples=10, deadline=None)
    @given(gens=st.integers(1, 2), extra=st.integers(1, 6),
           seed=st.integers(0, 2**31 - 1))
    def prop(gens, extra, seed):
        sched = genetic.phase_schedule(genetic.FOUR_PHASES, gens)
        key = jax.random.PRNGKey(seed)
        kw = dict(p_h=12, p_e=8, p_ga=6)
        ref = genetic.search_kernel(key, cards, sched, sc.score, None,
                                    **kw)
        pad, act = _padded(sched, sched.shape[0] + extra)
        got = genetic.search_kernel(key, cards, pad, sc.score, None,
                                    active=act, **kw)
        np.testing.assert_array_equal(np.asarray(ref[0]),
                                      np.asarray(got[0]))
        np.testing.assert_array_equal(np.asarray(ref[1]),
                                      np.asarray(got[1]))

    prop()


# ---------------------------------------------------------------------------
# the in-process kernel cache: LRU bound + counters
# ---------------------------------------------------------------------------


def test_cached_compile_lru_eviction(monkeypatch):
    monkeypatch.setattr(distributed, "KERNEL_CACHE_MAXSIZE", 3)
    distributed.kernel_cache_clear()
    built = []

    def use(key):
        return distributed.cached_compile(
            key, lambda: built.append(key) or key)

    for k in ("a", "b", "c"):
        use(k)
    assert distributed.kernel_cache_stats() == {
        "hits": 0, "misses": 3, "evictions": 0, "size": 3}
    use("a")                      # refresh "a" -> "b" is now LRU
    use("d")                      # evicts "b"
    st = distributed.kernel_cache_stats()
    assert st["evictions"] == 1 and st["size"] == 3
    assert st["hits"] == 1 and st["misses"] == 4
    use("b")                      # rebuilt: it was evicted
    assert built == ["a", "b", "c", "d", "b"]
    distributed.kernel_cache_clear()
    assert distributed.kernel_cache_stats()["size"] == 0


# ---------------------------------------------------------------------------
# schema-versioned result cache
# ---------------------------------------------------------------------------


def test_result_cache_schema_version(tmp_path):
    out = str(tmp_path)
    r1 = runner.run_scenario(TINY, out_dir=out, n_seeds=1)
    assert r1["schema_version"] == runner.RESULT_SCHEMA_VERSION
    r2 = runner.run_scenario(TINY, out_dir=out, n_seeds=1)
    assert r2["cached"]
    # a stale-schema entry (e.g. pre-campaign result.json) recomputes
    path = os.path.join(out, TINY.name, "result.json")
    with open(path) as f:
        doc = json.load(f)
    doc["schema_version"] = runner.RESULT_SCHEMA_VERSION - 1
    with open(path, "w") as f:
        json.dump(doc, f)
    assert runner.load_cached_result(TINY, out, TINY.seed, 1) is None
    r3 = runner.run_scenario(TINY, out_dir=out, n_seeds=1)
    assert not r3["cached"]
    del doc["schema_version"]     # legacy entry: no field at all
    with open(path, "w") as f:
        json.dump(doc, f)
    assert runner.load_cached_result(TINY, out, TINY.seed, 1) is None


# ---------------------------------------------------------------------------
# campaign vs sequential: identical results
# ---------------------------------------------------------------------------


def test_campaign_matches_sequential(tmp_path):
    scs = [TINY, TINY_PLAIN, TINY_MO]
    d_seq, d_camp = str(tmp_path / "seq"), str(tmp_path / "camp")
    for sc in scs:
        runner.run_scenario(sc, out_dir=d_seq, n_seeds=2)
    results, stats = campaign.run_campaign(scs, out_dir=d_camp,
                                           n_seeds=2)
    for sc in scs:
        with open(os.path.join(d_seq, sc.name, "result.json")) as f:
            a = _strip(json.load(f))
        with open(os.path.join(d_camp, sc.name, "result.json")) as f:
            b = _strip(json.load(f))
        assert a == b, f"{sc.name} diverged"
        # the specific-baseline side files too, byte for byte
        for fn in sorted(os.listdir(os.path.join(d_seq, sc.name))):
            if fn.startswith("specific_"):
                with open(os.path.join(d_seq, sc.name, fn)) as f:
                    x = f.read()
                with open(os.path.join(d_camp, sc.name, fn)) as f:
                    y = f.read()
                assert x == y
    assert stats["n_bucketed"] == 3
    assert [r["scenario"] for r in results] == [s.name for s in scs]
    # re-running serves every scenario from the result cache
    _, stats2 = campaign.run_campaign(scs, out_dir=d_camp, n_seeds=2)
    assert stats2["n_cached"] == 3 and stats2["n_buckets"] == 0


def test_campaign_buckets_share_kernel(tmp_path):
    """Two scenarios identical up to the name land in ONE bucket and
    compile ONE kernel per lane flavor (the campaign's raison
    d'être): one generalized-search kernel, one specific-baseline
    kernel — NOT one pair per scenario."""
    distributed.kernel_cache_clear()
    results, stats = campaign.run_campaign(
        [TINY, TINY_B], out_dir=str(tmp_path), n_seeds=1)
    assert stats["n_buckets"] == 1
    b = stats["buckets"][0]
    assert b["scenarios"] == [TINY.name, TINY_B.name]
    # 2 scenarios x (1 generalized + 2 specific lanes) = 6 lanes
    assert b["lanes"] == 6
    assert stats["kernel_cache"]["misses"] == 2
    assert stats["kernel_cache"]["hits"] == 0
    # same seed + same scorer => the shared-bucket runs are identical
    assert (_strip(results[0]) | {"scenario": TINY_B.name}
            == _strip(results[1]))


def test_campaign_stats_schema_and_render(tmp_path):
    _, stats = campaign.run_campaign([TINY], out_dir=str(tmp_path),
                                     n_seeds=1, force=True)
    for k in ("n_scenarios", "n_buckets", "scenarios_per_sec",
              "kernel_cache", "persistent_cache", "buckets"):
        assert k in stats
    text = report.render_campaign_stats(stats)
    assert "Campaign execution" in text
    assert "scenarios/s" in text
    # stats land on disk next to the results + render into summary.md
    loaded = report.load_campaign_stats(str(tmp_path))
    assert loaded is not None
    assert loaded["n_scenarios"] == 1
    summary = report.write_summary(str(tmp_path))
    assert "## Campaign execution" in summary


def test_compile_cache_dir_resolution(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins; unset, the cache sits at one fixed
    path inside the checkout (a moving path would never hit)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert campaign.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first, second = campaign.compile_cache_dir(), campaign.compile_cache_dir()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == second == os.path.join(repo_root, ".jax_compile_cache")


def test_campaign_persistent_cache_index(tmp_path, monkeypatch):
    cache_dir = str(tmp_path / "xla_cache")
    out = str(tmp_path / "results")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    try:
        index = campaign.enable_persistent_cache()
        assert index == os.path.join(cache_dir, "campaign_index.json")
        assert jax.config.jax_compilation_cache_dir == cache_dir
        _, s1 = campaign.run_campaign([TINY], out_dir=out, n_seeds=1,
                                      force=True)
        pc1 = s1["persistent_cache"]
        assert pc1["enabled"] and pc1["signature_misses"] == 1
        assert pc1["dir"] == cache_dir
        assert os.path.exists(index)
        # the signature index recognizes the bucket next invocation
        _, s2 = campaign.run_campaign([TINY], out_dir=out, n_seeds=1,
                                      force=True)
        assert s2["persistent_cache"]["signature_hits"] == 1
    finally:
        # tmp_path is deleted after the test: don't leave jax's
        # on-disk cache pointed at it for the rest of the session
        jax.config.update("jax_compilation_cache_dir", None)
