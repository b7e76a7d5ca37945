"""Finalize's design table (experiments/runner.design_table).

The load-bearing guarantees:

  * finalize evaluates every design it reports in ONE compiled call,
    and the numbers equal an eager evaluation of the scorer's traced
    closures (``metrics`` / ``score`` / ``accuracy``) on the same
    genomes, for every finalize path: plain EDAP, accuracy-scored,
    the post-hoc EDAP x cost front, the NSGA-II representative design
    and the joint genome;
  * ``design_calls`` reads one per finalized job, in ``run_campaign``'s
    bucket stats and in the service's ``stats()``;
  * the table compiles once per scorer content and row tier: a second
    campaign adds no compile, and front blocks whose candidate counts
    differ inside one tier share one executable.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import CodesignService, SearchRequest
from repro.core import distributed
from repro.core.objectives import (MultiObjective, Objective,
                                   per_workload_scores)
from repro.experiments import campaign, runner
from repro.experiments.scenarios import Budget, Scenario, get_scenario

TINY = Scenario(name="tiny_design", mem="rram",
                workloads=("alexnet", "resnet18"), algorithm="fourphase",
                budget=Budget(p_h=16, p_e=8, p_ga=6, generations=1))
TINY_B = dataclasses.replace(TINY, name="tiny_design_b")

# Table against eager oracle: both evaluate the same float32 closures,
# compiled whole or op by op, so each number the cost or accuracy model
# returns agrees to a few ULPs (float32 eps 1.2e-7): RTOL. A product of
# k such factors agrees to k * RTOL: EDAP e * l * a (3), edap_acc's
# score EDAP / prod_w(acc_w) (3 + W). A reported gap, 100 * (g / s - 1),
# moves by up to 2 * rtol * (100 + gap) percentage points (a mean or
# std of gaps by no more than the largest gap's bound).
RTOL = 1e-6


def _factors(table) -> dict:
    n_acc = table["accuracy"].shape[1] if "accuracy" in table else 0
    return {"energy": 1, "latency": 1, "area": 1, "cost": 1,
            "accuracy": 1, "edap": 3, "edap_agg": 3, "score": 3 + n_acc}


def _eager_table(scenario, traced, genomes):
    """The oracle: the scorer's traced closures called on host arrays,
    outside any jit, exactly as finalize evaluated designs before the
    table."""
    g = jnp.asarray(genomes)
    m = traced.metrics(g)
    objective = runner.setup_scenario(scenario).objective
    first = (objective.components[0]
             if isinstance(objective, MultiObjective) else objective)
    table = {"energy": m.energy, "latency": m.latency, "area": m.area,
             "feasible": m.feasible, "feasible_w": m.feasible_w,
             "cost": m.cost, "edap": per_workload_scores(m, "edap"),
             "edap_agg": Objective("edap", first.aggregation,
                                   first.area_constraint)(m),
             "score": traced.score(g)}
    if traced.accuracy is not None:
        table["accuracy"] = traced.accuracy(g)
    return {k: np.asarray(v) for k, v in table.items()}


def _compare(a, b, rtol, gap_atol, path=""):
    if isinstance(a, dict):
        assert a.keys() == b.keys(), path
        for k in a:
            _compare(a[k], b[k], rtol, gap_atol, f"{path}/{k}")
    elif isinstance(a, list):
        assert len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            _compare(x, y, rtol, gap_atol, f"{path}[{i}]")
    elif isinstance(a, float):
        atol = gap_atol if "pct" in path else 0.0
        np.testing.assert_allclose(b, a, rtol=rtol, atol=atol,
                                   err_msg=path)
    else:
        assert a == b, path


def _largest_gap(result) -> float:
    gap = result.get("gap", {})
    pcts = [gap.get("mean_pct", 0.0), gap.get("max_pct", 0.0)]
    pcts += list(gap.get("per_workload_pct", {}).values())
    pcts += result["seeds"].get("gap_mean_pct", {}).get("per_seed", [])
    return max([abs(p) for p in pcts], default=0.0)


def _search(scenario, seeds):
    st = runner.setup_scenario(scenario)
    traced = runner.build_scenario_scorer(scenario, st)
    if st.is_mo:
        res = runner.run_mo_search_batched(scenario, st.space, traced,
                                           seeds)
    else:
        res = runner.run_search_batched(scenario, st.space, traced,
                                        seeds, traced.score_host,
                                        traced.evaluator)
    spec = None
    if scenario.specific_baselines and len(st.workloads) > 1 \
            and not st.is_mo:
        spec = runner.run_specific_fanout(scenario, st.space, traced,
                                          seeds, len(st.workloads))
    return st, traced, res, spec


@pytest.mark.parametrize("name", [
    "rram_small_set",             # edap
    "rram_accuracy",              # edap_acc: accuracies in the table
    "rram_tech_cost",             # edap_cost: the post-hoc front block
    "rram_tech_cost_mo",          # NSGA-II: the representative design
    "joint_rram_resnet_family",   # joint genome: the architecture slice
])
def test_finalize_matches_eager_oracle(name, monkeypatch):
    sc = get_scenario(name)
    sc = dataclasses.replace(sc, budget=sc.smoke_budget)
    seeds = [sc.seed, sc.seed + 1]
    st, traced, res, spec = _search(sc, seeds)

    table_fn, seen = runner.design_table, []

    def spy(scenario, traced, genomes):
        seen.append((genomes, table_fn(scenario, traced, genomes)))
        return seen[-1][1]

    monkeypatch.setattr(runner, "design_table", spy)
    calls = runner.design_calls()
    got = runner.finalize_result(sc, st, traced, res, seeds, spec=spec,
                                 write=False)
    assert runner.design_calls() - calls == len(seen) == 1
    genomes, table = seen[0]
    oracle = _eager_table(sc, traced, genomes)
    assert table.keys() == oracle.keys()
    factors = _factors(oracle)
    for k, v in oracle.items():
        if v.dtype == bool:
            np.testing.assert_array_equal(table[k], v, err_msg=k)
        else:
            np.testing.assert_allclose(table[k], v,
                                       rtol=factors[k] * RTOL, err_msg=k)

    monkeypatch.setattr(runner, "design_table", _eager_table)
    want = runner.finalize_result(sc, st, traced, res, seeds, spec=spec,
                                  write=False)
    got.pop("wall_time_s")
    want.pop("wall_time_s")
    # every reported number is one table entry or a ratio of two
    rtol = RTOL * max(factors.values())
    _compare(want, got, rtol, 2 * rtol * (100.0 + _largest_gap(want)))
    for block in ("generalized", "seeds"):
        assert block in got
    if spec is not None:
        assert "specific" in got and "gap" in got
    if name.startswith("rram_tech_cost"):
        assert got["pareto"]["front"]
    if name == "rram_accuracy":
        assert all("accuracy" in w
                   for w in got["generalized"]["per_workload"].values())


def test_design_calls_one_per_finalized_job(tmp_path):
    _, stats = campaign.run_campaign([TINY, TINY_B],
                                     out_dir=str(tmp_path / "c"),
                                     n_seeds=2, force=True)
    b, = stats["buckets"]
    assert b["scenarios"] == [TINY.name, TINY_B.name]
    assert b["design_calls"] == 2

    svc = CodesignService(out_dir=str(tmp_path / "s"), force=True,
                          window_s=0.05)
    try:
        rids = [svc.submit(SearchRequest(TINY, seed=s, n_seeds=1))
                for s in (1, 2, 3)]
        for rid in rids:
            assert svc.result(rid, timeout=600).status == "completed"
    finally:
        svc.close()
    st = svc.stats()
    assert st.completed == 3 and st.result_cache_hits == 0
    assert st.design_calls == 3


def test_second_campaign_compiles_no_design_table(tmp_path):
    campaign.run_campaign([TINY], out_dir=str(tmp_path), n_seeds=2,
                          force=True)
    _, stats = campaign.run_campaign([TINY], out_dir=str(tmp_path),
                                     n_seeds=2, force=True)
    # the second campaign builds a new Scorer, yet reuses the design
    # table (and the bucket kernels) compiled for the first
    assert stats["kernel_cache"]["misses"] == 0
    assert stats["kernel_cache"]["hits"] >= 1
    assert stats["buckets"][0]["design_calls"] == 1


def test_front_blocks_within_a_tier_share_one_shape():
    sc = dataclasses.replace(get_scenario("sram_tech_cost"),
                             budget=TINY.budget)
    st = runner.setup_scenario(sc)
    traced = runner.build_scenario_scorer(sc, st)
    rng = np.random.default_rng(0)
    cards = st.space.cardinalities
    genomes = rng.integers(0, cards, size=(16, len(cards))).astype(
        np.int32)
    distributed.kernel_cache_clear()
    tables = [runner.design_table(sc, traced, genomes[:n])
              for n in (9, 13, 16)]
    assert distributed.kernel_cache_stats()["misses"] == 1
    fn = distributed.cached_compile(
        ("design_table", runner.scorer_key(sc), 16),
        lambda: pytest.fail("the tier-16 table was never built"))
    assert fn._cache_size() == 1
    # padding rows change no real row
    for n, t in zip((9, 13, 16), tables):
        assert t["score"].shape == (n,)
        np.testing.assert_array_equal(t["score"], tables[-1]["score"][:n])
