"""Scenario registry, runner, report layer, and the README contract."""
import dataclasses
import json
import os
import re

import jax
import numpy as np
import pytest

from repro.core import make_objective, random_search, get_space
from repro.core import Calib, ScorerSpec, build_scorer
from repro.experiments import (Budget, Scenario, compute_gap,
                               baseline_reductions, get_scenario,
                               render_markdown,
                               render_summary, run_scenario,
                               run_specific_fanout,
                               run_specific_sequential, scenario_names)

REPO_ROOT = os.path.join(os.path.dirname(__file__), os.pardir)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_exposes_paper_grid():
    names = scenario_names()
    assert len(names) >= 6
    assert len(set(names)) == len(names)
    # the paper's grid: both memories x both set sizes x all algorithms
    for mem in ("rram", "sram"):
        for s in ("small_set", "large_set"):
            assert f"{mem}_{s}" in names
            assert f"{mem}_{s}_plain" in names
            assert f"{mem}_{s}_random" in names
        assert f"{mem}_smoke" in names
    # §IV-H accuracy-aware and §IV-I technology-cost design points
    assert "rram_accuracy" in names
    acc = get_scenario("rram_accuracy")
    assert acc.objective.startswith("edap_acc")
    assert acc.workloads == ("resnet18", "vgg16", "alexnet",
                             "mobilenetv3")
    for mem in ("rram", "sram"):
        tc = get_scenario(f"{mem}_tech_cost")
        assert tc.objective.startswith("edap_cost")
        assert tc.tech_variable
        assert "tech_idx" in tc.space().names
        # §IV-I by direct multi-objective (NSGA-II) search
        mo = get_scenario(f"{mem}_tech_cost_mo")
        assert "+" in mo.objective
        assert mo.tech_variable and not mo.specific_baselines
        from repro.core.objectives import MultiObjective
        assert isinstance(make_objective(mo.objective), MultiObjective)
    # Table 3 / §III-C1 algorithm-comparison scenarios
    t3 = get_scenario("table3_reduced_rram")
    assert t3.algorithm == "alg_compare" and t3.reduced_space
    assert t3.space().size == 240
    assert t3.budget.n_seeds >= 5
    assert t3.smoke_budget.n_seeds >= 5  # hit rates need seeds even in CI
    full = get_scenario("alg_compare_rram")
    assert full.algorithm == "alg_compare" and not full.reduced_space
    assert full.space().size > 240
    assert full.budget.n_seeds >= 5 and full.smoke_budget.n_seeds >= 5


def test_every_scenario_resolves():
    for name in scenario_names():
        sc = get_scenario(name)
        space = sc.space()
        wls = sc.resolve_workloads()
        assert space.mem_type == sc.mem
        assert len(wls) == len(sc.workloads)
        assert all(w.n_layers > 0 for w in wls)
        make_objective(sc.objective)  # parses
        from repro.experiments.scenarios import ALGORITHMS
        assert sc.algorithm in ALGORITHMS
        assert sc.budget.n_evaluations > 0


def test_get_scenario_unknown_name():
    with pytest.raises(KeyError, match="unknown scenario"):
        get_scenario("nope")


def test_make_objective_specs():
    assert make_objective("edap").aggregation == "max"
    assert make_objective("edp:mean").kind == "edp"
    with pytest.raises(ValueError):
        make_objective("bogus")
    with pytest.raises(ValueError):
        make_objective("edap:bogus")


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

TINY = Scenario(
    name="tiny_test", mem="sram", workloads=("alexnet", "resnet18"),
    algorithm="fourphase", budget=Budget(p_h=16, p_e=8, p_ga=6,
                                         generations=1),
    description="test-only tiny scenario")


def test_runner_smoke_writes_artifacts(tmp_path):
    out = str(tmp_path)
    res = run_scenario(TINY, out_dir=out)
    assert not res["cached"]
    assert res["best_score"] < 1e29  # found a feasible design
    g = res["generalized"]
    assert set(g["per_workload"]) == {"alexnet", "resnet18"}
    for m in g["per_workload"].values():
        assert m["edap"] > 0
    # gap (workload-specific vs generalized) present and finite
    assert np.isfinite(res["gap"]["mean_pct"])
    # artifacts on disk
    sdir = os.path.join(out, "tiny_test")
    with open(os.path.join(sdir, "result.json")) as f:
        on_disk = json.load(f)
    assert on_disk["best_score"] == res["best_score"]
    md = open(os.path.join(sdir, "report.md")).read()
    assert "EDAP" in md and "gap" in md
    # per-workload specific sub-results cached for resumability
    assert os.path.exists(os.path.join(sdir, "specific_alexnet.json"))
    # second run is a cache hit
    res2 = run_scenario(TINY, out_dir=out)
    assert res2["cached"]
    assert res2["best_score"] == res["best_score"]
    # a different seed misses the cache AND re-runs the specific
    # baselines (sub-caches record their seed; no silent seed mixing)
    res3 = run_scenario(TINY, out_dir=out, seed=7)
    assert not res3["cached"]
    with open(os.path.join(sdir, "specific_alexnet.json")) as f:
        assert json.load(f)["seed"] == 7


def test_runner_algorithms_dispatch(tmp_path):
    for alg in ("plain", "random"):
        sc = dataclasses.replace(TINY, name=f"tiny_{alg}", algorithm=alg,
                                 specific_baselines=False)
        res = run_scenario(sc, write=False)
        assert res["algorithm"] == alg
        assert np.isfinite(res["best_score"])
        assert "gap" not in res


def test_multiseed_aggregation():
    """Budget.n_seeds / run_scenario(n_seeds=...): seeds run as one
    batched device call; the seeds block carries consistent mean/std
    and the top-level result is the best seed."""
    res = run_scenario(TINY, write=False, n_seeds=3)
    sb = res["seeds"]
    assert res["n_seeds"] == 3
    assert sb["count"] == 3 and sb["list"] == [0, 1, 2]
    per = sb["best_score"]["per_seed"]
    assert len(per) == 3
    assert sb["best_score"]["mean"] == pytest.approx(np.mean(per))
    assert sb["best_score"]["std"] == pytest.approx(np.std(per))
    assert res["best_score"] == min(per)
    # best_seed is the seed *value* at the argmin position
    assert sb["best_seed"] == sb["list"][int(np.argmin(per))]
    # each seed's best genome rides along and decodes to the top-level
    # best design for the best seed
    genomes = sb["best_genome"]["per_seed"]
    assert len(genomes) == 3
    assert (TINY.space().decode(genomes[int(np.argmin(per))])
            == res["generalized"]["design"])
    # gap statistics present (TINY has specific baselines)
    gp = sb["gap_mean_pct"]["per_seed"]
    assert len(gp) == 3 and np.isfinite(sb["gap_mean_pct"]["mean"])
    # seed 0 of the batch reproduces the single-seed run
    r1 = run_scenario(TINY, write=False)
    assert per[0] == pytest.approx(r1["best_score"], rel=1e-5)
    # n_seeds defaulting through the budget
    multi = dataclasses.replace(
        TINY, budget=dataclasses.replace(TINY.budget, n_seeds=2))
    r2 = run_scenario(multi, write=False)
    assert r2["seeds"]["count"] == 2


@pytest.mark.parametrize("objective,tech", [
    ("edap:mean", False),
    ("edap_acc:mean", False),   # §IV-H: accuracy-aware
    ("edap_cost:mean", True),   # §IV-I: cost-aware, variable tech
])
def test_specific_fanout_matches_sequential(objective, tech):
    """The (seed x workload) specific-baseline fan-out (one batched
    device call) reproduces the sequential per-workload loop's EDAPs —
    for EVERY objective kind, including the accuracy- and cost-aware
    ones that previously fell back to the sequential path.

    SRAM on purpose: without a capacity filter both paths draw the
    identical initial pool, so the equivalence is exact; with one
    (RRAM) the init draws legitimately differ (device-masked
    oversampling vs host rejection loop — see run_specific_sequential).
    """
    sc = dataclasses.replace(TINY, objective=objective,
                             tech_variable=tech)
    space = sc.space()
    wls = sc.resolve_workloads()
    from repro.core import make_objective, pack
    obj = make_objective(sc.objective)
    traced = build_scorer(space, ScorerSpec(obj, workloads=pack(wls)))
    seeds = [0, 1]
    fan = run_specific_fanout(sc, space, traced, seeds, len(wls))
    seq = run_specific_sequential(sc, space, obj, wls, seeds)
    assert fan["edap"].shape == (2, len(wls))
    np.testing.assert_allclose(fan["edap"], seq["edap"], rtol=1e-4)
    np.testing.assert_allclose(fan["best_scores"], seq["best_scores"],
                               rtol=1e-4)


def test_accuracy_scenario_runs_device_resident(tmp_path):
    """A tiny edap_acc scenario end-to-end: batched accuracy model in
    the compiled search, accuracy in the generalized block, specific
    baselines via the fan-out, artifacts rendered."""
    sc = dataclasses.replace(TINY, name="tiny_acc",
                             objective="edap_acc:mean")
    res = run_scenario(sc, out_dir=str(tmp_path))
    assert res["best_score"] < 1e29
    per = res["generalized"]["per_workload"]
    for m in per.values():
        assert 0.2 < m["accuracy"] <= 1.0
    assert np.isfinite(res["gap"]["mean_pct"])
    md = open(os.path.join(str(tmp_path), "tiny_acc",
                           "report.md")).read()
    assert "accuracy" in md


def test_tech_cost_scenario_attaches_pareto(tmp_path):
    """A tiny edap_cost scenario: variable-technology space, pareto
    block in the result, Fig. 9 section in the report."""
    sc = dataclasses.replace(TINY, name="tiny_cost",
                             objective="edap_cost:mean",
                             tech_variable=True)
    res = run_scenario(sc, out_dir=str(tmp_path), n_seeds=2)
    p = res["pareto"]
    assert p["n_candidates"] >= len(p["front"]) >= 1
    costs = [f["cost"] for f in p["front"]]
    edaps = [f["edap"] for f in p["front"]]
    assert costs == sorted(costs)
    assert edaps == sorted(edaps, reverse=True)
    for f in p["front"]:
        assert f["tech_nm"] in (90, 65, 45, 32, 22, 14, 10, 7)
        assert "xbar_rows" in f["design"]
    md = open(os.path.join(str(tmp_path), "tiny_cost",
                           "report.md")).read()
    assert "Pareto front" in md


TINY_MO = dataclasses.replace(
    TINY, name="tiny_mo", objective="edap:mean+cost",
    tech_variable=True, specific_baselines=False)


def test_mo_scenario_runs_device_resident(tmp_path):
    """A tiny multi-objective scenario end-to-end: NSGA-II inside the
    compiled search, searched-front pareto block, hypervolume, per-seed
    front sizes, Fig. 9 direct-search section in the report."""
    res = run_scenario(TINY_MO, out_dir=str(tmp_path), n_seeds=2)
    assert res["best_score"] < 1e29
    p = res["pareto"]
    assert p["searched"] is True
    assert p["axes"] == ["edap", "cost"]
    assert p["n_candidates"] >= len(p["front"]) >= 1
    assert len(p["front_sizes_per_seed"]) == 2
    costs = [f["cost"] for f in p["front"]]
    edaps = [f["edap"] for f in p["front"]]
    assert costs == sorted(costs)
    assert edaps == sorted(edaps, reverse=True)  # a real trade-off
    assert p["hypervolume"] is None or p["hypervolume"] >= 0
    # the representative (best-EDAP) design is the front's EDAP minimum
    assert res["best_score"] == pytest.approx(min(edaps), rel=1e-5)
    # multi-objective histories: scalar first-objective trajectory for
    # the convergence section + the full (T+1, D) ideal-point one
    assert len(res["histories"]) == 2
    hmo = np.asarray(res["history_mo"])
    assert hmo.ndim == 2 and hmo.shape[1] == 2
    assert np.all(np.diff(hmo, axis=0) <= 1e-6)
    md = open(os.path.join(str(tmp_path), "tiny_mo", "report.md")).read()
    assert "direct search" in md and "Pareto front" in md
    assert "Hypervolume" in md


def test_mo_searched_front_not_dominated_by_posthoc():
    """Acceptance pin, at the budget the claim is made for: running
    `rram_tech_cost_mo` at the smoke budget (the CI invocation), its
    NSGA-II-searched EDAP × cost front contains no point strictly
    dominated by the post-hoc front of the scalarized `rram_tech_cost`
    search on the same budget and seeds, and the summary renders the
    head-to-head comparison. (The guarantee is empirical, not
    structural — a *severely* under-budgeted NSGA run can keep
    diverse-but-dominated designs — which is exactly why the nightly
    CI artifact tracks the comparison.)"""
    from repro.experiments import SMOKE_BUDGET
    r_mo = run_scenario(
        dataclasses.replace(get_scenario("rram_tech_cost_mo"),
                            budget=SMOKE_BUDGET),
        write=False, n_seeds=2)
    r_ph = run_scenario(
        dataclasses.replace(get_scenario("rram_tech_cost"),
                            budget=SMOKE_BUDGET, specific_baselines=False),
        write=False, n_seeds=2)
    searched = np.asarray([[p["edap"], p["cost"]]
                           for p in r_mo["pareto"]["front"]])
    posthoc = np.asarray([[p["edap"], p["cost"]]
                          for p in r_ph["pareto"]["front"]])
    for s in searched:
        dominated = np.any(np.all(posthoc <= s, axis=1)
                           & np.any(posthoc < s, axis=1))
        assert not dominated, (s, posthoc)
    text = render_summary([r_mo, r_ph])
    assert "Searched vs post-hoc" in text
    assert "| rram_tech_cost_mo |" in text


def test_mo_rejects_non_fourphase():
    from repro.experiments import run_mo_search_batched
    sc = dataclasses.replace(TINY_MO, algorithm="plain")
    with pytest.raises(ValueError, match="NSGA-II"):
        run_mo_search_batched(sc, sc.space(), None, [0])


def test_removed_scorer_constructors_raise():
    """The pre-build_scorer constructors survive only as ImportError
    stubs pointing at the unified API."""
    from repro.experiments import make_scorer
    with pytest.raises(ImportError, match="build_scorer"):
        make_scorer(TINY_MO.space(), None,
                    make_objective(TINY_MO.objective))


def test_calib_is_part_of_cache_key(tmp_path):
    """n_calib/calib_k are Scenario fields and cache-key components: a
    changed calibration fidelity must not be served from the stale
    cache."""
    out = str(tmp_path)
    r1 = run_scenario(TINY, out_dir=out)
    assert run_scenario(TINY, out_dir=out)["cached"]
    assert r1["calib"] == {"n_calib": 32, "calib_k": 256}
    other = dataclasses.replace(TINY, n_calib=8, calib_k=128)
    r2 = run_scenario(other, out_dir=out)
    assert not r2["cached"]
    assert r2["calib"] == {"n_calib": 8, "calib_k": 128}


def test_calib_fields_reach_accuracy_model():
    """The registry's calibration knobs actually change the accuracy
    model's calibration GEMM (different fidelity -> different scores),
    while the same knobs reproduce identical scores."""
    sc = dataclasses.replace(TINY, objective="edap_acc:mean")
    space = sc.space()
    wls = sc.resolve_workloads()
    from repro.core import pack
    obj = make_objective(sc.objective)
    g = np.zeros((4, space.n_params), np.int32)
    spec = ScorerSpec(obj, workloads=pack(wls))
    a = build_scorer(space, spec, calib=Calib(8, 128)).accuracy(g)
    b = build_scorer(space, spec, calib=Calib(8, 128)).accuracy(g)
    c = build_scorer(space, spec).accuracy(g)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_budget_is_part_of_cache_key(tmp_path):
    """A smoke-budget run must not be served from a full-budget cache
    (and vice versa) — the --smoke CLI flag relies on this."""
    out = str(tmp_path)
    r1 = run_scenario(TINY, out_dir=out)
    assert run_scenario(TINY, out_dir=out)["cached"]
    other = dataclasses.replace(
        TINY, budget=dataclasses.replace(TINY.budget, generations=2))
    r2 = run_scenario(other, out_dir=out)
    assert not r2["cached"]
    assert r2["budget"]["generations"] == 2
    assert r1["budget"]["generations"] == 1


def test_artifacts_deterministic_json(tmp_path):
    """All JSON artifacts are written with sorted keys so CI artifact
    comparisons diff cleanly."""
    out = str(tmp_path)
    run_scenario(TINY, out_dir=out)
    sdir = os.path.join(out, "tiny_test")
    for name in ("result.json", "specific_alexnet.json"):
        text = open(os.path.join(sdir, name)).read()
        loaded = json.loads(text)
        assert text == json.dumps(loaded, indent=1, sort_keys=True)


def test_random_search_deterministic():
    space = get_space("sram")
    obj = make_objective("edap:mean")
    from repro.core import make_evaluator, pack, get_workload_set
    ev = make_evaluator(space, pack(get_workload_set(("alexnet",))))
    def sf(g):
        return obj(ev(g))
    r1 = random_search(jax.random.PRNGKey(3), space, sf, n_evals=50)
    r2 = random_search(jax.random.PRNGKey(3), space, sf, n_evals=50)
    assert r1.best_score == r2.best_score
    assert np.array_equal(r1.best_genome, r2.best_genome)


# ---------------------------------------------------------------------------
# report layer (canned results, no search)
# ---------------------------------------------------------------------------

def _canned(name, alg, score, gap=True):
    per = {"wl_a": {"energy_mJ": 1.0, "latency_ms": 2.0, "edap": 20.0},
           "wl_b": {"energy_mJ": 3.0, "latency_ms": 4.0, "edap": 60.0}}
    r = {"scenario": name, "mem": "rram", "algorithm": alg,
         "objective": "edap:mean", "paper_ref": "Table 1",
         "description": "canned", "seed": 0,
         "workloads": ["wl_a", "wl_b"], "best_score": score,
         "generalized": {"design": {"xbar_rows": 256.0},
                         "objective_score": score, "area_mm2": 10.0,
                         "feasible": True, "per_workload": per},
         "history": [score], "search_wall_time_s": 1.0,
         "sampling_time_s": 0.1, "wall_time_s": 1.1, "cached": False}
    if gap:
        r["specific"] = {"wl_a": {"design": {}, "edap": 16.0},
                         "wl_b": {"design": {}, "edap": 50.0}}
        r["gap"] = compute_gap(r)
    return r


def test_compute_gap_values():
    r = _canned("x", "fourphase", 40.0)
    g = r["gap"]["per_workload_pct"]
    assert g["wl_a"] == pytest.approx(25.0)   # 20/16 - 1
    assert g["wl_b"] == pytest.approx(20.0)   # 60/50 - 1
    assert r["gap"]["mean_pct"] == pytest.approx(22.5)
    assert r["gap"]["max_pct"] == pytest.approx(25.0)


def test_render_markdown_canned():
    md = render_markdown(_canned("x", "fourphase", 40.0))
    assert "| wl_a | 1 | 2 | 20 | 16 | 25 |" in md
    assert "mean 22.5%" in md


def test_summary_pairs_baselines():
    results = [_canned("rram_small_set", "fourphase", 25.0),
               _canned("rram_small_set_plain", "plain", 50.0, gap=False),
               _canned("rram_small_set_random", "random", 100.0,
                       gap=False)]
    red = baseline_reductions(results)
    assert red["rram_small_set"]["plain"] == pytest.approx(50.0)
    assert red["rram_small_set"]["random"] == pytest.approx(75.0)
    md = render_summary(results)
    assert md.count("| rram_small_set") == 3
    assert "| 50 |" in md and "| 75 |" in md


def _canned_table3(name="table3_reduced_rram"):
    algs = {}
    for i, a in enumerate(("GA", "PSO", "ES", "SRES", "CMA-ES",
                           "G3PCX")):
        algs[a] = {"hits": 5 - i % 3, "n_seeds": 5, "n_feasible": 5,
                   "hit_rate": f"{5 - i % 3}/5",
                   "best_scores": [100.0 + i] * 5,
                   "mean_best": 100.0 + i, "std_best": 0.0,
                   "best_score": 100.0 + i,
                   "best_design": {"xbar_rows": 256.0},
                   "mean_wall_time_s": 0.1, "evaluations": 1000}
    return {"scenario": name, "mem": "rram", "algorithm": "alg_compare",
            "objective": "edap:mean", "paper_ref": "Table 3 / §III-C1",
            "description": "canned", "seed": 0, "n_seeds": 5,
            "workloads": ["wl"], "space_size": 240,
            "seeds": {"count": 5, "list": [0, 1, 2, 3, 4]},
            "ground_truth": {"exhaustive": True, "global_min": 100.0,
                             "n_enumerated": 240,
                             "global_design": {},
                             "criterion": "x"},
            "algorithms": algs, "best_algorithm": "GA",
            "best_score": 100.0, "wall_time_s": 1.0, "cached": False}


def test_summary_renders_table3_section():
    """alg_compare results render in the dedicated Table 3 section (in
    canonical row order) and are skipped by the main scenario table."""
    from repro.experiments import render_markdown
    results = [_canned("rram_small_set", "fourphase", 25.0),
               _canned_table3()]
    md = render_summary(results)
    assert "Algorithm comparison (Table 3" in md
    assert "table3_reduced_rram" in md
    # canonical row order survives the sorted-keys JSON round-trip
    order = [md.index(f"| {a} |") for a in
             ("GA", "PSO", "ES", "SRES", "CMA-ES", "G3PCX")]
    assert order == sorted(order)
    # not a row of the main scenario table
    main = md.split("## Algorithm comparison")[0]
    assert "table3_reduced_rram" not in main
    # per-scenario report renders the Table 3 layout
    md_one = render_markdown(_canned_table3())
    assert "global-min hits" in md_one and "| G3PCX |" in md_one


# ---------------------------------------------------------------------------
# README contract: reproduce-table commands == registry names
# ---------------------------------------------------------------------------

def test_cli_unknown_name_exits_2_with_listing(capsys):
    """Unknown scenario/workload names exit 2 with the valid choices
    listed on stderr — no traceback (satellite of the joint-search PR:
    KeyError/ValueError both route through the clean error path)."""
    from repro.experiments.__main__ import main
    for argv in (["show", "--scenario", "nope"],
                 ["run", "--scenario", "nope"]):
        assert main(argv) == 2
        err = capsys.readouterr().err
        assert "unknown scenario 'nope'" in err
        assert "rram_small_set" in err
        assert "Traceback" not in err


def test_readme_commands_match_registry():
    readme = open(os.path.join(REPO_ROOT, "README.md")).read()
    commanded = set(re.findall(r"--scenario\s+(\S+)", readme))
    registered = set(scenario_names())
    # every command in the README names a real scenario
    assert commanded <= registered, commanded - registered
    # every registered scenario is mentioned in the README
    mentioned = {n for n in registered if re.search(rf"\b{n}\b", readme)}
    assert mentioned == registered, registered - mentioned
    # and the headline table scenarios are runnable commands
    for must in ("rram_small_set", "rram_large_set", "sram_small_set",
                 "sram_large_set", "rram_smoke"):
        assert must in commanded
