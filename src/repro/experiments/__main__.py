"""CLI for the experiment registry.

  python -m repro.experiments list [--verbose]
  python -m repro.experiments show --scenario rram_small_set
  python -m repro.experiments run --scenario rram_small_set \
      [--out DIR] [--seed N] [--seeds S] [--force] [--smoke]
      [--backend auto|pallas|ref|jnp] [--campaign]
  python -m repro.experiments run --all [--out DIR] [--sequential]
  python -m repro.experiments report [--out DIR]

``run`` executes a named scenario (cached/resumable; see runner.py) and
writes ``result.json`` + ``report.md`` under ``--out``; ``report``
aggregates every cached result into ``summary.md`` — the regenerated
paper tables. README.md maps each paper table to its scenario names.

``run --all`` routes through the campaign engine (campaign.py): shape-
bucketed mega-batched scenario execution with async pipelining. ``run``
keeps compiled kernels in jax's persistent compilation cache, at
``JAX_COMPILATION_CACHE_DIR`` when set, else at the checkout's
``.jax_compile_cache/``.
Results are byte-identical to sequential execution (modulo timing
fields); ``--sequential`` restores the old one-scenario-at-a-time
loop.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

from . import report, runner
from .scenarios import REGISTRY, get_scenario


def cmd_list(args) -> int:
    rows = [("name", "mem", "W", "algorithm", "paper ref")]
    rows += [(s.name, s.mem, str(len(s.workloads)), s.algorithm,
              s.paper_ref) for s in REGISTRY.values()]
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    for i, r in enumerate(rows):
        print("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip())
        if i == 0:
            print("  ".join("-" * w for w in widths))
    if args.verbose:
        print()
        for s in REGISTRY.values():
            print(f"{s.name}: {s.description}")
    return 0


def cmd_show(args) -> int:
    s = get_scenario(args.scenario)
    d = dataclasses.asdict(s)
    d["workloads"] = list(d["workloads"])
    print(json.dumps(d, indent=1))
    return 0


def _prepare(args, name):
    sc = get_scenario(name)
    if args.smoke:
        # scenario-specific smoke budget: the Table 3 study keeps
        # its >= 5 seeds (hit rates) even at smoke scale
        sc = dataclasses.replace(sc, budget=sc.smoke_budget)
    if args.backend:
        sc = dataclasses.replace(sc, backend=args.backend)
    return sc


def _print_campaign_stats(stats, out) -> None:
    kc, pc = stats["kernel_cache"], stats["persistent_cache"]
    line = (f"campaign: {stats['n_bucketed']} scenarios in "
            f"{stats['n_buckets']} buckets "
            f"({stats['lanes_total']} lanes, "
            f"{stats['lanes_padded']} pad), "
            f"{stats['n_cached']} cached, "
            f"{stats['n_fallback']} sequential; "
            f"{stats['scenarios_per_sec']:.2f} scenarios/s; "
            f"kernel cache {kc['hits']}h/{kc['misses']}m")
    if pc["enabled"]:
        line += (f"; compile cache {pc['signature_hits']}h/"
                 f"{pc['signature_misses']}m sigs, "
                 f"{pc['entries_after'] - pc['entries_before']} new "
                 f"entries")
    print(line)
    print(f"  -> {out}/campaign_stats.json")


def cmd_run(args) -> int:
    names = list(REGISTRY) if args.all else [args.scenario]
    if not args.all and args.scenario is None:
        print("run: pass --scenario NAME or --all", file=sys.stderr)
        return 2
    scenarios = [_prepare(args, n) for n in names]
    from . import campaign
    campaign.enable_persistent_cache()
    use_campaign = ((args.all or args.campaign)
                    and not args.sequential)
    if use_campaign:
        results, stats = campaign.run_campaign(
            scenarios, out_dir=args.out, force=args.force,
            seed=args.seed, n_seeds=args.seeds)
        for name, res in zip(names, results):
            _print_result(name, res, args.out)
        _print_campaign_stats(stats, args.out)
        return 0
    for name, sc in zip(names, scenarios):
        res = runner.run_scenario(
            sc, out_dir=args.out, force=args.force,
            seed=args.seed, n_seeds=args.seeds)
        _print_result(name, res, args.out)
    return 0


def _print_result(name, res, out) -> None:
    tag = "cached" if res.get("cached") else \
        f"{res['wall_time_s']:.1f}s"
    if res.get("algorithm") == "alg_compare":
        hits = ", ".join(f"{n} {a['hit_rate']}"
                         for n, a in res["algorithms"].items())
        print(f"[{tag}] {name}: best {res['objective']} score "
              f"{res['best_score']:.4g} by "
              f"{res['best_algorithm']}; hits: {hits}")
        print(f"  -> {out}/{name}/result.json (+ report.md)")
        return
    gap = res.get("gap", {}).get("mean_pct")
    gap_s = f", mean gap {gap:.1f}%" if gap is not None else ""
    seeds = res.get("seeds")
    seed_s = ""
    if seeds and seeds.get("count", 1) > 1:
        bs = seeds["best_score"]
        seed_s = (f" [{seeds['count']} seeds: "
                  f"{bs['mean']:.4g} ± {bs['std']:.3g}]")
    front_s = ""
    pareto = res.get("pareto")
    if pareto and pareto.get("searched"):
        front_s = f", searched front: {len(pareto['front'])} designs"
        if pareto.get("hypervolume") is not None:
            front_s += f" (HV {pareto['hypervolume']:.4g})"
    print(f"[{tag}] {name}: best {res['objective']} score "
          f"{res['best_score']:.4g}, area "
          f"{res['generalized']['area_mm2']:.1f} mm²"
          f"{gap_s}{seed_s}{front_s}")
    print(f"  -> {out}/{name}/result.json (+ report.md)")


def cmd_report(args) -> int:
    results = report.load_results(args.out)
    if not results:
        print(f"no cached results under {args.out!r}; run scenarios "
              "first (python -m repro.experiments run --scenario ...)",
              file=sys.stderr)
        return 1
    text = report.render_summary(results)
    stats = report.load_campaign_stats(args.out)
    if stats is not None:
        text += report.render_campaign_stats(stats)
    path = os.path.join(args.out, "summary.md")
    with open(path, "w") as f:
        f.write(text)
    print(text, end="")
    print(f"\n-> {args.out}/summary.md", file=sys.stderr)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.experiments",
                                 description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("list", help="list named scenarios")
    p.add_argument("--verbose", action="store_true")
    p.set_defaults(fn=cmd_list)

    p = sub.add_parser("show", help="print one scenario's full config")
    p.add_argument("--scenario", required=True)
    p.set_defaults(fn=cmd_show)

    p = sub.add_parser("run", help="run a scenario end-to-end")
    p.add_argument("--scenario", default=None)
    p.add_argument("--all", action="store_true",
                   help="run every registered scenario")
    p.add_argument("--out", default=runner.DEFAULT_OUT_DIR)
    p.add_argument("--seed", type=int, default=None,
                   help="override the scenario's seed")
    p.add_argument("--seeds", type=int, default=None,
                   help="run N independent seeds as one batched device "
                        "computation and report mean±std EDAP/gap")
    p.add_argument("--force", action="store_true",
                   help="ignore cached results")
    p.add_argument("--smoke", action="store_true",
                   help="run with the scenario's smoke budget (CI / "
                        "quick checks); the budget is part of the cache "
                        "key, so smoke results never shadow full runs")
    p.add_argument("--backend", default=None,
                   choices=["auto", "pallas", "ref", "jnp"],
                   help="accuracy-model crossbar-GEMM route (default: "
                        "the scenario's own, usually 'auto' = platform-"
                        "dependent); the resolved choice is part of the "
                        "cache key")
    p.add_argument("--campaign", action="store_true",
                   help="route single-scenario runs through the "
                        "campaign engine too (--all uses it by "
                        "default)")
    p.add_argument("--sequential", action="store_true",
                   help="disable the campaign engine and run scenarios "
                        "strictly sequentially (the pre-campaign "
                        "behaviour; results are identical modulo "
                        "timing fields)")
    p.set_defaults(fn=cmd_run)

    p = sub.add_parser("report", help="aggregate results into summary.md")
    p.add_argument("--out", default=runner.DEFAULT_OUT_DIR)
    p.set_defaults(fn=cmd_report)

    args = ap.parse_args(argv)
    try:
        return args.fn(args)
    except (KeyError, ValueError) as e:
        # unknown scenario/workload name: clean message listing the
        # valid choices (see scenarios.get_scenario and
        # core.workloads.get_workload), not a traceback
        print(f"error: {e.args[0]}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
