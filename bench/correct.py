"""The comparison that decides ``correct``: what the timed path delivered
against the plain reference (``reference/``).

Every answer due in the window is checked once the window has closed:

* ``missing`` — answers that never came: a campaign or request without a
  result, a seed, a per-seed entry or a workload's specific baseline
  absent. Limit 0.
* ``score_gap`` — the largest relative gap between a number the program
  delivered and the reference's value for the same design: every seed's
  best objective score (from its best genome), the best design's score,
  energy, latency and EDAP on each workload, each specific baseline's
  EDAP (and, where the campaign wrote it, its objective score), the
  generalization gap as the ratio of EDAPs, and the best score as the
  minimum over seeds.
* ``acc_gap`` — for accuracy-scored configurations, the largest absolute
  gap between a delivered per-workload accuracy and the reference's.
* ``stalled_share`` — the share of seeds whose best-so-far history never
  moved over the generations: a search step that returned its state
  unchanged delivers valid designs with the right scores, and only this
  shows it.

A check reads every number against the limit in the configuration's
``limits``; a run is correct when each reading is at most its limit.
"""
from __future__ import annotations

import copy
import json
import math
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from reference import accuracy as ref_acc
from reference import cost as ref_cost
from reference import workloads as ref_wl


class Reference:
    """The plain reference of one configuration, at one precision."""

    def __init__(self, ref: Dict, cost_dtype=np.float64, acc_dtype=None):
        import jax.numpy as jnp
        self.mem = ref["mem"]
        self.workloads = list(ref["workloads"])
        self.kind, _, agg = ref["objective"].partition(":")
        self.agg = agg or "max"
        self.n_calib = int(ref.get("n_calib", 32))
        self.calib_k = int(ref.get("calib_k", 256))
        self.tables = [ref_wl.layer_table(n) for n in self.workloads]
        self.n_layers = [len(t["layers"]) for t in self.tables]
        self.cards = ref_cost.cardinalities(self.mem)
        self.cost_dtype = cost_dtype
        self.acc_dtype = acc_dtype or jnp.float32
        self._memo: Dict = {}

    @property
    def scores_accuracy(self) -> bool:
        return self.kind == "edap_acc"

    def encode(self, design: Dict[str, float]) -> List[int]:
        """Decoded design -> index genome (nearest admissible value)."""
        return [int(np.argmin(np.abs(np.asarray(v) - design[n])))
                for n, v in ref_cost.SPACES[self.mem]]

    def evaluate(self, genome: Sequence[int]) -> Dict:
        key = tuple(int(g) for g in genome)
        if key not in self._memo:
            d = ref_cost.decode(self.mem, key)
            m = ref_cost.design_metrics(self.mem, d, self.tables,
                                        self.cost_dtype)
            m = {k: np.asarray(v, np.float64) for k, v in m.items()}
            m["feasible_w"] = m["feasible_w"].astype(bool)
            acc = None
            if self.scores_accuracy:
                acc = ref_acc.accuracies(
                    d, ref_acc.flat_index(self.cards, key), self.workloads,
                    self.n_layers, ref_wl.BASE_ACCURACY,
                    n_calib=self.n_calib, calib_k=self.calib_k,
                    dtype=self.acc_dtype)
            m["accuracy"] = acc
            m["edap"] = ref_cost.edap_per_workload(m)
            m["score"] = ref_cost.objective(self.kind, self.agg, m, acc)
            m["score_w"] = [ref_cost.objective(self.kind, self.agg, m, acc,
                                               workload=w)
                            for w in range(len(self.workloads))]
            self._memo[key] = m
        return self._memo[key]


def rel_gap(got: float, want: float) -> float:
    """|got - want| / |want|; penalty scores compare exactly."""
    got, want = float(got), float(want)
    if not (math.isfinite(got) and math.isfinite(want)):
        return math.inf
    if want >= ref_cost.PENALTY or got >= ref_cost.PENALTY:
        return 0.0 if got == want else math.inf
    return abs(got - want) / max(abs(want), 1e-300)


class Tally:
    """Running readings of one check."""

    def __init__(self):
        self.missing = 0
        self.score_gap = 0.0
        self.acc_gap = 0.0
        self.seeds = 0
        self.stalled = 0
        self.where: Dict[str, str] = {}

    def gap(self, got, want, where: str) -> None:
        g = rel_gap(got, want)
        if g > self.score_gap:
            self.score_gap, self.where["score_gap"] = g, where

    def acc(self, got, want, where: str) -> None:
        g = abs(float(got) - float(want))
        if not math.isfinite(g):
            g = math.inf
        if g > self.acc_gap:
            self.acc_gap, self.where["acc_gap"] = g, where

    def lost(self, n: int, where: str) -> None:
        if n > 0:
            self.missing += n
            self.where.setdefault("missing", where)

    def readings(self, scores_accuracy: bool) -> Dict[str, float]:
        out = {"missing": float(self.missing),
               "score_gap": self.score_gap,
               "stalled_share": (self.stalled / self.seeds
                                 if self.seeds else 1.0)}
        if scores_accuracy:
            out["acc_gap"] = self.acc_gap
        return out


def check_result(ref: Reference, res: Optional[Dict], seeds: List[int],
                 tally: Tally, where: str,
                 out_dir: Optional[str] = None) -> None:
    """Hold one scenario result (a campaign's or a request's) to the
    reference."""
    if not res:
        tally.lost(len(seeds), f"{where}: no result")
        return
    sd = res.get("seeds", {})
    per_seed = sd.get("best_score", {}).get("per_seed", [])
    genomes = sd.get("best_genome", {}).get("per_seed", [])
    hist = res.get("histories", [])
    if sd.get("list") != list(seeds):
        tally.lost(len(seeds), f"{where}: seeds {sd.get('list')} "
                               f"!= {list(seeds)}")
    tally.lost(len(seeds) - min(len(per_seed), len(genomes)),
               f"{where}: per-seed entries")
    for i, (s, g) in enumerate(zip(per_seed, genomes)):
        tally.gap(s, ref.evaluate(g)["score"], f"{where}: seed {i} score")
    if per_seed:
        tally.gap(res.get("best_score", math.nan), min(per_seed),
                  f"{where}: best_score")
    for h in hist:
        tally.seeds += 1
        if len(h) < 2 or h[0] == h[-1]:
            tally.stalled += 1

    gen = res.get("generalized", {})
    design = gen.get("design")
    if design is None:
        tally.lost(1, f"{where}: no generalized design")
        return
    m = ref.evaluate(ref.encode(design))
    tally.gap(gen.get("objective_score", math.nan), m["score"],
              f"{where}: generalized objective")
    tally.gap(gen.get("area_mm2", math.nan), m["area"], f"{where}: area")
    per = gen.get("per_workload", {})
    for i, wl in enumerate(ref.workloads):
        p = per.get(wl)
        if p is None:
            tally.lost(1, f"{where}: generalized {wl}")
            continue
        tally.gap(p["energy_mJ"], m["energy"][i] * 1e3, f"{where}: {wl} E")
        tally.gap(p["latency_ms"], m["latency"][i] * 1e3, f"{where}: {wl} L")
        tally.gap(p["edap"], m["edap"][i], f"{where}: {wl} edap")
        if ref.scores_accuracy:
            tally.acc(p.get("accuracy", math.nan), m["accuracy"][i],
                      f"{where}: {wl} accuracy")

    spec = res.get("specific")
    if spec is None:
        tally.lost(len(ref.workloads), f"{where}: no specific baselines")
        return
    gaps = res.get("gap", {}).get("per_workload_pct", {})
    for i, wl in enumerate(ref.workloads):
        s = spec.get(wl)
        if s is None:
            tally.lost(1, f"{where}: specific {wl}")
            continue
        ms = ref.evaluate(ref.encode(s["design"]))
        tally.gap(s["edap"], ms["edap"][i], f"{where}: specific {wl} edap")
        tally.gap(1.0 + gaps.get(wl, math.nan) / 100.0,
                  m["edap"][i] / ms["edap"][i], f"{where}: gap {wl}")
        path = (os.path.join(out_dir, res["scenario"], f"specific_{wl}.json")
                if out_dir else None)
        if path and os.path.exists(path):
            with open(path) as f:
                sub = json.load(f)
            tally.gap(sub["objective_score"], ms["score_w"][i],
                      f"{where}: specific {wl} objective")


def check_units(ref: Reference, units, limits: Dict[str, float]) -> Dict:
    """Hold every unit of the window to the reference. Returns
    {"correct": bool, "checks": {name: {"value", "limit"}}, "where": ...}."""
    tally = Tally()
    for u in units:
        where = f"unit {u.index}"
        if u.status != "completed":
            tally.lost(len(u.seeds), f"{where}: {u.status} {u.error}"[:300])
            continue
        if len(u.results) != 1:
            tally.lost(len(u.seeds), f"{where}: {len(u.results)} results")
            continue
        check_result(ref, u.results[0], u.seeds, tally, where,
                     u.out_dir or None)
    return verdict(tally.readings(ref.scores_accuracy), limits, tally.where)


def verdict(readings: Dict[str, float], limits: Dict[str, float],
            where: Dict[str, str]) -> Dict:
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in readings.items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return {"correct": bool(ok), "checks": checks, "where": where}


def substitute(ref: Reference, units) -> List:
    """The control: a deep copy of the window's units in which every
    number the check compares is replaced by ``ref``'s value (the
    reference at a lower precision, put in the program's place)."""
    out = copy.deepcopy(units)
    for u in out:
        u.out_dir = ""
        for res in u.results:
            sd = res["seeds"]
            scores = [ref.evaluate(g)["score"]
                      for g in sd["best_genome"]["per_seed"]]
            sd["best_score"]["per_seed"] = scores
            res["best_score"] = min(scores)
            gen = res["generalized"]
            m = ref.evaluate(ref.encode(gen["design"]))
            gen["objective_score"] = m["score"]
            gen["area_mm2"] = float(m["area"])
            for i, wl in enumerate(ref.workloads):
                p = gen["per_workload"][wl]
                p["energy_mJ"] = float(m["energy"][i]) * 1e3
                p["latency_ms"] = float(m["latency"][i]) * 1e3
                p["edap"] = float(m["edap"][i])
                if ref.scores_accuracy:
                    p["accuracy"] = float(m["accuracy"][i])
                s = res["specific"][wl]
                ms = ref.evaluate(ref.encode(s["design"]))
                s["edap"] = float(ms["edap"][i])
                res["gap"]["per_workload_pct"][wl] = 100.0 * (
                    m["edap"][i] / ms["edap"][i] - 1.0)
    return out
