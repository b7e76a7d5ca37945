"""Layer 2: jaxpr purity + recompilation + bloat audit.

Lowers every registered scenario's scorer and search kernel with
``jax.make_jaxpr`` at smoke-budget shapes (tracing only — nothing is
compiled or executed) and checks three properties:

J001  purity: the lowered jaxpr contains ZERO callback primitives
      (``pure_callback`` / ``io_callback`` / ``debug_callback`` / any
      ``*callback*``) — the whole search is device-resident, nothing
      punches out to host mid-computation.
J002  recompilation: kernels whose content signature is identical
      (runner.scorer_key + engine + population/schedule shape) must
      lower to ONE jaxpr — a hash split inside a signature group means
      the compile cache misses for work that should share a kernel.
J003  bloat: per-kernel total primitive counts are diffed against the
      committed ``analysis/baseline.json``; growth beyond 25% + 16
      primitives fails the build (an accidental unroll / lost fusion
      shows up here before it shows up as compile time). Kernels not
      in the baseline yet report as warnings until
      ``--update-baseline`` commits them.

A lowering crash is itself a finding (J000): the audit covers every
registered scenario by construction, never by luck.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Callable, Dict, List, Optional, Tuple

from .findings import Finding

BASELINE_FILE = os.path.join("analysis", "baseline.json")

# J003: allowed growth of a kernel's total primitive count over the
# committed baseline — generous enough for honest feature work, tight
# enough that an accidental scan unroll (which multiplies counts by
# the generation count) cannot slip through.
BLOAT_RATIO = 1.25
BLOAT_SLACK = 16

_SCENARIOS_PATH = "src/repro/experiments/scenarios.py"


def count_primitives(jaxpr) -> Dict[str, int]:
    """Primitive-name -> count over a (Closed)Jaxpr and every sub-jaxpr
    reachable through equation params (scan/cond/pjit bodies...)."""
    counts: Dict[str, int] = {}

    def walk_value(val) -> None:
        if hasattr(val, "jaxpr"):          # ClosedJaxpr
            visit(val.jaxpr)
        elif hasattr(val, "eqns"):         # Jaxpr
            visit(val)
        elif isinstance(val, (list, tuple)):
            for v in val:
                walk_value(v)

    def visit(j) -> None:
        for eqn in j.eqns:
            name = eqn.primitive.name
            counts[name] = counts.get(name, 0) + 1
            for v in eqn.params.values():
                walk_value(v)

    visit(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return counts


def callback_primitives(counts: Dict[str, int]) -> Dict[str, int]:
    return {name: n for name, n in counts.items()
            if "callback" in name or name in ("infeed", "outfeed")}


def jaxpr_hash(jaxpr) -> str:
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One lowered computation of one scenario."""
    kernel_id: str      # "<scenario>::<label>"
    scenario: str
    label: str          # "scorer" | "kernel" | "kernel:<alg>"
    group: str          # J002 signature-group key
    hash: str
    n_primitives: int
    primitives: Dict[str, int]

    def asdict(self) -> dict:
        return dataclasses.asdict(self)


def _smoke(scenario):
    return dataclasses.replace(scenario, budget=scenario.smoke_budget)


def _group_key(scenario, engine: str, shape: Tuple) -> str:
    """J002 signature: scenarios sharing this string MUST lower to one
    jaxpr (it is the campaign engine's bucketing contract)."""
    from ..experiments.runner import scorer_key
    return repr((scorer_key(scenario), engine, shape))


def lower_scenario(scenario) -> List[KernelEntry]:
    """Lower one (smoke-budget) scenario's scorer + search kernel(s)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..core import FOUR_PHASES, PLAIN_PHASE, phase_schedule
    from ..core.baselines import baseline_kernel
    from ..core.genetic import search_kernel
    from ..core.nsga import nsga_search_kernel
    from ..experiments import runner

    sc = _smoke(scenario)
    st = runner.setup_scenario(sc)
    b = sc.budget
    space = st.space
    genomes = jnp.zeros((b.p_ga, space.n_params), jnp.int32)
    cards = jnp.asarray(space.cardinalities.astype(np.float32))
    key = jax.random.PRNGKey(0)

    entries: List[KernelEntry] = []

    def add(label: str, fn: Callable, engine: str, shape: Tuple,
            *example_args) -> None:
        closed = jax.make_jaxpr(fn)(*example_args)
        counts = count_primitives(closed)
        entries.append(KernelEntry(
            kernel_id=f"{scenario.name}::{label}",
            scenario=scenario.name, label=label,
            group=_group_key(sc, engine, shape),
            hash=jaxpr_hash(closed),
            n_primitives=sum(counts.values()), primitives=counts))

    if sc.algorithm == "alg_compare":
        if sc.reduced_space:
            score = runner.make_landscape_scorer(space, st.wa,
                                                 st.objective)
            penalty = None
        else:
            traced = runner.build_scenario_scorer(sc, st)
            score = traced.score
            penalty = runner.make_infeasibility_penalty(traced,
                                                        st.objective)
        pop, iters = b.p_ga, b.total_generations
        add("scorer", score, "score", (b.p_ga,), genomes)
        sched = jnp.asarray(phase_schedule((PLAIN_PHASE,), iters))
        add("kernel:ga",
            lambda k: search_kernel(k, cards, sched, score, None,
                                    p_h=pop, p_e=pop, p_ga=pop,
                                    hamming_sampling=False),
            "ga", (pop, pop, pop, iters), key)
        for _, alg in runner.TABLE3_ALGORITHMS:
            if alg == "ga":
                continue
            pen = penalty if alg == "sres" else None
            add(f"kernel:{alg}",
                lambda k, a=alg, p=pen: baseline_kernel(
                    k, cards, score, algorithm=a, pop=pop, iters=iters,
                    penalty_fn=p),
                alg, (pop, iters), key)
        return entries

    traced = runner.build_scenario_scorer(sc, st)
    feas = traced.feasible if sc.mem == "rram" else None

    if st.is_mo:
        add("scorer", traced.score_vec, "score_vec", (b.p_ga,), genomes)
        sched = jnp.asarray(phase_schedule(FOUR_PHASES, b.generations))
        add("kernel",
            lambda k: nsga_search_kernel(k, cards, sched,
                                         traced.score_vec, feas,
                                         p_h=b.p_h, p_e=b.p_e,
                                         p_ga=b.p_ga),
            "nsga", (b.p_h, b.p_e, b.p_ga, sched.shape[0]), key)
        return entries

    add("scorer", traced.score, "score", (b.p_ga,), genomes)
    if sc.algorithm == "fourphase":
        sched = jnp.asarray(phase_schedule(FOUR_PHASES, b.generations))
        add("kernel",
            lambda k: search_kernel(k, cards, sched, traced.score, feas,
                                    p_h=b.p_h, p_e=b.p_e, p_ga=b.p_ga),
            "ga", (b.p_h, b.p_e, b.p_ga, sched.shape[0]), key)
    elif sc.algorithm == "plain":
        p_h = max(4 * b.p_ga, 200)
        sched = jnp.asarray(phase_schedule((PLAIN_PHASE,),
                                           b.total_generations))
        add("kernel",
            lambda k: search_kernel(k, cards, sched, traced.score, feas,
                                    p_h=p_h, p_e=b.p_ga, p_ga=b.p_ga,
                                    hamming_sampling=False),
            "ga", (p_h, b.p_ga, b.p_ga, sched.shape[0]), key)
    # "random" is a host-driven engine: the scorer lowering above is
    # the whole device surface.
    return entries


def load_baseline(repo_root: str) -> Optional[Dict[str, int]]:
    path = os.path.join(repo_root, BASELINE_FILE)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f).get("kernels", {})


def write_baseline(repo_root: str, entries: List[KernelEntry]) -> str:
    path = os.path.join(repo_root, BASELINE_FILE)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    payload = {
        "comment": "per-kernel total primitive counts at smoke-budget "
                   "shapes; refreshed via "
                   "`python -m repro.analysis --jaxpr --update-baseline`",
        "kernels": {e.kernel_id: e.n_primitives for e in entries},
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def audit_entries(entries: List[KernelEntry],
                  baseline: Optional[Dict[str, int]]) -> List[Finding]:
    """J001-J003 over the lowered kernels."""
    findings: List[Finding] = []
    for e in entries:
        bad = callback_primitives(e.primitives)
        if bad:
            shown = ", ".join(f"{k} x{v}" for k, v in sorted(bad.items()))
            findings.append(Finding(
                rule="J001", path=_SCENARIOS_PATH, line=1,
                symbol=e.kernel_id,
                message=f"lowered jaxpr contains host-callback "
                        f"primitives ({shown}) — the search must stay "
                        "device-resident"))

    groups: Dict[str, Dict[str, List[str]]] = {}
    for e in entries:
        if e.label == "scorer":
            continue  # scorers are audited via their enclosing kernel
        groups.setdefault(e.group, {}).setdefault(e.hash, []) \
            .append(e.kernel_id)
    for group, by_hash in groups.items():
        if len(by_hash) > 1:
            shown = "; ".join(
                f"{h}: {', '.join(ids)}" for h, ids in
                sorted(by_hash.items()))
            findings.append(Finding(
                rule="J002", path=_SCENARIOS_PATH, line=1,
                symbol="recompilation",
                message=f"kernels with one content signature lower to "
                        f"{len(by_hash)} distinct jaxprs ({shown}) — "
                        "the compile cache cannot share them"))

    if baseline is not None:
        for e in entries:
            old = baseline.get(e.kernel_id)
            if old is None:
                findings.append(Finding(
                    rule="J003", path=BASELINE_FILE.replace(os.sep, "/"),
                    line=1, symbol=e.kernel_id,
                    message=f"kernel not in baseline.json (now "
                            f"{e.n_primitives} primitives) — run "
                            "--jaxpr --update-baseline and commit",
                    severity="warning"))
                continue
            limit = int(old * BLOAT_RATIO + BLOAT_SLACK)
            if e.n_primitives > limit:
                findings.append(Finding(
                    rule="J003", path=BASELINE_FILE.replace(os.sep, "/"),
                    line=1, symbol=e.kernel_id,
                    message=f"jaxpr bloat: {old} -> {e.n_primitives} "
                            f"primitives (limit {limit}) — an unroll or "
                            "lost fusion grew the lowered kernel; fix "
                            "it or deliberately refresh the baseline"))
        current = {e.kernel_id for e in entries}
        for kid in sorted(set(baseline) - current):
            findings.append(Finding(
                rule="J003", path=BASELINE_FILE.replace(os.sep, "/"),
                line=1, symbol=kid,
                message="baseline entry matches no current kernel — "
                        "refresh the baseline", severity="warning"))
    return findings


def run_jaxpr_audit(repo_root: str, update_baseline: bool = False,
                    ) -> Tuple[List[Finding], Dict]:
    """Lower every registered scenario; returns (findings, report)."""
    from ..experiments.scenarios import get_scenario, scenario_names

    entries: List[KernelEntry] = []
    findings: List[Finding] = []
    for name in scenario_names():
        try:
            entries += lower_scenario(get_scenario(name))
        except Exception as exc:  # any lowering crash -> J000 finding
            findings.append(Finding(
                rule="J000", path=_SCENARIOS_PATH, line=1, symbol=name,
                message=f"lowering failed: {type(exc).__name__}: {exc}"))

    if update_baseline:
        write_baseline(repo_root, entries)
        baseline = {e.kernel_id: e.n_primitives for e in entries}
    else:
        baseline = load_baseline(repo_root)
    findings += audit_entries(entries, baseline)

    report = {
        "schema": 1,
        "n_scenarios": len(set(e.scenario for e in entries)),
        "n_kernels": len(entries),
        "kernels": {e.kernel_id: e.asdict() for e in entries},
        "findings": [f.asdict() for f in findings],
    }
    return findings, report
