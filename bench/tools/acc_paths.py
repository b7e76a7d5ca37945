"""Where an accuracy-scored cell's score gap comes from: the design with the
largest gap of a campaign, evaluated by the program on each of its paths
and by the reference, on the chip.

    python3 bench/tools/acc_paths.py --workload paper4_edapacc_campaign \
        --seeds 41 42
    python3 bench/tools/acc_paths.py --workload paper4_edapacc_campaign \
        --genomes '[[2, 3, 1, 3, 3, 6, 4, 0, 7]]'

For each seed it runs one campaign of the cell and picks the seed's best
design whose delivered score lies farthest from the reference (or it
takes the designs given), and prints one JSON line with, per path:

* ``score`` and ``snr_db`` (read back from the first workload's
  accuracy) of the program's scorer and accuracy model, op by op, jitted
  at the batch sizes the search uses (P_GA, P_E, P_H), vmapped over the
  campaign's lanes, and inside a ``lax.scan``;
* op by op and jitted, the relative gaps of the score's factors to the
  reference's: energy and latency on each workload, area, and the
  product of the accuracies;
* the steps by which the objective forms the product of the accuracies
  on the device (``log``, their sum, ``exp``) and the final division,
  each against the same step in float64 on the device's own inputs;
* the design's two conductance-noise fields drawn op by op and inside a
  jitted, vmapped draw: how many of their elements differ, and by how
  much;
* how many ADC codes of the reference's crossbar tiles change when the
  jitted draw takes the op-by-op draw's place, and the SNR each gives.

It imports the program: it is a diagnostic, not part of a run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def _fields(k_noise, flat, shape):
    import jax
    k = jax.random.fold_in(k_noise, flat)
    k_pos, k_neg, _ = jax.random.split(k, 3)
    return (jax.random.normal(k_pos, shape), jax.random.normal(k_neg, shape))


def _ref_codes(eps_pos, eps_neg, rows: int, n_calib: int, calib_k: int):
    """The reference's ADC codes of every crossbar tile and bit plane, and
    the SNR before output noise and multi-cell averaging, for given noise
    fields (float32 at HIGHEST, as ``reference/accuracy.py`` tiles
    statically)."""
    import jax
    import jax.numpy as jnp
    from reference import accuracy as ra
    _, x_q, w, y_ref = ra._calibration(n_calib, calib_k)
    g_pos = jnp.clip(jnp.clip(w, 0.0, 1.0)
                     + ra._sigma(jnp.clip(w, 0.0, 1.0)) * eps_pos, 0, 1)
    g_neg = jnp.clip(jnp.clip(-w, 0.0, 1.0)
                     + ra._sigma(jnp.clip(-w, 0.0, 1.0)) * eps_neg, 0, 1)
    w_eff = (g_pos - g_neg) * (1.0 - 0.04 * 0.5 * rows / 512.0)
    delta = (rows / 4.0) / 2.0 ** (ra.ADC_BITS - 1)
    codes, total = [], 0.0
    for t0 in range(0, x_q.shape[1], rows):
        xt, wt = x_q[:, t0:t0 + rows], w_eff[t0:t0 + rows]
        for b in range(8):
            col = jnp.matmul(((xt >> b) & 1).astype(jnp.float32), wt,
                             precision=jax.lax.Precision.HIGHEST)
            codes.append(jnp.round(col / delta))
            total = total + ra._adc(col, float(rows), jnp.float32) * 2.0 ** b
    y = total / 255.0
    err = jnp.mean((y - y_ref) ** 2)
    snr = 10.0 * jnp.log10(jnp.mean(y_ref ** 2) / err)
    return jnp.stack(codes), float(snr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--genomes", type=json.loads, default=[],
                    help="designs to evaluate, as a JSON list of genomes")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import harness
    from correct import Reference, rel_gap
    from reference import accuracy as ra
    cell = harness.find_cell(args.workload)
    harness._devices(int(cell.entry["chips"]), True)
    from repro.api import enable_persistent_cache
    from repro.core import nonideal
    from repro.experiments import runner
    enable_persistent_cache()
    drive = cell.driver
    system = drive.System(cell.config, os.path.join(harness.OUT, cell.name))
    ref = Reference(cell.config["reference"])
    scenario = system.scenarios[0]
    st = runner.setup_scenario(scenario)
    scorer = runner.build_scenario_scorer(scenario, st)
    base, pen = nonideal._workload_accuracy_params(st.wa)
    k_noise = jax.random.split(jax.random.PRNGKey(ra.CALIB_SEED))[1]
    shape = (ref.calib_k, ra.CALIB_N)
    b = cell.config["budget"]
    lanes = int(cell.config["n_seeds"])
    sizes = {"jit_P_GA": b["p_ga"], "jit_P_E": b["p_e"], "jit_P_H": b["p_h"]}

    def snr_of(acc0: float) -> float:
        keep = ((acc0 / (base[0] * pen[0]) - nonideal._ACC_FLOOR)
                / (1.0 - nonideal._ACC_FLOOR))
        return float(nonideal._SNR_MID_DB + nonideal._SNR_SCALE_DB
                     * np.log(keep / (1.0 - keep)))

    score_j = jax.jit(scorer.score)
    acc_j = jax.jit(scorer.accuracy)
    score_l = jax.jit(jax.vmap(scorer.score))
    acc_l = jax.jit(jax.vmap(scorer.accuracy))

    def in_scan(fn):
        def body(pop, _):
            return pop, fn(pop)
        return jax.jit(lambda pop: jax.lax.scan(body, pop, None,
                                                length=2)[1][-1])
    score_s, acc_s = in_scan(scorer.score), in_scan(scorer.accuracy)
    fields_j = jax.jit(jax.vmap(lambda f: _fields(k_noise, f, shape)))
    metrics_j = jax.jit(scorer.metrics)

    def factors(pm, acc, m) -> Dict[str, float]:
        """Relative gaps of the score's factors to the reference's."""
        return {"energy": _worst(np.asarray(pm.energy)[0], m["energy"]),
                "latency": _worst(np.asarray(pm.latency)[0], m["latency"]),
                "area": _worst(np.asarray(pm.area)[:1], [m["area"]]),
                "acc_product": _worst([np.prod(np.asarray(acc, np.float64))],
                                      [np.prod(m["accuracy"])])}

    def designs():
        for k, g in enumerate(args.genomes):
            yield {"design": k}, np.asarray(g, np.int32), None
        for seed in args.seeds:
            res = system.campaign(seed, 1).results[0]
            sd = res["seeds"]
            genomes = sd["best_genome"]["per_seed"]
            delivered = sd["best_score"]["per_seed"]
            gaps = [rel_gap(s, ref.evaluate(g)["score"])
                    for s, g in zip(delivered, genomes)]
            i = int(np.argmax(gaps))
            yield ({"seed": seed, "seed_index": i},
                   np.asarray(genomes[i], np.int32),
                   {"score": delivered[i], "score_gap": gaps[i]})

    try:
        if args.seeds:
            drive.warm(system, cell.mix, args.seeds[0])
        print(json.dumps({"setup_s": time.perf_counter() - T_START}),
              flush=True)
        for label, g, delivered in designs():
            seed = label.get("seed", 0)
            m = ref.evaluate(g)
            rng = np.random.default_rng(seed)
            others = rng.integers(0, ref.cards, size=(max(sizes.values())
                                                      * lanes, len(g)))
            paths = {}

            def put(name, score, acc):
                acc = np.asarray(acc, np.float64)
                paths[name] = {"score": float(score),
                               "score_gap": rel_gap(score, m["score"]),
                               "acc_gap": float(np.max(np.abs(
                                   acc - m["accuracy"]))),
                               "snr_db": snr_of(float(acc[0]))}
            one = jnp.asarray(g[None])
            put("op_by_op", scorer.score(one)[0], scorer.accuracy(one)[0])
            put("jit_P1", score_j(one)[0], acc_j(one)[0])
            cost = {"op_by_op": factors(scorer.metrics(one),
                                        scorer.accuracy(one)[0], m),
                    "jit_P1": factors(metrics_j(one), acc_j(one)[0], m)}
            steps = objective_steps(scorer.metrics(one),
                                    scorer.accuracy(one))
            for name, p in sizes.items():
                pop = jnp.asarray(np.concatenate([g[None], others[:p - 1]]))
                put(name, score_j(pop)[0], acc_j(pop)[0])
            pop = jnp.asarray(np.concatenate(
                [g[None], others[:lanes * b["p_ga"] - 1]]).reshape(
                    lanes, b["p_ga"], -1))
            put("jit_lanes_x_P_GA", score_l(pop)[0, 0], acc_l(pop)[0, 0])
            put("scan_P_GA", score_s(pop[0])[0], acc_s(pop[0])[0])

            flat = ra.flat_index(ref.cards, g)
            eager = _fields(k_noise, jnp.int32(flat), shape)
            flats = jnp.asarray([flat] + [ra.flat_index(ref.cards, o)
                                          for o in others[:b["p_ga"] - 1]],
                                jnp.int32)
            jitted = [f[0] for f in fields_j(flats)]
            eps = {}
            for side, e, j in zip(("pos", "neg"), eager, jitted):
                d = np.abs(np.asarray(e) - np.asarray(j))
                eps[side] = {"differ": int((d > 0).sum()),
                             "max_abs": float(d.max())}
            rows = int(ref_rows(ref, g))
            c_e, snr_e = _ref_codes(*eager, rows, ref.n_calib, ref.calib_k)
            c_j, snr_j = _ref_codes(*jitted, rows, ref.n_calib, ref.calib_k)
            print(json.dumps(dict(
                label, genome=g.tolist(), flat_index=flat, xbar_rows=rows,
                delivered=delivered,
                reference={"score": float(m["score"]),
                           "snr_db": snr_of(float(m["accuracy"][0]))},
                paths=paths, factor_gaps=cost, objective_steps=steps, **{
                "eps_jit_vs_op_by_op": eps,
                "adc_codes_changed": int((np.asarray(c_e)
                                          != np.asarray(c_j)).sum()),
                "adc_codes": int(np.asarray(c_e).size),
                "ref_tile_snr_db": {"op_by_op_eps": snr_e,
                                    "jit_eps": snr_j}})), flush=True)
    finally:
        system.close()
    return 0


def objective_steps(pm, acc) -> Dict[str, float]:
    """Relative gap of each device step of ``edap_acc``'s
    ``e * l * a / exp(sum(log(acc)))`` to float64 on the same inputs."""
    import jax.numpy as jnp
    import numpy as np
    f64 = lambda x: np.asarray(x, np.float64)
    logs = jnp.log(jnp.maximum(acc, 1e-6))
    total = jnp.sum(logs, axis=1)
    prod = jnp.exp(total)
    num = (jnp.mean(pm.energy * 1e3, axis=1) * jnp.mean(pm.latency * 1e3,
                                                        axis=1) * pm.area)
    score = num / prod
    return {"log": _worst(f64(logs)[0], np.log(f64(acc)[0])),
            "sum": _worst(f64(total), [f64(logs)[0].sum()]),
            "exp": _worst(f64(prod), np.exp(f64(total))),
            "acc_product": _worst(f64(prod), [np.prod(f64(acc)[0])]),
            "numerator": _worst(f64(num), [np.mean(f64(pm.energy)[0] * 1e3)
                                           * np.mean(f64(pm.latency)[0]
                                                     * 1e3)
                                           * f64(pm.area)[0]]),
            "divide": _worst(f64(score), f64(num) / f64(prod))}


def _worst(got, want) -> float:
    from correct import rel_gap
    return max(rel_gap(a, b) for a, b in zip(got, want))


def ref_rows(ref, genome) -> float:
    from reference import cost as rc
    return rc.decode(ref.mem, genome)["xbar_rows"]


if __name__ == "__main__":
    sys.exit(main())
