"""Chip smoke: the campaign engine and the co-design service on a TPU.

    python chip_smoke.py              # one chip: campaign + service phases
    python chip_smoke.py --chips 4    # four chips: the sharded search axis only

One process drives the chip; nothing here starts another. Every phase
raises on failure, so any failure ends in a traceback and a nonzero exit
code. Only a run in which every phase passed prints, as the last line of
standard output, one JSON object naming the device as JAX reports it.

Phases on one chip (paper scenarios at their registered budget, 4 seeds):

* campaign — ``campaign.run_campaign`` (the path behind
  ``python -m repro.experiments run --all``) on ``rram_small_set``,
  ``sram_small_set`` and ``rram_accuracy``. Every result must be finite and
  feasible; ``rram_accuracy`` must record the ``pallas`` backend and its
  compiled accuracy route must hold a Mosaic kernel (``tpu_custom_call``)
  whose outputs match its CPU oracle on identical inputs; every seed's
  best score on the chip must equal a re-score of that seed's best genome
  on the host CPU (the ``jnp`` route) within ``RTOL`` (``RTOL_ACC`` for
  accuracy-scored objectives).
* service — four concurrent ``rram_small_set`` requests with distinct
  seeds through ``CodesignService``: all complete, no bucket degrades to
  the sequential runner, and a resubmission is served from the result
  cache with payloads equal to the first round.

With ``--chips 4`` the script runs only the path that exists across
chips: one campaign bucket (``rram_small_set``, 4 seeds: 4 generalized +
16 specific-baseline lanes) dispatched sharded over the four chips and
again on one chip. The sharded outputs must span four devices and the
two runs must agree.

Results go to ``chip_smoke_out/`` in the checkout (gitignored); the
compile cache to ``JAX_COMPILATION_CACHE_DIR`` or, unset, the checkout's
``.jax_compile_cache/``. Times printed are of this one run on this chip,
compilation included, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chip_smoke_out")

# Relative tolerance between a best score found on the chip and the same
# genome re-scored on the host CPU, for cost-model objectives: with every
# f32 matmul pinned at HIGHEST the two agree to ~4e-7 on a v5e, while a
# matmul left at the TPU's default bf16 passes would miss by ~1e-3.
RTOL = 1e-4
# The same for accuracy-scored objectives. Their conductance noise comes
# from jax.random.normal, whose transcendental functions differ between
# the TPU and the CPU (draws up to 3e-5 apart on a v5e), and the ADC's
# rounding step amplifies that: on the CPU, moving the draws by 3e-5
# moves a 4-workload edap_acc score by up to ~1e-3. The fused kernel's own
# precision is held to its CPU oracle element by element instead
# (kernel_check, MAX_KERNEL_MISMATCH).
RTOL_ACC = 2e-3
# Fraction of the fused kernel's ADC outputs allowed to differ from the
# CPU oracle on identical inputs (one rounding-boundary case in ~1e4 at
# f32; bf16 passes flip a large fraction).
MAX_KERNEL_MISMATCH = 1e-3

CAMPAIGN_SCENARIOS = ("rram_small_set", "sram_small_set", "rram_accuracy")
N_SEEDS = 4
N_REQUESTS = 4


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _import_repro():
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SmokeFailure(f"no src/repro next to {__file__}: run the script "
                           "from a checkout of the repository")
    sys.path.insert(0, src)


def check_device(chips: int):
    """The default device must be a TPU, and ``chips`` of them visible."""
    import jax
    devices = jax.devices()
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    _check(d.platform == "tpu",
           f"needs a TPU, JAX found platform {d.platform!r}")
    _check(len(devices) == chips,
           f"asked for {chips} chip(s), JAX sees {len(devices)}")
    return d


def _finite_feasible(name: str, res: dict) -> None:
    from repro.core.objectives import INFEASIBLE_PENALTY
    per_seed = res["seeds"]["best_score"]["per_seed"]
    _check(len(per_seed) == N_SEEDS, f"{name}: {len(per_seed)} seeds")
    for s in per_seed + [res["best_score"]]:
        _check(math.isfinite(s) and s < INFEASIBLE_PENALTY,
               f"{name}: best score {s} is not finite and feasible")
    gen = res["generalized"]
    _check(gen["feasible"], f"{name}: best design is infeasible")
    for wl, m in gen["per_workload"].items():
        for k, v in m.items():
            _check(math.isfinite(v), f"{name}/{wl}: {k} = {v}")


def _cpu_rescore(sc, genomes) -> "np.ndarray":
    """Score ``genomes`` with the scenario's scorer built and run on the
    host CPU device through the plain-jnp accuracy route."""
    import jax
    import numpy as np
    from repro.experiments import runner
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        st = runner.setup_scenario(sc)
        ref = runner.build_scenario_scorer(
            dataclasses.replace(sc, backend="jnp"), st)
        g = jax.device_put(np.asarray(genomes, np.int32), cpu)
        return np.asarray(jax.jit(ref.score)(g))


def _accuracy_route_text(sc) -> str:
    """Compiled text of the scenario's accuracy model on the chip."""
    import jax
    import jax.numpy as jnp
    from repro.experiments import runner
    st = runner.setup_scenario(sc)
    scorer = runner.build_scenario_scorer(sc, st)
    g = jax.ShapeDtypeStruct((sc.budget.p_ga, st.space.n_params), jnp.int32)
    return jax.jit(scorer.accuracy).lower(g).compile().as_text()


def kernel_check() -> None:
    """The fused kernel on the chip against its pure-jnp oracle on the
    host CPU, on identical inputs at the rram_accuracy shapes."""
    import jax
    import numpy as np
    from repro.kernels.imc_fused import imc_fused_gemm
    from repro.kernels.ref import imc_fused_ref
    P, B, K, N, sub = 24, 32, 256, 32, 64
    row_values = np.array([64, 128, 256, 512], np.float32)
    rng = np.random.default_rng(0)
    x_q = rng.integers(0, 256, (B, K), dtype=np.int32)
    w = np.clip(rng.normal(0.0, 0.3, (K, N)), -1, 1).astype(np.float32)
    eps_pos = rng.standard_normal((P, K, N), dtype=np.float32)
    eps_neg = rng.standard_normal((P, K, N), dtype=np.float32)
    rows_idx = (np.arange(P) % len(row_values)).astype(np.int32)
    chip = np.asarray(imc_fused_gemm(x_q, w, eps_pos, eps_neg, rows_idx,
                                     row_values, sub=sub))
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        host = np.asarray(jax.jit(jax.vmap(
            lambda ep, en, r: imc_fused_ref(x_q, w, ep, en, r, sub=sub)))(
                eps_pos, eps_neg, row_values[rows_idx]))
    mismatch = float(np.mean(chip != host))
    print(f"  fused kernel vs CPU oracle: {mismatch:.2e} of "
          f"{chip.size} ADC outputs differ (limit "
          f"{MAX_KERNEL_MISMATCH:g})", flush=True)
    _check(mismatch <= MAX_KERNEL_MISMATCH,
           f"fused kernel: {mismatch:.2e} of its outputs differ from the "
           f"CPU oracle (limit {MAX_KERNEL_MISMATCH:g})")


def campaign_phase() -> None:
    import numpy as np
    from repro.experiments import campaign, get_scenario
    scenarios = [get_scenario(n) for n in CAMPAIGN_SCENARIOS]
    out = os.path.join(OUT_DIR, "campaign")
    t0 = time.perf_counter()
    results, stats = campaign.run_campaign(scenarios, out_dir=out,
                                           force=True, n_seeds=N_SEEDS)
    wall = time.perf_counter() - t0
    print(f"campaign: {len(scenarios)} scenarios x {N_SEEDS} seeds in "
          f"{stats['n_buckets']} buckets, {wall:.1f} s wall "
          "(one chip run, compilation included; not a benchmark metric)",
          flush=True)
    for sc, res in zip(scenarios, results):
        _finite_feasible(sc.name, res)
        chip = np.asarray(res["seeds"]["best_score"]["per_seed"])
        genomes = res["seeds"]["best_genome"]["per_seed"]
        host = _cpu_rescore(sc, genomes)
        rel = np.abs(chip / host - 1.0)
        rtol = RTOL_ACC if "acc" in sc.objective else RTOL
        print(f"  {sc.name}: backend={res['backend']} chip best "
              f"{chip.tolist()} cpu re-score {host.tolist()} max rel "
              f"diff {float(rel.max()):.3g} (rtol {rtol:g})", flush=True)
        _check(bool(np.all(rel <= rtol)),
               f"{sc.name}: chip best scores {chip.tolist()} differ from "
               f"the CPU re-score {host.tolist()} beyond rtol {rtol:g}")

    acc = scenarios[CAMPAIGN_SCENARIOS.index("rram_accuracy")]
    with open(os.path.join(out, acc.name, "result.json")) as f:
        backend = json.load(f)["backend"]
    _check(backend == "pallas",
           f"{acc.name}: result.json records backend {backend!r}, "
           "not 'pallas'")
    _check("tpu_custom_call" in _accuracy_route_text(acc),
           f"{acc.name}: the compiled accuracy route holds no Mosaic "
           "kernel (tpu_custom_call)")
    print(f"  {acc.name}: fused Pallas kernel compiled for the chip "
          "(tpu_custom_call present)", flush=True)
    kernel_check()


def service_phase() -> None:
    import numpy as np
    from repro.api import CodesignService, SearchRequest, get_scenario
    from repro.launch.codesign_serve import _strip, render_stats
    base = get_scenario("rram_small_set")
    clones = [dataclasses.replace(base, name=f"{base.name}@r{i}",
                                  seed=base.seed + i)
              for i in range(N_REQUESTS)]
    out = os.path.join(OUT_DIR, "service")
    with CodesignService(out_dir=out, window_s=0.25) as svc:
        rids = [None] * N_REQUESTS

        def submit(i: int) -> None:
            rids[i] = svc.submit(SearchRequest(clones[i]))

        t0 = time.perf_counter()
        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(N_REQUESTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        _check(all(r is not None for r in rids),
               "a submitting thread did not return a request id")
        first = [svc.result(rid, timeout=900) for rid in rids]
        wall = time.perf_counter() - t0
        for r in first:
            _check(r.status == "completed",
                   f"{r.request_id} ({r.scenario}): {r.status}: {r.error}")
        stats = svc.stats()
        _check(stats.degraded_buckets == 0,
               f"{stats.degraded_buckets} bucket(s) degraded to the "
               "sequential runner")
        lat = [r.latency_s for r in first]
        print(f"service: {N_REQUESTS}/{N_REQUESTS} requests completed in "
              f"{wall:.1f} s wall, p50 latency {float(np.median(lat)):.2f} s, "
              f"{stats.degraded_buckets} degraded buckets "
              "(one chip run; not a benchmark metric)", flush=True)

        replay = [svc.result(svc.submit(SearchRequest(sc)), timeout=300)
                  for sc in clones]
        for a, b in zip(first, replay):
            _check(b.status == "completed" and b.cached,
                   f"replay {b.request_id} ({b.scenario}) not served from "
                   f"the result cache: {b.status} cached={b.cached}")
            _check(_strip(a.result) == _strip(b.result),
                   f"replay {b.request_id} ({b.scenario}): cached payload "
                   "differs from the first round")
        print(f"  replay: {N_REQUESTS} requests served from the result "
              "cache, payloads equal", flush=True)
        print(render_stats(svc.stats()), flush=True)


def four_chip_phase() -> None:
    """One campaign bucket sharded over four chips vs on one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.experiments import campaign, get_scenario
    sc = get_scenario("rram_small_set")
    jobs = campaign.plan_campaign([sc], n_seeds=N_SEEDS, write=False,
                                  force=True)
    (bucket,) = campaign.bucket_jobs(jobs).values()
    job = bucket.jobs[0]

    t0 = time.perf_counter()
    bucket.dispatch()          # the campaign's own (sharded) dispatch
    sharded = {"main": [np.asarray(o) for o in bucket.outs],
               "spec": [np.asarray(o) for o in bucket.spec_outs]}
    for part, outs in (("main", bucket.outs), ("spec", bucket.spec_outs)):
        for o in outs:
            n = len(o.sharding.device_set)
            _check(n == 4, f"{part} output spans {n} device(s), not 4")
    t_sharded = time.perf_counter() - t0

    t0 = time.perf_counter()
    single = {}
    for part, arrays in (("main", bucket._main_arrays()),
                         ("spec", bucket._spec_arrays())):
        kern = campaign._build_bucket_kernel(
            bucket.key, job.traced, job.setup.space, None, part)
        outs = kern(*[jnp.asarray(a) for a in arrays])
        for o in outs:
            n = len(o.sharding.device_set)
            _check(n == 1, f"one-chip {part} output spans {n} devices")
        single[part] = [np.asarray(o) for o in outs]
    t_single = time.perf_counter() - t0

    for part in ("main", "spec"):
        best_g4, best_s4 = sharded[part][:2]
        best_g1, best_s1 = single[part][:2]
        _check(np.array_equal(best_g4, best_g1),
               f"{part}: best genomes differ between 4 chips and 1 chip")
        _check(np.array_equal(best_s4, best_s1),
               f"{part}: best scores differ between 4 chips and 1 chip: "
               f"{best_s4.tolist()} vs {best_s1.tolist()}")
        print(f"  {part}: {best_s4.shape[0]} lanes, best genomes and "
              f"scores equal on 4 chips and on 1 chip "
              f"(best {float(best_s4.min()):.6g})", flush=True)
    print(f"four chips: sharded dispatch {t_sharded:.1f} s, one-chip "
          f"{t_single:.1f} s, compilation included (one run; not a "
          f"benchmark metric) on {jax.device_count()} devices", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the search axis sharded over four "
                         "chips and its one-chip comparison")
    args = ap.parse_args(argv)
    # the host CPU re-scores chip results: keep its backend available
    # where the environment restricts JAX to the TPU
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    _import_repro()
    device = check_device(args.chips)
    from repro.api import enable_persistent_cache
    enable_persistent_cache()
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    if args.chips == 4:
        four_chip_phase()
    else:
        campaign_phase()
        service_phase()
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
