"""The paper's large set (``rram_large_set``: nine workloads, 784 layer
rows) through ``run_campaign`` with its search lanes sharded over four
devices, against the same campaign on one device and against the plain
reference of ``bench/reference``.

Four virtual CPU devices need ``XLA_FLAGS`` set before JAX starts, so
the sharded campaign runs in a subprocess (``conftest.py`` keeps this
process at one device). The budget is tiny; the lanes, their padding to
the device count and the workload set are the paper-budget cell's."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

BUDGET = dict(p_h=40, p_e=16, p_ga=8, generations=1)
N_SEEDS = 2
SEED = 2026041500

CAMPAIGN = f"""
import dataclasses, json, sys
import jax
from repro.core import distributed
from repro.experiments import campaign, get_scenario
from repro.experiments.scenarios import Budget
sc = get_scenario("rram_large_set")
sc = dataclasses.replace(sc, budget=Budget(n_seeds={N_SEEDS}, **{BUDGET!r}))
(res,), stats = campaign.run_campaign([sc], n_seeds={N_SEEDS}, seed={SEED},
                                      write=False, force=True)
kernels = sorted((k[2], k[3], k[4]) for k in distributed._KERNEL_CACHE
                 if k[0] == "campaign")
json.dump({{"devices": jax.device_count(), "kernels": kernels,
           "buckets": stats["buckets"], "result": res}}, sys.stdout)
"""


def _campaign(n_devices: int) -> dict:
    path = [os.path.join(ROOT, "src"), os.environ.get("PYTHONPATH", "")]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(path))
    env["XLA_FLAGS"] = " ".join(
        [os.environ.get("XLA_FLAGS", ""),
         f"--xla_force_host_platform_device_count={n_devices}"]).strip()
    p = subprocess.run([sys.executable, "-c", CAMPAIGN], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout)


@pytest.fixture(scope="module")
def runs():
    return {n: _campaign(n) for n in (4, 1)}


def test_lanes_shard_over_four_devices(runs):
    four = runs[4]
    assert four["devices"] == 4
    # 2 seeds + 2 x 9 specific lanes; each part padded to the device count
    b, = four["buckets"]
    assert b["lanes"] == N_SEEDS * 10
    assert b["lanes_padded_to"] % 4 == 0
    assert {(part, n_dev) for part, _, n_dev in four["kernels"]} == \
        {("main", 4), ("spec", 4)}
    assert {n_dev for _, _, n_dev in runs[1]["kernels"]} == {0}


def test_sharded_equals_one_device(runs):
    a, b = runs[4]["result"], runs[1]["result"]
    for k in ("best_genome", "best_score"):
        assert a["seeds"][k]["per_seed"] == b["seeds"][k]["per_seed"], k
    assert a["seeds"]["list"] == b["seeds"]["list"]
    assert a["generalized"]["design"] == b["generalized"]["design"]
    assert a["specific"] == b["specific"]


def test_chosen_designs_match_the_reference(runs):
    from correct import Reference, rel_gap
    with open(os.path.join(BENCH, "configs", "paper9_rram_edap.json")) as f:
        cfg = json.load(f)
    ref = Reference(cfg["reference"])
    limit = cfg["limits"]["score_gap"]
    res = runs[4]["result"]
    assert set(res["generalized"]["per_workload"]) == set(ref.workloads)
    m = ref.evaluate(ref.encode(res["generalized"]["design"]))
    gaps = {}
    for i, wl in enumerate(ref.workloads):
        gaps[f"generalized {wl}"] = rel_gap(
            res["generalized"]["per_workload"][wl]["edap"], m["edap"][i])
        s = res["specific"][wl]
        ms = ref.evaluate(ref.encode(s["design"]))
        gaps[f"specific {wl}"] = rel_gap(s["edap"], ms["edap"][i])
    worst = max(gaps, key=gaps.get)
    assert gaps[worst] <= limit, (worst, gaps[worst])
