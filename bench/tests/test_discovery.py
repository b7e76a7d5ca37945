"""A configuration, a mix, a traffic driver and a metric are found by
name: adding one is adding files and entries, with no edit of any
existing file."""
import json
import os
import shutil

import harness
from conftest import BENCH, ROOT

# a way of offering load that no existing driver has: the whole window's
# campaigns submitted at once
NEW_DRIVER = '''
ENTRY = "campaign"


class System:
    def __init__(self, config, out_root):
        self.config = config

    def close(self):
        pass


def warm(system, mix, seed):
    pass


def run(system, mix, seed, seconds, tracer):
    return None


def describe(window):
    return {"burst": mix_size}


mix_size = 0
'''


def test_new_config_mix_driver_and_metric_need_no_code_edit(tmp_path):
    bench_dir = tmp_path / "bench"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = {p: open(p, "rb").read() for p in
              (str(x) for x in bench_dir.rglob("*.py"))}

    cfg = json.load(open(bench_dir / "configs" / "paper4_rram_edap.json"))
    cfg.update(name="sram_paper4", scenario="sram_small_set",
               reference=dict(cfg["reference"], mem="sram"))
    json.dump(cfg, open(bench_dir / "configs" / "sram_paper4.json", "w"))
    json.dump({"driver": "closed_campaign", "warmup_units": 2},
              open(bench_dir / "traffic" / "twice_warm.json", "w"))
    (bench_dir / "drivers" / "all_at_once.py").write_text(NEW_DRIVER)
    json.dump({"driver": "all_at_once", "burst": 4},
              open(bench_dir / "traffic" / "burst.json", "w"))
    (bench_dir / "metrics" / "units_done.py").write_text(
        "def read(run):\n    return float(len(run.completed))\n")
    bench["configs"].append({"name": "sram_paper4", "source": "x",
                             "file": "bench/configs/sram_paper4.json",
                             "reduced": [], "why": "x"})
    bench["workloads"] += [
        {"name": "sram_cell", "config": "sram_paper4",
         "traffic": "twice_warm", "chips": 1, "why": "x"},
        {"name": "burst_cell", "config": "sram_paper4", "traffic": "burst",
         "chips": 1, "why": "x"}]
    bench["per_layer"].append({"name": "units_done", "unit": "campaigns",
                               "better": "higher", "source":
                               "program_counter", "layer": "x",
                               "moves": "campaign_s",
                               "workloads": ["sram_cell"]})

    cell = harness.find_cell("sram_cell", bench, str(bench_dir))
    assert cell.config["scenario"] == "sram_small_set"
    assert cell.mix["warmup_units"] == 2
    assert cell.driver.ENTRY == "campaign"
    assert [m["name"] for m in cell.per_layer] == ["units_done"]
    reader = harness.load_reader("units_done", str(bench_dir))

    class Run:
        completed = [1, 2, 3]
    assert reader(Run()) == 3.0

    burst = harness.find_cell("burst_cell", bench, str(bench_dir))
    assert burst.mix["burst"] == 4
    assert burst.driver.__file__ == str(bench_dir / "drivers"
                                        / "all_at_once.py")
    assert burst.driver.describe(None) == {"burst": 0}
    after = {p: open(p, "rb").read() for p in before}
    assert after == before
