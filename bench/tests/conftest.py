"""CPU tests of the benchmark: ``python -m pytest bench/tests``."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

# the smallest budget at which every path of a cell still runs
SMALL_BUDGET = {"p_h": 40, "p_e": 16, "p_ga": 8, "generations": 1}


@pytest.fixture
def small_cell():
    """A cell of BENCHMARK.json at a budget a CPU test can hold."""
    import harness

    def make(name: str, n_seeds: int = 2):
        cell = harness.find_cell(name)
        cell.config["budget"] = dict(SMALL_BUDGET)
        cell.config["n_seeds"] = n_seeds
        if cell.driver.ENTRY == "service":
            cell.mix.update(warm_batches=[1, 2], rate_per_s=1.0,
                            drain_s=20.0)
        return cell
    return make
