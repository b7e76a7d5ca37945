"""The check that decides ``correct``: it passes the program, and it fails
the control and every fault a cell can have.

Each test drives a whole run of a cell at a small budget on the CPU (the
harness's look for a chip skipped), so the check sees what the timed path
delivered. The control is the reference at the next precision down
(bfloat16) put in the program's place; the faults are planted in the
program underneath the timed path.
"""
import numpy as np
import pytest

import harness
from correct import Reference, check_units, substitute

SECONDS = 3.0


def run(cell, seed=987654321012):
    return harness.run_cell(cell, seed, SECONDS, False, require_tpu=False,
                            log=lambda s: None)


@pytest.fixture
def fresh_kernels():
    """Planted faults change traced code: start and end with no compiled
    search kernel kept in memory."""
    from repro.core.distributed import kernel_cache_clear
    kernel_cache_clear()
    yield
    kernel_cache_clear()


@pytest.mark.parametrize("name", ["paper4_edap_campaign",
                                  "paper4_edapacc_campaign"])
def test_program_passes_and_control_fails(small_cell, name, monkeypatch):
    cell = small_cell(name)
    windows = []
    orig = cell.driver.run

    def keep(*a, **k):
        w = orig(*a, **k)
        windows.append(w)
        return w
    monkeypatch.setattr(cell.driver, "run", keep)
    line = run(cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0

    import jax.numpy as jnp
    import ml_dtypes
    ref_cfg = cell.config["reference"]
    control = Reference(ref_cfg, cost_dtype=ml_dtypes.bfloat16,
                        acc_dtype=jnp.bfloat16)
    units = substitute(control, windows[0].units)
    v = check_units(Reference(ref_cfg), units, cell.config["limits"])
    assert not v["correct"], v["checks"]
    assert v["checks"]["score_gap"]["value"] > \
        cell.config["limits"]["score_gap"]
    if "acc_gap" in v["checks"]:
        assert v["checks"]["acc_gap"]["value"] > \
            cell.config["limits"]["acc_gap"]


def _answer_altered(monkeypatch):
    from repro.core import genetic
    orig = genetic.ga_scan

    def altered(*a, **k):
        out = orig(*a, **k)
        return (out[0], out[1] * 1.01) + tuple(out[2:])
    monkeypatch.setattr(genetic, "ga_scan", altered)


def _half_batch(monkeypatch):
    # the mean over workloads taken over the first half of them
    from repro.core import objectives
    orig = objectives._agg

    def half(x, scheme):
        if scheme == "mean":
            return orig(x[:, : max(x.shape[1] // 2, 1)], scheme)
        return orig(x, scheme)
    monkeypatch.setattr(objectives, "_agg", half)


def _exchange_left_out(monkeypatch):
    # for a cell on several chips: lanes beyond the first device's share
    # never come back, their outputs stay zero-filled buffers
    from repro.experiments import campaign
    orig = campaign.compile_batched_search

    def no_gather(*a, **k):
        fn = orig(*a, **k)

        def call(*args):
            outs = fn(*args)
            keep = max(outs[0].shape[0] // 4, 1)
            return tuple(o.at[keep:].set(0) for o in outs)
        return call
    monkeypatch.setattr(campaign, "compile_batched_search", no_gather)


def _state_unchanged(monkeypatch):
    # every generation step hands back the population it was given
    from repro.core import genetic
    monkeypatch.setattr(genetic, "_generation_step",
                        lambda key, pop, *a, **k: pop)


@pytest.mark.parametrize("fault", [_answer_altered, _half_batch,
                                   _exchange_left_out, _state_unchanged])
def test_campaign_fault_is_caught(small_cell, fault, monkeypatch,
                                  fresh_kernels):
    fault(monkeypatch)
    line = run(small_cell("paper4_edap_campaign"))
    assert not line["correct"], line["checks"]


def test_accuracy_fault_is_caught(small_cell, monkeypatch, fresh_kernels):
    # an accuracy altered where it is produced: the SNR read 0.05 dB high
    from repro.core import nonideal
    orig = nonideal._snr_to_accuracy
    monkeypatch.setattr(nonideal, "_snr_to_accuracy",
                        lambda snr, b, p: orig(snr + 0.05, b, p))
    line = run(small_cell("paper4_edapacc_campaign"))
    assert not line["correct"], line["checks"]


def test_service_drops_half_the_batch(small_cell, monkeypatch):
    from repro.serve import codesign
    orig = codesign.CodesignService._finish_job
    seen = []

    def drop(self, rec, job):
        seen.append(rec)
        if len(seen) % 2 == 0:
            return            # the request never completes
        orig(self, rec, job)
    monkeypatch.setattr(codesign.CodesignService, "_finish_job", drop)
    cell = small_cell("paper4_edap_service")
    cell.mix.update(warm_batches=[], drain_s=3.0)
    line = run(cell)
    assert not line["correct"], line["checks"]
    assert line["checks"]["missing"]["value"] >= 1


def test_service_answer_altered(small_cell, monkeypatch, fresh_kernels):
    _answer_altered(monkeypatch)
    cell = small_cell("paper4_edap_service")
    cell.mix.update(warm_batches=[])
    line = run(cell)
    assert not line["correct"], line["checks"]
    assert np.isfinite(line["checks"]["score_gap"]["value"])
