"""The readers the large-set cell adds: the share of evaluated cost-model
rows its objectives read, the share of padded search lanes, and the
spread of device-busy time over the cell's devices. A reader that finds
nothing to read returns None, never 0."""
import types

import pytest

import harness
import trace_reduce
from conftest import BENCH
from generator import Unit, Window

CELL = "paper9_edap_campaign_4chip"
SMALL = f"{BENCH}/tests/data/small_trace.xplane.pb"


def _run(units=(), trace=None, cell=CELL):
    window = Window(units=list(units), t0=0.0, t1=1.0, traced=[])
    return harness.RunRecord(cell=harness.find_cell(cell),
                             device_kind="TPU v5 lite", setup_s=1.0,
                             window=window, jit_events=[], trace=trace)


def _read(name, run):
    return harness.load_reader(name, BENCH)(run)


def _campaign(buckets, lanes_padded=0, status="completed"):
    return Unit(index=0, seeds=[1], status=status,
                stats={"lanes_padded": lanes_padded, "buckets": buckets})


# one 8-seed campaign of the 9-workload set: 8 + 72 lanes on 784 rows,
# padded to 8 + 96 lanes on four devices
BUCKET = {"lanes": 80, "lanes_padded_to": 104,
          "cost_rows_evaluated": 80 * 784, "cost_rows_used": 16 * 784}


def test_useful_row_share():
    units = [_campaign([BUCKET], 24), _campaign([BUCKET], 24)]
    assert _read("useful_row_share.campaign", _run(units)) == \
        pytest.approx(20.0)
    # the small set's 8 + 32 lanes read 16 lanes' worth of rows
    small = dict(BUCKET, cost_rows_evaluated=40 * 93,
                 cost_rows_used=16 * 93)
    assert _read("useful_row_share.campaign",
                 _run([_campaign([small])])) == pytest.approx(40.0)


def test_pad_lane_share():
    units = [_campaign([BUCKET], 24), _campaign([BUCKET], 24)]
    assert _read("pad_lane_share.campaign", _run(units)) == \
        pytest.approx(100.0 * 24 / 104)


def test_device_busy_spread():
    trace = types.SimpleNamespace(busy_s=[1.0, 0.9, 1.1, 1.0])
    assert _read("device_busy_spread.campaign", _run(trace=trace)) == \
        pytest.approx(20.0)
    even = types.SimpleNamespace(busy_s=[0.5] * 4)
    assert _read("device_busy_spread.campaign", _run(trace=even)) == 0.0


@pytest.mark.parametrize("name", ["useful_row_share.campaign",
                                  "pad_lane_share.campaign"])
def test_counter_readers_without_counters(name):
    # no campaign completed; a failed one; a bucket of a program
    # without the counters (the parent's stats)
    assert _read(name, _run()) is None
    assert _read(name, _run([_campaign([BUCKET], 24, "failed")])) is None
    if name == "useful_row_share.campaign":
        legacy = {k: BUCKET[k] for k in ("lanes", "lanes_padded_to")}
        assert _read(name, _run([_campaign([legacy])])) is None
    assert _read(name, _run([_campaign([])])) is None
    # the service's entry is not a campaign
    assert _read(name, _run([_campaign([BUCKET], 24)],
                            cell="paper4_edap_service")) is None


def test_busy_spread_needs_devices_with_work():
    # untraced; a trace of one device (the recorded small trace)
    assert _read("device_busy_spread.campaign", _run()) is None
    one = trace_reduce.Summary(trace_reduce.load(SMALL))
    assert len(one.busy_s) == 1
    assert _read("device_busy_spread.campaign", _run(trace=one)) is None
    idle = types.SimpleNamespace(busy_s=[0.0] * 4)
    assert _read("device_busy_spread.campaign", _run(trace=idle)) is None
