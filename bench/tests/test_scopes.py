"""Device time by named scope (``scopes.py``), idle time by program span
(``tools/idle_by_span.py``) and the readers of the metrics built on them
and on the program's spans.

``synthetic_trace`` writes a trace with known nesting: one device whose
``XLA Ops`` hold a while loop (ns 0-100 of the slice) around a
cost-model op (10-40) and a Hamming-selection op (50-70), then the fused
kernel (200-300) and a noise draw (300-330) under the accuracy model,
then a loop with no ``tf_op`` (400-450) around a Hamming-selection op
(410-420) and a copy with no ``tf_op`` (430-435); the host holds the
slice span (0-500), a dispatch span (90-210) and a finalize span
(320-480)."""
import dataclasses
import json
import os
import shutil
import sys

import pytest

import harness
import scopes
import trace_reduce
from conftest import BENCH
from generator import Unit, Window

sys.path.insert(0, os.path.join(BENCH, "tools"))
import idle_by_span  # noqa: E402

SMALL = os.path.join(BENCH, "tests", "data", "small_trace.xplane.pb")
T0 = 10 ** 6                     # the lines' timestamp, ns

# name -> (tf_op, start ns, end ns), relative to T0
DEVICE_OPS = {
    "while": ("jit(one)/while", 0, 100),
    "add_fusion": ("jit(one)/while/body/ga_step/cost_model/add", 10, 40),
    "gather": ("jit(one)/vmap(hamming_select)/gather:", 50, 70),
    "kernel": ("jit(one)/vmap(accuracy_model)/imc_fused/pallas_call",
               200, 300),
    "noise": ("jit(one)/vmap(accuracy_model)/vmap(jit(_threefry))/"
              "noise_fields/add", 300, 330),
    "loop": (None, 400, 450),
    "distances": ("jit(one)/vmap(hamming_select)/while/body/ne", 410, 420),
    "copy": (None, 430, 435),
}
HOST_SPANS = {"bench_traced_slice": (0, 500),
              "campaign.dispatch": (90, 210),
              "campaign.finalize": (320, 480)}


def _line(plane, name, line_id, spans, metadata_ids):
    line = plane.lines.add(id=line_id, name=name, timestamp_ns=T0)
    for key, (s, e) in spans.items():
        line.events.add(metadata_id=metadata_ids[key], offset_ps=s * 1000,
                        duration_ps=(e - s) * 1000)


def synthetic_trace(path: str) -> None:
    space = scopes.messages()["XSpace"]()
    dev = space.planes.add(id=1, name="/device:TPU:0")
    dev.stat_metadata.add(key=1, value={"id": 1, "name": "tf_op"})
    ids = {}
    for i, (name, (tf_op, _, _)) in enumerate(DEVICE_OPS.items(), 1):
        md = dev.event_metadata.add(key=i, value={"id": i, "name": name})
        if tf_op is None:        # XLA's own loops and copies carry none
            pass
        elif name == "gather":   # a tf_op held by reference
            dev.stat_metadata.add(key=100, value={"id": 100, "name": tf_op})
            md.value.stats.add(metadata_id=1, ref_value=100)
        else:
            md.value.stats.add(metadata_id=1, str_value=tf_op)
        ids[name] = i
    _line(dev, "XLA Ops", 1,
          {k: v[1:] for k, v in DEVICE_OPS.items()}, ids)
    host = space.planes.add(id=2, name="/host:CPU")
    hids = {}
    for i, name in enumerate(HOST_SPANS, 1):
        host.event_metadata.add(key=i, value={"id": i, "name": name})
        hids[name] = i
    _line(host, "python3", 7, HOST_SPANS, hids)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


@pytest.fixture(scope="module")
def small():
    return (scopes.load_xspace(SMALL),
            trace_reduce.Summary(trace_reduce.load(SMALL)))


@pytest.fixture
def cell_dir(tmp_path):
    """A copy of a campaign cell whose run writes under ``tmp_path``."""
    cell = harness.find_cell("paper4_edapacc_campaign")
    return dataclasses.replace(cell, bench_dir=str(tmp_path))


def _trace_path(cell) -> str:
    return os.path.join(cell.bench_dir, "out", cell.name, "trace",
                        "host", "t.xplane.pb")


def _run(cell, trace=None, units=(), extra=None):
    window = Window(units=list(units), t0=0.0, t1=1.0, traced=[],
                    extra=extra or {})
    return harness.RunRecord(cell=cell, device_kind="TPU v5 lite",
                             setup_s=1.0, window=window, jit_events=[],
                             trace=trace)


def _read(name, run):
    return harness.load_reader(name, BENCH)(run)


# ---------------------------------------------------------------------------
# scopes.py
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tf_op,chain", [
    ("jit(accuracy)/squeeze:", ()),
    ("jit(one)/vmap(hamming_select)/gather", ("hamming_select",)),
    ("jit(one)/while/body/ga_step/cost_model/add:",
     ("ga_step", "cost_model")),
    ("jit(f)/vmap(jit(accuracy_model))/vmap(noise_fields)/xor",
     ("accuracy_model", "noise_fields")),
    ("jit(f)/hamming_select.<locals>.body/add", ()),
    ("", ()),
])
def test_scope_chain(tf_op, chain):
    assert scopes.scope_chain(tf_op) == chain


def test_leaf_time_sums_to_busy_time(small):
    space, summary = small
    by_scope = scopes.leaf_time_by_scope(space, summary.t0, summary.t1)
    assert len(by_scope) == len(summary.busy_s) == 1
    # recorded before the program had scopes: all of it is unscoped
    assert set(by_scope[0]) == {scopes.UNSCOPED}
    assert by_scope[0][scopes.UNSCOPED] == pytest.approx(
        summary.busy_s[0], rel=1e-12)


def test_leaf_time_by_scope_on_known_nesting(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    synthetic_trace(path)
    space = scopes.load_xspace(path)
    by_scope = scopes.leaf_time_by_scope(space, T0, T0 + 500)[0]
    # the first loop's own time (100 - 30 - 20) is its own tf_op's; the
    # second loop and its copy take the scope of the op inside
    assert {k: round(v * 1e9) for k, v in by_scope.items()} == {
        scopes.UNSCOPED: 50, "cost_model": 30, "hamming_select": 70,
        "imc_fused": 100, "noise_fields": 30}
    chains = scopes.leaf_time_by_chain(space, T0, T0 + 500)
    assert round(scopes.time_under(chains, "accuracy_model") * 1e9) == 130
    summary = trace_reduce.Summary(trace_reduce.load(path))
    assert sum(by_scope.values()) == pytest.approx(summary.busy_s[0])
    # clipped to a slice that cuts the kernel in half
    half = scopes.leaf_time_by_scope(space, T0, T0 + 250)[0]
    assert round(half["imc_fused"] * 1e9) == 50


def test_innermost_latest_start_wins_and_mask():
    items = [(0, 10, "outer"), (2, 4, "a"), (4, 6, "b"), (5, 6, "c")]
    assert scopes.innermost(items, 0, 10) == {"outer": 6, "a": 2,
                                              "b": 1, "c": 1}
    got = scopes.innermost(items, 0, 12, mask=[(3, 5), (9, 12)],
                           uncovered="none")
    assert got == {"a": 1, "b": 1, "outer": 1, "none": 2}


def test_schema_reads_what_profile_data_reads(small):
    """The schema's field numbers are xplane.proto's: on the recorded
    trace it finds the planes, lines, events, names and times that
    ``ProfileData`` finds, and a ``tf_op`` on every device op."""
    space, summary = small
    pd = summary.pd
    assert [p.name for p in space.planes] == [p.name for p in pd.planes]
    for mine, theirs in zip(space.planes, pd.planes):
        names = {e.key: e.value.name for e in mine.event_metadata}
        assert [ln.name for ln in mine.lines] == \
            [ln.name for ln in theirs.lines]
        for ml, tl in zip(mine.lines, theirs.lines):
            got = [(names[e.metadata_id],
                    ml.timestamp_ns + e.offset_ps // 1000,
                    e.duration_ps // 1000) for e in ml.events]
            want = [(e.name, int(e.start_ns), int(e.duration_ns))
                    for e in tl.events]
            assert got == want, (mine.name, ml.name)
    dev = space.planes[1]
    assert dev.name == "/device:TPU:0"
    stat_names = {e.key: e.value.name for e in dev.stat_metadata}
    ops = {e.metadata_id for ln in dev.lines if ln.name == "XLA Ops"
           for e in ln.events}
    found = {entry.value.name: scopes._stat_text(st, stat_names)
             for entry in dev.event_metadata if entry.key in ops
             for st in entry.value.stats
             if stat_names.get(st.metadata_id) == scopes.TF_OP}
    # XLA's own copies carry none; the program's ops all do
    named = {e.value.name for e in dev.event_metadata if e.key in ops}
    assert {n for n in named if n not in found} == \
        {n for n in named if " copy-start(" in n or " copy-done(" in n}
    assert all(t.startswith("jit(") for t in found.values())
    assert any("tpu_custom_call" in name for name in found)


# ---------------------------------------------------------------------------
# tools/idle_by_span.py
# ---------------------------------------------------------------------------

def test_idle_by_span_on_known_nesting(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    synthetic_trace(path)
    got = idle_by_span.attribute(trace_reduce.Summary(
        trace_reduce.load(path)))
    assert round(got["idle_s"] * 1e9) == 220
    ns = {k: (round(v["idle_s"] * 1e9), round(v["self_s"] * 1e9),
              v["count"]) for k, v in got["spans"].items()}
    assert ns == {"campaign.finalize": (100, 160, 1),
                  "campaign.dispatch": (100, 120, 1),
                  idle_by_span.NO_SPAN: (20, 0, 0)}
    assert list(ns)[-1] == idle_by_span.NO_SPAN


def test_idle_by_span_cli_on_small_trace(capsys):
    assert idle_by_span.main([SMALL]) == 0
    out = json.loads(capsys.readouterr().out)
    assert list(out["spans"]) == [idle_by_span.NO_SPAN]
    assert out["spans"][idle_by_span.NO_SPAN]["idle_s"] == \
        pytest.approx(out["idle_s"])


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

SHARES = {"cost_model_busy_share.campaign": 30,
          "selection_busy_share.campaign": 70,
          "accuracy_model_busy_share.campaign": 130}


@pytest.mark.parametrize("name", sorted(SHARES))
def test_share_readers(cell_dir, name):
    path = _trace_path(cell_dir)
    synthetic_trace(path)
    summary = trace_reduce.Summary(trace_reduce.load(path))
    assert _read(name, _run(cell_dir, trace=summary)) == \
        pytest.approx(100.0 * SHARES[name] / 280)
    # an untraced run; a trace of a program without the scopes
    assert _read(name, _run(cell_dir)) is None
    shutil.copy(SMALL, path)
    old = trace_reduce.Summary(trace_reduce.load(path))
    assert _read(name, _run(cell_dir, trace=old)) is None


def _campaign(stats):
    return Unit(index=0, seeds=[1], status="completed", stats=stats)


SPAN_STATS = {"plan_s": 0.01, "buckets": [
    {"dispatch_s": 0.05, "wait_s": 0.02, "finalize_s": 0.4,
     "drain_s": 0.43},
    {"dispatch_s": 0.05, "wait_s": 0.04, "finalize_s": 0.2,
     "drain_s": 0.25}]}


@pytest.mark.parametrize("name,ms", [("plan_ms.campaign", 10.0),
                                     ("device_wait_ms.campaign", 60.0),
                                     ("finalize_ms.campaign", 600.0)])
def test_campaign_span_readers(cell_dir, name, ms):
    assert _read(name, _run(cell_dir, units=[_campaign(SPAN_STATS)])) \
        == pytest.approx(ms)
    # stats of a program without the spans
    old = {"buckets": [{"dispatch_s": 0.05, "drain_s": 0.4}]}
    assert _read(name, _run(cell_dir, units=[_campaign(old)])) is None
    assert _read(name, _run(cell_dir)) is None


@pytest.mark.parametrize("name,ms", [("queue_wait_ms.service", 500.0),
                                     ("batch_ms.service", 900.0)])
def test_service_counter_readers(name, ms):
    cell = harness.find_cell("paper4_edap_service")
    before = {"batches": 2, "dispatched": 3, "queue_wait_s": 1.0,
              "batch_s": 1.5, "completed": 3}
    after = {"batches": 6, "dispatched": 11, "queue_wait_s": 5.0,
             "batch_s": 5.1, "completed": 11}
    run = _run(cell, extra={"service_before": before,
                            "service_after": after})
    assert _read(name, run) == pytest.approx(ms)
    legacy = {k: before[k] for k in ("batches", "completed")}
    run = _run(cell, extra={"service_before": legacy,
                            "service_after": legacy})
    assert _read(name, run) is None
