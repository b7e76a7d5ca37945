"""Host spans and device scopes (core/tracing.py).

The load-bearing guarantees:

  * ``run_campaign``'s stats split each bucket's drain into the device
    wait and the finalize of its jobs, and time the plan;
  * ``ServiceStats`` carries the cumulative queue wait of dispatched
    requests and the time spent in batches;
  * under ``jax.profiler`` every span is a host event whose stats carry
    the ids (bucket signature, request id) that link one bucket's or
    one request's spans;
  * the compiled search and the fused kernel carry the named scopes
    the benchmark attributes device time by.
"""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.api import CodesignService, SearchRequest
from repro.core.tracing import span
from repro.experiments import campaign
from repro.experiments.scenarios import Budget, Scenario
from repro.kernels.imc_fused import imc_fused_gemm

TINY = Scenario(name="tiny_spans", mem="rram",
                workloads=("alexnet", "resnet18"), algorithm="fourphase",
                budget=Budget(p_h=16, p_e=8, p_ga=6, generations=1))


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One campaign and three service requests inside one profiler
    trace: (campaign stats, service stats, request ids, host events)."""
    from jax.profiler import ProfileData
    out = tmp_path_factory.mktemp("spans")
    trace_dir = str(out / "trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # program spans only
    with jax.profiler.trace(trace_dir, profiler_options=options):
        _, stats = campaign.run_campaign([TINY], out_dir=str(out / "c"),
                                         n_seeds=1, force=True)
        svc = CodesignService(out_dir=str(out / "s"), force=True,
                              window_s=0.05)
        try:
            rids = [svc.submit(SearchRequest(TINY, seed=s, n_seeds=1))
                    for s in (1, 2, 3)]
            for rid in rids:
                assert svc.result(rid, timeout=600).status == "completed"
        finally:
            svc.close()
    path, = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)
    events = [(e.name, dict(e.stats))
              for p in ProfileData.from_file(path).planes
              if p.name == "/host:CPU"
              for line in p.lines for e in line.events
              if e.name.startswith(("campaign.", "runner.", "service."))]
    return stats, svc.stats(), rids, events


def _named(events, name):
    return [st for n, st in events if n == name]


def test_campaign_stats_split_the_drain(traced_run):
    stats = traced_run[0]
    assert stats["plan_s"] >= 0.0
    assert stats["buckets"]
    for b in stats["buckets"]:
        assert b["wait_s"] >= 0.0 and b["finalize_s"] > 0.0
        assert b["drain_s"] >= b["wait_s"] + b["finalize_s"]
        assert b["dispatch_s"] > 0.0


def test_campaign_spans_carry_the_bucket(traced_run):
    stats, _, _, events = traced_run
    sig = stats["buckets"][0]["signature"]
    assert 1 in {st["n_scenarios"]
                 for st in _named(events, "campaign.plan")}
    dispatch = [st for st in _named(events, "campaign.dispatch")
                if st.get("bucket") == sig]
    assert len(dispatch) == 1
    assert dispatch[0]["kernel_cache"] in ("hit", "miss")
    parts = {st["part"] for st in _named(events, "campaign.lane_arrays")
             if st["bucket"] == sig}
    assert parts == {"main", "spec"}
    assert any(st["bucket"] == sig
               for st in _named(events, "campaign.device_wait"))
    fin = [st for st in _named(events, "campaign.finalize")
           if st["bucket"] == sig]
    assert [st["scenario"] for st in fin] == [TINY.name]
    for name in ("runner.design_metrics", "runner.write_artifacts"):
        assert TINY.name in {st["scenario"] for st in _named(events, name)}


def test_service_spans_carry_the_request(traced_run):
    _, _, rids, events = traced_run
    assert {st["request"] for st in _named(events, "service.submit")} \
        == set(rids)
    batched = " ".join(st["requests"]
                       for st in _named(events, "service.batch"))
    assert sorted(batched.split()) == sorted(rids)
    assert _named(events, "service.collect_wait")
    # the service plans each batch under the campaign's plan span
    plans = [st["n_scenarios"] for st in _named(events, "campaign.plan")]
    assert sum(plans) == 1 + len(rids)


def test_service_stats_queue_wait_and_batch_time(traced_run):
    st = traced_run[1]
    assert st.dispatched == 3 and st.completed == 3
    # every request waited at least the collect window
    assert st.queue_wait_s >= 3 * 0.05 * 0.9
    assert st.batch_s > 0.0 and st.batches >= 1
    # latency runs from submit (before resolution) to completion
    assert st.latency_p50_s > 0.0


def test_span_measures_its_block():
    with span("test.block", n=1) as sp:
        x = sum(range(1000))
    assert x == 499500 and sp.seconds > 0.0


def test_bucket_kernel_lowering_holds_the_scopes():
    jobs = campaign.plan_campaign([TINY], n_seeds=1, write=False)
    bucket, = campaign.bucket_jobs(jobs).values()
    key, mesh = bucket._kernel_key("main", bucket.n_main)
    kern = bucket._kernel(key, mesh, "main")
    text = kern.lower(*bucket._main_arrays()).as_text(debug_info=True)
    for scope in ("cost_model", "ga_step", "hamming_select"):
        # under vmap a scope reads "vmap(<scope>)" in the name stack
        assert re.search(rf'[("/]{scope}[)/]', text), scope


def test_fused_kernel_is_named():
    P, B, K, N = 2, 4, 16, 8
    fn = jax.jit(lambda x, w, ep, en, r, t: imc_fused_gemm(
        x, w, ep, en, r, t, sub=8))
    text = fn.lower(jnp.zeros((B, K), jnp.int32), jnp.zeros((K, N)),
                    jnp.zeros((P, K, N)), jnp.zeros((P, K, N)),
                    jnp.zeros((P,), jnp.int32),
                    jnp.ones((3,))).as_text(debug_info=True)
    assert re.search(r'"imc_fused/', text)
