"""The open loop times each request on the benchmark's own clock, from its
due time to when the client has the answer, whatever the program reports."""
import threading
import time
from types import SimpleNamespace

import generator
from conftest import BENCH

SERVICE_S = 0.2


class FakeService:
    """Answers each request SERVICE_S after its submit, and reports a
    latency of its own that the client must not use; one request in
    five never completes."""

    def __init__(self):
        self.submitted = {}
        self.lock = threading.Lock()

    def submit(self, seed):
        with self.lock:
            rid = f"r{len(self.submitted)}"
            self.submitted[rid] = time.perf_counter()
        return rid

    def result(self, rid, timeout):
        if int(rid[1:]) % 5 == 4:
            time.sleep(timeout)
            raise TimeoutError(rid)
        time.sleep(max(self.submitted[rid] + SERVICE_S - time.perf_counter(),
                       0.0))
        return SimpleNamespace(status="completed", result={}, error=None,
                               latency_s=1000.0)

    def counters(self):
        return {"completed": 0, "batches": 0}


def test_latency_is_the_clients_own():
    drive = generator.load_driver("open_service", BENCH)
    mix = {"rate_per_s": 10.0, "drain_s": 0.5}
    w = drive.run(FakeService(), mix, 3200000901, 1.0,
                  generator.Tracer(None))
    assert len(w.units) == 10
    done = [u for u in w.units if u.status == "completed"]
    assert [u.index for u in w.units if u.status == "missing"] == [4, 9]
    for u in done:
        assert SERVICE_S <= u.latency < SERVICE_S + 0.1, u.latency
    assert w.t1 == max(u.end for u in done)
    assert not [t for t in threading.enumerate()
                if t.name.startswith("bench-await")]


def test_every_seed_offers_the_same_arrivals():
    """The schedule is one fixed set of gaps in one order, ending at the
    window's length; two seeds differ only in what the requests ask."""
    due = generator.arrival_times(1.2, 51.0)
    assert len(due) == 61
    assert due[0] == 0.0 and abs(due[-1] - 51.0) < 1e-9
    assert (due[1:] > due[:-1]).all()
    drive = generator.load_driver("open_service", BENCH)
    mix = {"rate_per_s": 20.0, "drain_s": 0.5}
    runs = [drive.run(FakeService(), mix, seed, 0.5, generator.Tracer(None))
            for seed in (3200000901, 2718281801)]
    starts = [[u.due - w.t0 for u in w.units] for w in runs]
    assert len(starts[0]) == len(starts[1]) == 10
    for a, b in zip(*starts):
        assert abs(a - b) < 1e-9
    seeds = [[u.seeds[0] for u in w.units] for w in runs]
    assert not set(seeds[0]) & set(seeds[1])
