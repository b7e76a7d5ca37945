"""requests_per_s: completed requests over the wall time from the first
request's due time to the last answer's arrival at the client (both on
the benchmark's clock)."""


def read(run):
    if run.entry != "service" or not run.completed:
        return None
    w = run.window
    return len(run.completed) / (w.t1 - w.t0)
