"""requests_per_batch.service: requests completed per service batch over
the window (the service's own counters, before and after)."""


def read(run):
    if run.entry != "service":
        return None
    a, b = run.window.extra["service_before"], run.window.extra["service_after"]
    batches = b["batches"] - a["batches"]
    return (b["completed"] - a["completed"]) / batches if batches else None
