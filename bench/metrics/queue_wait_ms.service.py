"""queue_wait_ms.service: mean milliseconds a request waited between its
submit and the batch that took it, over the requests batched in the
window (the service's ``queue_wait_s`` and ``dispatched`` counters,
before and after)."""


def read(run):
    if run.entry != "service":
        return None
    a, b = run.window.extra["service_before"], run.window.extra["service_after"]
    if "queue_wait_s" not in a or "queue_wait_s" not in b:
        return None
    n = b["dispatched"] - a["dispatched"]
    return 1e3 * (b["queue_wait_s"] - a["queue_wait_s"]) / n if n else None
