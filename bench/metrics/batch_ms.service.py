"""batch_ms.service: mean milliseconds of one service batch, from its
plan to its last bucket's drain (``service.batch`` spans), over the
batches of the window (the service's ``batch_s`` and ``batches``
counters, before and after)."""


def read(run):
    if run.entry != "service":
        return None
    a, b = run.window.extra["service_before"], run.window.extra["service_after"]
    if "batch_s" not in a or "batch_s" not in b:
        return None
    n = b["batches"] - a["batches"]
    return 1e3 * (b["batch_s"] - a["batch_s"]) / n if n else None
