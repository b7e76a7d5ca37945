"""idle_share.service: percent of the traced slice in which no operation ran
on the device, averaged over the cell's devices (profiler trace)."""


def read(run):
    return run.idle_pct() if run.entry == "service" else None
