"""device_busy_spread.campaign: the spread of device-busy time over the
cell's devices in the traced slice, 100 x (max - min) / mean of the
per-device busy seconds: a bucket left on one chip, or shards of unequal
work, read high. It needs two devices or more."""


def read(run):
    if run.entry != "campaign" or run.trace is None:
        return None
    busy = run.trace.busy_s
    if len(busy) < 2:
        return None
    mean = sum(busy) / len(busy)
    if mean <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / mean
