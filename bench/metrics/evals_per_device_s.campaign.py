"""evals_per_device_s.campaign: design evaluations the budget prescribes,
P_H + P_GA * 4G per unpadded search lane (seeds and specific baselines),
over the campaigns inside the traced slice, per second in which an
operation ran on a device, summed over the cell's devices."""


def read(run):
    if run.entry != "campaign" or run.trace is None:
        return None
    b = run.cell.config["budget"]
    per_lane = b["p_h"] + b["p_ga"] * 4 * b["generations"]
    lanes = sum(bk["lanes"] for u in run.window.traced
                if u.status == "completed" for bk in u.stats["buckets"])
    busy = sum(run.trace.busy_s)
    if not lanes or busy <= 0:
        return None
    return lanes * per_lane / busy
