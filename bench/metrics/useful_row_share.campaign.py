"""useful_row_share.campaign: percent of the cost-model layer rows the
window's campaigns evaluated that their objectives read, 100 x the sum of
``buckets[].cost_rows_used`` over the sum of
``buckets[].cost_rows_evaluated`` in run_campaign's stats. A specific
lane evaluates every workload's rows and reads one workload's."""


def read(run):
    done = [u for u in run.completed if u.stats]
    if run.entry != "campaign" or not done:
        return None
    buckets = [b for u in done for b in u.stats["buckets"]]
    if not buckets or any("cost_rows_evaluated" not in b for b in buckets):
        return None
    evaluated = sum(b["cost_rows_evaluated"] for b in buckets)
    if evaluated <= 0:
        return None
    return 100.0 * sum(b["cost_rows_used"] for b in buckets) / evaluated
