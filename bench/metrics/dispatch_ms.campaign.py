"""dispatch_ms.campaign: milliseconds per campaign spent in its buckets'
dispatch (trace or fetch the bucket kernel and enqueue it), the sum of
``buckets[].dispatch_s`` in run_campaign's stats."""


def read(run):
    done = [u for u in run.completed if u.stats]
    if run.entry != "campaign" or not done:
        return None
    return 1e3 * sum(b["dispatch_s"] for u in done
                     for b in u.stats["buckets"]) / len(done)
