"""device_wait_ms.campaign: milliseconds per campaign that the host
waited for its buckets' device results (``campaign.device_wait`` span),
the sum of ``buckets[].wait_s`` in run_campaign's stats."""


def read(run):
    done = [u for u in run.completed if u.stats
            and all("wait_s" in b for b in u.stats["buckets"])]
    if run.entry != "campaign" or not done:
        return None
    return 1e3 * sum(b["wait_s"] for u in done
                     for b in u.stats["buckets"]) / len(done)
