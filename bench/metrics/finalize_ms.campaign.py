"""finalize_ms.campaign: milliseconds per campaign spent turning its
buckets' device results into result dicts and artifacts
(``campaign.finalize`` spans), the sum of ``buckets[].finalize_s`` in
run_campaign's stats."""


def read(run):
    done = [u for u in run.completed if u.stats
            and all("finalize_s" in b for b in u.stats["buckets"])]
    if run.entry != "campaign" or not done:
        return None
    return 1e3 * sum(b["finalize_s"] for u in done
                     for b in u.stats["buckets"]) / len(done)
