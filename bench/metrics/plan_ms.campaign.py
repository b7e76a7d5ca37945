"""plan_ms.campaign: milliseconds per campaign spent planning it
(``campaign.plan`` span: result-cache lookups, scenario set-up, scorer
and accuracy-model builds), the ``plan_s`` of run_campaign's stats."""


def read(run):
    done = [u for u in run.completed if u.stats and "plan_s" in u.stats]
    if run.entry != "campaign" or not done:
        return None
    return 1e3 * sum(u.stats["plan_s"] for u in done) / len(done)
