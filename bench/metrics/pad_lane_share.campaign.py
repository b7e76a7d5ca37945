"""pad_lane_share.campaign: percent of the window's campaigns' search
lanes that are padding, 100 x the sum of ``lanes_padded`` over the sum of
``buckets[].lanes_padded_to`` in run_campaign's stats: lanes the lane
tier (rounded to the device count) adds and the device runs for
nothing."""


def read(run):
    done = [u for u in run.completed if u.stats]
    if run.entry != "campaign" or not done:
        return None
    slots = sum(b["lanes_padded_to"] for u in done
                for b in u.stats["buckets"])
    if slots <= 0:
        return None
    return 100.0 * sum(u.stats["lanes_padded"] for u in done) / slots
