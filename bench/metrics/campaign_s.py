"""campaign_s: the window's wall time over the campaigns completed in it.
The window runs from the first timed campaign's start to the end of the
last campaign that started before the run's seconds ran out."""


def read(run):
    if run.entry != "campaign" or not run.completed:
        return None
    w = run.window
    return (w.t1 - w.t0) / len(run.completed)
