"""selection_busy_share.campaign: percent of device-busy time in ops
under the ``hamming_select`` scope, the initial population's greedy
Hamming selection (leaf time, ``scopes.py``), summed over the cell's
devices, in the traced slice."""
import scopes


def read(run):
    return scopes.busy_share(run, "hamming_select") \
        if run.entry == "campaign" else None
