"""cost_model_busy_share.campaign: percent of device-busy time in ops
under the ``cost_model`` scope (leaf time, ``scopes.py``), summed over
the cell's devices, in the traced slice."""
import scopes


def read(run):
    return scopes.busy_share(run, "cost_model") \
        if run.entry == "campaign" else None
