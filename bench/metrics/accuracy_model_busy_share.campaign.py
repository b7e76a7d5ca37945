"""accuracy_model_busy_share.campaign: percent of device-busy time in ops
under the ``accuracy_model`` scope, its ``noise_fields`` draws and the
``imc_fused`` kernel included (leaf time, ``scopes.py``), summed over
the cell's devices, in the traced slice."""
import scopes


def read(run):
    return scopes.busy_share(run, "accuracy_model") \
        if run.entry == "campaign" else None
