"""jit_ms.campaign: milliseconds per campaign that JAX spent inside the
window tracing, lowering, compiling or loading programs from the
persistent cache (jax.monitoring duration events)."""


def read(run):
    return run.jit_ms_per_unit() if run.entry == "campaign" else None
