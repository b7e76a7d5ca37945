"""fused_gemm_busy_share: percent of device-busy time spent in the fused
crossbar kernel (profiler trace, summed over the cell's devices)."""
KERNEL = r'custom_call_target="tpu_custom_call"'


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.kernel_s(KERNEL)
    busy = sum(run.trace.busy_s)
    if kernel_s <= 0 or busy <= 0:
        return None
    return 100.0 * kernel_s / busy
