"""fused_gemm_roofline: percent of its roofline that the fused crossbar
kernel reached: the least time the chip could take for the kernel's work
(operations over the bf16 peak or bytes over the HBM bandwidth, whichever
is longer, ``work.py``) over the kernel's device time in the trace.

The kernel is the program's one Mosaic call: in the trace each call is an
``XLA Ops`` event named by its HLO text, ``%closed_call.N = f32[P,B,N]
custom-call(...), custom_call_target="tpu_custom_call"``; P, the designs
the call scored, is read from that output shape."""
import re

import work

KERNEL = r'custom_call_target="tpu_custom_call"'
OUTPUT = re.compile(r"^\S+ = f32\[(\d+),(\d+),(\d+)\]")
CALIB_N = 32


def read(run):
    if run.trace is None:
        return None
    ref = run.cell.config["reference"]
    b, k = int(ref.get("n_calib", 32)), int(ref.get("calib_k", 256))
    ops = nbytes = secs = 0.0
    for evs in run.trace.kernel_events(KERNEL):
        for e in evs:
            m = OUTPUT.match(e.name)
            if not m or (int(m[2]), int(m[3])) != (b, CALIB_N):
                return None
            o, by = work.fused_gemm_work(int(m[1]), b, k, CALIB_N)
            ops, nbytes = ops + o, nbytes + by
            secs += e.duration_ns * 1e-9
    if secs <= 0:
        return None
    share, _bound = work.roofline_share(ops, nbytes, secs, run.device_kind)
    return 100.0 * share
