"""Operations and bytes a kernel's algorithm needs, from its shapes, and
the chip peaks they are held against (``peaks.json``)."""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")
F32 = 4
BIT_PLANES = 8


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; a device that is not in
    the table is an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def fused_gemm_work(P: int, B: int, K: int, N: int) -> Tuple[float, float]:
    """(operations, bytes) of the fused crossbar evaluation of P designs:
    one (B, K) x (K, N) multiply-add per activation bit plane per design,
    reading two (P, K, N) f32 noise fields, the int32 activations and the
    f32 weights once, and writing the (P, B, N) f32 outputs."""
    ops = 2.0 * BIT_PLANES * P * B * K * N
    nbytes = F32 * (2 * P * K * N + P * B * N + B * K + K * N)
    return ops, nbytes


def roofline_share(ops: float, nbytes: float, seconds: float,
                   device_kind: str) -> Tuple[float, str]:
    """(least time the chip could take / the time taken, the bound that
    binds). The ops are held against the published bf16 peak, the only
    matrix peak the source gives."""
    pk = peaks(device_kind)
    t_ops = ops / pk["bf16_flops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_ops else "ops"
    return max(t_ops, t_bytes) / seconds, bound
