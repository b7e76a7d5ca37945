"""Plain reference of the analytical IMC cost model and the objectives.

One design at a time, in NumPy, in a precision the caller chooses:
float64 is the reference, and a lower one (bfloat16) is the control
that a comparison must reject. It restates the semantics of the
program's cost model (tiled crossbar chip, 8-bit bit-serial inputs, one
muxed ADC per macro, RRAM weight-stationary capacity and duplication,
SRAM weight swapping from LPDDR4, technology and voltage scaling) and
of its objectives (EDAP with mean/max aggregation, EDAP over the
product of accuracies, an 800 mm^2 area limit, a 1e30 penalty), from
the description in the paper and the program's documentation. It
imports nothing of the program.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

# the search spaces: parameter order and admissible values (the genome
# is a vector of indices into these)
SPACES = {
    "rram": [
        ("bits_cell", [1.0, 2.0, 4.0]),
        ("xbar_rows", [64.0, 128.0, 256.0, 512.0]),
        ("xbar_cols", [64.0, 128.0, 256.0, 512.0]),
        ("c_per_tile", [2.0, 4.0, 8.0, 16.0, 32.0]),
        ("t_per_router", [2.0, 4.0, 8.0, 16.0]),
        ("g_per_chip", [1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0]),
        ("glb_kb", [128.0, 256.0, 512.0, 1024.0, 2048.0, 4096.0, 8192.0]),
        ("t_cycle_ns", [1.0, 2.0, 3.0, 5.0, 10.0]),
        ("v_op_step", list(np.linspace(0.0, 1.0, 8))),
    ],
    "sram": [
        ("xbar_rows", [64.0, 128.0, 256.0, 512.0]),
        ("xbar_cols", [64.0, 128.0, 256.0, 512.0]),
        ("c_per_tile", [2.0, 4.0, 8.0, 16.0, 32.0]),
        ("t_per_router", [2.0, 4.0, 8.0, 16.0]),
        ("g_per_chip", [1.0, 2.0, 4.0, 8.0, 16.0, 32.0]),
        ("glb_kb", [512.0, 1024.0, 2048.0, 4096.0, 8192.0, 16384.0,
                    32768.0]),
        ("t_cycle_ns", [1.0, 2.0, 3.0, 5.0, 10.0]),
        ("v_op_step", list(np.linspace(0.0, 1.0, 8))),
    ],
}

# 32 nm node (the fixed technology of every non-technology scenario)
TECH_NM, TECH_VMIN, TECH_VMAX, V_NOM = 32.0, 0.65, 1.00, 0.85
AREA_LIMIT_MM2 = 800.0
PENALTY = 1.0e30


def decode(mem: str, genome: Sequence[int]) -> Dict[str, float]:
    """Index genome -> {parameter: value}."""
    space = SPACES[mem]
    if len(genome) != len(space):
        raise ValueError(f"{mem} genome has {len(space)} genes, "
                         f"got {len(genome)}")
    return {n: float(v[int(i)]) for (n, v), i in zip(space, genome)}


def cardinalities(mem: str) -> np.ndarray:
    return np.asarray([len(v) for _, v in SPACES[mem]], np.int64)


def design_metrics(mem: str, design: Dict[str, float],
                   workloads: Sequence[Dict[str, np.ndarray]],
                   dtype=np.float64) -> Dict[str, np.ndarray]:
    """Energy (J) and latency (s) per workload, area (mm^2) and
    capacity feasibility of one design, computed in ``dtype``."""
    f = np.dtype(dtype).type

    def c(x):
        return f(x)

    rram = mem == "rram"
    rows, cols = c(design["xbar_rows"]), c(design["xbar_cols"])
    ct, tr, g = (c(design["c_per_tile"]), c(design["t_per_router"]),
                 c(design["g_per_chip"]))
    glb_kb = c(design["glb_kb"])
    bits = c(design.get("bits_cell", 1.0))
    n_xb = ct * tr * g
    cpw = np.ceil(c(8.0) / bits)

    tech_r = c(TECH_NM / 32.0)
    v_op = c(TECH_VMIN) + c(design["v_op_step"]) * c(TECH_VMAX - TECH_VMIN)
    v_scale = (v_op / c(V_NOM)) ** 2
    e_scale = tech_r * v_scale
    e_scale_adc = np.sqrt(tech_r) * v_scale
    area_scale = np.maximum(tech_r ** 2, c(0.30))
    area_scale_analog = np.maximum(tech_r, c(0.30))
    min_cycle = (c(1.0e-9) * tech_r
                 * (c(0.7) / np.maximum(v_op - c(0.3), c(0.05))) ** c(1.3))
    t_cycle = np.maximum(c(design["t_cycle_ns"]) * c(1e-9), min_cycle)

    energy, latency, fit = [], [], []
    for wl in workloads:
        lay = np.asarray(wl["layers"], dtype)
        M, K, N = lay[:, 0], lay[:, 1], lay[:, 2]
        n_row = np.ceil(K / rows)
        n_col = np.ceil(N * cpw / cols)
        n_layer = n_row * n_col
        mapped = np.sum(n_layer)
        extra = np.maximum(c(wl["stored"]) - np.sum(K * N), c(0.0))
        mapped = mapped + np.ceil(extra * cpw / (rows * cols))
        mapped_cells = mapped * rows * cols
        fit.append(bool(mapped <= n_xb) if rram else True)
        dup = (np.clip(np.floor(n_xb / np.maximum(mapped, c(1.0))),
                       c(1.0), c(16.0)) if rram else c(1.0))

        bitmacs = M * c(8.0) * K * N * cpw
        conversions = M * c(8.0) * n_row * (N * cpw)
        act = M * (K + N)
        e_mac = c(0.010e-12) if rram else c(0.015e-12)
        hops = c(1.0) + np.log2(g)
        e_dig = (bitmacs * e_mac + c(2.0) * act * c(0.05e-12)
                 + act * c(0.5e-12) * hops)
        e_adc = conversions * c(2.0e-12)
        tmux = np.maximum(np.ceil(n_layer / n_xb), c(1.0))
        l_compute = M * c(8.0) * cols * t_cycle * tmux
        noc_bw = c(16.0) * g / t_cycle
        l_noc = act / noc_bw
        spill = np.maximum(act - glb_kb * c(1024.0), c(0.0))
        e_spill = spill * c(40.0e-12)
        l_spill = spill / c(25.6e9)

        E = (np.sum(e_dig) * e_scale + np.sum(e_adc) * e_scale_adc
             + np.sum(e_spill))
        L = np.sum(l_compute) / dup + np.sum(l_noc + l_spill)
        if not rram:
            capacity = n_xb * rows * cols
            swap = np.clip(c(1.0) - capacity
                           / np.maximum(mapped_cells, c(1.0)),
                           c(0.0), c(1.0))
            swapped = c(wl["stored"]) * swap
            E = E + swapped * c(40.0e-12)
            L = L + swapped / c(25.6e9)
        p_static = n_xb * c(30.0e-6) + tr * g * c(5.0e-6)
        E = E + p_static * L * e_scale
        energy.append(E)
        latency.append(L)

    f2 = c(32.0e-6) ** 2
    cell_f2 = c(4.0) if rram else c(160.0)
    macro_dig = rows * cols * cell_f2 * f2
    macro_ana = c(0.0012) + rows * c(1.7e-7)
    group_dig = tr * (ct * macro_dig + c(0.005)) + c(0.02)
    group_ana = tr * ct * macro_ana
    glb_area = (glb_kb / c(1024.0)) / c(0.75)
    area = c(1.10) * ((g * group_dig + glb_area) * area_scale
                      + g * group_ana * area_scale_analog)
    return {"energy": np.asarray(energy, dtype),
            "latency": np.asarray(latency, dtype),
            "area": area, "feasible_w": np.asarray(fit, bool)}


def edap_per_workload(m: Dict[str, np.ndarray]) -> np.ndarray:
    """EDAP of the design on each workload alone (mJ * ms * mm^2)."""
    return m["energy"] * 1e3 * (m["latency"] * 1e3) * m["area"]


def objective(kind: str, agg: str, m: Dict[str, np.ndarray],
              accuracy: Optional[np.ndarray] = None,
              workload: Optional[int] = None) -> float:
    """The search objective of one design: ``kind`` 'edap' or
    'edap_acc', aggregated over workloads by ``agg`` ('mean' / 'max'),
    or restricted to one ``workload``; infeasible or over-area designs
    score the penalty."""
    e, lat = m["energy"] * 1e3, m["latency"] * 1e3
    acc = None if accuracy is None else np.maximum(accuracy, 1e-6)
    if workload is not None:
        s = e[workload] * lat[workload] * m["area"]
        if kind == "edap_acc":
            s = s / acc[workload]
        bad = not m["feasible_w"][workload]
    else:
        red = {"mean": np.mean, "max": np.max}[agg]
        s = red(e) * red(lat) * m["area"]
        if kind == "edap_acc":
            s = s / np.exp(np.sum(np.log(acc)))
        bad = not bool(np.all(m["feasible_w"]))
    if kind not in ("edap", "edap_acc"):
        raise ValueError(f"objective kind {kind!r} has no reference")
    if bad or float(m["area"]) > AREA_LIMIT_MM2:
        return PENALTY
    return float(s)
