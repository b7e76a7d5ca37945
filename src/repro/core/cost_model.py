"""JAX-vectorized analytical IMC cost model (the CIMLoop role, §III-A).

Given a *population* of hardware genomes and a packed workload set, this
computes energy (J), latency (s) per (design × workload) and chip area
(mm²) per design — fully vectorized (vmap-free broadcasting), jittable,
and shardable over the population axis (see core/distributed.py).

Model structure (tiled crossbar architecture, Fig. 2 of the paper):
  chip = G_per_chip tile groups × (T_per_router tiles + 1 router) + GLB
  tile = C_per_tile crossbar macros + I/O buffers
  macro = Xbar_rows × Xbar_cols cells + drivers + ONE 8-bit ADC
Inputs are 1-bit activation streams (8 bits serial); the single ADC per
macro is muxed over all columns (paper §III-B), so one input vector
costs 8 × Xbar_cols ADC cycles.

RRAM: weight-stationary — all weights on-chip or the design is
infeasible; spare capacity is used for layer duplication (throughput).
SRAM: weight swapping via LPDDR4 — weights streamed from DRAM when the
chip is too small; costs DRAM energy + latency.

Constants are calibrated to the NeuroSim/ISAAC literature at 32 nm and
scaled by technology node and operating voltage (Table 7 ranges):
  energy ∝ (tech/32) · (V/V_nom)²,  min cycle ∝ tech · alpha-power(V),
  area ∝ (tech/32)².
Absolute values are estimates; relative comparisons (the paper's own use
case, §III-A) are what the search consumes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from .search_space import (SearchSpace, TECH_COST_ALPHA, TECH_NODES_NM,
                           TECH_VMIN, TECH_VMAX, TECH_32NM_INDEX, V_NOM)
from .tracing import traced_closure
from .workloads import WorkloadArrays


@dataclasses.dataclass(frozen=True)
class HWConstants:
    """32 nm reference constants."""
    e_mac_rram: float = 0.010e-12   # J per 1-bit MAC in the array
    e_mac_sram: float = 0.015e-12
    e_adc: float = 2.0e-12          # J per 8-bit conversion
    e_buf: float = 0.05e-12         # J per byte buffer access
    e_router: float = 0.5e-12       # J per byte per hop
    e_dram: float = 40.0e-12        # J per byte (LPDDR4)
    dram_bw: float = 25.6e9         # B/s (LPDDR4)
    noc_bytes_per_cycle: float = 16.0  # per router
    p_static_xbar: float = 30.0e-6  # W leak per macro
    p_static_tile: float = 5.0e-6   # W leak per tile
    base_min_cycle_ns: float = 1.0  # at 32nm, V=1.0
    cell_f2_rram: float = 4.0
    cell_f2_sram: float = 160.0
    adc_area_mm2: float = 0.0012
    driver_area_per_row_mm2: float = 1.7e-7
    tile_buf_area_mm2: float = 0.005
    router_area_mm2: float = 0.02
    glb_mb_per_mm2: float = 0.75    # SRAM density at 32nm
    max_duplication: float = 16.0   # router/IO-bound cap on replication
    weight_bits: float = 8.0
    # memory-cell scaling saturates below ~14nm (SRAM bitcell / analog
    # array pitch stops tracking F^2) — floor on the area shrink factor
    mem_area_scale_floor: float = 0.30


class CostMetrics(NamedTuple):
    energy: jax.Array    # (P, W) joules
    latency: jax.Array   # (P, W) seconds
    area: jax.Array      # (P,) mm^2
    feasible: jax.Array  # (P,) bool — capacity feasibility (RRAM)
    cost: jax.Array      # (P,) normalized fabrication cost (alpha * area)
    # per-workload capacity fit (all-true for SRAM): feasible == all
    # workloads fit. Lets a full-set evaluation stand in for a
    # single-workload pack (the specific-baseline fan-out in
    # experiments/runner.py) without re-packing per workload.
    feasible_w: jax.Array  # (P, W) bool


# defaults for parameters a (reduced) space fixes rather than searches
# (paper §III-C1 fixes everything but bits_cell/rows/cols/c_per_tile)
_PARAM_DEFAULTS = {
    "bits_cell": 1.0,               # SRAM: 1 bit per cell
    "t_per_router": 8.0,
    "g_per_chip": 16.0,
    "glb_kb": 2048.0,
    "t_cycle_ns": 1.0,
    "v_op_step": 1.0,
    "tech_idx": float(TECH_32NM_INDEX),
}


@traced_closure
@jax.named_scope("cost_model")
def _resolve(space: SearchSpace, table: jax.Array, genomes: jax.Array,
             ) -> Dict[str, jax.Array]:
    """Gather parameter values for each genome: dict of (P,) arrays.
    Parameters absent from the space take fixed defaults."""
    out = {}
    for i, name in enumerate(space.names):
        out[name] = table[i, genomes[:, i]]
    P = genomes.shape[0]
    for name, val in _PARAM_DEFAULTS.items():
        if name not in out:
            out[name] = jnp.full((P,), val, jnp.float32)
    return out


@traced_closure
@jax.named_scope("cost_model")
def _cost_core(space: SearchSpace, c: HWConstants, p: Dict[str, jax.Array],
               *, M: jax.Array, K: jax.Array, N: jax.Array,
               seg_onehot: jax.Array, stored_weights: jax.Array,
               mask: jax.Array | None = None,
               wbits: jax.Array | None = None) -> CostMetrics:
    """Shared cost math over a (B, Lt) layer axis reduced to (P, W).

    Two callers:
      fixed path (``evaluate_population``) — B=1, Lt=Ltot ragged flat
        layers, ``mask``/``wbits`` None: layer sums are a plain
        ``x @ seg_onehot`` and cells-per-weight is the per-genome scalar
        ceil(8/bits_cell). Bit-identical to the pre-refactor model.
      joint path (``evaluate_population_joint``) — B=P, Lt=W*Lmax padded
        per-genome layers from a traced workload builder: pad rows are
        zeroed by ``mask`` before every segment sum and ``wbits`` gives
        per-layer weight precision (searched by the arch genome slice).
    """
    is_rram = space.mem_type == "rram"

    rows, cols = p["xbar_rows"], p["xbar_cols"]
    n_xb = p["c_per_tile"] * p["t_per_router"] * p["g_per_chip"]
    bits_cell = p["bits_cell"]
    cpw = jnp.ceil(c.weight_bits / bits_cell)          # cells per weight

    # --- technology / voltage scaling -------------------------------------
    tech_i = p["tech_idx"].astype(jnp.int32)
    tech_nm = jnp.asarray(TECH_NODES_NM)[tech_i]
    vmin = jnp.asarray(TECH_VMIN)[tech_i]
    vmax = jnp.asarray(TECH_VMAX)[tech_i]
    v_op = vmin + p["v_op_step"] * (vmax - vmin)
    tech_r = tech_nm / 32.0
    v_scale = (v_op / V_NOM) ** 2
    e_scale = tech_r * v_scale            # digital switching energy
    e_scale_adc = jnp.sqrt(tech_r) * v_scale  # ADCs scale weakly w/ node
    # memory/digital area ~F^2 until bitcell scaling saturates (floor)
    area_scale = jnp.maximum(tech_r ** 2, c.mem_area_scale_floor)
    area_scale_analog = jnp.maximum(tech_r, c.mem_area_scale_floor)
    min_cycle = (c.base_min_cycle_ns * 1e-9 * tech_r
                 * ((1.0 - 0.3) / jnp.maximum(v_op - 0.3, 0.05)) ** 1.3)
    t_cycle = jnp.maximum(p["t_cycle_ns"] * 1e-9, min_cycle)

    # --- per-layer crossbar mapping -----------------------------------------
    r_ = rows[:, None]
    c_ = cols[:, None]
    if wbits is None:
        cpw_ = cpw[:, None]
    else:
        cpw_ = jnp.ceil(wbits / bits_cell[:, None])    # per-layer cells

    def sum_l(x):                                               # (P, W)
        # HIGHEST: a TPU's default f32 matmul rounds x to bf16
        if mask is not None:
            x = x * mask
        return jnp.matmul(x, seg_onehot,
                          precision=jax.lax.Precision.HIGHEST)

    n_xb_row = jnp.ceil(K / r_)
    n_xb_col = jnp.ceil(N * cpw_ / c_)
    n_xb_layer = n_xb_row * n_xb_col

    # --- capacity / duplication / swap -------------------------------------
    # Weight-stationary mapping consumes WHOLE crossbars: a K=9 depthwise
    # layer on a 512-row array wastes 98% of it. Mapped-crossbar demand
    # (not raw weight count) drives capacity, duplication, and swapping —
    # this utilization effect is exactly the cross-workload tension on
    # crossbar size the paper's search exploits (§IV-F).
    capacity_cells = n_xb * rows * cols                          # (P,)
    mapped_xbars = sum_l(n_xb_layer)                             # (P, W)
    # stored-only weights (inactive MoE experts): dense slabs, packed ~1
    extra_w = jnp.maximum(
        stored_weights - sum_l(K * N), 0.0)                      # (P, W)
    mapped_xbars = mapped_xbars + jnp.ceil(
        extra_w * cpw[:, None] / (rows * cols)[:, None])
    mapped_cells = mapped_xbars * (rows * cols)[:, None]         # (P, W)
    cap_ok = mapped_xbars <= n_xb[:, None]
    feasible_w = cap_ok if is_rram else jnp.ones_like(cap_ok, bool)
    feasible = jnp.all(feasible_w, axis=1)
    dup = jnp.clip(jnp.floor(n_xb[:, None] /
                             jnp.maximum(mapped_xbars, 1.0)),
                   1.0, c.max_duplication)
    if not is_rram:
        dup = jnp.ones_like(dup)

    bitmacs = M * 8.0 * K * N * cpw_
    conversions = M * 8.0 * n_xb_row * (N * cpw_)
    act_bytes = M * (K + N)                      # 8-bit activations

    e_mac = c.e_mac_rram if is_rram else c.e_mac_sram
    hops = 1.0 + jnp.log2(p["g_per_chip"])[:, None]
    e_layer_dig = (bitmacs * e_mac + 2.0 * act_bytes * c.e_buf
                   + act_bytes * c.e_router * hops)
    e_layer_adc = conversions * c.e_adc

    # compute latency: ADC-muxed column readout, time-multiplexed if the
    # layer exceeds the chip's macro count, sped up by duplication.
    tmux = jnp.maximum(jnp.ceil(n_xb_layer / n_xb[:, None]), 1.0)
    l_compute = M * 8.0 * c_ * t_cycle[:, None] * tmux
    noc_bw = (c.noc_bytes_per_cycle * p["g_per_chip"] / t_cycle)  # B/s
    l_noc = act_bytes / noc_bw[:, None]

    # GLB spills: activations that do not fit the global buffer hit DRAM.
    glb_bytes = p["glb_kb"][:, None] * 1024.0
    spill = jnp.maximum(act_bytes - glb_bytes, 0.0)
    e_spill = spill * c.e_dram
    l_spill = spill / c.dram_bw

    # DRAM (external) energy does not scale with the on-chip node
    E = (sum_l(e_layer_dig) * e_scale[:, None]
         + sum_l(e_layer_adc) * e_scale_adc[:, None]
         + sum_l(e_spill))
    L = sum_l(l_compute) / dup + sum_l(l_noc + l_spill)

    # SRAM weight swapping: the fraction of MAPPED capacity that does not
    # fit on-chip is streamed from DRAM as 8-bit weights each inference.
    if not is_rram:
        swap_frac = jnp.clip(
            1.0 - capacity_cells[:, None] / jnp.maximum(mapped_cells, 1.0),
            0.0, 1.0)
        swapped = stored_weights * swap_frac                    # bytes
        E = E + swapped * c.e_dram                              # external
        L = L + swapped / c.dram_bw

    # static power over the run
    p_static = (n_xb * c.p_static_xbar
                + p["t_per_router"] * p["g_per_chip"] * c.p_static_tile)
    E = E + p_static[:, None] * L * e_scale[:, None]

    # --- area ---------------------------------------------------------------
    f2_mm2 = (32.0e-6) ** 2  # F^2 in mm^2 at 32nm
    cell_f2 = c.cell_f2_rram if is_rram else c.cell_f2_sram
    macro_dig = rows * cols * cell_f2 * f2_mm2
    macro_ana = c.adc_area_mm2 + rows * c.driver_area_per_row_mm2
    tile_dig = p["c_per_tile"] * macro_dig + c.tile_buf_area_mm2
    tile_ana = p["c_per_tile"] * macro_ana
    group_dig = p["t_per_router"] * tile_dig + c.router_area_mm2
    group_ana = p["t_per_router"] * tile_ana
    glb_area = (p["glb_kb"] / 1024.0) / c.glb_mb_per_mm2
    A = 1.10 * (
        (p["g_per_chip"] * group_dig + glb_area) * area_scale
        + p["g_per_chip"] * group_ana * area_scale_analog)

    cost = jnp.asarray(TECH_COST_ALPHA)[tech_i] * A
    return CostMetrics(energy=E, latency=L, area=A, feasible=feasible,
                       cost=cost, feasible_w=feasible_w)


@traced_closure
def evaluate_population(space: SearchSpace, wl: WorkloadArrays,
                        genomes: jax.Array,
                        constants: HWConstants = HWConstants(),
                        table: jax.Array | None = None) -> CostMetrics:
    """Pure function: (P, n_params) int32 genomes -> CostMetrics.

    All math broadcasts over P (population) and W (workloads); layer
    sums reduce the ragged flat layer axis with a one-hot segment
    matmul — no padding waste (§Perf it.8).
    """
    c = constants
    if table is None:
        table = jnp.asarray(space.value_table())
    p = _resolve(space, table, genomes)
    seg_onehot = jax.nn.one_hot(wl.seg_ids, wl.n_workloads,
                                dtype=jnp.float32)        # (Ltot, W)
    return _cost_core(space, c, p,
                      M=wl.flat_layers[None, :, 0],       # (1, Ltot)
                      K=wl.flat_layers[None, :, 1],
                      N=wl.flat_layers[None, :, 2],
                      seg_onehot=seg_onehot,
                      stored_weights=wl.stored_weights[None, :])


@traced_closure
def evaluate_population_joint(space: SearchSpace, builder,
                              genomes: jax.Array,
                              constants: HWConstants = HWConstants(),
                              table: jax.Array | None = None) -> CostMetrics:
    """Joint co-search cost path: the workload layer tensor is a traced
    function of each genome's arch slice (``WorkloadBuilder``), so the
    whole evaluation stays one pure jittable function of the genomes.

    Layer axes are padded (W * Lmax per genome) with a validity mask;
    per-layer weight precision from the builder feeds the cells-per-
    weight mapping. With zero families this is the same math as the
    flat path up to summation order (pads are masked, not absent).
    """
    c = constants
    if table is None:
        table = jnp.asarray(space.value_table())
    p = _resolve(space, table, genomes)
    wt = builder(genomes)
    P = genomes.shape[0]
    W, Lm = builder.n_workloads, builder.lmax
    layers = wt.layers.reshape(P, W * Lm, 3)
    seg_ids = jnp.repeat(jnp.arange(W, dtype=jnp.int32), Lm)
    seg_onehot = jax.nn.one_hot(seg_ids, W, dtype=jnp.float32)
    return _cost_core(space, c, p,
                      M=layers[:, :, 0], K=layers[:, :, 1],
                      N=layers[:, :, 2],
                      seg_onehot=seg_onehot,
                      stored_weights=wt.stored,
                      mask=wt.mask.reshape(P, W * Lm),
                      wbits=wt.wbits.reshape(P, W * Lm))


def make_evaluator(space: SearchSpace, wl: WorkloadArrays,
                   constants: HWConstants = HWConstants()):
    """jit-compiled population evaluator: genomes (P, n) -> CostMetrics."""
    table = jnp.asarray(space.value_table())

    @jax.jit
    def evaluator(genomes: jax.Array) -> CostMetrics:
        return evaluate_population(space, wl, genomes, constants, table)

    return evaluator


def make_joint_evaluator(space: SearchSpace, builder,
                         constants: HWConstants = HWConstants()):
    """jit-compiled joint evaluator: genomes (P, n_hw+n_arch) ->
    CostMetrics, with workload tensors built from the arch slice."""
    table = jnp.asarray(space.value_table())

    @jax.jit
    def evaluator(genomes: jax.Array) -> CostMetrics:
        return evaluate_population_joint(space, builder, genomes,
                                         constants, table)

    return evaluator
