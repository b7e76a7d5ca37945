"""Fused IMC crossbar evaluation: gather → noise → GEMM → ADC, one pass.

The accuracy model's hot loop (core/nonideal.make_accuracy_model)
evaluates, per genome, a noisy bit-serial crossbar GEMM: resolve the
genome's ``xbar_rows`` by value-table gather, inject conductance
variability into the differential weight pairs, accumulate per-sub-tile
bit-plane partial sums, and ADC-quantize each physical crossbar's
column sums (kernels/adc.py conventions). The pure-``jnp`` path
materializes the (8, B, n_sub, N) partial-sum tensor and the noised
weights per genome in HBM; this kernel fuses the whole chain so only
the (P, B, N) quantized outputs ever leave the kernel.

Grid: ``(P, n_sub)`` — one program instance per (genome, static
sub-tile). The reduction axis is split into static sub-tiles of
``sub = gcd(row values)`` rows; a VMEM scratch accumulator carries the
running (8, B, N) bit-plane sums and is flushed through the ADC at
each *crossbar-group* boundary, detected in-kernel from the genome's
traced row count (``floor((s+1)·sub/rows) != floor(s·sub/rows)``).
That reproduces core/nonideal's one-hot sub-tile grouping exactly, so
the kernel stays a single static grid while ``xbar_rows`` varies per
genome.

Noise draws happen OUTSIDE the kernel (jax.random is not portable
inside Pallas): callers pass the per-genome standard-normal fields
``eps_pos``/``eps_neg`` drawn on the untiled (K, N) weight shape with
the same fold_in keys as every other path, and the kernel applies the
conductance-noise *arithmetic* (clip + sigma polynomial + IR drop).
Scores are therefore bit-comparable across the 'jnp' / 'ref' /
'pallas' backends of core/nonideal.make_accuracy_model.

The sigma(g) polynomial and IR-drop attenuation constants live here
(single source of truth for the kernel, its oracle in ref.py, and
core/nonideal.py, which re-exports them) so the kernels package does
not import core.

Validated in interpret mode against ref.imc_fused_ref
(tests/test_kernels.py) and against the pre-existing einsum path on
every registry calibration config (tests/test_nonideal.py); compiled
for a described TPU v5e by tests/test_tpu_compile.py, and checked on
the chip against the CPU oracle by chip_smoke.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .adc import WEIGHT_BITS, adc_full_scale, adc_quantize

# sigma(g~) / g_max polynomial coefficients (c0 + c1 g + ... + c4 g^4),
# fitted to the Wan et al. RRAM data (paper [1]). Moved here from
# core/nonideal.py (which re-exports) so the kernel, its oracle, and
# the accuracy model share one definition without a core import.
SIGMA_POLY = np.array([0.010, 0.150, -0.133, -0.0005, 0.0396], np.float32)


def sigma_of_g(g_norm: jax.Array) -> jax.Array:
    """Conductance-dependent std (normalized to g_max)."""
    p = jnp.asarray(SIGMA_POLY)
    return jnp.clip(p[0] + p[1] * g_norm + p[2] * g_norm ** 2
                    + p[3] * g_norm ** 3 + p[4] * g_norm ** 4, 0.0, 0.5)


def ir_drop_factor(xbar_rows: jax.Array, activity: float = 0.5,
                   beta: float = 0.04) -> jax.Array:
    """Approximate IR-drop attenuation: larger arrays drop more supply
    along the bit/word lines; modeled as a multiplicative column-current
    attenuation (paper: 'approximate resistive interconnect effect')."""
    return 1.0 - beta * activity * (xbar_rows / 512.0)


def _sigma_scalar(g: jax.Array) -> jax.Array:
    # sigma_of_g with the coefficients as Python scalars: Pallas kernels
    # cannot capture array constants, and a float32-exact scalar
    # multiply is bit-identical to the indexed form.
    c0, c1, c2, c3, c4 = (float(c) for c in SIGMA_POLY)
    return jnp.clip(c0 + c1 * g + c2 * g ** 2 + c3 * g ** 3 + c4 * g ** 4,
                    0.0, 0.5)


def _fused_kernel(idx_ref, table_ref, xt_ref, w_ref, ep_ref, en_ref,
                  o_ref, acc_ref, *, sub: int, n_sub: int, adc_bits: int):
    p, s = pl.program_id(0), pl.program_id(1)
    # value-table gather (scalar prefetch, SMEM): the genome's integer
    # crossbar row count
    rows_i = table_ref[idx_ref[p]]
    rows = rows_i.astype(jnp.float32)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # conductance-noise injection on the differential pair (the same
    # arithmetic as nonideal.apply_conductance_noise, eps precomputed)
    w = w_ref[...]
    g_pos = jnp.clip(w, 0.0, 1.0)
    g_pos = jnp.clip(g_pos + _sigma_scalar(g_pos) * ep_ref[0], 0.0, 1.0)
    g_neg = jnp.clip(-w, 0.0, 1.0)
    g_neg = jnp.clip(g_neg + _sigma_scalar(g_neg) * en_ref[0], 0.0, 1.0)
    w_eff = (g_pos - g_neg) * ir_drop_factor(rows)

    # bit-serial partial sums of this sub-tile into the running group
    # accumulator (8, B, N). The activations arrive transposed, (sub, B),
    # so the sub-tile is a sublane slice: a (B, sub) block would put
    # sub (a gcd, often 64) on the 128-wide lane axis. HIGHEST keeps the
    # f32 contraction exact on the MXU (the default rounds w_eff to bf16).
    xt = xt_ref[...]
    for b in range(WEIGHT_BITS):
        bit = ((xt >> b) & 1).astype(jnp.float32)
        acc_ref[b] += jax.lax.dot_general(
            bit, w_eff, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    # crossbar-group boundary: the next sub-tile belongs to a new
    # physical crossbar of `rows` rows (traced, genome-dependent);
    # integer floor division equals the oracle's float floor here
    group_end = jnp.logical_or(
        s == n_sub - 1,
        (s + 1) * sub // rows_i != s * sub // rows_i)

    @pl.when(group_end)
    def _flush():
        q = adc_quantize(acc_ref[...], adc_full_scale(rows), adc_bits)
        # shift-accumulate; every term is an exact multiple of the ADC
        # step, so the unrolled sum equals the oracle's reduction
        tot = q[0]
        for b in range(1, WEIGHT_BITS):
            tot = tot + q[b] * float(2 ** b)
        o_ref[0] += tot
        acc_ref[...] = jnp.zeros_like(acc_ref)


@functools.partial(jax.jit, static_argnames=("sub", "adc_bits", "interpret"))
def imc_fused_gemm(x_q: jax.Array, w: jax.Array, eps_pos: jax.Array,
                   eps_neg: jax.Array, rows_idx: jax.Array,
                   row_table: jax.Array, *, sub: int, adc_bits: int = 8,
                   interpret: bool | None = None) -> jax.Array:
    """Fused population crossbar evaluation.

    x_q: (B, K) int32 activation codes in [0, 255] (shared by every
    genome); w: (K, N) f32 target weights in [-1, 1]; eps_pos/eps_neg:
    (P, K, N) per-genome standard-normal conductance-noise fields;
    rows_idx: (P,) int32 indices into ``row_table`` ((V,) integer-valued
    crossbar row counts — both scalar-prefetched to SMEM and gathered
    in-kernel). Returns the (P, B, N)
    shift-accumulated ADC-quantized column sums at the analog code
    scale (divide by 255 for the activation scale, as in
    imc_matmul_ref). K is padded to a multiple of ``sub`` here; callers
    pass natural shapes.
    """
    P, K, N = eps_pos.shape
    B = x_q.shape[0]
    pad = (-K) % sub
    if pad:
        x_q = jnp.pad(x_q, ((0, 0), (0, pad)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
        eps_pos = jnp.pad(eps_pos, ((0, 0), (0, pad), (0, 0)))
        eps_neg = jnp.pad(eps_neg, ((0, 0), (0, pad), (0, 0)))
    n_sub = (K + pad) // sub
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    kernel = functools.partial(_fused_kernel, sub=sub, n_sub=n_sub,
                               adc_bits=adc_bits)
    # index maps receive the two scalar-prefetch refs after the grid ids
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(P, n_sub),
        in_specs=[
            pl.BlockSpec((sub, B), lambda p, s, *_: (s, 0)),
            pl.BlockSpec((sub, N), lambda p, s, *_: (s, 0)),
            pl.BlockSpec((1, sub, N), lambda p, s, *_: (p, s, 0)),
            pl.BlockSpec((1, sub, N), lambda p, s, *_: (p, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, B, N), lambda p, s, *_: (p, 0, 0)),
        scratch_shapes=[pltpu.VMEM((WEIGHT_BITS, B, N), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, B, N), jnp.float32),
        interpret=interpret,
        name="imc_fused",
    )(rows_idx.astype(jnp.int32), row_table.astype(jnp.int32),
      x_q.astype(jnp.int32).T, w.astype(jnp.float32), eps_pos, eps_neg)
