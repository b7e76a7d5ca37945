"""Pure-jnp oracles for the Pallas kernels (allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .adc import WEIGHT_BITS, adc_full_scale, adc_quantize
from .imc_fused import ir_drop_factor, sigma_of_g


def imc_matmul_ref(x_q: jax.Array, w: jax.Array, *, xbar_rows: int = 256,
                   adc_bits: int = 8, w_scale: float = 1.0) -> jax.Array:
    """Bit-serial crossbar GEMM oracle. x_q: (M, K) int32 in [0, 255];
    w: (K, N) f32. Per (K-tile, bit-plane) partial sums are
    ADC-quantized (shared convention: kernels/adc.py) then
    shift-accumulated — same math as the kernel."""
    M, K = x_q.shape
    N = w.shape[1]
    assert K % xbar_rows == 0
    n_tiles = K // xbar_rows
    xt = x_q.reshape(M, n_tiles, xbar_rows)
    wt = w.reshape(n_tiles, xbar_rows, N)

    full_scale = adc_full_scale(xbar_rows, w_scale)
    out = jnp.zeros((M, N), jnp.float32)
    for b in range(WEIGHT_BITS):
        bit = ((xt >> b) & 1).astype(jnp.float32)
        partial = jnp.einsum("mtk,tkn->mtn", bit, wt.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
        q = adc_quantize(partial, full_scale, adc_bits)
        out = out + jnp.sum(q, axis=1) * (2.0 ** b)
    return out


def imc_fused_ref(x_q: jax.Array, w: jax.Array, eps_pos: jax.Array,
                  eps_neg: jax.Array, rows, *, sub: int,
                  adc_bits: int = 8) -> jax.Array:
    """Single-design oracle for imc_fused.imc_fused_gemm: conductance
    noise (precomputed eps fields), sub-tile bit-plane partial sums,
    one-hot grouping of sub-tiles into crossbars of ``rows`` rows
    (``rows`` may be traced), ADC per crossbar, shift-accumulate.
    x_q: (B, K) int32 codes; w, eps_pos, eps_neg: (K, N). Returns
    (B, N) at the analog code scale. vmap over (eps_pos, eps_neg, rows)
    for a population."""
    B, K = x_q.shape
    N = w.shape[1]
    pad = (-K) % sub
    g_pos = jnp.clip(w, 0.0, 1.0)
    g_pos = jnp.clip(g_pos + sigma_of_g(g_pos) * eps_pos, 0.0, 1.0)
    g_neg = jnp.clip(-w, 0.0, 1.0)
    g_neg = jnp.clip(g_neg + sigma_of_g(g_neg) * eps_neg, 0.0, 1.0)
    w_eff = (g_pos - g_neg) * ir_drop_factor(rows)
    n_sub = (K + pad) // sub
    xp = jnp.pad(x_q, ((0, 0), (0, pad)))
    wt = jnp.pad(w_eff, ((0, pad), (0, 0))).reshape(n_sub, sub, N)
    planes = jnp.stack(
        [((xp >> b) & 1).astype(jnp.float32) for b in range(WEIGHT_BITS)])
    planes = planes.reshape(WEIGHT_BITS, B, n_sub, sub)
    partial = jnp.einsum("qbsk,skn->qbsn", planes, wt,
                         precision=jax.lax.Precision.HIGHEST)
    sub_idx = jnp.arange(n_sub, dtype=jnp.float32)
    grp = jnp.floor(sub_idx * float(sub) / rows)
    onehot = (grp[:, None] == sub_idx[None, :]).astype(jnp.float32)
    tiles = jnp.einsum("qbsn,sg->qbgn", partial, onehot,
                       precision=jax.lax.Precision.HIGHEST)
    q = adc_quantize(tiles, adc_full_scale(rows), adc_bits)
    pow2 = 2.0 ** jnp.arange(WEIGHT_BITS, dtype=jnp.float32)
    return jnp.sum(q * pow2[:, None, None, None], axis=(0, 2))


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int = 0) -> jax.Array:
    """Plain softmax attention oracle. q: (BH, S, hd); k, v: (BH, T, hd)."""
    S, T = q.shape[1], k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(q.shape[-1]))
    q_pos = jnp.arange(S)[:, None]
    k_pos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
