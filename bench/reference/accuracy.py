"""Plain reference of the RRAM non-ideality accuracy model (§IV-H, Eq. 4).

Per design: calibration GEMMs through a noisy crossbar of the design's
row count. Target weights map to a differential conductance pair;
each conductance gets variability g + sigma(g) * eps, with eps drawn
per design from a key folded from the design's mixed-radix index, and
an IR-drop attenuation. 8-bit activations enter bit-serially, each
crossbar's column sums pass a signed mid-tread ADC, and 1% output
noise is added. The output SNR against the clean GEMM maps to a
retained accuracy by a logistic curve. The reference tiles statically,
one design at a time, in plain ``jax.numpy``; the precision of the
arithmetic is a parameter (float32 at HIGHEST is the reference,
bfloat16 the control). It imports nothing of the program.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

CALIB_SEED = 20260415      # the model's fixed calibration/noise seed
CALIB_N = 32               # calibration GEMM outputs
SIGMA_POLY = (0.010, 0.150, -0.133, -0.0005, 0.0396)
OUTPUT_NOISE = 0.01
SNR_MID_DB, SNR_SCALE_DB, ACC_FLOOR = 18.0, 4.0, 0.35
ADC_BITS = 8


def _sigma(g):
    c0, c1, c2, c3, c4 = SIGMA_POLY
    return jnp.clip(c0 + c1 * g + c2 * g ** 2 + c3 * g ** 3 + c4 * g ** 4,
                    0.0, 0.5)


def _adc(x, rows, dt):
    delta = (rows / 4.0) / 2.0 ** (ADC_BITS - 1)
    lo, hi = -2.0 ** (ADC_BITS - 1), 2.0 ** (ADC_BITS - 1) - 1.0
    return (jnp.clip(jnp.round(x / delta), lo, hi) * delta).astype(dt)


@functools.lru_cache(maxsize=None)
def _calibration(n_calib: int, calib_k: int):
    k_calib, k_noise = jax.random.split(jax.random.PRNGKey(CALIB_SEED))
    kx, kw = jax.random.split(k_calib)
    x = jax.random.uniform(kx, (n_calib, calib_k))
    w = jax.random.normal(kw, (calib_k, CALIB_N)) * 0.3
    x_q = jnp.round(jnp.clip(x, 0.0, 1.0) * 255.0).astype(jnp.int32)
    y_ref = jnp.matmul(x_q.astype(jnp.float32), w,
                       precision=jax.lax.Precision.HIGHEST) / 255.0
    return k_noise, x_q, w, y_ref


@functools.partial(jax.jit, static_argnames=("rows", "dtype"))
def _snr_db(k_noise, x_q, w, y_ref, flat_idx, *, rows: int, dtype):
    dt = jnp.dtype(dtype)
    prec = (jax.lax.Precision.HIGHEST if dt == jnp.float32
            else jax.lax.Precision.DEFAULT)
    k = jax.random.fold_in(k_noise, flat_idx)
    k_pos, k_neg, k_out = jax.random.split(k, 3)
    g_pos = jnp.clip(w, 0.0, 1.0).astype(dt)
    g_neg = jnp.clip(-w, 0.0, 1.0).astype(dt)
    g_pos = jnp.clip(g_pos + _sigma(g_pos)
                     * jax.random.normal(k_pos, w.shape).astype(dt), 0, 1)
    g_neg = jnp.clip(g_neg + _sigma(g_neg)
                     * jax.random.normal(k_neg, w.shape).astype(dt), 0, 1)
    w_eff = ((g_pos - g_neg) * (1.0 - 0.04 * 0.5 * rows / 512.0)).astype(dt)
    K = x_q.shape[1]
    total = jnp.zeros((x_q.shape[0], w.shape[1]), dt)
    for t0 in range(0, K, rows):        # one physical crossbar per tile
        xt, wt = x_q[:, t0:t0 + rows], w_eff[t0:t0 + rows]
        for b in range(8):              # bit-serial activation planes
            plane = ((xt >> b) & 1).astype(dt)
            col = jnp.matmul(plane, wt, precision=prec,
                             preferred_element_type=dt)
            total = total + _adc(col, float(rows), dt) * float(2 ** b)
    y = total.astype(jnp.float32) / 255.0
    y = y + OUTPUT_NOISE * jnp.std(y) * jax.random.normal(k_out, y.shape)
    err = jnp.mean((y - y_ref) ** 2)
    return 10.0 * jnp.log10(jnp.mean(y_ref ** 2) / jnp.maximum(err, 1e-12))


def flat_index(cards: Sequence[int], genome: Sequence[int]) -> int:
    """The design's mixed-radix index in the search space."""
    idx = 0
    for c, g in zip(cards, genome):
        idx = idx * int(c) + int(g)
    return idx


def accuracies(design: Dict[str, float], flat_idx: int,
               workloads: Sequence[str], n_layers: Sequence[int],
               base_acc: Dict[str, float], *, n_calib: int, calib_k: int,
               dtype=jnp.float32) -> np.ndarray:
    """Retained accuracy of one design on each workload."""
    k_noise, x_q, w, y_ref = _calibration(n_calib, calib_k)
    snr = float(_snr_db(k_noise, x_q, w, y_ref, jnp.int32(flat_idx),
                        rows=int(design["xbar_rows"]),
                        dtype=jnp.dtype(dtype).name))
    cpw = max(1.0, float(np.floor(8.0 / design.get("bits_cell", 1.0))))
    snr += 10.0 * np.log10(cpw)           # multi-cell averaging
    keep = 1.0 / (1.0 + np.exp(-(snr - SNR_MID_DB) / SNR_SCALE_DB))
    out = []
    for name, n in zip(workloads, n_layers):
        pen = float(np.clip(1.0 - 0.002 * n, 0.8, 1.0))
        out.append(base_acc.get(name, 0.90)
                   * (ACC_FLOOR + (1.0 - ACC_FLOOR) * keep) * pen)
    return np.asarray(out)
