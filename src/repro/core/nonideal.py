"""RRAM non-idealities and the batched accuracy model (paper §IV-H, Eq. 4).

Conductance variability: g = g_t + sigma(g_t) * eps, eps ~ N(0,1), with
sigma a polynomial of the normalized target conductance fitted to the
Wan et al. RRAM data (paper [1]). We use a 4th-order even-ish profile
peaking mid-range, consistent with [58]'s fitted curve shape. Also:
IR-drop as a row-depth-dependent attenuation, bit-serial 8-bit
activations with per-tile ADC quantization (the SAME signed-delta ADC
convention as the Pallas kernel — kernels/adc.py is the single source
of truth), and 1% additive output noise.

Accuracy proxy: the paper runs full AIHWKIT inference per workload;
retraining/inference of real CIFAR models is outside this container, so
we derive accuracy from the output SNR of calibration GEMMs pushed
through the noisy-crossbar model. The logistic SNR->accuracy map is
calibrated so that the clean 8-bit baselines of §IV-H
(94.9/97.9/93.5/70.0 %) degrade by a few percent under the paper's
noise model — matching the reported qualitative behavior (accuracy drop
without hardware-aware retraining). Relative design comparisons are
what the objective consumes.

The model is **device-resident**: ``make_accuracy_model`` returns a
traceable closure ``(P, n) genomes -> (P, W) accuracies`` in which
genome-dependent parameters resolve by table gather (the same pattern
as cost_model._resolve) and the noisy calibration GEMMs vmap over the
population — so the accuracy-aware objective compiles into the scanned
GA exactly like the analytical cost model. Per-genome noise keys derive
from the genome's flat index in the search space (fold_in), so a design
always sees the same noise draw: scoring is deterministic, repeatable
across host/device paths, and stable inside lax.scan.

``accuracy_proxy_host`` retains the host-side per-genome loop (static
crossbar tiling, optional Pallas-kernel GEMM route) as the equivalence
oracle — tests/test_nonideal.py pins the vmapped model against it.
"""
from __future__ import annotations

from typing import Callable, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.adc import adc_full_scale, adc_quantize
# The sigma(g) polynomial and IR-drop model moved to the kernels
# package (single source of truth for the fused Pallas kernel, its
# oracle, and this model); re-exported here for back-compat.
from ..kernels.imc_fused import SIGMA_POLY  # noqa: F401  (back-compat)
from ..kernels.imc_fused import imc_fused_gemm, ir_drop_factor, sigma_of_g
from .search_space import SearchSpace
from .tracing import traced_closure
from .workloads import Workload, WorkloadArrays

OUTPUT_NOISE_FRAC = 0.01  # 1% output-referred noise [58]

# Crossbar-GEMM backends of the accuracy model. 'jnp' is the original
# einsum path (the equivalence reference), 'pallas' the fused kernel
# (kernels/imc_fused.py; interpret mode on CPU), 'ref' its pure-jnp
# oracle (the same fused dataflow without pallas_call), 'auto' picks
# 'pallas' on accelerator backends and 'jnp' on CPU (where interpret
# mode is a correctness tool, not a fast path).
BACKENDS = ("auto", "pallas", "ref", "jnp")


def resolve_backend(backend: str) -> str:
    """'auto' -> a concrete backend for the current jax platform."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    if backend == "auto":
        return "jnp" if jax.default_backend() == "cpu" else "pallas"
    return backend

# Calibration data / noise base seed: part of the *model*, not of the
# search — fixed so every search path (host loop, scanned GA, specific
# fan-out) scores a given design identically.
CALIB_SEED = 20260415

# Clean 8-bit baseline accuracies (paper §IV-H).
BASELINE_ACC = {
    "resnet18": 0.9488, "vgg16": 0.9789, "alexnet": 0.9350,
    "mobilenetv3": 0.7003,
}
_DEFAULT_BASE_ACC = 0.90

# Logistic SNR(dB) -> retained-accuracy map (full retention above
# ~35 dB, collapse below ~10 dB).
_SNR_MID_DB = 18.0
_SNR_SCALE_DB = 4.0
_ACC_FLOOR = 0.35


@traced_closure
def apply_conductance_noise(key: jax.Array, g_norm: jax.Array) -> jax.Array:
    eps = jax.random.normal(key, g_norm.shape)
    return jnp.clip(g_norm + sigma_of_g(g_norm) * eps, 0.0, 1.0)


@traced_closure
def _noised_weights(k_pos: jax.Array, k_neg: jax.Array, w: jax.Array,
                    rows) -> jax.Array:
    """Differential-pair conductance mapping + variability + IR drop.

    Noise is sampled on the UNTILED (K, N) weight shape so the host
    (static tiling) and device (traced grouping) paths draw identical
    values from the same key."""
    g_pos = apply_conductance_noise(k_pos, jnp.clip(w, 0.0, 1.0))
    g_neg = apply_conductance_noise(k_neg, jnp.clip(-w, 0.0, 1.0))
    return (g_pos - g_neg) * ir_drop_factor(rows)


@traced_closure
def quantize_activations(x: jax.Array) -> jax.Array:
    """8-bit DAC: [0, 1] activations -> int32 codes in [0, 255]."""
    return jnp.round(jnp.clip(x, 0.0, 1.0) * 255.0).astype(jnp.int32)


def noisy_crossbar_gemm(key: jax.Array, x: jax.Array, w: jax.Array,
                        xbar_rows: int, adc_bits: int = 8,
                        use_kernel: bool = False) -> jax.Array:
    """Reference noisy IMC GEMM (static ``xbar_rows``): weights in
    [-1, 1] mapped to differential conductance pairs with variability
    and IR drop, 8-bit bit-serial activations, per-tile signed-delta
    ADC (kernels/adc.py), 1% output noise. x: (B, K) float in [0, 1];
    w: (K, N). Returns (B, N) at the analog (float) activation scale.

    ``use_kernel=True`` routes the bit-serial GEMM through the Pallas
    kernel (kernels/ops.imc_gemm; interpret mode on CPU) instead of the
    pure-jnp oracle — identical math, pinned by tests/test_kernels.py.
    """
    x_q = quantize_activations(x)
    k_pos, k_neg, k_out = jax.random.split(key, 3)
    w_eff = _noised_weights(k_pos, k_neg, w,
                            jnp.asarray(float(xbar_rows)))
    if use_kernel:
        from ..kernels.ops import imc_gemm
        y_q = imc_gemm(x_q, w_eff, xbar_rows=xbar_rows,
                       adc_bits=adc_bits)
    else:
        from ..kernels.ref import imc_matmul_ref
        K = x_q.shape[1]
        pad = (-K) % xbar_rows
        y_q = imc_matmul_ref(jnp.pad(x_q, ((0, 0), (0, pad))),
                             jnp.pad(w_eff, ((0, pad), (0, 0))),
                             xbar_rows=xbar_rows, adc_bits=adc_bits)
    y = y_q / 255.0
    return y + OUTPUT_NOISE_FRAC * jnp.std(y) * \
        jax.random.normal(k_out, y.shape)


# ---------------------------------------------------------------------------
# batched (vmapped, jittable) accuracy model
# ---------------------------------------------------------------------------

def flat_index_strides(space: SearchSpace) -> np.ndarray:
    """(n,) int32 mixed-radix strides of the space — the host-time
    constant behind ``genome_flat_index``. Traced closures must hoist
    this (one ``jnp.asarray`` at build time) instead of recomputing the
    ``np.cumprod`` on every trace (analysis rule R001)."""
    cards = space.cardinalities.astype(np.int64)
    return np.concatenate(
        [np.cumprod(cards[::-1])[::-1][1:], [1]]).astype(np.int32)


def _flat_index(genomes: jax.Array, strides: jax.Array) -> jax.Array:
    # an elementwise product and sum, not an int32 matmul: exact on
    # every backend (the index keys each design's noise draw)
    return jnp.sum(genomes * strides, axis=-1)


def genome_flat_index(space: SearchSpace, genomes: jax.Array) -> jax.Array:
    """(P, n) index genomes -> (P,) unique flat (mixed-radix) index.

    The per-design noise key is fold_in(base, flat_index): the same
    design draws the same noise on every path. Space sizes stay below
    2^31 (paper: <= 1.21e7), so int32 is safe. Host-facing convenience;
    the accuracy model's traced closure precomputes the strides once
    via ``flat_index_strides``."""
    return _flat_index(genomes, jnp.asarray(flat_index_strides(space)))


def _workload_accuracy_params(
        workloads: Union[WorkloadArrays, Sequence[Workload]],
) -> Tuple[np.ndarray, np.ndarray]:
    """(base_acc (W,), depth_penalty (W,)) for either a packed
    WorkloadArrays or a plain Workload sequence."""
    if isinstance(workloads, WorkloadArrays):
        names = workloads.names
        n_layers = np.bincount(workloads.seg_ids,
                               minlength=len(names)).astype(np.float32)
    else:
        names = [w.name for w in workloads]
        n_layers = np.asarray([w.n_layers for w in workloads], np.float32)
    base = np.asarray([BASELINE_ACC.get(n, _DEFAULT_BASE_ACC)
                       for n in names], np.float32)
    # deeper models accumulate more noise
    pen = np.clip(1.0 - 0.002 * n_layers, 0.8, 1.0).astype(np.float32)
    return base, pen


@traced_closure
def _snr_to_accuracy(snr_db: jax.Array, base: jax.Array,
                     depth_pen: jax.Array) -> jax.Array:
    keep = jax.nn.sigmoid((snr_db - _SNR_MID_DB) / _SNR_SCALE_DB)
    return base * (_ACC_FLOOR + (1.0 - _ACC_FLOOR) * keep) * depth_pen


def _clean_gemm(x_q: jax.Array, w: jax.Array) -> jax.Array:
    """The noise-free quantized calibration GEMM, the SNR reference.
    HIGHEST: a TPU's default f32 matmul rounds ``w`` to bf16."""
    return jnp.matmul(x_q.astype(jnp.float32), w,
                      precision=jax.lax.Precision.HIGHEST) / 255.0


def calibration_data(key: jax.Array, n_calib: int, calib_k: int,
                     calib_n: int) -> Tuple[jax.Array, jax.Array]:
    """Shared calibration GEMM operands: activations in [0, 1] and
    weights ~ 0.3 * N(0, 1) (clipped by the conductance mapping)."""
    kx, kw = jax.random.split(key)
    x = jax.random.uniform(kx, (n_calib, calib_k))
    w = jax.random.normal(kw, (calib_k, calib_n)) * 0.3
    return x, w


def make_accuracy_model(space: SearchSpace,
                        workloads: Union[WorkloadArrays, Sequence[Workload],
                                         None] = None,
                        *, key: jax.Array | None = None,
                        n_calib: int = 32, calib_k: int = 256,
                        calib_n: int = 32, adc_bits: int = 8,
                        builder=None, backend: str = "auto",
                        ) -> Callable[[jax.Array], jax.Array]:
    """Traceable batched accuracy model: (P, n) genomes -> (P, W).

    Genome-dependent parameters (xbar_rows, bits_cell) resolve via the
    same value-table gather as cost_model._resolve; the noisy
    calibration GEMM vmaps over the population. Crossbar tiling with a
    *traced* row count uses a sub-tile grouping trick: the reduction
    axis is split into static sub-tiles of gcd(rows values) rows, and a
    one-hot segment matmul sums the sub-tiles belonging to each
    physical crossbar before the ADC — bit-identical (up to float
    summation order) to the static tiling of noisy_crossbar_gemm /
    kernels/ref.imc_matmul_ref.

    Joint co-search: pass a ``WorkloadBuilder`` as ``builder`` instead
    of fixed ``workloads`` — per-genome clean base accuracy and depth
    penalty then come from the genome's own architecture slice, while
    the hardware slice still drives the SNR retention. The per-genome
    accuracy couples both slices: noisy hardware (deep rows, multi-bit
    cells) punishes low-precision/shallow architectures first.

    The closure is pure JAX: compose it into objective scorers and it
    compiles into the scanned GA / vmapped search batch unchanged.

    ``backend`` selects the crossbar-GEMM route declaratively (see
    BACKENDS): 'jnp' keeps the einsum path above, 'pallas' fuses
    gather/noise/GEMM/ADC into one kernel (kernels/imc_fused.py),
    'ref' runs the kernel's pure-jnp oracle. All three draw identical
    per-design noise (eps fields precomputed from the same fold_in
    keys), so scores agree to float tolerance across backends —
    tests/test_nonideal.py pins this on every registry calibration
    config.
    """
    if (workloads is None) == (builder is None):
        raise ValueError("pass exactly one of workloads / builder")
    backend = resolve_backend(backend)
    key = jax.random.PRNGKey(CALIB_SEED) if key is None else key
    k_calib, k_noise = jax.random.split(key)
    x, w = calibration_data(k_calib, n_calib, calib_k, calib_n)
    x_q = quantize_activations(x)
    y_ref = _clean_gemm(x_q, w)

    table = jnp.asarray(space.value_table())
    rows_i = space.index("xbar_rows")
    bits_i = (space.index("bits_cell")
              if "bits_cell" in space.names else None)
    row_values = space.values[rows_i].astype(np.int64)
    sub = int(np.gcd.reduce(row_values))  # static sub-tile row count
    pad = (-calib_k) % sub
    K = calib_k + pad
    n_sub = K // sub
    # static bit-plane decomposition of the shared activations
    xp = jnp.pad(x_q, ((0, 0), (0, pad)))
    planes = jnp.stack(
        [((xp >> b) & 1).astype(jnp.float32) for b in range(8)])
    planes = planes.reshape(8, n_calib, n_sub, sub)
    # sub-tile start rows, prescaled by the static sub-tile height so
    # the traced closure divides by the (traced) row count directly
    sub_rows = jnp.arange(n_sub, dtype=jnp.float32) * sub
    group_idx = jnp.arange(n_sub, dtype=jnp.float32)
    pow2 = 2.0 ** jnp.arange(8, dtype=jnp.float32)
    if builder is None:
        base_np, pen_np = _workload_accuracy_params(workloads)
        base_acc, depth_pen = jnp.asarray(base_np), jnp.asarray(pen_np)

    strides = jnp.asarray(flat_index_strides(space))

    @traced_closure
    def one(genome: jax.Array, flat_idx: jax.Array) -> jax.Array:
        rows = table[rows_i, genome[rows_i]]
        bits = table[bits_i, genome[bits_i]] if bits_i is not None else 1.0
        cpw = jnp.maximum(1.0, jnp.floor(8.0 / bits))  # cells per weight
        k = jax.random.fold_in(k_noise, flat_idx)
        k_pos, k_neg, k_out = jax.random.split(k, 3)
        w_eff = _noised_weights(k_pos, k_neg, w, rows)
        wt = jnp.pad(w_eff, ((0, pad), (0, 0))).reshape(n_sub, sub, -1)
        # (8, B, n_sub, N) per-sub-tile bit-plane partial sums
        partial = jnp.einsum("qbsk,skn->qbsn", planes, wt,
                             precision=jax.lax.Precision.HIGHEST)
        # sum sub-tiles into crossbars of `rows` rows (traced grouping)
        grp = jnp.floor(sub_rows / rows)
        onehot = (grp[:, None] == group_idx[None, :]).astype(jnp.float32)
        tiles = jnp.einsum("qbsn,sg->qbgn", partial, onehot,
                           precision=jax.lax.Precision.HIGHEST)
        q = adc_quantize(tiles, adc_full_scale(rows), adc_bits)
        y = jnp.sum(q * pow2[:, None, None, None], axis=(0, 2)) / 255.0
        y = y + OUTPUT_NOISE_FRAC * jnp.std(y) * \
            jax.random.normal(k_out, y.shape)
        err = jnp.mean((y - y_ref) ** 2)
        sig = jnp.mean(y_ref ** 2)
        snr_db = 10.0 * jnp.log10(sig / jnp.maximum(err, 1e-12))
        return snr_db + 10.0 * jnp.log10(cpw)  # multi-cell averaging

    @traced_closure
    @jax.named_scope("noise_fields")
    def _eps_fields(flat_idx):
        # the SAME draws as _noised_weights: eps on the untiled (K, N)
        # weight shape from the design's fold_in key
        k = jax.random.fold_in(k_noise, flat_idx)
        k_pos, k_neg, k_out = jax.random.split(k, 3)
        return (jax.random.normal(k_pos, w.shape),
                jax.random.normal(k_neg, w.shape), k_out)

    @traced_closure
    def _add_output_noise(raw, k_out):
        y = raw / 255.0
        return y + OUTPUT_NOISE_FRAC * jnp.std(y) * \
            jax.random.normal(k_out, y.shape)

    row_table_f = jnp.asarray(row_values.astype(np.float32))

    @traced_closure
    def fused(genomes: jax.Array, flat: jax.Array) -> jax.Array:
        # fused dataflow: the (P, B, N) quantized outputs are the only
        # per-population intermediate that reaches HBM
        rows_idx = genomes[:, rows_i].astype(jnp.int32)
        eps_pos, eps_neg, k_outs = jax.vmap(_eps_fields)(flat)
        if backend == "pallas":
            raw = imc_fused_gemm(x_q, w, eps_pos, eps_neg, rows_idx,
                                 row_table_f, sub=sub, adc_bits=adc_bits)
        else:
            from ..kernels.ref import imc_fused_ref
            raw = jax.vmap(
                lambda ep, en, r: imc_fused_ref(
                    x_q, w, ep, en, r, sub=sub, adc_bits=adc_bits)
            )(eps_pos, eps_neg, row_table_f[rows_idx])
        y = jax.vmap(_add_output_noise)(raw, k_outs)
        err = jnp.mean((y - y_ref[None]) ** 2, axis=(1, 2))
        sig = jnp.mean(y_ref ** 2)
        snr_db = 10.0 * jnp.log10(sig / jnp.maximum(err, 1e-12))
        bits = table[bits_i, genomes[:, bits_i]] if bits_i is not None \
            else 1.0
        cpw = jnp.maximum(1.0, jnp.floor(8.0 / bits))
        return snr_db + 10.0 * jnp.log10(cpw)  # multi-cell averaging

    batched = jax.vmap(one)

    @traced_closure
    @jax.named_scope("accuracy_model")
    def accuracy(genomes: jax.Array) -> jax.Array:
        genomes = jnp.asarray(genomes)
        flat = _flat_index(genomes, strides)
        if backend == "jnp":
            snr_db = batched(genomes, flat)
        else:
            snr_db = fused(genomes, flat)
        if builder is None:
            return _snr_to_accuracy(snr_db[:, None], base_acc[None, :],
                                    depth_pen[None, :])
        wt = builder(genomes)
        pen = jnp.clip(1.0 - 0.002 * wt.n_layers, 0.8, 1.0)    # (P, W)
        return _snr_to_accuracy(snr_db[:, None], wt.base_acc, pen)

    return accuracy


def accuracy_proxy_host(space: SearchSpace, genomes: np.ndarray,
                        workloads: Union[WorkloadArrays,
                                         Sequence[Workload]],
                        *, key: jax.Array | None = None,
                        n_calib: int = 32, calib_k: int = 256,
                        calib_n: int = 32, adc_bits: int = 8,
                        use_kernel: bool = False) -> np.ndarray:
    """Host-side per-genome reference of make_accuracy_model.

    The retained equivalence oracle (and the benchmark baseline in
    benchmarks/bench_experiments.py): one Python iteration per genome,
    static crossbar tiling through noisy_crossbar_gemm — optionally via
    the Pallas kernel (``use_kernel=True``). Same calibration data,
    same per-design noise keys, same ADC convention; the vmapped model
    must reproduce it to float tolerance."""
    key = jax.random.PRNGKey(CALIB_SEED) if key is None else key
    k_calib, k_noise = jax.random.split(key)
    x, w = calibration_data(k_calib, n_calib, calib_k, calib_n)
    x_q = quantize_activations(x)
    y_ref = _clean_gemm(x_q, w)

    genomes = np.asarray(genomes)
    table = space.value_table()
    rows_i = space.index("xbar_rows")
    bits_i = (space.index("bits_cell")
              if "bits_cell" in space.names else None)
    base, pen = _workload_accuracy_params(workloads)
    flat = np.asarray(genome_flat_index(space, jnp.asarray(genomes)))

    accs = np.zeros((genomes.shape[0], len(base)), np.float32)
    for pi in range(genomes.shape[0]):
        rows = int(table[rows_i, genomes[pi, rows_i]])
        bits = (float(table[bits_i, genomes[pi, bits_i]])
                if bits_i is not None else 1.0)
        cpw = max(1.0, float(np.floor(8.0 / bits)))
        k = jax.random.fold_in(k_noise, int(flat[pi]))
        y = noisy_crossbar_gemm(k, x, w, xbar_rows=rows,
                                adc_bits=adc_bits, use_kernel=use_kernel)
        err = float(jnp.mean((y - y_ref) ** 2))
        sig = float(jnp.mean(y_ref ** 2))
        snr_db = 10.0 * np.log10(sig / max(err, 1e-12))
        snr_db += 10.0 * np.log10(cpw)
        accs[pi] = np.asarray(
            _snr_to_accuracy(jnp.float32(snr_db), jnp.asarray(base),
                             jnp.asarray(pen)))
    return accs
