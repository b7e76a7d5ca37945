"""Compile the main path's device programs for a described TPU v5e.

No chip is needed: the TPU compiler installed with jax compiles for a
topology that is described, not attached, and raises what the chip's
compiler would raise (unaligned blocks, VMEM overuse, programs that do
not fit). Nothing runs, so these tests say nothing about results; the
CPU tests and ``chip_smoke.py`` cover those.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.experiments import campaign, get_scenario
from repro.kernels.imc_fused import imc_fused_gemm

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _fits_one_chip(compiled) -> None:
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert used < V5E_HBM_BYTES, used


@pytest.mark.parametrize("P,K", [
    (24, 256),   # rram_accuracy: P_GA=24, calib_k=256 (4 sub-tiles of 64)
    (5, 200),    # P and K that need padding to the 64-row sub-tile
])
def test_fused_kernel_compiles_for_v5e(one_chip, no_persistent_cache, P, K):
    B, N, V = 32, 32, 4          # n_calib, calib_n, RRAM xbar_rows values

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((B, K), jnp.int32), spec((K, N), jnp.float32),
            spec((P, K, N), jnp.float32), spec((P, K, N), jnp.float32),
            spec((P,), jnp.int32), spec((V,), jnp.float32))
    compiled = jax.jit(
        lambda *a: imc_fused_gemm(*a, sub=64, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("name,fused", [
    ("rram_small_set", False),    # plain EDAP: cost model only
    ("rram_accuracy", True),      # EDAP / accuracy through the kernel
])
def test_search_kernel_compiles_for_v5e(one_chip, no_persistent_cache,
                                        monkeypatch, name, fused):
    """The 4-seed campaign bucket kernel at the registered budget, as the
    chip builds it: on a TPU, 'auto' resolves the accuracy route to the
    fused kernel and the kernel compiles to Mosaic, so the test makes
    this CPU process answer as a TPU while the scorer is built."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jobs = campaign.plan_campaign([get_scenario(name)], n_seeds=4,
                                  write=False, force=True)
    (bucket,) = campaign.bucket_jobs(jobs).values()
    job = bucket.jobs[0]
    assert job.traced.backend == "pallas"
    assert (job.traced.accuracy is not None) == fused
    kern = campaign._build_bucket_kernel(bucket.key, job.traced,
                                         job.setup.space, None, "main")
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in bucket._main_arrays()]
    compiled = kern.lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == fused
    _fits_one_chip(compiled)
