"""Compile rehearsal: the served path's Pallas kernels, at the sizes
chip_smoke.py runs them, compiled by the TPU compiler for a described (not
attached) v5e chip.

Interpret mode accepts what Mosaic refuses — unaligned slices, slices of
loaded values, more VMEM than the scoped limit — so these compiles are
what keeps the VMEM accounting (``fused_fits`` / ``stream_fits`` /
``sweep_vmem_bytes``) honest: every shape the dispatch admits must
compile, and a shape it rejects is one the compiler refuses too.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (block_update, fused_fits, fused_solve,
                           score_features, solvebakp_persweep_kernel,
                           stream_fits, stream_solve)
from repro.kernels.cd_sweep import sweep_vmem_bytes

_CD = importlib.import_module("repro.kernels.cd_sweep")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel, not a fallback
    return text


F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.mark.parametrize("nvars,obs,k,dtype,variant,max_iter", [
    (512, 16384, 1, F32, "bakp", 200),     # phase b, fp32
    (1024, 16384, 64, BF16, "bakp", 200),  # phase b, bf16 tier
    (1024, 16384, 64, F32, "bakp", 32),    # its fp32 polish
    (256, 16384, 1, F32, "bak", 200),      # phase b, Algorithm 1
    (1792, 16384, 1, F32, "bakp", 50),     # at the VMEM budget
])
def test_fused_solve_compiles(one_chip, nvars, obs, k, dtype, variant,
                              max_iter):
    assert fused_fits(nvars, obs, k, jnp.dtype(dtype).itemsize,
                      max_iter=max_iter)

    def run(x_t, y, inv):
        return fused_solve(x_t, y, inv_cn=inv, block=256, max_iter=max_iter,
                           variant=variant, interpret=False, donate=False)

    _compile(run, one_chip, ((nvars, obs), dtype),
             ((obs, k) if k > 1 else (obs,), F32), ((nvars,), F32))


def test_stream_solve_compiles(one_chip):
    """Phase c: 8192 x 32768 fp32, 64 MiB of double-buffered tiles."""
    nvars, obs = 8192, 32768
    assert stream_fits(nvars, obs, 1, 4, block=256, max_iter=300)

    def run(x_t, y, inv):
        return stream_solve(x_t, y, inv_cn=inv, block=256, max_iter=300,
                            interpret=False, donate=False)

    _compile(run, one_chip, ((nvars, obs), F32), ((obs,), F32),
             ((nvars,), F32))


@pytest.mark.parametrize("variant,k", [("bakp", 1), ("bakp", 64),
                                       ("bak", 1)])
def test_persweep_compiles(one_chip, variant, k):
    """cd_sweep / bakp_sweep at 1024 x 16384 fp32: the grid double-buffers
    the (256, obs) tile, which ``sweep_vmem_bytes`` counts."""
    nvars, obs = 1024, 16384
    assert sweep_vmem_bytes(obs, k, 4, block=256) <= _CD.VMEM_BUDGET_BYTES

    def run(x_t, y, inv):
        return solvebakp_persweep_kernel(x_t, y, inv_cn=inv, block=256,
                                         max_iter=20, variant=variant,
                                         interpret=False, donate=False)

    _compile(run, one_chip, ((nvars, obs), F32),
             ((obs, k) if k > 1 else (obs,), F32), ((nvars,), F32))


def test_block_kernels_compile(one_chip):
    def upd(x_blk, e, da):
        return block_update(x_blk, e, da, interpret=False)

    _compile(upd, one_chip, ((256, 16384), F32), ((64, 16384), F32),
             ((256, 64), F32))

    def score(x_t, e, inv):
        return score_features(x_t, e, inv, interpret=False)

    _compile(score, one_chip, ((1024, 16384), F32), ((16384,), F32),
             ((1024,), F32))


def test_rejected_shape_is_refused_by_the_compiler(one_chip, monkeypatch):
    """2048 x 16384 fp32 is 128 MiB of x alone: the dispatch says no, and
    the compiler agrees when the budget check is bypassed."""
    nvars, obs = 2048, 16384
    assert not fused_fits(nvars, obs, 1, 4, max_iter=50)
    with pytest.raises(ValueError, match="VMEM"):
        fused_solve(jax.ShapeDtypeStruct((nvars, obs), F32),
                    jax.ShapeDtypeStruct((obs,), F32), block=256,
                    max_iter=50, interpret=False)
    monkeypatch.setattr(_CD, "VMEM_BUDGET_BYTES", 1 << 40)

    def run(x_t, y, inv):
        return fused_solve(x_t, y, inv_cn=inv, block=256, max_iter=50,
                           interpret=False, donate=False)

    with pytest.raises(Exception, match="(?i)vmem"):
        _compile(run, one_chip, ((nvars, obs), F32), ((obs,), F32),
                 ((nvars,), F32))
