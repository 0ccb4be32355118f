"""Compile rehearsal: the served path's Pallas kernels, at the sizes
chip_smoke.py runs them, and the XLA gram solve and its prepared block
factors at the tall and sharded deployments' sizes, compiled by the TPU
compiler for described (not attached) v5e chips.

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
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import (block_gram_cholesky, solvebakp,
                        solvebakp_obs_sharded)
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


@pytest.mark.parametrize("k", [1, 4])
def test_xla_gram_solve_reads_blocks_in_place(one_chip, k):
    """The XLA ``bakp_gram`` program at the tall deployment's padded shape
    (1048576 x 1024 fp32, thr 128, factors precomputed as the serving
    handle passes them).  Each column block is sliced out of x where it
    lies: the program's scratch stays below one (obs, thr) block, so it
    holds neither a blocked copy of x nor a materialised block."""
    obs, nvars, thr = 1 << 20, 1024, 128

    def run(x, y, cn, chol):
        return solvebakp(x, y, thr=thr, max_iter=50, rtol=1e-8, mode="gram",
                         cn=cn, chol=chol, donate=False)

    args = [jax.ShapeDtypeStruct(s, F32, sharding=one_chip) for s in
            ((obs, nvars), (obs, k) if k > 1 else (obs,), (nvars,),
             (nvars // thr, thr, thr))]
    compiled = jax.jit(run).lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()  # the XLA program
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < obs * thr * 4, temp


def test_sharded_gram_solve_reads_blocks_in_place(topo):
    """The obs-sharded ``bakp_gram`` program at the sharded deployment's
    padded shape (4194304 x 1024 fp32 over the four chips of a v5e:2x2,
    thr 128, k = 8, cold).  Each chip forms its block Grams by one einsum on
    the blocked view of its local shard and slices each block step's
    columns out of the shard where they lie: the per-chip scratch stays
    below one (obs_loc, thr) block.  The program that cut each block from a
    per-call blocked copy of the shard needed 4,832,871,424 B of scratch
    here."""
    obs, nvars, thr, k = 1 << 22, 1024, 128, 8
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))

    def run(x, y):
        return solvebakp_obs_sharded(x, y, mesh, thr=thr, max_iter=50,
                                     rtol=1e-8, mode="gram")

    rows = NamedSharding(mesh, P("data", None))
    args = [jax.ShapeDtypeStruct((obs, nvars), F32, sharding=rows),
            jax.ShapeDtypeStruct((obs, k), F32, sharding=rows)]
    compiled = jax.jit(run).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text  # the XLA program
    assert "all-reduce" in text           # the psums across the chips
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (obs // 4) * thr * 4, temp


def test_prepared_gram_factor_reads_blocks_in_place(one_chip):
    """The factor program ``PreparedDesign.chol_for`` runs
    (``block_gram_cholesky``) at the tall deployment's padded shape
    (1048576 x 1024 fp32, thr 128): the block Grams come from one einsum on
    the blocked view of x, so the scratch stays below one (obs, thr) block.
    Forming each block's Gram from a slice in a ``lax.map`` needed
    2,147,548,160 B here."""
    obs, nvars, thr = 1 << 20, 1024, 128
    x = jax.ShapeDtypeStruct((obs, nvars), F32, sharding=one_chip)
    compiled = jax.jit(
        lambda x: block_gram_cholesky(x, thr, 1e-6)).lower(x).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < obs * thr * 4, temp


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
