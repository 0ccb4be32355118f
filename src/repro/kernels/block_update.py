"""Pallas TPU kernels with *streamed* obs — no VMEM-residency requirement.

These cover the regimes where the residual itself is too large for VMEM
(obs beyond ~10⁶ per device): the residual tiles through VMEM one block per
grid step, so obs is unbounded.

``block_update`` — paper Algorithm 2 line 9, the rank-``thr`` residual
correction ``e ← e − x_blkᵀ·da``: one MXU (CB×obs_tile) pass per grid step.

``score_features`` — SolveBakF line 3 scoring for *all* features in a single
pass over x: partial ⟨x_j, e⟩ accumulate in a VMEM scratch across the inner
(obs) grid dimension; the finished scores ⟨x_j,e⟩²/⟨x_j,x_j⟩ are written once
per column block.  Fuses the matvec, square and scale the paper does with
three BLAS calls.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.types import fp32_matmuls
from repro.kernels.cd_sweep import (compiler_params, interpret_mode,
                                    vmem_tile_bytes)


def _block_update_kernel(x_ref, da_ref, e_ref, out_ref):
    """Grid: (n_obs_tiles,).  x_ref: (CB, OT); da_ref: (CB, k);
    e_ref/out_ref: (k, OT) — k right-hand sides share the x stream."""
    xb = x_ref[...].astype(jnp.float32)
    da = da_ref[...]
    corr = jax.lax.dot_general(da, xb, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    out_ref[...] = e_ref[...].astype(jnp.float32) - corr


@fp32_matmuls
def block_update(x_t_blk, e, da, *, obs_tile=4096, interpret=None):
    """e' = e − x_blkᵀ·da with obs streamed in ``obs_tile`` chunks.

    Args:
      x_t_blk: (CB, obs) transposed column block.
      e: (obs,) residual or (k, obs) multi-RHS residuals.
      da: (CB,) or (CB, k) block coefficient increments.
    Returns:
      Updated residual, same rank as ``e``.
    """
    cb, obs = x_t_blk.shape
    single = e.ndim == 1
    e2 = e.reshape(1, obs) if single else e
    nrhs = e2.shape[0]
    obs_tile = min(obs_tile, obs)
    assert obs % obs_tile == 0, (obs, obs_tile)
    grid = (obs // obs_tile,)
    # Double-buffered x/e/out tiles plus the resident increments.
    vmem = 2 * (vmem_tile_bytes(cb, obs_tile, x_t_blk.dtype.itemsize)
                + 2 * vmem_tile_bytes(nrhs, obs_tile, 4)
                + vmem_tile_bytes(cb, nrhs, 4))
    out = pl.pallas_call(
        _block_update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((cb, obs_tile), lambda k: (0, k)),
            pl.BlockSpec((cb, nrhs), lambda k: (0, 0)),
            pl.BlockSpec((nrhs, obs_tile), lambda k: (0, k)),
        ],
        out_specs=pl.BlockSpec((nrhs, obs_tile), lambda k: (0, k)),
        out_shape=jax.ShapeDtypeStruct((nrhs, obs), jnp.float32),
        compiler_params=compiler_params(vmem),
        interpret=interpret_mode(interpret),
    )(x_t_blk, da.reshape(cb, nrhs).astype(jnp.float32),
      e2.astype(jnp.float32))
    return out[0] if single else out


def _score_kernel(x_ref, e_ref, invcn_ref, out_ref, g_scr):
    """Grid: (n_col_blocks, n_obs_tiles) — obs is the inner (fastest) dim.
    x_ref: (CB, OT); e_ref: (1, OT); invcn_ref/out_ref: (CB, 1);
    g_scr: (CB, 1) fp32 partial-dot accumulator."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _zero():
        g_scr[...] = jnp.zeros_like(g_scr)

    xb = x_ref[...].astype(jnp.float32)
    eb = e_ref[...].astype(jnp.float32)
    g_scr[...] += jax.lax.dot_general(xb, eb, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _finish():
        g = g_scr[...]
        out_ref[...] = g * g * invcn_ref[...]


@fp32_matmuls
def score_features(x_t, e, inv_cn, *, col_block=512, obs_tile=4096,
                   interpret=None):
    """SolveBakF scores for all features: ⟨x_j,e⟩²/⟨x_j,x_j⟩, one x pass.

    Args:
      x_t: (vars, obs); e: (obs,); inv_cn: (vars,).
    Returns: (vars,) fp32 scores.
    """
    nvars, obs = x_t.shape
    col_block = min(col_block, nvars)
    obs_tile = min(obs_tile, obs)
    assert nvars % col_block == 0 and obs % obs_tile == 0
    grid = (nvars // col_block, obs // obs_tile)
    vmem = (2 * (vmem_tile_bytes(col_block, obs_tile, x_t.dtype.itemsize)
                 + vmem_tile_bytes(1, obs_tile, 4)
                 + 2 * vmem_tile_bytes(col_block, 1, 4))
            + vmem_tile_bytes(col_block, 1, 4))
    out = pl.pallas_call(
        _score_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((col_block, obs_tile), lambda i, k: (i, k)),
            pl.BlockSpec((1, obs_tile), lambda i, k: (0, k)),
            pl.BlockSpec((col_block, 1), lambda i, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((col_block, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nvars, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((col_block, 1), jnp.float32)],
        compiler_params=compiler_params(vmem),
        interpret=interpret_mode(interpret),
    )(x_t, e.reshape(1, obs).astype(jnp.float32),
      inv_cn.reshape(nvars, 1).astype(jnp.float32))
    return out[:, 0]
