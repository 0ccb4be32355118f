"""Streaming out-of-core SolveBakP Pallas kernel — x tiles in ``pl.ANY``
memory, double-buffered into VMEM scratch.

The fused megakernel (``repro.kernels.fused_solve``) keeps the whole design
``x_t`` VMEM-resident for the solve, which caps the design size at the VMEM
budget.  This module generalises the same in-kernel (sweeps × col-blocks)
iteration space to designs that only fit **HBM** (or, via the design
store's host fallback below, not even that):

  * ``x_t`` stays in ``pl.ANY`` memory (the compiler leaves it in HBM);
    only a ``(2, block, obs)`` double buffer of it lives in VMEM scratch.
  * Each column block is DMA'd in with ``pltpu.make_async_copy`` one block
    ahead of the compute (slot ``b % 2`` computes while slot ``(b+1) % 2``
    fills), so the paper's "one dimension of X per iteration" memory claim
    is literal: x-bytes resident = ``2·block·obs·itemsize``, independent of
    ``vars``.
  * Everything else matches the fused kernel exactly — the residual(s) and
    coefficient accumulator are VMEM-resident across sweeps, the per-sweep
    SSE reduces on-chip, and the shared ``sweep_stop_flags`` criterion
    aborts the in-kernel loop on convergence (no DMA for sweeps that never
    run).  Warm-start ``a0`` and k ≥ 1 right-hand sides ride along
    unchanged.

x crosses HBM once per *sweep* here (vs once per *solve* fused) — the
price of unbounded design size; the block math itself is the shared
``cd_sweep.bakp_block_update_ref``, so the two execution models cannot
drift numerically (the ``bakp_stream`` parity tests pin this).

``stream_solve_blocks`` is the out-of-core endpoint: a host-side
per-block sweep loop over any object exposing the ``StoreBlockSource``
interface (``shape``, ``num_blocks(thr)``, ``block_t(thr, j)``), used for
designs whose bytes live on the host/disk tiers of ``repro.store`` — and,
on the CPU, as the interpret-friendly reference the parity suite runs
everywhere.  It uses the same shared block update and stopping criterion.

On the CPU backend the Pallas kernel runs in interpret mode (DMA semantics
included): the same math as the compiled path.
"""
from __future__ import annotations

import functools
import importlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.types import (SolveResult, donate_default, fp32_matmuls,
                              sweep_stop_flags)
from repro.kernels.fused_solve import solve_init, validate_solver_args

# Shared block math + VMEM budget (see fused_solve on the importlib note).
_cd = importlib.import_module("repro.kernels.cd_sweep")


def stream_x_resident_bytes(block: int, obs: int, itemsize: int) -> int:
    """x bytes resident on-chip during a streaming solve: the two scratch
    buffers.  Independent of ``vars`` — the whole point."""
    return 2 * block * obs * itemsize


def stream_vmem_bytes(nvars: int, obs: int, nrhs: int, itemsize: int, *,
                      block: int, max_iter: int = 1) -> int:
    """VMEM working set of one streaming solve (bytes), in Mosaic's padded
    layout: the x double buffer + residual in/out (2 × (k, obs) fp32) +
    a0/coef (2 × (nvars, k)) + inv_cn (nvars, 1) + history.  The block
    update's per-chunk scratch is inside ``cd_sweep.VMEM_SLACK_BYTES``."""
    tile = _cd.vmem_tile_bytes
    return (2 * tile(block, obs, itemsize)
            + 2 * tile(nrhs, obs, 4)
            + 2 * tile(nvars, nrhs, 4)
            + tile(nvars, 1, 4)
            + tile(max_iter, 1, 4))


def stream_fits(nvars: int, obs: int, nrhs: int, itemsize: int, *,
                block: int, max_iter: int = 1) -> bool:
    """Whether a streaming solve's scratch + accumulators fit the shared
    VMEM budget (``repro.kernels.cd_sweep.VMEM_BUDGET_BYTES``, read at call
    time).  Note ``vars`` only enters through the O(vars·k) accumulators —
    designs far past the fused kernel's cap stream fine."""
    return stream_vmem_bytes(nvars, obs, nrhs, itemsize, block=block,
                             max_iter=max_iter) <= _cd.VMEM_BUDGET_BYTES


def _stream_kernel(scal_ref, x_hbm_ref, invcn_ref, e0_ref, a0_ref,
                   coef_ref, e_ref, hist_ref, sse_ref, n_ref, conv_ref,
                   *, block, max_iter):
    """Streaming whole-solve kernel body.  Refs as ``_fused_kernel`` except
    ``x_hbm_ref`` lives in ``pl.ANY`` (HBM) — the kernel DMAs one
    (block, obs) tile ahead of the compute into VMEM scratch."""
    atol_sse, rtol, omega = scal_ref[0], scal_ref[1], scal_ref[2]
    nvars, obs_p = x_hbm_ref.shape
    nblocks = nvars // block

    e_ref[...] = e0_ref[...].astype(jnp.float32)
    coef_ref[...] = a0_ref[...]
    hist_ref[...] = jnp.full((max_iter, 1), jnp.nan, jnp.float32)

    def _sse():
        # Same flattened dot reduction as the fused kernel — bit-for-bit
        # stopping parity with the host solvers in interpret mode.
        e = e_ref[...]
        ef = e.reshape(1, e.shape[0] * e.shape[1])
        return lax.dot_general(ef, ef, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)[0, 0]

    def solve_body(xscr_ref, sem_ref):
        sse0 = _sse()

        def dma(slot, b):
            return pltpu.make_async_copy(
                x_hbm_ref.at[pl.ds(b * block, block)],
                xscr_ref.at[slot], sem_ref.at[slot])

        def block_step(b, _):
            @pl.when(b + 1 < nblocks)
            def _prefetch():
                # Slot (b+1)%2 last served block b-1, whose wait+compute
                # finished in the previous (sequential) iteration — safe
                # to overwrite while block b computes out of slot b%2.
                dma((b + 1) % 2, b + 1).start()

            dma(b % 2, b).wait()
            rows = pl.ds(pl.multiple_of(b * block, block), block)
            da = _cd.bakp_block_update_ref(xscr_ref.at[b % 2],
                                           invcn_ref[rows, :], e_ref, omega)
            coef_ref[rows, :] = coef_ref[rows, :] + da
            return 0

        def sweep_body(state):
            i, sse_prev, converged, stop = state
            dma(0, 0).start()                              # warm-up fetch
            lax.fori_loop(0, nblocks, block_step, 0)
            sse = _sse()
            hist_ref[pl.ds(i, 1), :] = sse.reshape(1, 1)
            converged, stop = sweep_stop_flags(sse, sse_prev, sse0,
                                               atol_sse, rtol)
            return i + 1, sse, converged, stop

        def cond(state):
            i, _, _, stop = state
            return (i < max_iter) & ~stop

        n, sse, converged, _ = lax.while_loop(
            cond, sweep_body,
            (jnp.int32(0), sse0, jnp.bool_(False), jnp.bool_(False)))
        sse_ref[0, 0] = sse
        n_ref[0, 0] = n
        conv_ref[0, 0] = converged.astype(jnp.int32)

    pl.run_scoped(solve_body,
                  xscr_ref=pltpu.VMEM((2, block, obs_p), x_hbm_ref.dtype),
                  sem_ref=pltpu.SemaphoreType.DMA((2,)))


def _stream_call(x_t, inv_cn, e0, a0m, scal, *, block, max_iter, interpret):
    nvars, obs_p = x_t.shape
    nrhs = e0.shape[0]
    itemsize = x_t.dtype.itemsize
    kern = functools.partial(_stream_kernel, block=block, max_iter=max_iter)
    return pl.pallas_call(
        kern,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),        # x stays in HBM
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nvars, nrhs), jnp.float32),   # coef
            jax.ShapeDtypeStruct((nrhs, obs_p), jnp.float32),   # residual
            jax.ShapeDtypeStruct((max_iter, 1), jnp.float32),   # history
            jax.ShapeDtypeStruct((1, 1), jnp.float32),          # sse
            jax.ShapeDtypeStruct((1, 1), jnp.int32),            # n_sweeps
            jax.ShapeDtypeStruct((1, 1), jnp.int32),            # converged
        ],
        cost_estimate=pl.CostEstimate(
            # x crosses HBM once per sweep here (vs once per solve fused).
            flops=4 * max_iter * nvars * obs_p * nrhs,
            bytes_accessed=max_iter * nvars * obs_p * itemsize
            + 2 * nrhs * obs_p * 4 + 2 * nvars * nrhs * 4,
            transcendentals=0,
        ),
        compiler_params=_cd.compiler_params(stream_vmem_bytes(
            nvars, obs_p, nrhs, itemsize, block=block, max_iter=max_iter)),
        interpret=interpret,
    )(scal, x_t, inv_cn, e0, a0m)


@fp32_matmuls
def _stream_impl(x_t, y, inv_cn, a0, atol, rtol, omega, *, block, max_iter,
                 multi, interpret):
    nvars, obs_p = x_t.shape
    nrhs = y.shape[1] if multi else 1
    inv_cn, a0m, e0 = solve_init(x_t, y, inv_cn, a0, multi)
    atol_sse = jnp.float32(obs_p * nrhs) * jnp.float32(atol) ** 2
    scal = jnp.stack([atol_sse, jnp.float32(rtol), jnp.float32(omega)])
    coef, e, hist, sse, n, conv = _stream_call(
        x_t, inv_cn.reshape(nvars, 1).astype(jnp.float32), e0, a0m, scal,
        block=block, max_iter=max_iter, interpret=interpret)
    converged = conv[0, 0] != 0
    if not multi:
        return SolveResult(coef[:, 0], e[0], sse[0, 0], n[0, 0], converged,
                           hist[:, 0])
    return SolveResult(coef, e.T, sse[0, 0], n[0, 0], converged, hist[:, 0])


@functools.lru_cache(maxsize=None)
def _jitted(block, max_iter, multi, interpret, donate):
    return jax.jit(
        functools.partial(_stream_impl, block=block, max_iter=max_iter,
                          multi=multi, interpret=interpret),
        donate_argnums=(1, 3) if donate else (),   # y, a0
    )


def stream_solve(
    x_t: jax.Array,
    y: jax.Array,
    *,
    inv_cn: Optional[jax.Array] = None,
    cn: Optional[jax.Array] = None,
    a0: Optional[jax.Array] = None,
    block: int = 256,
    max_iter: int = 50,
    atol: float = 0.0,
    rtol: float = 0.0,
    omega: float = 1.0,
    interpret: Optional[bool] = None,
    donate: Optional[bool] = None,
) -> SolveResult:
    """Streaming whole-solve SolveBakP kernel (see module doc).

    Arguments exactly as ``fused_solve`` minus ``variant`` (Algorithm 2
    only — the sequential Algorithm 1 order gains nothing from tile
    prefetch).  ``x_t`` may be any size that fits HBM; only the scratch +
    accumulators (``stream_vmem_bytes``) must fit the VMEM budget.
    """
    nvars, obs_p = x_t.shape
    if nvars % block != 0:
        raise ValueError(
            f"vars ({nvars}) must be a multiple of block ({block}); pad "
            f"columns (PreparedDesign.x_t_for does this)")
    if max_iter < 1:
        raise ValueError(f"max_iter must be >= 1, got {max_iter}")
    multi, nrhs, inv_cn = validate_solver_args(x_t, y, cn, inv_cn, a0)
    vmem = stream_vmem_bytes(nvars, obs_p, nrhs, x_t.dtype.itemsize,
                             block=block, max_iter=max_iter)
    if vmem > _cd.VMEM_BUDGET_BYTES:
        raise ValueError(
            f"stream_solve scratch+accumulators {vmem / 2**20:.1f} MiB "
            f"exceed the VMEM budget "
            f"({_cd.VMEM_BUDGET_BYTES / 2**20:.0f} MiB); reduce block "
            f"({block}) / nrhs ({nrhs}), or use the per-sweep stream")
    fn = _jitted(block, max_iter, multi, _cd.interpret_mode(interpret),
                 donate_default(donate, y, a0))
    return fn(x_t, y, inv_cn, a0, atol, rtol, omega)


@fp32_matmuls
def stream_solve_blocks(
    blocks,
    y,
    *,
    inv_cn,
    a0=None,
    block: int = 256,
    max_iter: int = 50,
    atol: float = 0.0,
    rtol: float = 0.0,
    omega: float = 1.0,
) -> SolveResult:
    """Out-of-core SolveBakP over a block source (host/disk-tier designs).

    ``blocks`` is any object with the ``StoreBlockSource`` interface:
    ``shape`` (obs, vars), ``block_t(thr, j)`` returning the (thr, obs)
    fp32 tile of the transposed layout.  One tile is fetched per block
    step — x never materialises in full anywhere, matching the paper's
    per-iteration memory claim even for designs bigger than host RAM
    (disk-tier tiles are memmapped).

    The block update (``cd_sweep.bakp_block_update``) and stopping
    criterion (``sweep_stop_flags``) are the exact functions the Pallas
    kernels run, so results track the resident paths to float-accumulation
    noise.  ``inv_cn`` must already be in the thr-padded layout
    (``PreparedDesign.inv_cn_for(block)``).
    """
    obs_p, vars_p = blocks.shape
    nblocks = -(-vars_p // block)
    vars_pb = nblocks * block
    y = jnp.asarray(y, jnp.float32)
    if y.ndim not in (1, 2):
        raise ValueError(f"y must be (obs,) or (obs, k), got {y.shape}")
    multi = y.ndim == 2
    nrhs = y.shape[1] if multi else 1
    if a0 is not None and a0.shape not in ((vars_pb,), (vars_pb, nrhs)):
        raise ValueError(
            f"a0 must be ({vars_pb},) or ({vars_pb}, {nrhs}), "
            f"got {tuple(a0.shape)}")
    inv = jnp.asarray(inv_cn, jnp.float32).reshape(vars_pb, 1)
    y2 = y.reshape(obs_p, nrhs)

    def fetch(j):
        return jnp.asarray(blocks.block_t(block, j), jnp.float32)

    if a0 is None:
        a = jnp.zeros((vars_pb, nrhs), jnp.float32)
        e = y2.T
    else:
        a = jnp.broadcast_to(
            jnp.asarray(a0, jnp.float32).reshape(vars_pb, -1),
            (vars_pb, nrhs))
        e = y2.T
        for j in range(nblocks):   # e0 = y.T - a0.T @ x_t, one tile at a time
            e = e - lax.dot_general(a[j * block:(j + 1) * block], fetch(j),
                                    (((0,), (0,)), ((), ())))
    sse0 = jnp.vdot(e, e)
    atol_sse = jnp.float32(obs_p * nrhs) * jnp.float32(atol) ** 2
    hist = np.full((max(max_iter, 0),), np.nan, np.float32)
    sse = sse_prev = sse0
    n = 0
    converged = False
    for i in range(max_iter):
        for j in range(nblocks):
            da, e = _cd.bakp_block_update(
                fetch(j), inv[j * block:(j + 1) * block], e, omega)
            a = a.at[j * block:(j + 1) * block].add(da)
        sse = jnp.vdot(e, e)
        hist[i] = float(sse)
        conv_f, stop_f = sweep_stop_flags(sse, sse_prev, sse0, atol_sse,
                                          jnp.float32(rtol))
        n = i + 1
        converged = bool(conv_f)
        sse_prev = sse
        if bool(stop_f):
            break
    if not multi:
        return SolveResult(a[:, 0], e[0], sse, jnp.int32(n),
                           jnp.bool_(converged), jnp.asarray(hist))
    return SolveResult(a, e.T, sse, jnp.int32(n), jnp.bool_(converged),
                       jnp.asarray(hist))
