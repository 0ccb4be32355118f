"""Pallas TPU kernel: VMEM-resident coordinate-descent sweep (Algorithm 1).

The paper's inner loop is strictly sequential per column:

    da = ⟨x_j, e⟩/⟨x_j, x_j⟩ ;  e ← e − x_j·da ;  a_j += da

A mechanical port would round-trip the residual ``e`` through HBM per column
(2·obs·4 bytes each way) and be memory-latency-bound.  This kernel instead:

  * keeps ``e`` resident in a VMEM scratch buffer for the whole sweep —
    TPU grid steps execute sequentially on a core, so the scratch carries
    across the grid;
  * streams ``x`` through VMEM one (block × obs) tile per grid step — each
    element of ``x`` is read from HBM exactly once per sweep (the optimal
    traffic for this algorithm);
  * consumes the transposed layout (vars, obs) so a paper-"column" is a
    contiguous row: the sequential-update axis lands on sublanes (cheap
    dynamic indexing) and the obs axis lands on the 128-wide lanes (full
    VPU utilisation for the dot/update).

HBM traffic per sweep:  vars·obs·dtype_bytes (reads) + O(vars+obs) —
byte-optimal; arithmetic intensity ≈ 4 flops / dtype_bytes bytes, i.e. the
algorithm is HBM-bandwidth-bound on TPU (819 GB/s v5e ⇒ roofline
~1.6 Tflop/s effective in bf16).  See ``benchmarks/solver_roofline.py``.

The dual kernel ``bakp_sweep_kernel`` is the SolveBakP (Algorithm 2) variant:
identical memory schedule but MXU matvecs instead of the scalar loop.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM of one TPU v5e TensorCore: 128 MiB (Google Cloud, "TPU v5e").  The
# compiler's default *scoped* limit is far below it (16 MiB), so every
# pallas_call states its own limit (``compiler_params``).
VMEM_CAPACITY_BYTES = 128 * 2**20
# Room the compiler needs beyond the buffers the accounting functions count
# (``fused_vmem_bytes``, ``stream_vmem_bytes``, ``sweep_vmem_bytes``):
# the block update's per-chunk scratch (``OBS_CHUNK``), Mosaic's internal
# scratch, semaphores and alignment padding.
VMEM_SLACK_BYTES = 8 * 2**20
# Working set a kernel may be dispatched with: capacity less slack.  The
# compile rehearsal (tests/test_tpu_compile.py) pins this figure to the v5e
# compiler — what the dispatch admits, the compiler accepts.
VMEM_BUDGET_BYTES = VMEM_CAPACITY_BYTES - VMEM_SLACK_BYTES


def vmem_tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) array as Mosaic lays it out: the lane
    axis pads to 128 and the sublane axis to 8 rows of 32-bit words
    (16 for 2-byte types; a single row is tiled (1, 128)) — so a (vars, 1)
    column costs 128× its data."""
    sub = 1 if rows == 1 else 8 * (4 // itemsize)
    return (-(-rows // sub) * sub) * (-(-cols // 128) * 128) * itemsize


def interpret_mode(interpret=None) -> bool:
    """Whether a Pallas kernel runs in interpret mode: an explicit choice
    wins; otherwise interpret exactly on the CPU backend, so an
    accelerator never silently runs the Python interpreter."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"


def compiler_params(vmem_bytes: int) -> pltpu.CompilerParams:
    """Mosaic parameters for a kernel whose accounted working set is
    ``vmem_bytes``: the scoped VMEM limit covers it plus the compiler's
    slack, capped at the chip's capacity."""
    limit = min(int(vmem_bytes) + VMEM_SLACK_BYTES, VMEM_CAPACITY_BYTES)
    return pltpu.CompilerParams(vmem_limit_bytes=limit)


def bak_row_update(xj, inv_j, e):
    """One Algorithm-1 column update on loaded values (shared by the
    per-sweep kernel below AND the fused megakernel — one definition so the
    two execution models cannot drift numerically).

    Args: xj (1, obs) column; inv_j (1, 1) 1/⟨x_j,x_j⟩; e (k, obs).
    Returns (da, e'): (1, k) increment and the corrected residual(s).
    """
    da = lax.dot_general(xj, e, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)      # ⟨x_j, e⟩
    da = da * inv_j
    e = e - lax.dot_general(da, xj, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return da, e


def bak_rows(x_ref, invcn_ref, row0, e_ref, on_da):
    """Algorithm 1 over the rows of the (CB, obs) block ``x_ref``, in
    order: each row's update reads the residual the previous one left in
    ``e_ref``.  Row ``r`` of the block is design row ``row0 + r`` (``row0``
    a multiple of the block's rows): its inverse norm is
    ``invcn_ref[row0 + r]``, and ``on_da(row0 + r, da)`` stores its (1, k)
    increment.

    Rows are staged through an fp32 VMEM buffer one sublane-aligned group
    at a time: Mosaic cannot slice an already-loaded value, and reads a
    packed (bf16) ref only in whole row groups.
    """
    nrows = x_ref.shape[0]
    grp = math.gcd(8 * (4 // x_ref.dtype.itemsize), nrows)

    def staged(xg_ref):
        def group(g, _):
            r0 = pl.multiple_of(g * grp, grp)
            xg_ref[...] = x_ref[pl.ds(r0, grp), :].astype(jnp.float32)
            j0 = pl.multiple_of(row0 + r0, grp)

            def row(t, _):
                da, e = bak_row_update(xg_ref[pl.ds(t, 1), :],
                                       invcn_ref[pl.ds(j0 + t, 1), :],
                                       e_ref[...])
                e_ref[...] = e
                on_da(j0 + t, da)
                return 0

            return lax.fori_loop(0, grp, row, 0)

        lax.fori_loop(0, nrows // grp, group, 0)

    pl.run_scoped(staged, pltpu.VMEM((grp, x_ref.shape[1]), jnp.float32))


def bakp_block_update(xb, inv, e, omega):
    """One Algorithm-2 block update on loaded values (the host block loop,
    ``stream_solve_blocks``; the kernels run ``bakp_block_update_ref``).

    Args: xb (CB, obs) block; inv (CB, 1); e (k, obs); omega relaxation.
    Returns (da, e'): (CB, k) increments and the rank-CB-corrected
    residual(s); both matvecs hit the MXU.
    """
    g = lax.dot_general(xb, e, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)       # (CB, k)
    da = omega * g * inv
    e = e - lax.dot_general(da, xb, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return da, e


# Lanes of obs one block update touches at a time in the kernels.  Dots of
# fp32 operands run at HIGHEST precision (``core.types.fp32_matmuls``: the
# default rounds them to one bfloat16 pass), and Mosaic's six-pass split
# holds several copies of its operands in VMEM — chunking obs bounds that
# scratch by the chunk, not the (block, obs) tile.
OBS_CHUNK = 2048


def bakp_block_update_ref(x_ref, inv, e_ref, omega):
    """``bakp_block_update`` on refs, inside a kernel: ``x_ref`` is the
    (CB, obs) block, ``e_ref`` the (k, obs) residual, updated in place.
    obs is swept in ``OBS_CHUNK``-lane chunks twice — inner products, then
    the residual correction.  Returns da (CB, k).

    Every kernel hands in the block as a whole ref (a grid tile, a scratch
    slot, or a leading-axis view of a (nblocks, CB, obs) design), never a
    row slice of a larger one: the CPU backend compiles a matvec on a
    sliced operand with another summation order, and the execution models
    would then drift apart in the last bit in interpret mode.
    """
    obs = e_ref.shape[1]
    chunk = OBS_CHUNK if obs % OBS_CHUNK == 0 else obs

    def cols(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def inner(c, g):
        xb = x_ref[:, cols(c)].astype(jnp.float32)
        return g + lax.dot_general(xb, e_ref[:, cols(c)],
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    g = lax.fori_loop(0, obs // chunk, inner,
                      jnp.zeros((inv.shape[0], e_ref.shape[0]), jnp.float32))
    da = omega * g * inv

    def correct(c, _):
        xb = x_ref[:, cols(c)].astype(jnp.float32)
        e_ref[:, cols(c)] = e_ref[:, cols(c)] - lax.dot_general(
            da, xb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return 0

    lax.fori_loop(0, obs // chunk, correct, 0)
    return da


def _cd_sweep_kernel(x_ref, invcn_ref, e_in_ref, da_ref, e_out_ref, e_scr):
    """Grid: (nblocks,).  Refs:
    x_ref: (CB, obs) tile of x_t        invcn_ref: (CB, 1)
    e_in_ref/e_out_ref: (k, obs)        da_ref: (CB, k)
    e_scr: VMEM scratch (k, obs) fp32 — the resident residual(s); k ≥ 1
    right-hand sides ride the same stream of x (multi-RHS serving).
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        e_scr[...] = e_in_ref[...].astype(jnp.float32)

    def store_da(r, da):
        da_ref[pl.ds(r, 1), :] = da

    bak_rows(x_ref, invcn_ref, 0, e_scr, store_da)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        e_out_ref[...] = e_scr[...]


def _bakp_sweep_kernel(omega, x_ref, invcn_ref, e_in_ref, da_ref, e_out_ref,
                       e_scr):
    """SolveBakP sweep: Jacobi within the (CB, obs) tile, sequential across
    tiles.  Same refs as ``_cd_sweep_kernel``; the two matvecs hit the MXU.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        e_scr[...] = e_in_ref[...].astype(jnp.float32)

    da_ref[...] = bakp_block_update_ref(x_ref, invcn_ref[...], e_scr, omega)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        e_out_ref[...] = e_scr[...]


def sweep_vmem_bytes(obs: int, nrhs: int, itemsize: int, *,
                     block: int) -> int:
    """VMEM working set of one per-sweep launch (bytes): the grid
    double-buffers the (block, obs) x tile and the (block, 1) norms, and
    the (k, obs) residual is held three times — input, output and the
    resident fp32 scratch."""
    return (2 * vmem_tile_bytes(block, obs, itemsize)
            + 2 * vmem_tile_bytes(block, 1, 4)
            + 3 * vmem_tile_bytes(nrhs, obs, 4)
            + 2 * vmem_tile_bytes(block, nrhs, 4))


def _sweep_call(kernel_fn, x_t, e, inv_cn, *, block, interpret):
    nvars, obs = x_t.shape
    assert nvars % block == 0, (nvars, block)
    single = e.ndim == 1
    e2 = e.reshape(1, obs) if single else e          # (k, obs) kernel layout
    nrhs = e2.shape[0]
    nblocks = nvars // block
    vmem = sweep_vmem_bytes(obs, nrhs, x_t.dtype.itemsize, block=block)
    if vmem > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"cd_sweep working set {vmem/2**20:.1f} MiB exceeds VMEM budget; "
            f"shard obs across devices (repro.core.distributed) or reduce "
            f"block ({block}) / obs ({obs}) / nrhs ({nrhs}).")

    grid = (nblocks,)
    da, e_out = pl.pallas_call(
        kernel_fn,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, obs), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec((nrhs, obs), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, nrhs), lambda i: (i, 0)),
            pl.BlockSpec((nrhs, obs), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nvars, nrhs), jnp.float32),
            jax.ShapeDtypeStruct((nrhs, obs), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((nrhs, obs), jnp.float32)],
        compiler_params=compiler_params(vmem),
        interpret=interpret,
    )(x_t, inv_cn.reshape(nvars, 1).astype(jnp.float32),
      e2.astype(jnp.float32))
    if single:
        return da[:, 0], e_out[0]
    return da, e_out


def cd_sweep(x_t, e, inv_cn, *, block=256, interpret=None):
    """One paper-faithful sequential CD sweep (all columns).  See module doc.

    Args:
      x_t: (vars, obs) transposed input; vars must divide ``block``.
      e: (obs,) residual, or (k, obs) for k right-hand sides sharing the
        single HBM stream of x (multi-RHS serving path).
      inv_cn: (vars,) inverse squared column norms.
      block: rows of x_t staged to VMEM per grid step (multiple of 8).
      interpret: force interpret mode (default: ``interpret_mode``).
    Returns:
      (da, e'): increments and post-sweep residual — (vars,)/(obs,) for 1D
      input, (vars, k)/(k, obs) for multi-RHS.
    """
    return _sweep_call(_cd_sweep_kernel, x_t, e, inv_cn, block=block,
                       interpret=interpret_mode(interpret))


def bakp_sweep(x_t, e, inv_cn, *, block=256, omega=1.0, interpret=None):
    """One SolveBakP (block-Jacobi) sweep; multi-RHS as ``cd_sweep``."""
    return _sweep_call(functools.partial(_bakp_sweep_kernel, omega),
                       x_t, e, inv_cn, block=block,
                       interpret=interpret_mode(interpret))
