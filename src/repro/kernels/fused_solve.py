"""Fused whole-solve Pallas megakernel — the entire SolveBak/SolveBakP
iteration in ONE ``pallas_call``.

The per-sweep kernel path (``repro.kernels.ops.solvebakp_persweep_kernel``)
drives each sweep as a separate ``pallas_call`` from a host-level
``lax.while_loop``: the residual round-trips HBM at every sweep boundary
(2·k·obs·4 bytes each way), convergence is decided off-chip, and every sweep
re-stages its VMEM working set.  This module fuses the whole solve instead:

  * **multi-sweep VMEM residency** — the design ``x_t`` (vars, obs), the
    residual(s) ``e`` (k, obs) and the coefficient accumulator (vars, k) are
    VMEM-resident for the *entire* solve.  ``x`` crosses HBM once per solve,
    not once per sweep — against the per-sweep stream that is an up-to-
    ``n_sweeps``× HBM-traffic reduction, which is everything for a kernel
    whose arithmetic intensity (≈4 flops/byte, see ``cd_sweep``) is far
    below the TPU ridge.
  * **on-chip convergence** — the per-sweep SSE is reduced on-chip and the
    ``sweep_stop_flags`` criterion (``repro.core.types``) is evaluated
    in-kernel; the scalar state (sse/n_sweeps/converged) lives in SMEM
    outputs.  No device→host sync per sweep.
  * **true early exit** — the logical (max_iter, n_col_blocks) grid runs
    *inside* the kernel as a ``while_loop`` over sweeps × ``fori_loop`` over
    column blocks, so post-convergence grid steps are genuinely skipped: no
    compute AND no DMA.  (A hardware 2-D grid cannot abort mid-flight —
    ``pl.when`` guards would still stream every remaining x block — which is
    why the iteration space is in-kernel.)  An early-converging solve costs
    only the sweeps it uses plus the one x load it actually reads.

The kernel accepts precomputed ``inv_cn`` (inverse squared column norms,
computed on the transposed layout — ``PreparedDesign`` caches them) and a
warm-start ``a0``, supports k ≥ 1 right-hand sides sharing the resident x,
and runs both block bodies:

  * ``variant="bakp"`` — Algorithm 2: per-block MXU matvec + rank-block
    residual correction (Jacobi within the block), ``omega`` relaxation.
  * ``variant="bak"``  — Algorithm 1: strictly sequential per-column scalar
    loop inside each block (bit-faithful ordering).

Fit check: whole-x residency needs ``fused_vmem_bytes`` of VMEM — callers
dispatch on ``fused_fits`` and fall back to the per-sweep stream or the XLA
solvers when the design is too large (``repro.core.methods`` wires exactly
that for the ``"bakp_fused"``/``"bak_fused"`` registry entries).

On the CPU backend the kernel runs in interpret mode (the same math, in
float32 on the host), used by the test suite and the CI benchmarks.
"""
from __future__ import annotations

import functools
import importlib
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.types import (SolveResult, column_norms_sq_t, donate_default,
                              fp32_matmuls, safe_inv, sweep_stop_flags)

# The VMEM budget is shared with the per-sweep path.  Import the module via
# importlib: the package re-exports a *function* named cd_sweep, which
# shadows the submodule as a package attribute.
_cd = importlib.import_module("repro.kernels.cd_sweep")


def fused_vmem_bytes(nvars: int, obs: int, nrhs: int, itemsize: int,
                     *, max_iter: int = 1) -> int:
    """VMEM working set of one fused solve (bytes), in Mosaic's padded
    layout (``cd_sweep.vmem_tile_bytes``): x resident (nvars, obs) +
    residual in/out (2 × (k, obs) fp32) + a0/coef (2 × (nvars, k)) +
    inv_cn (nvars, 1) + history (max_iter, 1).  The block update's
    per-chunk scratch and Algorithm 1's row staging buffer are inside
    ``cd_sweep.VMEM_SLACK_BYTES``.
    """
    tile = _cd.vmem_tile_bytes
    return (tile(nvars, obs, itemsize)
            + 2 * tile(nrhs, obs, 4)
            + 2 * tile(nvars, nrhs, 4)
            + tile(nvars, 1, 4)
            + tile(max_iter, 1, 4))


def fused_fits(nvars: int, obs: int, nrhs: int, itemsize: int,
               *, max_iter: int = 1) -> bool:
    """Whether a fused whole-solve fits the VMEM budget.

    Reads ``repro.kernels.cd_sweep.VMEM_BUDGET_BYTES`` at call time (the
    same budget the per-sweep path enforces), so tests and deployments that
    adjust the budget adjust fused dispatch with it.
    """
    return fused_vmem_bytes(nvars, obs, nrhs, itemsize,
                            max_iter=max_iter) <= _cd.VMEM_BUDGET_BYTES


def _fused_kernel(scal_ref, x_ref, invcn_ref, e0_ref, a0_ref,
                  coef_ref, e_ref, hist_ref, sse_ref, n_ref, conv_ref,
                  *, block, max_iter, variant):
    """Whole-solve kernel body.  Refs:

    scal_ref: (3,) SMEM — [atol_sse, rtol, omega] (traced solver knobs,
        scalar-memory so tolerance changes never recompile).
    x_ref: (nblocks, block, obs) VMEM — the resident design, transposed
        layout, one leading index per column block (a free reshape of
        (nvars, obs)).
    invcn_ref: (nvars, 1) VMEM — inverse squared column norms (0 for
        zero/padded columns, so their updates are pinned to 0).
    e0_ref: (k, obs) / a0_ref: (nvars, k) VMEM — initial residual(s) and
        warm-start coefficients.
    coef_ref/e_ref/hist_ref: VMEM outputs, written in place as the solve's
        resident accumulators.  sse/n/conv: (1, 1) SMEM scalar outputs.

    The iteration space is the logical (max_iter, n_col_blocks) grid, run
    as while(sweeps) × fori(blocks) so convergence aborts it outright.
    """
    atol_sse, rtol, omega = scal_ref[0], scal_ref[1], scal_ref[2]
    nblocks = x_ref.shape[0]

    e_ref[...] = e0_ref[...].astype(jnp.float32)
    coef_ref[...] = a0_ref[...]
    hist_ref[...] = jnp.full((max_iter, 1), jnp.nan, jnp.float32)

    def _sse():
        # dot-product reduction: matches the host solvers' jnp.vdot(e, e)
        # bit-for-bit in interpret mode, so fused/unfused stopping decisions
        # agree even at the rtol stall point (n_sweeps parity tests).
        e = e_ref[...]
        ef = e.reshape(1, e.shape[0] * e.shape[1])
        return lax.dot_general(ef, ef, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)[0, 0]

    sse0 = _sse()

    def block_step(b, _):
        # Block math shared with the per-sweep kernels (cd_sweep.py) — one
        # definition keeps the two execution models numerically in lockstep
        # (the n_sweeps/history parity tests depend on it).
        if variant == "bak":
            # Algorithm 1: strictly sequential per column within the block.
            def add_da(r, da):
                coef_ref[pl.ds(r, 1), :] = coef_ref[pl.ds(r, 1), :] + da

            _cd.bak_rows(x_ref.at[b], invcn_ref, b * block, e_ref, add_da)
        else:
            # Algorithm 2: Jacobi within the block, both matvecs on the MXU.
            rows = pl.ds(pl.multiple_of(b * block, block), block)
            da = _cd.bakp_block_update_ref(x_ref.at[b], invcn_ref[rows, :],
                                           e_ref, omega)
            coef_ref[rows, :] = coef_ref[rows, :] + da
        return 0

    def sweep_body(state):
        i, sse_prev, converged, stop = state
        lax.fori_loop(0, nblocks, block_step, 0)
        sse = _sse()
        hist_ref[pl.ds(i, 1), :] = sse.reshape(1, 1)
        # The shared stopping criterion, evaluated on-chip — scalar jnp ops
        # trace fine inside the kernel, so the fused path can never drift
        # from the host solvers' semantics.
        converged, stop = sweep_stop_flags(sse, sse_prev, sse0, atol_sse,
                                           rtol)
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


def _fused_call(x_t, inv_cn, e0, a0m, scal, *, block, max_iter, variant,
                interpret):
    nvars, obs = x_t.shape
    nrhs = e0.shape[0]
    itemsize = x_t.dtype.itemsize
    kern = functools.partial(_fused_kernel, block=block, max_iter=max_iter,
                             variant=variant)
    return pl.pallas_call(
        kern,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
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
            jax.ShapeDtypeStruct((nrhs, obs), jnp.float32),     # residual
            jax.ShapeDtypeStruct((max_iter, 1), jnp.float32),   # history
            jax.ShapeDtypeStruct((1, 1), jnp.float32),          # sse
            jax.ShapeDtypeStruct((1, 1), jnp.int32),            # n_sweeps
            jax.ShapeDtypeStruct((1, 1), jnp.int32),            # converged
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * max_iter * nvars * obs * nrhs,
            bytes_accessed=nvars * obs * itemsize
            + 2 * nrhs * obs * 4 + 2 * nvars * nrhs * 4,
            transcendentals=0,
        ),
        compiler_params=_cd.compiler_params(fused_vmem_bytes(
            nvars, obs, nrhs, itemsize, max_iter=max_iter)),
        interpret=interpret,
    )(scal, x_t.reshape(nvars // block, block, obs), inv_cn, e0, a0m)


def validate_solver_args(x_t, y, cn, inv_cn, a0):
    """Shared shape validation + norm resolution for the kernel solver
    entries (this wrapper AND ops.py's per-sweep/shim wrappers — one
    definition, one set of error messages).  Returns (multi, nrhs, inv_cn),
    with ``cn`` folded into ``inv_cn`` when only the raw norms were given.
    """
    nvars, obs = x_t.shape
    if y.ndim not in (1, 2):
        raise ValueError(f"y must be (obs,) or (obs, k), got {y.shape}")
    multi = y.ndim == 2
    nrhs = y.shape[1] if multi else 1
    if a0 is not None and a0.shape not in ((nvars,), (nvars, nrhs)):
        raise ValueError(
            f"a0 must be ({nvars},) or ({nvars}, {nrhs}) matching x_t rows "
            f"and y RHS count, got {a0.shape}")
    if inv_cn is None and cn is not None:
        inv_cn = safe_inv(cn)
    return multi, nrhs, inv_cn


def solve_init(x_t, y, inv_cn, a0, multi):
    """Shared kernel-solver initialisation (fused AND per-sweep paths):
    resolve the inverse norms, cast ``y`` to the (k, obs) kernel layout and
    build the initial coefficients/residual — ``e0 = y - x @ a0`` computed
    on the transposed layout ((vars,) ``a0`` broadcasts across all RHS,
    paper line 2).  One definition so a future change (dtype handling, the
    broadcast rule) cannot split the two execution models' numerics.

    Returns ``(inv_cn, a0m, e0)`` with a0m (vars, k) and e0 (k, obs), fp32.
    """
    nvars, obs = x_t.shape
    nrhs = y.shape[1] if multi else 1
    if inv_cn is None:
        inv_cn = safe_inv(column_norms_sq_t(x_t))
    y2 = y.reshape(obs, nrhs).astype(jnp.float32)
    if a0 is None:
        a0m = jnp.zeros((nvars, nrhs), jnp.float32)
        e0 = y2.T
    else:
        a0m = jnp.broadcast_to(
            a0.astype(jnp.float32).reshape(nvars, -1), (nvars, nrhs))
        e0 = y2.T - lax.dot_general(a0m, x_t.astype(jnp.float32),
                                    (((0,), (0,)), ((), ())))
    return inv_cn, a0m, e0


@fp32_matmuls
def _fused_impl(x_t, y, inv_cn, a0, atol, rtol, omega, *, block, max_iter,
                variant, multi, interpret):
    nvars, obs = x_t.shape
    nrhs = y.shape[1] if multi else 1
    inv_cn, a0m, e0 = solve_init(x_t, y, inv_cn, a0, multi)
    atol_sse = jnp.float32(obs * nrhs) * jnp.float32(atol) ** 2
    scal = jnp.stack([atol_sse, jnp.float32(rtol), jnp.float32(omega)])
    coef, e, hist, sse, n, conv = _fused_call(
        x_t, inv_cn.reshape(nvars, 1).astype(jnp.float32), e0, a0m, scal,
        block=block, max_iter=max_iter, variant=variant, interpret=interpret)
    converged = conv[0, 0] != 0
    if not multi:
        return SolveResult(coef[:, 0], e[0], sse[0, 0], n[0, 0], converged,
                           hist[:, 0])
    return SolveResult(coef, e.T, sse[0, 0], n[0, 0], converged, hist[:, 0])


@functools.lru_cache(maxsize=None)
def _jitted(block, max_iter, variant, multi, interpret, donate):
    return jax.jit(
        functools.partial(_fused_impl, block=block, max_iter=max_iter,
                          variant=variant, multi=multi, interpret=interpret),
        donate_argnums=(1, 3) if donate else (),   # y, a0
    )


def fused_solve(
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
    variant: str = "bakp",
    interpret: Optional[bool] = None,
    donate: Optional[bool] = None,
) -> SolveResult:
    """Whole-solve fused SolveBak/SolveBakP megakernel (see module doc).

    Args:
      x_t: (vars, obs) TRANSPOSED design (kernel layout); vars must be a
        multiple of ``block``.  Resident in VMEM for the whole solve — use
        ``fused_fits`` to check, or call through ``solvebakp_kernel`` /
        method ``"bakp_fused"`` which fall back automatically.
      y: (obs,) right-hand side, or (obs, k) for k systems sharing the
        resident x (multi-RHS serving path).
      inv_cn / cn: optional precomputed inverse / raw squared column norms
        (vars,) — ``PreparedDesign`` caches these so repeated solves skip
        the norms pass.  ``inv_cn`` wins when both are given; neither →
        computed on the transposed layout (no ``x_t.T`` materialisation).
      a0: optional (vars,) / (vars, k) warm-start coefficients.
      block / max_iter / atol / rtol / omega: as ``solvebakp_kernel``.
      variant: "bakp" (Algorithm 2, MXU) or "bak" (Algorithm 1,
        bit-faithful sequential order).
      interpret: force interpret mode (default: ``cd_sweep.interpret_mode``).
      donate: donate the ``y``/``a0`` buffers to the solve (cuts
        steady-state HBM allocation on the serving flush path).  Default:
        auto-donate only host (numpy) operands, on accelerator backends at
        top level — a ``jax.Array`` you pass is never auto-donated (reuse
        stays safe); force with ``donate=True``.

    Returns:
      ``SolveResult`` exactly as ``solvebakp_kernel`` — multi-RHS gives
      (vars, k) coef / (obs, k) residual with total-SSE accounting.
    """
    nvars, obs = x_t.shape
    if variant not in ("bak", "bakp"):
        raise ValueError(f"unknown variant {variant!r}")
    if nvars % block != 0:
        raise ValueError(
            f"vars ({nvars}) must be a multiple of block ({block}); pad "
            f"columns (PreparedDesign.x_t_for does this)")
    if max_iter < 1:
        raise ValueError(f"max_iter must be >= 1, got {max_iter}")
    multi, nrhs, inv_cn = validate_solver_args(x_t, y, cn, inv_cn, a0)
    vmem = fused_vmem_bytes(nvars, obs, nrhs, x_t.dtype.itemsize,
                            max_iter=max_iter)
    if vmem > _cd.VMEM_BUDGET_BYTES:
        raise ValueError(
            f"fused_solve working set {vmem / 2**20:.1f} MiB exceeds VMEM "
            f"budget ({_cd.VMEM_BUDGET_BYTES / 2**20:.0f} MiB); use the "
            f"per-sweep stream (solvebakp_persweep_kernel), shard obs "
            f"across devices (repro.core.distributed), or reduce "
            f"obs ({obs}) / vars ({nvars}) / nrhs ({nrhs}).")
    fn = _jitted(block, max_iter, variant, multi,
                 _cd.interpret_mode(interpret), donate_default(donate, y, a0))
    return fn(x_t, y, inv_cn, a0, atol, rtol, omega)
