"""jit'd public wrappers around the Pallas kernels.

``solvebakp_kernel`` is the TPU production entry for the paper's solver: it
dispatches the whole solve to the fused megakernel
(``repro.kernels.fused_solve`` — x/residual/coefficients VMEM-resident
across all sweeps, convergence decided on-chip, true early exit) whenever
the design fits the VMEM budget, and falls back to the original per-sweep
launch loop (``solvebakp_persweep_kernel`` — residual streamed back to HBM
at each sweep boundary, convergence decided off-chip) when it does not.
The per-sweep loop also remains the benchmark baseline
(``benchmarks.solver_roofline``).

Buffer donation: the jitted solver entries donate their ``y``/``a0``
operands on accelerator backends when those operands are HOST (numpy)
buffers — their in-jit device transfer is fresh, so donation is safe by
construction, and the serving flush path (which hands in host buffers
every batch) gets its steady-state HBM allocation cut.  ``jax.Array``
operands are never auto-donated (callers may reuse them); ``donate=True``
forces it, ``donate=False`` disables it.

On the CPU backend all kernels run in interpret mode (Python execution of
the kernel body; ``cd_sweep.interpret_mode``) — numerically identical,
used by the test suite.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.types import (SolveResult, column_norms_sq_t, donate_default,
                              fp32_matmuls, safe_inv, sweep_stop_flags)
from repro.obs import record_dispatch
from repro.kernels.block_update import block_update, score_features
from repro.kernels.cd_sweep import bakp_sweep, cd_sweep, interpret_mode
from repro.kernels.fused_solve import (fused_fits, fused_solve, solve_init,
                                       validate_solver_args)
from repro.kernels.stream_solve import stream_fits, stream_solve


@fp32_matmuls
def _persweep_impl(x_t, y, inv_cn, a0, atol, rtol, *, block, max_iter,
                   variant, multi, interpret, omega):
    # omega is compile-time here: the sweep kernels close over it (a traced
    # scalar cannot be captured by a pallas kernel body); the fused path
    # keeps it traced via its SMEM scalar input.
    nvars, obs = x_t.shape
    nrhs = y.shape[1] if multi else 1
    sweep = cd_sweep if variant == "bak" else functools.partial(
        bakp_sweep, omega=omega)
    inv_cn, a, e0 = solve_init(x_t, y, inv_cn, a0, multi)
    sse0 = jnp.vdot(e0, e0)
    history0 = jnp.full((max_iter,), jnp.nan, jnp.float32)
    atol_sse = jnp.float32(obs * nrhs) * jnp.float32(atol) ** 2

    def body(state):
        a, e, i, sse_prev, history, converged, stop = state
        da, e = sweep(x_t, e, inv_cn, block=block, interpret=interpret)
        a = a + da
        sse = jnp.vdot(e, e)
        history = history.at[i].set(sse)
        converged, stop = sweep_stop_flags(sse, sse_prev, sse0, atol_sse,
                                           rtol)
        return a, e, i + 1, sse, history, converged, stop

    def cond(state):
        _, _, i, _, _, _, stop = state
        return (i < max_iter) & ~stop

    a, e, n, sse, history, converged, _ = lax.while_loop(
        cond, body, (a, e0, jnp.int32(0), sse0, history0, jnp.bool_(False),
                     jnp.bool_(False)))
    if not multi:
        return SolveResult(a[:, 0], e[0], sse, n, converged, history)
    return SolveResult(a, e.T, sse, n, converged, history)


@functools.lru_cache(maxsize=None)
def _jitted_persweep(block, max_iter, variant, multi, interpret, donate,
                     omega):
    return jax.jit(
        functools.partial(_persweep_impl, block=block, max_iter=max_iter,
                          variant=variant, multi=multi, interpret=interpret,
                          omega=omega),
        donate_argnums=(1, 3) if donate else (),   # y, a0
    )


def solvebakp_persweep_kernel(
    x_t: jax.Array,
    y: jax.Array,
    *,
    cn: Optional[jax.Array] = None,
    inv_cn: Optional[jax.Array] = None,
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
    """Per-sweep-launch SolveBak/SolveBakP: one ``pallas_call`` per sweep
    driven by a host-level ``lax.while_loop`` (the pre-fusion execution
    model — kept as the large-design fallback and benchmark baseline; see
    module doc).  Arguments as ``solvebakp_kernel``.
    """
    multi, _, inv_cn = validate_solver_args(x_t, y, cn, inv_cn, a0)
    fn = _jitted_persweep(block, max_iter, variant, multi,
                          interpret_mode(interpret),
                          donate_default(donate, y, a0), float(omega))
    return fn(x_t, y, inv_cn, a0, atol, rtol)


def solvebakp_kernel(
    x_t: jax.Array,
    y: jax.Array,
    *,
    cn: Optional[jax.Array] = None,
    inv_cn: Optional[jax.Array] = None,
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
    """Kernel-accelerated SolveBak/SolveBakP.

    Dispatch: the fused whole-solve megakernel when the design fits VMEM
    (``fused_fits``), else the per-sweep launch loop — same results either
    way, different execution models (see module doc).

    Args:
      x_t: (vars, obs) TRANSPOSED input matrix (kernel layout; see
        repro.kernels.ref docstring).  vars must be a multiple of ``block``.
      y: (obs,) right-hand side, or (obs, k) for k right-hand sides sharing
        one stream of x per sweep (multi-RHS serving path).
      cn / inv_cn: optional precomputed (inverse) squared column norms
        (vars,) — lets ``PreparedDesign`` reuse its cached norms instead of
        recomputing them every solve.  Neither given → computed on the
        transposed layout directly (no ``x_t.T`` materialisation).
      a0: optional (vars,) / (vars, k) warm-start coefficients.
      variant: "bakp" (Algorithm 2 sweeps, MXU) or "bak" (Algorithm 1
        sequential sweeps, bit-faithful).
      donate: buffer donation for ``y``/``a0`` (see module doc).

    Returns:
      SolveResult; multi-RHS input gives (vars, k) coef and (obs, k)
      residual with total-SSE convergence accounting.
    """
    nvars, obs = x_t.shape
    _, nrhs, inv_cn = validate_solver_args(x_t, y, cn, inv_cn, a0)
    if (max_iter >= 1
            and fused_fits(nvars, obs, nrhs, x_t.dtype.itemsize,
                           max_iter=max_iter)):
        # This dispatch decision runs eagerly on every call (jit lives
        # inside fused_solve), so the relay reports the path each solve
        # actually took — the engine pops it via obs.consume_dispatch().
        record_dispatch("fused", method=variant)
        return fused_solve(x_t, y, cn=cn, inv_cn=inv_cn, a0=a0, block=block,
                           max_iter=max_iter, atol=atol, rtol=rtol,
                           omega=omega, variant=variant, interpret=interpret,
                           donate=donate)
    reason = "max_iter" if max_iter < 1 else "vmem"
    record_dispatch("persweep", method=variant, reason=reason)
    return solvebakp_persweep_kernel(
        x_t, y, cn=cn, inv_cn=inv_cn, a0=a0, block=block, max_iter=max_iter,
        atol=atol, rtol=rtol, omega=omega, variant=variant,
        interpret=interpret, donate=donate)


def solvebakp_stream_kernel(
    x_t: jax.Array,
    y: jax.Array,
    *,
    cn: Optional[jax.Array] = None,
    inv_cn: Optional[jax.Array] = None,
    a0: Optional[jax.Array] = None,
    block: int = 256,
    max_iter: int = 50,
    atol: float = 0.0,
    rtol: float = 0.0,
    omega: float = 1.0,
    interpret: Optional[bool] = None,
    donate: Optional[bool] = None,
) -> SolveResult:
    """Streaming out-of-core SolveBakP: x stays in HBM (``pl.ANY``) and
    tiles double-buffer through a two-slot VMEM scratch while the residual,
    coefficients and convergence state stay on-chip for every sweep
    (``repro.kernels.stream_solve``).  The VMEM working set is two
    (block, obs) x tiles plus the accumulators — independent of vars — so
    designs far over the whole-solve budget keep the fused kernel's
    single-launch, early-exit execution model.  Arguments as
    ``solvebakp_kernel``; falls back to the per-sweep launch loop when even
    the two-tile scratch exceeds the VMEM budget or ``max_iter < 1``.
    """
    nvars, obs = x_t.shape
    _, nrhs, inv_cn = validate_solver_args(x_t, y, cn, inv_cn, a0)
    if (max_iter >= 1
            and stream_fits(nvars, obs, nrhs, x_t.dtype.itemsize,
                            block=block, max_iter=max_iter)):
        record_dispatch("stream", method="bakp")
        return stream_solve(x_t, y, inv_cn=inv_cn, a0=a0, block=block,
                            max_iter=max_iter, atol=atol, rtol=rtol,
                            omega=omega, interpret=interpret, donate=donate)
    reason = "max_iter" if max_iter < 1 else "vmem"
    record_dispatch("persweep", method="bakp", reason=reason)
    return solvebakp_persweep_kernel(
        x_t, y, inv_cn=inv_cn, a0=a0, block=block, max_iter=max_iter,
        atol=atol, rtol=rtol, omega=omega, variant="bakp",
        interpret=interpret, donate=donate)


@functools.partial(jax.jit, static_argnames=("col_block", "obs_tile",
                                             "interpret"))
def score_features_kernel(x_t, e, *, col_block=512, obs_tile=4096,
                          interpret=None):
    """Fused SolveBakF feature scoring (see block_update.score_features)."""
    inv_cn = safe_inv(column_norms_sq_t(x_t))
    return score_features(x_t, e, inv_cn, col_block=col_block,
                          obs_tile=obs_tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("obs_tile", "interpret"))
def block_update_kernel(x_t_blk, e, da, *, obs_tile=4096, interpret=None):
    """Fused rank-CB residual correction (paper Alg. 2 line 9)."""
    return block_update(x_t_blk, e, da, obs_tile=obs_tile,
                        interpret=interpret)
