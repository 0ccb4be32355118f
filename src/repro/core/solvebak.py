"""SolveBak — Algorithm 1 of the paper, bit-faithful serial coordinate descent.

For each column ``j`` (cyclically, or in a fresh random order per sweep):

    da   = ⟨x_j, e⟩ / ⟨x_j, x_j⟩
    e   ←  e - x_j * da
    a_j ←  a_j + da

One sweep costs O(obs * vars) flops and touches each element of ``x`` exactly
once; auxiliary memory is O(obs + vars).  This module is the *paper-faithful
baseline*: the TPU-optimised variants live in ``solvebakp.py`` (block CD),
``gram_cd.py`` via ``solvebakp(mode="gram")``, and ``repro.kernels``.

All inner products accumulate in fp32 regardless of the storage dtype of
``x``/``y`` (the paper runs Float32 end-to-end; we additionally support bf16
storage for TPU and validate MAPE against the fp32 oracle in tests).

Multi-RHS: ``y`` may be ``(obs, k)`` — the same single pass over ``x`` then
serves ``k`` right-hand sides at once (``da`` becomes a ``(k,)`` row per
column), amortising the HBM stream of ``x`` over all of them.  This is the
core primitive behind ``repro.serve``'s same-design request coalescing.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.types import (SolveResult, column_norms_sq, donate_default,
                              fp32_matmuls, safe_inv, sweep_stop_flags)


@fp32_matmuls
def _solvebak_impl(
    x: jax.Array,
    y: jax.Array,
    a0: Optional[jax.Array],
    cn: Optional[jax.Array],
    key: Optional[jax.Array],
    atol,
    rtol,
    *,
    max_iter: int,
    order: str,
    unroll: int,
) -> SolveResult:
    if x.ndim != 2:
        raise ValueError(f"x must be 2D (obs, vars), got {x.shape}")
    if y.ndim not in (1, 2):
        raise ValueError(f"y must be (obs,) or (obs, k), got {y.shape}")
    obs, nvars = x.shape
    if order not in ("cyclic", "random"):
        raise ValueError(f"unknown order {order!r}")
    if order == "random" and key is None:
        raise ValueError("order='random' requires a PRNG key")
    if key is None:
        key = jax.random.PRNGKey(0)

    multi = y.ndim == 2
    nrhs = y.shape[1] if multi else 1
    y2 = y.reshape(obs, nrhs)
    if a0 is not None and a0.shape not in ((nvars,), (nvars, nrhs)):
        raise ValueError(
            f"a0 must be ({nvars},) or ({nvars}, {nrhs}) matching x columns "
            f"and y RHS count, got {a0.shape}")

    if cn is None:
        cn = column_norms_sq(x)
    inv_cn = safe_inv(cn)

    if a0 is None:
        a = jnp.zeros((nvars, nrhs), jnp.float32)
    else:  # (vars,) broadcasts across all right-hand sides
        a = jnp.broadcast_to(
            a0.astype(jnp.float32).reshape(nvars, -1), (nvars, nrhs))
    e0 = y2.astype(jnp.float32) - x.astype(jnp.float32) @ a  # paper line 2
    sse0 = jnp.vdot(e0, e0)
    history0 = jnp.full((max_iter,), jnp.nan, jnp.float32)

    atol_sse = jnp.float32(obs * nrhs) * jnp.float32(atol) ** 2

    def column_step(i, carry, perm):
        a, e = carry
        j = perm[i]
        xj = lax.dynamic_slice_in_dim(x, j, 1, axis=1)[:, 0].astype(jnp.float32)
        da = (xj @ e) * inv_cn[j]            # (k,)
        e = e - xj[:, None] * da[None, :]
        a = lax.dynamic_update_slice_in_dim(
            a, lax.dynamic_slice_in_dim(a, j, 1, axis=0) + da[None, :], j,
            axis=0)
        return a, e

    def sweep_body(state):
        a, e, i, sse_prev, history, converged, stop = state
        if order == "random":  # static: resolved at trace time
            perm = jax.random.permutation(jax.random.fold_in(key, i), nvars)
        else:
            perm = jnp.arange(nvars)
        a, e = lax.fori_loop(
            0, nvars, functools.partial(column_step, perm=perm), (a, e),
            unroll=unroll,
        )
        sse = jnp.vdot(e, e)
        history = history.at[i].set(sse)
        converged, stop = sweep_stop_flags(sse, sse_prev, sse0, atol_sse,
                                           rtol)
        return a, e, i + 1, sse, history, converged, stop

    def cond(state):
        _, _, i, _, _, _, stop = state
        return (i < max_iter) & ~stop

    a, e, n, sse, history, converged, _ = lax.while_loop(
        cond, sweep_body,
        (a, e0, jnp.int32(0), sse0, history0, jnp.bool_(False),
         jnp.bool_(False))
    )
    if not multi:
        a, e = a[:, 0], e[:, 0]
    return SolveResult(a, e, sse, n, converged, history)


@functools.lru_cache(maxsize=None)
def _jitted_solvebak(max_iter, order, unroll, donate):
    return jax.jit(
        functools.partial(_solvebak_impl, max_iter=max_iter, order=order,
                          unroll=unroll),
        donate_argnums=(1, 2) if donate else (),   # y, a0
    )


def solvebak(
    x: jax.Array,
    y: jax.Array,
    *,
    max_iter: int = 50,
    atol: float = 0.0,
    rtol: float = 0.0,
    a0: Optional[jax.Array] = None,
    order: str = "cyclic",
    key: Optional[jax.Array] = None,
    unroll: int = 1,
    cn: Optional[jax.Array] = None,
    donate: Optional[bool] = None,
) -> SolveResult:
    """Algorithm 1 (SolveBak).

    Args:
      x: (obs, vars) input matrix (any float dtype; fp32 accumulation).
      y: (obs,) right-hand side, or (obs, k) for k right-hand sides solved
        in one pass (multi-RHS; see module doc).
      max_iter: maximum number of full sweeps over all columns.
      atol: absolute tolerance on the *RMSE*; converged when
        ``sse <= obs * atol**2`` (multi-RHS: total SSE vs ``obs*k*atol²``).
        ``0`` disables.
      rtol: relative per-sweep improvement tolerance; converged when
        ``(sse_prev - sse) <= rtol * sse_prev``.  ``0`` disables.
      a0: optional (vars,) / (vars, k) initial guess (paper line 1: zeros);
        a (vars,) guess with multi-RHS ``y`` broadcasts across all k.
      order: "cyclic" (paper Algorithm 1) or "random" (paper §2, randomly
        selected indices; requires ``key``).
      key: PRNG key for ``order="random"``.
      unroll: unroll factor for the inner column loop (compile-time knob).
      cn: optional precomputed squared column norms ``⟨x_j,x_j⟩`` (vars,) —
        lets ``repro.serve``'s design cache skip the norms pass on repeated
        design matrices.
      donate: donate the ``y``/``a0`` buffers to the solve — cuts
        steady-state HBM allocation on the serving flush path (which hands
        in fresh host buffers every batch).  Default: on for accelerator
        backends at top level when ``y``/``a0`` are HOST (numpy) buffers —
        a ``jax.Array`` you pass is never auto-donated, so reuse stays
        safe; force with ``donate=True`` for device buffers you own.

    Returns:
      SolveResult.  ``history[i]`` is the SSE after sweep ``i``; for
      multi-RHS input ``coef``/``residual`` are (vars, k)/(obs, k) and
      ``sse`` is the total over all k systems.
    """
    fn = _jitted_solvebak(int(max_iter), order, int(unroll),
                          donate_default(donate, y, a0))
    return fn(x, y, a0, cn, key, atol, rtol)


@fp32_matmuls
def solvebak_onesweep(x: jax.Array, y: jax.Array, a: jax.Array, e: jax.Array):
    """A single cyclic sweep (used by the Pallas-kernel reference tests).

    Returns (a', e') after one pass over all columns, exactly the inner loop
    of Algorithm 1.
    """
    inv_cn = safe_inv(column_norms_sq(x))

    def column_step(j, carry):
        a, e = carry
        xj = lax.dynamic_slice_in_dim(x, j, 1, axis=1)[:, 0].astype(jnp.float32)
        da = jnp.vdot(xj, e) * inv_cn[j]
        return a.at[j].add(da), e - xj * da

    return lax.fori_loop(0, x.shape[1], column_step, (a, e))
