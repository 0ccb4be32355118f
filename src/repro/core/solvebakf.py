"""SolveBakF — Algorithm 3 of the paper: greedy forward feature selection.

Each step scores *every* feature by the SSE reduction a single CD step on it
would achieve.  With ``da_j = ⟨x_j, e⟩ / ⟨x_j, x_j⟩`` the post-step SSE is

    ||e - x_j da_j||² = ||e||² - ⟨x_j, e⟩² / ⟨x_j, x_j⟩,

so ``argmin_j e_j`` (paper line 5) is ``argmax_j ⟨x_j, e⟩² / ⟨x_j, x_j⟩``.
The scoring of all features is one (vars × obs)·(obs,) matvec — the paper's
"line 3 can be easily vectorised by using basic BLAS functions" — which on TPU
is a single MXU pass over ``x``.

After adding the winning feature we *refit* the coefficients on the selected
set (paper line 7) — here with the BAK solver itself (``solvebakp``) on the
gathered submatrix, which keeps the whole pipeline paper-native.

The jit-friendly formulation keeps fixed shapes: ``selected`` is a
(max_feat,) index buffer and the refit matrix is a (obs, max_feat) gather
with zero columns for not-yet-selected slots (zero columns are inert for the
solver: ``safe_inv`` gives da = 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.solvebakp import solvebakp
from repro.core.types import (SelectResult, column_norms_sq, fp32_matmuls,
                              safe_inv)


@functools.partial(
    jax.jit, static_argnames=("max_feat", "refit_sweeps", "refit_thr")
)
@fp32_matmuls
def solvebakf(
    x: jax.Array,
    y: jax.Array,
    *,
    max_feat: int,
    refit_sweeps: int = 8,
    refit_thr: int = 16,
) -> SelectResult:
    """Algorithm 3 (SolveBakF).

    Args:
      x: (obs, vars) feature matrix.
      y: (obs,) target.
      max_feat: number of features to select (paper's ``max_feat``).
      refit_sweeps: CD sweeps for the per-step refit on the selected set.
      refit_thr: block width for the refit solver.

    Returns:
      SelectResult with selection order, refit coefficients and the SSE path.
    """
    obs, nvars = x.shape
    xf32 = x.astype(jnp.float32)
    inv_cn = safe_inv(column_norms_sq(x))

    e0 = y.astype(jnp.float32)
    selected0 = jnp.full((max_feat,), -1, jnp.int32)
    coef0 = jnp.zeros((max_feat,), jnp.float32)
    sse0 = jnp.full((max_feat,), jnp.nan, jnp.float32)
    taken0 = jnp.zeros((nvars,), jnp.bool_)

    def step(carry, f):
        e, selected, coef, sse_path, taken = carry
        # Score all features in one matvec (paper line 3, vectorised).
        g = xf32.T @ e  # ⟨x_j, e⟩ for all j
        reduction = g * g * inv_cn
        reduction = jnp.where(taken, -jnp.inf, reduction)
        jhat = jnp.argmax(reduction)
        selected = selected.at[f].set(jhat.astype(jnp.int32))
        taken = taken.at[jhat].set(True)

        # Refit on the selected set (paper line 7) with the BAK solver.
        # Gather → (obs, max_feat); unselected slots are zero columns.
        sel_mask = jnp.arange(max_feat) <= f
        gather_idx = jnp.where(sel_mask, jnp.clip(selected, 0, nvars - 1), 0)
        x_sel = jnp.take(xf32, gather_idx, axis=1) * sel_mask[None, :]
        res = solvebakp(
            x_sel, y.astype(jnp.float32),
            thr=refit_thr, max_iter=refit_sweeps, mode="gram", a0=coef,
        )
        coef = res.coef
        e = res.residual
        sse_path = sse_path.at[f].set(res.sse)
        return (e, selected, coef, sse_path, taken), None

    (e, selected, coef, sse_path, _), _ = lax.scan(
        step, (e0, selected0, coef0, sse0, taken0), jnp.arange(max_feat)
    )
    return SelectResult(selected, coef, sse_path, e)


def stepwise_regression_baseline(
    x: jax.Array, y: jax.Array, *, max_feat: int
) -> SelectResult:
    """The paper's comparison baseline (Fig 2): classical stepwise (forward)
    regression — at each step, trial-fit OLS on (selected + candidate) for
    every candidate and keep the best.  O(vars) full least-squares solves per
    step, versus SolveBakF's single matvec — this is the gap Fig 2 plots.

    Implemented with normal-equation Cholesky solves on the gathered
    submatrix, vmapped over candidates.
    """
    obs, nvars = x.shape
    xf32 = x.astype(jnp.float32)
    yf32 = y.astype(jnp.float32)

    selected0 = jnp.full((max_feat,), -1, jnp.int32)
    sse0 = jnp.full((max_feat,), jnp.nan, jnp.float32)
    taken0 = jnp.zeros((nvars,), jnp.bool_)

    def trial_sse(gather_idx, col_mask):
        # OLS on masked columns via ridge-stabilised normal equations.
        xs = jnp.take(xf32, gather_idx, axis=1) * col_mask[None, :]
        g = xs.T @ xs + 1e-5 * jnp.eye(xs.shape[1], dtype=jnp.float32)
        b = xs.T @ yf32
        coef = jnp.linalg.solve(g, b) * col_mask
        r = yf32 - xs @ coef
        return jnp.vdot(r, r), coef

    def step(carry, f):
        selected, sse_path, taken = carry
        sel_mask = jnp.arange(max_feat) < f

        def candidate_sse(j):
            cand_sel = selected.at[f].set(j)
            cand_mask = sel_mask.at[f].set(True)
            idx = jnp.where(cand_mask, jnp.clip(cand_sel, 0, nvars - 1), 0)
            sse, _ = trial_sse(idx, cand_mask.astype(jnp.float32))
            return jnp.where(taken[j], jnp.inf, sse)

        sses = jax.vmap(candidate_sse)(jnp.arange(nvars))
        jhat = jnp.argmin(sses).astype(jnp.int32)
        selected = selected.at[f].set(jhat)
        taken = taken.at[jhat].set(True)
        sse_path = sse_path.at[f].set(sses[jhat])
        return (selected, sse_path, taken), None

    (selected, sse_path, _), _ = lax.scan(
        step, (selected0, sse0, taken0), jnp.arange(max_feat)
    )
    final_mask = (selected >= 0).astype(jnp.float32)
    idx = jnp.where(selected >= 0, jnp.clip(selected, 0, nvars - 1), 0)
    _, coef = trial_sse(idx, final_mask)
    xs = jnp.take(xf32, idx, axis=1) * final_mask[None, :]
    residual = yf32 - xs @ coef
    return SelectResult(selected, coef, sse_path, residual)
