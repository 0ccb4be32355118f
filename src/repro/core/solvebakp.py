"""SolveBakP — Algorithm 2 of the paper (block-parallel CD) + Gram-block upgrade.

The paper parallelises Algorithm 1 by processing ``thr`` columns at a time:
the per-column steps ``da_k = ⟨x_k, e⟩ / ⟨x_k, x_k⟩`` inside a block all read
the *same* residual (Jacobi-within-block), then the residual is corrected once
per block with a rank-``thr`` update

    e ← e - x_blk @ (a_blk - aprev_blk).

On TPU the block update is an MXU matmul and the per-block inner products are
a single (thr × obs)·(obs,) matvec, so this variant is the natural TPU
formulation of the paper's multi-thread loop (DESIGN.md §3).

``mode="jacobi"`` is the paper-faithful Algorithm 2.

``mode="gram"`` is a *beyond-paper* upgrade (recorded separately in
EXPERIMENTS.md §Perf): solve the thr×thr block normal equations exactly,

    da = (x_blkᵀ x_blk + ridge·I)⁻¹ x_blkᵀ e,

i.e. exact block Gauss–Seidel.  The Cholesky factors of all block Gram
matrices are computed once (O(obs·vars·thr) flops, amortised over sweeps) so
the per-sweep cost stays O(obs·vars) like the paper's variant, but each sweep
makes strictly more progress: within-block correlations no longer slow
convergence, and ``thr`` can be as large as VMEM allows instead of the paper's
"small with respect to vars" requirement.

``omega`` is an optional over/under-relaxation factor (beyond-paper; 1.0 is
faithful).  Jacobi-within-block can diverge when columns inside a block are
strongly correlated — the paper's remedy is small ``thr``; ours is ``omega<1``
or ``mode="gram"``.

Multi-RHS: ``y`` may be ``(obs, k)`` — the per-block inner products become a
(thr × obs)·(obs × k) matmul and the residual correction a rank-``thr``
update of a (obs, k) residual, so one stream of ``x`` (and one block-Gram
factorisation in ``mode="gram"``) serves all k systems.  This is the core
primitive behind ``repro.serve``'s same-design request coalescing.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.types import (SolveResult, column_norms_sq, donate_default,
                              fp32_matmuls, safe_inv, sweep_stop_flags)


def _pad_cols(x: jax.Array, thr: int):
    """Zero-pad columns of x to a multiple of thr. Returns (x_pad, mask)."""
    obs, nvars = x.shape
    nblocks = -(-nvars // thr)
    pad = nblocks * thr - nvars
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    mask = (jnp.arange(nblocks * thr) < nvars).astype(jnp.float32)
    return x, mask, nblocks


@fp32_matmuls
def block_gram_cholesky(xb: jax.Array, ridge: float) -> jax.Array:
    """Cholesky factors of per-block Gram matrices.

    Args:
      xb: (obs, nblocks, thr) blocked view of the (padded) input matrix.
      ridge: Tikhonov term added to the diagonal; also makes padded (zero)
        columns well-posed.
    Returns:
      (nblocks, thr, thr) lower Cholesky factors in fp32.
    """
    xf = xb.astype(jnp.float32)
    gram = jnp.einsum("obt,obs->bts", xf, xf)
    thr = xb.shape[-1]
    gram = gram + ridge * jnp.eye(thr, dtype=jnp.float32)[None]
    return jax.vmap(lambda g: jax.scipy.linalg.cholesky(g, lower=True))(gram)


@fp32_matmuls
def _solvebakp_impl(
    x: jax.Array,
    y: jax.Array,
    a0: Optional[jax.Array],
    cn: Optional[jax.Array],
    chol: Optional[jax.Array],
    atol,
    rtol,
    omega,
    ridge,
    *,
    thr: int,
    max_iter: int,
    mode: str,
) -> SolveResult:
    obs, nvars = x.shape
    if y.ndim not in (1, 2):
        raise ValueError(f"y must be (obs,) or (obs, k), got {y.shape}")
    multi = y.ndim == 2
    nrhs = y.shape[1] if multi else 1
    y2 = y.reshape(obs, nrhs)
    if a0 is not None and a0.shape not in ((nvars,), (nvars, nrhs)):
        raise ValueError(
            f"a0 must be ({nvars},) or ({nvars}, {nrhs}) matching x columns "
            f"and y RHS count, got {a0.shape}")
    x_pad, mask, nblocks = _pad_cols(x, thr)

    if cn is None:
        cn = column_norms_sq(x_pad)
    inv_cn = (safe_inv(cn) * mask).reshape(nblocks, thr)
    mask_b = mask.reshape(nblocks, thr)

    if mode == "gram":
        if chol is None:
            chol = block_gram_cholesky(x_pad.reshape(obs, nblocks, thr),
                                       ridge)
    elif mode == "jacobi":
        chol = None
    else:
        raise ValueError(f"unknown mode {mode!r}")

    a = jnp.zeros((nblocks * thr, nrhs), jnp.float32)
    if a0 is not None:  # (vars,) broadcasts across all right-hand sides
        a = a.at[:nvars].set(jnp.broadcast_to(
            a0.astype(jnp.float32).reshape(nvars, -1), (nvars, nrhs)))
    e0 = y2.astype(jnp.float32) - x_pad.astype(jnp.float32) @ a
    sse0 = jnp.vdot(e0, e0)
    history0 = jnp.full((max_iter,), jnp.nan, jnp.float32)
    atol_sse = jnp.float32(obs * nrhs) * jnp.float32(atol) ** 2
    ab0 = a.reshape(nblocks, thr, nrhs)

    def block_step(carry, b):
        ab, e = carry
        # Column block b read where it lies in x: whole (8, 128) tiles when
        # thr is a multiple of 128, so no blocked copy of x is made.
        xblk = lax.dynamic_slice_in_dim(x_pad, b * thr, thr, axis=1)
        xblk = xblk.astype(jnp.float32)  # (obs, thr)
        g = xblk.T @ e  # (thr, k)  ⟨x_k, e⟩ for all k in block, all RHS
        if mode == "jacobi":
            da = g * inv_cn[b][:, None]
        else:
            lb = lax.dynamic_index_in_dim(chol, b, axis=0, keepdims=False)
            da = jax.scipy.linalg.cho_solve((lb, True), g) * mask_b[b][:, None]
        da = omega * da
        e = e - xblk @ da  # paper line 9 (rank-thr residual correction)
        ab = lax.dynamic_update_index_in_dim(ab, ab[b] + da, b, axis=0)
        return (ab, e), None

    def sweep_body(state):
        ab, e, i, sse_prev, history, converged, stop = state
        (ab, e), _ = lax.scan(block_step, (ab, e), jnp.arange(nblocks))
        sse = jnp.vdot(e, e)
        history = history.at[i].set(sse)
        converged, stop = sweep_stop_flags(sse, sse_prev, sse0, atol_sse,
                                           rtol)
        return ab, e, i + 1, sse, history, converged, stop

    def cond(state):
        _, _, i, _, _, _, stop = state
        return (i < max_iter) & ~stop

    ab, e, n, sse, history, converged, _ = lax.while_loop(
        cond, sweep_body,
        (ab0, e0, jnp.int32(0), sse0, history0, jnp.bool_(False),
         jnp.bool_(False))
    )
    coef = ab.reshape(nblocks * thr, nrhs)[:nvars]
    if not multi:
        coef, e = coef[:, 0], e[:, 0]
    return SolveResult(coef, e, sse, n, converged, history)


@functools.lru_cache(maxsize=None)
def _jitted_solvebakp(thr, max_iter, mode, donate):
    return jax.jit(
        functools.partial(_solvebakp_impl, thr=thr, max_iter=max_iter,
                          mode=mode),
        donate_argnums=(1, 2) if donate else (),   # y, a0
    )


def solvebakp(
    x: jax.Array,
    y: jax.Array,
    *,
    thr: int = 128,
    max_iter: int = 50,
    atol: float = 0.0,
    rtol: float = 0.0,
    omega: float = 1.0,
    mode: str = "jacobi",
    ridge: float = 1e-6,
    a0: Optional[jax.Array] = None,
    cn: Optional[jax.Array] = None,
    chol: Optional[jax.Array] = None,
    donate: Optional[bool] = None,
) -> SolveResult:
    """Algorithm 2 (SolveBakP), blocked over ``thr`` columns.

    Args:
      x: (obs, vars) input matrix.
      y: (obs,) right-hand side, or (obs, k) for k right-hand sides solved
        in one pass over ``x`` (multi-RHS; see module doc).
      thr: block width (the paper's thread-count parameter).  Multiples of
        128 line up with TPU lanes/MXU tiles.
      max_iter / atol / rtol: as in ``solvebak``.
      omega: relaxation factor applied to every block update (1.0 = paper).
      mode: "jacobi" (paper Algorithm 2) or "gram" (exact block CD).
      ridge: diagonal regulariser for mode="gram".
      a0: optional initial coefficients, (vars,) or (vars, k); a (vars,)
        guess with multi-RHS ``y`` broadcasts across all k.
      cn: optional precomputed squared column norms of the *padded* matrix,
        shape (nblocks*thr,) — see ``repro.serve.cache``.
      chol: optional precomputed ``block_gram_cholesky(xb, ridge)`` factors,
        shape (nblocks, thr, thr); only used for mode="gram".  Repeated-X
        serving amortises this O(obs·vars·thr) factorisation across requests.
      donate: donate the ``y``/``a0`` buffers to the solve (cuts
        steady-state HBM allocation on the serving flush path).  Default:
        auto-donate only host (numpy) operands on accelerator backends at
        top level; see ``solvebak``.

    Returns:
      SolveResult (coef truncated back to the unpadded ``vars``); multi-RHS
      input gives (vars, k) coef, (obs, k) residual and total-SSE scalars.
    """
    fn = _jitted_solvebakp(int(thr), int(max_iter), mode,
                           donate_default(donate, y, a0))
    return fn(x, y, a0, cn, chol, atol, rtol, omega, ridge)
