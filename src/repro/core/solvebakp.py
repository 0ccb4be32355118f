"""SolveBakP — Algorithm 2 of the paper (block-parallel CD) + Gram-block upgrade.

The paper parallelises Algorithm 1 by processing ``thr`` columns at a time:
the per-column steps ``da_k = ⟨x_k, e⟩ / ⟨x_k, x_k⟩`` inside a block all read
the *same* residual (Jacobi-within-block), then the residual is corrected once
per block with a rank-``thr`` update

    e ← e - x_blk @ (a_blk - aprev_blk).

On TPU the block update is an MXU matmul and the per-block inner products are
a single (thr × obs)·(obs,) matvec, so this variant is the natural TPU
formulation of the paper's multi-thread loop.

``mode="jacobi"`` is the paper-faithful Algorithm 2.

``mode="gram"`` is a *beyond-paper* upgrade: solve the thr×thr block normal
equations exactly,

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
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.types import (SolveResult, column_norms_sq, donate_default,
                              fp32_matmuls, safe_inv, sweep_stop_flags)


def _psum(v, axes):
    return lax.psum(v, axes) if axes else v


def _pad_cols(x: jax.Array, thr: int):
    """Zero-pad columns of x to a multiple of thr.

    Returns (x_pad, mask): mask is (nblocks, thr), 1 on x's own columns.
    """
    nvars = x.shape[1]
    nblocks = -(-nvars // thr)
    pad = nblocks * thr - nvars
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    mask = (jnp.arange(nblocks * thr) < nvars).astype(jnp.float32)
    return x, mask.reshape(nblocks, thr)


@fp32_matmuls
def block_factors(x_pad: jax.Array, thr: int, mode: str, ridge, *,
                  cn: Optional[jax.Array] = None,
                  g_axes: Tuple[str, ...] = ()) -> jax.Array:
    """The factors of every block step, formed once per design or solve.

    ``x_pad`` is (obs, nblocks*thr), zero-padded to a multiple of thr (under
    ``shard_map``: this device's shard, whose partials psum over
    ``g_axes``).  mode="gram": (nblocks, thr, thr) lower Cholesky factors of
    x_blkᵀ x_blk + ridge·I, from one einsum on the blocked view of x (no
    blocked copy is made).  mode="jacobi": (nblocks, thr) inverse squared
    column norms, of the prepared ``cn`` when given.
    """
    obs, width = x_pad.shape
    nblocks = width // thr
    if mode == "gram":
        xb = x_pad.astype(jnp.float32).reshape(obs, nblocks, thr)
        gram = _psum(jnp.einsum("obt,obs->bts", xb, xb), g_axes)
        gram = gram + ridge * jnp.eye(thr, dtype=jnp.float32)[None]
        return jax.vmap(
            lambda g: jax.scipy.linalg.cholesky(g, lower=True))(gram)
    if mode != "jacobi":
        raise ValueError(f"unknown mode {mode!r}")
    if cn is None:
        cn = _psum(column_norms_sq(x_pad), g_axes)
    return safe_inv(cn).reshape(nblocks, thr)


@functools.partial(jax.jit, static_argnums=1)
def block_gram_cholesky(x: jax.Array, thr: int, ridge) -> jax.Array:
    """Gram-mode ``block_factors`` of x, zero-padded to a multiple of thr:
    the (nblocks, thr, thr) factors ``solvebakp(chol=...)`` takes.  One
    program, so the blocked view of x is read in place."""
    return block_factors(_pad_cols(x, thr)[0], thr, "gram", ridge)


def check_rhs(y, a0, nvars: int) -> int:
    """Check y is (obs,) or (obs, k) and a0 (vars,) or (vars, k); return k."""
    if y.ndim not in (1, 2):
        raise ValueError(f"y must be (obs,) or (obs, k), got {y.shape}")
    nrhs = y.shape[1] if y.ndim == 2 else 1
    if a0 is not None and a0.shape not in ((nvars,), (nvars, nrhs)):
        raise ValueError(
            f"a0 must be ({nvars},) or ({nvars}, {nrhs}) matching x columns "
            f"and y RHS count, got {a0.shape}")
    return nrhs


def first_rhs(res: SolveResult) -> SolveResult:
    """A single-RHS result of ``block_cd``: (vars,) coef, (obs,) residual."""
    return res._replace(coef=res.coef[:, 0], residual=res.residual[:, 0])


@fp32_matmuls
def block_cd(x, y, a0, cn, chol, atol_sse, rtol, omega, ridge, *, thr: int,
             max_iter: int, mode: str, g_axes: Tuple[str, ...] = (),
             corr_axes: Tuple[str, ...] = (),
             sse_axes: Tuple[str, ...] = ()) -> SolveResult:
    """Block CD sweeps (Algorithm 2) until the stopping rule or max_iter.

    The one body of every SolveBakP program: with empty axes it is the
    single-device solve (and vmaps over designs); under ``shard_map`` each
    device runs it on its local shard and only the collective axes differ:
      * ``g_axes``    — block inner products ⟨x_k, e⟩ (and the factors
                        formed from x) psum over these;
      * ``corr_axes`` — the rank-thr residual correction psums over these
                        (Jacobi across column shards);
      * ``sse_axes``  — the per-sweep SSE psums over these, so the stopping
                        decision (and history) is global and every device
                        runs the same trip count.

    ``x`` is (obs, vars), unpadded; ``y`` is (obs, k); ``a0`` is None (cold:
    the residual starts at y) or (vars,)/(vars, k).  ``cn``/``chol`` are
    prepared factors or None (formed here by ``block_factors``).
    ``atol_sse``, ``rtol``, ``omega`` and ``ridge`` may be traced.  Returns
    a SolveResult with (vars, k) coef and (obs, k) residual.
    """
    nvars = x.shape[1]
    nrhs = y.shape[1]
    x_pad, mask = _pad_cols(x, thr)
    nblocks = mask.shape[0]
    if mode == "gram" and chol is not None:
        factor = chol
    else:
        factor = block_factors(x_pad, thr, mode, ridge, cn=cn, g_axes=g_axes)

    e0 = y.astype(jnp.float32)
    a = jnp.zeros((nblocks * thr, nrhs), jnp.float32)
    if a0 is not None:  # (vars,) broadcasts across all right-hand sides
        a = a.at[:nvars].set(jnp.broadcast_to(
            a0.astype(jnp.float32).reshape(nvars, -1), (nvars, nrhs)))
        # Column shards each contribute their slice of x @ a0.
        e0 = e0 - _psum(x_pad.astype(jnp.float32) @ a, corr_axes)
    sse0 = _psum(jnp.vdot(e0, e0), sse_axes)
    history0 = jnp.full((max_iter,), jnp.nan, jnp.float32)

    def block_step(carry, b):
        ab, e = carry
        # Column block b read where it lies in x: whole (8, 128) tiles when
        # thr is a multiple of 128, so no blocked copy of x is made.
        xblk = lax.dynamic_slice_in_dim(x_pad, b * thr, thr, axis=1)
        xblk = xblk.astype(jnp.float32)  # (obs, thr)
        # (thr, k) ⟨x_k, e⟩ for all k in block, all RHS: one fused psum
        g = _psum(xblk.T @ e, g_axes)
        fb = lax.dynamic_index_in_dim(factor, b, 0, keepdims=False)
        if mode == "jacobi":
            da = g * fb[:, None]
        else:
            da = jax.scipy.linalg.cho_solve((fb, True), g)
        # Padded columns stay at 0.
        mb = lax.dynamic_index_in_dim(mask, b, 0, keepdims=False)
        da = omega * (da * mb[:, None])
        # Paper line 9 (rank-thr residual correction), with every column
        # shard's update: Jacobi across corr_axes.
        e = e - _psum(xblk @ da, corr_axes)
        ab = lax.dynamic_update_index_in_dim(ab, ab[b] + da, b, axis=0)
        return (ab, e), None

    def sweep_body(state):
        ab, e, i, sse_prev, history, converged, stop = state
        (ab, e), _ = lax.scan(block_step, (ab, e), jnp.arange(nblocks))
        sse = _psum(jnp.vdot(e, e), sse_axes)
        history = history.at[i].set(sse)
        converged, stop = sweep_stop_flags(sse, sse_prev, sse0, atol_sse,
                                           rtol)
        return ab, e, i + 1, sse, history, converged, stop

    def cond(state):
        _, _, i, _, _, _, stop = state
        return (i < max_iter) & ~stop

    ab, e, n, sse, history, converged, _ = lax.while_loop(
        cond, sweep_body,
        (a.reshape(nblocks, thr, nrhs), e0, jnp.int32(0), sse0, history0,
         jnp.bool_(False), jnp.bool_(False)))
    coef = ab.reshape(nblocks * thr, nrhs)[:nvars]
    return SolveResult(coef, e, sse, n, converged, history)


def _solvebakp_impl(x, y, a0, cn, chol, atol, rtol, omega, ridge, *,
                    thr: int, max_iter: int, mode: str) -> SolveResult:
    obs, nvars = x.shape
    nrhs = check_rhs(y, a0, nvars)
    atol_sse = jnp.float32(obs * nrhs) * jnp.float32(atol) ** 2
    res = block_cd(x, y.reshape(obs, nrhs), a0, cn, chol, atol_sse, rtol,
                   omega, ridge, thr=thr, max_iter=max_iter, mode=mode)
    return res if y.ndim == 2 else first_rhs(res)


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
      chol: optional precomputed ``block_gram_cholesky(x, thr, ridge)`` factors,
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
