"""Distributed SolveBakP — the paper's §6 parallelisation mapped onto a TPU mesh.

Four shardings (DESIGN.md §3/§6):

* **obs-sharded** (`solvebakp_obs_sharded`) — rows of ``x`` shard over the
  data-parallel mesh axes.  This is the paper's "only one column needs to be
  on the accelerator" memory story re-architected: every device holds a
  (obs/D × vars) shard and the residual shard that goes with it; the block
  inner products ⟨x_k, e⟩ become one fused ``psum`` of a (thr, k) partial per
  block step.  Per-device peak memory = shard + O(obs/D + vars), preserving
  the paper's O(m+n) *overhead* invariant per device.

* **vars-sharded** (`solvebakp_vars_sharded`) — columns shard over the model
  axis.  Each device updates its local block Jacobi-style from a shared
  residual, then the residual correction is a ``psum`` of the local rank-thr
  updates.  This is Algorithm 2's thread loop lifted across devices: the
  effective block size is ``n_devices * thr_local``, so the paper's
  "thr small w.r.t. vars" condition applies to the *global* block — we default
  to mode="gram" + omega damping to keep it robust.

* **2-D** (`solvebakp_2d`) — both of the above composed; inner products psum
  over the data axes, residual corrections psum over the model axis.

* **rhs-sharded** (`solvebakp_rhs_sharded`) — the multi-RHS ``k`` axis shards
  over the data axes while ``x`` is replicated: each device sweeps the SAME
  blocks against its own slice of right-hand sides, so one mesh-wide stream
  of ``x`` serves all k tenants of a giant same-design serving group.  The
  per-sweep stopping decision psums the local SSEs, so the sweep count (and
  the returned history) is the group-global one — bit-comparable with the
  single-device multi-RHS solve.

All variants accept ``y`` of shape (obs,) or (obs, k) and an optional warm
start ``a0`` of shape (vars,) or (vars, k), matching ``solvebakp``'s
single-device API, so ``repro.serve`` routes its coalesced multi-RHS and
warm-started buckets onto a mesh without changing semantics.

All four run under ``shard_map`` with explicit collectives so the dry-run
HLO shows exactly the communication the paper's algorithm requires — nothing
auto-inserted.  Programs are built once per (mesh, shape, static-knob)
combination and cached, so repeated serving flushes reuse the compiled
executable.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.types import (SolveResult, fp32_matmuls, safe_inv,
                              sweep_stop_flags)


def _psum(v, axes):
    return lax.psum(v, axes) if axes else v


@fp32_matmuls
def _bakp_local(x_loc, y_loc, a0_loc, atol_sse, rtol, *, nvars_loc: int,
                thr: int, max_iter: int, omega: float, mode: str,
                ridge: float, g_axes: Tuple[str, ...],
                corr_axes: Tuple[str, ...], sse_axes: Tuple[str, ...]):
    """Per-device SolveBakP sweeps over a local (rows × cols) shard.

    The same body serves every sharding; only the collective axes differ:
      * ``g_axes``    — block inner products ⟨x_k, e⟩ (and the block Gram /
                        column-norm factors) partial-sum over these axes;
      * ``corr_axes`` — the rank-thr residual correction psums over these
                        (Jacobi across column shards);
      * ``sse_axes``  — the per-sweep SSE psums over these, so the stopping
                        decision (and history) is global and every device
                        runs the same trip count.

    ``x_loc`` is (obs_loc, nvars_loc); ``y_loc``/``a0_loc`` carry the local
    slice of right-hand sides, (obs_loc, k_loc) / (nvars_loc·padded, k_loc).
    ``a0_loc`` may be None (cold start — skips the residual matmul).
    ``atol_sse``/``rtol`` are *traced* replicated scalars, not compile-time
    constants: the serving engine's padding-corrected atol varies with the
    real (unpadded) group size, and must not retrace the shard_map program
    — mirroring the single-device solvers, where they are jit operands.
    """
    nrhs_loc = y_loc.shape[1]
    nblocks = -(-nvars_loc // thr)
    pad = nblocks * thr - nvars_loc
    if pad:
        x_loc = jnp.pad(x_loc, ((0, 0), (0, pad)))
    mask = (jnp.arange(nblocks * thr) < nvars_loc).astype(jnp.float32)
    mask_b = mask.reshape(nblocks, thr)

    def block(b):
        # Column block b read where it lies in the shard: whole (8, 128)
        # tiles when thr is a multiple of 128, so no blocked copy is made.
        xblk = lax.dynamic_slice_in_dim(x_loc, b * thr, thr, axis=1)
        return xblk.astype(jnp.float32)  # (obs_loc, thr)

    if mode == "gram":
        def block_gram(b):
            xblk = block(b)
            return xblk.T @ xblk

        gram = _psum(lax.map(block_gram, jnp.arange(nblocks)), g_axes)
        gram = gram + ridge * jnp.eye(thr, dtype=jnp.float32)[None]
        factor = jax.vmap(
            lambda g: jax.scipy.linalg.cholesky(g, lower=True))(gram)
    else:
        xf = x_loc.astype(jnp.float32)
        cn = _psum(jnp.sum(xf * xf, axis=0).reshape(nblocks, thr), g_axes)
        factor = safe_inv(cn) * mask_b

    if a0_loc is None:
        ab0 = jnp.zeros((nblocks, thr, nrhs_loc), jnp.float32)
        e0 = y_loc.astype(jnp.float32)
    else:
        a0p = a0_loc.astype(jnp.float32)
        if pad:
            a0p = jnp.pad(a0p, ((0, pad), (0, 0)))
        ab0 = a0p.reshape(nblocks, thr, nrhs_loc)
        # Warm residual: column shards each contribute their slice of x@a0.
        e0 = y_loc.astype(jnp.float32) - _psum(
            x_loc.astype(jnp.float32) @ a0p, corr_axes)
    sse0 = _psum(jnp.vdot(e0, e0), sse_axes)
    history0 = jnp.full((max_iter,), jnp.nan, jnp.float32)

    def block_step(carry, b):
        ab, e = carry
        xblk = block(b)
        g = _psum(xblk.T @ e, g_axes)  # (thr, k) fused collective per block
        if mode == "jacobi":
            da = g * lax.dynamic_index_in_dim(
                factor, b, 0, keepdims=False)[:, None]
        else:
            lb = lax.dynamic_index_in_dim(factor, b, 0, keepdims=False)
            mb = lax.dynamic_index_in_dim(mask_b, b, 0, keepdims=False)
            da = jax.scipy.linalg.cho_solve((lb, True), g) * mb[:, None]
        da = omega * da
        # Residual correction must include every column shard's update:
        # Jacobi across corr_axes (paper's thread loop, lifted to devices).
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
        (ab0, e0, jnp.int32(0), sse0, history0, jnp.bool_(False),
         jnp.bool_(False)))
    coef_loc = ab.reshape(nblocks * thr, nrhs_loc)[:nvars_loc]
    return coef_loc, e, sse, n, converged, history


# Per-kind shard_map spec table: (x, y, a0) in-specs and
# (coef, residual) out-specs as functions of the axis names, plus which
# collective axes the local kernel uses.  d = data axes tuple, m = model.
_KINDS = {
    # kind: (in_x, in_y, in_a0, out_coef, out_e, g_axes, corr_axes, sse_axes)
    "obs": lambda d, m: (P(d, None), P(d, None), P(None, None),
                         P(None, None), P(d, None), d, (), d),
    "vars": lambda d, m: (P(None, m), P(None, None), P(m, None),
                          P(m, None), P(None, None), (), (m,), ()),
    "2d": lambda d, m: (P(d, m), P(d, None), P(m, None),
                        P(m, None), P(d, None), d, (m,), d),
    "rhs": lambda d, m: (P(None, None), P(None, d), P(None, d),
                         P(None, d), P(None, d), (), (), d),
}


@functools.lru_cache(maxsize=128)
def _sharded_program(kind: str, mesh: Mesh, xshape: Tuple[int, int],
                     nrhs: int, warm: bool, data_axes: Tuple[str, ...],
                     model_axis: Optional[str], thr: int, max_iter: int,
                     omega: float, mode: str, ridge: float):
    """Build (once) the jitted shard_map program for one solver config.

    The cache key is the full static configuration — mesh object, padded
    shape, RHS count, warm/cold — so serving flushes that repeat a bucket
    reuse the compiled executable instead of re-tracing the shard_map.
    Tolerances (``atol_sse``/``rtol``) are traced replicated operands, NOT
    part of the key: per-request values never recompile.  ``warm=False``
    programs never take an ``a0`` operand (cold solves skip the warm path's
    extra residual matmul, mirroring the engine's jit signature split for
    single-device solves).
    """
    obs, nvars = xshape
    in_x, in_y, in_a0, out_coef, out_e, g_axes, corr_axes, sse_axes = \
        _KINDS[kind](data_axes, model_axis)
    nvars_loc = nvars // mesh.shape[model_axis] if kind in ("vars", "2d") \
        else nvars
    kw = dict(nvars_loc=nvars_loc, thr=thr, max_iter=max_iter, omega=omega,
              mode=mode, ridge=ridge, g_axes=g_axes, corr_axes=corr_axes,
              sse_axes=sse_axes)
    out_specs = (out_coef, out_e, P(), P(), P(), P(None))

    if warm:
        def run(x_loc, y_loc, a0_loc, atol_sse, rtol):
            return _bakp_local(x_loc, y_loc, a0_loc, atol_sse, rtol, **kw)
        in_specs = (in_x, in_y, in_a0, P(), P())
    else:
        def run(x_loc, y_loc, atol_sse, rtol):
            return _bakp_local(x_loc, y_loc, None, atol_sse, rtol, **kw)
        in_specs = (in_x, in_y, P(), P())
    return jax.jit(jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _solve_sharded(kind, x, y, mesh, *, data_axes, model_axis, thr, max_iter,
                   atol, rtol, omega, mode, ridge, a0):
    """Shared driver: normalise y/a0, run the cached program, reshape back."""
    obs, nvars = x.shape
    if y.ndim not in (1, 2):
        raise ValueError(f"y must be (obs,) or (obs, k), got {y.shape}")
    multi = y.ndim == 2
    nrhs = y.shape[1] if multi else 1
    y2 = jnp.asarray(y).reshape(obs, nrhs)
    if a0 is not None:
        a0 = jnp.asarray(a0)
        if a0.shape not in ((nvars,), (nvars, nrhs)):
            raise ValueError(
                f"a0 must be ({nvars},) or ({nvars}, {nrhs}) matching x "
                f"columns and y RHS count, got {a0.shape}")
        # (vars,) broadcasts across all right-hand sides; materialised so
        # rhs-sharding can slice it per device like any other (vars, k).
        a0 = jnp.broadcast_to(a0.reshape(nvars, -1), (nvars, nrhs))

    data_axes = tuple(data_axes)
    dsize = 1
    for ax in data_axes:
        dsize *= mesh.shape[ax]
    if kind in ("obs", "2d") and obs % dsize:
        raise ValueError(f"obs={obs} must divide data axes size {dsize}")
    if kind in ("vars", "2d"):
        msize = mesh.shape[model_axis]
        if nvars % msize:
            raise ValueError(
                f"vars={nvars} must divide model axis size {msize}")
    if kind == "rhs":
        if not multi:
            raise ValueError("rhs-sharded solve needs multi-RHS y=(obs, k)")
        if nrhs % dsize:
            raise ValueError(f"k={nrhs} must divide data axes size {dsize}")

    program = _sharded_program(
        kind, mesh, (obs, nvars), nrhs, a0 is not None, data_axes,
        model_axis, int(thr), int(max_iter), float(omega), mode,
        float(ridge))
    atol_sse = jnp.float32(float(obs) * float(nrhs) * float(atol) ** 2)
    rtol_t = jnp.float32(rtol)
    args = ((x, y2) if a0 is None else (x, y2, a0)) + (atol_sse, rtol_t)
    coef, e, sse, n, converged, history = program(*args)
    if not multi:
        coef, e = coef[:, 0], e[:, 0]
    return SolveResult(coef, e, sse, n, converged, history)


def solvebakp_obs_sharded(
    x: jax.Array,
    y: jax.Array,
    mesh: Mesh,
    *,
    data_axes: Sequence[str] = ("data",),
    thr: int = 128,
    max_iter: int = 50,
    atol: float = 0.0,
    rtol: float = 0.0,
    omega: float = 1.0,
    mode: str = "gram",
    ridge: float = 1e-6,
    a0: Optional[jax.Array] = None,
) -> SolveResult:
    """SolveBakP with rows sharded over ``data_axes`` of ``mesh``.

    ``x`` is (obs, vars) with obs divisible by the product of data axis
    sizes; ``y`` is (obs,) or (obs, k); ``a0`` is an optional (vars,) or
    (vars, k) warm start (replicated).  Returns a replicated SolveResult
    (residual stays obs-sharded).  Block structure and update order match
    the single-device ``solvebakp`` exactly — only the inner products gain
    a psum — so the sweep iterates agree to reduction-order rounding.
    """
    return _solve_sharded(
        "obs", x, y, mesh, data_axes=data_axes, model_axis=None, thr=thr,
        max_iter=max_iter, atol=atol, rtol=rtol, omega=omega, mode=mode,
        ridge=ridge, a0=a0)


def solvebakp_vars_sharded(
    x: jax.Array,
    y: jax.Array,
    mesh: Mesh,
    *,
    model_axis: str = "model",
    thr: int = 128,
    max_iter: int = 50,
    atol: float = 0.0,
    rtol: float = 0.0,
    omega: float = 0.5,
    mode: str = "gram",
    ridge: float = 1e-6,
    a0: Optional[jax.Array] = None,
) -> SolveResult:
    """SolveBakP with columns sharded over ``model_axis``.

    Each device sweeps its local blocks Jacobi-style against a replicated
    residual; every block step ends with a psum'd rank-(D·thr) residual
    correction.  Defaults to gram + ω=0.5 damping because the effective
    cross-device block is large (see module docstring).  ``y`` may be
    (obs, k); ``a0`` warm starts are column-sharded with the coefficients.
    """
    return _solve_sharded(
        "vars", x, y, mesh, data_axes=(), model_axis=model_axis, thr=thr,
        max_iter=max_iter, atol=atol, rtol=rtol, omega=omega, mode=mode,
        ridge=ridge, a0=a0)


def solvebakp_2d(
    x: jax.Array,
    y: jax.Array,
    mesh: Mesh,
    *,
    data_axes: Sequence[str] = ("data",),
    model_axis: str = "model",
    thr: int = 128,
    max_iter: int = 50,
    atol: float = 0.0,
    rtol: float = 0.0,
    omega: float = 0.5,
    mode: str = "gram",
    ridge: float = 1e-6,
    a0: Optional[jax.Array] = None,
) -> SolveResult:
    """Fully 2-D sharded SolveBakP: obs over data axes, vars over model axis.

    ⟨x_k, e⟩ partials psum over data; residual corrections psum over model.
    This is the production configuration for pod-scale systems (e.g.
    obs=10⁹ tokens × vars=10⁵ features on a 16×16 mesh).  Multi-RHS ``y``
    and warm starts thread through like the 1-D variants.
    """
    return _solve_sharded(
        "2d", x, y, mesh, data_axes=data_axes, model_axis=model_axis,
        thr=thr, max_iter=max_iter, atol=atol, rtol=rtol, omega=omega,
        mode=mode, ridge=ridge, a0=a0)


def solvebakp_rhs_sharded(
    x: jax.Array,
    y: jax.Array,
    mesh: Mesh,
    *,
    data_axes: Sequence[str] = ("data",),
    thr: int = 128,
    max_iter: int = 50,
    atol: float = 0.0,
    rtol: float = 0.0,
    omega: float = 1.0,
    mode: str = "gram",
    ridge: float = 1e-6,
    a0: Optional[jax.Array] = None,
) -> SolveResult:
    """SolveBakP with the multi-RHS ``k`` axis sharded over ``data_axes``.

    ``x`` is replicated; each device runs the identical block sweeps against
    its own (obs, k/D) slice of right-hand sides — the serving engine's
    giant same-design groups scaled across a mesh, one stream of ``x`` per
    device serving k/D tenants.  The only collective is the per-sweep SSE
    psum, which makes the stopping decision (and history) group-global:
    iterates and sweep counts match the single-device multi-RHS solve
    exactly, because per-RHS coordinate updates never interact.

    ``y`` must be (obs, k) with k divisible by the data axes product;
    ``a0`` may be (vars,) (broadcast) or (vars, k) (sharded with ``y``).
    """
    return _solve_sharded(
        "rhs", x, y, mesh, data_axes=data_axes, model_axis=None, thr=thr,
        max_iter=max_iter, atol=atol, rtol=rtol, omega=omega, mode=mode,
        ridge=ridge, a0=a0)
