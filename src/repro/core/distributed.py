"""Distributed SolveBakP — the paper's §6 parallelisation mapped onto a TPU mesh.

Four shardings, each running ``solvebakp.block_cd`` on its local shard:

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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.solvebakp import block_cd, check_rhs, first_rhs
from repro.core.types import SolveResult


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

# The serving placement kinds (``repro.serve.placement``) and the ``_KINDS``
# entry each one's backend runs.
_PLACEMENT_KINDS = {"obs_sharded": "obs", "rhs_sharded": "rhs",
                    "mesh_2d": "2d"}


def _kind_of(placement_kind: str) -> str:
    if placement_kind not in _PLACEMENT_KINDS:
        raise ValueError(f"unknown placement kind {placement_kind!r}")
    return _PLACEMENT_KINDS[placement_kind]


def placement_shardings(kind: str, smesh
                        ) -> Tuple[NamedSharding, NamedSharding]:
    """The ``NamedSharding``s a serving placement's program takes ``x`` and
    its (obs, k) ``y`` in, read from ``_KINDS``: ``x`` by rows over the
    data axes (obs_sharded), replicated (rhs_sharded) or by rows × columns
    (mesh_2d); ``y`` by rows, or by right-hand sides (rhs_sharded).  The
    residual comes back in ``y``'s (every kind's ``out_e`` is its
    ``in_y``)."""
    in_x, in_y = _KINDS[_kind_of(kind)](tuple(smesh.data_axes),
                                        smesh.model_axis)[:2]
    return NamedSharding(smesh.mesh, in_x), NamedSharding(smesh.mesh, in_y)


def solvebakp_placed(kind: str, x, y, smesh, **kw) -> SolveResult:
    """SolveBakP for a serving placement ``kind`` on the ``ServeMesh``
    ``smesh``: the ``_KINDS`` entry the placement runs, over the mesh's
    data axes (and its model axis for mesh_2d).  ``kw`` as for
    ``solvebakp_obs_sharded`` (thr, max_iter, atol, rtol, omega, mode,
    ridge, a0)."""
    k = _kind_of(kind)
    return _solve_sharded(
        k, x, y, smesh.mesh, data_axes=smesh.data_axes,
        model_axis=smesh.model_axis if k == "2d" else None, **kw)


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
    in_x, in_y, in_a0, out_coef, out_e, g_axes, corr_axes, sse_axes = \
        _KINDS[kind](data_axes, model_axis)
    kw = dict(thr=thr, max_iter=max_iter, mode=mode, g_axes=g_axes,
              corr_axes=corr_axes, sse_axes=sse_axes)
    out_specs = SolveResult(out_coef, out_e, P(), P(), P(), P(None))

    if warm:
        def run(x_loc, y_loc, a0_loc, atol_sse, rtol):
            return block_cd(x_loc, y_loc, a0_loc, None, None, atol_sse, rtol,
                            omega, ridge, **kw)
        in_specs = (in_x, in_y, in_a0, P(), P())
    else:
        def run(x_loc, y_loc, atol_sse, rtol):
            return block_cd(x_loc, y_loc, None, None, None, atol_sse, rtol,
                            omega, ridge, **kw)
        in_specs = (in_x, in_y, P(), P())
    return jax.jit(jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _solve_sharded(kind, x, y, mesh, *, data_axes, model_axis, thr, max_iter,
                   atol, rtol, omega, mode, ridge, a0):
    """Shared driver: normalise y/a0, run the cached program, reshape back."""
    obs, nvars = x.shape
    a0 = None if a0 is None else jnp.asarray(a0)
    nrhs = check_rhs(y, a0, nvars)
    multi = y.ndim == 2
    y2 = jnp.asarray(y).reshape(obs, nrhs)
    if a0 is not None:
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
    res = program(*args)
    return res if multi else first_rhs(res)


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
