"""Built-in solver methods, registered against ``repro.core.spec``.

Every method the public API dispatches on is declared here as a
``MethodEntry`` whose kernel consumes a ``PreparedDesign``:

  * "bak"        — Algorithm 1, serial cyclic CD (paper-faithful baseline).
  * "bakp"       — Algorithm 2, block-Jacobi CD (paper-faithful parallel).
  * "bakp_gram"  — beyond-paper exact block CD (``solvebakp`` mode "gram").
  * "bakp_fused" — Algorithm 2 on the fused whole-solve Pallas megakernel
                   (``repro.kernels.fused_solve``): one kernel launch runs
                   every sweep with x/residual/coefficients VMEM-resident
                   and convergence decided on-chip.  Selected for
                   VMEM-fitting designs; larger ones fall back to the XLA
                   "bakp" path automatically (same algorithm, same result).
  * "bak_fused"  — the megakernel's ``variant="bak"`` body (Algorithm 1
                   sequential order); falls back to "bak" when too large.
  * "bakf"       — Algorithm 3 run to full selection: greedy forward CD over
                   every column with per-step refit.  Single-RHS, ignores
                   warm starts (selection always restarts).
  * "lstsq"      — LAPACK-path baseline (the paper's comparison column).
  * "normal"     — normal-equation Cholesky with a ``SolverSpec.ridge``
                   Tikhonov diagonal (the fast direct baseline).

The BAK family reads its reusable design state (column norms, block Gram
Cholesky factors, per-placement sharded copies) off the handle, so repeated
solves against one design never recompute it; the prepare hooks warm exactly
that state.  The mesh-sharded placements route to
``repro.core.distributed`` — only methods registered ``shardable=True`` are
eligible, which is what the serving placement policy keys on.

Adding a backend = writing a kernel with this signature and calling
``register_method`` — ``solve()``, ``prepare()``, the serving engine, the
async dispatcher and the placement policy all pick it up from the registry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import solvebakp_placed
from repro.core.solvebak import solvebak
from repro.core.solvebakf import solvebakf
from repro.core.solvebakp import solvebakp
from repro.core.spec import (_ITER_FIELDS, MethodEntry, SolverSpec,
                             register_method)
from repro.core.types import SolveResult, fp32_matmuls
from repro.obs import record_dispatch

# --------------------------------------------------------------- BAK family
def _bak_solve(p, y, spec: SolverSpec, *, a0=None, key=None, placement=None,
               mesh=None, donate=None):
    # Kernel-path relay: these solve bodies run eagerly per call (jit lives
    # inside the solvers), so recording here reports the route each solve
    # actually took; the vmap_one closures are jit-traced and must NOT
    # record (they'd only fire at compile time).
    record_dispatch("xla", method="bak")
    return solvebak(p.x_pad, y, max_iter=spec.max_iter, atol=spec.atol,
                    rtol=spec.rtol, a0=a0, order=spec.order, key=key,
                    cn=p.cn, donate=donate)


def _bak_vmap_one(spec: SolverSpec):
    if spec.order != "cyclic":
        # Keep batch and single-solve semantics identical: the single path
        # rejects order="random" without a PRNG key (serving requests carry
        # none), so the vmapped path must error too rather than silently
        # solving with cyclic order.
        raise ValueError(
            f"order={spec.order!r} requires a PRNG key and is not "
            f"vmap-batchable; serve it with order='cyclic'")

    def one(x, y, cn, atol, a0=None):
        return solvebak(x, y, max_iter=spec.max_iter, atol=atol,
                        rtol=spec.rtol, cn=cn, a0=a0)
    return one


def _bakp_solve(mode: str):
    method_name = "bakp" if mode == "jacobi" else "bakp_gram"

    def kernel(p, y, spec: SolverSpec, *, a0=None, key=None, placement=None,
               mesh=None, donate=None):
        if placement is not None and placement.sharded:
            if mesh is None:
                raise ValueError(
                    f"placement {placement.kind!r} needs a ServeMesh")
            record_dispatch("sharded", method=method_name)
            return solvebakp_placed(
                placement.kind, p.x_for_placement(placement, mesh), y, mesh,
                thr=spec.thr, max_iter=spec.max_iter, atol=spec.atol,
                rtol=spec.rtol, omega=spec.omega, mode=mode,
                ridge=spec.ridge, a0=a0)
        record_dispatch("xla", method=method_name)
        return solvebakp(
            p.x_pad, y, thr=spec.thr, max_iter=spec.max_iter, atol=spec.atol,
            rtol=spec.rtol, omega=spec.omega, mode=mode, ridge=spec.ridge,
            cn=p.cn_for_thr(spec.thr),
            chol=(p.chol_for(spec.thr, spec.ridge) if mode == "gram"
                  else None),
            a0=a0, donate=donate)
    return kernel


def _bakp_vmap_one(mode: str):
    def build(spec: SolverSpec):
        if mode == "gram":
            def one(x, y, cn, atol, chol, a0=None):
                return solvebakp(x, y, thr=spec.thr, max_iter=spec.max_iter,
                                 atol=atol, rtol=spec.rtol, omega=spec.omega,
                                 mode="gram", ridge=spec.ridge, cn=cn,
                                 chol=chol, a0=a0)
        else:
            def one(x, y, cn, atol, a0=None):
                return solvebakp(x, y, thr=spec.thr, max_iter=spec.max_iter,
                                 atol=atol, rtol=spec.rtol, omega=spec.omega,
                                 mode="jacobi", cn=cn, a0=a0)
        return one
    return build


def _prep_bak(p, spec: SolverSpec):
    p.cn  # property access materialises the lazy column norms


def _prep_bakp(p, spec: SolverSpec):
    p.cn_for_thr(spec.thr)


def _prep_bakp_gram(p, spec: SolverSpec):
    p.cn_for_thr(spec.thr)
    p.chol_for(spec.thr, spec.ridge)


# ------------------------------------------------- fused megakernel methods
def _refine_fp32(p, y, spec: SolverSpec, lp: SolveResult, *, variant: str,
                 nrhs: int, donate=None) -> SolveResult:
    """fp32 polish for ``precision="bf16_fp32acc"`` (iterative refinement).

    Starts from the low-precision solution: the kernel entry's shared
    ``solve_init`` recomputes the residual in fp32 from the solved
    coefficients against the fp32 design, then up to ``spec.refine_sweeps``
    full-precision sweeps run against it — honouring ``atol``/``rtol``, so
    an already-converged polish exits early.  Routed through the same
    fused-vs-per-sweep fit check as the main solve (at fp32 itemsize); the
    per-sweep stream covers designs where only the bf16 copy fits fused.

    Deliberately does NOT record a dispatch: the solve's reported kernel
    path stays the low-precision route the bulk of the bytes took.
    """
    from repro.kernels.fused_solve import fused_fits, fused_solve
    from repro.kernels.ops import solvebakp_persweep_kernel

    block = spec.thr
    obs_p = p.x_pad.shape[0]
    x_t = p.x_t_for(block)
    kw = dict(inv_cn=p.inv_cn_for(block), a0=lp.coef, block=block,
              max_iter=spec.refine_sweeps, atol=spec.atol, rtol=spec.rtol,
              omega=spec.omega if variant == "bakp" else 1.0,
              variant=variant, donate=donate)
    if fused_fits(x_t.shape[0], obs_p, nrhs, x_t.dtype.itemsize,
                  max_iter=spec.refine_sweeps):
        pol = fused_solve(x_t, y, **kw)
    else:
        pol = solvebakp_persweep_kernel(x_t, y, **kw)
    # Merged accounting: sweeps add, histories concatenate (length
    # max_iter + refine_sweeps for this precision), convergence is the OR
    # (a polish that runs its full budget after a converged lp solve is
    # still a success).
    return SolveResult(
        pol.coef, pol.residual, pol.sse, lp.n_sweeps + pol.n_sweeps,
        lp.converged | pol.converged,
        jnp.concatenate([lp.history, pol.history]))


def _fused_method(variant: str):
    """Whole-solve Pallas megakernel entry (repro.kernels.fused_solve).

    Consumes the handle's cached transposed padded design (``x_t_for``) and
    inverse column norms (``inv_cn_for``) — no per-solve norms pass, no
    ``x_t.T`` materialisation.  Designs whose whole-solve working set
    exceeds ``repro.kernels.cd_sweep.VMEM_BUDGET_BYTES`` (checked via
    ``fused_fits``) fall back to the XLA path of the same algorithm, so
    every dispatch route (``solve()``, ``PreparedDesign.solve``, the
    serving engine) serves any size without raising.

    Precision (PR 7): under ``spec.precision != "fp32"`` the kernels
    stream the handle's bf16 cache tier (``x_bf16_for``) instead — half
    the HBM traffic, and the VMEM fit check runs at itemsize 2, so designs
    twice as large stay on the fused path.  A bf16 solve too large even at
    itemsize 2 falls back to the *per-sweep* bf16 stream (keeping the
    halved traffic) rather than the fp32 XLA solvers.
    ``"bf16_fp32acc"`` appends the ``_refine_fp32`` polish.
    """
    def kernel(p, y, spec: SolverSpec, *, a0=None, key=None, placement=None,
               mesh=None, donate=None):
        # Imported at call time: repro.kernels itself imports repro.core
        # (types), so a module-level import here would make the package
        # import order matter (kernels-first would hit a half-initialised
        # fused_solve through this registration module).
        from repro.kernels.fused_solve import fused_fits, fused_solve
        from repro.kernels.ops import solvebakp_persweep_kernel

        block = spec.thr
        lowp = spec.precision != "fp32"
        polish = spec.precision == "bf16_fp32acc" and spec.refine_sweeps > 0
        obs_p, vars_p = p.x_pad.shape
        if not hasattr(y, "ndim"):  # host buffers stay host (donation)
            y = jnp.asarray(y)
        nrhs = y.shape[1] if y.ndim == 2 else 1
        vars_pb = -(-vars_p // block) * block
        itemsize = 2 if lowp else p.x_pad.dtype.itemsize
        fits = (spec.max_iter >= 1
                and fused_fits(vars_pb, obs_p, nrhs, itemsize,
                               max_iter=spec.max_iter))
        if spec.max_iter < 1 or (not fits and not lowp):
            record_dispatch(
                "xla", method=f"{variant}_fused",
                reason="max_iter" if spec.max_iter < 1 else "vmem")
            if variant == "bak":
                return solvebak(p.x_pad, y, max_iter=spec.max_iter,
                                atol=spec.atol, rtol=spec.rtol, a0=a0,
                                cn=p.cn, donate=donate)
            return solvebakp(p.x_pad, y, thr=block, max_iter=spec.max_iter,
                             atol=spec.atol, rtol=spec.rtol,
                             omega=spec.omega, mode="jacobi",
                             cn=p.cn_for_thr(block), a0=a0, donate=donate)
        if a0 is not None and vars_pb != vars_p:
            # Pad with the operand's own library: a host a0 must STAY host
            # (numpy) or the solver entry's auto-donation — the flush
            # path's HBM saving — silently turns off (types.donate_default
            # never donates jax.Array operands).
            xp = jnp if isinstance(a0, jax.Array) else np
            a0 = xp.pad(xp.asarray(a0, jnp.float32),
                        ((0, vars_pb - vars_p),) + ((0, 0),) * (a0.ndim - 1))
        x_t = p.x_bf16_for(block) if lowp else p.x_t_for(block)
        # The polish reads y again, so only its own solve may consume it.
        kw = dict(inv_cn=p.inv_cn_for(block), a0=a0, block=block,
                  max_iter=spec.max_iter, atol=spec.atol, rtol=spec.rtol,
                  omega=spec.omega if variant == "bakp" else 1.0,
                  variant=variant, donate=None if polish else donate)
        if fits:
            record_dispatch("fused", method=f"{variant}_fused")
            res = fused_solve(x_t, y, **kw)
        else:
            # bf16-only fallback: stream the bf16 copy per sweep instead of
            # re-inflating to the fp32 XLA path — large designs are exactly
            # where the halved HBM traffic matters most.
            record_dispatch("persweep", method=f"{variant}_fused",
                            reason="vmem")
            res = solvebakp_persweep_kernel(x_t, y, **kw)
        if polish:
            res = _refine_fp32(p, y, spec, res, variant=variant, nrhs=nrhs,
                               donate=donate)
        if vars_pb != vars_p:
            res = res._replace(coef=res.coef[:vars_p])
        return res
    return kernel


def _prep_fused(p, spec: SolverSpec):
    p.x_t_for(spec.thr)
    p.inv_cn_for(spec.thr)
    if spec.precision != "fp32":
        p.x_bf16_for(spec.thr)  # quantized cache tier, warmed off-thread


# ------------------------------------------------- streaming out-of-core
def _stream_solve_method(p, y, spec: SolverSpec, *, a0=None, key=None,
                         placement=None, mesh=None, donate=None):
    """Algorithm 2 with X streamed rather than VMEM-resident.

    Resident designs run the double-buffered Pallas kernel
    (``repro.kernels.stream_solve``): x tiles live in ``pl.ANY`` (HBM)
    and DMA through a two-slot VMEM scratch while residual/coefficients
    stay on-chip, so the VMEM working set is two (block, obs) tiles
    regardless of vars.  Non-resident designs — store-backed handles whose
    X never fits the device budget — take the host block loop
    (``stream_solve_blocks``), fetching tiles through the design store's
    host/disk tiers per block.  Same block-Jacobi math and stopping rule
    as "bakp"/"bakp_fused" either way.
    """
    from repro.kernels.ops import solvebakp_persweep_kernel
    from repro.kernels.stream_solve import (stream_fits, stream_solve,
                                            stream_solve_blocks)

    block = spec.thr
    lowp = spec.precision != "fp32"
    obs_p, vars_p = p.shape
    if not hasattr(y, "ndim"):  # host buffers stay host (donation)
        y = jnp.asarray(y)
    nrhs = y.shape[1] if y.ndim == 2 else 1
    vars_pb = -(-vars_p // block) * block
    if spec.max_iter < 1 and p.x_pad is not None:
        record_dispatch("xla", method="bakp_stream", reason="max_iter")
        return solvebakp(p.x_pad, y, thr=block, max_iter=spec.max_iter,
                         atol=spec.atol, rtol=spec.rtol, omega=spec.omega,
                         mode="jacobi", cn=p.cn_for_thr(block), a0=a0,
                         donate=donate)
    if a0 is not None and vars_pb != vars_p:
        xp = jnp if isinstance(a0, jax.Array) else np
        a0 = xp.pad(xp.asarray(a0, jnp.float32),
                    ((0, vars_pb - vars_p),) + ((0, 0),) * (a0.ndim - 1))
    kw = dict(inv_cn=p.inv_cn_for(block), a0=a0, block=block,
              max_iter=spec.max_iter, atol=spec.atol, rtol=spec.rtol,
              omega=spec.omega)
    if p.x_pad is None:
        record_dispatch("stream_host", method="bakp_stream")
        res = stream_solve_blocks(p.blocks, y, **kw)
    else:
        itemsize = 2 if lowp else 4
        x_t = p.x_bf16_for(block) if lowp else p.x_t_for(block)
        if stream_fits(vars_pb, obs_p, nrhs, itemsize, block=block,
                       max_iter=spec.max_iter):
            record_dispatch("stream", method="bakp_stream")
            res = stream_solve(x_t, y, donate=donate, **kw)
        else:
            # Even the two-tile scratch is over budget (huge obs): the
            # per-sweep stream shares the bounded-VMEM property.
            record_dispatch("persweep", method="bakp_stream", reason="vmem")
            res = solvebakp_persweep_kernel(x_t, y, variant="bakp",
                                            donate=donate, **kw)
    if vars_pb != vars_p:
        res = res._replace(coef=res.coef[:vars_p])
    return res


def _prep_stream(p, spec: SolverSpec):
    p.inv_cn_for(spec.thr)
    if p.x_pad is not None:
        p.x_t_for(spec.thr)
        if spec.precision != "fp32":
            p.x_bf16_for(spec.thr)


# ---------------------------------------------------- greedy selection (A3)
def _bakf_solve(p, y, spec: SolverSpec, *, a0=None, key=None, placement=None,
                mesh=None, donate=None):
    """Algorithm 3 run to full selection as a solver: greedily order every
    column by SSE reduction, refitting after each pick.  The final refit
    over all columns is an exact-block CD solve, so the solution matches
    "bak"/"bakp" on the same system (parity-tested); the selection order
    itself is the extra information this method pays O(vars) matvecs for.
    """
    record_dispatch("xla", method="bakf")
    nvars = p.x_pad.shape[1]
    sel = solvebakf(p.x_pad, y, max_feat=nvars,
                    refit_sweeps=spec.max_iter,
                    refit_thr=min(spec.thr, nvars))
    coef = jnp.zeros((nvars,), jnp.float32).at[sel.selected].set(sel.coef)
    e = sel.residual
    sse = jnp.vdot(e, e)
    hist = jnp.full((spec.max_iter,), jnp.nan, jnp.float32).at[0].set(sse)
    return SolveResult(coef, e, sse, jnp.int32(nvars), jnp.bool_(True), hist)


# ----------------------------------------------------------- direct methods
def _direct_result(x, y, coef, max_iter: int) -> SolveResult:
    e = y.astype(jnp.float32) - x @ coef
    sse = jnp.vdot(e, e)
    hist = jnp.full((max_iter,), jnp.nan, jnp.float32).at[0].set(sse)
    return SolveResult(coef, e, sse, jnp.int32(1), jnp.bool_(True), hist)


@functools.partial(jax.jit, static_argnames=("max_iter",))
@fp32_matmuls
def _lstsq_kernel(x, y, max_iter: int) -> SolveResult:
    coef = jnp.linalg.lstsq(x, y.astype(jnp.float32))[0]
    return _direct_result(x, y, coef, max_iter)


@functools.partial(jax.jit, static_argnames=("max_iter",))
@fp32_matmuls
def _normal_kernel(x, y, ridge, max_iter: int) -> SolveResult:
    g = x.T @ x + ridge * jnp.eye(x.shape[1], dtype=jnp.float32)
    coef = jax.scipy.linalg.cho_solve(
        (jax.scipy.linalg.cholesky(g, lower=True), True),
        x.T @ y.astype(jnp.float32))
    return _direct_result(x, y, coef, max_iter)


def _lstsq_solve(p, y, spec: SolverSpec, *, a0=None, key=None, placement=None,
                 mesh=None, donate=None):
    record_dispatch("xla", method="lstsq")
    return _lstsq_kernel(p.x_pad, y, spec.max_iter)


def _normal_solve(p, y, spec: SolverSpec, *, a0=None, key=None,
                  placement=None, mesh=None, donate=None):
    record_dispatch("xla", method="normal")
    return _normal_kernel(p.x_pad, y, jnp.float32(spec.ridge), spec.max_iter)


# ------------------------------------------------------------- registration
register_method(MethodEntry(
    name="bak", solve=_bak_solve, consumes=_ITER_FIELDS + ("order",),
    iterative=True, multi_rhs=True, batchable=True, shardable=False,
    blocked=False, prepare=_prep_bak, vmap_one=_bak_vmap_one,
    fallback="lstsq",
    summary="Algorithm 1: serial cyclic coordinate descent"))
register_method(MethodEntry(
    name="bakp", solve=_bakp_solve("jacobi"),
    consumes=_ITER_FIELDS + ("thr", "omega"),
    iterative=True, multi_rhs=True, batchable=True, shardable=True,
    blocked=True, prepare=_prep_bakp, vmap_one=_bakp_vmap_one("jacobi"),
    fallback="bakp_stream",
    summary="Algorithm 2: block-Jacobi coordinate descent"))
register_method(MethodEntry(
    name="bakp_gram", solve=_bakp_solve("gram"),
    consumes=_ITER_FIELDS + ("thr", "omega", "ridge"),
    iterative=True, multi_rhs=True, batchable=True, shardable=True,
    blocked=True, needs_chol=True, prepare=_prep_bakp_gram,
    vmap_one=_bakp_vmap_one("gram"), fallback="bakp",
    summary="exact block CD via cached block-Gram Cholesky (beyond-paper)"))
register_method(MethodEntry(
    name="bakp_fused", solve=_fused_method("bakp"),
    consumes=_ITER_FIELDS + ("thr", "omega", "precision", "refine_sweeps"),
    iterative=True, multi_rhs=True, batchable=False, shardable=False,
    blocked=True, precisions=("fp32", "bf16", "bf16_fp32acc"),
    lane="fused", prepare=_prep_fused, fallback="bakp",
    summary="Algorithm 2 on the fused whole-solve Pallas megakernel "
            "(VMEM-resident sweeps, on-chip convergence; XLA fallback "
            "when the design exceeds the VMEM budget; bf16 X streaming "
            "with fp32 accumulators + fp32 polish)"))
register_method(MethodEntry(
    name="bak_fused", solve=_fused_method("bak"),
    consumes=_ITER_FIELDS + ("thr", "precision", "refine_sweeps"),
    iterative=True, multi_rhs=True, batchable=False, shardable=False,
    blocked=True, precisions=("fp32", "bf16", "bf16_fp32acc"),
    lane="fused", prepare=_prep_fused, fallback="bak",
    summary="Algorithm 1 on the fused megakernel (sequential column "
            "order; XLA fallback when over the VMEM budget; bf16 X "
            "streaming with fp32 accumulators + fp32 polish)"))
register_method(MethodEntry(
    name="bakp_stream", solve=_stream_solve_method,
    consumes=_ITER_FIELDS + ("thr", "omega", "precision"),
    iterative=True, multi_rhs=True, batchable=False, shardable=False,
    blocked=True, streams=True, precisions=("fp32", "bf16"),
    lane="stream", prepare=_prep_stream, fallback="lstsq",
    summary="Algorithm 2 streaming out-of-core: x tiles double-buffered "
            "from HBM (pl.ANY) through VMEM scratch, or fetched "
            "per-block through the design store's host/disk tiers for "
            "non-resident designs"))
register_method(MethodEntry(
    name="lstsq", solve=_lstsq_solve, consumes=(),
    iterative=False, multi_rhs=True,
    summary="LAPACK lstsq baseline (the paper's comparison column)"))
register_method(MethodEntry(
    name="normal", solve=_normal_solve, consumes=("ridge",),
    iterative=False, multi_rhs=True, fallback="lstsq",
    summary="normal-equation Cholesky with SolverSpec.ridge diagonal"))
register_method(MethodEntry(
    name="bakf", solve=_bakf_solve, consumes=("max_iter", "thr"),
    iterative=False, multi_rhs=False,
    summary="Algorithm 3 to full selection: greedy forward CD + refit"))
