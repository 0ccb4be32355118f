"""Shared result/record types for the BAK solver family."""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class SolveResult(NamedTuple):
    """Result of a linear-system solve.

    Attributes:
      coef:       (vars,) solution vector ``a`` with ``x @ a ≈ y``; for a
                  multi-RHS solve (``y`` of shape (obs, k)): (vars, k).
      residual:   (obs,) final residual ``e = y - x @ a`` (fp32); multi-RHS:
                  (obs, k).
      sse:        scalar fp32 sum of squared residuals at exit (multi-RHS:
                  total over all k systems).
      n_sweeps:   scalar int32, number of full sweeps executed.
      converged:  scalar bool, True if a tolerance criterion fired before
                  ``max_iter`` was exhausted.
      history:    (max_iter,) fp32 SSE after each sweep (NaN for sweeps not
                  executed).  Used by the convergence benchmarks/tests; the
                  paper's Theorem 1 asserts this sequence is non-increasing.
    """

    coef: jax.Array
    residual: jax.Array
    sse: jax.Array
    n_sweeps: jax.Array
    converged: jax.Array
    history: jax.Array


class SelectResult(NamedTuple):
    """Result of SolveBakF greedy feature selection.

    Attributes:
      selected:  (max_feat,) int32 indices of selected columns, in selection
                 order.
      coef:      (max_feat,) fp32 coefficients of the refit on the selected
                 columns (aligned with ``selected``).
      sse_path:  (max_feat,) fp32 SSE after each selection + refit step — the
                 greedy error-reduction path.
      residual:  (obs,) fp32 final residual.
    """

    selected: jax.Array
    coef: jax.Array
    sse_path: jax.Array
    residual: jax.Array


def fp32_matmuls(fn):
    """Trace ``fn`` with float32 matrix-multiply precision.

    On TPU the default precision runs a float32 matmul in one bfloat16 pass
    (about three significant digits), which would cap every solver's
    residual — and so its coefficients — near 1e-3 relative error.  The
    solvers' dots, norms and SSE reductions therefore all run at
    ``"highest"`` (six bfloat16 passes on the MXU; no effect on CPU).
    Applied to the functions jit traces, so the setting is baked into the
    compiled programs whatever the caller's configuration.
    """
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


@fp32_matmuls
def column_norms_sq(x: jax.Array) -> jax.Array:
    """Squared column norms ``⟨x_j, x_j⟩`` accumulated in fp32, shape (vars,).

    ``preferred_element_type`` forces the *accumulator* to fp32 even for a
    bf16 design — an in-dtype accumulation would lose norm accuracy that
    ``safe_inv``/``inv_cn`` then amplifies in every sweep's update.
    """
    return jnp.einsum("ij,ij->j", x, x,
                      preferred_element_type=jnp.float32)


@fp32_matmuls
def column_norms_sq_t(x_t: jax.Array) -> jax.Array:
    """``column_norms_sq`` on the TRANSPOSED (vars, obs) kernel layout.

    A paper-"column" is a contiguous row of ``x_t``, so the norms reduce
    over the trailing (obs) axis directly — no ``x_t.T`` materialisation,
    which for the kernel wrappers used to be a full (obs, vars) relayout
    just to throw it away after one reduction.  Accumulates in fp32
    regardless of input dtype (see ``column_norms_sq``).
    """
    return jnp.einsum("vo,vo->v", x_t, x_t,
                      preferred_element_type=jnp.float32)


def safe_inv(cn: jax.Array) -> jax.Array:
    """1/cn with zero (not inf) for zero-norm columns.

    A zero column can never reduce the residual, so the paper's ``da`` is
    defined as 0 for it; this keeps the update well-posed.
    """
    return jnp.where(cn > 0.0, 1.0 / jnp.where(cn > 0.0, cn, 1.0), 0.0)


def donate_default(donate, *operands) -> bool:
    """Shared buffer-donation default for the jitted solver entry points.

    Auto-donation must be safe for every caller, so it fires only when ALL
    of the following hold — an explicit ``donate`` always wins:

      * accelerator backend (the CPU backend cannot donate; requesting it
        just emits warnings);
      * top level (a re-entrant call under vmap / shard_map / an outer jit
        cannot consume donations);
      * every donatable ``operand`` is a HOST buffer (numpy / None), whose
        device transfer inside the jit is fresh by construction — nobody
        else can hold it.  A ``jax.Array`` operand is never auto-donated:
        the caller may reuse it (benchmarks re-solving one ``y``, parity
        loops), and a deleted-buffer crash is worse than a copy.  The
        serving engine hands a single solve host buffers, so that solve
        donates; a coalesced group's ``y`` is a device operand the engine
        owns, and it passes ``donate=True``.  Pass that for any device
        operand you own.
    """
    if donate is not None:
        return bool(donate)
    return (jax.default_backend() != "cpu"
            and jax.core.trace_ctx.is_top_level()
            and not any(isinstance(op, jax.Array) for op in operands))


def warm_retention_ok(res: "SolveResult") -> bool:
    """Whether a solve's coefficients are safe to retain as a warm start.

    False exactly when the solve looks *diverged*: ``converged`` is False
    AND its recorded SSE history net-rose (last finite entry materially
    above the first — the geometric blow-up ``sweep_stop_flags`` classifies
    as genuine divergence).  Plain budget exhaustion (``converged=False``
    with the non-increasing history Theorem 1 guarantees, e.g. ``rtol=0``
    runs that simply spent ``max_iter``) still retains — those coefficients
    are the best seen and warm-starting from them is the whole point.

    A diverged solve's coefficients, by contrast, are *worse than zero*:
    retaining them poisons the tenant's next warm start into starting from
    the blown-up point (and likely diverging again).  Both retention sites
    gate on this — ``PreparedDesign.solve``'s tenant store and the serving
    engine's ``_strip``.

    Scalar (single/multi-RHS group) flags only; a batched ``converged``
    (the vmapped path) returns True and the caller gates per row.
    """
    try:
        conv = np.asarray(res.converged)
        if conv.ndim != 0 or bool(conv):
            return True
        h = np.asarray(res.history, np.float32).ravel()
        h = h[np.isfinite(h)]
        if h.size >= 2 and float(h[-1]) > 1.01 * float(h[0]):
            return False
    except Exception:
        return True  # malformed/absent history: keep the old behaviour
    return True


def sweep_stop_flags(sse, sse_prev, sse0, atol_sse, rtol):
    """Per-sweep stopping decision shared by every iterative solver.

    Returns ``(converged, stop)``:

      * ``stop`` — the loop should exit: the absolute tolerance fired, the
        sweep improved SSE by less than ``rtol * sse_prev``, or SSE *rose*
        (no further progress is coming from more sweeps either way).
      * ``converged`` — whether that exit may be reported as success.  An
        SSE rise splits on net progress: staying at/near the starting
        ``sse0`` (within a 1% band — float-accumulation jitter, e.g. a cold
        run stalled at its accuracy floor or a warm start that was already
        at the fixed point) is a stall and reports True exactly like the
        classic rtol exit, while ending materially *above* ``sse0`` is
        genuine divergence (Jacobi-within-block with correlated columns /
        too-large ω blows up geometrically, so it clears the band within a
        sweep) and reports False.  Without the distinction,
        ``(sse_prev - sse) <= rtol * sse_prev`` is trivially true for any
        SSE increase and a diverging solve would stop after one sweep
        claiming success.

    With ``rtol == 0`` the relative/divergence checks are off (the solve
    runs its full ``max_iter`` budget exactly as before).
    """
    improved = sse <= sse_prev
    hit_atol = (atol_sse > 0.0) & (sse <= atol_sse)
    hit_rtol = (rtol > 0.0) & improved & ((sse_prev - sse) <= rtol * sse_prev)
    rose = (rtol > 0.0) & ~improved
    converged = hit_atol | hit_rtol | (rose & (sse <= 1.01 * sse0))
    return converged, hit_atol | hit_rtol | rose
