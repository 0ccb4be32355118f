"""Things put in the program's place to show that the check catches them.

Each is a context manager for ``harness.run(..., solve_patch=...)``: it
replaces ``PreparedDesign.solve`` (the call every served solve goes
through) or a piece under it for the set-up and the window, and restores it
after.  The served path around it (dispatcher, batching, lanes, padding
and stripping) runs as it is.

* ``control``: the reference's algorithm in float32 one precision below the
  configuration's (``bench/reference.py``), answering in the program's
  place.  The check has to fail it.
* the faults a cell of this benchmark can have: a solve that returns its
  starting state unchanged; a coalesced group of which only half is
  solved; the exchange between chips left out of the sharded solve; one
  answer altered where it is produced.
"""
from __future__ import annotations

import contextlib
import functools


@contextlib.contextmanager
def _replace_solve(make):
    """Patch ``PreparedDesign.solve`` with ``make(orig)``."""
    from repro.core.prepare import PreparedDesign

    orig = PreparedDesign.solve
    PreparedDesign.solve = make(orig)
    try:
        yield
    finally:
        PreparedDesign.solve = orig


def _design(p, placement, mesh):
    if placement is not None and placement.sharded:
        return p.x_for_placement(placement, mesh or p.mesh)
    return p.x_pad


def control(path: str, precision: str = "high"):
    """CGLS in float32 at ``precision`` in the program's place; records
    ``path`` as the kernel path so that only its answers can fail."""
    def make(orig):
        def solve(self, y, a0=None, *, spec=None, key=None, tenant_id=None,
                  placement=None, mesh=None):
            import jax.numpy as jnp
            from repro import obs
            from repro.core.types import SolveResult

            from bench.reference import cgls_device

            spec = spec if spec is not None else self.spec
            coef, resid, it = cgls_device(
                _design(self, placement, mesh), jnp.asarray(y),
                precision=precision)
            obs.record_dispatch(path, method=spec.method)
            sse = jnp.sum(resid * resid)
            return SolveResult(coef=coef, residual=resid, sse=sse,
                               n_sweeps=it, converged=jnp.asarray(True),
                               history=jnp.reshape(sse, (1,)))
        return solve
    return _replace_solve(make)


def unchanged_state():
    """The solve hands back its starting point: zero coefficients, the
    right-hand side as residual."""
    def make(orig):
        def solve(self, y, a0=None, **kw):
            res = orig(self, y, a0, **kw)
            return res._replace(coef=res.coef * 0.0)
        return solve
    return _replace_solve(make)


def half_batch():
    """Only the first half of a coalesced group's right-hand sides is
    solved; the rest come back as zeros."""
    def make(orig):
        def solve(self, y, a0=None, **kw):
            import jax.numpy as jnp

            if getattr(y, "ndim", 1) != 2 or y.shape[1] < 2:
                return orig(self, y, a0, **kw)
            h = y.shape[1] // 2
            y = jnp.asarray(y)
            res = orig(self, y[:, :h], None if a0 is None else a0[:, :h],
                       **kw)
            pad = ((0, 0), (0, y.shape[1] - h))
            return res._replace(coef=jnp.pad(res.coef, pad),
                                residual=jnp.pad(res.residual, pad))
        return solve
    return _replace_solve(make)


def altered_answer():
    """The sign of one coefficient of every answer flips as it is made."""
    def make(orig):
        def solve(self, y, a0=None, **kw):
            res = orig(self, y, a0, **kw)
            return res._replace(coef=res.coef.at[0].multiply(-1.0))
        return solve
    return _replace_solve(make)


@contextlib.contextmanager
def no_exchange():
    """The sharded solve's cross-chip sums are left out: each chip works
    from its own rows' inner products alone."""
    from repro.core import distributed

    orig = distributed._psum
    distributed._psum = lambda v, axes: v
    clear = [f for f in vars(distributed).values()
             if isinstance(f, functools._lru_cache_wrapper)]
    for f in clear:
        f.cache_clear()
    try:
        yield
    finally:
        distributed._psum = orig
        for f in clear:
            f.cache_clear()


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "no_exchange": no_exchange, "altered_answer": altered_answer}
