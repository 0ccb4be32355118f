"""The plain reference a cell's answers are compared with, and its control.

The reference solves each least-squares system ``min_a |x a - y|`` on the
host in float64, all right-hand sides at once, by whichever of two plain
methods costs fewer operations for the shape (``lstsq_f64``): the normal
equations, with the Gram matrix ``x^T x`` formed once, for a design
narrow against the number of right-hand sides; else CGLS (conjugate
gradients on the normal equations, Hestenes-Stiefel form), which stops
when every column's normal-equation gradient ``|x^T (y - x a)|`` has
fallen below ``tol`` times its start.  For the well-conditioned Gaussian
designs of the cells either bounds the error of the answer near float64
rounding.  It imports nothing of the program and takes nothing the
program made: it reads the same seeded float32 design and right-hand
sides the requests carry.

The control is the same algorithm in float32 on the accelerator, with its
matrix products at ``HIGH`` precision (three bfloat16 passes): the next
precision below what the configurations state (float32 at ``HIGHEST``).
``bench/inject.py`` puts it in the program's place to show that the
comparison fails it.
"""
from __future__ import annotations

import functools

import numpy as np

TOL = 1e-13
MAX_ITER = 1000
# The fewest sweeps CGLS takes to TOL on the cells' designs: forming the
# Gram matrix (obs * vars**2) is cheaper than that many CGLS iterations
# (4 * obs * vars * count each) when vars < 4 * CGLS_ITERS * count.
CGLS_ITERS = 10


def lstsq_f64(x: np.ndarray, ys: np.ndarray):
    """Float64 least-squares solutions of ``x`` (obs, vars) against each
    row of ``ys`` (count, obs), by the normal equations or by CGLS,
    whichever takes fewer operations.  Returns ``(coefs, rel_grad,
    iters)`` as ``cgls_f64`` does; from the normal equations ``rel_grad``
    is the largest ``|x^T (y - x a)| / |x^T y|``, computed from ``x``, and
    ``iters`` is 0."""
    x64 = np.asarray(x, np.float64)
    y64 = np.asarray(ys, np.float64)
    if x64.shape[1] >= 4 * CGLS_ITERS * y64.shape[0]:
        return cgls_f64(x64, y64)
    xty = x64.T @ y64.T                                # (vars, count)
    a = np.linalg.solve(x64.T @ x64, xty)
    grad = x64.T @ (y64.T - x64 @ a)
    rel = np.linalg.norm(grad, axis=0) / np.linalg.norm(xty, axis=0)
    return a.T, float(np.max(rel)), 0


def cgls_f64(x: np.ndarray, ys: np.ndarray, *, tol: float = TOL,
             max_iter: int = MAX_ITER):
    """Float64 least-squares solutions of ``x`` against each row of ``ys``.

    ``x`` is (obs, vars) in any float dtype (copied to float64 unless it
    is already);
    ``ys`` is (count, obs).  Returns ``(coefs, rel_grad, iters)``: the
    (count, vars) float64 solutions, the largest final relative gradient
    norm over the columns, and the iterations taken.
    """
    x64 = np.asarray(x, np.float64)
    r = np.asarray(ys, np.float64).T.copy()          # (obs, count)
    a = np.zeros((x64.shape[1], r.shape[1]))
    s = x64.T @ r
    p = s.copy()
    gamma = np.einsum("ij,ij->j", s, s)
    g0 = np.sqrt(gamma)
    rel = np.ones_like(g0)
    it = 0
    while it < max_iter and np.max(rel) > tol:
        it += 1
        q = x64 @ p
        alpha = gamma / np.einsum("ij,ij->j", q, q)
        a += alpha * p
        r -= alpha * q
        s = x64.T @ r
        gamma_new = np.einsum("ij,ij->j", s, s)
        rel = np.sqrt(gamma_new) / g0
        p = s + (gamma_new / gamma) * p
        gamma = gamma_new
    return a.T, float(np.max(rel)), it


def rel_errors(coefs, refs) -> np.ndarray:
    """Per-answer ``|a - a_ref|_2 / |a_ref|_2`` (rows are answers)."""
    c = np.asarray(coefs, np.float64)
    r = np.asarray(refs, np.float64)
    return np.linalg.norm(c - r, axis=1) / np.linalg.norm(r, axis=1)


def product(precision: str):
    """A float32 matrix product at ``precision``, spelled out so that it
    means the same on every backend: operands split into bfloat16 parts,
    the parts multiplied exactly and summed in float32.  ``"high"`` keeps
    three of the partial products (hi*hi + hi*lo + lo*hi), as the TPU's
    ``Precision.HIGH`` does; ``"default"`` keeps one (hi*hi); ``"highest"``
    is the float32 product itself.

    The parts are cut with ``lax.reduce_precision``, which XLA keeps: a
    float32 -> bfloat16 -> float32 round trip it may drop as excess
    precision, leaving ``lo`` zero (on a TPU v5e a control spelled so read
    as a float32 product on one design and as a one-pass bfloat16 product
    on another).  They are held as bfloat16, exactly, so that the parts of
    a 4 GB design fit the chip beside it."""
    import jax.numpy as jnp
    from jax import lax

    def split(a):
        hi = lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        lo = lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

    def dot(a, b):
        return jnp.matmul(a, b, preferred_element_type=jnp.float32)

    def mm(a, b):
        if precision == "highest":
            return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)
        a_hi, a_lo = split(a)
        b_hi, b_lo = split(b)
        out = dot(a_hi, b_hi)
        if precision == "high":
            out = out + (dot(a_hi, b_lo) + dot(a_lo, b_hi))
        elif precision != "default":
            raise ValueError(f"unknown precision {precision!r}")
        return out
    return mm


def cgls_device(x, y, *, precision: str = "high", tol: float = 1e-7,
                max_iter: int = 300):
    """The control: CGLS in float32 on the device with every matrix
    product at ``precision`` (see ``product``).  It stops at a relative
    gradient of ``tol``, which float32 can reach (the reference's 1e-13
    it cannot: CGLS run on past its precision's floor blows up, which
    would show nothing about precision).  ``x`` is (obs, vars), ``y``
    (obs,) or (obs, k); returns ``(coef, residual, iters)`` shaped like
    ``y``."""
    return _cgls_program(precision, tol, max_iter)(x, y)


@functools.lru_cache(maxsize=None)
def _cgls_program(precision: str, tol: float, max_iter: int):
    """The jitted control, one per setting (traced once per shape)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    mm = product(precision)

    @jax.jit
    def run(x, y):
        y2 = y.reshape(y.shape[0], -1).astype(jnp.float32)
        s = mm(y2.T, x).T
        g0 = jnp.sqrt(jnp.sum(s * s, 0))
        g0 = jnp.where(g0 > 0, g0, 1.0)
        init = (0, jnp.zeros((x.shape[1], y2.shape[1]), jnp.float32), y2,
                s, jnp.sum(s * s, 0))

        def cond(st):
            it, _, _, _, gamma = st
            return (it < max_iter) & (jnp.max(jnp.sqrt(gamma) / g0) > tol)

        def body(st):
            it, a, r, p, gamma = st
            q = mm(x, p)
            qq = jnp.sum(q * q, 0)
            alpha = jnp.where(qq > 0, gamma / jnp.where(qq > 0, qq, 1.0), 0.0)
            a = a + alpha * p
            r = r - alpha * q
            s = mm(r.T, x).T
            gnew = jnp.sum(s * s, 0)
            beta = jnp.where(gamma > 0, gnew / jnp.where(gamma > 0, gamma,
                                                         1.0), 0.0)
            return it + 1, a, r, s + beta * p, gnew

        it, a, r, _, _ = lax.while_loop(cond, body, init)
        return a.reshape((x.shape[1],) + y.shape[1:]), r.reshape(y.shape), it

    return run
