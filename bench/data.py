"""Seeded inputs of a cell: designs, each with a pool of right-hand sides.

A configuration names its design generator (``"design": "<kind>"``), a
module ``bench/designs/<kind>.py`` whose ``make(seed, config, pool)``
returns a list of ``Design``s built from the pieces here.  Everything here
is numpy on the host and depends only on the seed (the targets' last bits
also on the host BLAS's summation order).  A Gaussian design is a standard
normal float32 matrix.  Each right-hand side is ``x @ a`` for a
planted coefficient vector ``a`` with ``|a_j|`` in [1, 2], plus Gaussian
noise of the size the configuration states (``target_noise``, relative to
``|x @ a|``), in float32.  With noise the systems are inconsistent, so the
answer depends on every row: a solve that drops rows, or a chip's share
of them, gives another answer.  The reference
solves each system as it is served; the planted vectors are never the
answer the check compares against.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np


@dataclass
class Design:
    """One design a cell serves: its ``design_key``, the host matrix the
    requests carry, and the (pool, obs) float32 targets they draw from."""

    key: str
    x: np.ndarray
    ys: np.ndarray


def _threads() -> int:
    return max(1, min(16, os.cpu_count() or 1))


def gaussian(seed: int, shape, *, chunks: int = 64) -> np.ndarray:
    """Standard normal float32 array of ``shape``, filled in ``chunks`` row
    ranges on a thread pool (numpy's generators release the GIL).  The
    result depends on the seed and ``chunks`` only, not on the pool."""
    out = np.empty(shape, np.float32)
    chunks = max(1, min(chunks, shape[0]))
    bounds = np.linspace(0, shape[0], chunks + 1).astype(int)
    seqs = np.random.SeedSequence([seed, 1]).spawn(chunks)

    def fill(i):
        np.random.default_rng(seqs[i]).standard_normal(
            dtype=np.float32, out=out[bounds[i]:bounds[i + 1]])

    with ThreadPoolExecutor(_threads()) as pool:
        list(pool.map(fill, range(chunks)))
    return out


def planted(seed: int, nvars: int, count: int) -> np.ndarray:
    """(count, nvars) float64 coefficient vectors, ``|a_j|`` in [1, 2]."""
    rng = np.random.default_rng([seed, 2])
    mag = rng.uniform(1.0, 2.0, (count, nvars))
    return mag * rng.choice([-1.0, 1.0], (count, nvars))


def rhs_pool(x: np.ndarray, coefs: np.ndarray, *, seed: int = 0,
             noise: float = 0.0) -> np.ndarray:
    """(count, obs) float32 right-hand sides ``x @ a + e`` for each row
    ``a`` of ``coefs``.  ``e`` is Gaussian noise whose norm is ``noise``
    times that of ``x @ a`` (none by default: consistent systems)."""
    out = np.ascontiguousarray(coefs.astype(np.float32) @ x.T)
    if noise:
        rng = np.random.default_rng([seed, 3])
        for row in out:
            e = rng.standard_normal(row.shape[0], dtype=np.float32)
            row += (noise * np.linalg.norm(row) / np.linalg.norm(e)) * e
    return out
