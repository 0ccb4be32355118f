"""Shared fixtures.  NOTE: no XLA device-count flags here by design — smoke
tests and benches must see the single real CPU device; only the dry-run
(repro.launch.dryrun) forces 512 host devices, and the distributed-solver
tests spawn subprocesses with their own flags."""
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def make_system(rng, obs, nvars, noise=0.0, dtype=np.float32):
    """Random consistent (or noisy) linear system."""
    x = rng.normal(size=(obs, nvars)).astype(dtype)
    a = rng.normal(size=(nvars,)).astype(dtype)
    y = x @ a
    if noise:
        y = y + noise * rng.normal(size=obs).astype(dtype)
    return x, y, a


def blocked_system(seed, k, obs=2048, nvars=150):
    """Noisy system whose vars (150) is a multiple of neither 48 nor 128,
    with its float64 least-squares solution."""
    r = np.random.default_rng(seed)
    x = r.normal(size=(obs, nvars)).astype(np.float32)
    y = (x @ r.normal(size=(nvars, k)).astype(np.float32)
         + 1e-3 * r.normal(size=(obs, k)).astype(np.float32))
    ref = np.linalg.lstsq(x.astype(np.float64), y.astype(np.float64),
                          rcond=None)[0]
    a0 = (ref + 0.05 * r.normal(size=ref.shape)).astype(np.float32)
    if k == 1:
        y, ref, a0 = y[:, 0], ref[:, 0], a0[:, 0]
    return x, y, ref, a0
