"""The least work a least-squares solve needs, and the least time for it.

The count is of the algorithm, not of any implementation, so it is the same
whatever serves the solve: a coalesced group of ``k`` right-hand sides over
the request's own (unpadded) ``obs x vars`` float32 design, for ``sweeps``
sweeps of block coordinate descent.  Per sweep:

* flops: ``4 * obs * vars * k``: the inner products ``x^T e`` and the
  residual correction ``e -= x da``, two multiply-adds per element each;
* bytes: the residual read and written (``2 * obs * k * 4``), and ``x``
  read once, or, where ``x`` fits the chip's VMEM, once for the whole solve.

Padding to the serving buckets is not counted: it is waste the roofline
share is there to show.  ``peaks.json`` holds each chip's peaks by JAX's
``device_kind``; a chip missing from it is an error, never a default.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def peaks(device_kind: str, path=PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; KeyError when the table lacks it."""
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def least_work(obs: int, nvars: int, k: int, sweeps: int, *,
               vmem_bytes: float = 0.0, chips: int = 1, itemsize: int = F32):
    """(flops, bytes) the solve cannot do with less of, over all ``chips``
    (each holds ``obs / chips`` rows of ``x``)."""
    x_bytes = obs * nvars * itemsize
    x_reads = 1 if x_bytes <= chips * vmem_bytes else sweeps
    nbytes = x_reads * x_bytes + sweeps * 2 * obs * k * F32
    flops = sweeps * 4 * obs * nvars * k
    return float(flops), float(nbytes)


def least_time_s(flops: float, nbytes: float, peak: dict,
                 chips: int = 1) -> float:
    """Roofline time on ``chips`` chips sharing the work evenly.  The
    matrix unit's bf16 peak bounds every float32 product from below."""
    return max(flops / (chips * peak["bf16_flops_per_s"]),
               nbytes / (chips * peak["hbm_bytes_per_s"]))
