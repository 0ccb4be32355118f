"""The general traffic generator: what each request is, and when it is sent.

A traffic mix is a data file, ``bench/traffic/<mix>.json``:

* ``loop``: ``"open"`` (requests are due on a schedule, whether or not
  earlier ones have finished: independent callers) or ``"closed"``
  (``clients`` callers, each sending its next request when its previous
  answer arrives);
* ``rate_per_s`` and ``arrivals_seed`` (open): the offered rate, and the
  seed of the arrival schedule.  A run of ``seconds`` sends exactly
  ``round(rate_per_s * seconds)`` requests at times drawn uniformly over
  the window and sorted (a Poisson process conditioned on its count).  The
  schedule comes from ``arrivals_seed``, not from the run's seed: near
  capacity the tail latency follows the schedule's bursts more than
  anything the program does, so every run offers the same arrivals, and
  the run's seed draws the data and which target arrives when;
* ``clients`` (closed): the number of callers;
* ``pool``: how many distinct right-hand sides each design has for the
  requests to draw from (as ``default_plan`` draws them, unless the mix's
  ``plan`` says otherwise);
* ``data_seed`` (optional): the seed the designs and their targets are
  made from, in place of the run's.  The sweeps a solve takes follow its
  data, so with it every run serves the same work, and the run's seed
  only orders the requests;
* ``deadline_s`` (optional): each request's deadline, from its due time.

A mix that needs more than these parameters brings a file of its own,
``bench/traffic/<mix>.py``, beside its ``.json``.  It may define either
hook or both, and the harness uses each in place of the default here:

* ``offsets(rng, traffic, seconds)``: the open loop's due times, in place
  of ``open_offsets``;
* ``plan(rng, traffic, n, designs)``: what the next ``n`` requests are,
  a list of ``Pick``s (which design, which of its targets, which tenant),
  in place of ``default_plan``.  The open loop draws its whole plan at
  once; each closed-loop client draws ``pool`` requests at a time;
* ``warm_groups(traffic, designs, max_batch)``: the groups of ``Pick``s
  set-up serves (twice) so that the window compiles nothing, in place of
  ``shape_groups``: a mix whose plan forms other groups (several designs
  in one, say) warms them here.
"""
from __future__ import annotations

import importlib.util
import pathlib
from dataclasses import dataclass
from typing import Optional

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use of the seed."""
    return np.random.default_rng([seed, 100 + stream])


def open_offsets(rng: np.random.Generator, traffic: dict,
                 seconds: float) -> np.ndarray:
    """Sorted due times (seconds from window start) of an open loop."""
    n = int(round(float(traffic["rate_per_s"]) * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


@dataclass(frozen=True)
class Pick:
    """What one request is: an index into the cell's designs, one into that
    design's targets, and the caller's tenant id (warm starts), if any."""

    design: int
    rhs: int
    tenant: Optional[str] = None


def default_plan(rng: np.random.Generator, traffic: dict, n: int,
                 designs: list) -> list:
    """``n`` requests, no tenant.  With one design, its targets in turn
    (each ``n // pool`` times or once more) in an order drawn from ``rng``:
    the sweeps a target takes vary, and an even spread over the pool keeps
    that from changing a window's work from seed to seed.  With several
    designs, a design drawn uniformly, then one of its targets."""
    if len(designs) == 1:
        pool = len(designs[0].ys)
        return [Pick(0, int(p))
                for p in rng.permutation(np.arange(n) % pool)]
    return [Pick(int(d), int(rng.integers(len(designs[d].ys))))
            for d in rng.integers(len(designs), size=n)]


def hook(mix: str, name: str, default, traffic_dir=TRAFFIC_DIR):
    """``mix``'s own ``name`` from ``bench/traffic/<mix>.py``, if that file
    defines it; else ``default``."""
    path = pathlib.Path(traffic_dir) / f"{mix}.py"
    if not path.exists():
        return default
    spec = importlib.util.spec_from_file_location(f"bench_traffic_{mix}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, name, default)


def coalesced_sizes(traffic: dict, max_batch: int) -> list:
    """The padded group sizes (k) the traffic can make the dispatcher
    coalesce: 1 and every power of two up to the largest group."""
    if traffic["loop"] == "closed":
        largest = min(int(traffic["clients"]), max_batch)
    else:
        largest = max_batch
    sizes, k = [], 1
    while k < largest:
        sizes.append(k)
        k *= 2
    return sizes + [k]


def shape_groups(traffic: dict, designs: list, max_batch: int) -> list:
    """For the first design of each shape, one group of every size the
    traffic can make the dispatcher coalesce."""
    firsts: dict = {}
    for i, d in enumerate(designs):
        firsts.setdefault(d.x.shape, i)
    return [[Pick(i, j % len(designs[i].ys)) for j in range(k)]
            for i in firsts.values()
            for k in coalesced_sizes(traffic, max_batch)]
