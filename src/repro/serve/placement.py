"""Placement layer — routing serving buckets onto mesh-sharded solvers.

The engine's compiled programs are keyed by (bucket, solver config); this
module adds the *where*: a ``Placement`` names which backend a bucket's
solves run on, a ``PlacementPolicy`` picks one per bucket from its padded
size, and a ``ServeMesh`` wraps the jax device mesh the sharded placements
run over.  Placement is part of the engine's grouping key, so a compiled
program only ever sees one mesh layout — single-device and sharded solves
never mix inside a batch.

Placements (backends in ``repro.core.distributed``):

  * ``single``       — the jit'd single-device solver family (default; the
                       only placement when the engine has no mesh).
  * ``obs_sharded``  — ``solvebakp_obs_sharded``: design rows shard over the
                       mesh data axes.  Chosen when a bucket's padded
                       ``obs_p × vars_p`` cell count crosses
                       ``obs_shard_min_cells`` — the regime where one
                       device's HBM stream is the bottleneck (or the design
                       no longer fits).  Per-device memory: shard +
                       O(obs/D + vars) overhead.
  * ``rhs_sharded``  — ``solvebakp_rhs_sharded``: a giant same-design
                       multi-RHS group's ``k`` axis shards over the data
                       devices, ``x`` replicated — one stream of ``x`` per
                       device serves k/D tenants, and the group-global SSE
                       stopping keeps results bit-comparable with the
                       single-device coalesced solve.  Chosen per *group*
                       (k is only known after design coalescing) when
                       ``k_pad >= rhs_shard_min_k``.
  * ``mesh_2d``      — ``solvebakp_2d``: rows over data axes AND columns
                       over the model axis; pod-scale designs.  Off by
                       default (``mesh_2d_min_cells=None``) because its
                       cross-device Jacobi block ordering changes the
                       iterates (needs ω damping) — opt in for buckets too
                       wide for a replicated coefficient vector.

Eligibility guards: sharded placements only apply to the block solvers
("bakp"/"bakp_gram" — the distributed backends are SolveBakP-shaped) and
only when the padded bucket divides the mesh axes (power-of-two buckets on
power-of-two meshes, so in practice: bucket at least as large as the axis).
Everything else falls back to ``single``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

import repro.core.methods  # noqa: F401  (populates the method registry)
from repro import obs
from repro.core.spec import is_registered, solver_method

# Module-level hooks record to the global default registry (placement is a
# pure function, not owned by one engine; per-engine routing detail is on
# serve_solves_total{placement=...}).
_m_decisions = obs.default_registry().counter(
    "serve_placement_decisions_total",
    "placement routing decisions, by level and chosen kind")


def _is_shardable(method: str) -> bool:
    """A method is placement-eligible iff its registry entry says so —
    third-party backends registered ``shardable=True`` route like the
    built-in SolveBakP family without touching this module.  O(1): this
    runs once per request in the grouping hot path."""
    return is_registered(method) and solver_method(method).shardable


@dataclass(frozen=True)
class Placement:
    """Where a bucket's solves run.  Frozen/hashable: part of group keys."""

    kind: str = "single"  # single | obs_sharded | rhs_sharded | mesh_2d

    @property
    def sharded(self) -> bool:
        return self.kind != "single"

    def lane_key(self, method: str) -> str:
        """Stable execution-lane identity for this placement + method.

        Sharded placements each get their own lane (one compiled mesh
        layout per lane, so programs never migrate); single-device solves
        split by the method's registry ``lane`` capability ("xla" vs the
        Pallas "fused" path — distinct compiled-program families that
        would otherwise serialise behind each other).  The device-set half
        of the identity lives on ``repro.serve.lanes.LaneKey``; this
        string is the kind half, shared by the grouping/config key and the
        per-lane metrics labels.
        """
        if self.sharded:
            return f"mesh:{self.kind}"
        lane = (solver_method(method).lane if is_registered(method)
                else "xla")
        return f"single:{lane}"


SINGLE = Placement("single")
OBS_SHARDED = Placement("obs_sharded")
RHS_SHARDED = Placement("rhs_sharded")
MESH_2D = Placement("mesh_2d")


@dataclass(frozen=True)
class PlacementPolicy:
    """Size thresholds mapping buckets/groups onto placements.

    Attributes:
      obs_shard_min_cells: padded ``obs_p * vars_p`` at or above which a
        bucket's solves route to the obs-sharded backend.  The default
        (2²¹ ≈ 2M cells ≈ 8 MB fp32) is sized for real accelerators; tests
        and CPU-mesh benchmarks pass something tiny to force the path.
      rhs_shard_min_k: padded RHS count at or above which a same-design
        multi-RHS group in a ``single`` bucket upgrades to the k-sharded
        backend (requires ``k_pad`` divisible by the data axes product).
      mesh_2d_min_cells: cell count at or above which a bucket routes to
        the 2-D mesh backend instead of obs-sharded (needs a model axis).
        None (default) disables 2-D placement — see module docstring.
    """

    obs_shard_min_cells: int = 1 << 21
    rhs_shard_min_k: int = 32
    mesh_2d_min_cells: Optional[int] = None


@dataclass(frozen=True)
class ServeMesh:
    """The engine's device mesh + the axis names the backends shard over."""

    mesh: object                       # jax.sharding.Mesh
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: Optional[str] = None

    @property
    def data_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.data_axes]))

    @property
    def model_size(self) -> int:
        return int(self.mesh.shape[self.model_axis]) if self.model_axis else 1

    def describe(self) -> str:
        axes = ", ".join(f"{a}={self.mesh.shape[a]}"
                         for a in self.mesh.axis_names)
        return f"ServeMesh({axes})"


def build_serve_mesh(spec: str) -> ServeMesh:
    """Build a ``ServeMesh`` from a ``"D"`` or ``"DxM"`` spec string.

    ``"8"`` → a 1-D (data=8) mesh; ``"4x2"`` → (data=4, model=2).  The
    total must not exceed the visible device count (on CPU, force virtual
    devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    *before* importing jax — ``repro.launch.solver_serve --mesh`` does this
    for you).
    """
    # Imported lazily: building a mesh is the first jax touch here.
    from repro.launch.mesh import make_mesh

    parts = [int(p) for p in spec.lower().split("x")]
    if not parts or any(p < 1 for p in parts) or len(parts) > 2:
        raise ValueError(f"mesh spec must be 'D' or 'DxM', got {spec!r}")
    if len(parts) == 1 or parts[1] == 1:
        mesh = make_mesh((parts[0],), ("data",))
        return ServeMesh(mesh=mesh, data_axes=("data",), model_axis=None)
    mesh = make_mesh(tuple(parts), ("data", "model"))
    return ServeMesh(mesh=mesh, data_axes=("data",), model_axis="model")


def mesh_device_count(spec: str) -> int:
    """Devices a ``"D"``/``"DxM"`` spec needs (no jax import)."""
    return int(np.prod([int(p) for p in spec.lower().split("x")]))


def placement_for_bucket(bucket: Tuple[int, int], method: str,
                         policy: PlacementPolicy,
                         smesh: Optional[ServeMesh]) -> Placement:
    """Bucket-level placement (known before design coalescing)."""
    chosen = SINGLE
    if smesh is not None and _is_shardable(method):
        obs_p, vars_p = bucket
        cells = obs_p * vars_p
        if (policy.mesh_2d_min_cells is not None
                and cells >= policy.mesh_2d_min_cells
                and smesh.model_size > 1
                and obs_p % smesh.data_size == 0
                and vars_p % smesh.model_size == 0):
            chosen = MESH_2D
        elif (cells >= policy.obs_shard_min_cells
                and obs_p % smesh.data_size == 0):
            chosen = OBS_SHARDED
    _m_decisions.inc(1, level="bucket", kind=chosen.kind)
    return chosen


def placement_for_group(base: Placement, k_pad: int,
                        policy: PlacementPolicy,
                        smesh: Optional[ServeMesh]) -> Placement:
    """Group-level upgrade: a big-k same-design group in a single-device
    bucket shards its RHS axis instead (obs-/2-D-sharded buckets already
    span the mesh, so they keep their bucket placement)."""
    if (smesh is not None and base.kind == "single"
            and k_pad >= policy.rhs_shard_min_k
            and k_pad % smesh.data_size == 0):
        _m_decisions.inc(1, level="group", kind=RHS_SHARDED.kind)
        return RHS_SHARDED
    return base
