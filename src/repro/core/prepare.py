"""prepare()/PreparedDesign — the design-handle half of the solver API.

The paper's central structural property is that one sweep streams each
element of ``x`` exactly once, while everything reusable about the design is
computable up front: squared column norms (Algorithm 1 line 3), block Gram
Cholesky factors (``mode="gram"``), and — in a serving system — the
device-resident (possibly mesh-sharded) copies of ``x`` itself.  Related
direct/sketching baselines make the same split (factor once, solve per RHS);
here it is first-class:

    spec = SolverSpec(method="bakp_gram", rtol=1e-8)
    design = prepare(x, spec)            # once per design matrix
    res1 = design.solve(y1)              # cheap per-RHS solves
    res2 = design.solve(y2, a0=res1.coef)  # warm-started re-solve

``PreparedDesign`` owns, per design matrix:

  * the device-resident fp32 copy of ``x`` (``x_pad`` — callers may hand in
    an already shape-padded matrix, as the serving engine does);
  * its content ``fingerprint`` (identity for caches and request coalescing);
  * the squared column norms, plus thr-padded layouts per block width and
    their inverses (``inv_cn_for`` — consumed directly by the fused
    megakernel);
  * the transposed padded device copy per block width (``x_t_for`` — the
    Pallas kernels' (vars, obs) layout, relayouted once and kept resident);
  * the quantized cache tier (``x_bf16_for`` — the same layout cast to
    bf16 once, streamed by mixed-precision solves at half the HBM traffic
    while accumulators stay fp32);
  * block Gram Cholesky factors per ``(thr, ridge)``;
  * per-placement sharded device copies (a mesh backend needs ``x`` laid out
    for its in_specs; the ``device_put`` happens once per placement);
  * an LRU of per-tenant warm-start coefficients (serving re-solves with
    drifting ``y`` start from the tenant's last solution);
  * device ownership for the serving lanes: a ``home`` placement kind
    (``bind_home`` — first-wins) plus the ``resident_lanes()`` summary of
    which per-lane tiers (fused transposed/bf16 copies, sharded mesh
    copies) are currently built; ``warm_lane_state`` warms all of them for
    one (spec, placement) off the lane threads.

All of that state is mutated lazily from multiple threads in the serving
path (the async dispatcher pre-warms entries while the solver thread reads
them), so every accessor takes the per-design ``_lock``; the lock is
per-design so a slow Cholesky build on one design never blocks another.

Compiled programs are cached keyed by (spec static knobs, operand shapes,
placement): the single-device kernels are ``jit``-cached, the mesh backends
``lru_cache`` their ``shard_map`` programs, and ``_solve_protocol`` below
memoises the per-(spec, placement) dispatch so a repeated solve re-enters
its compiled program without re-touching the registry.
"""
from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spec import (SolverSpec, UnsupportedSpecError,
                             ensure_precision_supported, solver_method,
                             streaming_methods)
from repro.core.types import (SolveResult, column_norms_sq, safe_inv,
                              warm_retention_ok)


def placement_sharding(placement, smesh):
    """The ``NamedSharding`` a sharded placement's backend takes ``x`` in:
    rows over the data axes (obs_sharded), replicated (rhs_sharded — the
    devices share x), or rows × columns (mesh_2d)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    if placement.kind == "obs_sharded":
        spec = P(smesh.data_axes, None)
    elif placement.kind == "rhs_sharded":
        spec = P(None, None)
    elif placement.kind == "mesh_2d":
        spec = P(smesh.data_axes, smesh.model_axis)
    else:
        raise ValueError(f"unknown placement kind {placement.kind!r}")
    return NamedSharding(smesh.mesh, spec)


def design_fingerprint(x, *, _prefix: str = "d") -> str:
    """Content fingerprint of a design matrix (shape + dtype + bytes).

    Two matrices that hash equal are the same design: they may share one
    ``PreparedDesign`` (and, in serving, coalesce into one multi-RHS solve).
    """
    a = np.ascontiguousarray(np.asarray(x))
    h = hashlib.blake2b(digest_size=16)
    h.update(str((a.shape, a.dtype.str)).encode())
    h.update(a.view(np.uint8).data)
    return f"{_prefix}:{h.hexdigest()}"


@dataclass
class PreparedDesign:
    """Per-design solver state + the ``solve`` handle (see module doc).

    ``x_pad`` is the device-resident fp32 design exactly as prepared —
    callers that bucket-pad (the serving engine) hand the padded matrix in.
    All mutable members (``chol``, ``_cn``, ``_cn_thr``, ``_warm``,
    ``_sharded``) are read AND written from concurrent threads in the
    serving path, so every accessor takes the per-design ``_lock``.

    Program caching note: the compiled programs behind ``solve`` are cached
    one level down, keyed by exactly (spec static knobs, operand shapes,
    placement) — ``jit`` on the single-device kernels, ``lru_cache``d
    ``shard_map`` programs for the mesh backends — so a repeat solve
    re-enters its compiled executable; the registry lookup itself is a
    plain dict access, never memoised (a re-``register_method`` with
    ``overwrite=True`` takes effect immediately).
    """

    x_pad: Optional[jax.Array]            # (obs, vars) fp32, device-resident;
    # None for a NON-RESIDENT handle (repro.store): the design's X bytes
    # live on the store's host/disk tiers and are fetched per column block
    # through ``blocks`` — only methods registered ``streams=True``
    # ("bakp_stream") can solve it; everything x-resident raises.
    spec: Optional[SolverSpec] = None     # default spec bound by prepare()
    fingerprint: Optional[str] = None
    mesh: Optional[object] = None         # serve.placement.ServeMesh-like
    home: Optional[str] = None            # home placement kind (lane home);
    # bound first-wins by bind_home() — the serving cache stamps it on the
    # first (pre)warm, so a design's primary residency is queryable even
    # after later solves add other lane tiers (see resident_lanes()).
    chol: Dict[Tuple[int, float], jax.Array] = field(default_factory=dict)
    max_tenants: int = 64
    blocks: Optional[object] = None       # StoreBlockSource of a
    # non-resident handle (shape / num_blocks(thr) / block_t(thr, j))
    _cn: Optional[jax.Array] = field(default=None, repr=False)
    _cn_thr: Dict[int, jax.Array] = field(default_factory=dict)
    _inv_cn: Dict[int, jax.Array] = field(default_factory=dict)
    _x_t: Dict[int, jax.Array] = field(default_factory=dict)
    _x_bf16: Dict[int, jax.Array] = field(default_factory=dict)
    _warm: "OrderedDict[str, np.ndarray]" = field(default_factory=OrderedDict)
    _sharded: Dict[object, jax.Array] = field(default_factory=dict)
    _lock: threading.RLock = field(default_factory=threading.RLock,
                                   repr=False, compare=False)

    # ------------------------------------------------------------ identity
    @property
    def shape(self) -> Tuple[int, int]:
        if self.x_pad is not None:
            return tuple(self.x_pad.shape)
        return tuple(self.blocks.shape)

    @property
    def resident(self) -> bool:
        """Whether the design is device-resident (vs a store-backed
        streaming handle)."""
        return self.x_pad is not None

    def _require_x(self, what: str) -> jax.Array:
        """The resident design, or a clear error on a streaming handle."""
        if self.x_pad is None:
            raise UnsupportedSpecError(
                f"{what} needs the device-resident design, but this "
                f"PreparedDesign is non-resident (X blocks stream through "
                f"the design store); solve with a streaming method "
                f"{streaming_methods()}")
        return self.x_pad

    def design_key(self) -> str:
        """This design's identity: the fingerprint handed to ``prepare``
        (serving passes its cache key) or, lazily on first use, the content
        hash of the matrix bytes.  Lazy because hashing is an O(obs·vars)
        host pass the plain ``solve()`` shim should never pay."""
        with self._lock:
            if self.fingerprint is None:
                self.fingerprint = design_fingerprint(
                    np.asarray(self._require_x("design_key")))
            return self.fingerprint

    # --------------------------------------------- per-tenant warm starts
    def warm_coef(self, tenant_id: Optional[str]) -> Optional[np.ndarray]:
        """Last stored coefficients for ``tenant_id`` (None = cold)."""
        if tenant_id is None:
            return None
        with self._lock:
            coef = self._warm.get(tenant_id)
            if coef is not None:
                self._warm.move_to_end(tenant_id)
            return coef

    def store_coef(self, tenant_id: Optional[str], coef: np.ndarray) -> None:
        """Retain a tenant's solved (unpadded) coefficients, LRU-bounded.

        Copies: the same array is handed back to callers, and an in-place
        mutation there must not corrupt the tenant's next warm start.
        """
        if tenant_id is None:
            return
        coef = np.array(coef, np.float32, copy=True)
        with self._lock:
            self._warm[tenant_id] = coef
            self._warm.move_to_end(tenant_id)
            while len(self._warm) > self.max_tenants:
                self._warm.popitem(last=False)

    # ------------------------------------------------- derived design state
    @property
    def cn(self) -> jax.Array:
        """Squared column norms (vars,), computed lazily on first use — the
        O(obs·vars) pass only the iterative methods need; direct methods
        ("lstsq"/"normal") never touch it, so a one-shot direct solve pays
        nothing extra."""
        with self._lock:
            if self._cn is None:
                self._cn = column_norms_sq(self._require_x("column norms"))
            return self._cn

    def cn_for_thr(self, thr: int) -> jax.Array:
        """Column norms extended to SolveBakP's thr-multiple padding."""
        vars_p = self.shape[1]
        nblocks = -(-vars_p // thr)
        pad = nblocks * thr - vars_p
        if pad == 0:
            return self.cn
        with self._lock:
            if thr not in self._cn_thr:
                self._cn_thr[thr] = jnp.concatenate(
                    [self.cn, jnp.zeros((pad,), jnp.float32)])
            return self._cn_thr[thr]

    def inv_cn_for(self, thr: int) -> jax.Array:
        """Inverse squared column norms in SolveBakP's thr-padded layout.

        The fused megakernel (``repro.kernels.fused_solve``) consumes these
        directly; padded (zero-norm) columns come back 0, which pins their
        updates to 0 exactly like the masked XLA path.
        """
        with self._lock:
            if thr not in self._inv_cn:
                self._inv_cn[thr] = safe_inv(self.cn_for_thr(thr))
            return self._inv_cn[thr]

    def x_t_for(self, thr: int) -> jax.Array:
        """Device-resident TRANSPOSED copy of the design, (vars_pad, obs)
        with vars zero-padded to a multiple of ``thr`` — the layout the
        Pallas kernels stream/hold (a paper-"column" is a contiguous row).
        The transpose relayout happens once per (design, thr) and is
        memoised; repeat fused solves reuse the resident copy.
        """
        with self._lock:
            if thr not in self._x_t:
                obs_p, vars_p = self._require_x("x_t_for").shape
                nblocks = -(-vars_p // thr)
                pad = nblocks * thr - vars_p
                x_t = jnp.swapaxes(self.x_pad, 0, 1)
                if pad:
                    x_t = jnp.pad(x_t, ((0, pad), (0, 0)))
                self._x_t[thr] = x_t
            return self._x_t[thr]

    def x_bf16_for(self, thr: int) -> jax.Array:
        """Quantized cache tier: ``x_t_for(thr)`` cast to bf16, memoised.

        The mixed-precision sweep kernels stream this copy instead of the
        fp32 one — half the HBM traffic, half the VMEM footprint — while
        every accumulator (residual, coef, SSE, norms) stays fp32.  The
        cast happens once per (design, thr); both copies stay resident so
        a later ``precision="fp32"`` solve (or the fp32 polish sweeps of
        ``"bf16_fp32acc"``) reuses ``x_t_for`` untouched.
        """
        with self._lock:
            if thr not in self._x_bf16:
                self._x_bf16[thr] = self.x_t_for(thr).astype(jnp.bfloat16)
            return self._x_bf16[thr]

    def chol_for(self, thr: int, ridge: float) -> jax.Array:
        """Block-Gram Cholesky factors for (thr, ridge), computed once."""
        from repro.core.solvebakp import block_gram_cholesky

        key = (int(thr), float(ridge))
        with self._lock:
            if key not in self.chol:
                self.chol[key] = block_gram_cholesky(
                    self._require_x("chol_for"), int(thr), ridge)
            return self.chol[key]

    def x_for_placement(self, placement, smesh) -> jax.Array:
        """``x_pad`` laid out for a sharded placement's in_specs.

        The ``device_put`` (an all-device scatter or broadcast) happens once
        per (design, placement) and is memoised, so repeat solves onto the
        same mesh reuse the resident copy instead of resharding.
        """
        if placement is None or not placement.sharded:
            return self.x_pad
        with self._lock:
            if placement not in self._sharded:
                self._sharded[placement] = jax.device_put(
                    self._require_x("x_for_placement"),
                    placement_sharding(placement, smesh))
            return self._sharded[placement]

    def warm_method_state(self, spec: SolverSpec) -> None:
        """Run ``spec.method``'s prepare hook (column-norm layouts, Gram
        factors, ...) so later solves find their derived state resident.
        Idempotent and thread-safe; serving pre-warm calls this off the
        solver thread."""
        entry = solver_method(spec.method)
        if entry.prepare is not None:
            entry.prepare(self, spec)

    # ------------------------------------------------- lane residency
    def bind_home(self, placement=None) -> str:
        """Bind (first-wins) and return this design's home placement kind.

        The home is where the design primarily serves — ``"single"`` or a
        sharded placement kind.  First-wins: a design warmed for an
        obs-sharded bucket keeps that home even when later single-device
        leftovers also solve against it, so eviction/streaming policies
        can ask "whose device memory does this design own?" with one read.
        """
        kind = placement.kind if placement is not None else "single"
        with self._lock:
            if self.home is None:
                self.home = kind
            return self.home

    def warm_lane_state(self, spec: SolverSpec, placement=None,
                        mesh=None) -> None:
        """Warm every lane-resident tier a (spec, placement) solve needs:
        the method's prepare hook (thr-padded norms, Gram factors, the
        fused kernel's transposed/bf16 copies) plus the placement's
        sharded device copy — and bind the design's home.  Idempotent;
        the serving cache / dispatcher pre-warm call this off the lane
        threads so first solves find their residents built.
        ``mesh`` defaults to the one bound at ``prepare`` time."""
        self.bind_home(placement)
        self.warm_method_state(spec)
        mesh = mesh if mesh is not None else self.mesh
        if (placement is not None and placement.sharded and mesh is not None
                and self.x_pad is not None):
            self.x_for_placement(placement, mesh)

    def resident_lanes(self) -> Tuple[str, ...]:
        """Which per-lane resident tiers this design currently holds:
        always ``"single"`` (``x_pad``), plus ``"fused"`` (transposed
        Pallas layout), ``"fused_bf16"`` (quantized tier) and each sharded
        placement kind with a resident mesh copy."""
        with self._lock:
            out = ["single"]
            if self._x_t:
                out.append("fused")
            if self._x_bf16:
                out.append("fused_bf16")
            out.extend(sorted({p.kind for p in self._sharded}))
            return tuple(out)

    # ---------------------------------------------------------------- solve
    def solve(
        self,
        y: jax.Array,
        a0: Optional[jax.Array] = None,
        *,
        spec: Optional[SolverSpec] = None,
        key: Optional[jax.Array] = None,
        tenant_id: Optional[str] = None,
        placement=None,
        mesh=None,
    ) -> SolveResult:
        """Solve ``x @ a ≈ y`` against this prepared design.

        Args:
          y: (obs,) right-hand side, or (obs, k) for a multi-RHS solve (one
            stream of ``x`` serves all k systems — methods with
            ``multi_rhs=False`` reject the 2-D form).
          a0: optional (vars,)/(vars, k) warm-start coefficients.  Direct
            methods ignore ``a0`` (see ``SolverSpec``); iterative methods
            start from it instead of zeros.
          spec: overrides the spec bound at ``prepare`` time (the serving
            engine shares one PreparedDesign across specs this way).
          key: PRNG key for ``order="random"``.
          tenant_id: when set and ``a0`` is None, warm-start from this
            tenant's last stored coefficients and store the new solution
            back afterwards (the serving warm-start protocol, available to
            direct users of the handle too).
          placement / mesh: mesh-sharded execution (serving placement layer;
            ``mesh`` defaults to the one bound at ``prepare`` time).

        Returns:
          ``SolveResult`` in this design's (padded) shapes.
        """
        spec = spec if spec is not None else self.spec
        if spec is None:
            raise ValueError(
                "no SolverSpec bound to this PreparedDesign; pass spec=")
        mesh = mesh if mesh is not None else self.mesh
        # Normalise to an ndim-carrying array but keep HOST buffers host:
        # the solver entry points auto-donate fresh in-jit transfers of
        # numpy operands (types.donate_default), which is how the serving
        # flush path sheds its steady-state HBM allocation.
        if not hasattr(y, "ndim"):
            y = np.asarray(y, np.float32)
        entry = ensure_precision_supported(spec)
        if self.x_pad is None and not entry.streams:
            raise UnsupportedSpecError(
                f"method {spec.method!r} cannot solve a non-resident design "
                f"(X blocks live in the design store, not on device); use a "
                f"streaming method {streaming_methods()}")
        if y.ndim == 2 and not entry.multi_rhs:
            raise ValueError(
                f"method {spec.method!r} does not support multi-RHS "
                f"y of shape {y.shape}")
        store_tenant = None
        if a0 is None and tenant_id is not None and entry.iterative:
            store_tenant = tenant_id
            warm = self.warm_coef(tenant_id)
            # A stored coefficient only warm-starts a compatible solve: the
            # kernels take (vars,) — broadcast over RHS — or exactly
            # (vars, k).  A tenant alternating RHS counts (say a (vars, 4)
            # multi-RHS fit followed by a single-RHS solve) falls back to a
            # cold start instead of crashing the kernel's a0 check.
            nvars = self.shape[1]
            nrhs = y.shape[1] if y.ndim == 2 else 1
            if warm is not None and warm.shape in ((nvars,), (nvars, nrhs)):
                a0 = jnp.asarray(warm)
        if a0 is not None and not entry.iterative:
            a0 = None  # direct methods ignore warm starts (SolverSpec doc)
        res = entry.solve(self, y, spec, a0=a0, key=key,
                          placement=placement, mesh=mesh)
        # A diverged solve's coefficients would poison the tenant's next
        # warm start (it would resume from the blown-up point); plain
        # budget exhaustion still retains — see warm_retention_ok.
        if store_tenant is not None and warm_retention_ok(res):
            self.store_coef(store_tenant, np.asarray(res.coef))
        return res


def prepare(
    x: jax.Array,
    spec: Optional[SolverSpec] = None,
    mesh=None,
    *,
    fingerprint: Optional[str] = None,
    max_tenants: int = 64,
    placement=None,
) -> PreparedDesign:
    """Build a ``PreparedDesign`` for ``x`` (see module doc).

    Args:
      x: (obs, vars) design matrix; copied to device as fp32.
      spec: default ``SolverSpec`` for ``PreparedDesign.solve``.  When given,
        the method's prepare hook runs eagerly (column norms for its block
        width, Gram Cholesky factors, ...) so the first ``solve`` is as
        cheap as a repeat one.  Without it, pass ``spec=`` per solve.
      mesh: optional ``repro.serve.placement.ServeMesh`` bound as the
        default for placement-routed solves.
      fingerprint: caller-known identity for ``x`` (skips hashing the
        bytes); None defers to a lazy content hash on first
        ``design_key()`` access.
      max_tenants: LRU bound on retained per-tenant warm-start coefficients.
      placement: a sharded placement (with ``mesh``) puts ``x`` straight
        into that layout, so a design larger than one device's memory is
        never gathered onto one device.
    """
    sharded = placement is not None and placement.sharded
    if sharded:
        x = jax.device_put(np.asarray(x, np.float32),
                           placement_sharding(placement, mesh))
    else:
        x = jnp.asarray(x, jnp.float32)
    if x.ndim != 2:
        raise ValueError(f"x must be 2D (obs, vars), got {x.shape}")
    if spec is not None:
        # Fail fast on unknown methods and unsupported precisions
        # (UnsupportedSpecError) before paying the device transfer.
        ensure_precision_supported(spec)
    prepared = PreparedDesign(
        x_pad=x,
        spec=spec,
        fingerprint=fingerprint,
        mesh=mesh,
        max_tenants=max_tenants,
    )
    if sharded:
        prepared._sharded[placement] = x
    if spec is not None:
        prepared.warm_method_state(spec)
    return prepared
