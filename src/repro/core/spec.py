"""SolverSpec + the solver-method registry — the public solver configuration.

The paper's structural split — everything reusable about the design matrix
is computable once up front, while each solve streams ``x`` against one (or
k) right-hand sides — is expressed here as two first-class objects:

  * ``SolverSpec``: a frozen, hashable bag of every solver knob.  It replaces
    the ``method="..."`` string plus loose kwargs that used to be duplicated
    across ``core.solve()``, ``serve.SolveRequest`` and the serving cache.
    Because it is hashable it keys compiled-program caches and serving batch
    groups directly.
  * the **method registry**: each solver method ("bak", "bakp", "bakp_gram",
    "bakf", "lstsq", "normal", ...) is a ``MethodEntry`` naming its kernel
    (a callable consuming a ``repro.core.prepare.PreparedDesign``), the spec
    fields it consumes, and its serving capabilities (multi-RHS?
    vmap-batchable? mesh-shardable?).  New backends register one entry plus
    an optional prepare hook instead of patching dispatch sites in
    ``core.api``, the serving engine, the placement policy and the async
    dispatcher.

This module is dependency-light on purpose (no jax import): specs are
constructed by CLIs and request validators that must stay cheap, and the
registry is populated by ``repro.core.methods`` at package import.

``SolverSpec`` semantics shared by every method:

  * ``atol``/``rtol`` — iterative stopping tolerances (see ``solvebak``);
    direct methods ("lstsq"/"normal") ignore them.
  * ``a0`` warm starts are a *solve-time* argument, not a spec field; direct
    methods ignore ``a0`` entirely (this is THE place that documents it —
    the per-solver docstrings defer here).
  * ``ridge`` — Tikhonov diagonal used by the "normal" baseline's normal
    equations AND by ``mode="gram"`` block factorisations (previously a
    hardcoded 1e-6 inside ``solve()``).
  * fields a method does not consume (``MethodEntry.consumes``) are ignored
    by it; ``canonical()`` resets them to defaults so equivalent specs
    compare/hash equal — serving uses this to coalesce requests whose knob
    differences are irrelevant to their method.
  * ``precision`` — the X-streaming storage precision (PR 7):

      - ``"fp32"``        — full-precision design everywhere (default; a
        spec constructed without the field is bit-identical in hash and
        equality to a pre-precision-API spec, so serving/cache keys never
        cold-start on upgrade).
      - ``"bf16"``        — the kernels stream a bf16 resident copy of X
        (half the HBM traffic, double the design size that fits the fused
        megakernel's VMEM budget) while every accumulator — residual,
        coefficients, SSE, column norms — stays fp32.  Accuracy lands at
        the bf16 representation floor (~1e-2 relative).
      - ``"bf16_fp32acc"`` — the bf16 stream plus ``refine_sweeps`` fp32
        polish sweeps (iterative refinement: the residual is recomputed in
        fp32 from the solved coefficients, then swept against the fp32
        design), recovering full fp32 accuracy the same way the sketching
        literature recovers it from a cheap approximate first pass.

    A method advertises what it can run via ``MethodEntry.precisions``;
    requesting an unsupported combination raises the typed
    ``UnsupportedSpecError`` from ``prepare``/``PreparedDesign.solve``
    (the serving engine instead downgrades to "fp32" and counts a
    ``solver_fallback_total{reason="precision"}``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

# Spec fields every iterative BAK-family method consumes.
_ITER_FIELDS = ("max_iter", "atol", "rtol")

# Recognised SolverSpec.precision values (storage precision of the X
# stream; accumulators are always fp32 — see module doc).
PRECISIONS = ("fp32", "bf16", "bf16_fp32acc")

# Default fp32 polish budget for precision="bf16_fp32acc" (also what
# canonical() resets refine_sweeps to when the precision ignores it).
_REFINE_DEFAULT = 4


class UnsupportedSpecError(ValueError):
    """A structurally valid ``SolverSpec`` names a capability its method
    does not implement (e.g. ``precision="bf16"`` on a method whose
    ``MethodEntry.precisions`` is fp32-only).

    A subclass of ``ValueError`` so pre-existing error handling keeps
    working, but typed so callers (the serving engine's downgrade path,
    batch validators) can catch exactly this case without string-matching
    assorted ValueErrors.
    """


@dataclass(frozen=True)
class SolverSpec:
    """Frozen, hashable solver configuration.

    Attributes:
      method:   registry name of the solver method (see ``method_names()``).
      max_iter: sweep budget for iterative methods.
      atol:     absolute RMSE tolerance (0 disables).
      rtol:     relative per-sweep improvement tolerance (0 disables).
      thr:      block width for the SolveBakP family (paper thread count).
      omega:    block-update relaxation factor (1.0 = paper-faithful).
      order:    column order for "bak": "cyclic" or "random" (the latter
                needs a PRNG ``key`` at solve time).
      ridge:    Tikhonov diagonal for the "normal" baseline and for
                ``mode="gram"`` block Gram factorisations.
      precision: storage precision of the X stream — "fp32" (default),
                "bf16" or "bf16_fp32acc" (see module doc).  Accumulators
                are always fp32; "bf16_fp32acc" adds the fp32 polish.
      refine_sweeps: fp32 polish-sweep budget for "bf16_fp32acc" (the
                polish still honours ``atol``/``rtol`` early exit, so this
                is a cap, not a fixed cost).  Ignored by every other
                precision — ``canonical()`` resets it there.

    Warm starts (``a0``) and PRNG keys are solve-time arguments — see
    ``PreparedDesign.solve``.  Direct methods ignore ``a0``.
    """

    method: str = "bakp_gram"
    max_iter: int = 50
    atol: float = 0.0
    rtol: float = 0.0
    thr: int = 128
    omega: float = 1.0
    order: str = "cyclic"
    ridge: float = 1e-6
    precision: str = "fp32"
    refine_sweeps: int = _REFINE_DEFAULT

    def __post_init__(self):
        # Type-normalise so e.g. rtol=0 and rtol=0.0 hash identically
        # (specs key program caches and serving groups).  Knob *values* are
        # deliberately not range-checked here: the kernels validate at
        # trace/call time, which lets the serving engine isolate a poisoned
        # request's batch instead of failing a whole flush at grouping.
        object.__setattr__(self, "max_iter", int(self.max_iter))
        object.__setattr__(self, "thr", int(self.thr))
        object.__setattr__(self, "refine_sweeps", int(self.refine_sweeps))
        for f in ("atol", "rtol", "omega", "ridge"):
            object.__setattr__(self, f, float(getattr(self, f)))
        # precision names a closed value set, so it IS range-checked here
        # (a typo'd precision is a malformed spec, not a per-kernel knob);
        # whether a given *method* supports it is a capability question
        # answered later by ensure_precision_supported — the split lets the
        # serving engine downgrade unsupported combinations instead of
        # rejecting the request at construction.
        if self.precision not in PRECISIONS:
            raise ValueError(
                f"precision must be one of {PRECISIONS}, "
                f"got {self.precision!r}")
        # Unknown methods fail on use (registry population happens at
        # repro.core import); validate eagerly when the registry is live.
        if _REGISTRY and self.method not in _REGISTRY:
            raise ValueError(
                f"method must be one of {method_names()}, got {self.method!r}")

    def replace(self, **changes) -> "SolverSpec":
        """A copy with ``changes`` applied (dataclasses.replace)."""
        return dataclasses.replace(self, **changes)

    def canonical(self) -> "SolverSpec":
        """The spec with every field its method ignores reset to defaults.

        Two requests whose canonical specs compare equal can legally share
        one compiled solve — the serving engine groups on this (e.g. any
        mix of ``max_iter``/``thr`` still coalesces under "lstsq").

        Precision normalisation: a method that never consumes ``precision``
        has it reset to "fp32" here, so legacy-kwargs requests, pre-PR-7
        pickled configs and new fp32 requests all land on byte-identical
        grouping/``config_key``/warm-coef keys — no compiled-program or
        cache cold-start on upgrade.  ``refine_sweeps`` only changes the
        result under ``precision="bf16_fp32acc"``, so any other precision
        resets it too (mixed refine budgets still coalesce under fp32).
        """
        entry = solver_method(self.method)
        changes = {
            f.name: f.default
            for f in dataclasses.fields(self)
            if f.name != "method" and f.name not in entry.consumes
        }
        c = self.replace(**changes) if changes else self
        if (c.precision != "bf16_fp32acc"
                and c.refine_sweeps != _REFINE_DEFAULT):
            c = c.replace(refine_sweeps=_REFINE_DEFAULT)
        return c


@dataclass(frozen=True)
class MethodEntry:
    """One registered solver method.

    Attributes:
      name:      registry key (``SolverSpec.method``).
      solve:     kernel ``(prepared, y, spec, *, a0, key, placement, mesh)
                 -> SolveResult`` consuming a ``PreparedDesign``; a kernel
                 that also takes ``donate`` gets it when the caller of
                 ``PreparedDesign.solve`` sets it (the serving engine does
                 for coalesced groups).
      consumes:  SolverSpec fields that change this method's result —
                 drives ``SolverSpec.canonical()`` and therefore serving
                 batch grouping.
      iterative: consumes ``max_iter``/``atol``/``rtol`` and honours ``a0``
                 warm starts (direct methods ignore all four).
      multi_rhs: accepts ``y`` of shape (obs, k) — required for the serving
                 engine's same-design coalescing.
      batchable: vmap-batchable across designs (needs ``vmap_one``).
      shardable: has mesh-sharded backends (``repro.core.distributed``) the
                 serving placement policy may route to.
      blocked:   consumes ``thr`` (SolveBakP family) — tells callers which
                 cached column-norm layout the kernel wants.
      needs_chol: wants precomputed block-Gram Cholesky factors
                 (``PreparedDesign.chol_for``).
      streams:   can solve a *non-resident* ``PreparedDesign`` (one with
                 ``x_pad=None`` whose X blocks are fetched through a
                 ``blocks`` source — the ``repro.store`` tiers).  Methods
                 without it raise ``UnsupportedSpecError`` on such handles;
                 the serving engine reroutes over-budget designs to a
                 streaming method instead (``"bakp_stream"``).
      precisions: ``SolverSpec.precision`` values this method can run —
                 the capability the registry/engine/placement check exactly
                 like ``shardable``.  Default fp32-only; the Pallas kernel
                 methods additionally stream a bf16 X
                 (``PreparedDesign.x_bf16_for``) with fp32 accumulators.
      lane:      single-device execution-lane kind for the serving stack
                 ("xla" for the jit'd XLA family, "fused" for the Pallas
                 whole-solve megakernels).  Together with ``shardable`` and
                 ``precisions`` this makes spec→lane routing one registry
                 lookup (``repro.serve.lanes.lane_for``): sharded
                 placements run on their mesh lane, everything else on the
                 method's declared single-device lane.
      prepare:   optional hook ``(prepared, spec) -> None`` warming the
                 per-design state this method reuses (column norms for a
                 given ``thr``, Gram factors, ...); run by ``prepare()`` and
                 by the serving cache's pre-warm path.
      vmap_one:  optional ``(spec) -> one(x, y, cn, atol[, chol][, a0])``
                 per-system callable the serving engine wraps in
                 ``jit(vmap(...))`` for cross-design batches.
      fallback:  name of the method a failed/diverged solve degrades to
                 (the serving engine's retry ladder —
                 ``repro.resilience.ladder``): fused megakernels fall back
                 to their per-sweep XLA family, the resident block-Jacobi
                 methods to the streaming out-of-core path, and the chain
                 bottoms out at the direct ``"lstsq"`` baseline (None =
                 ladder floor).
      summary:   one-line description (shown by ``describe_methods()``).
    """

    name: str
    solve: Callable
    consumes: Tuple[str, ...]
    iterative: bool = True
    multi_rhs: bool = True
    batchable: bool = False
    shardable: bool = False
    blocked: bool = False
    needs_chol: bool = False
    streams: bool = False
    precisions: Tuple[str, ...] = ("fp32",)
    lane: str = "xla"
    prepare: Optional[Callable] = None
    vmap_one: Optional[Callable] = None
    fallback: Optional[str] = None
    summary: str = ""


_REGISTRY: Dict[str, MethodEntry] = {}


def register_method(entry: MethodEntry, *, overwrite: bool = False) -> MethodEntry:
    """Register a solver method.  Third-party backends call this once and
    become dispatchable from ``solve()``, ``prepare()`` and ``repro.serve``
    without touching any of those call sites."""
    if not overwrite and entry.name in _REGISTRY:
        raise ValueError(f"method {entry.name!r} is already registered")
    _REGISTRY[entry.name] = entry
    return entry


def solver_method(name: str) -> MethodEntry:
    """Look up a registered method; raises ValueError on unknown names."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"method must be one of {method_names()}, got {name!r}") from None


def method_names() -> Tuple[str, ...]:
    """Registered method names, in registration order."""
    return tuple(_REGISTRY)


def is_registered(name: str) -> bool:
    return name in _REGISTRY


def shardable_methods() -> Tuple[str, ...]:
    """Methods with a mesh-sharded backend (serving placement eligibility)."""
    return tuple(n for n, e in _REGISTRY.items() if e.shardable)


def streaming_methods() -> Tuple[str, ...]:
    """Methods that can solve non-resident (store-backed) designs."""
    return tuple(n for n, e in _REGISTRY.items() if e.streams)


def methods_for_precision(precision: str) -> Tuple[str, ...]:
    """Methods whose registry entry supports ``precision`` (serving/CLI
    eligibility listing, the precision analogue of ``shardable_methods``)."""
    return tuple(n for n, e in _REGISTRY.items() if precision in e.precisions)


def ensure_precision_supported(spec: SolverSpec) -> MethodEntry:
    """Look up ``spec.method`` and verify it implements ``spec.precision``.

    The single choke point for precision capability: ``prepare()`` and
    ``PreparedDesign.solve`` both call it, so an unsupported combination
    always surfaces as the one typed ``UnsupportedSpecError`` (never
    assorted ValueErrors from deep inside a kernel).  Returns the entry so
    callers don't pay a second registry lookup.
    """
    entry = solver_method(spec.method)
    if spec.precision not in entry.precisions:
        raise UnsupportedSpecError(
            f"method {spec.method!r} does not support "
            f"precision={spec.precision!r} (supports {entry.precisions}); "
            f"pick one of methods {methods_for_precision(spec.precision)} "
            f"or precision='fp32'")
    return entry


def batchable_methods() -> Tuple[str, ...]:
    """Methods the serving engine may vmap-batch across designs."""
    return tuple(n for n, e in _REGISTRY.items() if e.batchable)


def describe_methods() -> str:
    """Human-readable registry listing (CLI ``--help`` fodder)."""
    width = max((len(n) for n in _REGISTRY), default=0)
    return "\n".join(f"{e.name:<{width}}  {e.summary}"
                     for e in _REGISTRY.values())
