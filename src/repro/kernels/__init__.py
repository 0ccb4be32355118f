"""repro.kernels — Pallas TPU kernels for the solver's compute hot-spots.

  fused_solve.py   whole-solve megakernel: ONE pallas_call runs the entire
                   SolveBak/SolveBakP iteration — x/residual/coefficients
                   VMEM-resident across all sweeps, convergence decided
                   on-chip, true early exit (no compute, no DMA after it).
  cd_sweep.py      per-sweep VMEM-resident CD sweep (Alg. 1) + block-Jacobi
                   sweep (Alg. 2) — x streamed HBM->VMEM once per sweep,
                   residual resident in VMEM scratch across the grid.
  block_update.py  obs-streamed rank-thr residual correction + fused
                   SolveBakF feature scoring.
  stream_solve.py  streaming out-of-core megakernel: x tiles stay in HBM
                   (pl.ANY) and double-buffer through a two-slot VMEM
                   scratch per block — the VMEM working set is independent
                   of vars, so over-budget designs keep the single-launch
                   early-exit execution model.  Plus a host block loop
                   (stream_solve_blocks) for store-backed non-resident
                   designs whose tiles fetch from host RAM or disk.
  ops.py           solver entries: solvebakp_kernel (fused when the design
                   fits VMEM, per-sweep launch loop otherwise),
                   solvebakp_stream_kernel (HBM-resident x, streamed) +
                   wrappers (interpret mode on the CPU, y/a0 buffer donation
                   on accelerators).
  ref.py           pure-jnp oracles, tested via shape/dtype sweeps.
"""
from repro.kernels.block_update import block_update, score_features
from repro.kernels.cd_sweep import bakp_sweep, cd_sweep
from repro.kernels.fused_solve import (
    fused_fits,
    fused_solve,
    fused_vmem_bytes,
)
from repro.kernels.ops import (
    block_update_kernel,
    score_features_kernel,
    solvebakp_kernel,
    solvebakp_persweep_kernel,
    solvebakp_stream_kernel,
)
from repro.kernels.stream_solve import (
    stream_fits,
    stream_solve,
    stream_solve_blocks,
    stream_vmem_bytes,
    stream_x_resident_bytes,
)

__all__ = [
    "bakp_sweep",
    "block_update",
    "block_update_kernel",
    "cd_sweep",
    "fused_fits",
    "fused_solve",
    "fused_vmem_bytes",
    "score_features",
    "score_features_kernel",
    "solvebakp_kernel",
    "solvebakp_persweep_kernel",
    "solvebakp_stream_kernel",
    "stream_fits",
    "stream_solve",
    "stream_solve_blocks",
    "stream_vmem_bytes",
    "stream_x_resident_bytes",
]
