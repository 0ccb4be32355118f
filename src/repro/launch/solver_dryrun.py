import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count at first init).

"""Dry-run of the paper's own workload at pod scale: the distributed
SolveBakP on the production mesh, lowered against ShapeDtypeStructs.

    PYTHONPATH=src python -m repro.launch.solver_dryrun \
        --obs 16777216 --vars 16384 --thr 512 --mode gram [--multi-pod] \
        [--sharding obs|2d] [--dtype bfloat16] --out results/solver.json

The system is obs×vars bf16 (default 16M×16k = 512 GiB, 2 GiB/chip on one
pod).  Roofline terms come from the same trip-count-aware HLO analyzer as
the LM cells; sweeps are bounded by --sweeps (the while-loop trip).
"""
import argparse
import functools
import json
import time

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--obs", type=int, default=16_777_216)
    ap.add_argument("--vars", type=int, default=16_384)
    ap.add_argument("--thr", type=int, default=512)
    ap.add_argument("--sweeps", type=int, default=8)
    ap.add_argument("--mode", default="gram", choices=["gram", "jacobi"])
    ap.add_argument("--sharding", default="obs", choices=["obs", "2d"])
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import (solvebakp_2d, solvebakp_obs_sharded)
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.hlo_analysis import analyze
    from repro.launch.mesh import make_production_mesh

    enable_compile_cache()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    chips = mesh.devices.size
    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32

    if args.sharding == "obs":
        fn = functools.partial(
            solvebakp_obs_sharded, mesh=mesh, data_axes=data_axes + ("model",),
            thr=args.thr, max_iter=args.sweeps, mode=args.mode)
        x_spec = P(data_axes + ("model",), None)
        y_spec = P(data_axes + ("model",))
    else:
        fn = functools.partial(
            solvebakp_2d, mesh=mesh, data_axes=data_axes,
            model_axis="model", thr=args.thr, max_iter=args.sweeps,
            mode=args.mode, omega=0.5)
        x_spec = P(data_axes, "model")
        y_spec = P(data_axes)

    x = jax.ShapeDtypeStruct((args.obs, args.vars), dt,
                             sharding=NamedSharding(mesh, x_spec))
    y = jax.ShapeDtypeStruct((args.obs,), jnp.float32,
                             sharding=NamedSharding(mesh, y_spec))

    t0 = time.time()
    lowered = jax.jit(lambda xx, yy: fn(xx, yy)).lower(x, y)
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    hc = analyze(hlo)

    per_sweep = 1.0 / args.sweeps
    # analytic per-sweep terms (the solver's own roofline, DESIGN.md §3)
    bytes_ideal = args.obs * args.vars * (2 if dt == jnp.bfloat16 else 4)
    flops_ideal = 4.0 * args.obs * args.vars
    res = {
        "workload": {"obs": args.obs, "vars": args.vars, "thr": args.thr,
                     "mode": args.mode, "sharding": args.sharding,
                     "dtype": args.dtype, "sweeps": args.sweeps},
        "mesh": "2x16x16" if args.multi_pod else "16x16", "chips": chips,
        "compile_s": round(t_compile, 1),
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.temp_size_in_bytes),
        },
        "per_sweep": {
            "compute_s": hc["flops"] * per_sweep / PEAK_FLOPS,
            "memory_s": hc["hbm_bytes"] * per_sweep / HBM_BW,
            "collective_s": hc["coll_total"] * per_sweep / LINK_BW,
        },
        "collectives": {k.replace("coll_", ""): v * per_sweep
                        for k, v in hc.items() if k.startswith("coll_")},
        "ideal_per_sweep": {
            "memory_s_per_chip": bytes_ideal / chips / HBM_BW,
            "compute_s_per_chip": flops_ideal / chips / PEAK_FLOPS,
        },
    }
    ps = res["per_sweep"]
    ps["bottleneck"] = max(("compute_s", "memory_s", "collective_s"),
                           key=lambda k: ps[k])
    res["roofline_fraction"] = (res["ideal_per_sweep"]["memory_s_per_chip"]
                                / max(ps["memory_s"], ps["compute_s"],
                                      ps["collective_s"]))
    js = json.dumps(res, indent=1)
    print(js)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(js)


if __name__ == "__main__":
    main()
