import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count at first init).

"""Multi-pod dry-run: lower + compile every (arch × shape-cell) on the
production meshes and extract the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
        --cell train_4k --multi-pod --out results/qwen3_train_mp.json

No full-size tensor is ever allocated: parameters, optimizer state, batch
and cache are ShapeDtypeStructs with NamedShardings attached; the proof of
coherence is that ``jit(step).lower(...).compile()`` succeeds under SPMD
partitioning for 256/512 devices, and ``memory_analysis`` bounds the
per-device HBM.

Outputs JSON: memory analysis, cost analysis, per-collective byte totals
(parsed from the partitioned HLO), derived roofline terms (v5e constants),
MODEL_FLOPS and the useful-flops ratio.
"""
import argparse
import json
import re
import time
from typing import Dict

# v5e constants (per spec)
PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # B/s / chip
LINK_BW = 50e9             # B/s / link (ICI)

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s32|u32|s16|u16|"
                       r"s8|u8|pred|s64|u64|c64|c128)\[([0-9,]*)\]")
_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
          "f8e5m2": 1, "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "u64": 8, "c64": 8, "c128": 16}
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _BYTES[dt]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes of every collective op in the partitioned HLO.

    Matches lines like ``%all-reduce.5 = f32[256]{0} all-reduce(f32[256]{0}
    %x) ...`` and sums the operand shapes inside the call parens.  Async
    pairs (-start/-done) are counted once via the -start op.
    """
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        if not s.startswith("%") and not s.startswith("ROOT"):
            continue
        for kind in _COLLECTIVES:
            # opcode appears right after the "=" result shape
            m = re.search(rf"= [^=]*?\b{kind}(-start)?\(", s)
            if m is None:
                continue
            if f"{kind}-done" in s:
                break
            args = s[m.end():]
            depth = 1
            end = 0
            for i, ch in enumerate(args):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            ops = args[:end]
            b = sum(_shape_bytes(mm) for mm in _SHAPE_RE.finditer(ops))
            out[kind] += b
            out["count"] += 1
            break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def run_cell(arch: str, cell_name: str, *, multi_pod: bool,
             overrides: Dict = None, fsdp: bool = True,
             serve_rules: bool = False) -> Dict:
    import jax
    from repro.configs.base import SHAPE_CELLS
    from repro.configs.registry import get
    from repro.distributed.sharding import make_rules
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import abstract_cell_args

    cfg = get(arch)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    cell = SHAPE_CELLS[cell_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(mesh, fsdp=fsdp, serve=serve_rules)
    chips = mesh.devices.size

    fn, args = abstract_cell_args(cfg, cell, mesh, rules)
    # production donation: train re-uses params/opt buffers, decode re-uses
    # the KV cache (halves the apparent cache memory in memory_analysis).
    donate = {"train": (0, 1), "prefill": (2,), "decode": (2,)}[cell.kind]
    t0 = time.time()
    lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    from repro.launch.hlo_analysis import analyze
    t0 = time.time()
    hc = analyze(hlo)   # trip-count-aware per-device flops/bytes/collectives
    t_analyze = time.time() - t0

    flops = hc["flops"]
    bytes_acc = hc["hbm_bytes"]
    coll = {k.replace("coll_", ""): v for k, v in hc.items()
            if k.startswith("coll_")}
    coll["total"] = hc["coll_total"]
    terms = {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_acc / HBM_BW,
        "collective_s": coll["total"] / LINK_BW,
    }
    terms["bottleneck"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])

    # MODEL_FLOPS: 6·N_active·D for train, 2·N_active·D_new for decode/prefill
    n_active = cfg.n_active_params()
    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    model_flops = (6.0 if cell.kind == "train" else 2.0) * n_active * tokens
    model_flops_per_chip = model_flops / chips

    out = {
        "arch": arch, "cell": cell_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "analyze_s": round(t_analyze, 1),
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.temp_size_in_bytes),
            "fits_16gb": (mem.argument_size_in_bytes
                          + mem.temp_size_in_bytes) < 16e9,
        },
        "hlo_cost": {"flops_per_device": flops,
                     "hbm_bytes_per_device": bytes_acc},
        "xla_cost_analysis_raw": {     # body-once; kept for reference only
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "collectives": coll,
        "roofline": terms,
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flops_ratio": (model_flops_per_chip / flops) if flops else 0.0,
        "params_total": cfg.n_params(),
        "params_active": n_active,
        "_hlo_text": hlo,   # persisted as .hlo.gz by main(); not in stdout
    }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--cell", required=True,
                    choices=["train_4k", "prefill_32k", "decode_32k",
                             "long_500k"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--serve-rules", action="store_true",
                    help="weight-stationary sharding (serving; §Perf H1)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--override", default=None,
                    help="JSON dict of ModelConfig overrides (perf levers)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    overrides = json.loads(args.override) if args.override else None
    res = run_cell(args.arch, args.cell, multi_pod=args.multi_pod,
                   overrides=overrides, fsdp=not args.no_fsdp,
                   serve_rules=args.serve_rules)
    hlo_text = res.pop("_hlo_text", None)
    js = json.dumps(res, indent=1)
    print(js)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(js)
        if hlo_text is not None:
            import gzip
            with gzip.open(args.out.replace(".json", ".hlo.gz"), "wt") as f:
                f.write(hlo_text)


if __name__ == "__main__":
    main()
