"""Tests of the benchmark harness that need no chip.

    PYTHONPATH=src python -m pytest -q bench/tests

They check the yardstick (least work, peaks, trace reduction), that every
name in ``BENCHMARK.json`` resolves to its files, that ``bench/run.py``
refuses a machine without a TPU, and, by driving whole runs at tiny sizes
on the CPU, that the check passes the program and fails the control and
each fault a cell can have.  Nothing here describes a TPU topology.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, inject, trace, work  # noqa: E402
from bench.reference import cgls_f64, lstsq_f64, rel_errors  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
# The four-chip cell whose configuration and traffic are kept under bench/
# for a later benchmark change to add (not measured on the chip yet); the
# tiny runs below drive it on four virtual devices.
SHARDED_CONFIG = {"name": "sharded_4m", "file": "bench/configs/sharded_4m.json",
                  "reduced": ["obs"]}
SHARDED_CELL = {"name": "sharded_4m.closed8", "config": "sharded_4m",
                "traffic": "closed8", "chips": 4}
TINY_BENCH = dict(BENCH, configs=BENCH["configs"] + [SHARDED_CONFIG],
                  workloads=BENCH["workloads"] + [SHARDED_CELL])
# Shapes at which a whole run fits a test: the widths are cut as well as
# the rows, and stream keeps obs >> thr so block Jacobi stays convergent.
TINY = {"tall_1m": (4096, 100), "stream_32k": (8192, 300),
        "sharded_4m": (8192, 100)}
# An open loop at tiny sizes offers enough to form coalesced groups.
TINY_RATE_PER_S = 40.0
PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0,
        "vmem_bytes": 1000}


# ------------------------------------------------------------ yardstick
def test_least_work_streamed_design():
    flops, nbytes = work.least_work(1000, 100, 4, 7, vmem_bytes=1000)
    assert flops == 7 * 4 * 1000 * 100 * 4
    # x (400 kB) does not fit: read once per sweep, residual in and out
    assert nbytes == 7 * 1000 * 100 * 4 + 7 * 2 * 1000 * 4 * 4


def test_least_work_vmem_resident_design():
    flops, nbytes = work.least_work(100, 10, 1, 9, vmem_bytes=4000)
    assert flops == 9 * 4 * 100 * 10
    assert nbytes == 100 * 10 * 4 + 9 * 2 * 100 * 4   # x read once


def test_least_work_sharded_design_fits_per_chip():
    _, one = work.least_work(100, 10, 1, 9, vmem_bytes=1000, chips=1)
    _, four = work.least_work(100, 10, 1, 9, vmem_bytes=1000, chips=4)
    assert one == 9 * 4000 + 9 * 800 and four == 4000 + 9 * 800


def test_least_time_is_the_binding_roof():
    assert work.least_time_s(1000.0, 10.0, PEAK) == 10.0     # compute
    assert work.least_time_s(10.0, 1000.0, PEAK) == 100.0    # bytes
    assert work.least_time_s(10.0, 1000.0, PEAK, chips=4) == 25.0


def test_peaks_known_and_unknown_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


# ---------------------------------------------------------------- trace
def test_merge_gaps_and_naming_exact():
    busy = trace.merge([(0, 10), (5, 20), (30, 40), (35, 38), (90, 120)],
                       0, 100)
    assert busy == [(0, 20), (30, 40), (90, 100)]
    idle = trace.gaps(busy, 0, 100)
    assert idle == [(20, 30), (40, 90)]
    spans = [("bench.submit", 15, 20),       # covers the first gap's midpoint
             ("engine.flush", 21, 8),        # program span wins there
             ("bench.submit", 60, 10)]       # covers the second's (65)
    named = trace.name_gaps(idle, spans)
    assert named == pytest.approx({"engine.flush": 10e-9,
                                   "bench.submit": 50e-9})


def test_summarize_synthetic_devices():
    t = trace.Trace(
        devices={"/device:TPU:0": {"ops": [("fusion.1", 100, 50),
                                           ("all-reduce.2", 150, 50)],
                                   "modules": [("jit_solve", 100, 100),
                                               ("jit_late", 350, 100)]},
                 "/device:TPU:1": {"ops": [("fusion.1", 100, 100)],
                                   "modules": [("jit_solve", 100, 100)]}},
        spans=[("bench.window", 0, 400), ("engine.flush", 50, 300)])
    s = trace.summarize(t)
    assert s["window_s"] == pytest.approx(400e-9)
    assert s["busy_s"] == pytest.approx(100e-9)
    assert s["program_s"] == pytest.approx(100e-9)   # jit_late ends after
    assert s["collective_s"] == pytest.approx(25e-9)
    # each device idles over [0, 100] and [200, 400], all inside the flush
    assert dict(s["idle_gaps"])["engine.flush"] == pytest.approx(300e-9)


def test_reduction_of_a_recorded_cpu_trace():
    """A window of the tiny tall cell traced on the CPU: its XLA work runs
    on the PjRt client's threads, which stand in for a device here."""
    t = trace.load(ROOT / "bench/testdata/cpu_window.xplane.pb",
                   device_plane=r"^/host:CPU$",
                   op_line=r"^tf_XLA(PjRtCpuClient|Eigen)/",
                   module_line=r"^$")
    names = {n for n, _, _ in t.spans}
    assert {"bench.window", "bench.submit", "engine.flush",
            "solve/bakp_gram"} <= names
    s = trace.summarize(t)
    assert 0.45 < s["window_s"] < 0.6
    assert 0 < s["busy_s"] < s["window_s"]
    gaps = dict(s["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    assert "engine.flush" in gaps or "solve/bakp_gram" in gaps
    assert len(s["device_ops"]) == 10


# ------------------------------------------------------- definitions
@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_name_in_the_benchmark_loads(workload):
    c = harness.cell(BENCH, workload)
    for key in ("obs", "vars", "spec", "path", "placement", "limits",
                "reduced", "source"):
        assert key in c.config, key
    assert c.config["reduced"] == next(
        x["reduced"] for x in BENCH["configs"] if x["name"] == c.config[
            "name"])
    assert c.traffic["loop"] in ("open", "closed") and c.traffic["pool"] > 0
    names = [m["name"] for m in c.end_to_end + c.per_layer]
    assert "setup_s" in names and len(c.per_layer) >= 1
    assert len(c.end_to_end) >= 2
    for name in names:
        assert callable(harness.metric_reader(name))
    assert callable(harness.design_maker(c.config["design"]))


def test_a_split_metric_falls_back_to_its_quantity_reader():
    assert (harness.metric_reader("latency_p50_s.open").__module__
            != harness.metric_reader("latency_p50_s").__module__)
    assert (harness.metric_reader("latency_p50_s.open").__code__.co_filename
            == harness.metric_reader("latency_p50_s").__code__.co_filename)


def test_run_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    name = BENCH["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", name, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


# ------------------------------------------------------------- whole runs
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout-shaped directory holding the benchmark's definitions with
    every configuration cut to a tiny shape."""
    root = tmp_path_factory.mktemp("tiny")
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    shutil.copytree(ROOT / "bench" / "designs", root / "bench" / "designs")
    for hook in (ROOT / "bench" / "traffic").glob("*.py"):
        shutil.copy(hook, root / "bench" / "traffic" / hook.name)
    for c in TINY_BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["obs"], cfg["vars"] = TINY[c["name"]]
        if cfg.get("placement_policy"):
            cfg["placement_policy"]["obs_shard_min_cells"] = 1024
        (root / c["file"]).write_text(json.dumps(cfg))
    for t in (ROOT / "bench" / "traffic").glob("*.json"):
        traffic = json.loads(t.read_text())
        if traffic["loop"] == "open":   # tiny solves are quick: offer more
            traffic["rate_per_s"] = TINY_RATE_PER_S
        (root / "bench" / "traffic" / t.name).write_text(json.dumps(traffic))
    (root / "BENCHMARK.json").write_text(json.dumps(TINY_BENCH))
    return root


def _run(root, workload, patch=None, seconds=1.5):
    return harness.run(workload, 2**31 + 11, seconds, False,
                       t_start=time.perf_counter(), root=root,
                       require_chip=False, solve_patch=patch)


SINGLE_CHIP = [w["name"] for w in TINY_BENCH["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("workload", SINGLE_CHIP)
def test_sound_run_is_correct(tiny_root, workload):
    r = _run(tiny_root, workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"


def test_data_seed_gives_every_run_the_same_work(tiny_root):
    """A mix with ``data_seed`` serves the same designs and targets whatever
    the run's seed; the run's seed only orders the requests."""
    name = next(w["name"] for w in TINY_BENCH["workloads"]
                if w["traffic"] == "closed1")
    c = harness.cell(TINY_BENCH, name, tiny_root)
    assert "data_seed" in c.traffic
    if harness.COUNTER is None:
        harness.COUNTER = harness.CompileCounter()
    designs = []
    for seed in (5, 2**31 + 7):
        r = harness.CellRun(c, seed)
        r.setup()
        designs.append(r.designs)
        r.close()
    (a,), (b,) = designs
    assert a.key == b.key
    np.testing.assert_array_equal(a.ys, b.ys)


@pytest.mark.parametrize("workload", SINGLE_CHIP)
def test_control_is_incorrect(tiny_root, workload):
    c = harness.cell(TINY_BENCH, workload)
    r = _run(tiny_root, workload,
             lambda: inject.control(c.config["path"], "high"))
    assert not r["correct"]
    assert any(r["checks"][n]["value"] > r["checks"][n]["limit"]
               for n in ("coef_rel_err", "resid_rel_err"))
    assert r["checks"]["failed_requests"]["value"] == 0


def _faults_of(workload):
    """The faults a cell can have: half a batch needs coalesced groups,
    the exchange between chips needs a mesh."""
    c = harness.cell(TINY_BENCH, workload)
    out = ["unchanged_state", "altered_answer"]
    if c.traffic["loop"] == "open" or c.traffic.get("clients", 1) > 1:
        out.append("half_batch")
    if c.chips > 1:
        out.append("no_exchange")
    return out


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in SINGLE_CHIP for f in _faults_of(w)])
def test_fault_is_incorrect(tiny_root, workload, fault):
    r = _run(tiny_root, workload, inject.FAULTS[fault], seconds=2.0)
    assert not r["correct"], (fault, r["checks"])


MULTI_CHIP = [w["name"] for w in TINY_BENCH["workloads"] if w["chips"] > 1]


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in MULTI_CHIP for f in [None] + _faults_of(w)])
def test_sharded_cell_on_virtual_devices(tiny_root, workload, fault):
    """Mesh cells run in a child process with four virtual CPU devices."""
    script = (
        "import sys, time, json\n"
        f"sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]\n"
        "from bench import harness, inject\n"
        f"patch = {('inject.FAULTS[%r]' % fault) if fault else None}\n"
        f"r = harness.run({workload!r}, 2**31 + 11, 2.0, False,"
        " t_start=time.perf_counter(), "
        f"root={str(tiny_root)!r}, require_chip=False, solve_patch=patch)\n"
        "print(json.dumps(r))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["correct"] == (fault is None), (fault, r["checks"])


# A cell added as files alone: a design kind of its own (two designs of
# one shape) and a traffic mix whose plan spreads requests over both
# designs and four tenants (warm starts).
TWO_DESIGNS = """
from bench import data


def make(seed, config, pool):
    out = []
    for i in range(2):
        x = data.gaussian(seed + i, (config["obs"], config["vars"]))
        ys = data.rhs_pool(x, data.planted(seed + i, config["vars"], pool),
                           seed=seed + i, noise=config["target_noise"])
        out.append(data.Design(key=f"two-{seed}-{i}", x=x, ys=ys))
    return out
"""
TENANTS = """
from bench.traffic import Pick, coalesced_sizes


def plan(rng, traffic, n, designs):
    return [Pick(int(rng.integers(len(designs))),
                 int(rng.integers(len(designs[0].ys))),
                 f"tenant-{int(rng.integers(4))}") for _ in range(n)]


def warm_groups(traffic, designs, max_batch):
    # every size twice: one design (the multi-RHS program), then both
    # designs alternating (the vmap program)
    return [[Pick(j % 2 if mixed else 0, j % len(designs[0].ys),
                  f"tenant-{j % 4}") for j in range(k)]
            for k in coalesced_sizes(traffic, max_batch)
            for mixed in (False, True)]
"""


@pytest.fixture(scope="module")
def added_root(tiny_root, tmp_path_factory):
    root = tmp_path_factory.mktemp("added")
    shutil.copytree(tiny_root / "bench", root / "bench")
    (root / "bench" / "designs" / "two_gaussians.py").write_text(TWO_DESIGNS)
    (root / "bench" / "traffic" / "tenants.py").write_text(TENANTS)
    traffic = json.loads((tiny_root / "bench/traffic/open.json").read_text())
    (root / "bench" / "traffic" / "tenants.json").write_text(
        json.dumps(traffic))
    cfg = json.loads((tiny_root / "bench/configs/tall_1m.json").read_text())
    cfg.update(name="two_tall", design="two_gaussians",
               path=["xla", "vmap"])
    (root / "bench" / "configs" / "two_tall.json").write_text(json.dumps(cfg))
    bench = dict(TINY_BENCH)
    bench["configs"] = bench["configs"] + [
        {"name": "two_tall", "file": "bench/configs/two_tall.json",
         "reduced": []}]
    bench["workloads"] = bench["workloads"] + [
        {"name": "two_tall.tenants", "config": "two_tall",
         "traffic": "tenants", "chips": 1}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("fault", [None, "altered_answer"])
def test_a_cell_added_as_files_alone(added_root, fault, capsys):
    from bench.traffic import hook, rng_for, default_plan

    c = harness.cell(harness.load_benchmark(added_root), "two_tall.tenants",
                     added_root)
    designs = harness.design_maker("two_gaussians", added_root / "bench")(
        7, c.config, 4)
    assert [d.key for d in designs] == ["two-7-0", "two-7-1"]
    plan = hook("tenants", "plan", default_plan, added_root / "bench/traffic")
    picks = plan(rng_for(7, 1), c.traffic, 64, designs)
    assert {p.design for p in picks} == {0, 1}
    assert len({p.tenant for p in picks}) == 4
    r = _run(added_root, "two_tall.tenants",
             fault and inject.FAULTS[fault])
    assert r["correct"] == (fault is None), r["checks"]
    assert r["attempted"] > 10
    info = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert info["info"]["window_compiles"] == 0


@pytest.mark.parametrize("solve,count", [(cgls_f64, 3), (lstsq_f64, 3),
                                         (lstsq_f64, 1)])
def test_reference_solves_to_float64_accuracy(solve, count):
    """CGLS, and ``lstsq_f64`` by the normal equations (40 vars against 3
    right-hand sides) and by CGLS (against 1)."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((500, 40)).astype(np.float32)
    ys = rng.standard_normal((count, 500)).astype(np.float32)
    a, rel_grad, _ = solve(x, ys)
    exact = np.linalg.lstsq(x.astype(np.float64), ys.T.astype(np.float64),
                            rcond=None)[0].T
    assert rel_grad < 1e-12
    assert rel_errors(a, exact).max() < 1e-12
