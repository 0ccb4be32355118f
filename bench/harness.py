"""One run of one benchmark cell: set-up, the measured window, the check.

A cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<config>.json``: the deployment's shapes, solver spec and
serving settings, and its design generator ``bench/designs/<kind>.py``)
and a traffic mix (``bench/traffic/<mix>.json``, with an optional ``.py``
of its own: see ``bench/traffic.py``).  Its metrics are readers of their
own (``bench/metrics/<metric>.py``), found by name.  Nothing here names a
cell, so a new cell is new files.

The timed path is the served one: ``SolveRequest``s carrying the host
design and its ``design_key`` go to ``AsyncDispatcher.submit`` on a
``SolverServeEngine``, and through its batching, lanes and solver path to
the XLA programs and Pallas kernels (and, on a mesh, the sharded solve).
Set-up builds the designs (the first of each shape into the engine's
cache) and serves one group of every coalesced size the traffic can form,
twice, so nothing compiles in the window; the window counts backend
compilations to show it.  Python's collector is frozen over the set-up's
objects for the window, so that a full collection scans only what the
window makes.

``correct`` compares every answer the window produced with the float64
host reference (``bench/reference.py``), computed after the window from the
same seeded data once the program's state is freed.  An answer that is
wrong, never comes, fails, or was served off the configuration's kernel
path or placement or after a retry or fallback, makes the run incorrect.
"""
from __future__ import annotations

import contextlib
import faulthandler
import gc
import importlib.util
import json
import pathlib
import queue
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Callable, List, Optional

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
WAIT_PAST_CLOSE_S = 60.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
REF_GRAD_LIMIT = 1e-11
RESID_SAMPLE = 32
# An open loop's sender that runs this late dumps every thread's stack to
# standard error: what held it up.
STALL_S = 0.25


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


# ------------------------------------------------------------ definitions
def load_benchmark(root=ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def _load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir=BENCH_DIR) -> Callable:
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``.  A
    quantity split by the cells that report it, ``<quantity>.<cells>``
    (``latency_p50_s.open``), is read by ``<quantity>.py`` unless it has a
    reader of its own."""
    path = pathlib.Path(bench_dir) / "metrics" / f"{name}.py"
    if not path.exists():
        path = path.with_name(f"{name.split('.')[0]}.py")
    return _load_module(path, f"bench_metric_{name}").read


def design_maker(kind: str, bench_dir=BENCH_DIR) -> Callable:
    """``make(seed, config, pool) -> [Design]`` of
    ``bench/designs/<kind>.py``."""
    path = pathlib.Path(bench_dir) / "designs" / f"{kind}.py"
    return _load_module(path, f"bench_design_{kind}").make


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    traffic_name: str
    end_to_end: List[dict]
    per_layer: List[dict]
    root: pathlib.Path = ROOT      # the checkout its files are read from


def cell(bench: dict, workload: str, root=ROOT) -> Cell:
    """Resolve a workload name to its configuration, traffic and metrics."""
    root = pathlib.Path(root)
    wl = {w["name"]: w for w in bench["workloads"]}
    if workload not in wl:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(wl)}")
    w = wl[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / cfgs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (applies(m) if "workloads" in m else m["moves"] in names)]
    return Cell(workload, int(w["chips"]), config, traffic, w["traffic"],
                e2e, per, root)


# ---------------------------------------------------------------- records
@dataclass
class Record:
    """One request of the window, on the harness's clock
    (``time.perf_counter``)."""

    pick: int                      # index into its design's targets
    due: float
    design: int = 0                # index into the cell's designs
    tenant: Optional[str] = None
    sent: float = 0.0
    done: Optional[float] = None   # when its answer reached the caller
    result: object = None          # ServedSolve
    error: Optional[str] = None
    failure: Optional[str] = None  # why it counts as failed, if it does
    deadline_s: Optional[float] = None
    ticket: object = None          # the dispatcher's SolveTicket

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.done is None else self.done - self.due


@dataclass
class RunData:
    """What a metric reader sees."""

    cell: Cell
    seconds: float
    setup_s: float
    t0: float
    records: List[Record]
    chips: int
    peak: dict
    shapes: List[tuple]            # (obs, vars) of each design
    trace: Optional[dict] = None

    @property
    def answered(self) -> List[Record]:
        return [r for r in self.records if r.result is not None]


# ------------------------------------------------------------- the device
def devices_for(chips: int):
    """The cell's devices; ``NoChip`` when JAX has no accelerator or too
    few of them (there is no CPU fallback)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    return devs


def use_compile_cache() -> None:
    """The program's persistent compilation cache (its directory as
    ``repro.launch.compile_cache`` picks it), caching every program, however
    small or quick to compile, so that only a cell's first run compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts backend compilations (cache loads included) while armed."""

    def __init__(self):
        import jax

        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == COMPILE_EVENT:
            self.count += 1


def _counters(name: str) -> dict:
    from repro import obs

    fam = obs.default_registry().snapshot().get(name)
    return dict(fam["values"]) if fam else {}


# --------------------------------------------------------------- the run
class CellRun:
    """Set-up and window of one cell for one seed."""

    def __init__(self, cell_: Cell, seed: int, *, solve_patch=None):
        self.cell = cell_
        self.seed = int(seed)
        self.solve_patch = solve_patch or contextlib.nullcontext
        self.pool = int(cell_.traffic["pool"])
        self.gc_pauses: List[float] = []

    # ---------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.core import SolverSpec
        from repro.serve import (DispatchConfig, PlacementPolicy,
                                 ServeConfig, SolverServeEngine,
                                 build_serve_mesh)

        cfg = self.cell.config
        t = time.perf_counter()
        self.designs = design_maker(cfg["design"], self.cell.root / "bench")(
            int(self.cell.traffic.get("data_seed", self.seed)), cfg,
            self.pool)
        self.timings = {"data_s": time.perf_counter() - t}
        self.spec = SolverSpec(**cfg["spec"])
        serve = dict(cfg.get("serve", {}))
        if cfg.get("placement_policy") is not None:
            serve["placement_policy"] = PlacementPolicy(
                **cfg["placement_policy"])
        mesh = build_serve_mesh(cfg["mesh"]) if cfg.get("mesh") else None
        self.engine = SolverServeEngine(ServeConfig(**serve), mesh=mesh)
        self.dispatch = DispatchConfig(**cfg.get("dispatch", {}))
        from bench.traffic import hook, shape_groups
        groups = hook(self.cell.traffic_name, "warm_groups", shape_groups,
                      self.cell.root / "bench/traffic")(
            self.cell.traffic, self.designs, self.dispatch.max_batch)
        self.warm_compiles = []
        with self.solve_patch():
            for _ in range(2):
                t = time.perf_counter()
                before = COUNTER.count
                COUNTER.armed = True
                for n, group in enumerate(groups):
                    self._warm_group(n, group)
                COUNTER.armed = False
                self.warm_compiles.append(COUNTER.count - before)
                self.timings.setdefault("warm_s", []).append(
                    time.perf_counter() - t)

    def request(self, rec: Record, rid: str):
        from repro.serve import SolveRequest

        d = self.designs[rec.design]
        return SolveRequest(x=d.x, y=d.ys[rec.pick], spec=self.spec,
                            design_key=d.key, request_id=rid,
                            deadline_s=rec.deadline_s, tenant_id=rec.tenant)

    def _warm_group(self, n: int, group: list) -> None:
        """Serve ``group`` (``Pick``s) at once; each has to succeed and, in
        a group of one design, to have been solved as one group."""
        from repro.serve import AsyncDispatcher

        k = len(group) if len({p.design for p in group}) == 1 else None
        with AsyncDispatcher(self.engine, self.dispatch) as disp:
            tickets = [disp.submit(self.request(
                Record(pick=p.rhs, due=0.0, design=p.design,
                       tenant=p.tenant), f"warm-{n}-{j}"))
                for j, p in enumerate(group)]
            results = [t.result() for t in tickets]
        bad = [r for r in results
               if not r.ok or (k is not None and r.group_size != k)]
        if bad:
            raise RuntimeError(
                f"warm-up of a group of {len(group)}: {len(bad)} results "
                f"failed or formed another group (first: ok={bad[0].ok}, "
                f"group_size={bad[0].group_size}, error={bad[0].error})")

    # ---------------------------------------------------------- window
    def window(self, seconds: float, *, rate_per_s=None,
               annotate=False) -> tuple:
        """Serve the traffic for ``seconds``; returns (t0, records,
        window-level counter deltas)."""
        from repro.serve import AsyncDispatcher

        names = ("solver_retries_total", "solver_fallback_total",
                 "solver_dispatch_total")
        before = {n: _counters(n) for n in names}
        traffic = dict(self.cell.traffic)
        if rate_per_s is not None:
            traffic["rate_per_s"] = rate_per_s
        span = _span if annotate else _no_span
        disp = AsyncDispatcher(self.engine, self.dispatch).start()
        COUNTER.count = 0
        COUNTER.armed = True
        self.gc_pauses = []
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self._on_gc)
        with self.solve_patch():
            try:
                t0 = time.perf_counter()
                # The traced window is exactly [t0, close].
                marker = threading.Thread(target=_mark_window,
                                          args=(t0 + seconds, span),
                                          name="bench-window")
                marker.start()
                loop = (self._open_loop if traffic["loop"] == "open"
                        else self._closed_loop)
                records = loop(disp, traffic, t0, seconds, span)
                marker.join()
            finally:
                disp.drain(timeout=WAIT_PAST_CLOSE_S)
                COUNTER.armed = False
                disp.stop(drain=False)
                gc.callbacks.remove(self._on_gc)
                gc.unfreeze()
        deltas = {n: {k: v - before[n].get(k, 0.0)
                      for k, v in _counters(n).items()
                      if v - before[n].get(k, 0.0)} for n in names}
        return t0, records, deltas

    def _on_gc(self, phase: str, info: dict) -> None:
        """Times each collection in the window (``gc.callbacks``)."""
        if phase == "start":
            self._gc_start = time.perf_counter()
        else:
            self.gc_pauses.append(time.perf_counter() - self._gc_start)

    def _open_loop(self, disp, traffic, t0, seconds, span):
        from bench.traffic import hook, open_offsets, rng_for, default_plan

        mix, where = self.cell.traffic_name, self.cell.root / "bench/traffic"
        rng = rng_for(int(traffic["arrivals_seed"]), 0)
        offsets = hook(mix, "offsets", open_offsets, where)(rng, traffic,
                                                            seconds)
        picks = hook(mix, "plan", default_plan, where)(
            rng_for(self.seed, 1), traffic, len(offsets), self.designs)
        self.timings["loop_ready_s"] = time.perf_counter() - t0
        deadline = traffic.get("deadline_s")
        pending: "queue.Queue" = queue.Queue()
        records: List[Record] = []
        close = t0 + seconds

        def collect():
            while True:
                rec = pending.get()
                if rec is None:
                    return
                _await(rec, close + WAIT_PAST_CLOSE_S, span)

        collector = threading.Thread(target=collect, name="bench-collect")
        collector.start()
        try:
            for i, (off, pick) in enumerate(zip(offsets, picks)):
                rec = Record(pick=pick.rhs, due=t0 + float(off),
                             design=pick.design, tenant=pick.tenant,
                             deadline_s=deadline)
                delay = rec.due - time.perf_counter()
                faulthandler.dump_traceback_later(max(delay, 0.0) + STALL_S,
                                                  file=sys.__stderr__)
                if delay > 0:
                    time.sleep(delay)
                rec.sent = time.perf_counter()
                with span("bench.submit"):
                    try:
                        rec.ticket = disp.submit(self.request(rec, f"w{i}"))
                    except Exception as exc:  # rejected: counts as failed
                        rec.error = f"{type(exc).__name__}: {exc}"
                        rec.done = time.perf_counter()
                faulthandler.cancel_dump_traceback_later()
                records.append(rec)
                pending.put(rec)
            delay = close - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        finally:
            faulthandler.cancel_dump_traceback_later()
            pending.put(None)
            collector.join()
        return records

    def _closed_loop(self, disp, traffic, t0, seconds, span):
        from bench.traffic import hook, rng_for, default_plan

        plan = hook(self.cell.traffic_name, "plan", default_plan,
                    self.cell.root / "bench/traffic")
        close = t0 + seconds
        deadline = traffic.get("deadline_s")
        lock = threading.Lock()
        records: List[Record] = []

        def client(ci):
            rng = rng_for(self.seed, 10 + ci)
            n, picks = 0, []
            while True:
                now = time.perf_counter()
                if now >= close:
                    return
                if not picks:  # the next pool's worth, evenly spread
                    picks = plan(rng, traffic, self.pool, self.designs)[::-1]
                pick = picks.pop()
                rec = Record(pick=pick.rhs, due=now, sent=now,
                             design=pick.design, tenant=pick.tenant,
                             deadline_s=deadline)
                with span("bench.submit"):
                    try:
                        rec.ticket = disp.submit(self.request(
                            rec, f"c{ci}-{n}"))
                    except Exception as exc:
                        rec.error = f"{type(exc).__name__}: {exc}"
                        rec.done = time.perf_counter()
                if rec.error is None:
                    _await(rec, close + WAIT_PAST_CLOSE_S, span)
                with lock:
                    records.append(rec)
                n += 1

        threads = [threading.Thread(target=client, args=(ci,),
                                    name=f"bench-client-{ci}")
                   for ci in range(int(traffic["clients"]))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        records.sort(key=lambda r: r.due)
        return records

    def close(self) -> None:
        """Free the program's state: engine lanes, design cache, device
        buffers."""
        self.engine.shutdown()
        self.engine = None
        gc.collect()


@contextlib.contextmanager
def _no_span(name):
    yield


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _mark_window(close: float, span) -> None:
    with span("bench.window"):
        time.sleep(max(0.0, close - time.perf_counter()))


def _await(rec: Record, until: float, span) -> None:
    """Wait for ``rec``'s answer until ``until``; stamp when it arrived."""
    if rec.error is not None:
        return
    try:
        res = rec.ticket.result(timeout=max(0.0, until - time.perf_counter()))
    except TimeoutError:
        rec.error = "no answer within a minute of the window's close"
        return
    except Exception as exc:
        rec.done = time.perf_counter()
        rec.error = f"{type(exc).__name__}: {exc}"
        return
    rec.done = time.perf_counter()
    with span("bench.result"):
        rec.result = res


COUNTER: Optional[CompileCounter] = None


# ------------------------------------------------------------- the check
def classify(records: List[Record], config: dict) -> None:
    """Mark each record's ``failure``: no answer, an error, or an answer
    served off the configuration's path (a kernel path, or a list of them
    for a mix whose groups take several) or placement, or after a retry."""
    paths = config["path"]
    paths = [paths] if isinstance(paths, str) else list(paths)
    for r in records:
        if r.result is None:
            r.failure = "unanswered" if r.done is None else "error"
            continue
        res = r.result
        tel = res.telemetry
        path = tel.kernel_path if tel is not None else None
        if not res.ok:
            r.failure = "error"
        elif res.retries:
            r.failure = "retry"
        elif path not in paths:
            r.failure = f"path {path}"
        elif res.placement != config["placement"]:
            r.failure = f"placement {res.placement}"


def compare(run: CellRun, records: List[Record]) -> dict:
    """Worst relative errors of the answers against the float64 reference:
    of the coefficients, against the reference's solution of each system;
    of the residual the answer carries, against ``y - x a`` of its own
    coefficients (every answer, or a sample of ``RESID_SAMPLE`` drawn from
    the seed), relative to ``|y|``.  One design at a time, so that one
    float64 copy of a design is held at once."""
    from bench.reference import lstsq_f64, rel_errors

    answered = [r for r in records if r.result is not None]
    t = time.perf_counter()
    rng = np.random.default_rng([run.seed, 4])
    sample = (set(range(len(answered))) if len(answered) <= RESID_SAMPLE
              else set(rng.choice(len(answered), RESID_SAMPLE,
                                  replace=False).tolist()))
    coef_err = resid_err = rel_grad = 0.0
    iters = 0
    for d, design in enumerate(run.designs):
        mine = [i for i, r in enumerate(answered) if r.design == d]
        if not mine:
            continue
        x64 = np.asarray(design.x, np.float64)
        targets = sorted({answered[i].pick for i in mine})
        refs, grad, it = lstsq_f64(x64, design.ys[targets])
        rel_grad, iters = max(rel_grad, grad), max(iters, it)
        row = {t: j for j, t in enumerate(targets)}
        coefs = np.stack([np.asarray(answered[i].result.coef, np.float64)
                          for i in mine])
        coef_err = max(coef_err, float(np.max(rel_errors(
            coefs, refs[[row[answered[i].pick] for i in mine]]))))
        idx = [j for j, i in enumerate(mine) if i in sample]
        if not idx:
            continue
        ys = np.asarray(design.ys, np.float64)[
            [answered[mine[j]].pick for j in idx]]
        served = np.stack([np.asarray(answered[mine[j]].result.residual,
                                      np.float64) for j in idx])
        true = ys - coefs[idx] @ x64.T
        resid_err = max(resid_err, float(np.max(
            np.linalg.norm(served - true, axis=1)
            / np.linalg.norm(ys, axis=1))))
        del x64
    return {"coef_rel_err": coef_err, "resid_rel_err": resid_err,
            "ref_rel_grad": rel_grad, "ref_iters": iters,
            "ref_s": time.perf_counter() - t}


def checks_of(config: dict, records, cmp: dict, deltas: dict) -> dict:
    """Each number compared, with its limit (exact counts have limit 0)."""
    failed = sum(1 for r in records if r.failure is not None)
    unanswered = sum(1 for r in records if r.failure == "unanswered")
    return {
        "coef_rel_err": {"value": cmp["coef_rel_err"],
                         "limit": float(config["limits"]["coef_rel_err"])},
        "resid_rel_err": {"value": cmp["resid_rel_err"],
                          "limit": float(config["limits"]["resid_rel_err"])},
        "failed_requests": {"value": failed, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
        "retries": {"value": sum(deltas["solver_retries_total"].values()),
                    "limit": 0},
        "fallbacks": {"value": sum(deltas["solver_fallback_total"].values()),
                      "limit": 0},
        "ref_rel_grad": {"value": cmp["ref_rel_grad"],
                         "limit": REF_GRAD_LIMIT},
    }


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


# ----------------------------------------------------------------- trace
@contextlib.contextmanager
def traced():
    """A profiler trace of the block, through the program's own
    ``start_profiling`` so its named regions are recorded, with the Python
    tracer off.  Yields a dict that holds the ``.xplane.pb`` path after."""
    import functools

    import jax
    from repro.obs import profiling

    out = {"dir": tempfile.mkdtemp(prefix="bench-trace-")}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    orig = jax.profiler.start_trace
    jax.profiler.start_trace = functools.partial(orig,
                                                 profiler_options=opts)
    try:
        started = profiling.start_profiling(out["dir"])
    finally:
        jax.profiler.start_trace = orig
    if not started:
        orig(out["dir"], profiler_options=opts)
    try:
        yield out
    finally:
        if started:
            profiling.stop_profiling()
        else:
            jax.profiler.stop_trace()
        found = sorted(pathlib.Path(out["dir"]).rglob("*.xplane.pb"))
        out["path"] = str(found[-1]) if found else None


# ------------------------------------------------------------------ main
def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root=ROOT, keep_trace=None, solve_patch=None,
        require_chip: bool = True, bench: Optional[dict] = None) -> dict:
    """One run of ``workload``; returns the result line's dict."""
    global COUNTER
    bench = bench or load_benchmark(root)
    c = cell(bench, workload, root)
    import jax

    if require_chip:
        devices = devices_for(c.chips)
    else:
        devices = jax.devices()
    use_compile_cache()
    if COUNTER is None:
        COUNTER = CompileCounter()
    from bench.work import peaks
    dev0 = devices[0]
    kind = dev0.device_kind
    peak = peaks(kind) if require_chip else {}
    used = devices[:c.chips]

    run_ = CellRun(c, seed, solve_patch=solve_patch)
    run_.setup()
    setup_s = time.perf_counter() - t_start
    trace_out = None
    if trace:
        with traced() as tr:
            t0, records, deltas = run_.window(seconds, annotate=True)
        trace_out = tr
    else:
        t0, records, deltas = run_.window(seconds)
    window_compiles = COUNTER.count
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in used)
    run_.close()
    classify(records, c.config)
    cmp = compare(run_, records)
    checks = checks_of(c.config, records, cmp, deltas)

    summary = None
    if trace_out is not None:
        from bench import trace as btrace
        if trace_out["path"] is None:
            raise RuntimeError("the profiler wrote no trace")
        if keep_trace:
            shutil.copy(trace_out["path"], keep_trace)
        summary = btrace.summarize(btrace.load(trace_out["path"]))
        shutil.rmtree(trace_out["dir"], ignore_errors=True)

    data = RunData(cell=c, seconds=seconds, setup_s=setup_s, t0=t0,
                   records=records, chips=c.chips, peak=peak,
                   shapes=[d.x.shape for d in run_.designs], trace=summary)
    metrics = {}
    for m in (c.per_layer if trace else c.end_to_end):
        value = metric_reader(m["name"])(data)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    lateness = [r.sent - r.due for r in records]
    worst = int(np.argmax(lateness)) if lateness else None
    by_sweeps: dict = {}
    for r in data.answered:
        by_sweeps.setdefault(int(r.result.n_sweeps), []).append(r.latency_s)
    info = {"window_compiles": window_compiles,
            "warmup_compiles": run_.warm_compiles,
            "generator_late_p95_s": (float(np.percentile(lateness, 95))
                                     if lateness else None),
            "generator_late_max_s": max(lateness) if lateness else None,
            "generator_late_max_at_s": (records[worst].due - t0
                                        if lateness else None),
            "gc_pauses": len(run_.gc_pauses),
            "gc_pause_max_s": max(run_.gc_pauses, default=0.0),
            "failures": sorted({r.failure for r in records
                                if r.failure is not None}),
            "dispatch_paths": deltas["solver_dispatch_total"],
            # Answers by the sweeps their solve took: how many, and their
            # median latency (what a seed's targets change, and what not).
            "sweeps_count": {n: len(v) for n, v in sorted(by_sweeps.items())},
            "sweeps_latency_p50_s": {n: float(np.median(v))
                                     for n, v in sorted(by_sweeps.items())},
            "ref_iters": cmp["ref_iters"], "ref_s": cmp["ref_s"],
            "setup_s": setup_s, **run_.timings}
    print(json.dumps({"info": info}), flush=True)

    device = {"platform": dev0.platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
    result = {"correct": passed(checks), "attempted": len(records),
              "failed": checks["failed_requests"]["value"],
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    return result


def print_checks(checks: dict, stream=sys.stderr) -> None:
    for name, c in checks.items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=stream, flush=True)
