"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load`` reads an ``.xplane.pb`` into plain lists: for each device, its
operations and its programs as ``(name, start_ns, dur_ns)``, and the host's
named spans (``jax.profiler.TraceAnnotation`` regions: the harness's own
``bench.*`` and the program's ``engine.flush`` / ``solve/<method>``).
``summarize`` reduces them over the traced window:

* busy time: the union of the intervals in which an operation ran on the
  device, clipped to the window; idle is the rest;
* idle gaps: each stretch of the window with no operation on the device,
  named by the host span open at its midpoint (the program's spans before
  the harness's, the innermost first), summed by name;
* per-operation device time (by the first ``OP_NAME_CHARS`` characters
  of the op's HLO text; loops and calls are left out of this ranking,
  since the ops inside them are in it), and the device time of the programs (XLA
  modules) that ended inside the window, whole (on the chip every program
  loaded from the compilation cache is named ``jit__unknown(<hash>)``, so
  programs are told apart by when they ran, not by name);
* collectives: operations named like an XLA collective.

Device numbers are means over the devices the cell used.
"""
from __future__ import annotations

import heapq
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]   # (name, start_ns, dur_ns)

TPU_PLANE = r"^/device:TPU:\d+$"
OP_LINE = r"^XLA Ops$"
MODULE_LINE = r"^XLA Modules$"
HOST_PLANE = r"^/host:CPU$"
PROGRAM_SPANS = r"^(engine\.flush|solve/.+|dispatch\..+)$"
HARNESS_SPANS = r"^bench\.(?!window$).+$"
WINDOW_SPAN = "bench.window"
COLLECTIVES = (r"all-reduce|all-gather|reduce-scatter|collective-permute"
               r"|all-to-all|psum")
NO_SPAN = "(no host span)"
OP_NAME_CHARS = 160   # an op's HLO text, cut: its name, shapes and kind
CONTAINER_OP = r"^%?(while|conditional|call)(\.\d+)? = "   # holds other ops


@dataclass
class Trace:
    devices: Dict[str, Dict[str, List[Event]]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)


def load(path, *, device_plane: str = TPU_PLANE, op_line: str = OP_LINE,
         module_line: str = MODULE_LINE, host_plane: str = HOST_PLANE,
         span_names: str = f"({PROGRAM_SPANS})|({HARNESS_SPANS})"
         f"|^{re.escape(WINDOW_SPAN)}$") -> Trace:
    """Read ``path`` (an ``.xplane.pb``) into a ``Trace``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    dev_re, op_re, mod_re = (re.compile(device_plane), re.compile(op_line),
                             re.compile(module_line))
    host_re, span_re = re.compile(host_plane), re.compile(span_names)
    out = Trace()
    for plane in pd.planes:
        if dev_re.search(plane.name):
            dev = out.devices.setdefault(plane.name,
                                         {"ops": [], "modules": []})
            for line in plane.lines:
                kind = ("ops" if op_re.search(line.name) else
                        "modules" if mod_re.search(line.name) else None)
                if kind is None:
                    continue
                dev[kind].extend((e.name, e.start_ns, e.duration_ns)
                                 for e in line.events if e.duration_ns > 0)
        if host_re.search(plane.name):
            for line in plane.lines:
                out.spans.extend((e.name, e.start_ns, e.duration_ns)
                                 for e in line.events
                                 if e.duration_ns > 0 and span_re.search(
                                     e.name))
    return out


def merge(intervals: Sequence[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that ``busy`` (merged) leaves out."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gaps(gap_list: Sequence[Tuple[float, float]],
              spans: Sequence[Event]) -> Dict[str, float]:
    """Seconds of idle gap by the host span open at each gap's midpoint:
    a program span before a harness span, then the latest-started."""
    prog, harness = re.compile(PROGRAM_SPANS), re.compile(HARNESS_SPANS)
    ranked = []
    for name, s, d in spans:
        rank = 2 if prog.search(name) else 1 if harness.search(name) else 0
        if rank:
            ranked.append((s, s + d, rank, name))
    ranked.sort()
    totals: Dict[str, float] = defaultdict(float)
    active: List[Tuple[float, int]] = []   # heap of (end, index)
    i = 0
    for g0, g1 in sorted(gap_list):
        mid = 0.5 * (g0 + g1)
        while i < len(ranked) and ranked[i][0] <= mid:
            heapq.heappush(active, (ranked[i][1], i))
            i += 1
        while active and active[0][0] < mid:
            heapq.heappop(active)
        best = max(((ranked[j][2], ranked[j][0], ranked[j][3])
                    for _, j in active), default=None)
        totals[best[2] if best else NO_SPAN] += (g1 - g0) * 1e-9
    return dict(totals)


def window_of(trace: Trace) -> Tuple[float, float]:
    """``[start, end]`` (ns) of the harness's window span, or of all device
    activity when the trace has no such span."""
    win = [(s, s + d) for n, s, d in trace.spans if n == WINDOW_SPAN]
    if win:
        return min(w[0] for w in win), max(w[1] for w in win)
    ev = [(s, s + d) for dev in trace.devices.values()
          for s_name in ("ops", "modules") for _, s, d in dev[s_name]]
    if not ev:
        raise ValueError("trace has neither a window span nor device events")
    return min(e[0] for e in ev), max(e[1] for e in ev)


def _clipped(events: Sequence[Event], lo: float, hi: float,
             pattern) -> float:
    """Seconds of the ``events`` matching ``pattern`` inside ``[lo, hi]``."""
    tot = 0.0
    for name, s, d in events:
        if not pattern.search(name):
            continue
        tot += max(0.0, min(s + d, hi) - max(s, lo))
    return tot * 1e-9


def summarize(trace: Trace, *, collectives: str = COLLECTIVES,
              top: int = 10) -> dict:
    """Device numbers of the traced window, means over the devices."""
    lo, hi = window_of(trace)
    window_s = (hi - lo) * 1e-9
    devs = sorted(trace.devices)
    if not devs:
        raise ValueError("trace holds no device plane")
    coll_re, container_re = re.compile(collectives), re.compile(CONTAINER_OP)
    busy_s = program_s = coll_s = 0.0
    op_tot: Dict[str, float] = defaultdict(float)
    gap_tot: Dict[str, float] = defaultdict(float)
    for name in devs:
        dev = trace.devices[name]
        merged = merge([(s, s + d) for _, s, d in dev["ops"]], lo, hi)
        busy_s += sum(e - s for s, e in merged) * 1e-9
        program_s += sum(d for _, s, d in dev["modules"]
                         if lo < s + d <= hi) * 1e-9
        coll_s += _clipped(dev["ops"], lo, hi, coll_re)
        for op, s, d in dev["ops"]:
            if container_re.search(op):
                continue
            op_tot[op[:OP_NAME_CHARS]] += max(0.0, min(s + d, hi) - max(s, lo)) * 1e-9
        for gname, secs in name_gaps(gaps(merged, lo, hi),
                                     trace.spans).items():
            gap_tot[gname] += secs
    n = len(devs)

    def ranked(d):
        return [[k, v / n] for k, v in sorted(d.items(), key=lambda kv:
                                              -kv[1])[:top]]

    return {"devices": n, "window_s": window_s, "busy_s": busy_s / n,
            "program_s": program_s / n,
            "collective_s": coll_s / n, "device_ops": ranked(op_tot),
            "idle_gaps": ranked(gap_tot)}
