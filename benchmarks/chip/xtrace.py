"""From a profiler trace to device busy time, kernel time and idle gaps.

A trace here is plain data, so that a test can write one by hand:

    {"start_wall_ns": <wall-clock ns of the profiler's start>,
     "planes": {plane name: [(line name, [(event name, start_ns, dur_ns)])]}}

Event times are relative to ``start_wall_ns``, as JAX's profiler writes them.
On a TPU the device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per operation, their ``XLA Modules`` line one per jitted
program (``jit_<function>(<fingerprint>)``). Host annotations the benchmark
writes with ``jax.profiler.TraceAnnotation`` are named ``bench.*`` and sit on
the host plane.
"""

from __future__ import annotations

import glob
import os
import re
from bisect import bisect_right
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS = "XLA Ops"
MODULES = "XLA Modules"
HOST_PREFIX = "bench."
#: Program spans that contain others; an idle gap is named after what runs
#: inside them.
CONTAINERS = ("server.read_range", "executor.run", "gateway.request", "reader.pread")
TOP = 10

Interval = Tuple[float, float]


def load(log_dir: str) -> dict:
    """Read the ``.xplane.pb`` that ``jax.profiler`` wrote under ``log_dir``."""
    import jax

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError("expected one profiler trace under %s, found %d" % (log_dir, len(paths)))
    data = jax.profiler.ProfileData.from_file(paths[0])
    start = None
    planes: Dict[str, list] = {}
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = int(dict(plane.stats)["profile_start_time"])
            continue
        device = plane.name.startswith(DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS, MODULES):
                continue
            events = [(e.name, e.start_ns, e.duration_ns) for e in line.events
                      if device or e.name.startswith(HOST_PREFIX)]
            if events:
                lines.append((line.name, events))
        if lines:
            planes[plane.name] = lines
    if start is None:
        raise RuntimeError("the profiler trace has no start time")
    return {"start_wall_ns": start, "planes": planes}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _line(lines, name) -> List[Tuple[str, float, float]]:
    return [e for line_name, events in lines if line_name == name for e in events]


def module_name(event_name: str) -> str:
    """``jit_crc32_segments_batched(1720...)`` -> ``jit_crc32_segments_batched``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%fusion.1 = s32[...] fusion(...)`` -> ``fusion.1``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _base(name: str) -> str:
    return re.sub(r"\.\d+$", "", name)


def device_planes(trace: dict) -> List[str]:
    return sorted(p for p in trace["planes"] if p.startswith(DEVICE_PREFIX))


def reduce(trace: dict, window_ns: Interval, kernels: Sequence[str],
           spans: Sequence[dict] = ()) -> dict:
    """Busy and idle time, kernel time and the breakdown of one window.

    ``window_ns`` is relative to the trace's start. ``spans`` are the
    program's own spans (``repro.obs``), whose wall-clock ``ts`` places them
    on the trace's clock, to name the idle gaps.
    """
    lo, hi = window_ns
    planes = device_planes(trace)
    busy, kernel_s = [], defaultdict(float)
    op_time: Dict[str, float] = defaultdict(float)
    gaps: List[Interval] = []
    for i, plane in enumerate(planes):
        lines = trace["planes"][plane]
        ops = [(s, s + d, n) for n, s, d in _line(lines, OPS)]
        modules = sorted((s, s + d, module_name(n)) for n, s, d in _line(lines, MODULES))
        on = union(clip(((a, b) for a, b, _ in ops), lo, hi))
        busy.append(length(on))
        for a, b, name in modules:
            for k in kernels:
                if name in ("jit_" + k, k):
                    kernel_s[k] += length(clip([(a, b)], lo, hi))
        starts = [m[0] for m in modules]
        for a, b, name in ops:
            j = bisect_right(starts, a) - 1
            module = modules[j][2] if j >= 0 and modules[j][1] >= a else "?"
            dur = length(clip([(a, b)], lo, hi))
            op_time[module + "/" + op_name(name)] += dur
        if i == 0:
            edges = [lo] + [x for a, b in on for x in (a, b)] + [hi]
            gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    # A kernel called inside a larger program has no module of its own:
    # fall back to its operations (a Pallas kernel's custom call keeps its name).
    for k in kernels:
        if not kernel_s.get(k):
            kernel_s[k] = sum(t for name, t in op_time.items()
                              if _base(name.rsplit("/", 1)[1]) == k)
    n = max(1, len(planes))
    hosts = _host_intervals(trace, spans)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "devices": len(planes),
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "kernel_s": {k: kernel_s[k] / n / 1e9 for k in kernels},
        "device_ops": [[name, t / n / 1e9] for name, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[gap_name(g, hosts), (g[1] - g[0]) / 1e9] for g in gaps[:TOP]],
    }


def _host_intervals(trace: dict, spans: Sequence[dict]) -> Dict[str, List[Interval]]:
    """Host activity by name on the trace's clock: the benchmark's
    annotations, and the program's spans (a fetcher task by its kind)."""
    out: Dict[str, List[Interval]] = defaultdict(list)
    for plane, lines in trace["planes"].items():
        if plane.startswith(DEVICE_PREFIX):
            continue
        for _, events in lines:
            for name, s, d in events:
                if name.startswith(HOST_PREFIX):
                    out[name].append((s, s + d))
    t0 = trace["start_wall_ns"]
    for s in spans:
        name = s["name"]
        if name == "fetcher.task":
            name += ":" + str(s["attrs"].get("kind", "?"))
        a = s["ts"] * 1e9 - t0
        out[name].append((a, a + s["dur_s"] * 1e9))
    return {k: union(v) for k, v in out.items()}


def gap_name(gap: Interval, hosts: Dict[str, List[Interval]]) -> str:
    """What the host was doing in an idle gap: the two program spans that
    cover most of it, containers aside; else the container that covers most
    of it; else the benchmark's annotation."""
    cover = sorted(((length(clip(iv, gap[0], gap[1])), n) for n, iv in hosts.items()),
                   reverse=True)
    cover = [(t, n) for t, n in cover if t > 0]
    for keep, many in ((lambda n: not n.startswith(HOST_PREFIX) and n not in CONTAINERS, 2),
                       (lambda n: n in CONTAINERS, 1),
                       (lambda n: True, 1)):
        names = [n for _, n in cover if keep(n)]
        if names:
            return "+".join(names[:many])
    return "no host span"
