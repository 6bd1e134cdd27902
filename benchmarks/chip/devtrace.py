"""Reduction of a profiler trace (``.xplane.pb``) to device intervals.

``load`` reads the file with JAX's own ``ProfileData`` into plain lists:
the device's operations ``(name, start_ns, dur_ns)`` per device plane, and
the host spans the harness records (names starting with ``bench.``).
``reduce`` then works on those lists alone, so a recorded trace trimmed to
JSON (``to_json`` / ``from_json``) is reduced exactly like the file.

The traced window runs from the start of the first ``bench.step`` span to
the end of the last one that began before the device planes end: where the
profiler's buffers filled, the window is the part that both kept, and
``steps`` says how many of the run's steps it holds.  Busy time is the union of operation intervals
(a layer loop's included) within it, per TPU, averaged over TPUs; an idle gap is a stretch of
the window with no operation, labelled by the host span that overlaps it
most (``host`` where none does).
"""
from __future__ import annotations

import bisect
import collections
import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

HOST_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# operations that contain others (a scanned layer loop): busy while they
# run, but their time is their body's, so they are left out of the
# per-operation breakdown
CONTAINERS = ("while", "conditional", "call")
_SUFFIX = re.compile(r"[.:]\d+$")


def _short(name: str) -> str:
    """``%fusion.141 = (bf16[...]) fusion(...)`` -> ``fusion.141``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _string_stats(ev) -> str:
    parts = []
    for _name, value in ev.stats:
        if isinstance(value, str):
            parts.append(value)
    return " ".join(parts)


def load(path: str, kernels: Iterable[str] = ()) -> dict:
    """Device operations and host spans of one ``.xplane.pb`` file.  An
    operation whose name or string stats mention one of ``kernels`` is
    renamed to that kernel."""
    from jax.profiler import ProfileData

    kernels = tuple(kernels)
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    modules: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    named: Dict[str, str] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = [(_short(ev.name), float(ev.start_ns), float(ev.duration_ns))
                                           for ev in line.events]
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = ev.name
                    if name not in named:
                        text = name + " " + _string_stats(ev)
                        named[name] = next((k for k in kernels if k in text), _short(name))
                    ops.append((named[name], float(ev.start_ns), float(ev.duration_ns)))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, float(ev.start_ns), float(ev.duration_ns)))
    return {"devices": devices, "modules": modules, "host": host}


def find_xplane(directory: str) -> str:
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def to_json(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def from_json(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def trim(trace: dict, t0: float, t1: float) -> dict:
    """The part of a trace that starts inside [t0, t1]."""
    keep = lambda evs: [e for e in evs if t0 <= e[1] <= t1]
    return {"devices": {k: keep(v) for k, v in trace["devices"].items()},
            "modules": {k: keep(v) for k, v in trace.get("modules", {}).items()},
            "host": keep(trace["host"])}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def op_family(name: str) -> str:
    """An operation's name without the instance number XLA appends."""
    return _SUFFIX.sub("", name)


def reduce(trace: dict, top: int = 10, mark: Iterable[str] = ("PrefillAttn",)) -> Optional[dict]:
    """Busy and idle time, time per operation family, and idle gaps by host
    span, over the traced window; ``program_s[k]`` is the time of the
    programs (``XLA Modules``) that ran kernel ``k`` of ``mark``.  None
    when the trace holds no step span or no device operation."""
    steps = sorted((s, s + d) for n, s, d in trace["host"] if n == HOST_PREFIX + "step")
    if not steps or not any(trace["devices"].values()):
        return None
    device_end = min(max(s + d for _, s, d in ops) for ops in trace["devices"].values() if ops)
    steps = [st for st in steps if st[0] < device_end]
    if not steps:
        return None
    t0 = steps[0][0]
    t1 = max(b for _, b in steps)
    window = t1 - t0
    spans = sorted((s, s + d, n[len(HOST_PREFIX):]) for n, s, d in trace["host"])
    starts = [s for s, _, _ in spans]
    reach = max(e - s for s, e, _ in spans)
    busy_total = 0.0
    by_op: Dict[str, float] = collections.Counter()
    gaps: Dict[str, float] = collections.Counter()
    longest: List[Tuple[float, str]] = []
    for ops in trace["devices"].values():
        inside = []
        for name, s, d in ops:
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                inside.append((a, b))
                family = op_family(name)
                if family not in CONTAINERS:
                    by_op[family] += b - a
        merged = _union(inside)
        busy_total += sum(b - a for a, b in merged)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            label = _label(spans, starts, reach, a, b)
            gaps[label] += b - a
            longest.append((b - a, label))
    ndev = len(trace["devices"])
    busy = busy_total / ndev
    marked: Dict[str, float] = collections.Counter()
    for dev, mods in trace.get("modules", {}).items():
        for kernel in mark:
            starts_k = sorted(s for n, s, _ in trace["devices"].get(dev, []) if n == kernel)
            for _, s, d in mods:
                a, b = max(s, t0), min(s + d, t1)
                i = bisect.bisect_left(starts_k, s)
                if b > a and i < len(starts_k) and starts_k[i] <= s + d:
                    marked[kernel] += b - a
    return {
        "window_s": window / 1e9,
        "steps": len(steps),
        "busy_s": busy / 1e9,
        "idle_share": 1.0 - busy / window if window > 0 else None,
        "op_s": {k: v / 1e9 / ndev for k, v in by_op.items()},
        "device_ops": [[k, v / 1e9 / ndev] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9 / ndev] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
        "program_s": {k: v / 1e9 / ndev for k, v in marked.items()},
        "gap_count": len(longest),
        "longest_gap_s": max(longest)[0] / 1e9 if longest else 0.0,
    }


def _label(spans, starts, reach: float, a: float, b: float) -> str:
    """The host span that overlaps [a, b] most; a span other than a step
    wins a tie (it lies inside one).  ``reach`` is the longest span."""
    best, best_len = "host", 0.0
    i = bisect.bisect_right(starts, b) - 1
    while i >= 0 and spans[i][0] >= a - reach:
        s, e, name = spans[i]
        i -= 1
        ov = min(e, b) - max(s, a)
        if ov > 0 and (ov > best_len or (ov == best_len and name != "step")):
            best, best_len = name, ov
    return best
