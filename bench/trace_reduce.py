"""Profiler trace (``.xplane.pb``) -> device busy and idle time, device
time per executable, and the breakdown a result line carries.

Device activity is read from the planes and lines named by regular
expressions (on a TPU: ``/device:TPU:N``, lines ``XLA Ops`` and ``XLA
Modules``). Host spans are the harness's own
``jax.profiler.TraceAnnotation`` names, on any line of the host plane.
The window is the host span named ``window``.
"""
from __future__ import annotations

import re
from typing import Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

TPU = dict(plane=r"^/device:TPU:\d+$", ops=r"^XLA Ops$", modules=r"^XLA Modules$")
#: a CPU trace has no device plane: XLA's CPU threads stand in for one
#: (tests and rehearsals only; a benchmark run refuses the CPU)
CPU = dict(plane=r"^/host:CPU$", ops=r"^tf_XLA(PjRtCpuClient|Eigen)", modules=None)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _exe_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def read_events(path) -> dict:
    """Events of a trace file: ``{"device": {plane: {"ops": [...],
    "modules": [...]}}, "host": [...]}`` as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    planes = []
    for p in pd.planes:
        lines = [(ln.name, [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in ln.events]) for ln in p.lines]
        planes.append((p.name, lines))
    return planes


def reduce(planes, *, plane: str, ops: str, modules: Optional[str],
           host_plane: str = r"^/host:CPU$", spans: Sequence[str] = (),
           top: int = 10) -> dict:
    """Reduce trace events to the window's device figures.

    Returns ``window_s``, ``busy_s`` (mean over device planes),
    ``executables`` (name -> [calls, device seconds], summed over
    planes), ``span_busy_s`` (label -> device busy seconds inside that
    label's host spans), ``idle_gaps`` (the ``top`` longest gaps as
    ``[label, seconds]``, labelled by the innermost host span at their
    middle, ``bookkeeping`` where none is open) and ``device_ops``
    (the ``top`` executables by device time as ``[name, seconds]``).
    """
    host = [(n, s, e) for pname, lines in planes if re.search(host_plane, pname)
            for _, evs in lines for n, s, e in evs
            if n in spans or n == "window"]
    win = [(s, e) for n, s, e in host if n == "window"]
    if not win:
        raise ValueError("trace holds no 'window' span")
    lo, hi = win[0]
    dev_planes = [(pn, lines) for pn, lines in planes if re.search(plane, pn)]
    if not dev_planes:
        raise ValueError(f"trace holds no plane matching {plane!r}")
    busy_per_plane, exes = [], {}
    span_iv = {lab: union(clip([(s, e) for n, s, e in host if n == lab], lo, hi))
               for lab in spans}
    span_busy = {lab: 0.0 for lab in spans}
    all_gaps = []
    for _, lines in dev_planes:
        op_ev = [ev for ln, evs in lines if re.search(ops, ln) for ev in evs
                 if ev[2] > ev[1]]
        mod_ev = ([ev for ln, evs in lines if re.search(modules, ln) for ev in evs
                   if ev[2] > ev[1]] if modules else op_ev)
        busy = union(clip([(s, e) for _, s, e in op_ev], lo, hi))
        busy_per_plane.append(total(busy))
        for name, s, e in mod_ev:
            if e > lo and s < hi:
                cnt = exes.setdefault(_exe_name(name), [0, 0.0])
                cnt[0] += 1
                cnt[1] += (min(e, hi) - max(s, lo)) * 1e-9
        for lab, iv in span_iv.items():
            span_busy[lab] += total(intersect(busy, iv)) * 1e-9 / len(dev_planes)
        all_gaps += gaps(busy, lo, hi)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for gs, ge in all_gaps[:top]:
        mid = (gs + ge) / 2
        open_ = [(e - s, n) for n, s, e in host if n != "window" and s <= mid <= e]
        labelled.append([min(open_)[1] if open_ else "bookkeeping", (ge - gs) * 1e-9])
    ranked = sorted(exes.items(), key=lambda kv: -kv[1][1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_per_plane) / len(busy_per_plane) * 1e-9,
        "executables": exes,
        "span_busy_s": span_busy,
        "idle_gaps": labelled,
        "device_ops": [[n, v[1]] for n, v in ranked[:top]],
    }
