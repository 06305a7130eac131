"""The program's own spans in a traced run, and the device's idle time
under them.

The engine opens ``jax.profiler.TraceAnnotation`` spans named
``engine.*``, ``kv.*`` and ``pool.*`` (``repro.serving.scheduler``);
they land in the profiler's trace on the device's clock. ``attribution``
reads the newest ``*.xplane.pb`` under ``<root>/.bench_trace/<cell>``,
the directory ``bench/run.py`` hands the harness, once per trace file,
and splits the window's device-idle time by the innermost program span
open in it. Idle that overlaps a JAX lowering or compile event on the
host is put in a ``compile`` row, whichever span is open; idle with no
program span and no compile is ``(none)``. The table goes to standard
error. A run without a trace file, or whose program opened no span in
the window, reads None.
"""
from __future__ import annotations

import functools
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce as tr

PROGRAM = r"^(engine|kv|pool)\."
#: JAX's own host events around tracing, lowering and compiling a program
COMPILE = (r"^(lower_sharding_computation|lower_parallel_callable"
           r"|backend_compile|backend_compile_and_load)$")
HOST = r"^/host:CPU$"
NONE, COMPILE_ROW = "(none)", "compile"
TOP = 10


def trace_file(run) -> Optional[Path]:
    cell = run["cell"]
    files = sorted(Path(cell.root, ".bench_trace", cell.name).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    return files[-1] if files else None


def attribution(run) -> Optional[dict]:
    """The attribution of the run's trace (see the module docstring),
    or None."""
    path = trace_file(run)
    if path is None:
        return None
    st = path.stat()
    return _attribution_of(str(path), st.st_mtime_ns, st.st_size)


@functools.lru_cache(maxsize=1)
def _attribution_of(path: str, mtime_ns: int, size: int) -> Optional[dict]:
    att = attribute(tr.read_events(path))
    if att is not None:
        log_table(att)
    return att


def _flatten(spans: Sequence[Tuple[str, float, float]], lo: float, hi: float
             ) -> Dict[str, List[Tuple[float, float]]]:
    """Partition ``[lo, hi]`` by the innermost span open at each point:
    label -> sorted disjoint intervals (``NONE`` where none is open).
    Spans of one thread nest; one that outlasts its parent is cut at
    the parent's end."""
    out: Dict[str, List[Tuple[float, float]]] = {}

    def emit(s, e, label):
        if e > s:
            out.setdefault(label, []).append((s, e))

    stack: List[Tuple[float, str]] = []       # (end, name)
    t = lo
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        s, e = max(s, lo), min(e, hi)
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            emit(t, end, top)
            t = max(t, end)
        emit(t, s, stack[-1][1] if stack else NONE)
        t = max(t, s)
        if stack:
            e = min(e, stack[-1][0])
        stack.append((e, name))
    while stack:
        end, top = stack.pop()
        emit(t, end, top)
        t = max(t, end)
    emit(t, hi, NONE)
    return out


def attribute(planes) -> Optional[dict]:
    """Device-idle attribution of trace events (``trace_reduce.read_events``).

    Returns ``window_s``, ``idle_s``, ``idle_by_span`` (innermost
    label -> idle seconds), ``untraced_idle_s`` (idle with no program
    span open, compile or not), ``spans`` (name -> ``count``, the
    seconds the spans of that name cover in the window ``span_s``, the
    device's ``busy_s`` and ``idle_s`` inside them) and ``gaps`` (the
    ``TOP`` longest idle gaps as ``[start offset s, seconds, label,
    [(label, seconds), ...]]``, labelled by what covers most of the
    gap, each label's share listed). None where no program span
    overlaps the window.
    """
    host = [ev for pname, lines in planes if re.search(HOST, pname)
            for _, evs in lines for ev in evs]
    win = [(s, e) for n, s, e in host if n == "window"]
    if not win:
        return None
    lo, hi = win[0]
    prog = [ev for ev in host if re.search(PROGRAM, ev[0]) and ev[2] > lo and ev[1] < hi]
    if not prog:
        return None
    where = tr.TPU if any(re.search(tr.TPU["plane"], p) for p, _ in planes) else tr.CPU
    busy = tr.union(tr.clip(
        [(s, e) for pname, lines in planes if re.search(where["plane"], pname)
         for ln, evs in lines if re.search(where["ops"], ln)
         for _, s, e in evs if e > s], lo, hi))
    idle = tr.gaps(busy, lo, hi)
    comp = tr.union(tr.clip([(s, e) for n, s, e in host if re.search(COMPILE, n)], lo, hi))
    idle_by = {COMPILE_ROW: tr.total(tr.intersect(idle, comp)) * 1e-9}
    nocomp = tr.gaps(comp, lo, hi)
    idle_nc = tr.intersect(idle, nocomp)
    parts = _flatten(prog, lo, hi)
    for label, iv in parts.items():
        idle_by[label] = tr.total(tr.intersect(idle_nc, iv)) * 1e-9
    traced = tr.union(tr.clip([(s, e) for _, s, e in prog], lo, hi))
    spans = {}
    for name in sorted({n for n, _, _ in prog}):
        iv = tr.union(tr.clip([(s, e) for n, s, e in prog if n == name], lo, hi))
        span_s = tr.total(iv) * 1e-9
        idle_in = tr.total(tr.intersect(idle, iv)) * 1e-9
        spans[name] = {"count": sum(n == name for n, _, _ in prog), "span_s": span_s,
                       "busy_s": span_s - idle_in, "idle_s": idle_in}
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:TOP]
    gaps = []
    for gs, ge in longest:
        rest = tr.intersect([(gs, ge)], nocomp)
        cover = {lab: tr.total(tr.intersect(rest, iv)) * 1e-9 for lab, iv in parts.items()}
        cover[COMPILE_ROW] = tr.total(tr.intersect([(gs, ge)], comp)) * 1e-9
        shares = sorted(((lab, c) for lab, c in cover.items() if c > 0), key=lambda x: -x[1])
        gaps.append([(gs - lo) * 1e-9, (ge - gs) * 1e-9, shares[0][0], shares])
    return {"window_s": (hi - lo) * 1e-9, "idle_s": tr.total(idle) * 1e-9,
            "idle_by_span": idle_by,
            "untraced_idle_s": tr.total(tr.intersect(idle, tr.gaps(traced, lo, hi))) * 1e-9,
            "spans": spans, "gaps": gaps}


def log_table(att: dict) -> None:
    w, idle = att["window_s"], att["idle_s"]
    lines = [f"spans: device idle {idle:.6f} s of the {w:.6f} s window "
             f"({100 * idle / w:.3f}%), by innermost program span",
             f"  {'span':<20}{'idle_s':>12}{'% of idle':>11}"]
    for lab, s in sorted(att["idle_by_span"].items(), key=lambda kv: -kv[1]):
        lines.append(f"  {lab:<20}{s:>12.6f}{100 * s / max(idle, 1e-12):>11.3f}")
    lines.append(f"spans: untraced idle {att['untraced_idle_s']:.6f} s (no program span open)")
    lines.append(f"  {'span, inclusive':<20}{'count':>7}{'span_s':>12}{'busy_s':>12}"
                 f"{'idle_s':>12}{'ms/span':>11}")
    for name, v in att["spans"].items():
        lines.append(f"  {name:<20}{v['count']:>7}{v['span_s']:>12.6f}{v['busy_s']:>12.6f}"
                     f"{v['idle_s']:>12.6f}{1e3 * v['span_s'] / v['count']:>11.3f}")
    lines.append(f"spans: the {len(att['gaps'])} longest idle gaps "
                 "(offset in the window, seconds, label, what covers it)")
    for off, s, lab, shares in att["gaps"]:
        parts = ", ".join(f"{n} {c:.6f}" for n, c in shares[:4])
        lines.append(f"  +{off:10.6f} s {s:10.6f} s  {lab:<18} {parts}")
    print("\n".join(lines), file=sys.stderr, flush=True)
