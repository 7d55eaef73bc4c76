"""From a profiler trace to busy time, idle gaps and the top device ops.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
:func:`load` reads it with ``jax.profiler.ProfileData``: the device
operations and program executions of each chip (the ``XLA Ops`` and
``XLA Modules`` lines of every ``/device:TPU:n`` plane) and the
benchmark's own host spans (``TraceAnnotation`` events named in
``span_names``).  The reductions below are plain interval arithmetic on
``(start_ns, end_ns)`` pairs.  Host and device stamps share one clock
only to about a millisecond (a v5e put its ops ~1.2 ms before the host
call that launched them), so a gap between two programs is measured on
the device's own stamps, and host spans only label the gaps.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[int, int]
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    """Device ops per chip and the harness's host spans."""

    ops: Dict[str, List[Tuple[int, int, str]]]     # plane -> (s, e, name)
    spans: List[Tuple[int, int, str]]              # (s, e, span name)
    modules: Dict[str, List[Tuple[int, int, str]]] = dataclasses.field(
        default_factory=dict)                      # plane -> executions
    dropped: int = 0       # trace buffers the device planes report lost


def xplane_file(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir: Path, span_names: Iterable[str]) -> Trace:
    from jax.profiler import ProfileData

    wanted = set(span_names)
    t0 = time.perf_counter()
    path = xplane_file(trace_dir)
    data = ProfileData.from_file(str(path))
    ops: Dict[str, List[Tuple[int, int, str]]] = {}
    modules: Dict[str, List[Tuple[int, int, str]]] = {}
    spans: List[Tuple[int, int, str]] = []
    dropped = 0
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dropped += sum(int(v) for k, v in plane.stats
                           if "drop" in str(k).lower()
                           and isinstance(v, (int, float)))
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    into = ops if line.name == OPS_LINE else modules
                    into[plane.name] = [
                        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                         op_name(ev.name)) for ev in line.events]
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        spans.append((int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns),
                                      ev.name))
    if not ops:
        raise ValueError(f"the trace has no '{OPS_LINE}' line on a "
                         f"{DEVICE_PLANE_PREFIX}n plane; planes: "
                         f"{[p.name for p in data.planes]}")
    spans.sort()
    print(f"trace: {path.stat().st_size / 2**20:.1f} MiB, "
          f"{sum(map(len, ops.values()))} ops, "
          f"{sum(map(len, modules.values()))} program runs, "
          f"{dropped} trace buffers dropped, read in "
          f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
    return Trace(ops=ops, spans=spans, modules=modules, dropped=dropped)


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def busy_ns(merged: Sequence[Interval], lo: int, hi: int) -> int:
    """Time in [lo, hi) in which some operation ran."""
    return sum(e - s for s, e in clip(merged, lo, hi))


def idle_gaps(merged: Sequence[Interval], lo: int, hi: int
              ) -> List[Interval]:
    """The idle stretches of [lo, hi), including those at either end."""
    gaps, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label(gap: Interval, spans: Sequence[Tuple[int, int, str]]) -> str:
    """The innermost host span that holds the gap's midpoint."""
    mid = (gap[0] + gap[1]) // 2
    best = None
    for s, e, name in spans:
        if s <= mid < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "outside_spans"


# control flow spans the ops it runs, which the line lists too
CONTAINERS = ("while", "conditional", "call")


def top_ops(ops: Sequence[Tuple[int, int, str]], lo: int, hi: int,
            n: int = 10) -> List[List]:
    """The ``n`` device operations with the most total time in [lo, hi),
    control-flow ops left out."""
    tot: Dict[str, int] = {}
    for s, e, name in ops:
        if name.startswith(CONTAINERS):
            continue
        s, e = max(s, lo), min(e, hi)
        if e > s:
            tot[name] = tot.get(name, 0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def traced_window(trace: Trace, first: str, last: str,
                  calls: int) -> Tuple[int, int]:
    """From the start of the first ``first`` span to the end of the last
    ``last`` span; the main program must have run once per traced call,
    and no trace buffer may be lost, or the trace lost device events."""
    runs = len(main_program(trace))
    if runs != calls or trace.dropped:
        raise RuntimeError(f"the trace holds {runs} runs of the main "
                           f"program for {calls} traced calls and lost "
                           f"{trace.dropped} trace buffers")
    lo = min(s for s, _, n in trace.spans if n == first)
    hi = max(e for _, e, n in trace.spans if n == last)
    return lo, hi


def reduce(trace: Trace, lo: int, hi: int) -> dict:
    """Busy and idle of the window [lo, hi), averaged over the chips, with
    the top device ops and the longest labelled idle gaps (first chip)."""
    planes = sorted(trace.ops)
    merged = {p: union((s, e) for s, e, _ in trace.ops[p]) for p in planes}
    busy = [busy_ns(merged[p], lo, hi) for p in planes]
    window = hi - lo
    first = planes[0]
    gaps = sorted(idle_gaps(merged[first], lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": window / 1e9,
        "idle_share": 1.0 - sum(busy) / len(busy) / window,
        "device_ops": top_ops(trace.ops[first], lo, hi),
        "idle_gaps": [[label(g, trace.spans), (g[1] - g[0]) / 1e9]
                      for g in gaps],
    }


def main_program(trace: Trace) -> List[Interval]:
    """The executions of the program that took the most device time on the
    first chip, in order."""
    plane = sorted(trace.ops)[0]
    total: Dict[str, int] = {}
    for s, e, name in trace.modules.get(plane, []):
        total[name] = total.get(name, 0) + (e - s)
    if not total:
        return []
    main = max(total, key=total.get)
    return sorted((s, e) for s, e, name in trace.modules[plane]
                  if name == main)


def program_gaps(trace: Trace, lo: int, hi: int) -> List[int]:
    """Device idle between consecutive executions, inside [lo, hi), of
    the program that took the most device time (first chip): from the end
    of one to the start of the next, less any other work the device did
    in between."""
    plane = sorted(trace.ops)[0]
    mine = [(s, e) for s, e in main_program(trace) if lo <= s and e <= hi]
    merged = union((s, e) for s, e, _ in trace.ops[plane])
    return [(s1 - e0) - busy_ns(merged, e0, s1)
            for (_, e0), (s1, _) in zip(mine, mine[1:])]


def op_runs(trace: Trace, lo: int, hi: int) -> Dict[str, Tuple[int, int]]:
    """For each device operation of the main program's executions inside
    [lo, hi) on the first chip: how often it ran and its total time in
    ns, control-flow ops left out."""
    plane = sorted(trace.ops)[0]
    runs = [(s, e) for s, e in main_program(trace) if lo <= s and e <= hi]
    out: Dict[str, Tuple[int, int]] = {}
    for s, e, name in trace.ops[plane]:
        if name.startswith(CONTAINERS) or not any(
                a <= s < b for a, b in runs):
            continue
        n, ns = out.get(name, (0, 0))
        out[name] = (n + 1, ns + e - s)
    return out
