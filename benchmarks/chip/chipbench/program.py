"""What the program says about itself: its metrics registry, its host
spans in the profiler's trace, and the named scopes of its device ops.

The program (``repro.obs.metrics``) times its own phases as host spans:
each is a ``TraceAnnotation`` in the profiler's trace and a histogram
``<span>_s`` in its registry, whose counters and histograms roll up
into one process-wide registry (:func:`registry`).  Its device ops
carry ``jax.named_scope`` paths (``.../sat_bwd/transpose(jvp(sat_fwd))
/...``) in the compiled program's text (``metadata={op_name="..."}``);
the TPU's op events in the trace carry none.  JAX's persistent
compilation cache keys a program without its scopes, so the text must
come from a compile with that cache off.

The reductions below are interval arithmetic on the trace of
:mod:`chipbench.trace`: the host–device clock offset bounded by the
program's own spans, device idle between two runs of the main program
split by the host span it fell in, and device time split by scope.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench import trace as tr

Span = Tuple[int, int, str, dict]               # (s, e, name, stats)
OP_NAME_RE = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*'
                        r'metadata=\{[^}]*op_name="([^"]*)"')


def registry() -> dict:
    """A snapshot of the program's process-wide metrics registry."""
    from repro.obs.metrics import global_registry

    return global_registry().to_dict()


def histogram_sum(snapshot: dict, name: str) -> Optional[float]:
    """The sum of a histogram in a registry snapshot; None if absent."""
    h = snapshot.get(name)
    if not isinstance(h, dict) or not h.get("count"):
        return None
    return float(h["sum"])


def load_spans(trace_dir: Path, prefix: str) -> List[Span]:
    """The program's host spans (``TraceAnnotation`` events named
    ``<prefix>...``) in a trace directory, with their stats (such as
    the ``revolution`` index of ``fleet.revolution``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(tr.xplane_file(trace_dir)))
    out: List[Span] = []
    for plane in data.planes:
        if plane.name.startswith(tr.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns), ev.name,
                                dict(ev.stats)))
    return sorted(out, key=lambda sp: sp[:3])


def op_scopes_from_hlo(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` path, from a compiled module's
    text; a fusion carries the path of its root."""
    out = {}
    for line in text.splitlines():
        m = OP_NAME_RE.match(line)
        if m:
            out.setdefault(m.group(1), m.group(2))
    return out


def scope_of(path: str, scopes: Sequence[str]) -> Optional[str]:
    """The outermost of ``scopes`` that is a component of ``path``, bare
    or inside a transform (``jvp(sat_fwd)``): ``sat_bwd`` for
    ``.../sat_bwd/transpose(jvp(sat_fwd))/mul``."""
    for part in path.split("/"):
        for name in re.split(r"[()]", part):
            if name in scopes:
                return name
    return None


def scope_ns(trace: tr.Trace, lo: int, hi: int, paths: Dict[str, str],
             scopes: Sequence[str]) -> Dict[Optional[str], int]:
    """Device time of the main program's executions inside [lo, hi) on
    the first chip, by scope (None: under none of ``scopes``); the
    values sum to all of that program's op time."""
    out: Dict[Optional[str], int] = {}
    for name, (_, ns) in tr.op_runs(trace, lo, hi).items():
        key = scope_of(paths.get(name, ""), scopes)
        out[key] = out.get(key, 0) + ns
    return out


def runs_in(trace: tr.Trace, lo: int, hi: int) -> List[tr.Interval]:
    """The main program's executions inside [lo, hi) (first chip)."""
    return [(s, e) for s, e in tr.main_program(trace) if lo <= s and e <= hi]


def clock_offset(spans: Sequence[Span], runs: Sequence[tr.Interval],
                 start: str, end: str) -> Tuple[float, float]:
    """The device clock less the host clock, in ns, and its bound.

    The i-th run of the main program cannot start before the i-th
    ``start`` span began on the host, nor end after the i-th ``end``
    span ended: offset <= run start - span start, offset >= run end -
    span end.  Returns the middle of what every run allows and half its
    width; raises where the counts differ or no offset fits them all."""
    starts = [s for s, _, n, _ in spans if n == start]
    ends = [e for _, e, n, _ in spans if n == end]
    if not runs or not len(runs) == len(starts) == len(ends):
        raise ValueError(f"{len(runs)} program runs but {len(starts)} "
                         f"{start!r} and {len(ends)} {end!r} spans")
    hi = min(rs - s for (rs, _), s in zip(runs, starts))
    lo = max(re_ - e for (_, re_), e in zip(runs, ends))
    if lo > hi:
        raise ValueError(f"no clock offset fits: needs >= {lo} ns and "
                         f"<= {hi} ns")
    return (lo + hi) / 2.0, (hi - lo) / 2.0


def boundary_split(trace: tr.Trace, lo: int, hi: int, spans: Iterable[Span],
                   names: Sequence[str], offset: float
                   ) -> Tuple[Dict[str, int], int]:
    """Device idle between consecutive runs of the main program inside
    [lo, hi) (first chip), split by the host span of ``names`` that held
    each idle instant, the spans moved onto the device's clock by
    ``offset``; what none holds is ``"remainder"``.  The values sum to
    ``trace.program_gaps``; returns them and the number of gaps."""
    plane = sorted(trace.ops)[0]
    merged = tr.union((s, e) for s, e, _ in trace.ops[plane])
    mine = runs_in(trace, lo, hi)
    shift = int(round(offset))
    held = [(s + shift, e + shift, n) for s, e, n, _ in spans
            if n in names]
    out = {n: 0 for n in names}
    out["remainder"] = 0
    for (_, e0), (s1, _) in zip(mine, mine[1:]):
        for a, b in tr.idle_gaps(merged, e0, s1):
            covered = 0
            for s, e, n in held:
                part = max(0, min(b, e) - max(a, s))
                out[n] += part
                covered += part
            out["remainder"] += (b - a) - covered
    return out, max(len(mine) - 1, 0)
