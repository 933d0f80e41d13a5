"""The program's own spans in a profiler trace, and the device's idle time
split by what the program was doing.

The served path marks its work with host spans (``repro.obs``): names that
start with ``gateway.``, ``orch.``, ``plane.``, ``engine.`` or ``py.gc``,
each with its stats. ``load`` reads them from the ``.xplane.pb`` that
``jax.profiler`` wrote, leaving the harness's own spans (``trace.SPANS``) to
``trace.load``. ``idle_by_span`` gives every nanosecond of the window in
which a device ran no operation to the innermost program span open at that
nanosecond, or to ``OUTSIDE`` where none was open, so many short gaps
count as much as one long one. The readings below take these lists and the
window of ``trace.reduce``; like ``trace``, they run without a chip.
"""

from __future__ import annotations

import glob
import os
import warnings
from typing import Dict, List, Optional, Tuple

from benchlib import trace as TR

#: name prefixes of the program's spans
PREFIXES = ("gateway.", "orch.", "plane.", "engine.", "py.gc")
#: where no program span was open
OUTSIDE = "outside the program"
#: a garbage collection: its idle time belongs to the span it interrupted
#: when a layer's share is read
GC = "py.gc"
#: the spans (by name prefix) whose idle time each layer's share counts
LAYERS = {
    "control": ("gateway.", "orch."),
    "prefill": ("plane.admit", "engine.prefill", "engine.slot_install"),
    "decode": ("plane.chunk", "plane.complete", "engine.decode"),
}

Span = Tuple[float, float, str, dict]


def load(directory: str) -> List[Span]:
    """[(start_ns, end_ns, name, stats)] of the program's host spans."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no trace under {directory}")
    out = []
    with warnings.catch_warnings():
        # reading stats warns of a builtin type without __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(files[-1]).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith(PREFIXES)
                           and e.name not in TR.SPANS)
    return out


def _owners(program: List[Span], lo: float, hi: float, skip=()):
    """[(a, b, name)]: ``[lo, hi)`` cut where a span opens or closes, each
    piece named by the innermost span open over it (the latest opened, then
    the shortest), ``OUTSIDE`` where none is."""
    spans = [(max(a, lo), min(b, hi), n) for a, b, n, _ in program
             if n not in skip and b > lo and a < hi and b > a]
    edges = sorted([(a, 1, i) for i, (a, _, _) in enumerate(spans)]
                   + [(b, 0, i) for i, (_, b, _) in enumerate(spans)])
    out, open_, t, j = [], {}, lo, 0
    while t < hi:
        while j < len(edges) and edges[j][0] <= t:
            _, opens, i = edges[j]
            if opens:
                open_[i] = spans[i]
            else:
                open_.pop(i, None)
            j += 1
        nxt = edges[j][0] if j < len(edges) else hi
        if open_:
            name = max(open_.values(),
                       key=lambda s: (s[0], -(s[1] - s[0])))[2]
        else:
            name = OUTSIDE
        out.append((t, min(nxt, hi), name))
        t = nxt
    return out


def idle_by_span(program: List[Span], tr: TR.Trace, red: dict,
                 skip=()) -> Dict[str, float]:
    """Idle seconds of the window under each innermost program span (and
    ``OUTSIDE``), averaged over the devices that ran any operation; spans
    named in ``skip`` are passed over, so their time goes to the span
    around them. The values add up to the window's idle time."""
    lo, hi = red["lo_ns"], red["hi_ns"]
    owners = _owners(program, lo, hi, skip)
    out: Dict[str, float] = {}
    used = 0
    for ops in tr.ops.values():
        ops = TR._clip(ops, lo, hi)
        if not ops:
            continue
        used += 1
        merged = TR._union((a, b) for a, b, _ in ops)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        k = 0
        for a, b, name in owners:
            while k < len(idle) and idle[k][1] <= a:
                k += 1
            m = k
            while m < len(idle) and idle[m][0] < b:
                cut = min(b, idle[m][1]) - max(a, idle[m][0])
                out[name] = out.get(name, 0.0) + cut / 1e9
                m += 1
    return {n: s / used for n, s in out.items()} if used else {}


def breakdown(program: List[Span], tr: TR.Trace, red: dict,
              top: int = 10) -> List[list]:
    """[[span, idle seconds]] of the ``top`` spans with most idle device
    time under them, then ``OUTSIDE``."""
    split = idle_by_span(program, tr, red)
    inside = sorted(([n, s] for n, s in split.items() if n != OUTSIDE),
                    key=lambda x: -x[1])
    return inside[:top] + [[OUTSIDE, split.get(OUTSIDE, 0.0)]]


def idle_frac(program: List[Span], tr: TR.Trace, red: dict,
              layer: str) -> Optional[float]:
    """Share of the window, in percent, in which the device was idle under
    a span of ``layer`` (``LAYERS``; a garbage collection counts for the
    span it interrupted). None without a device or without program spans."""
    if not program or not red["devices"] or not red["window_s"]:
        return None
    split = idle_by_span(program, tr, red, skip=(GC,))
    s = sum(v for n, v in split.items() if n.startswith(LAYERS[layer]))
    return 100.0 * s / red["window_s"]


def outside_frac(program: List[Span], tr: TR.Trace,
                 red: dict) -> Optional[float]:
    """Share of the window, in percent, in which the device was idle and
    no program span was open."""
    if not program or not red["devices"] or not red["window_s"]:
        return None
    split = idle_by_span(program, tr, red, skip=(GC,))
    return 100.0 * split.get(OUTSIDE, 0.0) / red["window_s"]


def _started_in(program: List[Span], red: dict, name: str) -> List[dict]:
    lo, hi = red["lo_ns"], red["hi_ns"]
    return [st for a, _, n, st in program if n == name and lo <= a < hi]


def prefill_useful_frac(program: List[Span], red: dict) -> Optional[float]:
    """Prompt tokens over the padded bucket positions prefilled, over the
    ``engine.prefill`` spans that start in the window, in percent."""
    st = _started_in(program, red, "engine.prefill")
    if not st:
        return None
    return 100.0 * sum(s["tokens"] for s in st) / sum(s["bucket"] for s in st)


def decode_chunk_steps(program: List[Span], red: dict) -> Optional[float]:
    """Mean decode steps per fused dispatch, over the ``engine.decode``
    spans that start in the window."""
    st = _started_in(program, red, "engine.decode")
    return sum(s["steps"] for s in st) / len(st) if st else None
