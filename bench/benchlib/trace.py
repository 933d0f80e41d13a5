"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
interval lists: the operations and the programs (XLA modules) each device
ran, and the host spans the driver wrote. ``reduce`` turns those into device
busy time (the union of operation intervals, averaged over the devices that
ran any), the traced window, the operations that took most time, and the
longest idle gaps, each named by the innermost host span that was open at
its middle. ``op_seconds`` and ``module_seconds`` give the device time of
the operations or programs a per-layer metric picks by name (each metric's
reader in ``bench/metrics`` holds its own names). They take only the lists,
so they are checked on a small recorded trace without a chip.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

#: the marker span the driver opens around the traced window
WINDOW = "bench.window"
#: host spans the driver writes, innermost last
SPANS = ("bench.window", "ais.establish", "ais.submit", "ais.heartbeat",
         "plane.round", "engine.admit", "engine.decode_round")
#: operations that only contain others on the same line (their time is
#: their children's): left out of the ranking, kept in the busy union
CONTAINERS = ("while", "conditional", "call")
#: the device line that holds one event per operation
OPS_LINE = "XLA Ops"
#: the device line that holds one event per program run
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    #: device name -> [(start_ns, end_ns, op name)]
    ops: Dict[str, List[Tuple[float, float, str]]] = field(
        default_factory=dict)
    #: device name -> [(start_ns, end_ns, program name)]
    modules: Dict[str, List[Tuple[float, float, str]]] = field(
        default_factory=dict)
    #: [(start_ns, end_ns, span name)]
    spans: List[Tuple[float, float, str]] = field(default_factory=list)


def load(directory: str) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no trace under {directory}")
    pd = ProfileData.from_file(files[-1])
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                into = {OPS_LINE: tr.ops, MODULES_LINE: tr.modules}.get(
                    line.name)
                if into is not None:
                    into.setdefault(plane.name, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        tr.spans.append((e.start_ns,
                                         e.start_ns + e.duration_ns, e.name))
    return tr


def short_name(op: str) -> str:
    """``%fusion.3 = bf16[..] fusion(...)`` -> ``fusion.3 fusion``; a
    custom call keeps its target. Names without HLO text pass through."""
    if " = " not in op:
        return op
    name, rest = op.split(" = ", 1)
    i = 0
    if rest.startswith("("):                  # tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
    j = rest.find(" ", i)
    code = rest[j + 1:].split("(", 1)[0] if j >= 0 else ""
    out = f"{name.lstrip('%')} {code}".strip()
    if 'custom_call_target="' in rest:
        out += " " + rest.split('custom_call_target="', 1)[1].split('"')[0]
    return out


def _union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) + tuple(r)
            for a, b, *r in intervals if b > lo and a < hi]


def _innermost(spans, t) -> str:
    """Name of the shortest host span that contains time ``t``."""
    best, name = None, "host idle"
    for a, b, n in spans:
        if a <= t <= b and n != WINDOW and (best is None or b - a < best):
            best, name = b - a, n
    return name


def reduce(tr: Trace, top: int = 10) -> dict:
    """Busy and idle time, top operations and idle gaps over the window
    marked by the ``bench.window`` span (or the whole trace)."""
    win = [s for s in tr.spans if s[2] == WINDOW]
    allops = [o for v in tr.ops.values() for o in v]
    if win:
        lo, hi = win[0][0], win[0][1]
    elif allops:
        lo, hi = min(o[0] for o in allops), max(o[1] for o in allops)
    else:
        lo = hi = 0.0
    window_s = (hi - lo) / 1e9
    busy, per_op = [], {}
    gaps = []
    spans = _clip(tr.spans, lo, hi)
    used = 0
    for dev, ops in sorted(tr.ops.items()):
        ops = _clip(ops, lo, hi)
        if not ops:
            continue
        used += 1
        merged = _union((a, b) for a, b, _ in ops)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for a, b, name in ops:
            short = short_name(name)
            if short.split(" ")[-1] not in CONTAINERS:
                per_op[short] = per_op.get(short, 0.0) + (b - a) / 1e9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) / 2))
    gaps.sort(reverse=True)
    return {
        "lo_ns": lo,
        "hi_ns": hi,
        "window_s": window_s,
        "busy_s": sum(busy) / used if used else 0.0,
        "devices": used,
        "device_ops": sorted(([n, s / max(used, 1)] for n, s in
                              per_op.items()), key=lambda x: -x[1])[:top],
        "idle_gaps": [[_innermost(spans, mid), g / 1e9]
                      for g, mid in gaps[:top]],
    }


def _started_in(lines, red: dict, match) -> float:
    """Seconds of the events of ``lines`` (device -> events) whose name
    ``match`` accepts and that started inside the window, each whole (so
    they line up with the host's calls that started inside it), averaged
    over the devices that ran any operation."""
    lo, hi = red["lo_ns"], red["hi_ns"]
    total = sum(b - a for evs in lines.values() for a, b, name in evs
                if lo <= a < hi and match(name))
    return total / 1e9 / max(red["devices"], 1)


def op_seconds(tr: Trace, red: dict, match) -> float:
    """Device time of the operations whose HLO text ``match`` accepts."""
    return _started_in(tr.ops, red, match)


def module_seconds(tr: Trace, red: dict, match) -> float:
    """Device time of the programs whose name (``jit_<function>(<id>)``)
    ``match`` accepts."""
    return _started_in(tr.modules, red, match)
