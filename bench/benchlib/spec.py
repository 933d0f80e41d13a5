"""Find a cell's files by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; each lives in a file of its
own (``configs/<name>.json``, ``traffic/<name>.json``), an optional
``cells/<workload>.json`` holds what belongs to that one cell (its fixed
request rate), and each per-layer metric is a reader ``metrics/<name>.py``.
Adding a cell or a metric therefore adds files and edits none.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict                 # bench/configs/<config>.json
    traffic: dict                # the traffic file, updated by the cell's
    end_to_end: List[dict]       # end-to-end metrics this cell reports
    per_layer: List[dict]        # per-layer metrics this cell reports
    #: ``cells/<workload>.json``: ``traffic`` (what this cell sets of the
    #: mix, such as its fixed rate) and ``limits`` of the comparison
    extra: dict = field(default_factory=dict)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bm = load_benchmark(root)
    wl = next((w for w in bm["workloads"] if w["name"] == workload), None)
    if wl is None:
        names = ", ".join(w["name"] for w in bm["workloads"])
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {names})")
    conf = next(c for c in bm["configs"] if c["name"] == wl["config"])
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{wl['traffic']}.json").read_text())
    cell_file = BENCH / "cells" / f"{workload}.json"
    extra = json.loads(cell_file.read_text()) if cell_file.is_file() else {}
    traffic = {**traffic, **extra.get("traffic", {})}
    e2e = [m for m in bm["end_to_end"] if _reports(m, workload)]
    per = [m for m in bm["per_layer"] if _reports(m, workload)]
    return Cell(workload, wl["config"], wl["traffic"], int(wl["chips"]),
                config, traffic, e2e, per, extra)


def metric_reader(name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> Optional[Dict[str, float]]:
    """Published peaks of one chip of ``device_kind``, or None."""
    table = json.loads((BENCH / "peaks.json").read_text())
    return table["devices"].get(device_kind)
