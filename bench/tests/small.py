"""A cell of BENCHMARK.json cut to a size the CPU runs in seconds: the same
harness, traffic generator, driver and comparison, on a model of the same
family at toy widths. For tests only; no number from it is a speed."""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import spec  # noqa: E402

SMALL_MODEL = {
    "ssm": {"num_layers": 2, "d_model": 64, "vocab_size": 512,
            "ssm_state": 16, "ssm_headdim": 16, "ssm_chunk": 16},
    "dense": {"num_layers": 2, "d_model": 64, "num_heads": 6,
              "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
              "vocab_size": 512},
}
SMALL_TRAFFIC = {
    "open": {"request_rate_per_s": 8.0, "warm_s": 0.5,
             "prompt_tokens": {"dist": "lognormal", "median": 16,
                               "sigma": 0.5, "min": 8, "max": 40},
             "output_tokens": {"dist": "lognormal", "median": 16,
                               "sigma": 0.4, "min": 8, "max": 32},
             "think_s": {"dist": "exponential", "mean": 0.1},
             "check_tokens": 400},
    "closed": {"warm_s": 0.5,
               "prompt_tokens": {"dist": "uniform_int", "min": 40,
                                 "max": 60},
               "output_tokens": {"dist": "uniform_int", "min": 8,
                                 "max": 16},
               "check_tokens": 400},
}


#: limits of the comparison at these sizes, read on the CPU as PERF.md
#: sets the cells' own: over seeds 1-5 and 392 served positions each
#: (test_control.py), the largest mean gap of the served program was
#: 3.3e-5 (SSM) and 2.0e-4 (dense), the smallest of the int8 control
#: 1.9e-4 and 1.35e-3
SMALL_LIMITS = {"ssm": {"mean_gap": 9e-5}, "dense": {"mean_gap": 9e-4}}


def small_cell(workload: str) -> spec.Cell:
    """``<config>.<traffic>`` from the files under bench/, whether or not
    BENCHMARK.json has that cell, cut to CPU size."""
    config, traffic = workload.rsplit(".", 1)
    bm = spec.load_benchmark()
    cell = spec.Cell(
        workload, config, traffic, 1,
        json.loads((BENCH / "configs" / f"{config}.json").read_text()),
        json.loads((BENCH / "traffic" / f"{traffic}.json").read_text()),
        [m for m in bm["end_to_end"] if spec._reports(m, workload)],
        [m for m in bm["per_layer"] if spec._reports(m, workload)])
    m = cell.config["model"]
    m.update(SMALL_MODEL[m["family"]])
    cell.config["serving"] = {"slots": 4, "max_len": 128}
    cell.traffic.update(SMALL_TRAFFIC[cell.traffic["loop"]])
    cell.extra = {"limits": SMALL_LIMITS[m["family"]]}
    return cell


def run_small(workload: str, seed: int = 7, seconds: float = 3.0, **kw):
    import jax
    sys.path.insert(0, str(BENCH))
    import run as harness
    peak = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
    return harness.run_cell(small_cell(workload), seed, seconds,
                            kw.pop("trace", False), jax.devices()[:1], peak,
                            **kw)
