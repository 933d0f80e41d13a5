#!/usr/bin/env python3
"""Readings that set a cell's fixed numbers, on the chip; the benchmark's
own runs never run this.

    python bench/calibrate.py limits --workload <name> --seeds 1,2,3 --seconds 10
    python bench/calibrate.py knee --workload <name> --rates 4,8,12 --seconds 20

``limits`` runs the cell once per seed in this one process and prints, per
seed, the compared numbers of the served program and whether it came out
correct, beside the int8 control's numbers at the same positions and the
harness's verdict on them under the cell's limits (the readings PERF.md sets
each limit from); a last line gives the largest reading of the program and
the smallest of the control for each gap number. It exits 1 where the
control came out correct on any seed or the program not correct.
``knee`` runs an open-loop cell at each fixed request rate and prints the
offered and completed tokens per second and the queue at the window's open
and close, for the sweep that finds the highest rate the system sustains.
One JSON object per line on standard output.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as harness
from benchlib import spec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("limits", "knee"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    a = ap.parse_args()
    cell = spec.load_cell(a.workload)
    harness.enable_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return harness.fail("needs a TPU")
    peak = spec.peaks(devices[0].device_kind)
    seeds = [int(s) for s in a.seeds.split(",")]
    if a.mode == "limits":
        ok, prog, ctrl = True, {}, {}
        for seed in seeds:
            res = harness.run_cell(cell, seed, a.seconds, False,
                                   devices[:cell.chips], peak, control=True,
                                   t_start=harness.time.monotonic())
            print(json.dumps({"seed": seed, "correct": res["correct"],
                              "control_correct": res["control_correct"],
                              "attempted": res["attempted"],
                              "failed": res["failed"],
                              "window": res["window"],
                              "compared": res["compared"],
                              "control": res["control"]}), flush=True)
            ok &= res["correct"] and not res["control_correct"]
            for out, got in ((prog, res["compared"]), (ctrl, res["control"])):
                for k in ("widest_gap", "mean_gap"):
                    v = got[k]["value"] if isinstance(got[k], dict) \
                        else got[k]
                    out.setdefault(k, []).append(v)
        print(json.dumps({"seeds": seeds,
                          "program_max": {k: max(v) for k, v in prog.items()},
                          "control_min": {k: min(v) for k, v in ctrl.items()},
                          "all_as_expected": ok}), flush=True)
        return 0 if ok else 1
    from benchlib.traffic import mean_of
    for rate in [float(r) for r in a.rates.split(",")]:
        cell.traffic["request_rate_per_s"] = rate
        res = harness.run_cell(cell, seeds[0], a.seconds, False,
                               devices[:cell.chips], peak,
                               t_start=harness.time.monotonic())
        offered = rate * mean_of(cell.traffic["output_tokens"])
        print(json.dumps({"rate": rate, "offered_tok_s": offered,
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "window": res["window"],
                          "correct": res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
