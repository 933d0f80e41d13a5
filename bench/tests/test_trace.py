"""The trace reduction on small traces: one written by hand, whose answer is
worked out below, and one excerpt recorded on the chip."""

import importlib.util
import json
from pathlib import Path

import pytest

from benchlib import trace as TR

DATA = Path(__file__).resolve().parent / "data"
METRICS = Path(__file__).resolve().parents[1] / "metrics"


def _metric(name):
    spec = importlib.util.spec_from_file_location(name,
                                                  METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _hand():
    ms = 1_000_000
    tr = TR.Trace()
    # window 0..100 ms; device ops cover 10-30 (two overlapping) and 50-60
    tr.ops["/device:TPU:0"] = [(10 * ms, 25 * ms, "fusion.1"),
                               (20 * ms, 30 * ms, "decode_attention_k"),
                               (50 * ms, 60 * ms, "fusion.1"),
                               (95 * ms, 130 * ms, "tail")]
    tr.spans = [(0, 100 * ms, "bench.window"),
                (0, 40 * ms, "plane.round"),
                (5 * ms, 35 * ms, "engine.decode_round"),
                (60 * ms, 95 * ms, "ais.establish")]
    tr.modules["/device:TPU:0"] = [(10 * ms, 30 * ms, "jit__fused_impl(7)"),
                                   (50 * ms, 60 * ms, "jit__prefill(3)"),
                                   (95 * ms, 130 * ms, "jit__fused_impl(7)"),
                                   (-5 * ms, 2 * ms, "jit__fused_impl(7)")]
    return tr, ms


def test_busy_idle_and_kernel_time_by_hand():
    tr, ms = _hand()
    red = TR.reduce(tr)
    assert red["window_s"] == pytest.approx(0.100)
    # union: 10-30, 50-60, 95-100 (clipped) = 35 ms
    assert red["busy_s"] == pytest.approx(0.035)
    assert TR.op_seconds(tr, red, lambda n: "decode_attention" in n) \
        == pytest.approx(0.010)
    # programs that started inside the window count whole: 20 + 35 ms; the
    # one that started before it does not count
    assert TR.module_seconds(tr, red,
                             lambda n: n.startswith("jit__fused_impl(")) \
        == pytest.approx(0.055)
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.025)
    assert ops["tail"] == pytest.approx(0.005)
    # gaps: 0-10 (in decode_round's span at 5 ms), 30-50 (plane.round
    # until 40, none after: the middle, 40 ms, is in plane.round), 60-95
    gaps = red["idle_gaps"]
    assert gaps[0] == ["ais.establish", pytest.approx(0.035)]
    assert gaps[1] == ["plane.round", pytest.approx(0.020)]
    assert gaps[2] == ["engine.decode_round", pytest.approx(0.010)]


def test_events_after_the_window_are_left_out():
    """The profiler stops after the drain, so the trace runs on past the
    window's end: ops, programs and spans that start after it change
    nothing that is read."""
    tr, ms = _hand()
    want = TR.reduce(tr)
    late = TR.Trace(ops={k: list(v) for k, v in tr.ops.items()},
                    modules={k: list(v) for k, v in tr.modules.items()},
                    spans=list(tr.spans))
    late.ops["/device:TPU:0"] += [(140 * ms, 180 * ms, "fusion.1"),
                                  (150 * ms, 170 * ms, "decode_attention_k"),
                                  (190 * ms, 250 * ms, "drain_only")]
    late.modules["/device:TPU:0"] += [(140 * ms, 180 * ms,
                                       "jit__fused_impl(7)")]
    late.spans += [(100 * ms, 260 * ms, "plane.round"),
                   (135 * ms, 185 * ms, "engine.decode_round"),
                   (186 * ms, 188 * ms, "ais.heartbeat")]
    red = TR.reduce(late)
    assert red == want
    assert TR.op_seconds(late, red, lambda n: "decode_attention" in n) \
        == pytest.approx(0.010)
    assert TR.module_seconds(late, red,
                             lambda n: n.startswith("jit__fused_impl(")) \
        == pytest.approx(0.055)


def test_no_device_ops_reads_zero_busy():
    tr = TR.Trace(spans=[(0, 10, "bench.window")])
    red = TR.reduce(tr)
    assert red["busy_s"] == 0.0 and red["devices"] == 0


def test_recorded_chip_excerpt():
    """An excerpt of a trace recorded on a TPU v5e (bench/tests/data):
    busy time is the union of the op intervals and never exceeds the
    window."""
    f = DATA / "trace_excerpt.json"
    raw = json.loads(f.read_text())
    tr = TR.Trace(ops={k: [tuple(o) for o in v]
                       for k, v in raw["ops"].items()},
                  spans=[tuple(s) for s in raw["spans"]])
    red = TR.reduce(tr)
    assert 0 < red["busy_s"] <= red["window_s"]
    want = raw["expected"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert TR.op_seconds(tr, red, lambda n: any(
        k in n for k in raw["kernel_names"])) == pytest.approx(
            want["kernel_s"], rel=1e-9)
    # the roofline metric's own matcher finds the same kernel: the excerpt
    # was recorded with 32 query heads over 8 kv heads, 32 slots
    kern = _metric("decode_attn_roofline")
    model = {"num_heads": 32, "num_kv_heads": 8, "head_dim": 128}
    assert TR.op_seconds(tr, red, lambda n: kern.is_kernel(n, model, 32)) \
        == pytest.approx(want["kernel_s"], rel=1e-9)
    # and no other kernel's tile
    model["num_heads"] = 48
    assert TR.op_seconds(tr, red,
                         lambda n: kern.is_kernel(n, model, 32)) == 0.0
