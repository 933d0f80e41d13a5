"""The device's idle time split by the program's spans, and the readings
taken from the spans' stats: on a trace written by hand, whose answer is
worked out below, on random nested traces, and on one traced run of a cell
cut to CPU size."""

import importlib.util
import random
from pathlib import Path

import pytest

from benchlib import spans as SP
from benchlib import trace as TR

METRICS = Path(__file__).resolve().parents[1] / "metrics"
MS = 1_000_000


def _device_idle_frac(red):
    spec = importlib.util.spec_from_file_location(
        "device_idle_frac", METRICS / "device_idle_frac.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(type("Run", (), {"trace": red}))


def _hand():
    """Window 0-100 ms; the device runs 10-30 and 50-60, so it is idle
    over 0-10, 30-50 and 60-100 (70 ms). Program spans, nested as the
    served path nests them (ms):

        gateway.handle 0-20 > orch.submit 2-18 > plane.admit 4-16
            > engine.prefill 5-15 (300 of 512)
        plane.chunk 21-24 > engine.decode 22-23 (1 step)
        plane.chunk 25-70 > engine.decode 26-45 (4 steps)
            > engine.decode.wait 28-44
          plane.chunk > plane.complete 46-58 > plane.admit 49-57
            > engine.prefill 50-55 (700 of 1024)
          plane.chunk > py.gc 62-66
        py.gc 80-85
        engine.prefill 110-115, engine.decode 120-130: after the window

    Idle 0-10: gateway.handle 0-2, orch.submit 2-4, plane.admit 4-5,
    engine.prefill 5-10. Idle 30-50: engine.decode.wait 30-44,
    engine.decode 44-45, plane.chunk 45-46, plane.complete 46-49,
    plane.admit 49-50. Idle 60-100: plane.chunk 60-62 and 66-70, py.gc
    62-66 and 80-85, outside 70-80 and 85-100."""
    tr = TR.Trace()
    tr.ops["/device:TPU:0"] = [(10 * MS, 30 * MS, "fusion.1"),
                               (50 * MS, 60 * MS, "fusion.2")]
    tr.spans = [(0, 100 * MS, "bench.window")]
    prog = [(0, 20, "gateway.handle", {"type": "serve_request"}),
            (2, 18, "orch.submit", {}),
            (4, 16, "plane.admit", {"rid": "s1/c1", "sid": "s1"}),
            (5, 15, "engine.prefill", {"sid": "s1", "tokens": 300,
                                       "bucket": 512, "first": 0}),
            (21, 24, "plane.chunk", {"steps": 1}),
            (22, 23, "engine.decode", {"steps": 1, "first": 0}),
            (25, 70, "plane.chunk", {"steps": 4}),
            (26, 45, "engine.decode", {"steps": 4, "first": 0}),
            (28, 44, "engine.decode.wait", {}),
            (46, 58, "plane.complete", {"rid": "s0/c3"}),
            (49, 57, "plane.admit", {"rid": "s2/c1", "sid": "s2"}),
            (50, 55, "engine.prefill", {"sid": "s2", "tokens": 700,
                                        "bucket": 1024, "first": 1}),
            (62, 66, "py.gc", {"gen": 2}),
            (80, 85, "py.gc", {"gen": 0}),
            (110, 115, "engine.prefill", {"sid": "s3", "tokens": 10,
                                          "bucket": 16, "first": 0}),
            (120, 130, "engine.decode", {"steps": 1, "first": 0})]
    prog = [(a * MS, b * MS, n, st) for a, b, n, st in prog]
    return tr, prog


def test_idle_split_by_hand():
    tr, prog = _hand()
    red = TR.reduce(tr)
    split = SP.idle_by_span(prog, tr, red)
    want = {"gateway.handle": 2, "orch.submit": 2, "plane.admit": 2,
            "engine.prefill": 5, "engine.decode.wait": 14,
            "engine.decode": 1, "plane.chunk": 7, "plane.complete": 3,
            "py.gc": 9, SP.OUTSIDE: 25}
    assert split == pytest.approx({n: v / 1e3 for n, v in want.items()})
    # a garbage collection passed over: its time goes to the span it
    # interrupted, or outside
    skip = SP.idle_by_span(prog, tr, red, skip=(SP.GC,))
    assert skip["plane.chunk"] == pytest.approx(0.011)
    assert skip[SP.OUTSIDE] == pytest.approx(0.030)
    assert SP.GC not in skip
    # the ten biggest, then outside
    assert SP.breakdown(prog, tr, red, top=2) == [
        ["engine.decode.wait", pytest.approx(0.014)],
        ["py.gc", pytest.approx(0.009)],
        [SP.OUTSIDE, pytest.approx(0.025)]]


def test_readings_by_hand():
    tr, prog = _hand()
    red = TR.reduce(tr)
    # control: gateway.handle 2 + orch.submit 2; prefill: plane.admit 2 +
    # engine.prefill 5; decode: engine.decode.wait 14 + engine.decode 1 +
    # plane.chunk 7 + the gc inside it 4 + plane.complete 3; outside: 25 +
    # the gc outside every span 5 (ms of a 100 ms window)
    assert SP.idle_frac(prog, tr, red, "control") == pytest.approx(4.0)
    assert SP.idle_frac(prog, tr, red, "prefill") == pytest.approx(7.0)
    assert SP.idle_frac(prog, tr, red, "decode") == pytest.approx(29.0)
    assert SP.outside_frac(prog, tr, red) == pytest.approx(30.0)
    # the prefills and decodes that start in the window: 300 + 700 of
    # 512 + 1024 positions; 4 and 1 steps
    assert SP.prefill_useful_frac(prog, red) == pytest.approx(
        100.0 * 1000 / 1536)
    assert SP.decode_chunk_steps(prog, red) == pytest.approx(2.5)


def test_layers_and_outside_add_up_to_the_idle_share():
    tr, prog = _hand()
    red = TR.reduce(tr)
    parts = [SP.idle_frac(prog, tr, red, k) for k in SP.LAYERS]
    assert sum(parts) + SP.outside_frac(prog, tr, red) == pytest.approx(
        _device_idle_frac(red))


def _random_trace(rng):
    """Nested program spans of every layer (and garbage collections) over
    a window of random device operations on two devices."""
    names = ["gateway.handle", "orch.submit", "orch.heartbeat",
             "plane.admit", "engine.prefill", "engine.prefill.sync",
             "engine.slot_install", "plane.chunk", "engine.decode",
             "engine.decode.inputs", "engine.decode.wait", "plane.complete",
             "py.gc"]
    prog = []

    def nest(a, b, depth):
        t = a
        while t < b and depth < 4:
            s = rng.uniform(t, b)
            e = rng.uniform(s, b)
            prog.append((s, e, rng.choice(names), {}))
            nest(s, e, depth + 1)
            t = e + rng.uniform(0, (b - a) / 3)
    nest(-5.0, 105.0, 0)
    tr = TR.Trace(spans=[(0.0, 100.0, "bench.window")])
    for dev in ("/device:TPU:0", "/device:TPU:1"):
        ops, t = [], -3.0
        while t < 103:
            s = t + rng.uniform(0, 4)
            t = s + rng.uniform(0, 6)
            ops.append((s, t, "op"))
        tr.ops[dev] = ops
    return tr, prog


@pytest.mark.parametrize("seed", range(8))
def test_random_split_adds_up(seed):
    tr, prog = _random_trace(random.Random(seed))
    red = TR.reduce(tr)
    idle = red["window_s"] - red["busy_s"]
    assert sum(SP.idle_by_span(prog, tr, red).values()) == pytest.approx(
        idle, rel=1e-9, abs=1e-15)
    parts = [SP.idle_frac(prog, tr, red, k) for k in SP.LAYERS]
    assert sum(parts) + SP.outside_frac(prog, tr, red) == pytest.approx(
        _device_idle_frac(red), rel=1e-9)


def test_nothing_to_read():
    tr, prog = _hand()
    red = TR.reduce(tr)
    assert SP.idle_frac([], tr, red, "decode") is None
    assert SP.outside_frac([], tr, red) is None
    assert SP.prefill_useful_frac([], red) is None
    assert SP.decode_chunk_steps([], red) is None
    # no device: nothing idle to split
    bare = TR.Trace(spans=tr.spans)
    assert SP.idle_frac(prog, bare, TR.reduce(bare), "control") is None


def test_small_traced_run_reads_what_the_harness_counted(monkeypatch):
    """One traced run of a cell cut to CPU size: the mean fused chunk and
    the useful share of the prefilled positions, read from the program's
    spans in the window, equal what the harness counted in the same window
    (its calls made between the window's opening and its close)."""
    from benchlib import drive
    from small import run_small

    seen = {}

    class Recorder(drive.Driver):
        def _wrap(self):
            super()._wrap()
            seen["harness"] = self
            self.admitted = []            # (prompt tokens, bucket)
            for plane in self.server.planes.values():
                be = plane.backend

                def admit(req, now, _f=be.admit, _e=be.engine):
                    self.admitted.append((len(req.prompt),
                                          _e._bucket(len(req.prompt))))
                    return _f(req, now)
                be.admit = admit

        def run(self, *a, on_open, on_close, **kw):
            return super().run(*a, on_open=marked(on_open, "open"),
                               on_close=marked(on_close, "close"), **kw)

    def marked(f, key):
        def g(*a, **kw):
            d = seen["harness"]
            seen[key] = (len(d.c.rounds), len(d.admitted))
            return f(*a, **kw)
        return g

    def load(directory, _f=TR.load):
        seen["program"] = SP.load(directory)
        return _f(directory)

    monkeypatch.setattr(drive, "Driver", Recorder)
    monkeypatch.setattr(TR, "load", load)
    monkeypatch.setattr(TR, "reduce",
                        lambda *a, _f=TR.reduce: seen.setdefault(
                            "red", _f(*a)))
    res = run_small("minitron-8b-4L.long-decode", seed=31, trace=True)
    assert res["correct"], res["compared"]
    d, prog, red = seen["harness"], seen["program"], seen["red"]
    (r0, a0), (r1, a1) = seen["open"], seen["close"]
    steps = [c.steps for c in d.c.rounds[r0:r1] if c.steps]
    assert steps
    assert SP.decode_chunk_steps(prog, red) == pytest.approx(
        sum(steps) / len(steps), rel=1e-12)
    adm = d.admitted[a0:a1]
    assert adm
    assert SP.prefill_useful_frac(prog, red) == pytest.approx(
        100.0 * sum(n for n, _ in adm) / sum(b for _, b in adm), rel=1e-12)
    # every span of the table is in the window's trace
    names = {n for _, _, n, _ in prog}
    assert {"gateway.handle", "gateway.pump", "orch.submit",
            "orch.record_results", "plane.admit", "plane.chunk",
            "plane.complete", "engine.prefill", "engine.prefill.sync",
            "engine.slot_install", "engine.decode", "engine.decode.inputs",
            "engine.decode.wait"} <= names
    assert not names & set(TR.SPANS)
