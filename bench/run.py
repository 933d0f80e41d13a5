#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell (a model configuration under a traffic
mix) is looked up by name in BENCHMARK.json; its files are
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``, an
optional ``bench/cells/<workload>.json`` and, per per-layer metric,
``bench/metrics/<metric>.py``.

One run: make the weights from the seed, build the server (the site engines
behind their serving planes and the northbound gateway), warm every prefill
bucket and decode chunk the traffic reaches on every site engine, serve a
warm stretch of the traffic, then measure for ``--seconds`` seconds of the
same traffic and wait for the answers due in that window. With ``--trace 1``
the window is traced with the JAX profiler and the per-layer metrics are
reported instead of the end-to-end ones; the profiler stops once those
answers are in, and the readings are clipped to the window's
``bench.window`` span. After the window the server is
freed and a sample of the finished requests is compared with the plain
float32 reference (``benchlib/reference.py``).

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced); its last key, ``compared``, holds each number compared beside its
limit, which are also the last lines on standard error. The run exits
nonzero and prints no result when JAX finds no TPU, fewer chips than the
cell asks for, a device kind missing from ``bench/peaks.json``, or no
program beside the benchmark.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import spec  # noqa: E402

#: fixed, git-ignored directories inside the checkout: a cache directory
#: that moves never hits
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def say(*a) -> None:
    print(*a, flush=True)


def log(msg: str) -> None:
    """Progress on standard error, with seconds since the process began."""
    print(f"bench: [{time.monotonic() - T_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def fail(msg: str, code: int = 2) -> int:
    print(f"bench: {msg}; no result", file=sys.stderr, flush=True)
    return code


def enable_cache() -> str:
    """JAX's persistent compilation cache, in ``JAX_COMPILATION_CACHE_DIR``
    where that is set and at the checkout's ``.jax_cache`` otherwise, for
    every program however quick to compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# the served system
# ---------------------------------------------------------------------------

def model_config(config: dict):
    """The program's ModelConfig for a configuration file: the registered
    config with every size the file states."""
    from repro.configs import get_config
    from repro.models.config import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    model = dict(config["model"])
    bad = sorted(set(model) - fields)
    if bad:
        raise KeyError(f"{config['name']}: not ModelConfig fields: {bad}")
    return dataclasses.replace(get_config(config["registered_as"]),
                               name=config["name"], **model)


def catalog_for(config: dict, cfg):
    """A catalog holding only this configuration, at the tier and price the
    default catalog gives the registered model."""
    from repro.core.catalog import Catalog, default_catalog
    base = default_catalog().get(config["registered_as"])
    cat = Catalog()
    cat.register(dataclasses.replace(base, model_id=config["name"], cfg=cfg))
    return cat


@contextlib.contextmanager
def served_weights(params):
    """Site engines built inside this block serve ``params`` (the server
    draws its own seed-0 weights otherwise; it has no argument for them)."""
    import repro.serving.server as srv
    base = srv.EngineFleet

    class Fleet(base):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self._params = params

    srv.EngineFleet = Fleet
    try:
        yield
    finally:
        srv.EngineFleet = base


def warm_up(server, plans, vocab: int) -> dict:
    """Run every prefill bucket the planned prompts reach and every fused
    decode chunk size the planes can pick, on every site engine."""
    import numpy as np
    sizes = {t.prompt_tokens for p in plans for t in p.turns}
    kmax = max(max(p.decode_chunk.values()) for p in server.planes.values())
    ks = [1 << i for i in range(kmax.bit_length()) if 1 << i <= kmax]
    ids = lambda n: (np.arange(n) % vocab).astype(np.int32)   # noqa: E731
    buckets = set()
    for eng in server.fleet._engines.values():
        bs = sorted({eng._bucket(n) for n in sizes})
        buckets.update(bs)
        for b in bs:
            eng.prefill_session("__warm__", ids(b))
            eng.release_slot("__warm__")
        eng.prefill_session("__warm__", ids(16))
        for k in ks:
            eng.decode_round(steps=k)
        eng.release_slot("__warm__")
    return {"buckets": sorted(buckets), "chunks": ks}


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------

def pct(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


def end_to_end(d, setup_s: float) -> dict:
    """Every end-to-end metric the harness can take from one window."""
    reqs = [r for r in d.window_requests() if r.ok]
    lo, hi = d.window
    calls = d.window_calls(d.c.admits) + d.window_calls(d.c.rounds)
    end = max([c.t1 for c in calls], default=hi)
    est = [(t1 - due) * 1e3 for due, t1, _ in d.c.establish
           if lo <= due < hi]
    tpots = [t for t in (r.tpot_ms() for r in reqs) if t is not None]
    return {
        "ttft_p95_ms": pct([r.ttft_ms() for r in reqs], 95),
        "tpot_p95_ms": pct(tpots, 95),
        "output_tok_s": sum(c.tokens for c in calls) / max(end - lo, 1e-9),
        "establish_p90_ms": pct(est, 90),
        "setup_s": setup_s,
    }


# ---------------------------------------------------------------------------
# the comparison that decides `correct`
# ---------------------------------------------------------------------------

def check_sample(d, seed: int, want_tokens: int):
    """Requests to compare: the longest finished one of the window, then
    others drawn from the seed until ``want_tokens`` served tokens."""
    import numpy as np
    done = [r for r in d.window_requests() if r.ok and r.done.token_ids]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.done.token_ids),
                                       r.session, r.turn))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 4]).permutation(len(rest))
    out, n = [longest], len(longest.done.token_ids)
    for i in order:
        if n >= want_tokens:
            break
        out.append(rest[i])
        n += len(rest[i].done.token_ids)
    return out


def gap_numbers(gaps, limits: dict) -> dict:
    """``widest_gap`` (the largest gap below the reference's best logit)
    and ``mean_gap`` over the served positions ``gaps``; those the cell's
    ``limits`` name are compared, beside their limit, the others only
    reported."""
    import numpy as np
    allg = np.concatenate(gaps) if len(gaps) else np.zeros(0)
    read = {"widest_gap": float(allg.max()) if allg.size else None,
            "mean_gap": float(allg.mean()) if allg.size else None}
    out = {k: {"value": v, "limit": limits[k]} if k in limits else v
           for k, v in read.items()}
    out["compared_tokens"] = int(allg.size)
    return out


def compare(d, sample, params, model: dict, limits: dict,
            control: bool = False):
    """Each compared number with its limit. ``failed`` counts the window's
    requests that failed, were refused or got no answer; ``malformed`` its
    finished answers whose length or token ids are wrong; then the gaps of
    the sample's served tokens below the reference
    (``benchlib/reference.py``, ``gap_numbers``). With ``control`` also
    returns the int8 control's gap numbers at the same positions, under the
    same limits (None otherwise)."""
    from benchlib.reference import score_request
    vocab = model["vocab_size"]
    bad = failed = 0
    for r in d.window_requests():
        if r.ok:
            ids = r.done.token_ids or []
            if len(ids) != r.gen or not all(0 <= t < vocab for t in ids):
                bad += 1
        else:
            failed += 1
    gaps, ctrl = [], []
    for r in sample:
        g, c = score_request(model, params, r.prompt, r.done.token_ids,
                             control=control)
        gaps.append(g)
        ctrl.append(c)
    out = {"failed": {"value": failed, "limit": 0},
           "malformed": {"value": bad, "limit": 0},
           **gap_numbers(gaps, limits)}
    return out, (gap_numbers(ctrl, limits) if control else None)


def verdict(cmp: dict, limits: dict, served: bool = True) -> bool:
    """Every number the cell compares present and within its limit: the
    ``limits`` of the cell, and for a served run ``failed`` and
    ``malformed`` too."""
    keys = (["failed", "malformed"] if served else []) + sorted(limits)
    return bool(keys) and all(
        isinstance(cmp.get(k), dict) and cmp[k]["value"] is not None
        and cmp[k]["value"] <= cmp[k]["limit"] for k in keys)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cell, seed: int, seconds: float, trace: bool, devices, peak,
             *, t_start: float = T_START, fault=None, control=False) -> dict:
    """Run the cell once and return the result object. ``fault`` (tests)
    breaks the served path under the driver after the server is built;
    ``control`` also scores the int8 control at the same positions, and the
    result then holds its numbers and its verdict under the cell's limits
    (``control``, ``control_correct``)."""
    import jax
    sys.path.insert(0, str(ROOT / "src"))
    from benchlib import traffic as T
    from benchlib.drive import Driver
    from benchlib.meter import CompileMeter, peak_bytes
    from benchlib.weights import make_weights
    from repro.core import Orchestrator
    from repro.core.asp import QualityTier
    from repro.core.clock import Clock
    from repro.launch.serve import hinted_asp
    from repro.models.transformer import LM
    from repro.serving.server import AIaaSServer

    meter = CompileMeter()
    config, traffic = cell.config, cell.traffic
    limits = cell.extra.get("limits", {})
    model = config["model"]
    cfg = model_config(config)
    cat = catalog_for(config, cfg)
    serving = config["serving"]

    shapes = jax.eval_shape(LM(cfg).init, jax.random.key(0))
    log(f"{cell.name} seed {seed}: drawing weights")
    params = make_weights(shapes, seed)
    jax.block_until_ready(params)
    log("building the server")
    orch = Orchestrator(clock=Clock(), catalog=cat)
    with served_weights(params):
        server = AIaaSServer(orch, config["name"], slots=serving["slots"],
                             max_len=serving["max_len"],
                             pallas_decode=cfg.use_pallas_decode)
    closed = traffic["loop"] == "closed"
    warm_s = float(traffic["warm_s"])
    from benchlib.drive import DRAIN_S
    if closed:
        plans = T.closed_loop(traffic, seed, serving["slots"])
    else:
        plans = T.open_loop(traffic, seed, warm_s + seconds + DRAIN_S)
    log("warming every site engine")
    warm = warm_up(server, plans, cfg.vocab_size)
    log(f"warm: {meter.since((0.0, 0, 0, 0))}")
    if fault is not None:
        fault(server)
    top = cat.get(config["name"]).tier
    tiers = {"premium": QualityTier.PREMIUM, "basic": QualityTier.BASIC}

    def asp_for(tier: str):
        return hinted_asp(config["name"], min(tiers[tier], top))

    d = Driver(server, seed, cfg.vocab_size, asp_for, orch.clock)
    marks = {}

    def queued():
        return sum(p.scheduler.queue_depth() for p in server.planes.values())

    def on_open():
        log("window opens")
        marks["setup_s"] = time.monotonic() - t_start
        marks["compile"] = meter.snapshot()
        marks["queue_open"] = queued()
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            marks["span"] = jax.profiler.TraceAnnotation("bench.window")
            marks["span"].__enter__()

    def on_close():
        log("window closes")
        marks["in_window"] = meter.since(marks["compile"])
        marks["queue_close"] = queued()
        if trace:
            marks["span"].__exit__(None, None, None)

    log("serving the traffic")
    d.run(warm_s, seconds, closed, plans, on_open=on_open,
          on_close=on_close)
    log("answers due in the window are in")
    if trace:
        # stopped only now: writing the trace blocks the host for seconds,
        # and inside the loop no session would be heartbeated meanwhile
        jax.profiler.stop_trace()
        log("profiler stopped")
    mem = peak_bytes(devices)
    e2e = end_to_end(d, marks["setup_s"])
    wreq = d.window_requests()
    attempted = len(wreq)
    failed = sum(1 for r in wreq if not r.ok)
    late = [max(r.sent - r.due, 0.0) * 1e3 for r in wreq if r.sent]
    cin = marks["in_window"]
    say(f"window: seconds={seconds} compiles_in_window={cin['compiles']} "
        f"compile_s_in_window={cin['compile_s']:.3f} sent={attempted} "
        f"succeeded={attempted - failed} failed={failed}")
    say(f"window: anchors={json.dumps(d.anchors, sort_keys=True)} "
        f"generator_late_ms_p95={pct(late, 95)} "
        f"generator_late_ms_max={max(late, default=None)} "
        f"queued_at_open={marks['queue_open']} "
        f"queued_at_close={marks['queue_close']} "
        f"memory_peak_bytes={mem} warmed={json.dumps(warm)}")
    failed_by = {}                  # reason (``Req.failed`` to its colon)
    for r in wreq:
        if not r.ok:
            say(f"window: failed session {r.session} turn {r.turn}: "
                f"{r.failed}")
            key = r.failed.split(":", 1)[0]
            failed_by[key] = failed_by.get(key, 0) + 1
    result = {}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": mem}
    if trace:
        from benchlib import trace as TR
        log("reading the trace")
        raw = TR.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        red = TR.reduce(raw)
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        run = SimpleNamespace(model=model, serving=serving, peak=peak,
                              driver=d, trace=red, raw_trace=raw,
                              window=d.window)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end
                   if e2e.get(m["name"]) is not None}
    # the reference runs with the program's state freed
    sample = check_sample(d, seed, int(traffic.get("check_tokens", 400)))
    d_anchors = dict(d.anchors)
    del d, server, orch
    gc.collect()
    log(f"comparing {len(sample)} requests with the reference")
    cmp, ctrl = compare(SimpleNamespace(window_requests=lambda: wreq),
                        sample, params, model, limits, control=control)
    log("compared")
    if control:
        result["control"] = ctrl
        result["control_correct"] = verdict(ctrl, limits, served=False)
    result = {"correct": verdict(cmp, limits), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev,
              "window": {"queued_at_open": marks["queue_open"],
                         "queued_at_close": marks["queue_close"],
                         "compiles": cin["compiles"],
                         "generator_late_ms_p95": pct(late, 95),
                         "anchors": d_anchors, "failed_by": failed_by,
                         "e2e": e2e},
              **result, "compared": cmp}
    return result


def emit(result: dict) -> None:
    cmp = result["compared"]
    for k, v in cmp.items():
        if isinstance(v, dict):
            print(f"compared {k}: {v['value']} limit {v['limit']}",
                  file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the program (src/repro) is not beside {BENCH}")
    try:
        cell = spec.load_cell(a.workload)
    except (KeyError, FileNotFoundError) as e:
        return fail(str(e))
    enable_cache()
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return fail(f"JAX found no device: {e}")
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < cell.chips:
        return fail(f"cell asks for {cell.chips} chips, JAX found "
                    f"{len(devices)}")
    peak = spec.peaks(devices[0].device_kind)
    if peak is None:
        return fail(f"device kind {devices[0].device_kind!r} is not in "
                    f"bench/peaks.json")
    emit(run_cell(cell, a.seed, a.seconds, bool(a.trace),
                  devices[:cell.chips], peak))
    return 0


if __name__ == "__main__":
    sys.exit(main())
