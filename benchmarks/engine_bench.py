"""InferenceEngine hot-path benchmark — the BENCH_engine trajectory.

Measures the four costs the fast serving path is about, on the CPU tiny
configs (the same code jit-compiles under the production mesh on a pod):

* ``decode tokens/s``   — serving throughput through the REAL hot path
                          (ServingPlane + RealEngineBackend) at batch
                          1 / 8 / 64: the seed's per-token loop (one jitted
                          step + eager argmax + host round-trip + per-token
                          plane accounting per token, reimplemented
                          faithfully below) versus fused K-step chunks.
                          The headline runs on the smoke tiny config, where
                          step compute does not mask the dispatch overhead
                          being measured — the same regime a production
                          decode cell is in (step time ~ dispatch+host
                          latency; that is why serving engines fuse
                          multi-step loops at all).
* ``prefill_compiles``  — jit variants traced over 100 mixed-length
                          prompts (bucketed: <= ceil(log2(max_len))),
* ``ttft_ms``           — median admit-to-first-token latency,
* ``export_ms`` / ``import_ms`` — slot state extraction/install (the
                          donated, index-addressed path migration rides).

    PYTHONPATH=src python -m benchmarks.engine_bench [--quick]
        [--check-baseline] [--write-baseline]

``--check-baseline`` compares fused decode tokens/s against the checked-in
``benchmarks/baselines/engine.json`` and exits non-zero on a >20% drop —
the CI regression guard for the serving hot path.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, "src")
sys.path.insert(0, ".")

import numpy as np  # noqa: E402

from benchmarks import _baseline  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.core.clock import Clock  # noqa: E402
from repro.serving.engine import InferenceEngine  # noqa: E402
from repro.serving.plane import (RealEngineBackend,  # noqa: E402
                                 ServingPlane)

BASELINE_NAME = "engine"


class SeedLoopEngine:
    """Faithful reimplementation of the pre-PR engine hot loop (the "before"
    arm): one jitted ``decode_step`` per token with NO buffer donation, an
    eager ``jnp.argmax`` dispatch, and a device→host token transfer every
    step."""

    def __init__(self, cfg, params, slots, max_len):
        import jax
        import jax.numpy as jnp
        from repro.models.transformer import LM
        self.cfg = cfg
        self.lm = LM(cfg)
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.cache = self.lm.init_cache(slots, max_len)
        self._slot_map = {}
        self._slots = [None] * slots
        self._prefill = jax.jit(lambda p, b: self.lm.prefill(p, b, max_len))
        self._decode = jax.jit(self.lm.decode_step)

    def free_slots(self):
        return sum(1 for s in self._slots if s is None)

    def prefill_session(self, sid, prompt):
        import jax
        import jax.numpy as jnp
        t0 = time.perf_counter()
        batch = {"tokens": jnp.asarray(prompt[None, :], jnp.int32)}
        logits, cache1 = self._prefill(self.params, batch)
        tok = int(jnp.argmax(logits[0]))
        idx = next(i for i, s in enumerate(self._slots) if s is None)
        self._slot_map[sid] = idx

        def ins(path, full, one):
            ax = 1 if any(str(getattr(k, "key", "")) in ("k", "v")
                          for k in path) else 0
            row = jax.lax.index_in_dim(one, 0, axis=ax, keepdims=False)
            return (full.at[idx].set(row) if ax == 0
                    else full.at[:, idx].set(row))

        self.cache = jax.tree_util.tree_map_with_path(ins, self.cache,
                                                      cache1)
        self._slots[idx] = {"sid": sid, "last": tok}
        return {"first_token": tok,
                "ttfb_ms": (time.perf_counter() - t0) * 1e3}

    def decode_round(self, steps=None):
        import jax.numpy as jnp
        if not self._slot_map:
            return {}
        toks = np.zeros((self.slots, 1), np.int32)
        for i, s in enumerate(self._slots):
            if s is not None:
                toks[i, 0] = s["last"]
        logits, self.cache = self._decode(self.params, self.cache,
                                          jnp.asarray(toks))
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        out = {}
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            s["last"] = int(nxt[i])
            out[s["sid"]] = [s["last"]] if steps is not None else s["last"]
        return out

    def release_slot(self, sid):
        idx = self._slot_map.pop(sid, None)
        if idx is not None:
            self._slots[idx] = None


def _prompt(n, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=n).astype(np.int32)


def _mk_plane(engine, *, batch, chunk):
    clock = Clock()
    # no premium reservation: measure clean batch-N continuous batching
    # (reserved slots would split the workload into waves)
    return ServingPlane(clock, RealEngineBackend(engine, clock),
                        slots=batch, site_id="bench", decode_chunk=chunk,
                        premium_reserved_frac=0.0)


def _drain_once(plane, *, batch, gen, vocab):
    for i in range(batch):
        plane.submit(session_id=f"s{i}", klass="best-effort",
                     prompt_tokens=12, gen_tokens=gen, t_max_ms=1e12,
                     prompt=_prompt(12, vocab, seed=i))
    t0 = time.perf_counter()
    plane.drain()
    wall = time.perf_counter() - t0
    plane.pop_results()
    return batch * gen / wall


PER_TOKEN_CHUNK = {"premium": 1, "assured": 1, "best-effort": 1}


def bench_decode(batch: int, *, gen: int = 49, max_len: int = 64,
                 reps: int = 6, cfg=None, params=None):
    """Decode tokens/s through the plane: seed per-token loop vs fused.

    The two arms run INTERLEAVED rep-by-rep and the speedup is the median
    of per-pair ratios — background load on a shared box then inflates
    both arms of a pair together instead of skewing whichever arm happened
    to run during the noisy window. Rep 0 pays jit compiles (discarded).
    """
    cfg = cfg or get_smoke_config("edge-tiny")
    fused_eng = InferenceEngine(cfg, params=params, slots=batch,
                                max_len=max_len)
    params = fused_eng.params
    seed_eng = SeedLoopEngine(cfg, params, batch, max_len)
    seed_plane = _mk_plane(seed_eng, batch=batch, chunk=PER_TOKEN_CHUNK)
    fused_plane = _mk_plane(fused_eng, batch=batch, chunk=None)
    vocab = cfg.vocab_size
    seeds, fuseds, ratios = [], [], []
    for rep in range(reps + 1):
        s = _drain_once(seed_plane, batch=batch, gen=gen, vocab=vocab)
        f = _drain_once(fused_plane, batch=batch, gen=gen, vocab=vocab)
        if rep > 0:                       # rep 0 = compile warmup
            seeds.append(s)
            fuseds.append(f)
            ratios.append(f / s)
    return {"per_token": statistics.median(seeds),
            "fused": statistics.median(fuseds),
            "speedup": statistics.median(ratios)}, params


def bench_prefill(n_prompts: int = 100, *, max_len: int = 256, params=None):
    """Compile count + TTFT over a mixed-length prompt population."""
    cfg = get_config("edge-tiny")
    eng = InferenceEngine(cfg, params=params, slots=2, max_len=max_len)
    rng = np.random.default_rng(11)
    lengths = rng.integers(1, max_len, size=n_prompts)
    # warm every bucket first so ttft measures steady-state dispatch
    for b in eng.buckets:
        eng.prefill_session("warm", _prompt(b, cfg.vocab_size))
        eng.release_slot("warm")
    warm_compiles = eng.prefill_compiles
    ttfts = []
    for i, n in enumerate(lengths):
        sid = f"p{i}"
        r = eng.prefill_session(sid, _prompt(int(n), cfg.vocab_size, seed=i))
        ttfts.append(r["ttfb_ms"])
        eng.release_slot(sid)
    return {
        "prefill_compiles": eng.prefill_compiles,
        "bucket_count": len(eng.buckets),
        "compiles_during_run": eng.prefill_compiles - warm_compiles,
        "ttft_ms_p50": round(statistics.median(ttfts), 3),
        "ttft_ms_p99": round(sorted(ttfts)[int(0.99 * (len(ttfts) - 1))], 3),
    }, eng.params


def bench_transfer(*, rounds: int = 20, max_len: int = 128, params=None):
    """export_slot / import_slot latency (the migration data-plane cost)."""
    import jax
    cfg = get_config("edge-tiny")
    src = InferenceEngine(cfg, params=params, slots=2, max_len=max_len)
    dst = InferenceEngine(cfg, params=src.params, slots=2, max_len=max_len)
    src.prefill_session("m", _prompt(24, cfg.vocab_size))
    src.decode_round(steps=4)
    exp_ms, imp_ms = [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        payload = src.export_slot("m")
        jax.block_until_ready(payload["cache"])
        exp_ms.append((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        dst.import_slot("m", payload)
        jax.block_until_ready(dst.cache)
        imp_ms.append((time.perf_counter() - t0) * 1e3)
        dst.release_slot("m")
    return {
        "export_ms_p50": round(statistics.median(exp_ms), 3),
        "import_ms_p50": round(statistics.median(imp_ms), 3),
    }, src.params


def run(*, quick: bool = False) -> dict:
    gen = 49
    n_prompts = 30 if quick else 100
    batches = (1, 8) if quick else (1, 8, 64)
    params = None
    decode = {}
    for b in batches:
        decode[b], params = bench_decode(
            b, gen=gen, reps=4 if quick else 6, params=params)
    # the demo config for reference: compute-bound regime (fusion still
    # wins, but the step time dominates the dispatch being amortised)
    demo, _ = bench_decode(8, gen=17, reps=2,
                           cfg=get_config("edge-tiny"))
    prefill, _ = bench_prefill(n_prompts)
    transfer, _ = bench_transfer(rounds=5 if quick else 20)
    return {
        "decode": {str(b): {k: round(v, 1) for k, v in d.items()}
                   for b, d in decode.items()},
        "decode_demo_cfg_batch8": {k: round(v, 1) for k, v in demo.items()},
        "prefill": prefill,
        "transfer": transfer,
        "gen": gen,
        "n_prompts": n_prompts,
    }


def figure_rows(quick: bool = False):
    """(rows, derived) in the benchmarks/figures.py convention."""
    import math
    r = run(quick=quick)
    rows = []
    for b, d in r["decode"].items():
        rows.append({"batch": int(b), "per_token_tok_s": d["per_token"],
                     "fused_tok_s": d["fused"], "speedup": d["speedup"]})
    at8 = r["decode"].get("8", next(iter(r["decode"].values())))
    max_len = 256
    derived = {
        "claim": "fused K-step decode >= 3x the seed per-token serving loop "
                 "at batch 8; prefill compiles bounded by log2 buckets",
        "speedup_at_batch8": at8["speedup"],
        "prefill_compiles": r["prefill"]["prefill_compiles"],
        "compile_ceiling": math.ceil(math.log2(max_len)),
        "ttft_ms_p50": r["prefill"]["ttft_ms_p50"],
        "export_ms_p50": r["transfer"]["export_ms_p50"],
        "import_ms_p50": r["transfer"]["import_ms_p50"],
        "holds": (at8["speedup"] >= 3.0
                  and r["prefill"]["prefill_compiles"]
                  <= math.ceil(math.log2(max_len))),
    }
    return rows, derived


def check_baseline(result: dict) -> list:
    """Regression guard, hardware-independent: the fused-vs-seed SPEEDUP
    ratio (both arms measured on the same machine in the same run) must not
    fall below the per-batch floor, and prefill compiles must stay within
    the bucket count. Absolute tok/s values in the baseline are reference
    only — they depend on the runner, the ratio does not. Returns failure
    messages."""
    base = _baseline.load_baseline(BASELINE_NAME)
    failures = []
    for b, d in base["decode"].items():
        got = result["decode"].get(b)
        floor = d.get("speedup_floor")
        if got is None or floor is None:
            continue
        if got["speedup"] < floor:
            failures.append(
                f"decode batch={b}: fused/seed speedup "
                f"{got['speedup']:.2f}x < floor {floor:.2f}x "
                f"(a fused-path regression; reversion to the per-token "
                f"loop reads ~1.0x)")
    ceiling = base["prefill"]["bucket_count"]
    if result["prefill"]["prefill_compiles"] > ceiling:
        failures.append(
            f"prefill compiles {result['prefill']['prefill_compiles']} > "
            f"bucket count {ceiling}")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--check-baseline", action="store_true",
                    help="fail on >20%% fused-decode regression vs "
                         "benchmarks/baselines/engine.json")
    ap.add_argument("--write-baseline", action="store_true",
                    help="overwrite the checked-in baseline with this run")
    args = ap.parse_args()
    r = run(quick=args.quick)
    print(json.dumps(r, indent=1))
    os.makedirs("artifacts/bench", exist_ok=True)
    with open("artifacts/bench/engine.json", "w") as f:
        json.dump(r, f, indent=1)
    if args.write_baseline:
        _baseline.write_baseline(r, BASELINE_NAME)
    if args.check_baseline:
        _baseline.enforce(check_baseline(r))


if __name__ == "__main__":
    main()
