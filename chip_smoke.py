#!/usr/bin/env python3
"""Bring-up check: the served path runs on one TPU chip, end to end.

    python3 chip_smoke.py

Everything runs in this one process (a chip belongs to one process at a
time), through the entry points a user calls: ``repro.launch.serve.serve``
→ ``AIaaSServer`` → ``SessionClient`` → ``NorthboundGateway`` →
orchestrator → ``ServingPlane`` → ``InferenceEngine``, with the default
timers (30 s leases, 2 s τ_mig) on the wall clock. Weights are random,
drawn from a fixed seed.

  A. mamba2-1.3b at its registered config, whole (48 layers).
  B. minitron-8b at its published widths, depth cut to 4 layers, with
     Pallas decode attention, registered with ``Catalog.register`` on its
     own orchestrator; then one make-before-break migration of a live
     session between two site engines, whose continuation must match a
     fresh engine's decode of the same prompt token for token.
  C. ``decode_attention`` and ``paged_decode_attention`` on the chip
     against the float32 references of ``kernels/decode_attention/ref.py``
     at minitron-8b decode widths.

Each phase prints the bound model, requests sent / served / failed (with
the cause and the error text of each failure), the seconds spent in XLA
compilation (or loading from the persistent cache), and the device's
``peak_bytes_in_use``. None of these is a speed. The last line is one JSON
object naming the device. The script exits nonzero, and prints no such
line, when JAX finds no TPU, when the repository's code is not beside it,
or when any phase fails.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import sys
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: |kernel - float32 reference| bound for bf16 operands: the output is
#: rounded to bf16 (2^-8 relative; |out| stays below ~4 for N(0, 1) values,
#: so up to ~0.016) and the kernel rounds the softmax weights to bf16 before
#: the PV matmul (2^-9 relative each) — the interpret-mode tests' bound
KERNEL_ATOL = 0.035


class CompileMeter:
    """Counts XLA compilations, persistent-cache hits and persistent-cache
    writes (JAX records a miss only when it writes the entry) via
    jax.monitoring."""

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.writes = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.hits, self.writes)

    def since(self, snap) -> str:
        s, c, h, m = (a - b for a, b in zip(self.snapshot(), snap))
        return (f"compile_s={s:.1f} compiles={c} "
                f"persistent_cache_hits={h} persistent_cache_writes={m}")


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return (f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')} "
            f"bytes_in_use={stats.get('bytes_in_use', 'n/a')}")


def check_served(label, rep, gen_tokens, vocab) -> list:
    """Failures of one serve() run: failed requests, mismatched bindings,
    and token streams of the wrong length or outside the vocabulary."""
    bad = []
    print(f"{label}: engine model={rep.model_id} bound="
          f"{sorted(set(rep.bound.values()))} "
          f"anchors={sorted(set(rep.anchors.values()))} sent={rep.sent} "
          f"served={rep.served} failed={len(rep.failed)}")
    for rid, cause, detail in rep.failed:
        bad.append(f"request {rid} failed: {cause}: {detail}")
    for sid, model in rep.mismatched().items():
        bad.append(f"session {sid} bound {model}, engines run {rep.model_id}")
    if rep.served != rep.sent:
        bad.append(f"served {rep.served} of {rep.sent} requests")
    for rid, toks in rep.tokens.items():
        if len(toks) != gen_tokens or not all(0 <= t < vocab for t in toks):
            bad.append(f"request {rid} returned tokens {toks}")
    return bad


def check_logits(label, engine) -> list:
    """One prefill through the engine's own compiled program: the logits
    must be finite, one row over the padded vocabulary."""
    import jax.numpy as jnp
    import numpy as np
    prompt = np.arange(1, 17, dtype=np.int32)[None, :]
    logits, _ = engine._prefill(engine.params,
                                {"tokens": jnp.asarray(prompt),
                                 "length": jnp.int32(16)})
    logits = np.asarray(logits, np.float32)
    want = (1, engine.cfg.padded_vocab)
    print(f"{label}: prefill logits shape={logits.shape} "
          f"finite={bool(np.isfinite(logits).all())}")
    bad = []
    if logits.shape != want:
        bad.append(f"logits shape {logits.shape}, expected {want}")
    if not np.isfinite(logits).all():
        bad.append("non-finite logits")
    return bad


def phase_serve(label, model, orch, *, pallas, slots, max_len,
                sessions=3, requests=9, gen_tokens=8):
    """One serve() run plus its output checks; returns (failures, report)."""
    from repro.launch.serve import serve
    rep = serve(model, sessions=sessions, requests=requests, slots=slots,
                max_len=max_len, gen_tokens=gen_tokens, seed=0, quiet=True,
                pallas_decode=pallas, orch=orch)
    cfg = rep.server.fleet.cfg
    bad = check_served(label, rep, gen_tokens, cfg.vocab_size)
    anchor = next(iter(orch.sites))
    bad += check_logits(label, rep.server.fleet.engine_for(anchor))
    return bad, rep


def decode_kernel_in_program(engine) -> bool:
    """Whether the fused decode program the engine runs holds the Pallas
    decode kernel (a Mosaic ``tpu_custom_call``), not the XLA fallback."""
    import jax.numpy as jnp
    n = engine.slots
    lowered = engine._decode_fused.lower(
        engine.params, engine.cache, jnp.zeros(n, jnp.int32),
        jnp.ones(n, bool), 1)
    return "tpu_custom_call" in lowered.as_text()


def phase_migration(label, server, *, rounds=5) -> list:
    """Make-before-break migration of a live session, fired northbound by
    a heartbeat whose Eq. 14 thresholds are zero, under the default
    leases and τ_mig. The target's continuation must equal what a fresh
    engine on the same weights decodes from the same prompt, with no
    transfer."""
    import numpy as np
    from repro.api.client import SessionClient
    from repro.core.asp import MobilityClass
    from repro.launch.serve import hinted_asp
    from repro.serving.engine import InferenceEngine
    model = server.fleet.entry.model_id
    prompt = np.arange(16, dtype=np.int32)
    eng_src = server.fleet.engine_for(next(iter(server.planes)))
    ref = InferenceEngine(eng_src.cfg, params=eng_src.params,
                          slots=eng_src.slots, max_len=eng_src.max_len)
    ref.prefill_session("ref", prompt)
    expect = [ref.decode_round()["ref"] for _ in range(2 * rounds)][rounds:]
    del ref
    asp = hinted_asp(model, server.fleet.entry.tier,
                     mobility=MobilityClass.VEHICULAR)
    client = SessionClient(server.gateway, asp, invoker="car-7",
                           zone="zone-a").establish()
    sid, src = client.session_id, client.record["anchor"]
    print(f"{label}: session {sid} bound {client.record['model']} at {src}")
    eng_src = server.fleet.engine_for(src)
    eng_src.prefill_session(sid, prompt)
    for _ in range(rounds):
        eng_src.decode_round()
    out = client.heartbeat(trigger_l99=0.0, trigger_ttfb=0.0).migration
    if not out or not out.get("migrated"):
        client.release()
        return [f"migration did not happen: {out}"]
    dst = server.fleet.engine_for(client.anchor)
    got = [dst.decode_round()[sid] for _ in range(rounds)]
    client.release()
    print(f"{label}: migrated {out['from_site']} -> {out['to_site']} "
          f"bytes={out['transfer_bytes']} "
          f"interruption_ms={out['interruption_ms']} "
          f"continuation_identical={got == expect}")
    bad = []
    if got != expect:
        bad.append(f"continuation after migration {got} != {expect}")
    if eng_src.has_slot(sid):
        bad.append("source slot still held after the swap")
    return bad


def phase_kernels(label, *, B=8, HQ=32, HKV=8, D=128, S=2048, page=128,
                  seed=0) -> list:
    """Pallas decode kernels on the device vs their float32 references."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attention import ops
    from repro.kernels.decode_attention.ref import (
        decode_attention_ref, paged_decode_attention_ref)
    ks = jax.random.split(jax.random.key(seed), 5)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, HQ, D), jnp.float32).astype(bf)
    k = jax.random.normal(ks[1], (B, HKV, S, D), jnp.float32).astype(bf)
    v = jax.random.normal(ks[2], (B, HKV, S, D), jnp.float32).astype(bf)
    lengths = jax.random.randint(ks[3], (B,), 1, S + 1)
    lengths = lengths.at[0].set(S).at[-1].set(1)     # full and one-token rows
    # page pool in the models.kvcache layout [P, page, Hkv, D]: shuffled
    # block tables, page 0 and spare pages hold garbage that must never
    # reach the softmax
    pps, extra = S // page, 3
    pool_n = 1 + B * pps + extra
    rows = 1 + jax.random.permutation(ks[4], B * pps + extra)
    tables = rows[:B * pps].reshape(B, pps).astype(jnp.int32)
    src_k = jnp.moveaxis(k, 2, 1).reshape(B * pps, page, HKV, D)
    src_v = jnp.moveaxis(v, 2, 1).reshape(B * pps, page, HKV, D)
    pool_k = jnp.full((pool_n, page, HKV, D), 1e4, bf).at[
        tables.reshape(-1)].set(src_k)
    pool_v = jnp.full((pool_n, page, HKV, D), -1e4, bf).at[
        tables.reshape(-1)].set(src_v)

    with jax.default_matmul_precision("highest"):
        ref = jax.jit(decode_attention_ref)(q, k, v, lengths)
        ref_paged = jax.jit(paged_decode_attention_ref)(
            q, pool_k, pool_v, lengths, tables)
    cases = {
        # the kernel reads the stacked cache [L, B, S, Hkv, D]: one layer
        "decode_attention": (ops.decode(q, jnp.moveaxis(k, 1, 2)[None],
                                        jnp.moveaxis(v, 1, 2)[None],
                                        lengths, 0), ref),
        "paged_decode_attention": (
            ops.paged_decode(q, pool_k, pool_v, lengths, tables),
            ref_paged),
    }
    bad = []
    for name, (out, want) in cases.items():
        out = np.asarray(out, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(out - want)))
        ok = out.shape == (B, HQ, D) and np.isfinite(out).all() \
            and err <= KERNEL_ATOL
        print(f"{label}: {name} B={B} Hq={HQ} Hkv={HKV} D={D} S={S} "
              f"max_abs_err={err:.6f} tol={KERNEL_ATOL} "
              f"max_abs_ref={float(np.max(np.abs(want))):.4f} ok={ok}")
        if not ok:
            bad.append(f"{name}: max |err| {err} over tolerance "
                       f"{KERNEL_ATOL} (shape {out.shape})")
    return bad


def minitron_cut_catalog(layers: int = 4):
    """minitron-8b at every published width, depth cut to ``layers``."""
    from repro.configs import get_config
    from repro.core.catalog import Catalog, default_catalog
    base = default_catalog().get("minitron-8b")
    name = f"minitron-8b-{layers}L"
    cfg = dataclasses.replace(get_config("minitron-8b"), name=name,
                              num_layers=layers)
    cat = Catalog()
    cat.register(dataclasses.replace(base, model_id=name, cfg=cfg))
    return cat, name


def run_phase(label, fn, meter, device, failures) -> None:
    snap = meter.snapshot()
    try:
        bad = fn()
    except Exception:                                # noqa: BLE001
        bad = [f"raised:\n{traceback.format_exc()}"]
    print(f"{label}: {meter.since(snap)} {peak_bytes(device)}")
    for b in bad:
        print(f"{label}: FAIL {b}")
    failures.extend(f"{label}: {b}" for b in bad)
    gc.collect()


def main() -> int:
    try:
        import jax
        device = jax.devices()[0]
    except Exception as e:                           # noqa: BLE001
        print(f"chip_smoke: JAX found no device: {e}", file=sys.stderr)
        return 2
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device.platform}; "
              f"nothing was run", file=sys.stderr)
        return 2
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's code is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    from repro.core import Orchestrator
    from repro.core.clock import Clock
    cache_dir = enable_compile_cache(ROOT)
    meter = CompileMeter()
    print(f"device: {device.platform} {device.device_kind} "
          f"count={len(jax.devices())} compile_cache={cache_dir}")
    failures: list = []

    def phase_a():
        bad, rep = phase_serve("A mamba2-1.3b", "mamba2-1.3b",
                               Orchestrator(clock=Clock()), pallas=False,
                               slots=8, max_len=2048)
        return bad

    def phase_b():
        cat, name = minitron_cut_catalog(4)
        bad, rep = phase_serve(f"B {name}", name,
                               Orchestrator(clock=Clock(), catalog=cat),
                               pallas=True, slots=8, max_len=2048)
        eng = rep.server.fleet.engine_for(next(iter(rep.server.planes)))
        has_kernel = decode_kernel_in_program(eng)
        print(f"B {name}: decode program holds tpu_custom_call={has_kernel}")
        if not has_kernel:
            bad.append("decode program has no Pallas kernel")
        return bad + phase_migration(f"B {name}", rep.server)

    run_phase("A mamba2-1.3b", phase_a, meter, device, failures)
    run_phase("B minitron-8b-4L", phase_b, meter, device, failures)
    run_phase("C kernels", lambda: phase_kernels("C kernels"), meter,
              device, failures)
    if failures:
        print(f"chip_smoke: {len(failures)} failure(s)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
