"""Serving hot path: fused multi-step decode, bucketed prefill, donated
slot state, and the Pallas decode-attention route.

Correctness bar for every fast path: BIT-IDENTICAL tokens to the slow
path it replaces — fused K-step chunks vs K sequential single-step rounds
(including export→import migration between chunks), bucketed prefill vs
exact-length prefill, Pallas decode vs the reference attention.
"""

import dataclasses
import math

import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.serving import state_transfer
from repro.serving.engine import InferenceEngine, prefill_buckets
from repro.serving.plane import RealEngineBackend, ServingPlane
from repro.serving.scheduler import Request

ARCHS = ["edge-tiny", "recurrentgemma-2b", "mamba2-1.3b"]   # dense/hybrid/ssm


def cfg_for(arch):
    return get_config(arch) if arch == "edge-tiny" else get_smoke_config(arch)


@pytest.fixture(scope="module")
def engines():
    """One engine per family (weights reused across tests)."""
    return {arch: InferenceEngine(cfg_for(arch), slots=4, max_len=64)
            for arch in ARCHS}


class TestFusedDecode:
    @pytest.mark.parametrize("arch", ARCHS)
    def test_fused_equals_sequential(self, engines, arch):
        """decode_round(steps=K) must be bit-identical to K sequential
        decode_round() calls — the fused scan IS the hot path, the
        sequential form is the oracle."""
        base = engines[arch]
        prompt = (np.arange(9, dtype=np.int32) * 5) % base.cfg.vocab_size

        seq = InferenceEngine(base.cfg, params=base.params, slots=4,
                              max_len=64)
        seq.prefill_session("s", prompt)
        toks_seq = [seq.decode_round()["s"] for _ in range(12)]

        fus = InferenceEngine(base.cfg, params=base.params, slots=4,
                              max_len=64)
        fus.prefill_session("s", prompt)
        toks_fus = []
        for k in (5, 4, 3):                      # uneven chunking
            toks_fus.extend(fus.decode_round(steps=k)["s"])
        assert toks_seq == toks_fus

    @pytest.mark.parametrize("arch", ARCHS)
    def test_fused_is_batch_composition_independent(self, engines, arch):
        """A fused chunk's tokens for one session must not depend on who
        shares the decode batch (per-slot positions + active mask)."""
        base = engines[arch]
        prompt = (np.arange(7, dtype=np.int32) * 3) % base.cfg.vocab_size

        solo = InferenceEngine(base.cfg, params=base.params, slots=4,
                               max_len=64)
        solo.prefill_session("s", prompt)
        alone = solo.decode_round(steps=6)["s"]

        shared = InferenceEngine(base.cfg, params=base.params, slots=4,
                                 max_len=64)
        shared.prefill_session("other", (np.arange(13, dtype=np.int32)
                                         % base.cfg.vocab_size))
        shared.decode_round(steps=2)
        shared.prefill_session("s", prompt)      # joins mid-flight
        together = shared.decode_round(steps=6)["s"]
        assert alone == together

    @pytest.mark.parametrize("arch", ARCHS)
    def test_migration_mid_chunk_bit_exact(self, engines, arch):
        """export_slot → import_slot between fused chunks: the stream
        continues bit-exact on the target, fingerprints match end-to-end."""
        base = engines[arch]
        prompt = (np.arange(11, dtype=np.int32) * 2) % base.cfg.vocab_size

        ref = InferenceEngine(base.cfg, params=base.params, slots=4,
                              max_len=64)
        ref.prefill_session("m", prompt)
        expect = []
        for k in (5, 7):
            expect.extend(ref.decode_round(steps=k)["m"])

        src = InferenceEngine(base.cfg, params=base.params, slots=4,
                              max_len=64)
        dst = InferenceEngine(base.cfg, params=base.params, slots=4,
                              max_len=64)
        src.prefill_session("m", prompt)
        got = list(src.decode_round(steps=5)["m"])
        meta = state_transfer.transfer(src, dst, "m")   # fingerprint-verified
        assert meta["bytes"] > 0
        src.release_slot("m")                           # the MBB break
        assert dst.position_of("m") == len(prompt) + 5
        got.extend(dst.decode_round(steps=7)["m"])
        assert got == expect

    def test_legacy_single_step_form_unchanged(self, engines):
        eng = InferenceEngine(engines["edge-tiny"].cfg,
                              params=engines["edge-tiny"].params,
                              slots=2, max_len=64)
        eng.prefill_session("s", np.arange(5, dtype=np.int32))
        out = eng.decode_round()
        assert isinstance(out["s"], int)
        out = eng.decode_round(steps=3)
        assert isinstance(out["s"], list) and len(out["s"]) == 3


class TestBucketedPrefill:
    def test_compile_count_bounded_over_mixed_lengths(self):
        """50 mixed-length prompts must trace at most len(buckets) prefill
        variants, and len(buckets) <= ceil(log2(max_len))."""
        cfg = get_config("edge-tiny")
        eng = InferenceEngine(cfg, slots=2, max_len=256)
        rng = np.random.default_rng(7)
        lengths = rng.integers(1, 256, size=50)
        for i, n in enumerate(lengths):
            sid = f"p{i}"
            eng.prefill_session(
                sid, (np.arange(n, dtype=np.int32) % cfg.vocab_size))
            eng.release_slot(sid)
        assert eng.prefill_compiles <= len(eng.buckets)
        assert len(eng.buckets) <= math.ceil(math.log2(eng.max_len))

    def test_buckets_cover_max_len(self):
        assert prefill_buckets(256) == [16, 32, 64, 128, 256]
        assert prefill_buckets(96) == [16, 32, 64, 96]
        assert all(b <= 512 for b in prefill_buckets(512))

    def test_oversized_prompt_rejected(self, engines):
        """A prompt longer than max_len must raise, not silently truncate —
        truncation would condition generation on a clipped prefix while
        position_of() (the migration payload size) reports the full
        length."""
        base = engines["edge-tiny"]
        eng = InferenceEngine(base.cfg, params=base.params, slots=2,
                              max_len=32)
        with pytest.raises(ValueError, match="exceeds engine max_len"):
            eng.prefill_session("big", np.arange(40, dtype=np.int32)
                                % base.cfg.vocab_size)
        assert not eng.has_slot("big")

    @pytest.mark.parametrize("arch", ARCHS)
    def test_bucketed_equals_exact_prefill(self, engines, arch):
        """The padded-bucket cache must continue the stream exactly like an
        exact-length (unpadded) prefill: same first token, same decode
        continuation — for KV, ring, RG-LRU, and SSD state alike."""
        import jax
        import jax.numpy as jnp
        base = engines[arch]
        lm = base.lm
        prompt = (np.arange(9, dtype=np.int32) * 7) % base.cfg.vocab_size

        # oracle: exact-length prefill straight through the LM
        logits, _ = jax.jit(lambda p, b: lm.prefill(p, b, 64))(
            base.params, {"tokens": jnp.asarray(prompt[None, :], jnp.int32)})
        first_exact = int(jnp.argmax(logits[0]))

        eng = InferenceEngine(base.cfg, params=base.params, slots=2,
                              max_len=64)
        pre = eng.prefill_session("s", prompt)     # padded to bucket 16
        assert pre["first_token"] == first_exact
        assert eng.position_of("s") == len(prompt)


class TestPallasDecodeRoute:
    def test_bit_close_to_reference_and_same_tokens(self):
        """cfg.use_pallas_decode must produce decode attention bit-close to
        the reference path (same math, same masking) and identical greedy
        tokens through the engine."""
        import jax
        import jax.numpy as jnp
        from repro.models import attention as A

        cfg = get_config("edge-tiny")
        ref_eng = InferenceEngine(cfg, slots=2, max_len=64)
        pal_cfg = dataclasses.replace(cfg, use_pallas_decode=True)
        pal_eng = InferenceEngine(pal_cfg, params=ref_eng.params,
                                  slots=2, max_len=64)
        prompt = np.arange(12, dtype=np.int32)
        a = ref_eng.prefill_session("s", prompt)
        b = pal_eng.prefill_session("s", prompt)
        assert a["first_token"] == b["first_token"]
        ta = ref_eng.decode_round(steps=8)["s"]
        tb = pal_eng.decode_round(steps=8)["s"]
        assert ta == tb

        # numeric closeness of the raw layer output (not just argmax)
        key = jax.random.key(0)
        p = A.attention_init(key, cfg)
        x = jax.random.normal(jax.random.key(1), (2, 1, cfg.d_model),
                              jnp.float32).astype(jnp.bfloat16)
        ck = jax.random.normal(jax.random.key(2),
                               (2, 32, cfg.num_kv_heads, cfg.head_dim),
                               jnp.float32).astype(jnp.bfloat16)
        cv = jax.random.normal(jax.random.key(3), ck.shape,
                               jnp.float32).astype(jnp.bfloat16)
        pos = jnp.array([5, 17], jnp.int32)
        o_ref, _, _ = A.decode_self_attention(p, cfg, x, ck, cv, pos)
        o_pal, _, _ = A.decode_self_attention(p, pal_cfg, x, ck, cv, pos)
        np.testing.assert_allclose(
            np.asarray(o_ref, np.float32), np.asarray(o_pal, np.float32),
            atol=2e-2, rtol=2e-2)   # bf16 accumulation-order tolerance

    def test_decode_past_buffer_stays_on_reference_mask(self):
        """Positions >= S (generation past the cache buffer): the kernel's
        ragged-length mask must clamp at S — unclamped it would admit rows
        past the buffer (a partial last kv block reads them; when the
        kernel padded the buffer this showed up as ~0.15 max divergence vs
        the ~3e-3 bf16 noise floor)."""
        import jax
        import jax.numpy as jnp
        from repro.models import attention as A

        cfg = get_config("edge-tiny")
        pal_cfg = dataclasses.replace(cfg, use_pallas_decode=True)
        p = A.attention_init(jax.random.key(0), cfg)
        S = 24
        x = jax.random.normal(jax.random.key(1), (2, 1, cfg.d_model),
                              jnp.float32).astype(jnp.bfloat16)
        ck = jax.random.normal(jax.random.key(2),
                               (2, S, cfg.num_kv_heads, cfg.head_dim),
                               jnp.float32).astype(jnp.bfloat16)
        cv = jax.random.normal(jax.random.key(3), ck.shape,
                               jnp.float32).astype(jnp.bfloat16)
        for pos in (S - 1, S, S + 10, S + 100):
            position = jnp.array([pos, pos + 3], jnp.int32)
            o_ref, _, _ = A.decode_self_attention(p, cfg, x, ck, cv,
                                                  position)
            o_pal, _, _ = A.decode_self_attention(p, pal_cfg, x, ck, cv,
                                                  position)
            np.testing.assert_allclose(
                np.asarray(o_ref, np.float32), np.asarray(o_pal, np.float32),
                atol=2e-2, rtol=2e-2)

    @pytest.mark.parametrize("pallas", [False, True],
                             ids=["reference", "pallas"])
    def test_stacked_cache_matches_layer_buffer(self, pallas):
        """Given the whole layer stack and a layer index, decode attention
        writes that layer's row in place and attends it exactly as it does
        the layer's own buffer; the other layers stay bit-identical."""
        import jax
        import jax.numpy as jnp
        from repro.models import attention as A

        cfg = dataclasses.replace(get_config("edge-tiny"),
                                  use_pallas_decode=pallas)
        p = A.attention_init(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(1), (2, 1, cfg.d_model),
                              jnp.float32).astype(jnp.bfloat16)
        shape = (3, 2, 40, cfg.num_kv_heads, cfg.head_dim)
        sk = jax.random.normal(jax.random.key(2), shape,
                               jnp.float32).astype(jnp.bfloat16)
        sv = jax.random.normal(jax.random.key(3), shape,
                               jnp.float32).astype(jnp.bfloat16)
        pos = jnp.array([7, 39], jnp.int32)
        active = jnp.array([True, False])
        for layer in (0, 2):
            o_one, ck, cv = A.decode_self_attention(
                p, cfg, x, sk[layer], sv[layer], pos, active=active)
            o_st, nk, nv = A.decode_self_attention(
                p, cfg, x, sk, sv, pos, active=active,
                layer=jnp.int32(layer))
            np.testing.assert_array_equal(np.asarray(o_one, np.float32),
                                          np.asarray(o_st, np.float32))
            np.testing.assert_array_equal(np.asarray(nk[layer]),
                                          np.asarray(ck))
            np.testing.assert_array_equal(np.asarray(nv[layer]),
                                          np.asarray(cv))
            others = [i for i in range(3) if i != layer]
            np.testing.assert_array_equal(np.asarray(nk)[others],
                                          np.asarray(sk)[others])

    def test_window_and_softcap_fall_back_to_reference(self):
        """The kernel only implements linear buffers without softcap; the
        flag must be a no-op for ring-buffer / softcapped configs (hybrid
        smoke uses sliding windows) instead of producing wrong attention."""
        cfg = dataclasses.replace(get_smoke_config("recurrentgemma-2b"),
                                  use_pallas_decode=True)
        base = InferenceEngine(cfg_for("recurrentgemma-2b"), slots=2,
                               max_len=48)
        eng = InferenceEngine(cfg, params=base.params, slots=2, max_len=48)
        ref = InferenceEngine(base.cfg, params=base.params, slots=2,
                              max_len=48)
        prompt = np.arange(9, dtype=np.int32)
        eng.prefill_session("s", prompt)
        ref.prefill_session("s", prompt)
        assert eng.decode_round(steps=5)["s"] == ref.decode_round(steps=5)["s"]


class _TickClock:
    """now() advances a fixed amount per call — deterministic timing for
    EWMA accounting tests."""

    def __init__(self, tick_s):
        self.t = 0.0
        self.tick = tick_s

    def now(self):
        self.t += self.tick
        return self.t


class _StubEngine:
    """Captures prompts; emits fixed token blocks."""

    def __init__(self):
        self.cfg = get_config("edge-tiny")
        self.prompts = {}
        self._slot_map = {}

    def prefill_session(self, sid, prompt):
        self.prompts[sid] = np.asarray(prompt)
        self._slot_map[sid] = 0
        return {"first_token": 1, "ttfb_ms": 1.0}

    def decode_round(self, steps=None):
        k = steps or 1
        return {sid: ([2] * k if steps is not None else 2)
                for sid in self._slot_map}

    def free_slots(self):
        return 1

    def release_slot(self, sid):
        self._slot_map.pop(sid, None)


class TestBackendAccounting:
    def test_ewma_normalizes_by_tokens_not_calls(self):
        """A K-step chunk taking T ms must train the per-token EWMA toward
        T/K — NOT T/len(sessions) — so predicted_service_ms (EWMA × G) stays
        calibrated for deadline fast-fail at any chunk size."""
        eng = _StubEngine()
        eng._slot_map = {"a": 0, "b": 1, "c": 2}    # 3 sessions share rounds
        clock = _TickClock(0.008)                    # 8 ms between now() calls
        be = RealEngineBackend(eng, clock)
        be.decode_round(steps=8)
        assert be._ms_per_token == pytest.approx(1.0)    # 8ms / 8 steps
        req = Request("r", "s", "premium", 16, 100, 1e9)
        assert be.predicted_service_ms(req) == pytest.approx(100.0)

    def test_admit_prompt_seed_is_crc32_not_hash(self):
        """Synthetic prompts must derive from crc32 (stable across
        processes), never from PYTHONHASHSEED-dependent hash()."""
        import zlib
        eng = _StubEngine()
        be = RealEngineBackend(eng, _TickClock(0.001), seed=3)
        req = Request("req-1", "sess-1", "assured", 6, 4, 1e9)
        be.admit(req, 0.0)
        expected = np.random.default_rng(
            (zlib.crc32(b"sess-1") ^ zlib.crc32(b"req-1") ^ 3)
            % 2**31).integers(0, eng.cfg.vocab_size, size=6).astype(np.int32)
        np.testing.assert_array_equal(eng.prompts["sess-1"], expected)

    def test_engine_serve_seed_is_crc32(self):
        import zlib
        cfg = get_config("edge-tiny")
        eng = InferenceEngine(cfg, slots=2, max_len=64)
        out = eng.serve("det-session", prompt_tokens=6, gen_tokens=4)
        assert len(out["tokens"]) == 4
        # same crc32-derived prompt on a FRESH engine with the same weights
        eng2 = InferenceEngine(cfg, params=eng.params, slots=2, max_len=64)
        out2 = eng2.serve("det-session", prompt_tokens=6, gen_tokens=4)
        assert out["tokens"] == out2["tokens"]


class TestPlaneChunking:
    def _plane(self, chunk=None):
        from repro.core.clock import VirtualClock
        clock = VirtualClock()
        cfg = get_config("edge-tiny")
        eng = InferenceEngine(cfg, slots=4, max_len=64)
        return ServingPlane(clock, RealEngineBackend(eng, clock), slots=4,
                            site_id="t", decode_chunk=chunk)

    def test_chunk_respects_remaining_budget(self):
        """The fused chunk never overshoots any running request's token
        budget — completion accounting stays exact."""
        plane = self._plane()
        plane.submit(session_id="a", klass="best-effort", prompt_tokens=4,
                     gen_tokens=5, t_max_ms=1e9)
        plane.submit(session_id="b", klass="best-effort", prompt_tokens=4,
                     gen_tokens=20, t_max_ms=1e9)
        # a has 4 tokens left after prefill's first token
        assert plane._chunk_steps() == 4
        plane.drain()
        res = {r.session_id: r for r in plane.pop_results()}
        assert res["a"].tokens == 5 and res["b"].tokens == 20
        assert len(res["a"].token_ids) == 5
        assert len(res["b"].token_ids) == 20

    def test_backend_admit_failure_frees_scheduler_slot(self):
        """A backend that refuses admission (oversized prompt) must yield a
        failed PlaneResult and free the scheduler slot — never leave the
        request wedged in running."""
        from repro.core.failures import FailureCause
        plane = self._plane()   # engine max_len = 64
        plane.submit(session_id="big", klass="best-effort",
                     prompt_tokens=100, gen_tokens=4, t_max_ms=1e9,
                     prompt=np.arange(100, dtype=np.int32))
        assert not plane.scheduler.running
        assert plane.scheduler.queue_depth() == 0
        res = plane.pop_results()
        assert len(res) == 1
        assert res[0].failed is FailureCause.NO_FEASIBLE_BINDING
        # the plane still serves well-formed requests afterwards
        ok = plane.serve(session_id="ok", klass="best-effort",
                         prompt_tokens=8, gen_tokens=3, t_max_ms=1e9,
                         prompt=np.arange(8, dtype=np.int32))
        assert ok.completed and ok.tokens == 3

    def test_chunk_caps_at_highest_class_present(self):
        """Premium work (running OR queued) shrinks the chunk: the chunk is
        the preemption granularity premium TTFT rides on."""
        plane = self._plane(chunk={"premium": 2, "assured": 8,
                                   "best-effort": 32})
        plane.submit(session_id="be", klass="best-effort", prompt_tokens=4,
                     gen_tokens=64, t_max_ms=1e9)
        assert plane._chunk_steps() == 32
        # a queued premium request tightens the cap without being admitted
        plane.scheduler.queues["premium"].append(
            Request("rq", "p", "premium", 4, 8, 1e9))
        assert plane._chunk_steps() == 2
