"""Serving plane: continuous batching, QoS scheduler, state transfer."""

import os

import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core.clock import VirtualClock
from repro.core.failures import FailureCause
from repro.serving.engine import InferenceEngine
from repro.serving.scheduler import QoSScheduler, Request
from repro.serving import state_transfer


@pytest.fixture(scope="module")
def engine():
    return InferenceEngine(get_config("edge-tiny"), slots=4, max_len=96)


class TestEngine:
    def test_staggered_sessions_independent(self, engine):
        """Continuous batching with per-slot positions: a session's output
        must not depend on who shares the batch."""
        cfg = engine.cfg
        p1 = np.arange(10, dtype=np.int32)
        # run s-alone: fresh engine, single session
        solo = InferenceEngine(cfg, params=engine.params, slots=4, max_len=96)
        solo.prefill_session("s", p1)
        toks_solo = [solo.decode_round()["s"] for _ in range(6)]

        shared = InferenceEngine(cfg, params=engine.params, slots=4,
                                 max_len=96)
        shared.prefill_session("other", np.arange(23, dtype=np.int32))
        shared.decode_round()
        shared.prefill_session("s", p1)        # joins mid-flight
        toks_shared = []
        for _ in range(6):
            out = shared.decode_round()
            toks_shared.append(out["s"])
        assert toks_solo == toks_shared

    def test_slot_exhaustion_is_lease_bug(self, engine):
        eng = InferenceEngine(engine.cfg, params=engine.params, slots=2,
                              max_len=64)
        eng.prefill_session("a", np.arange(5, dtype=np.int32))
        eng.prefill_session("b", np.arange(5, dtype=np.int32))
        with pytest.raises(RuntimeError):
            eng.prefill_session("c", np.arange(5, dtype=np.int32))

    @pytest.mark.parametrize("arch", ["edge-tiny", "recurrentgemma-2b",
                                      "mamba2-1.3b", "mixtral-8x7b"])
    def test_transfer_roundtrip_all_families(self, arch):
        cfg = get_smoke_config(arch) if arch != "edge-tiny" \
            else get_config(arch)
        src = InferenceEngine(cfg, slots=2, max_len=48)
        src.prefill_session("m", np.arange(9, dtype=np.int32))
        src_next = None
        dst = InferenceEngine(cfg, params=src.params, slots=2, max_len=48)
        meta = state_transfer.transfer(src, dst, "m")
        assert meta["bytes"] > 0
        # both engines continue identically after the transfer
        for _ in range(4):
            a = src.decode_round()["m"]
            b = dst.decode_round()["m"]
            assert a == b

    def test_transfer_failure_keeps_source(self):
        cfg = get_config("edge-tiny")
        src = InferenceEngine(cfg, slots=2, max_len=48)
        src.prefill_session("m", np.arange(9, dtype=np.int32))
        dst = InferenceEngine(cfg, params=src.params, slots=2, max_len=48)

        def boom(payload):
            raise IOError("wire cut")

        with pytest.raises(IOError):
            state_transfer.transfer(src, dst, "m", fail_injector=boom)
        assert "m" in src._slot_map          # source slot intact
        assert "m" not in dst._slot_map


class TestScheduler:
    def mk(self, clock, **kw):
        return QoSScheduler(clock, slots=4, **kw)

    def req(self, i, klass, t_max=1000.0):
        return Request(f"r{i}", f"s{i}", klass, 16, 8, t_max)

    def test_strict_class_order(self):
        clock = VirtualClock()
        s = self.mk(clock)
        s.submit(self.req(1, "best-effort"))
        s.submit(self.req(2, "premium"))
        s.submit(self.req(3, "assured"))
        batch = s.next_batch()
        assert [r.klass for r in batch[:3]] == ["premium", "assured",
                                                "best-effort"]

    def test_premium_reservation(self):
        clock = VirtualClock()
        s = self.mk(clock)    # 4 slots, 1 reserved for premium
        for i in range(6):
            s.submit(self.req(i, "best-effort"))
        batch = s.next_batch()
        assert len(batch) == 3           # one slot held back
        s.submit(self.req(99, "premium"))
        batch2 = s.next_batch()
        assert [r.klass for r in batch2] == ["premium"]

    def test_deadline_fast_fail(self):
        clock = VirtualClock()
        s = self.mk(clock)
        r = self.req(1, "premium", t_max=100.0)
        s.submit(r)
        clock.advance(0.2)               # 200 ms queued already
        batch = s.next_batch(predicted_service_ms=50.0)
        assert batch == []
        assert r.failed is FailureCause.DEADLINE_EXPIRY
        assert s.stats.fast_failed == 1

    def test_completion_accounting(self):
        clock = VirtualClock()
        s = self.mk(clock)
        s.submit(self.req(1, "premium"))
        batch = s.next_batch()
        s.complete(batch[0].request_id)
        assert s.stats.completed == 1
        assert not s.running


class TestLauncher:
    def test_serve_binds_sessions_to_the_served_model(self):
        """Every session of serve() binds the model its site engines run
        (the ASP names it as the only ladder rung), even where the
        alternating session tier asks for more than the model offers."""
        from repro.launch.serve import serve
        rep = serve("edge-tiny", sessions=2, requests=2, slots=2,
                    max_len=64, gen_tokens=2, quiet=True)
        assert rep.model_id == "edge-tiny"
        assert set(rep.bound.values()) == {"edge-tiny@1.0"}
        assert not rep.mismatched() and not rep.failed
        assert rep.served == rep.sent == 2
        assert all(len(t) == 2 for t in rep.tokens.values())

    def test_compile_cache_dir_is_fixed_or_the_environments(self,
                                                            monkeypatch):
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        before = jax.config.jax_compilation_cache_dir
        try:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
            assert enable_compile_cache() == "/elsewhere"
            assert jax.config.jax_compilation_cache_dir == before
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            path = enable_compile_cache()
            repo = os.path.dirname(os.path.dirname(os.path.abspath(
                __file__)))
            assert path == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            assert enable_compile_cache("/srv/aiaas") == \
                os.path.join("/srv/aiaas", ".jax_cache")
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

