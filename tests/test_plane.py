"""ServingPlane + QoSScheduler + closed analytics loop.

Covers the QoS-contract enforcement mechanics (premium reserved share,
strict class ordering, deadline fast-fail accounting), plane-level
mixed-class admission under VirtualClock, the plane-driven §V scenarios,
and the regression the refactor exists for: measured congestion (queue
depth / arrival rate) flowing from the serving plane through
``Orchestrator.heartbeat`` into ``Analytics`` and changing Eq. (14)
migration-trigger behavior.
"""

import numpy as np
import pytest

from repro.core import Orchestrator, default_asp
from repro.core.asp import MobilityClass
from repro.core.clock import VirtualClock
from repro.core.failures import FailureCause
from repro.core.migration import MigrationTriggers
from repro.serving.engine import (InferenceEngine, PagePoolExhausted,
                                  RequestRefused)
from repro.serving.plane import ServingPlane, SimulatedEngine
from repro.serving.scheduler import QoSScheduler, Request


def req(i, klass, *, t_max=10_000.0, gen=8, total_ms=None):
    return Request(f"r{i}", f"s{i}", klass, 16, gen, t_max,
                   hint_total_ms=total_ms)


class TestSchedulerContract:
    def test_premium_reserved_share_enforced(self):
        """Non-premium classes can NEVER occupy the reserved slots, even
        with an empty premium queue; premium can use the whole machine."""
        clock = VirtualClock()
        s = QoSScheduler(clock, slots=8, premium_reserved_frac=0.25)
        for i in range(12):
            s.submit(req(i, "best-effort"))
        batch = s.next_batch()
        assert len(batch) == 6                       # 2 of 8 held back
        for r in batch:
            s.complete(r.request_id)
        for i in range(20, 30):
            s.submit(req(i, "premium"))
        assert len(s.next_batch()) == 8              # premium takes all

    def test_strict_class_order_interleaved(self):
        clock = VirtualClock()
        s = QoSScheduler(clock, slots=3, premium_reserved_frac=0.0)
        s.submit(req(1, "best-effort"))
        s.submit(req(2, "assured"))
        s.submit(req(3, "premium"))
        s.submit(req(4, "premium"))
        assert [r.klass for r in s.next_batch()] == \
            ["premium", "premium", "assured"]

    def test_fast_fail_accounting_and_callback(self):
        clock = VirtualClock()
        s = QoSScheduler(clock, slots=2)
        dropped = []
        r1 = req(1, "premium", t_max=100.0)
        r2 = req(2, "premium", t_max=100_000.0)
        s.submit(r1)
        s.submit(r2)
        clock.advance(0.2)          # r1 has already waited 200 ms > T_max
        batch = s.next_batch(predicted_service_ms=50.0,
                             on_fast_fail=dropped.append)
        assert [r.request_id for r in batch] == ["r2"]
        assert r1.failed is FailureCause.DEADLINE_EXPIRY
        assert s.stats.fast_failed == 1 and dropped == [r1]

    def test_per_request_predicted_service(self):
        """A callable predictor fast-fails only the request whose OWN
        predicted work blows its deadline."""
        clock = VirtualClock()
        s = QoSScheduler(clock, slots=4)
        small = req(1, "premium", t_max=100.0, total_ms=50.0)
        big = req(2, "premium", t_max=100.0, total_ms=500.0)
        s.submit(small)
        s.submit(big)
        batch = s.next_batch(
            predicted_service_ms=lambda r: r.hint_total_ms)
        assert [r.request_id for r in batch] == ["r1"]
        assert big.failed is FailureCause.DEADLINE_EXPIRY


class TestPlaneVirtualTime:
    def mk(self, slots=2, **kw):
        clock = VirtualClock()
        plane = ServingPlane(clock, SimulatedEngine(clock), slots=slots,
                             site_id="t", **kw)
        return clock, plane

    def test_mixed_class_admission_order_under_load(self):
        """With the only slot busy, queued premium overtakes earlier-queued
        best-effort at the next slot release."""
        clock, plane = self.mk(slots=1, premium_reserved_frac=0.0)
        plane.submit(session_id="hold", klass="best-effort",
                     prompt_tokens=8, gen_tokens=4, t_max_ms=1e6,
                     hint_total_ms=100.0)
        plane.submit(session_id="late-be", klass="best-effort",
                     prompt_tokens=8, gen_tokens=4, t_max_ms=1e6,
                     hint_total_ms=10.0)
        plane.submit(session_id="prem", klass="premium",
                     prompt_tokens=8, gen_tokens=4, t_max_ms=1e6,
                     hint_total_ms=10.0)
        plane.drain()
        done = {r.session_id: r for r in plane.pop_results()}
        assert done["prem"].queue_wait_ms == pytest.approx(100.0)
        assert done["late-be"].queue_wait_ms == pytest.approx(110.0)
        assert all(r.completed for r in done.values())

    def test_queue_wait_measured_not_assumed(self):
        clock, plane = self.mk(slots=1)
        plane.submit(session_id="a", klass="premium", prompt_tokens=8,
                     gen_tokens=4, t_max_ms=1e6, hint_total_ms=250.0)
        plane.submit(session_id="b", klass="premium", prompt_tokens=8,
                     gen_tokens=4, t_max_ms=1e6, hint_total_ms=250.0)
        plane.drain()
        waits = {r.session_id: r.queue_wait_ms for r in plane.pop_results()}
        assert waits["a"] == pytest.approx(0.0)
        assert waits["b"] == pytest.approx(250.0)
        assert clock.now() == pytest.approx(0.5)

    def test_deadline_fast_fail_is_a_result(self):
        clock, plane = self.mk(slots=1)
        plane.submit(session_id="slow", klass="premium", prompt_tokens=8,
                     gen_tokens=4, t_max_ms=1e6, hint_total_ms=500.0)
        plane.submit(session_id="doomed", klass="premium", prompt_tokens=8,
                     gen_tokens=4, t_max_ms=100.0, hint_total_ms=200.0)
        plane.drain()
        res = {r.session_id: r for r in plane.pop_results()}
        assert res["doomed"].failed is FailureCause.DEADLINE_EXPIRY
        assert not res["doomed"].completed
        assert plane.scheduler.stats.fast_failed == 1
        assert res["slow"].completed

    def test_bounded_queue_rejects_and_accounts(self):
        clock, plane = self.mk(slots=1, max_queue=0)
        assert plane.submit(session_id="a", klass="premium", prompt_tokens=8,
                            gen_tokens=4, t_max_ms=1e6,
                            hint_total_ms=100.0) is not None
        assert plane.submit(session_id="b", klass="premium", prompt_tokens=8,
                            gen_tokens=4, t_max_ms=1e6,
                            hint_total_ms=100.0) is None
        assert plane.scheduler.stats.rejected == 1

    def test_load_snapshot(self):
        clock, plane = self.mk(slots=2)
        for i in range(6):
            clock.advance(0.01)
            plane.submit(session_id=f"s{i}", klass="premium",
                         prompt_tokens=8, gen_tokens=4, t_max_ms=1e6,
                         hint_total_ms=1000.0)
        load = plane.load()
        assert load.running == 2
        assert load.queue_depth == pytest.approx(4 / 2)
        assert load.arrival_rate > 0


class TestAnalyticsLoopClosed:
    """The refactor's acceptance criterion: Analytics.observe_site receives
    nonzero queue/arrival signals under load, and congestion changes
    migration-trigger behavior (heartbeat no longer reports zeros)."""

    def _orch_with_congested_anchor(self, backlog_per_slot):
        orch = Orchestrator(clock=VirtualClock())
        asp = default_asp(mobility=MobilityClass.NOMADIC)
        s = orch.establish(asp, "ue", "zone-a")
        site = orch.sites[s.binding.site_id]
        plane = orch.plane_for(site)
        # fill every slot, then pile `backlog_per_slot` waiting per slot
        n_queued = int(site.spec.decode_slots * (1 + backlog_per_slot))
        for i in range(n_queued):
            orch.clock.advance(1e-5)
            plane.submit(session_id=f"bg{i}", klass="premium",
                         prompt_tokens=128, gen_tokens=16, t_max_ms=1e9,
                         hint_total_ms=5e6)       # long-running: queue holds
        return orch, s, site

    def test_heartbeat_feeds_measured_congestion(self):
        orch, s, site = self._orch_with_congested_anchor(
            backlog_per_slot=2)
        orch.heartbeat(s, triggers=MigrationTriggers(1.1, 1.1))
        ctx = orch.analytics.site_context(site.spec.site_id)
        assert ctx.queue_depth > 0.0
        assert ctx.arrival_rate > 0.0

    def test_congestion_changes_migration_trigger(self):
        trig = MigrationTriggers(delta_l99=0.35, delta_ttfb=0.35)
        # idle anchor: no trigger
        orch = Orchestrator(clock=VirtualClock())
        s = orch.establish(default_asp(mobility=MobilityClass.NOMADIC),
                           "ue", "zone-a")
        orch.heartbeat(s, triggers=MigrationTriggers(1.1, 1.1))
        assert not orch.migrations.check_trigger(s, s.zone, trig)
        # same session shape, deeply congested anchor: heartbeat observes
        # the backlog and Eq. (14) fires
        orch2, s2, site2 = self._orch_with_congested_anchor(
            backlog_per_slot=40)
        for _ in range(4):          # EWMA warm-up
            orch2.heartbeat(s2, triggers=MigrationTriggers(1.1, 1.1))
        ctx = orch2.analytics.site_context(site2.spec.site_id)
        assert ctx.queue_depth > 1.0
        assert orch2.migrations.check_trigger(s2, s2.zone, trig)


class TestPlaneScenarios:
    @pytest.fixture(scope="class")
    def model(self):
        from repro.sim import LatencyModel, SimConfig
        return LatencyModel(SimConfig(n_requests=2000))

    def test_neaiaas_arm_runs_through_plane(self, model):
        from repro.sim import simulate_neaiaas
        r = simulate_neaiaas(0.95, model, ell99=400, t_max=1000)
        assert r.admitted_frac < 1.0          # admission rejected load
        assert r.violation_prob < 0.05        # served-and-failed stays low

    def test_multiclass_differentiation(self, model):
        from repro.sim import simulate_multiclass
        r = simulate_multiclass(0.95, model, n_requests=2000)
        prem = r.per_class["premium"]
        be = r.per_class["best-effort"]
        assert prem.p99_wait_ms < be.p99_wait_ms
        assert prem.p99_latency_ms < be.p99_latency_ms

    def test_bursty_arrivals_raise_tail_wait(self, model):
        from repro.sim import simulate_bursty
        flat = simulate_bursty(model, burst_factor=1.0, n_requests=2000)
        burst = simulate_bursty(model, burst_factor=5.0, n_requests=2000)
        assert burst.p99_wait_ms > flat.p99_wait_ms
        assert burst.completed_frac > 0.9

    def test_load_mobility_at_scale(self):
        from repro.sim import simulate_load_mobility
        r = simulate_load_mobility(n_sessions=10_000,
                                   requests_per_session=2)
        assert r.n_sessions == 10_000
        assert r.handovers > 100
        assert r.completed_frac > 0.95
        assert sum(r.per_site_served.values()) > 15_000


class TestPlaneRealEngine:
    """The same plane in front of a real continuous-batching engine."""

    @pytest.fixture(scope="class")
    def server(self):
        from repro.serving.server import AIaaSServer
        orch = Orchestrator(clock=VirtualClock())
        return AIaaSServer(orch, "edge-tiny", slots=4, max_len=96), orch

    def test_serve_through_plane_records_boundary(self, server):
        srv, orch = server
        s = orch.establish(default_asp(), "ue-a", "zone-a")
        r = orch.serve(s, prompt_tokens=12, gen_tokens=4)
        assert r.text_tokens == 4 and r.failed is None
        plane = srv.planes[s.binding.site_id]
        assert plane.scheduler.stats.completed == 1
        assert len(orch.telemetry[s.session_id]) == 1

    def test_batched_submit_drain_mixed_sessions(self, server):
        srv, orch = server
        a = orch.establish(default_asp(), "ue-b", "zone-a")
        b = orch.establish(default_asp(), "ue-c", "zone-a")
        for _ in range(2):
            srv.submit(a, prompt_tokens=8, gen_tokens=3)
            srv.submit(b, prompt_tokens=8, gen_tokens=3)
        results = srv.drain()
        mine = [r for r in results.values()
                if r.session_id in (a.session_id, b.session_id)]
        assert len(mine) == 4
        assert all(r.failed is None and r.tokens == 3 for r in mine)

    def test_request_serves_callers_prompt_tokens(self, server):
        """request() must generate from the SUPPLIED prompt and return the
        engine's real token ids (identical to driving the engine direct)."""
        srv, orch = server
        s = orch.establish(default_asp(), "ue-d", "zone-a")
        eng = srv.fleet.engine_for(s.binding.site_id)
        prompt = np.arange(9, dtype=np.int32)
        ref = InferenceEngine(eng.cfg, params=eng.params, slots=2,
                              max_len=96)
        pre = ref.prefill_session("ref", prompt)
        expect = [pre["first_token"]] + \
            [ref.decode_round()["ref"] for _ in range(3)]
        out = srv.request(s, prompt, gen_tokens=4)
        assert out["tokens"] == expect

    def test_migrated_session_can_still_be_served(self, server):
        """Regression: a make-before-break migration leaves the session's
        state in the target engine's slot map; subsequent plane requests
        must supersede it, not head-of-line block forever."""
        srv, orch = server
        s = orch.establish(default_asp(mobility=MobilityClass.VEHICULAR),
                           "ue-mig", "zone-a")
        eng = srv.fleet.engine_for(s.binding.site_id)
        eng.prefill_session(s.session_id, np.arange(7, dtype=np.int32))
        out = orch.migrations.migrate(s, "zone-a")
        assert out.migrated and s.committed()
        dst_eng = srv.fleet.engine_for(s.binding.site_id)
        assert s.session_id in dst_eng._slot_map    # migrated-in state
        r = orch.serve(s, prompt_tokens=8, gen_tokens=3)
        assert r.failed is None and r.text_tokens == 3
        # async path drains too
        srv.submit(s, prompt_tokens=8, gen_tokens=3)
        results = srv.drain()
        assert any(res.session_id == s.session_id and res.failed is None
                   for res in results.values())


class _FailingBackend:
    """A backend whose every admission raises ``exc``."""
    exclusive_sessions = True
    needs_service_hints = False

    def __init__(self, exc):
        self.exc = exc

    def predicted_service_ms(self, req):
        return 0.0

    def ensure_capacity(self, active_sessions):
        pass

    def admit(self, req, now):
        raise self.exc

    def decode_round(self, steps=None):
        return {}

    def release(self, session_id):
        pass


def _submit_one(plane):
    return plane.submit(session_id="s", klass="premium", prompt_tokens=8,
                        gen_tokens=4, t_max_ms=10_000.0)


class TestAdmissionFailures:
    @pytest.mark.parametrize("exc,cause", [
        (RequestRefused("prompt of 99 tokens exceeds engine max_len 64"),
         FailureCause.NO_FEASIBLE_BINDING),
        (PagePoolExhausted("page pool exhausted: need 2 pages, 0 free"),
         FailureCause.COMPUTE_SCARCITY),
    ], ids=["refused", "no-pages"])
    def test_designed_refusal_is_a_failed_result(self, exc, cause):
        """Refusals free the slot and carry their text to the invoker."""
        plane = ServingPlane(VirtualClock(), _FailingBackend(exc), slots=2)
        _submit_one(plane)
        (res,) = plane.pop_results()
        assert res.failed is cause and str(exc) in res.detail
        assert not plane.scheduler.running

    def test_device_fault_propagates(self):
        """A runtime, compiler or device-memory error is a fault of the
        site, not a busy site: it must not become a failed result."""
        err = RuntimeError("RESOURCE_EXHAUSTED: out of HBM")
        plane = ServingPlane(VirtualClock(), _FailingBackend(err), slots=2)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            _submit_one(plane)
        assert not plane.pop_results()
