"""Property tests for the migration data plane (hypothesis; the offline
stub from tests/_hypothesis_stub.py supplies a deterministic API-compatible
fallback — see conftest.py).

Properties (§IV-B continuity claim, Eq. 14):

* a migrate → migrate-back round trip preserves the state fingerprint and
  the cache position for all three payload families (dense KV, hybrid
  RG-LRU, SSM);
* ``interruption_ms == 0`` for EVERY successful make-before-break outcome,
  across random context shapes — on the real engine path and the
  VirtualClock simulation arm alike;
* an export → hibernate → resume round trip through the host tier
  preserves the state fingerprint and continues the token stream
  bit-exactly against an uninterrupted twin, for all three families.
"""

import itertools

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config, get_smoke_config
from repro.core import Orchestrator, default_asp
from repro.core.asp import MobilityClass
from repro.core.clock import VirtualClock
from repro.serving import state_transfer
from repro.serving.engine import InferenceEngine

FAMILIES = {
    "edge-tiny": "dense",
    "recurrentgemma-2b": "hybrid",
    "mamba2-1.3b": "ssm",
}

_uid = itertools.count()

# module-level lazy caches (the hypothesis stub's @given wrapper takes no
# pytest fixtures; engines/servers are expensive, so build each once)
_PAIRS = {}
_SERVER = []


def engine_pair(arch):
    """One (src, dst) engine pair per payload family, shared weights."""
    if arch not in _PAIRS:
        cfg = get_config(arch) if arch == "edge-tiny" \
            else get_smoke_config(arch)
        src = InferenceEngine(cfg, slots=2, max_len=64)
        dst = InferenceEngine(cfg, params=src.params, slots=2, max_len=64)
        _PAIRS[arch] = (src, dst)
    return _PAIRS[arch]


def real_server():
    if not _SERVER:
        from repro.serving.server import AIaaSServer
        orch = Orchestrator(clock=VirtualClock())
        # per-token decode chunks: the mid-stream property drives _round()
        # by hand and must catch the session before its budget completes
        chunk = {"premium": 1, "assured": 1, "best-effort": 1}
        _SERVER.append((AIaaSServer(orch, "edge-tiny", slots=4, max_len=96,
                                    decode_chunk=chunk),
                        orch))
    return _SERVER[0]


class TestRoundTripFingerprint:
    @settings(max_examples=5, deadline=None)
    @given(arch=st.sampled_from(sorted(FAMILIES)),
           prompt_len=st.integers(min_value=4, max_value=20),
           rounds=st.integers(min_value=0, max_value=5))
    def test_migrate_and_back_preserves_state(self, arch,
                                              prompt_len, rounds):
        src, dst = engine_pair(arch)
        sid = f"rt-{next(_uid)}"
        src.prefill_session(sid, np.arange(prompt_len, dtype=np.int32))
        for _ in range(rounds):
            src.decode_round()
        payload0 = src.export_slot(sid)
        fp0 = state_transfer.fingerprint(payload0)
        pos0 = payload0["position"]

        # migrate out ...
        meta = state_transfer.transfer(src, dst, sid)
        assert meta["fingerprint"] == fp0
        src.release_slot(sid)                    # the MBB break
        # ... and back
        meta_back = state_transfer.transfer(dst, src, sid)
        dst.release_slot(sid)

        payload1 = src.export_slot(sid)
        assert state_transfer.fingerprint(payload1) == fp0
        assert meta_back["fingerprint"] == fp0
        assert payload1["position"] == pos0
        assert payload1["last_token"] == payload0["last_token"]
        src.release_slot(sid)

    @settings(max_examples=6)
    @given(prompt=st.integers(min_value=16, max_value=256),
           gen=st.integers(min_value=4, max_value=48))
    def test_sim_round_trip_preserves_state(self, prompt, gen):
        """The SimulatedEngine arm: migrate twice (away and onward); the
        serialized session state is invariant under transfer."""
        orch = Orchestrator(clock=VirtualClock())
        s = orch.establish(default_asp(mobility=MobilityClass.VEHICULAR),
                           invoker=f"prop-{next(_uid)}", zone="zone-a")
        orch.serve(s, prompt_tokens=prompt, gen_tokens=gen)
        backend = orch.plane_for(orch.sites[s.binding.site_id]).backend
        payload0 = backend.export_slot(s.session_id)
        fp0 = state_transfer.fingerprint(payload0)
        for _ in range(2):
            out = orch.migrations.migrate(s, "zone-a")
            assert out.migrated
            assert out.fingerprint == fp0
        backend = orch.plane_for(orch.sites[s.binding.site_id]).backend
        payload1 = backend.export_slot(s.session_id)
        assert state_transfer.fingerprint(payload1) == fp0
        assert payload1["position"] == payload0["position"]


_HIB = {}


def hib_engine(arch):
    """One hibernation-capable engine per family (paged where the family
    supports it) plus an uninterrupted dense twin sharing its weights —
    the bit-exactness oracle for resumed token streams."""
    if arch not in _HIB:
        cfg = get_config(arch) if arch == "edge-tiny" \
            else get_smoke_config(arch)
        eng = InferenceEngine(cfg, slots=2, max_len=64,
                              paged=(arch == "edge-tiny"), page_size=16,
                              hibernation=True)
        twin = InferenceEngine(cfg, params=eng.params, slots=2, max_len=64)
        _HIB[arch] = (eng, twin)
    return _HIB[arch]


class TestHibernateRoundTrip:
    @settings(max_examples=6, deadline=None)
    @given(arch=st.sampled_from(sorted(FAMILIES)),
           prompt_len=st.integers(min_value=4, max_value=20),
           pre_rounds=st.integers(min_value=0, max_value=4),
           post_rounds=st.integers(min_value=1, max_value=5))
    def test_hibernate_resume_is_transparent(self, arch, prompt_len,
                                             pre_rounds, post_rounds):
        """Hibernating to host and resuming is invisible to the stream:
        same fingerprint on re-import, and the continued tokens match an
        identical session that never left the device."""
        eng, twin = hib_engine(arch)
        sid = f"hib-{next(_uid)}"
        r0 = eng.prefill_session(sid, np.arange(prompt_len, dtype=np.int32))
        r1 = twin.prefill_session(sid, np.arange(prompt_len, dtype=np.int32))
        assert r0["first_token"] == r1["first_token"]
        for _ in range(pre_rounds):
            assert eng.decode_round()[sid] == twin.decode_round()[sid]

        fp0 = state_transfer.fingerprint(eng.export_slot(sid))
        eng.hibernate_slot(sid)
        assert not eng.has_slot(sid) and eng.hibernation.has(sid)
        assert eng.bound_sessions() == eng.hibernated_sessions() + \
            eng.resident_sessions()
        eng.resume_slot(sid)
        assert state_transfer.fingerprint(eng.export_slot(sid)) == fp0
        assert not eng.hibernation.has(sid)      # dropped after re-import

        for _ in range(post_rounds):
            assert eng.decode_round()[sid] == twin.decode_round()[sid]
        eng.release_slot(sid)
        twin.release_slot(sid)


class TestZeroInterruption:
    @settings(max_examples=8)
    @given(prompt=st.integers(min_value=16, max_value=1024),
           gen=st.integers(min_value=1, max_value=128))
    def test_successful_mbb_never_gaps(self, prompt, gen):
        """Every successful make-before-break outcome has zero contract-gap
        time, whatever the served context shape."""
        orch = Orchestrator(clock=VirtualClock())
        s = orch.establish(default_asp(mobility=MobilityClass.VEHICULAR),
                           invoker=f"gap-{next(_uid)}", zone="zone-a")
        orch.serve(s, prompt_tokens=prompt, gen_tokens=gen)
        out = orch.migrations.migrate(s, "zone-a")
        if out.migrated:
            assert out.interruption_ms == 0.0
            assert s.committed() and s.binding.site_id == out.to_site
        else:
            # aborts never gap either: the source binding stays committed
            assert out.interruption_ms == 0.0
            assert s.committed() and s.binding.site_id == out.from_site

    @settings(max_examples=4, deadline=None)
    @given(pre_rounds=st.integers(min_value=0, max_value=4),
           gen=st.integers(min_value=8, max_value=16))
    def test_real_engine_mid_stream_never_gaps(self, pre_rounds, gen):
        """Real-engine arm: mid-decode migration keeps interruption at 0 and
        the stream completes with the full token budget on the target."""
        srv, orch = real_server()
        s = orch.establish(default_asp(mobility=MobilityClass.VEHICULAR),
                           invoker=f"real-{next(_uid)}", zone="zone-a")
        plane = srv.planes[s.binding.site_id]
        srv.submit(s, prompt=np.arange(6, dtype=np.int32), gen_tokens=gen)
        for _ in range(pre_rounds):
            plane._round()
        out = orch.migrations.migrate(s, "zone-a")
        assert out.migrated
        assert out.interruption_ms == 0.0
        dst_plane = srv.planes[s.binding.site_id]
        dst_plane.drain()
        results = orch.record_results(orch.sites[s.binding.site_id])
        mine = [r for r in results if r.session_id == s.session_id]
        assert len(mine) == 1 and mine[0].tokens == gen
        orch.release(s)
