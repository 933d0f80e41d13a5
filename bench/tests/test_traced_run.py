"""A traced run whose profiler takes longer to stop than a session's lease
lasts: the window's requests must all be answered, as in an untraced run.

Writing the trace blocks the host. The harness stops the profiler after the
window's answers are in, not inside the serving loop, so no session goes
unheartbeated while it writes. This runs a cell cut to CPU size
(``small.py``) with the lease shrunk to 2 s, the heartbeat to every 0.5 s,
and ``jax.profiler.stop_trace`` blocking 3 s before it writes."""

import time

import jax
import pytest

from small import run_small, small_cell

WORKLOAD = "minitron-8b-4L.long-decode"
LEASE_S = 2.0
STOP_S = 3.0


@pytest.fixture
def short_leases(monkeypatch, tmp_path):
    """Sessions leased for ``LEASE_S``, heartbeated every 0.5 s, and a
    profiler that blocks ``STOP_S`` as it stops; returns the wall times at
    which the profiler stopped and the serving loop ended, and the sessions
    whose leases had lapsed when the loop ended."""
    import repro.core as core
    import run as harness
    from benchlib import drive
    from repro.core.failures import Timers

    base = core.Orchestrator
    marks = {}

    class ShortLeases(base):
        def __init__(self, *a, **kw):
            kw["timers"] = Timers(lease_s=LEASE_S, tau_mig=LEASE_S / 2)
            super().__init__(*a, **kw)

    def slow_stop(_f=jax.profiler.stop_trace):
        marks["stop"] = time.monotonic()
        time.sleep(STOP_S)
        return _f()

    def run(self, *a, _f=drive.Driver.run, **kw):
        try:
            return _f(self, *a, **kw)
        finally:
            marks["loop_end"] = time.monotonic()
            live = self.server.orch.sessions
            marks["lapsed"] = [s.client.session_id for s in self.sessions
                               if not live[s.client.session_id].committed()]

    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(core, "Orchestrator", ShortLeases)
    monkeypatch.setattr(drive, "RENEW_S", 0.5)
    monkeypatch.setattr(drive.Driver, "run", run)
    monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)
    return marks


@pytest.mark.parametrize("seed", [41, 42])
def test_slow_trace_write_lapses_no_session(short_leases, seed):
    res = run_small(WORKLOAD, seed=seed, trace=True)
    assert res["attempted"] > 0
    assert res["failed"] == 0, res["window"]
    assert res["window"]["failed_by"] == {}
    assert short_leases["lapsed"] == []
    # the profiler stopped after the serving loop had ended
    assert short_leases["stop"] >= short_leases["loop_end"]
    assert res["correct"], res["compared"]
    # the readings cover the window, not the drain after it; the device's
    # own readings need a chip, the host's are read here
    assert res["device"]["window_s"] == pytest.approx(3.0, rel=0.1)
    host = {m["name"] for m in small_cell(WORKLOAD).per_layer
            if m["source"] == "host_clock"}
    assert host and host <= set(res["metrics"])
