"""A run whose timed path is broken underneath must come out not correct.

Each test skips the harness's look for a chip and drives a whole run of a
cell cut to CPU size (``small.py``), with one fault planted in every site
engine after the server is built: the driver, the traffic, the window and
the comparison are the benchmark's own. The exchange between chips has no
fault to plant: every cell runs on one chip.
"""

import jax
import jax.numpy as jnp
import pytest

from small import run_small

CELLS = ["mamba2-1.3b.chat-burst", "minitron-8b-4L.long-decode"]


def _each_engine(server, patch):
    for eng in server.fleet._engines.values():
        patch(eng)


def state_unchanged(server):
    """The fused decode returns the cache it was given."""
    def patch(eng):
        f = eng._decode_fused

        def decode(params, cache, last, active, k):
            keep = jax.tree.map(jnp.copy, cache)
            _, toks = f(params, cache, last, active, k)
            return keep, toks
        eng._decode_fused = decode
    _each_engine(server, patch)


def half_batch(server):
    """Only half of the slots (the even ones) is decoded; the others repeat
    the token they were fed."""
    def patch(eng):
        f = eng._decode_fused

        def decode(params, cache, last, active, k):
            half = jnp.arange(active.shape[0]) % 2 == 0
            cache, toks = f(params, cache, last, active & half, k)
            return cache, jnp.where(half[:, None], toks, last[:, None])
        eng._decode_fused = decode
    _each_engine(server, patch)


def token_altered(server):
    """The first token of every fused chunk is altered where it is
    produced (the scan goes on from the true one)."""
    def patch(eng):
        f = eng._decode_fused
        vocab = eng.cfg.vocab_size

        def decode(params, cache, last, active, k):
            cache, toks = f(params, cache, last, active, k)
            return cache, toks.at[:, 0].set((toks[:, 0] + 1) % vocab)
        eng._decode_fused = decode
    _each_engine(server, patch)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    res = run_small(workload, seed=21)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_unanswered_requests_fail_by_reason(monkeypatch):
    """Answers lost once the window has closed: the requests still queued
    or running then get none within the drain, and each counts as failed,
    under the reason ``drain`` in the result's ``failed_by``."""
    from benchlib import drive

    def lossy(self, _f=drive.Driver._step):
        done = _f(self)
        if self.clock.now() < self.window[1]:
            return done
        for r in done:
            r.done, r.failed = None, ""
        return []

    monkeypatch.setattr(drive.Driver, "_step", lossy)
    monkeypatch.setattr(drive, "DRAIN_S", 0.5)
    res = run_small("minitron-8b-4L.long-decode", seed=23)
    assert res["failed"] > 0
    assert res["window"]["failed_by"] == {"drain": res["failed"]}
    assert not res["correct"]


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_caught(workload, fault):
    res = run_small(workload, seed=22, fault=fault)
    assert not res["correct"], res["compared"]
