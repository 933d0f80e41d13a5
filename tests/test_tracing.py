"""The served path's spans, read back from a profiler trace on the CPU.

A small engine -> plane -> gateway flow runs under ``jax.profiler.trace``
into a temporary directory; the host events of the ``.xplane.pb`` it
writes must hold every span the program marks (``repro.obs``), nested as
the calls are, with stats equal to what the engine did. The invoker's own
spans (named as the benchmark harness names them) share the trace and its
clock.
"""

import gc
import glob
import os
import warnings

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.api.client import SessionClient
from repro.core import Orchestrator
from repro.core.asp import QualityTier
from repro.core.clock import Clock
from repro.launch.serve import hinted_asp
from repro.serving.server import AIaaSServer

MODEL = "edge-tiny"
#: (prompt tokens, prefill bucket): the third reuses the first's bucket
PROMPTS = ((20, 32), (9, 16), (20, 32))
GEN = 8                 # 1 from prefill, then fused chunks of 4, 2 and 1
CHUNKS = (4, 2, 1)

SPANS = {
    "gateway.handle", "gateway.pump",
    "orch.discover", "orch.page", "orch.prepare", "orch.commit",
    "orch.submit", "orch.heartbeat", "orch.record_results",
    "plane.admit", "plane.chunk", "plane.complete",
    "engine.prefill", "engine.prefill.sync", "engine.slot_install",
    "engine.decode", "engine.decode.inputs", "engine.decode.wait",
    "py.gc",
}
#: child -> the spans one of which must enclose it
PARENTS = {
    "engine.prefill.sync": ("engine.prefill",),
    "engine.slot_install": ("engine.prefill",),
    "engine.prefill": ("plane.admit",),
    "plane.admit": ("orch.submit", "plane.complete"),
    "engine.decode.inputs": ("engine.decode",),
    "engine.decode.wait": ("engine.decode",),
    "engine.decode": ("plane.chunk",),
    "plane.complete": ("plane.chunk",),
    "orch.discover": ("gateway.handle",),
    "orch.page": ("gateway.handle",),
    "orch.prepare": ("gateway.handle",),
    "orch.commit": ("gateway.handle",),
    "orch.submit": ("gateway.handle",),
    "orch.heartbeat": ("gateway.handle",),
    "orch.record_results": ("gateway.pump", "orch.heartbeat"),
}


def _flow():
    """Establish one session, submit the prompts, serve them round by
    round, heartbeat, collect; returns the generated token ids in order.
    The invoker's calls are wrapped in the harness's span names."""
    orch = Orchestrator(clock=Clock())
    server = AIaaSServer(orch, MODEL, slots=2, max_len=64)
    client = SessionClient(server.gateway,
                           hinted_asp(MODEL, QualityTier.BASIC),
                           invoker="ue-trace", zone="zone-a",
                           subscribe_events=False)
    with TraceAnnotation("ais.establish"):
        client.establish()
    rng = np.random.default_rng(0)
    rids = []
    for n, _ in PROMPTS:
        prompt = [int(t) for t in rng.integers(0, 256, n)]
        with TraceAnnotation("ais.submit"):
            rids.append(client.submit(prompt_tokens=n, gen_tokens=GEN,
                                      prompt=prompt))
    done = {}
    planes = server.planes.values()
    while any(p.scheduler.running or p.scheduler.queue_depth()
              for p in planes):
        for p in planes:
            if p.scheduler.running:
                with TraceAnnotation("plane.round"):
                    p._round()
        server.gateway.pump(orch.clock.now())
        done.update((c.request_id, c.token_ids)
                    for c in client.completions())
    with TraceAnnotation("ais.heartbeat"):
        client.heartbeat()
    gc.collect()
    client.release()
    assert set(done) == set(rids)
    return [done[r] for r in rids]


def _host_events(directory):
    """[(start_ns, end_ns, name, stats)] of every host event."""
    f = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                         recursive=True))[-1]
    out = []
    with warnings.catch_warnings():
        # reading stats warns of a builtin type without __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(f).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    out.extend((e.start_ns, e.end_ns, e.name, dict(e.stats))
                               for e in line.events)
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    quiet = jax.profiler.ProfileOptions()
    quiet.python_tracer_level = 0       # spans only, not every Python call
    with jax.profiler.trace(d, profiler_options=quiet):
        tokens = _flow()
    return tokens, [e for e in _host_events(d)
                    if e[2] in SPANS or e[2].startswith("ais.")
                    or e[2] == "plane.round"]


def _named(events, name):
    return [e for e in events if e[2] == name]


def _inside(child, parents) -> bool:
    return any(p[0] <= child[0] and child[1] <= p[1] for p in parents)


def test_every_span_is_written(traced):
    _, ev = traced
    assert SPANS <= {e[2] for e in ev}


def test_children_nest_inside_their_parents(traced):
    _, ev = traced
    for child, parents in PARENTS.items():
        outer = [e for e in ev if e[2] in parents]
        for c in _named(ev, child):
            assert _inside(c, outer), (child, c)


def test_program_spans_nest_inside_the_invokers_spans(traced):
    """One clock: the invoker's spans enclose the program's."""
    _, ev = traced
    for name, outer in (("orch.submit", "ais.submit"),
                         ("orch.discover", "ais.establish"),
                         ("orch.commit", "ais.establish"),
                         ("orch.heartbeat", "ais.heartbeat"),
                         ("plane.chunk", "plane.round")):
        spans = _named(ev, name)
        assert spans and all(_inside(s, _named(ev, outer)) for s in spans)


def test_admit_and_prefill_carry_the_same_session(traced):
    _, ev = traced
    admits = _named(ev, "plane.admit")
    assert len(admits) == len(PROMPTS)
    for a in admits:
        inner = [p for p in _named(ev, "engine.prefill") if _inside(p, [a])]
        assert len(inner) == 1
        assert inner[0][3]["sid"] == a[3]["sid"]
        assert a[3]["rid"].startswith(a[3]["sid"])


def test_stats_are_what_the_engine_did(traced):
    _, ev = traced
    pre = sorted(_named(ev, "engine.prefill"))
    seen = set()
    want = []
    for n, bucket in PROMPTS:
        want.append((n, bucket, int(bucket not in seen)))
        seen.add(bucket)
    assert [(p[3]["tokens"], p[3]["bucket"], p[3]["first"])
            for p in pre] == want
    dec = sorted(_named(ev, "engine.decode"))
    assert [d[3]["steps"] for d in dec] == list(CHUNKS) * len(PROMPTS)
    assert [d[3]["first"] for d in dec] == \
        [1] * len(CHUNKS) + [0] * len(CHUNKS) * (len(PROMPTS) - 1)
    chunks = sorted(_named(ev, "plane.chunk"))
    assert [c[3]["steps"] for c in chunks] == [d[3]["steps"] for d in dec]
    gen = {g[3]["gen"] for g in _named(ev, "py.gc")}
    assert 2 in gen                  # the flow's own gc.collect()
    assert {h[3]["type"] for h in _named(ev, "gateway.handle")} >= {
        "discover_request", "page_request", "prepare_request",
        "commit_request", "serve_request", "heartbeat_report",
        "completion_poll"}


def test_tokens_are_the_same_without_the_profiler(traced):
    tokens, _ = traced
    assert tokens == _flow()
