"""Drive the served path with a cell's traffic, as an invoker uses it.

Every session is established by a ``SessionClient`` over the JSON wire of the
server's ``NorthboundGateway`` (DISCOVER -> PAGE -> PREPARE -> COMMIT), every
turn is a JSON submit, and completions come back through the invoker's JSON
completion poll. Between submits the driver runs one continuous-batching
round on every site plane that has work (``ServingPlane._round``: the plane
has no public single-round step) and pumps the gateway, which admits queued
requests and records results.

The driver wraps its own instances, never the program's code, to time the
calls into each layer: host counters for the per-layer metrics, and
``jax.profiler.TraceAnnotation`` spans (``ais.establish``, ``ais.submit``,
``ais.heartbeat``, ``plane.round``, ``engine.admit``,
``engine.decode_round``) that a traced run lines up with the device trace.
Like an invoker holding sessions open, it heartbeats every live session
every ``RENEW_S`` seconds.
"""

from __future__ import annotations

import heapq
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from benchlib import traffic as T

#: the invoker: one application service provider whose end users' sessions
#: all arrive through it
INVOKER = "asp-bench"
#: how long after the window closes the driver waits for the answers due
#: in it; one that has not come by then is counted as failed
DRAIN_S = 60.0
#: how often the invoker heartbeats each live session: a third of the
#: default 30 s lease (``SessionClient`` renews only on a submit, from the
#: server time of its previous reply, so a session whose turns come 15 s
#: or more apart lapses without these)
RENEW_S = 10.0


@dataclass
class Req:
    session: int
    turn: int
    due: float                      # when the invoker meant to send it
    prompt: List[int]
    gen: int
    in_window: bool
    sent: Optional[float] = None
    rid: Optional[str] = None
    done: Optional[object] = None   # the ServeComplete
    failed: str = ""                # "<reason>: <detail>"

    @property
    def ok(self) -> bool:
        return self.done is not None and not self.failed \
            and not self.done.error_code

    def ttft_ms(self) -> float:
        d = self.done
        return (self.sent - self.due) * 1e3 + d.queue_wait_ms + d.ttfb_ms

    def tpot_ms(self) -> Optional[float]:
        d = self.done
        if d.tokens < 2:
            return None
        return (d.latency_ms - d.queue_wait_ms - d.ttfb_ms) / (d.tokens - 1)

    def finished_at(self) -> float:
        return self.sent + self.done.latency_ms / 1e3


@dataclass
class Call:
    """One timed call into a layer."""
    t0: float
    t1: float
    tokens: int = 0
    steps: int = 0
    slot_steps: int = 0          # active sequences x decode steps
    keys: int = 0                # cached positions attended, summed


@dataclass
class Counters:
    admits: List[Call] = field(default_factory=list)
    rounds: List[Call] = field(default_factory=list)
    establish: List[tuple] = field(default_factory=list)  # (due, t1, host_s)
    wire_s: float = 0.0             # host time in gateway JSON handling


class Session:
    def __init__(self, plan: T.SessionPlan):
        self.plan = plan
        self.client = None
        self.next_turn = 0
        self.dead = False
        self.renewed = 0.0


class Driver:
    def __init__(self, server, seed: int, vocab: int, asp_for, clock):
        self.server = server
        self.seed = seed
        self.vocab = vocab
        self.asp_for = asp_for
        self.clock = clock
        self.c = Counters()
        self.reqs: List[Req] = []
        self.by_rid: Dict[str, Req] = {}
        self.sessions: List[Session] = []
        self.anchors: Dict[str, int] = {}
        self._establishing = False
        self._est_wire = 0.0
        self._wrap()

    # -- instrumentation of the driver's own instances -----------------
    def _transport(self, payload):
        t0 = time.monotonic()
        out = self.server.gateway.handle_json(payload)
        dt = time.monotonic() - t0
        self.c.wire_s += dt
        if self._establishing:
            self._est_wire += dt
        return out

    def _wrap(self):
        for plane in self.server.planes.values():
            be, eng = plane.backend, plane.backend.engine

            def admit(req, now, _f=be.admit):
                t0 = time.monotonic()
                with TraceAnnotation("engine.admit"):
                    adm = _f(req, now)
                self.c.admits.append(Call(t0, time.monotonic(),
                                          tokens=0 if adm.resumed else 1))
                return adm

            def decode_round(steps=None, _f=be.decode_round, _e=eng):
                pos = [s.position for s in _e._slots
                       if s is not None and not s.parked]
                t0 = time.monotonic()
                with TraceAnnotation("engine.decode_round"):
                    out = _f(steps=steps)
                t1 = time.monotonic()
                k = max(1, int(steps or 1))
                call = Call(t0, t1, steps=k if out else 0)
                if out:
                    call.tokens = sum(len(v) if isinstance(v, list) else 1
                                      for v in out.values())
                    # step j of a slot at position p attends p + j + 1 keys
                    call.slot_steps = len(pos) * k
                    call.keys = k * sum(pos) + len(pos) * k * (k + 1) // 2
                self.c.rounds.append(call)
                return out

            def round_(_f=plane._round):
                with TraceAnnotation("plane.round"):
                    return _f()

            be.admit = admit
            be.decode_round = decode_round
            plane._round = round_

    # -- sessions --------------------------------------------------------
    def _client(self, tier: str):
        from repro.api.client import SessionClient
        return SessionClient(self.server.gateway, self.asp_for(tier),
                             invoker=INVOKER, zone="zone-a",
                             subscribe_events=False,
                             transport=self._transport, clock=self.clock)

    def establish(self, s: Session, due: float) -> bool:
        from repro.api.client import NorthboundError
        self._establishing, self._est_wire = True, 0.0
        try:
            with TraceAnnotation("ais.establish"):
                s.client = self._client(s.plan.tier).establish()
        except NorthboundError as e:
            s.dead = True
            s.error = f"establish: {e}"
            return False
        finally:
            self._establishing = False
        s.renewed = self.clock.now()
        self.c.establish.append((due, s.renewed, self._est_wire))
        a = s.client.record.get("anchor", "?")
        self.anchors[a] = self.anchors.get(a, 0) + 1
        return True

    def submit(self, s: Session, due: float, window) -> None:
        from repro.api.client import NorthboundError
        turn = s.plan.turns[s.next_turn]
        s.next_turn += 1
        prompt = T.prompt_ids(self.seed, s.plan.index, s.next_turn - 1,
                              turn.prompt_tokens, self.vocab)
        r = Req(s.plan.index, s.next_turn - 1, due, prompt, turn.gen_tokens,
                window[0] <= due < window[1])
        self.reqs.append(r)
        r.sent = self.clock.now()
        try:
            with TraceAnnotation("ais.submit"):
                r.rid = s.client.submit(prompt_tokens=len(prompt),
                                        gen_tokens=turn.gen_tokens,
                                        prompt=prompt)
        except NorthboundError as e:
            r.failed = f"submit: {e}"
            s.dead = True
            return
        if r.rid is None:
            r.failed = "refused: by admission control"
            return
        self.by_rid[r.rid] = r

    # -- the loop --------------------------------------------------------
    def _renew(self, now: float) -> None:
        from repro.api.client import NorthboundError
        for s in self.sessions:
            if s.client is not None and not s.dead \
                    and now - s.renewed >= RENEW_S:
                try:
                    with TraceAnnotation("ais.heartbeat"):
                        s.client.heartbeat()
                except NorthboundError as e:
                    s.dead, s.error = True, f"heartbeat: {e}"
                s.renewed = now

    def _busy(self) -> bool:
        return any(p.scheduler.running or p.scheduler.queue_depth()
                   for p in self.server.planes.values())

    def _step(self) -> List[Req]:
        """One round on every plane with work, then collect answers."""
        for plane in self.server.planes.values():
            if plane.scheduler.running:
                plane._round()
        self.server.gateway.pump(self.clock.now())
        done = []
        for msg in self.poller.completions():
            r = self.by_rid.pop(msg.request_id, None)
            if r is None:
                continue
            r.done = msg
            if msg.error_code:
                r.failed = f"{msg.error_code}: {msg.detail}"
            done.append(r)
        return done

    def run(self, warm_s: float, seconds: float, closed: bool, plans,
            on_open=None, on_close=None):
        """Serve the traffic; the window is [start + warm_s, start + warm_s
        + seconds), and ``on_open`` / ``on_close`` are called as it opens
        and closes. Returns (window open, window close)."""
        self.poller = self._client("basic")
        self.sessions = [Session(p) for p in plans]
        if closed:
            for s in self.sessions:
                self.establish(s, self.clock.now())
        start = self.clock.now()
        w = (start + warm_s, start + warm_s + seconds)
        self.window = w
        heap = []                   # (due, seq, session, needs establish)
        for i, s in enumerate(self.sessions):
            if not s.dead:
                heapq.heappush(heap, (start + s.plan.arrival_s, i, s,
                                      not closed))
        seq = len(self.sessions)
        opened = closed_ = False
        while True:
            now = self.clock.now()
            if not opened and now >= w[0]:
                opened = True
                if on_open:
                    on_open()
            if not closed_ and now >= w[1]:
                closed_ = True
                if on_close:
                    on_close()
            while heap and heap[0][0] <= now:
                due, _, s, new = heapq.heappop(heap)
                if new and not self.establish(s, due):
                    r = Req(s.plan.index, 0, due, [], 0,
                            w[0] <= due < w[1], sent=now,
                            failed=getattr(s, "error", "establish"))
                    self.reqs.append(r)
                    continue
                self.submit(s, due, w)
            for r in self._step():
                s = self.sessions[r.session]
                if s.dead:
                    continue
                if s.next_turn < len(s.plan.turns):
                    think = s.plan.turns[s.next_turn].think_s
                    at = r.finished_at() + think if r.ok else self.clock.now()
                    heapq.heappush(heap, (at, seq, s, False))
                    seq += 1
                elif not closed:
                    self._release(s)
            now = self.clock.now()
            self._renew(now)
            if now >= w[1]:
                pending = [r for r in self.reqs
                           if r.in_window and r.done is None and not r.failed]
                if not pending or now >= w[1] + DRAIN_S:
                    for r in pending:
                        r.failed = "drain: no answer within it"
                    break
            if not self._busy() and heap:
                time.sleep(min(max(heap[0][0] - now, 0.0), 0.05))
        return w

    def _release(self, s: Session) -> None:
        from repro.api.client import NorthboundError
        try:
            s.client.release()
        except NorthboundError:
            pass
        s.dead = True

    # -- what the window measured ----------------------------------------
    def window_requests(self) -> List[Req]:
        return [r for r in self.reqs if r.in_window]

    def window_calls(self, calls: List[Call]) -> List[Call]:
        lo, hi = self.window
        return [c for c in calls if lo <= c.t0 < hi]
