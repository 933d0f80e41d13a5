"""NE-AIaaS serving launcher: real engines behind QoS-scheduled serving
planes, driven END-TO-END through the northbound session API.

    PYTHONPATH=src python -m repro.launch.serve --model edge-tiny \
        --sessions 4 --requests 12

Every session here is established, served, and released by a
:class:`~repro.api.client.SessionClient` speaking JSON to the
:class:`~repro.api.gateway.NorthboundGateway` — the exact wire surface a
remote application-service-provider would use. Each execution site runs
one single-device :class:`~repro.serving.engine.InferenceEngine` of the
served model, all on the process's first device (no serving code builds a
mesh yet). The AIS lifecycle, QoS-scheduled admission (class order +
premium reservation + deadline fast-fail), telemetry, and charging are the
same whatever the model size — that is the paper's point.
"""

from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from repro.api.client import SessionClient
from repro.configs import ARCH_IDS
from repro.core import Orchestrator, default_asp
from repro.core.asp import ASP, MobilityClass, QualityTier
from repro.core.clock import Clock
from repro.serving.server import AIaaSServer


@dataclass
class ServeReport:
    """What one ``serve()`` run did, as the invoker side saw it."""
    model_id: str                      # the model the site engines run
    sent: int = 0
    served: int = 0
    #: (request_id, Eq. 12 cause, refusal text) per failed or refused request
    failed: List[Tuple[str, str, str]] = field(default_factory=list)
    #: session_id -> bound "model@version" from the COMMIT record
    bound: Dict[str, str] = field(default_factory=dict)
    #: session_id -> anchor site from the COMMIT record
    anchors: Dict[str, str] = field(default_factory=dict)
    #: request_id -> generated token ids, per served request
    tokens: Dict[str, List[int]] = field(default_factory=dict)
    fast_failed: int = 0
    server: Optional[AIaaSServer] = None

    def mismatched(self) -> Dict[str, str]:
        """Sessions bound to a model other than the one the engines run."""
        return {s: m for s, m in self.bound.items()
                if m.split("@")[0] != self.model_id}


#: a request's whole-generation deadline in serve()
T_MAX_MS = 300_000.0


def hinted_asp(model: str, tier: QualityTier, t_max_ms: float = T_MAX_MS,
               *, mobility: MobilityClass = MobilityClass.STATIC) -> ASP:
    """Text-generation ASP that names ``model`` as its only fallback-ladder
    rung, with latency objectives scaled to ``t_max_ms``."""
    asp = default_asp(model, tier=tier, mobility=mobility)
    return dataclasses.replace(
        asp, objectives=dataclasses.replace(
            asp.objectives, ttfb_ms=t_max_ms / 10, p95_ms=t_max_ms / 3,
            p99_ms=t_max_ms / 2, t_max_ms=t_max_ms, nu_min=0.0))


def serve(model: str = "edge-tiny", *, sessions: int = 4, requests: int = 12,
          slots: int = 8, max_len: int = 192, gen_tokens: int = 8,
          t_max_ms: float = T_MAX_MS, seed: int = 0, quiet: bool = False,
          decode_chunk: int = 0, pallas_decode: bool = False,
          orch: Optional[Orchestrator] = None) -> ServeReport:
    """Serve ``requests`` synthetic requests over ``sessions`` AI Sessions.

    ``orch`` carries the deployment (catalog, sites, timers); by default
    the full catalog. Every ASP names ``model`` as its only fallback-ladder
    rung, so DISCOVER and PAGING bind each session to the model the site
    engines run, at a tier that model offers."""
    import numpy as np
    orch = orch if orch is not None else Orchestrator(clock=Clock())
    # decode_chunk > 0 overrides the per-class fused-chunk caps uniformly
    # (benchmarks / A-B runs); 0 keeps the QoS-adaptive defaults
    chunks = ({k: decode_chunk for k in ("premium", "assured", "best-effort")}
              if decode_chunk > 0 else None)
    server = AIaaSServer(orch, model, slots=slots, max_len=max_len,
                         decode_chunk=chunks, pallas_decode=pallas_decode)
    out = ServeReport(model_id=server.fleet.entry.model_id, server=server)
    top = server.fleet.entry.tier
    rng = np.random.default_rng(seed)

    clients = []
    for i in range(sessions):
        tier = min(QualityTier.PREMIUM if i % 2 == 0 else QualityTier.BASIC,
                   top)
        c = SessionClient(server.gateway, hinted_asp(model, tier, t_max_ms),
                          invoker=f"ue-{i}",
                          zone="zone-a").establish()
        clients.append(c)
        out.bound[c.session_id] = c.record["model"]
        out.anchors[c.session_id] = c.record["anchor"]
        if not quiet:
            print(f"AIS {c.session_id} tier={tier.name} "
                  f"model={c.record['model']} "
                  f"anchor={c.record['anchor']} qfi={c.record['qfi']}")

    # submit everything through the northbound API — admission order
    # (premium first, reserved share, fast-fail) is the site planes' job
    for r in range(requests):
        c = clients[r % len(clients)]
        rid = c.submit(prompt_tokens=int(rng.integers(8, 32)),
                       gen_tokens=gen_tokens)
        out.sent += 1
        if rid is None:
            out.failed.append((f"{c.session_id}/refused", "rejected",
                               "admission control refused the submit"))
    results = server.drain()
    for res in results.values():
        if res.failed is None:
            out.served += 1
            out.tokens[res.request_id] = list(res.token_ids or [])
        else:
            out.failed.append((res.request_id, res.failed.value,
                               res.detail))
    out.fast_failed = sum(p.scheduler.stats.fast_failed
                          for p in server.planes.values())

    for c in clients:
        rep = c.compliance()
        ack = c.release()
        if not quiet and rep.n:
            z = rep.z
            print(f"{c.session_id} q99={z['q99_ms']:9.1f}ms ρ̂={z['rho']:.2f} "
                  f"ν̂={z['nu_tokens_per_s']:7.1f} tok/s "
                  f"compliant={rep.in_compliance} cost={ack.total_cost:.4f}")
    if not quiet:
        print(f"served {out.served}/{requests} "
              f"(fast-failed {out.fast_failed} on deadline)")
        for rid, cause, detail in out.failed:
            print(f"  failed {rid}: {cause} {detail}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="edge-tiny", choices=ARCH_IDS)
    ap.add_argument("--sessions", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--gen-tokens", type=int, default=8)
    ap.add_argument("--decode-chunk", type=int, default=0,
                    help="uniform fused-decode chunk size "
                         "(0 = QoS-adaptive per-class defaults)")
    ap.add_argument("--pallas-decode", action="store_true",
                    help="route decode attention through the Pallas "
                         "flash-decode kernel (interpret mode off-TPU)")
    a = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rep = serve(a.model, sessions=a.sessions, requests=a.requests,
                slots=a.slots, gen_tokens=a.gen_tokens,
                decode_chunk=a.decode_chunk, pallas_decode=a.pallas_decode)
    if rep.failed or rep.mismatched():
        raise SystemExit(1)


if __name__ == "__main__":
    main()
