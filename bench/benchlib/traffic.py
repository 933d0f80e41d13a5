"""One general generator for every traffic mix: a mix is a file of
parameters (``bench/traffic/<name>.json``), never code.

Every seed gets the same set of sizes, think times and arrival gaps in
another order: each quantity is drawn in blocks, and every block is a
shuffled copy of the same stratified quantiles of its distribution. So two
seeds put the same work into a window and differ only in its order, and the
spread between runs is the system's, not the sampler's.

Open loop (``"loop": "open"``): AI Sessions arrive on a Markov-modulated
schedule (a calm and a burst state alternating, exponential sojourns, the
burst state at ``burst_factor`` times the calm rate, as in the bursty
scenario of ``sim/scenarios.py``); each session sends its turns one after
another, each after the reply to the last plus a think time.
Closed loop (``"loop": "closed"``): ``clients_per_slot * slots`` clients,
each one long-lived session that sends its next turn as soon as the last
completes.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

#: values per stratified block
BLOCK = 32


def quantile(dist: dict, p: float) -> float:
    kind = dist["dist"]
    if kind == "uniform_int":
        lo, hi = dist["min"], dist["max"]
        return float(min(hi, lo + math.floor(p * (hi - lo + 1))))
    if kind == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(p))
        return float(min(max(round(v), dist["min"]), dist["max"]))
    if kind == "exponential":
        return -dist["mean"] * math.log(1.0 - p)
    if kind == "fixed":
        return float(dist["value"])
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(dist: dict, n: int, rng, block: int = BLOCK) -> np.ndarray:
    """``n`` draws: blocks of the same ``block`` stratified quantiles, each
    block shuffled by ``rng``."""
    base = np.array([quantile(dist, (i + 0.5) / block) for i in range(block)])
    out = [base[rng.permutation(block)] for _ in range(-(-n // block))]
    return np.concatenate(out)[:n] if out else np.zeros(0)


def mean_of(dist: dict) -> float:
    return float(np.mean(stratified(dist, 4096, np.random.default_rng(0),
                                    block=4096)))


@dataclass
class Turn:
    prompt_tokens: int
    gen_tokens: int
    think_s: float               # pause after the previous reply (open loop)


@dataclass
class SessionPlan:
    index: int
    arrival_s: float             # offset from the start of the traffic
    tier: str
    turns: List[Turn]


def _mmpp_arrivals(p: dict, rate: float, horizon: float, rng) -> List[float]:
    """Session arrival offsets in [0, horizon): calm and burst periods
    alternate with stratified exponential lengths; each period gets its
    share of the expected arrivals (cumulative rounding, so the count over
    the horizon is fixed), placed uniformly at random inside it."""
    a = p["arrivals"]
    bf, mb, mc = a["burst_factor"], a["mean_burst_s"], a["mean_calm_s"]
    calm_rate = rate * (mb + mc) / (mc + bf * mb)
    n_per = int(math.ceil(horizon / (mb + mc))) + 2
    calm = stratified({"dist": "exponential", "mean": mc}, n_per, rng, 8)
    burst = stratified({"dist": "exponential", "mean": mb}, n_per, rng, 8)
    out, t, expected, placed = [], 0.0, 0.0, 0
    start_burst = bool(rng.integers(2))
    for i in range(n_per):
        order = ((burst[i], bf), (calm[i], 1.0))
        for dur, f in (order if start_burst else order[::-1]):
            expected += calm_rate * f * dur
            k = int(round(expected)) - placed
            placed += k
            out.extend(t + np.sort(rng.uniform(0.0, dur, k)))
            t += dur
        if t >= horizon:
            break
    return [x for x in out if x < horizon]


def open_loop(p: dict, seed: int, horizon: float) -> List[SessionPlan]:
    rng = np.random.default_rng([seed, 1])
    turns_mean = mean_of(p["turns"])
    arrivals = _mmpp_arrivals(p, p["request_rate_per_s"] / turns_mean,
                              horizon, rng)
    n = len(arrivals)
    nturns = stratified(p["turns"], n, rng).astype(int)
    total = int(nturns.sum())
    prompts = stratified(p["prompt_tokens"], total, rng).astype(int)
    outputs = stratified(p["output_tokens"], total, rng).astype(int)
    thinks = stratified(p["think_s"], total, rng)
    tiers = p["tiers"]
    plans, j = [], 0
    for i, (t, k) in enumerate(zip(arrivals, nturns)):
        turns = [Turn(int(prompts[j + q]), int(outputs[j + q]),
                      0.0 if q == 0 else float(thinks[j + q]))
                 for q in range(k)]
        j += k
        plans.append(SessionPlan(i, float(t), tiers[i % len(tiers)], turns))
    return plans


def closed_loop(p: dict, seed: int, slots: int,
                turns_per_client: int = 48) -> List[SessionPlan]:
    """Each client's turn r comes from round r, and every round is a
    shuffled copy of the same stratified sizes across the clients."""
    rng = np.random.default_rng([seed, 2])
    clients = int(p["clients_per_slot"] * slots)
    tiers = p["tiers"]
    rounds_p = [stratified(p["prompt_tokens"], clients, rng, clients)
                for _ in range(turns_per_client)]
    rounds_o = [stratified(p["output_tokens"], clients, rng, clients)
                for _ in range(turns_per_client)]
    return [SessionPlan(c, 0.0, tiers[c % len(tiers)],
                        [Turn(int(rounds_p[r][c]), int(rounds_o[r][c]), 0.0)
                         for r in range(turns_per_client)])
            for c in range(clients)]


def prompt_ids(seed: int, session: int, turn: int, n: int,
               vocab: int) -> List[int]:
    """The token ids of one prompt, fixed by the seed."""
    rng = np.random.default_rng([seed, 3, session, turn])
    return rng.integers(0, vocab, size=n).tolist()
