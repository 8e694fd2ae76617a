"""The one traffic generator. A mix is a data file, ``traffic/<name>.json``;
this module turns it and a seed into the requests of one run.

Every mix is a fixed multiset. Lengths are the n evenly spaced quantiles of
the stated distribution, arrival gaps the quantiles of an exponential, and the
seed only orders them, pairs them and fills the token ids. So every seed
offers the same number of requests, the same prompt tokens and the same output
tokens inside the window; only the order differs. The order is stratified: of
any ``block`` consecutive draws one comes from each ``block``-th of the sorted
multiset, and the blocks are made up so that each sums to nearly the same, so
any stretch of a run holds the same work under every seed.

An open-loop run has three segments on the clock of the window's start,
``[-ramp, 0)``, ``[0, seconds)`` and ``[seconds, seconds + drain)``, each with
its own multiset: the window's is what is measured, the ramp's fills the
system first and the drain's keeps the load on while the window's requests
finish. ``burst`` makes the rate periodic: of every ``period_s`` the last
``burst_s`` run at ``factor`` times the calm rate, and ``rate_rps`` stays the
mean.

With ``sessions``, the requests are the turns of conversations, each in a
lane: a lane's turns resend its whole history and add one user turn, and after
``turns`` turns the lane starts a new conversation under the same tenant's
system prompt. Lanes start at staggered turn indices (lane l at turn ``l mod
turns``) so that every index is present from the window's first second. Open
loop: arrival i belongs to lane ``i mod lanes``, so which turn an arrival is
follows from its number alone and the window holds the same turn indices under
every seed. Closed loop: every caller is a lane (``clients`` = ``lanes``) and
sends its next turn when the last is answered, taking the turn's lengths from
the pool.
"""

from __future__ import annotations

import json
import math
import os
import random
from dataclasses import dataclass, field
from statistics import NormalDist

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    """``traffic/<name>.json``: the one file that says what a cell offers."""
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- multisets

def quantiles(dist: dict, n: int) -> list[int]:
    """The n evenly spaced quantiles of a length distribution, as whole
    numbers, ascending. ``lognormal``: median, sigma, clipped to [min, max].
    ``uniform``: [min, max]. ``fixed``: value."""
    ps = [(i + 0.5) / n for i in range(n)]
    kind = dist["kind"]
    if kind == "fixed":
        return [int(dist["value"])] * n
    if kind == "uniform":
        return [int(round(dist["min"] + p * (dist["max"] - dist["min"])))
                for p in ps]
    if kind == "lognormal":
        nd = NormalDist()
        mu = math.log(dist["median"])
        return [int(min(dist["max"], max(dist["min"], round(
            math.exp(mu + dist["sigma"] * nd.inv_cdf(p)))))) for p in ps]
    raise ValueError(f"unknown distribution kind {kind!r}")


def exp_gaps(n: int) -> list[float]:
    """The n evenly spaced quantiles of a unit exponential, scaled to sum to
    n exactly, ascending."""
    g = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    k = n / sum(g)
    return [x * k for x in g]


def stratified(values: list, block: int, rng: random.Random) -> list:
    """``values`` (ascending) in a seeded order in which every run of
    ``block`` consecutive items holds one item of each of ``block`` strata,
    and every such block sums to nearly the same.

    Which items share a block does not depend on the seed: block i takes the
    i-th item of every even stratum and the i-th from the top of every odd
    one, so a block that got the low end of one stratum got the high end of
    the next. The seed orders the blocks, and the items inside each block.
    A stretch of a run then holds the same work under every seed, to within
    the half block at each of its ends."""
    n = len(values)
    block = max(1, min(block, n))
    strata = [list(values[j * n // block:(j + 1) * n // block])
              for j in range(block)]
    for j in range(1, block, 2):
        strata[j].reverse()
    blocks = []
    for i in range(max(len(s) for s in strata)):
        row = [s[i] for s in strata if i < len(s)]
        rng.shuffle(row)
        blocks.append(row)
    rng.shuffle(blocks)
    return [v for row in blocks for v in row]


# ----------------------------------------------------------------- arrivals

def _cum_rate(mix: dict, t: float) -> float:
    """Expected arrivals in [0, t) (negative before 0)."""
    rate = mix["rate_rps"]
    b = mix.get("burst")
    if not b:
        return rate * t
    period, bs, fac = b["period_s"], b["burst_s"], b["factor"]
    calm = rate * period / ((period - bs) + fac * bs)
    whole, frac = divmod(t, period)
    in_calm = min(frac, period - bs)
    in_burst = max(0.0, frac - (period - bs))
    return whole * rate * period + calm * in_calm + calm * fac * in_burst


def _invert(mix: dict, target: float, lo: float, hi: float) -> float:
    """The time in [lo, hi] at which the cumulative rate reaches target
    (bisection: the cumulative rate is piecewise linear and increasing)."""
    for _ in range(60):
        mid = (lo + hi) / 2
        if _cum_rate(mix, mid) < target:
            lo = mid
        else:
            hi = mid
    return (lo + hi) / 2


def segment_arrivals(mix: dict, start: float, end: float, block: int,
                     rng: random.Random) -> list[float]:
    """Arrival times in [start, end): as many as the rate expects there
    (rounded), at the seeded order of the exponential's quantiles, stretched
    so that the last gap ends at the segment's end."""
    a, b = _cum_rate(mix, start), _cum_rate(mix, end)
    n = int(round(b - a))
    if n <= 0:
        return []
    gaps = stratified(exp_gaps(n + 1), block, rng)
    k = (b - a) / sum(gaps)
    out, s = [], a
    for g in gaps[:-1]:
        s += g * k
        out.append(_invert(mix, s, start, end))
    return out


# ----------------------------------------------------------------- requests

@dataclass
class Request:
    due: float  # seconds from the window's start; closed loop: order only
    segment: str  # ramp | window | drain | pool
    prompt_len: int  # new prompt tokens (a session turn: the user's turn)
    max_tokens: int
    lane: int = -1  # session lane, -1 without sessions
    turn: int = 0  # index of this turn in its conversation
    tenant: int = 0
    index: int = 0
    token_seed: int = 0


@dataclass
class Schedule:
    mix: dict
    requests: list = field(default_factory=list)

    def window(self) -> list:
        return [r for r in self.requests if r.segment == "window"]

    def totals(self, segment: str = "window") -> dict:
        rs = [r for r in self.requests if r.segment == segment]
        return {"requests": len(rs),
                "prompt_tokens": sum(r.prompt_len for r in rs),
                "output_tokens": sum(r.max_tokens for r in rs)}


def _lengths(mix: dict, n: int, block: int, rng: random.Random):
    p = stratified(quantiles(mix["prompt"], n), block, rng)
    o = stratified(quantiles(mix["output"], n), block, rng)
    return list(zip(p, o))


def build(mix: dict, seed: int, seconds: float) -> Schedule:
    """The requests of one run of ``seconds`` measured seconds."""
    rng = random.Random(seed)
    block = int(mix.get("block", 16))
    sched = Schedule(mix=mix)
    if mix["loop"] == "closed":
        if mix.get("sessions") and \
                mix["sessions"]["lanes"] != mix["clients"]:
            raise ValueError("a closed loop of sessions has one lane a caller")
        n = int(mix["pool_requests"])
        for i, (p, o) in enumerate(_lengths(mix, n, block, rng)):
            sched.requests.append(Request(
                due=float(i), segment="pool", prompt_len=p, max_tokens=o,
                index=i, token_seed=rng.getrandbits(48)))
        return sched
    ses = mix.get("sessions")
    segs = [("ramp", -float(mix["ramp_s"]), 0.0), ("window", 0.0, seconds),
            ("drain", seconds, seconds + float(mix["drain_s"]))]
    i = 0
    for name, a, b in segs:
        times = segment_arrivals(mix, a, b, block, rng)
        for t, (p, o) in zip(times, _lengths(mix, len(times), block, rng)):
            r = Request(due=t, segment=name, prompt_len=p, max_tokens=o,
                        index=i, token_seed=rng.getrandbits(48))
            if ses:
                lanes, turns = ses["lanes"], ses["turns"]
                r.lane = i % lanes
                r.tenant = r.lane % ses["tenants"]
                # lane l begins the run at turn (l mod turns) of a
                # conversation whose earlier turns the ramp replays
                r.turn = (r.lane + i // lanes) % turns
            sched.requests.append(r)
            i += 1
    return sched


def token_ids(token_seed: int, n: int, vocab: int) -> list[int]:
    rng = random.Random(token_seed)
    # ids from 2 up: 0 and 1 are the id tokenizer's bos and eos
    return [rng.randrange(2, vocab) for _ in range(n)]
