"""The traffic builder: a fixed multiset that the seed only orders."""

import collections
import random

import pytest

import traffic

SEEDS = (1, 3000000019)
# the cells' own mixes, and two open-loop ones made from them: the generator
# keeps the open loop for the cells PERF.md lists for later
_OPEN = {"loop": "open", "rate_rps": 8.0, "ramp_s": 10, "drain_s": 25,
         "burst": {"period_s": 10, "burst_s": 2, "factor": 3}}
MIXES = {
    "offline-closed": traffic.load_mix("offline-closed"),
    "sessions-closed": traffic.load_mix("sessions-closed"),
    "chat-open": dict(traffic.load_mix("offline-closed"), **_OPEN),
    "sessions-open": dict(traffic.load_mix("sessions-closed"), **_OPEN),
}


@pytest.mark.parametrize("name", list(MIXES))
def test_same_counts_and_totals_for_every_seed_in_another_order(name):
    mix = MIXES[name]
    a, b = (traffic.build(mix, s, 40) for s in SEEDS)
    for seg in ("ramp", "window", "drain", "pool"):
        assert a.totals(seg) == b.totals(seg)
    key = lambda r: (r.segment, r.prompt_len)  # noqa: E731
    assert sorted(map(key, a.requests)) == sorted(map(key, b.requests))
    assert sorted(r.max_tokens for r in a.requests) == \
        sorted(r.max_tokens for r in b.requests)
    assert [(r.prompt_len, r.max_tokens) for r in a.requests] != \
        [(r.prompt_len, r.max_tokens) for r in b.requests]
    assert [r.token_seed for r in a.requests] != \
        [r.token_seed for r in b.requests]


@pytest.mark.parametrize("name", list(MIXES))
def test_same_seed_same_traffic(name):
    mix = MIXES[name]
    a, b = (traffic.build(mix, 77, 40) for _ in range(2))
    assert a.requests == b.requests
    r = a.requests[3]
    assert traffic.token_ids(r.token_seed, 50, 1000) == \
        traffic.token_ids(r.token_seed, 50, 1000)


@pytest.mark.parametrize("name", ["chat-open", "sessions-open"])
def test_open_loop_window_holds_the_rate_and_the_bursts(name):
    mix = MIXES[name]
    s = traffic.build(mix, 5, 40)
    win = s.window()
    assert len(win) == round(mix["rate_rps"] * 40)
    assert all(0 <= r.due < 40 for r in win)
    assert [r.due for r in s.requests] == sorted(r.due for r in s.requests)
    b = mix["burst"]
    in_burst = sum(1 for r in win
                   if r.due % b["period_s"] >= b["period_s"] - b["burst_s"])
    share = b["factor"] * b["burst_s"] / (
        b["period_s"] - b["burst_s"] + b["factor"] * b["burst_s"])
    assert abs(in_burst / len(win) - share) < 0.03


def test_session_turns_do_not_depend_on_the_seed():
    mix = MIXES["sessions-open"]
    a, b = (traffic.build(mix, s, 40) for s in SEEDS)
    assert [(r.lane, r.turn, r.tenant) for r in a.requests] == \
        [(r.lane, r.turn, r.tenant) for r in b.requests]
    turns = collections.Counter(r.turn for r in a.window())
    assert set(turns) == set(range(mix["sessions"]["turns"]))
    # a lane's turns count up by one, and wrap to a new conversation
    by_lane = collections.defaultdict(list)
    for r in a.requests:
        by_lane[r.lane].append(r.turn)
    n = mix["sessions"]["turns"]
    for ts in by_lane.values():
        assert all((y - x) % n == 1 for x, y in zip(ts, ts[1:]))


def test_stratified_blocks_hold_one_of_each_stratum():
    vals = list(range(160))
    out = traffic.stratified(vals, 16, random.Random(3))
    assert sorted(out) == vals
    for i in range(0, 160, 16):
        assert sorted(v // 10 for v in out[i:i + 16]) == list(range(16))
    # so any stretch of a run offers nearly the same work
    sums = [sum(out[i:i + 16]) for i in range(0, 160, 16)]
    assert max(sums) - min(sums) <= 16
    # which items share a block does not depend on the seed
    other = traffic.stratified(vals, 16, random.Random(4))
    assert {frozenset(out[i:i + 16]) for i in range(0, 160, 16)} == \
        {frozenset(other[i:i + 16]) for i in range(0, 160, 16)}
    assert out != other


def test_blocks_of_the_offline_pool_offer_equal_work():
    mix = traffic.load_mix("offline-closed")
    s = traffic.build(mix, 9, 50)
    p = [sum(r.prompt_len for r in s.requests[i:i + 16])
         for i in range(0, len(s.requests), 16)]
    o = [sum(r.max_tokens for r in s.requests[i:i + 16])
         for i in range(0, len(s.requests), 16)]
    assert (max(p) - min(p)) / (sum(p) / len(p)) < 0.02
    assert (max(o) - min(o)) / (sum(o) / len(o)) < 0.03


def test_quantiles_are_clipped_and_centred():
    q = traffic.quantiles({"kind": "lognormal", "median": 512, "sigma": 0.8,
                           "min": 128, "max": 2048}, 1001)
    assert q[0] == 128 and q[-1] == 2048 and q[500] == 512
    g = traffic.exp_gaps(100)
    assert abs(sum(g) - 100) < 1e-9 and g == sorted(g)


def test_a_closed_loop_of_sessions_has_one_lane_a_caller():
    mix = traffic.load_mix("sessions-closed")
    assert mix["clients"] == mix["sessions"]["lanes"]
    with pytest.raises(ValueError):
        traffic.build(dict(mix, clients=mix["clients"] + 1), 1, 50)
