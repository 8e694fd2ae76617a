"""SLO attribution plane (ISSUE 13): phase ledgers, tenant accounting,
burn-rate windows, and the fleet rollup.

Covers:
- ledger sums to wall BY CONSTRUCTION on both planes, under retries,
  preemption, cross-engine kv pulls, and chained (overlapped) decode;
- residual as the unknown-unknown series: unrecognized events and the
  post-terminal tail land there, nothing silently vanishes;
- the on_finish exporter: every retired request's phases reach the
  ``llmd_tpu:request_phase_seconds{phase,tenant,model}`` histogram and the
  per-request export sums to the recorded wall clock within 5%;
- tenant identity: header clamping, per-tenant SLO overrides, attainment
  gauges that disagree exactly when the tenants' objectives disagree;
- burn-rate minute-window boundaries with an injected clock, and series
  boundedness + idle-tenant pruning;
- fleet rollup: tok/s from counter deltas (reset-safe), min-headroom
  aggregation, and boundedness under 50 cycles of replica churn.
"""

import time
import types

from llmd_tpu.core.request import (HDR_TENANT, InferenceRequest, clamp_request_id,
                                   clamp_tenant)
from llmd_tpu.obs.attribution import PHASES, attach_phase_exporter, build_ledger
from llmd_tpu.obs.events import FlightRecorder
from llmd_tpu.obs.fleet import FleetRollup
from llmd_tpu.obs.slo import SLOConfig, SLOEngine, _parse_overrides

# ------------------------------------------------------------ ledger helpers


def _rec(events, wall_ms, **extra):
    """Flight record in the to_dict() shape from (name, t_ms[, attrs])."""
    evs = []
    for e in events:
        name, t_ms = e[0], e[1]
        ev = {"event": name, "t_ms": t_ms}
        if len(e) > 2:
            ev.update(e[2])
        evs.append(ev)
    rec = {"request_id": "r1", "model": "m", "status": "finished",
           "latency_ms": wall_ms, "events": evs}
    rec.update(extra)
    return rec


def _total(ledger):
    return sum(ledger["phases"].values()) + ledger["residual_ms"]


# ------------------------------------------------------- ledger: sum-to-wall


def test_engine_ledger_sums_to_wall_with_kv_pull_and_preemption():
    rec = _rec([
        ("kv_pull", 5.0), ("kv_reload", 25.0), ("arrival", 27.0),
        ("admitted", 30.0), ("prefill_start", 31.0), ("prefill_end", 80.0),
        ("first_token", 82.0), ("preempted", 120.0), ("admitted", 150.0),
        ("decode", 151.0), ("retired", 200.0),
    ], wall_ms=200.0)
    ledger = build_ledger(rec)
    assert ledger["plane"] == "engine"
    assert abs(_total(ledger) - 200.0) < 1e-6
    # lead-in before the kv_pull event is the pull setup, the interval after
    # it is the transfer; both land in kv_pull-adjacent phases
    assert ledger["phases"]["kv_pull"] == 5.0        # open → kv_pull event
    # kv_pull → kv_reload (20) plus arrival → admitted (3)
    assert ledger["phases"]["queue_wait"] == 23.0
    assert ledger["phases"]["preempted"] == 30.0     # preempted → re-admit
    assert ledger["phases"]["prefill"] == 51.0       # 31→80 + 80→82
    assert ledger["residual_frac"] == 0.0


def test_engine_ledger_ignores_kv_pull_tier_attr():
    """Durable-tier fetches ride the existing kv_pull event NAME with a
    tier attr (PR 18); attribution keys on names only, so the ledger is
    bit-identical to a peer pull and still sums to wall."""
    events = [
        ("kv_pull", 5.0, {"tier": "durable", "outcome": "hit",
                          "peer": "10.0.0.9:9400", "n_blocks": 6}),
        ("arrival", 7.0), ("admitted", 10.0), ("prefill_start", 11.0),
        ("prefill_end", 40.0), ("first_token", 42.0), ("decode", 43.0),
        ("retired", 100.0),
    ]
    durable = build_ledger(_rec(events, wall_ms=100.0))
    peer = build_ledger(_rec(
        [(n, t, {**a, "tier": "peer"}) if len(e) > 2 else e
         for e in events
         for n, t, a in [(e[0], e[1], e[2] if len(e) > 2 else {})]],
        wall_ms=100.0))
    assert abs(_total(durable) - 100.0) < 1e-6
    assert durable["phases"] == peer["phases"]
    assert durable["phases"]["kv_pull"] == 5.0
    assert durable["residual_frac"] == 0.0


def test_router_ledger_sums_to_wall_under_retry_and_hedge():
    rec = _rec([
        ("arrival", 2.0), ("flow_enqueue", 3.0), ("flow_dispatch", 40.0),
        ("routing_decision", 41.0), ("forward", 42.0), ("retry", 90.0),
        ("forward", 95.0), ("hedge", 140.0), ("response", 230.0),
    ], wall_ms=230.5)
    ledger = build_ledger(rec)
    assert ledger["plane"] == "router"
    assert abs(_total(ledger) - 230.5) < 1e-6
    assert ledger["phases"]["queue_wait"] == 37.0   # flow_enqueue → dispatch
    assert ledger["phases"]["retry"] == 5.0         # retry → re-forward
    # both forwards and the hedge race are upstream time
    assert ledger["phases"]["upstream"] == (90.0 - 42.0) + (140.0 - 95.0) + 90.0
    # terminal tail (230 → 230.5) is finish bookkeeping → residual
    assert abs(ledger["residual_ms"] - 0.5) < 1e-6


def test_chained_decode_splits_overlap_and_chain_stage():
    rec = _rec([
        ("arrival", 0.0), ("admitted", 1.0), ("prefill_start", 2.0),
        ("first_token", 10.0), ("chain_dispatch", 12.0),
        ("chain_dispatch", 30.0, {"masked": True}), ("decode", 55.0),
        ("retired", 60.0),
    ], wall_ms=60.0)
    ledger = build_ledger(rec)
    assert abs(_total(ledger) - 60.0) < 1e-6
    assert ledger["phases"]["decode_overlap"] == 18.0  # plain chain dispatch
    assert ledger["phases"]["chain_stage"] == 25.0     # masked: table staging


def test_unknown_event_and_no_events_become_residual():
    ledger = build_ledger(_rec([
        ("arrival", 0.0), ("mystery_event", 10.0), ("retired", 50.0),
    ], wall_ms=50.0))
    assert abs(_total(ledger) - 50.0) < 1e-6
    assert ledger["residual_ms"] == 40.0  # interval after the unknown event
    assert "unattributed" not in ledger["phases"]  # folded into residual

    empty = build_ledger(_rec([], wall_ms=33.0))
    assert empty["residual_ms"] == 33.0
    assert empty["residual_frac"] == 1.0


def test_active_record_attributes_tail_to_current_state():
    # non-terminal last event: the request is still decoding right now
    ledger = build_ledger(_rec([
        ("arrival", 0.0), ("admitted", 5.0), ("prefill_start", 6.0),
        ("first_token", 20.0), ("decode", 21.0),
    ], wall_ms=100.0, status="active"))
    assert abs(_total(ledger) - 100.0) < 1e-6
    assert ledger["phases"]["decode"] == 80.0  # 21 → 100 tail + 20 → 21
    assert ledger["residual_ms"] == 0.0


def test_ledger_phases_stay_in_canonical_vocabulary():
    rec = _rec([
        ("kv_pull", 2.0), ("arrival", 4.0), ("admitted", 6.0),
        ("prefill_start", 7.0), ("spec_draft", 30.0), ("spec_verify", 35.0),
        ("structured_mask", 40.0), ("retired", 50.0),
    ], wall_ms=50.0)
    for phase in build_ledger(rec)["phases"]:
        assert phase in PHASES


# ----------------------------------------------------------- live exporter


class _FakeHistogram:
    def __init__(self):
        self.observed = []  # (labels, value)

    def labels(self, **kv):
        obs = self.observed

        class _Child:
            def observe(self, v):
                obs.append((kv, v))

        return _Child()


def test_on_finish_exporter_sums_to_wall_within_5pct():
    fr = FlightRecorder(max_requests=8)
    hist = _FakeHistogram()
    attach_phase_exporter(fr, hist)
    fr.start("req-1", model="llama", tenant="gold")
    fr.record("req-1", "admitted")
    time.sleep(0.02)
    fr.record("req-1", "prefill_start")
    time.sleep(0.01)
    fr.record("req-1", "first_token")
    fr.finish("req-1", "retired")
    assert hist.observed, "on_finish exporter never fired"
    total_s = sum(v for _, v in hist.observed)
    wall_s = fr.get("req-1")["latency_ms"] / 1e3
    assert abs(total_s - wall_s) <= 0.05 * wall_s + 1e-9
    labels = {tuple(sorted(kv.items())) for kv, _ in hist.observed}
    for kv in labels:
        d = dict(kv)
        assert d["tenant"] == "gold" and d["model"] == "llama"


def test_on_finish_exporter_failure_never_breaks_retirement():
    fr = FlightRecorder(max_requests=8)

    def boom(rec):
        raise RuntimeError("exporter bug")

    fr.on_finish = boom
    fr.start("req-2")
    fr.finish("req-2", "retired")  # must not raise
    assert fr.get("req-2")["status"] == "finished"


# ------------------------------------------------------------ tenant identity


def test_clamp_tenant_and_request_id():
    assert clamp_tenant("gold") == "gold"
    assert clamp_tenant(None) == "anon"
    assert clamp_tenant("") == "anon"
    assert clamp_tenant("team/../etc") == "anon"   # invalid chars rejected
    assert clamp_tenant("x" * 65) == "anon"        # over MAX_TENANT_LEN
    assert clamp_tenant("A-Z.0_9") == "A-Z.0_9"

    assert clamp_request_id("req-123") == "req-123"
    minted = clamp_request_id(None)
    assert len(minted) == 32 and minted != clamp_request_id(None)
    assert clamp_request_id("bad id\n") != "bad id\n"  # re-minted


def test_tenant_threads_from_header_into_request():
    req = InferenceRequest.from_headers(
        {"content-type": "application/json", HDR_TENANT: "gold"},
        model="m", prompt="hi")
    assert req.tenant == "gold"
    anon = InferenceRequest.from_headers({}, model="m", prompt="hi")
    assert anon.tenant == "anon"


# ----------------------------------------------------- SLO engine + windows


def _engine(now, **base):
    eng = SLOEngine(default=SLOConfig(**base), now_fn=lambda: now[0])
    return eng


def test_tenant_overrides_make_attainment_disagree():
    now = [10_000.0]
    eng = SLOEngine(
        default=SLOConfig(e2e_ms=5000.0, target=0.99),
        overrides=_parse_overrides("gold:e2e_ms=1000,target=0.999",
                                   SLOConfig(e2e_ms=5000.0, target=0.99)),
        now_fn=lambda: now[0])
    # identical traffic: 2s e2e. Breaches gold's 1s objective, meets the
    # default 5s one — the per-tenant gauges MUST disagree.
    for _ in range(10):
        assert eng.observe("gold", "e2e", 2.0) is True
        assert eng.observe("bronze", "e2e", 2.0) is False
    assert eng.attainment("gold", "e2e", 300) == 0.0
    assert eng.attainment("bronze", "e2e", 300) == 1.0
    # burn: gold spends budget 1000x faster than its 0.999 target allows
    assert eng.burn_rate("gold", "e2e", 300) == (1.0 - 0.0) / (1.0 - 0.999)
    assert eng.burn_rate("bronze", "e2e", 300) == 0.0
    samples = {(d["tenant"], d["window"]): v
               for d, v in eng.gauge_samples("attainment")}
    assert samples[("gold", "5m")] == 0.0
    assert samples[("bronze", "5m")] == 1.0


def test_burn_window_boundaries_with_injected_clock():
    now = [60_000.0]  # exactly on a minute boundary
    eng = _engine(now, e2e_ms=100.0, target=0.99)
    eng.observe("t", "e2e", 1.0)  # breach in minute 1000
    assert eng.attainment("t", "e2e", 300) == 0.0
    # advance to minute 1004: window [1000..1004] still holds the breach
    now[0] = 60_000.0 + 4 * 60
    eng.observe("t", "e2e", 0.05)  # good
    assert eng.attainment("t", "e2e", 300) == 0.5
    # minute 1005: the breach minute falls OUT of the 5m window...
    now[0] = 60_000.0 + 5 * 60
    assert eng.attainment("t", "e2e", 300) == 1.0
    # ...but stays inside the 1h window
    assert eng.attainment("t", "e2e", 3600) == 0.5
    # empty window → None, not a division crash
    now[0] = 60_000.0 + 3 * 3600
    assert eng.attainment("t", "e2e", 300) is None


def test_series_bounded_and_idle_tenants_pruned():
    now = [0.0]
    eng = _engine(now, e2e_ms=100.0)
    for i in range(200):  # 200 minutes of traffic: > the 61-bucket bound
        now[0] = i * 60.0
        eng.observe("t", "e2e", 0.05)
    series = eng._series[("t", "e2e")]
    assert len(series.buckets) <= 3600 // 60 + 1
    # a second tenant goes idle past the long window → pruned at scrape
    eng.observe("ghost", "e2e", 0.05)
    now[0] = 200 * 60.0 + 2 * 3600
    eng.observe("t", "e2e", 0.05)
    eng.gauge_samples("attainment")
    assert ("ghost", "e2e") not in eng._series
    assert ("t", "e2e") in eng._series


def test_observe_ignores_unconfigured_objective_and_counts_breaches():
    class _Counter(_FakeHistogram):
        def labels(self, **kv):
            obs = self.observed

            class _Child:
                def inc(self):
                    obs.append(kv)

            return _Child()

    now = [0.0]
    eng = _engine(now, e2e_ms=100.0)  # no ttft objective
    eng.breach_counter = counter = _Counter()
    assert eng.observe("t", "ttft", 99.0) is False  # unconfigured: ignored
    assert eng.attainment("t", "ttft", 300) is None
    assert eng.observe("t", "e2e", 99.0) is True
    assert counter.observed == [{"tenant": "t", "objective": "e2e"}]


# ------------------------------------------------------------- fleet rollup


def _ep(address):
    return types.SimpleNamespace(address=address)


def _raw(tokens, running=1.0, waiting=0.0, kv=0.5,
         hbm=((0, 8e9, 6e9), (1, 8e9, 5e9)), fabric=1.0, stalled=0.0):
    out = [("llmd_tpu:decode_tokens_total", {}, tokens),
           ("vllm:num_requests_running", {}, running),
           ("vllm:num_requests_waiting", {}, waiting),
           ("vllm:kv_cache_usage_perc", {}, kv),
           ("llmd_tpu:device_fabric_alive", {}, fabric),
           ("llmd_tpu:engine_stalled", {}, stalled)]
    for dev, limit, use in hbm:
        out.append(("llmd_tpu:device_hbm_limit_bytes",
                    {"device": str(dev)}, limit))
        out.append(("llmd_tpu:device_hbm_bytes_in_use",
                    {"device": str(dev)}, use))
    return out


def test_fleet_tok_per_s_from_deltas_and_reset_rebaseline():
    now = [100.0]
    fleet = FleetRollup(now_fn=lambda: now[0])
    ep = _ep("10.0.0.1:8000")
    fleet.extract(ep, _raw(tokens=1000.0))
    now[0] = 110.0
    fleet.extract(ep, _raw(tokens=1500.0))
    assert fleet.snapshot()["tokens_per_second"] == 50.0
    # replica restart: counter resets below the baseline → 0, never negative
    now[0] = 120.0
    fleet.extract(ep, _raw(tokens=30.0))
    assert fleet.snapshot()["tokens_per_second"] == 0.0
    now[0] = 130.0
    fleet.extract(ep, _raw(tokens=130.0))
    assert fleet.snapshot()["tokens_per_second"] == 10.0


def test_fleet_aggregates_min_headroom_and_counts():
    fleet = FleetRollup()
    fleet.extract(_ep("a:1"), _raw(tokens=0, running=3, waiting=2,
                                   hbm=((0, 8e9, 6e9),)))        # headroom 2e9
    fleet.extract(_ep("b:1"), _raw(tokens=0, running=1, waiting=0,
                                   hbm=((0, 8e9, 7.5e9),), stalled=1.0))
    snap = fleet.snapshot()
    assert snap["replicas"] == 2
    assert snap["running"] == 4.0 and fleet.running_total() == 4.0
    assert snap["waiting"] == 2.0
    assert snap["hbm_headroom_min"] == 0.5e9
    assert snap["hbm_headroom_total"] == 2.5e9
    assert snap["stalled"] == 1 and snap["fabric_alive"] == 2
    # CPU backend: no device-plane gauges → alive, not stalled
    fleet.extract(_ep("c:1"), [("vllm:num_requests_running", {}, 1.0)])
    snap = fleet.snapshot()
    assert snap["fabric_alive"] == 3 and snap["stalled"] == 1


def test_fleet_bounded_under_replica_churn():
    fleet = FleetRollup()
    for cycle in range(50):
        addrs = [f"10.0.{cycle}.{i}:8000" for i in range(4)]
        for a in addrs:
            fleet.extract(_ep(a), _raw(tokens=float(cycle)))
        # discovery drops the whole generation except the last one
        if cycle < 49:
            for a in addrs:
                fleet.forget(a)
    assert len(fleet) == 4  # only the live generation remains
    assert fleet.snapshot()["replicas"] == 4


# --------------------------------------------- P/D split stack (ISSUE 20)


def test_split_stack_ledger_kv_pull_replaces_prefill():
    """A disaggregated decode: the engine adopts the remote prefill's blocks
    via kv_pull, so its phase ledger shows kv_pull and NO prefill — and
    still sums to the wall clock. An aggregated twin shows the inverse."""
    import aiohttp

    from llmd_tpu.testing.fake_server import FakeModelServer, FakeServerConfig
    from tests.conftest import run_async

    async def scenario():
        server = FakeModelServer(FakeServerConfig(role="decode"))
        await server.start()
        try:
            prompt = "pd split ledger prompt " * 8
            async with aiohttp.ClientSession() as sess:
                for ktp in ({"do_remote_prefill": True,
                             "remote_request_id": "pd-test-1"}, None):
                    body = {"prompt": prompt, "max_tokens": 4,
                            "model": server.cfg.model}
                    if ktp:
                        body["kv_transfer_params"] = ktp
                    async with sess.post(
                        f"http://{server.address}/v1/completions",
                        json=body) as r:
                        assert r.status == 200
                        await r.read()
            return server.remote_pulls, list(server.request_records)
        finally:
            await server.stop()

    remote_pulls, records = run_async(scenario())
    assert remote_pulls == 1 and len(records) == 2
    split, aggregated = build_ledger(records[0]), build_ledger(records[1])
    # the split stack: kv_pull replaces prefill on the decode replica
    assert split["phases"]["kv_pull"] > 0.0
    assert "prefill" not in split["phases"]
    assert abs(_total(split) - records[0]["latency_ms"]) < 1e-6
    # the aggregated twin prefills locally and never pulls
    assert aggregated["phases"]["prefill"] > 0.0
    assert "kv_pull" not in aggregated["phases"]
    assert abs(_total(aggregated) - records[1]["latency_ms"]) < 1e-6


_PD_CFG = """
plugins:
  - {name: prefix-producer, type: approx-prefix-cache-producer, params: {blockSize: 16}}
  - {name: inflight, type: inflight-load-producer}
  - {name: predicted, type: predicted-latency-producer}
  - {name: queue, type: queue-depth-scorer}
  - {name: pre-filter, type: prefill-endpoints-filter}
  - {name: dec-filter, type: decode-endpoints-filter}
profileHandler: disagg-profile-handler
disaggregation: {uncachedSuffixThreshold: 64}
schedulingProfiles:
  - name: decode
    plugins:
      - {pluginRef: dec-filter}
      - {pluginRef: queue, weight: 2}
  - name: prefill
    plugins:
      - {pluginRef: pre-filter}
      - {pluginRef: queue, weight: 2}
"""


def _pd_pool():
    from llmd_tpu.core.endpoint import Endpoint, EndpointPool, EndpointRole

    pool = EndpointPool()
    pool.upsert(Endpoint(address="10.0.0.1:8000", role=EndpointRole.PREFILL))
    pool.upsert(Endpoint(address="10.0.0.2:8000", role=EndpointRole.DECODE))
    return pool


def _pd_sched(pool):
    from llmd_tpu.core.config import FrameworkConfig
    from llmd_tpu.router import filters_pickers as _fp  # noqa: F401
    from llmd_tpu.router import latency_plugins as _lp  # noqa: F401
    from llmd_tpu.router import scorers as _s  # noqa: F401
    from llmd_tpu.router.plugins import known_plugin_types
    from llmd_tpu.router.scheduler import Scheduler

    cfg = FrameworkConfig.from_yaml(_PD_CFG, known_types=known_plugin_types())
    return Scheduler(cfg, pool)


def test_disagg_decider_stamps_pd_and_gates_on_predictor():
    from llmd_tpu.core.metrics_contract import StdMetric
    from llmd_tpu.core.request import InferenceRequest, SamplingParams

    pool = _pd_pool()
    sched = _pd_sched(pool)
    dec = pool.get("10.0.0.2:8000")

    def req(prompt):
        return InferenceRequest(prompt=prompt,
                                sampling=SamplingParams(max_tokens=4))

    # short uncached suffix: the hop is skipped, with the predicted
    # aggregated TTFT stamped as evidence
    res = sched.schedule(req("short prompt"))
    assert res.prefill_endpoint is None
    assert res.pd["decision"] == "aggregated"
    assert res.pd["reason"] == "short_uncached_suffix"
    assert "ttft_agg_ms" in res.pd
    # long prompt, idle decode replica: the hop costs more than it saves
    res = sched.schedule(req("an uncached long prompt " * 8))
    assert res.prefill_endpoint is None
    assert res.pd["reason"] == "hop_not_worth_it"
    assert res.pd["delta_ms"] <= 0.0
    # loaded decode replica: predicted TTFT-on-P + hop wins -> split
    dec.attrs.put(StdMetric.KV_UTILIZATION, 1.0)
    dec.attrs.put(StdMetric.QUEUED_REQUESTS, 4.0)
    res = sched.schedule(req("another uncached long prompt " * 8))
    assert res.prefill_endpoint is not None
    assert res.prefill_endpoint.address == "10.0.0.1:8000"
    assert res.pd["decision"] == "split"
    assert res.pd["reason"] == "predicted_ttft"
    assert res.pd["delta_ms"] > 0.0
    assert res.pd["ttft_split_ms"] >= res.pd["hop_ms"]  # hop priced in
    assert res.pd["ttft_split_ms"] < res.pd["ttft_agg_ms"]
    assert sched.metrics["pd_splits_total"] == 1
    assert sched.metrics["pd_aggregated_total"] == 2


def test_decision_ledger_carries_pd_stamp():
    """The pd decision rides the route_decision event into the decision
    ledger fold (obs/decisions.py), like breakers and kv_plane do."""
    from llmd_tpu.obs.decisions import build_decision

    pd = {"decision": "split", "reason": "predicted_ttft",
          "uncached_tokens": 160, "hop_ms": 7.0,
          "prefill": "10.0.0.1:8000", "decode": "10.0.0.2:8000",
          "ttft_agg_ms": 250.0, "ttft_split_ms": 40.0, "delta_ms": 203.0}
    rec = _rec([
        ("arrival", 1.0),
        ("route_decision", 2.0, {"profiles": {"decode": {}}, "pd": pd}),
        ("forward", 3.0), ("response", 90.0),
    ], wall_ms=91.0)
    ledger = build_decision(rec)
    assert ledger["plane"] == "router"
    assert ledger["pd"] == pd
    # aggregated rows carry their stamp too
    rec2 = _rec([
        ("route_decision", 2.0,
         {"pd": {"decision": "aggregated",
                 "reason": "short_uncached_suffix"}}),
        ("response", 50.0),
    ], wall_ms=50.0)
    assert build_decision(rec2)["pd"]["reason"] == "short_uncached_suffix"
