"""Decision observability plane (ISSUE 16): routing score ledgers, predictor
calibration, and lever-efficiency accounting.

Covers:
- scorer clamping: a scorer returning scores for endpoints a filter already
  eliminated (stale snapshot) can never leak them back into the pick;
- Profile.run detail capture: full filter/score/tie detail when the ledger is
  on, literally None allocated when it is off;
- the zero-overhead-off contract: with LLMD_DECISION_LEDGER=0 the scheduler
  records no detail, schedule() stamps no pre_drops, the RouterServer attaches
  no exporter and the decision metric families stay untouched;
- schedule determinism: identical request + endpoint state produce identical
  score maps and the same pick across 50 schedules;
- build_decision folds on synthetic router and engine flight records
  (calibration join gating, reschedule counting, KV/spec lever sums);
- exporter chaining: the decision hook wraps the phase exporter (on_finish is
  a single slot) and both planes' families fill from one retirement;
- /debug/requests/<id> embeds the ledger under "decision";
- dump_flight: --phases and --decisions compose in one invocation over the
  shared record-selection path.
"""

import json

from llmd_tpu.core.config import FrameworkConfig
from llmd_tpu.core.endpoint import Endpoint, EndpointPool
from llmd_tpu.core.metrics_contract import StdMetric
from llmd_tpu.core.request import InferenceRequest, SamplingParams
from llmd_tpu.obs.decisions import (CalibrationWindows, build_decision,
                                    decisions_enabled)
from llmd_tpu.obs.events import FlightRecorder, debug_detail_response
from llmd_tpu.router import filters_pickers as _fp  # noqa: F401
from llmd_tpu.router import scorers as _s  # noqa: F401
from llmd_tpu.router.plugins import known_plugin_types
from llmd_tpu.router.scheduler import Profile, Scheduler
from llmd_tpu.router.scorers import clamp_scores

CFG = """
plugins:
  - {name: queue, type: queue-depth-scorer}
  - {name: kv-util, type: kv-cache-utilization-scorer}
schedulingProfiles:
  - name: default
    plugins:
      - {pluginRef: queue, weight: 2}
      - {pluginRef: kv-util, weight: 1}
"""


def _pool(n=3):
    pool = EndpointPool()
    for i in range(n):
        ep = Endpoint(address=f"10.0.0.{i}:8000")
        ep.attrs.put(StdMetric.QUEUED_REQUESTS, float(i * 5))
        ep.attrs.put(StdMetric.KV_UTILIZATION, 0.1 * i)
        pool.upsert(ep)
    return pool


def _req(prompt="hello world"):
    return InferenceRequest(prompt=prompt, sampling=SamplingParams(max_tokens=8))


# ------------------------------------------------------------ scorer clamping


class _DropFirst:
    def filter(self, req, eps):
        return eps[1:]


class _StaleScorer:
    """Returns a huge score for an endpoint a filter already removed — the
    stale-snapshot bug clamp_scores exists to contain."""

    def __init__(self, stale):
        self.stale = stale

    def score(self, req, eps):
        scores = {e: 0.5 for e in eps}
        scores[self.stale] = 100.0
        return scores


class _MaxPick:
    def pick(self, req, scores):
        return max(scores, key=lambda e: scores[e]) if scores else None


def test_clamp_scores_drops_and_renormalizes():
    a, b, c = (Endpoint(address=f"e{i}:1") for i in range(3))
    # in-set scores pass through untouched (no allocation on the hot path)
    s = {a: 0.2, b: 1.0}
    assert clamp_scores(s, {a: 0.0, b: 0.0}) is s
    # out-of-set endpoints are dropped and the survivors re-normalized so a
    # stale max doesn't deflate this scorer's weight vs its peers
    out = clamp_scores({a: 0.2, b: 0.8, c: 1.0}, {a: 0.0, b: 0.0})
    assert c not in out
    assert abs(out[b] - 1.0) < 1e-9 and abs(out[a] - 0.25) < 1e-9


def test_stale_scorer_cannot_resurrect_filtered_endpoint():
    eps = [Endpoint(address=f"10.0.0.{i}:8000") for i in range(3)]
    prof = Profile("p", [(_DropFirst(), 1.0),
                         (_StaleScorer(eps[0]), 1.0),
                         (_MaxPick(), 1.0)])
    run = prof.run(_req(), eps, detail=True)
    assert run.endpoint in eps[1:]           # never the filtered-out one
    assert eps[0] not in run.scores          # nor does its score leak
    assert run.detail["candidates"] == 2
    for _, _, smap in run.detail["scorers"]:
        assert eps[0] not in smap


# ------------------------------------------------------ detail on/off capture


def test_profile_run_detail_on_off():
    eps = [Endpoint(address=f"10.0.0.{i}:8000") for i in range(3)]
    prof = Profile("p", [(_DropFirst(), 1.0),
                         (_StaleScorer(eps[0]), 2.0),
                         (_MaxPick(), 1.0)])
    off = prof.run(_req(), eps)
    assert off.detail is None
    on = prof.run(_req(), eps, detail=True)
    assert on.detail["filters"] == [["_DropFirst", 1]]
    assert on.detail["candidates"] == 2
    assert on.detail["tie"] == 2             # both survivors score 0.5
    [(name, weight, smap)] = on.detail["scorers"]
    assert name == "_StaleScorer" and weight == 2.0 and len(smap) == 2


def test_scheduler_off_allocates_nothing(monkeypatch):
    monkeypatch.setenv("LLMD_DECISION_LEDGER", "0")
    assert not decisions_enabled()
    cfg = FrameworkConfig.from_yaml(CFG, known_types=known_plugin_types())
    sched = Scheduler(cfg, _pool(3))
    assert sched.record_decisions is False
    # even with exclusions (the pre_drops trigger when the ledger is on)
    res = sched.schedule(_req(), exclude={"10.0.0.2:8000"})
    assert res.endpoint is not None
    assert res.pre_drops is None
    assert all(run.detail is None for run in res.profiles.values())


def test_scheduler_on_records_detail_and_pre_drops(monkeypatch):
    monkeypatch.setenv("LLMD_DECISION_LEDGER", "1")
    cfg = FrameworkConfig.from_yaml(CFG, known_types=known_plugin_types())
    sched = Scheduler(cfg, _pool(3))
    assert sched.record_decisions is True
    res = sched.schedule(_req(), exclude={"10.0.0.2:8000"})
    assert res.pre_drops == {"excluded": 1, "resilience_dropped": 0}
    run = res.profiles["default"]
    assert run.detail is not None and run.detail["candidates"] == 2
    # no drops → no pre_drops dict either (nothing to report, nothing kept)
    assert sched.schedule(_req()).pre_drops is None


def test_router_server_off_attaches_no_exporter(monkeypatch):
    from llmd_tpu.router.server import RouterServer

    def _families(env_value):
        monkeypatch.setenv("LLMD_DECISION_LEDGER", env_value)
        cfg = FrameworkConfig.from_yaml(CFG, known_types=known_plugin_types())
        rs = RouterServer(cfg, _pool(2), port=0)
        rs.flight.start("r1", model="m")
        rs.flight.record("r1", "route_decision",
                         profiles={"default": {"candidates": 2, "tie": 1}},
                         regret=-0.25)
        rs.flight.finish("r1", "retired")
        return rs.metrics.registry.expose()

    off = _families("0")
    assert 'llmd_tpu:decision_ledgers_total{plane="router"}' not in off
    assert "llmd_tpu:decision_regret_count" not in off
    on = _families("1")
    assert 'llmd_tpu:decision_ledgers_total{plane="router"} 1' in on
    # chaining preserved: the phase exporter underneath still fired
    assert "llmd_tpu:request_phase_seconds" in on


# --------------------------------------------------------------- determinism


def test_schedule_determinism_over_50_runs(monkeypatch):
    monkeypatch.setenv("LLMD_DECISION_LEDGER", "1")
    cfg = FrameworkConfig.from_yaml(CFG, known_types=known_plugin_types())
    sched = Scheduler(cfg, _pool(4))
    baseline = None
    for _ in range(50):
        res = sched.schedule(_req("determinism probe " * 4))
        run = res.profiles["default"]
        snap = (res.endpoint.address, run.detail["tie"],
                tuple(sorted((e.address, round(s, 12))
                             for e, s in run.scores.items())))
        if baseline is None:
            baseline = snap
        assert snap == baseline


# ------------------------------------------------------- build_decision folds


def _rec(events, wall_ms=100.0, **extra):
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


_ROUTE = {"profiles": {"default": {"candidates": 3, "tie": 1,
                                   "chosen": "a:1",
                                   "top": [["a:1", 1.0], ["b:1", 0.6]],
                                   "regret": 0.4}},
          "regret": 0.4}


def test_build_decision_router_fold_with_calibration():
    rec = _rec([
        ("arrival", 0.0),
        ("route_decision", 1.0, dict(_ROUTE, predicted_ttft_ms=20.0,
                                     predicted_e2e_ms=90.0, excluded=1)),
        ("forward", 2.0),
        ("response", 99.0, {"ttft_ms": 25.0}),
    ], wall_ms=100.0)
    d = build_decision(rec)
    assert d["plane"] == "router" and d["schedules"] == 1
    assert d["regret"] == 0.4 and d["excluded"] == 1
    assert d["reschedules"] == {"retry": 0, "hedge": 0}
    assert d["slo_breached"] is False
    calib = d["calibration"]
    assert calib["ttft_error_ms"] == 5.0          # 25 observed - 20 predicted
    assert calib["e2e_error_ms"] == 10.0          # 100 wall - 90 predicted
    assert d["profiles"]["default"]["chosen"] == "a:1"


def test_build_decision_retry_voids_e2e_calibration_and_counts():
    rec = _rec([
        ("route_decision", 1.0, dict(_ROUTE, predicted_e2e_ms=90.0)),
        ("forward", 2.0), ("retry", 50.0),
        ("route_decision", 51.0, dict(_ROUTE, predicted_e2e_ms=40.0,
                                      attempt=1)),
        ("forward", 52.0), ("slo_breach", 99.0), ("response", 99.5),
    ], wall_ms=100.0)
    d = build_decision(rec)
    assert d["schedules"] == 2
    assert d["reschedules"]["retry"] == 1
    assert d["slo_breached"] is True
    # retried wall clock measures the retry loop, not the model: no e2e join
    assert "calibration" not in d


def test_build_decision_router_kv_lever_sums_stamped_pulls():
    rec = _rec([
        ("route_decision", 1.0, dict(_ROUTE)),
        ("kv_pull_stamped", 2.0, {"blocks": 4, "saved_tokens_est": 64}),
        ("kv_pull_stamped", 3.0, {"blocks": 2, "saved_tokens_est": 32}),
        ("response", 99.0),
    ])
    d = build_decision(rec)
    assert d["kv"] == {"stamped": 2, "blocks": 6, "saved_tokens_est": 96}


def test_build_decision_engine_fold_and_none_when_empty():
    rec = _rec([
        ("arrival", 0.0), ("admitted", 1.0),
        ("kv_pull", 2.0, {"outcome": "ok", "blocks": 3, "ms": 1.5}),
        ("retired", 90.0, {"spec_drafted": 10, "spec_accepted": 7,
                           "spec_flips": 2, "cached_tokens": 16}),
    ])
    d = build_decision(rec)
    assert d["plane"] == "engine"
    assert d["spec"] == {"drafted": 10, "accepted": 7, "wasted": 3, "flips": 2}
    assert d["kv"] == {"outcome": "ok", "blocks": 3, "ms": 1.5}
    assert d["cached_tokens"] == 16
    # nothing decision-relevant → no ledger at all, not an empty shell
    bare = _rec([("arrival", 0.0), ("admitted", 1.0), ("retired", 9.0)])
    assert build_decision(bare) is None


# ------------------------------------------------------------- live exporter


class _Child:
    def __init__(self, sink, labels):
        self.sink, self.labels_kv = sink, labels

    def inc(self, n=1):
        self.sink.append((self.labels_kv, float(n)))

    def observe(self, v):
        self.sink.append((self.labels_kv, float(v)))


class _Fam:
    def __init__(self):
        self.samples = []

    def labels(self, **kv):
        return _Child(self.samples, kv)

    def inc(self, n=1):
        self.samples.append(({}, float(n)))

    def set_labels_function(self, fn):
        self.fn = fn


class _FakeMetrics:
    def __init__(self):
        for name in ("decision_ledgers", "decision_regret",
                     "decision_reschedules", "predictor_calibration_error",
                     "predictor_calibration_ape", "decision_kv_pull_blocks",
                     "decision_kv_tokens_saved", "decision_spec_wasted",
                     "decision_spec_flips"):
            setattr(self, name, _Fam())


def test_exporter_chains_after_phase_exporter_and_fills_families():
    from llmd_tpu.obs.attribution import attach_phase_exporter
    from llmd_tpu.obs.decisions import attach_decision_exporter

    fr = FlightRecorder(max_requests=8)
    phase_hist = _Fam()
    attach_phase_exporter(fr, phase_hist)
    metrics = _FakeMetrics()
    windows = CalibrationWindows(window=16)
    attach_decision_exporter(fr, metrics, plane="router", windows=windows)

    fr.start("r1", model="llama")
    fr.record("r1", "route_decision",
              **dict(_ROUTE, predicted_ttft_ms=20.0, predicted_e2e_ms=90.0))
    fr.record("r1", "kv_pull_stamped", blocks=4, saved_tokens_est=64)
    fr.record("r1", "response", ttft_ms=25.0)
    fr.finish("r1", "retired")

    assert phase_hist.samples, "phase exporter lost in the chain"
    assert metrics.decision_ledgers.samples == [({"plane": "router"}, 1.0)]
    [(labels, regret)] = metrics.decision_regret.samples
    assert labels == {"slo_breached": "no"} and regret == 0.4
    errs = {kv["objective"]: v
            for kv, v in metrics.predictor_calibration_error.samples}
    assert errs["ttft"] == 5.0 and set(errs) == {"ttft", "e2e"}
    assert metrics.decision_kv_pull_blocks.samples == [({}, 4.0)]
    assert metrics.decision_kv_tokens_saved.samples == [({}, 64.0)]
    # the APE window saw both joins and the gauge callback reports per-pair
    ape = {d["objective"]: v for d, v in windows.samples()}
    assert abs(ape["ttft"] - 5.0 / 25.0) < 1e-9
    assert metrics.predictor_calibration_ape.fn.__self__ is windows


def test_engine_exporter_fills_spec_families():
    from llmd_tpu.obs.decisions import attach_decision_exporter

    fr = FlightRecorder(max_requests=8)
    metrics = _FakeMetrics()
    attach_decision_exporter(fr, metrics, plane="engine")
    fr.start("e1", model="m")
    fr.record("e1", "admitted")
    fr.finish("e1", "retired", spec_drafted=10, spec_accepted=7, spec_flips=3)
    assert metrics.decision_ledgers.samples == [({"plane": "engine"}, 1.0)]
    assert metrics.decision_spec_wasted.samples == [({}, 3.0)]
    assert metrics.decision_spec_flips.samples == [({}, 3.0)]


def test_exporter_failure_never_breaks_retirement():
    from llmd_tpu.obs.decisions import attach_decision_exporter

    fr = FlightRecorder(max_requests=8)

    class _Boom:
        # the APE gauge wiring happens at attach (construction) time; the
        # never-break contract is about per-retirement export failures
        predictor_calibration_ape = _Fam()

        def __getattr__(self, name):
            raise RuntimeError("metrics down")

    attach_decision_exporter(fr, _Boom(), plane="router",
                             windows=CalibrationWindows(window=16))
    fr.start("r1")
    fr.record("r1", "route_decision", **_ROUTE)
    fr.finish("r1", "retired")  # must not raise
    assert fr.get("r1")["status"] == "finished"


# --------------------------------------------------- debug view + dump_flight


def test_debug_detail_embeds_decision():
    fr = FlightRecorder(max_requests=8)
    fr.start("r1", model="m")
    fr.record("r1", "route_decision", **_ROUTE)
    fr.record("r1", "response")
    fr.finish("r1", "retired")
    status, rec = debug_detail_response(fr, "r1")
    assert status == 200
    assert rec["decision"]["plane"] == "router"
    assert rec["decision"]["regret"] == 0.4
    assert "phase_ledger" in rec  # both ledgers ride the same fetch


def test_dump_flight_phases_and_decisions_compose(tmp_path, capsys):
    from tools.dump_flight import main as dump_main

    rec = _rec([
        ("arrival", 0.0),
        ("route_decision", 1.0, dict(_ROUTE, predicted_e2e_ms=90.0)),
        ("forward", 2.0), ("response", 99.0),
    ], wall_ms=100.0, trace_id="t" * 32)
    dump = tmp_path / "flight.json"
    dump.write_text(json.dumps({"requests": [rec], "system": []}))

    assert dump_main([str(dump), "--id", "r1", "--phases", "--decisions"]) == 0
    out = capsys.readouterr().out
    assert "phase ledger" in out
    assert "decision ledger (router plane)" in out
    assert "profile default" in out

    # same shared selection path under --trace
    assert dump_main([str(dump), "--trace", "t" * 32, "--phases",
                      "--decisions"]) == 0
    out = capsys.readouterr().out
    assert out.startswith(f"trace {'t' * 32}: 1 request(s)")
    assert "phase ledger" in out and "decision ledger" in out

    # unknown trace is an error, not an empty render
    assert dump_main([str(dump), "--trace", "nope", "--decisions"]) == 1


def test_router_overhead_measures_both_sides_against_its_bounds():
    """tools/decision_check.py's ledger-overhead reading: the scheduler timed
    with the ledger off and on, and a verdict that is the documented bound
    (<2% relative or <25us a call) applied to those two readings. Run in a
    child: the tool sets the ledger's variables in its own environment."""
    import subprocess
    import sys
    from pathlib import Path

    proc = subprocess.run(
        [sys.executable, "-c",
         "import json; from tools.decision_check import router_overhead; "
         "print(json.dumps(router_overhead(n_requests=30, rounds=1)))"],
        cwd=Path(__file__).resolve().parent.parent, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    v = json.loads(proc.stdout.strip().splitlines()[-1])
    assert v["schedule_us_off"] > 0 and v["schedule_us_on"] > 0
    assert abs(v["delta_us"] - (v["schedule_us_on"] - v["schedule_us_off"])) < 0.02
    assert (v["rel_bound"], v["abs_bound_us"]) == (0.02, 25.0)
    assert v["ok"] == (v["rel_delta"] <= v["rel_bound"]
                       or v["delta_us"] <= v["abs_bound_us"])
    assert v["router_overhead"] == ("ok" if v["ok"] else "failed")
