"""Pool plane: snapshot store, trace generators, launchers, and the
controller's reconcile loop (launch / drain-retire / health-sweep /
scale-from-zero), plus the router-side eviction regression for scale churn."""

import asyncio
import os
import time

import aiohttp

from llmd_tpu.core.config import FrameworkConfig
from llmd_tpu.core.endpoint import Endpoint, EndpointPool
from llmd_tpu.pool.controller import PoolConfig, PoolController
from llmd_tpu.pool.launcher import FakeReplicaLauncher
from llmd_tpu.pool.snapshot import PoolSnapshotStore, config_fingerprint
from llmd_tpu.pool.traces import (
    bursty_trace,
    diurnal_trace,
    dump_jsonl,
    load_jsonl,
    multi_tenant_ramp,
)
from llmd_tpu.router import filters_pickers as _fp  # noqa: F401
from llmd_tpu.router import scorers as _s  # noqa: F401
from llmd_tpu.router.plugins import known_plugin_types
from llmd_tpu.router.server import RouterServer
from llmd_tpu.testing.fake_server import FakeServerConfig
from tests.conftest import run_async


# ---------------------------------------------------------------- snapshots
def test_config_fingerprint_canonical():
    a = config_fingerprint({"model": "m", "block_size": 16})
    b = config_fingerprint({"block_size": 16, "model": "m"})  # order-free
    c = config_fingerprint({"model": "m", "block_size": 32})
    assert a == b and a != c
    assert len(a) == 16 and all(ch in "0123456789abcdef" for ch in a)


def test_snapshot_store_roundtrip(tmp_path):
    store = PoolSnapshotStore(str(tmp_path))
    fp = config_fingerprint({"model": "m"})
    assert not store.has(fp) and store.load(fp) is None
    ckpt = store.path(fp, "checkpoint")
    assert os.path.isdir(ckpt)  # artifact dirs exist before meta commits
    assert not store.has(fp)  # half-built snapshot never reads warm
    store.save(fp, {"kind": "fake"})
    assert store.has(fp)
    assert store.load(fp)["kind"] == "fake"
    assert store.fingerprints() == [fp]


# ------------------------------------------------------------------- traces
def test_traces_deterministic_and_bursty():
    t1 = bursty_trace(duration_s=6, base_rps=5, burst_rps=50,
                      burst_start_s=2, burst_end_s=4, seed=7)
    t2 = bursty_trace(duration_s=6, base_rps=5, burst_rps=50,
                      burst_start_s=2, burst_end_s=4, seed=7)
    assert [r.t for r in t1] == [r.t for r in t2]  # seeded → reproducible
    base = sum(1 for r in t1 if r.t < 2.0) / 2.0
    burst = sum(1 for r in t1 if 2.0 <= r.t < 4.0) / 2.0
    assert burst > 5 * base  # the swing is visible in arrival density
    assert all(t1[i].t <= t1[i + 1].t for i in range(len(t1) - 1))


def test_diurnal_and_ramp_shapes():
    d = diurnal_trace(duration_s=8, min_rps=2, peak_rps=30, period_s=8, seed=3)
    assert len(d) > 0
    ramp = multi_tenant_ramp(duration_s=6, tenants=["a", "b", "c"],
                             start_rps=1, end_rps=10, stagger_s=1.0, seed=3)
    names = {r.tenant for r in ramp}
    assert names == {"a", "b", "c"}
    # staggered starts: each tenant's first arrival comes later than the last
    firsts = sorted(min(r.t for r in ramp if r.tenant == n) for n in names)
    assert firsts[0] < firsts[-1]


def test_trace_jsonl_roundtrip(tmp_path):
    trace = bursty_trace(duration_s=3, base_rps=5, burst_rps=20,
                         burst_start_s=1, burst_end_s=2, seed=1)
    path = str(tmp_path / "trace.jsonl")
    dump_jsonl(trace, path)
    back = load_jsonl(path)
    assert [(r.t, r.tenant, r.prompt_tokens, r.max_tokens) for r in back] == \
        [(r.t, r.tenant, r.prompt_tokens, r.max_tokens) for r in trace]


# ---------------------------------------------------------------- launchers
def test_fake_launcher_cold_then_warm(tmp_path):
    async def scenario():
        store = PoolSnapshotStore(str(tmp_path))
        launcher = FakeReplicaLauncher(
            server_config=FakeServerConfig(),
            snapshots=store, engine_build_s=0.15)
        t0 = time.monotonic()
        h1 = await launcher.launch()
        cold_s = time.monotonic() - t0
        t0 = time.monotonic()
        h2 = await launcher.launch()
        warm_s = time.monotonic() - t0
        assert not h1.warm and h2.warm  # snapshot committed by first launch
        assert cold_s >= 0.15 and warm_s < cold_s
        # both actually serve
        async with aiohttp.ClientSession() as sess:
            for h in (h1, h2):
                async with sess.get(f"http://{h.address}/health") as r:
                    assert r.status == 200
        await launcher.stop(h1)
        await launcher.stop(h2)
        assert not launcher.alive(h1)

    run_async(scenario())


# --------------------------------------------------------------- controller
def _controller(tmp_path, **cfg_kw):
    cfg_kw.setdefault("min_replicas", 1)
    cfg_kw.setdefault("max_replicas", 4)
    cfg_kw.setdefault("interval_s", 3600)  # tests drive step() by hand
    cfg_kw.setdefault("sfz_interval_s", 0.02)
    cfg_kw.setdefault("drain_timeout_s", 2.0)
    launcher = FakeReplicaLauncher(
        server_config=FakeServerConfig(),
        snapshots=PoolSnapshotStore(str(tmp_path)))
    pool = EndpointPool()
    depth = {"v": 0.0}
    ctl = PoolController(PoolConfig(**cfg_kw), launcher, pool=pool,
                         flow_depth_fn=lambda: depth["v"])
    return ctl, pool, depth


def test_controller_launch_retire_and_discovery(tmp_path):
    async def scenario():
        ctl, pool, _ = _controller(tmp_path)
        await ctl.start()
        try:
            assert len(ctl.replicas) == 1  # reconciled to the floor
            assert [e.address for e in pool.list()] == sorted(ctl.replicas)
            await ctl.scale_to(3)
            assert len(ctl.replicas) == 3
            assert len(pool.list()) == 3  # discovery tracks the live set
            await ctl.scale_to(1)  # drain + retire the surplus
            assert len(ctl.replicas) == 1 and len(pool.list()) == 1
            kinds = [r.kind for r in ctl.launch_records]
            assert kinds[0] == "cold" and set(kinds[1:]) == {"warm"}
        finally:
            await ctl.stop()
        assert pool.list() == [] and ctl.replicas == {}

    run_async(scenario())


def test_controller_health_sweep_replaces_dead(tmp_path):
    async def scenario():
        ctl, pool, _ = _controller(tmp_path, min_replicas=2)
        await ctl.start()
        try:
            assert len(ctl.replicas) == 2
            victim = ctl.replicas[sorted(ctl.replicas)[0]]
            await victim.server.stop()  # dies without draining
            victim.server = None
            await ctl.step()  # sweep retires it, reconcile replaces it
            assert len(ctl.replicas) == 2
            assert victim.address not in ctl.replicas
            reasons = [e for e in (ctl.launch_records or [])]
            assert len(reasons) == 3  # 2 at start + 1 replacement
        finally:
            await ctl.stop()

    run_async(scenario())


def test_controller_scale_from_zero_on_queue(tmp_path):
    async def scenario():
        ctl, pool, depth = _controller(
            tmp_path, min_replicas=0, scale_to_zero=True, retention_s=0.05)
        await ctl.start()
        try:
            assert len(ctl.replicas) == 0  # floor of zero: nothing launched
            depth["v"] = 3.0  # requests piling up at the empty pool
            for _ in range(100):
                await asyncio.sleep(0.02)
                if ctl.replicas:
                    break
            assert len(ctl.replicas) == 1  # fast tick woke the pool
            assert len(pool.list()) == 1
            # traffic gone + retention elapsed → the full step zeroes it
            depth["v"] = 0.0
            await asyncio.sleep(0.1)
            await ctl.step()
            assert len(ctl.replicas) == 0
        finally:
            await ctl.stop()

    run_async(scenario())


def test_controller_predictor_state_enriches_metrics(tmp_path):
    """With the router's latency predictor in ctx, live ReplicaMetrics carry
    predicted TTFT/ITL — the SLOAnalyzer's inputs come from predictor state."""
    from types import SimpleNamespace

    from llmd_tpu.core.metrics_contract import StdMetric
    from llmd_tpu.pool.launcher import ReplicaHandle

    class StubPredictor:
        def predict(self, samples):
            assert samples[0].queue_depth == 2.0
            return [(120.0, 15.0)]  # ms

    pool = EndpointPool()
    ctl = PoolController(
        PoolConfig(), FakeReplicaLauncher(server_config=FakeServerConfig()),
        pool=pool,
        router=SimpleNamespace(ctx={"latency_predictor": StubPredictor()}),
        flow_depth_fn=lambda: 0.0)
    ep = Endpoint(address="10.0.0.1:8000")
    ep.attrs.put(StdMetric.QUEUED_REQUESTS, 2.0)
    ep.attrs.put(StdMetric.KV_UTILIZATION, 0.5)
    pool.upsert(ep)
    ctl.replicas[ep.address] = ReplicaHandle(address=ep.address)
    (rm,) = ctl._live_metrics()
    assert rm.avg_ttft_s == 0.12 and rm.avg_itl_s == 0.015
    # no predictor in ctx → plain scraped metrics, no enrichment
    ctl.router = SimpleNamespace(ctx={})
    (rm,) = ctl._live_metrics()
    assert rm.avg_ttft_s == 0.0


# --------------------------------------------- router eviction (regression)
ROUTER_CFG = """
plugins:
  - {name: inflight, type: inflight-load-producer}
  - {name: queue, type: queue-depth-scorer}
schedulingProfiles:
  - name: default
    plugins:
      - {pluginRef: queue, weight: 1}
"""


KV_ROUTER_CFG = ROUTER_CFG + """
kvEvents:
  bindPort: 0
"""


def test_kv_index_bounded_under_pool_churn():
    """Centralized kvEvents mode (bindPort): the subscriber binds a socket and
    never watches the pool, so the ROUTER's pool listener must evict departed
    pods from the block index — same listener that forgets breaker/poller
    state. Without it, kill/relaunch churn grows the index without bound."""

    async def scenario():
        from llmd_tpu.core.kv_events import BlockStored
        from llmd_tpu.kv.plugins import CTX_KV_INDEX

        pool = EndpointPool()
        cfg = FrameworkConfig.from_yaml(KV_ROUTER_CFG,
                                        known_types=known_plugin_types())
        router = RouterServer(cfg, pool, port=0, poll_interval_s=3600)
        await router.start()
        try:
            idx = router.ctx[CTX_KV_INDEX]
            for i in range(50):  # kill/relaunch churn: add, publish, remove
                addr = f"10.9.1.{i % 8}:{9100 + i}"
                pool.upsert(Endpoint(address=addr))
                idx.apply(addr, BlockStored(
                    block_hashes=[i * 100 + j for j in range(10)],
                    parent_block_hash=None, token_ids=[0] * 160,
                    block_size=16))
                assert len(idx) == 10
                pool.remove(addr)
                assert len(idx) == 0  # departure evicted the pod's blocks
        finally:
            await router.stop()

    run_async(scenario())


def test_router_forgets_departed_endpoints():
    async def scenario():
        pool = EndpointPool()
        cfg = FrameworkConfig.from_yaml(ROUTER_CFG,
                                        known_types=known_plugin_types())
        router = RouterServer(cfg, pool, port=0, poll_interval_s=3600)
        await router.start()
        try:
            for i in range(50):  # scale churn: add, dirty, remove
                addr = f"10.9.0.{i % 8}:{9000 + i}"
                pool.upsert(Endpoint(address=addr))
                router.resilience.on_failure(addr, reason="http 503")
                router.resilience.set_draining(addr, True)
                router.poller.error_counts[addr] = 1
                router.poller.error_counts[f"{addr}:core-metrics-extractor"] = 2
                pool.remove(addr)
                # the pool listener must evict breaker + poller state
                assert addr not in router.resilience._breakers
                assert addr not in router.resilience._draining
                assert not any(k == addr or k.startswith(addr + ":")
                               for k in router.poller.error_counts)
            assert router.resilience.snapshot()["breakers"] == {}
        finally:
            await router.stop()

    run_async(scenario())
