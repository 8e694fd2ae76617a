"""Regression tests for the round-2 advisor findings (ADVICE.md): each test
pins the fixed behavior so the finding cannot silently reopen."""

from __future__ import annotations

import asyncio
import json

import pytest

from tests.conftest import run_async


# ---------------------------------------------------------------- LoRA keys


def test_precise_producer_resolves_learned_lora_generation_key():
    """Engine publishes BlockStored under 'name@digest'; after the indexer learns
    the mapping, router-side precise prefix scoring for plain-name adapter
    traffic must produce NONZERO hits (was: permanently 0 for LoRA traffic)."""
    from llmd_tpu.core.kv_events import BlockStored, block_keys_for_tokens
    from llmd_tpu.core.request import InferenceRequest
    from llmd_tpu.core.endpoint import Endpoint
    from llmd_tpu.kv.plugins import PrecisePrefixCacheProducer
    from llmd_tpu.router.scorers import STATE_PREFIX_HITS, STATE_TOKEN_IDS

    ctx: dict = {}
    prod = PrecisePrefixCacheProducer(ctx, blockSize=4)
    tokens = list(range(16))
    gen_key = "my-adapter@abc123digest"
    engine_keys = block_keys_for_tokens(tokens, 4, gen_key)
    # engine-side event stream: blocks hashed under the generation-scoped key
    prod.index.apply("pod-a:8000", BlockStored(
        block_hashes=engine_keys, parent_block_hash=None, token_ids=tokens,
        block_size=4, lora_id=gen_key))

    req = InferenceRequest(model="m", lora_adapter="my-adapter")
    req.state[STATE_TOKEN_IDS] = tokens
    prod.produce(req, [Endpoint(address="pod-a:8000")])
    assert req.state[STATE_PREFIX_HITS]["pod-a:8000"] == 16, (
        "router-side hashes must match engine generation-scoped hashes")

    # unknown adapter: falls back to the plain name without raising
    req2 = InferenceRequest(model="m", lora_adapter="never-seen")
    req2.state[STATE_TOKEN_IDS] = tokens
    prod.produce(req2, [Endpoint(address="pod-a:8000")])
    assert req2.state[STATE_PREFIX_HITS]["pod-a:8000"] == 0


def test_index_resolve_lora_key_fallback():
    from llmd_tpu.kv.indexer import KVBlockIndex

    idx = KVBlockIndex()
    assert idx.resolve_lora_key(None) is None
    assert idx.resolve_lora_key("") == ""
    assert idx.resolve_lora_key("a") == "a"  # unlearned → plain name
    idx._lora_keys["a"] = "a@d1"
    assert idx.resolve_lora_key("a") == "a@d1"


# ------------------------------------------------------- request content parts


def test_flatten_messages_tolerates_string_parts():
    """A bare-string content part must not raise (was AttributeError → 500)."""
    from llmd_tpu.core.request import flatten_messages, mm_hashes_from_messages

    msgs = [{"role": "user", "content": ["look at ", {"type": "text", "text": "this"},
                                         42]}]
    out = flatten_messages(msgs)
    assert "look at" in out and "this" in out and "42" in out
    assert mm_hashes_from_messages(msgs) == []


# ---------------------------------------------------- batch gateway semaphores


def test_hot_model_backlog_does_not_starve_other_models(tmp_path):
    """global=3, per-model=1: a hot model's 3 blocked requests must occupy ONE
    global slot (queueing at their own per-model semaphore), leaving global
    capacity for another model's batch (was: global acquired first → starved)."""
    from llmd_tpu.batch.gateway import BatchGateway, BatchGatewayConfig

    async def scenario():
        gw = BatchGateway(BatchGatewayConfig(
            files_root=str(tmp_path), global_concurrency=3,
            per_model_concurrency=1))
        hot_gate = asyncio.Event()

        async def fake_dispatch(row, req):
            if req["body"]["model"] == "hot":
                await hot_gate.wait()
            return {"status_code": 200, "body": {"ok": True}}

        gw._dispatch = fake_dispatch

        def mk_batch(model, n):
            lines = "\n".join(json.dumps({
                "custom_id": f"{model}-{i}", "method": "POST",
                "url": "/v1/completions", "body": {"model": model, "prompt": "p"},
            }) for i in range(n)).encode()
            meta = gw.files.put("t", "in.jsonl", lines)
            return gw.store.create("t", meta.id, "/v1/completions")

        row_hot, row_cold = mk_batch("hot", 3), mk_batch("cold", 1)
        t_hot = asyncio.create_task(gw._run_batch(row_hot))
        await asyncio.sleep(0.05)  # hot batch parks: 1 dispatching, 2 queued
        t_cold = asyncio.create_task(gw._run_batch(row_cold))
        await asyncio.wait_for(t_cold, timeout=2.0)  # must NOT be starved
        assert row_cold.status == "completed"
        hot_gate.set()
        await asyncio.wait_for(t_hot, timeout=2.0)
        assert row_hot.status == "completed"

    run_async(scenario())


# ------------------------------------------------------- async-processor nack


def test_memory_puller_nack_wakes_parked_getter():
    """A worker parked in get() must wake when an item is nacked back (was: no
    notify → redelivery waited for an unrelated put())."""
    from llmd_tpu.batch.async_processor import AsyncItem, MemoryQueuePuller

    async def scenario():
        q = MemoryQueuePuller()
        item = AsyncItem(id="i1", url="/v1/completions", body={})
        getter = asyncio.create_task(q.get())
        await asyncio.sleep(0.01)  # park the getter on the condition
        q.nack(item)
        got = await asyncio.wait_for(getter, timeout=1.0)
        assert got.id == "i1"

    run_async(scenario())


# ----------------------------------------------------------- dp_group report


def test_dp_engine_drops_to_solo_after_coordinator_outage():
    """After a report() failure the engine must deregister and serve solo on the
    paced re-register schedule — NOT re-attempt a blocking connect every step."""
    from llmd_tpu.engine.dp_group import DPAsyncEngine, DPWorkerSync
    from llmd_tpu.obs.metrics import EngineMetrics, Registry

    class FakeEngine:
        def __init__(self):
            self.stepped = 0
            # the loop books its turns here, as the base loop does
            self.metrics = EngineMetrics(Registry())

        def has_work(self):
            return True

        def step(self):
            self.stepped += 1
            return []

    class DeadWorker(DPWorkerSync):
        def __init__(self):
            super().__init__(rank=0, host="127.0.0.1", port=1)
            self.report_calls = 0

        def register(self, barrier_timeout_s=30.0):
            raise ConnectionError("coordinator down")

        def report(self, has_work):
            self.report_calls += 1
            raise ConnectionError("coordinator down")

    eng = FakeEngine()
    worker = DeadWorker()
    ae = DPAsyncEngine(eng, worker, register_retry_interval_s=60.0)
    ae.registered = True  # simulate: was registered, coordinator then died
    ae._next_register = float("inf")  # freeze re-registration for the test

    # drive the loop body a few ticks in a thread
    ae.start()
    import time as _t

    deadline = _t.monotonic() + 2.0
    while eng.stepped < 5 and _t.monotonic() < deadline:
        _t.sleep(0.01)
    ae.stop()
    assert eng.stepped >= 5, "engine must keep stepping solo"
    assert worker.report_calls == 1, (
        "exactly one failed report; no per-step reconnect attempts")
    assert ae.registered is False and ae.register_failures >= 1


def test_dp_worker_report_raises_on_outage():
    from llmd_tpu.engine.dp_group import DPWorkerSync

    w = DPWorkerSync(rank=0, host="127.0.0.1", port=1, timeout_s=0.2)
    with pytest.raises((OSError, ConnectionError)):
        w.report(True)


# ------------------------------------------------- r4: sticky routing roles


def test_sticky_endpoint_skips_prefill_only_pods():
    """Conversation rendezvous hashing must only consider decode-capable pods:
    a prefill-only pod has no Conversations state and no decode path (was:
    hashed over pool.list() unfiltered)."""
    from llmd_tpu.core.endpoint import Endpoint, EndpointRole
    from llmd_tpu.router.datalayer import EndpointPool
    from llmd_tpu.router.server import RouterServer
    from llmd_tpu.core.config import FrameworkConfig
    from llmd_tpu.router.plugins import known_plugin_types

    pool = EndpointPool()
    pool.upsert(Endpoint(address="p1:8000", role=EndpointRole.PREFILL))
    pool.upsert(Endpoint(address="p2:8000", role=EndpointRole.PREFILL))
    pool.upsert(Endpoint(address="d1:8000", role=EndpointRole.DECODE))
    cfg = FrameworkConfig.from_yaml(
        """
plugins:
  - {name: queue, type: queue-depth-scorer}
schedulingProfiles:
  - name: default
    plugins:
      - {pluginRef: queue, weight: 1}
""", known_types=known_plugin_types())
    srv = RouterServer(cfg, pool, port=0)
    # over many conversation ids, NO pick may land on a prefill pod
    for i in range(64):
        ep = srv._sticky_endpoint(f"conv_{i}")
        assert ep.address == "d1:8000"
    pool.upsert(Endpoint(address="d2:8000", role=EndpointRole.BOTH))
    picks = {srv._sticky_endpoint(f"conv_{i}").address for i in range(64)}
    assert picks <= {"d1:8000", "d2:8000"} and len(picks) == 2


# ------------------------------------------- r4: conversation growth bounded


def test_conversation_item_growth_is_capped():
    """One long-lived conversation must not grow pod memory without bound:
    past the per-conversation cap the oldest items roll off."""
    from llmd_tpu.engine.server import EngineServer

    srv = EngineServer.__new__(EngineServer)  # _conv_trim needs no engine
    srv._max_conv_items = 512
    conv = {"items": [{"n": i} for i in range(600)]}
    srv._conv_trim(conv)
    assert len(conv["items"]) == 512
    assert conv["items"][0] == {"n": 88} and conv["items"][-1] == {"n": 599}
    srv._conv_trim(conv)  # idempotent at the cap
    assert len(conv["items"]) == 512


def test_dp_worker_report_raises_on_error_response():
    """A coordinator ERROR reply (no 'step' key: corrupted line, version skew)
    must raise like an outage — not KeyError past the solo-mode handling and
    kill the engine loop thread."""
    from llmd_tpu.engine.dp_group import DPWorkerSync

    w = DPWorkerSync(rank=0, host="127.0.0.1", port=1)
    w._rpc = lambda msg: {"error": "unknown cmd"}
    with pytest.raises(ConnectionError, match="error response"):
        w.report(True)
