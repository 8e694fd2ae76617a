"""P/D disaggregation tests: transfer roundtrip, sidecar flow, e2e correctness.

Mirrors the reference's disaggregation semantics (disaggregation/README.md): the
decode output through the P/D path must equal the aggregated path (KV transfer is
exact, not approximate), prefill-side blocks are freed on notify, and a dead
prefiller degrades to decoder-only fallback.
"""

import asyncio

import aiohttp
import numpy as np
import jax.numpy as jnp

from llmd_tpu.core.kv_events import block_keys_for_tokens
from llmd_tpu.core.request import HDR_PREFILLER_HOST_PORT
from llmd_tpu.disagg.sidecar import RoutingSidecar
from llmd_tpu.disagg.transfer import (
    KVTransferClient,
    KVTransferSource,
    extract_blocks,
    insert_blocks,
)
from llmd_tpu.engine.config import EngineConfig
from llmd_tpu.engine.server import EngineServer
from llmd_tpu.models import get_model_config
from tests.conftest import run_async


def test_extract_insert_roundtrip():
    # flat layer-folded pool [L*P, ps, 2Hk, Dhp] with L=2, P=6
    cache = jnp.arange(2 * 6 * 4 * 2 * 3, dtype=jnp.float32).reshape(12, 4, 2, 3)
    blocks = extract_blocks(cache, [1, 4], pages_per_layer=6)
    assert blocks.shape == (2, 2, 4, 2, 3)  # [n, L, ps, 2Hk, Dhp]
    target = jnp.zeros_like(cache)
    out = insert_blocks(target, [0, 5], blocks, pages_per_layer=6)
    for l in range(2):
        np.testing.assert_array_equal(np.asarray(out[l * 6 + 0]), np.asarray(cache[l * 6 + 1]))
        np.testing.assert_array_equal(np.asarray(out[l * 6 + 5]), np.asarray(cache[l * 6 + 4]))
        np.testing.assert_array_equal(np.asarray(out[l * 6 + 2]), 0)


import pytest


@pytest.mark.parametrize("transport", ["python", "native"])
def test_transfer_pull_and_notify(transport):
    if transport == "native":
        from llmd_tpu.native import native_available

        if not native_available("kv_transfer"):
            pytest.skip("g++ build unavailable")
    src = KVTransferSource(host="127.0.0.1", transport=transport)
    src.start()
    try:
        blocks = np.arange(2 * 3 * 2 * 4 * 2 * 3, dtype=np.float32).reshape(2, 3, 2, 4, 2, 3)
        src.register("req-1", [11, 22], [[1, 2], [3, 4]], blocks)
        cli = KVTransferClient(timeout_s=5)
        pulled = cli.pull("127.0.0.1", src.port, "req-1")
        assert pulled is not None
        assert pulled.block_hashes == [11, 22]
        assert pulled.token_chunks == [[1, 2], [3, 4]]
        np.testing.assert_array_equal(pulled.blocks, blocks)
        # unknown id → miss, not error
        assert cli.pull("127.0.0.1", src.port, "nope") is None
        # notify frees the export
        assert cli.notify("127.0.0.1", src.port, "req-1")
        assert len(src) == 0
        assert src.stats["pulls"] == 1 and src.stats["notifies"] == 1
        assert (src.native is not None) == (transport == "native")
    finally:
        src.stop()


def _engine_cfg():
    return EngineConfig(page_size=8, num_pages=64, max_model_len=256,
                        max_batch_size=4, prefill_chunk=32)


PROMPT = "the quick brown fox jumps over the lazy dog and keeps on running far"


async def _pd_scenario(model: str = "tiny"):
    cfg = get_model_config(model)
    # identical seed → identical weights on P, D, and the aggregated control engine
    prefill = EngineServer(cfg, _engine_cfg(), model_name="m", host="127.0.0.1",
                           port=0, kv_transfer_port=0)
    decode = EngineServer(cfg, _engine_cfg(), model_name="m", host="127.0.0.1",
                          port=0, kv_transfer_port=0)
    control = EngineServer(cfg, _engine_cfg(), model_name="m", host="127.0.0.1", port=0)
    await prefill.start()
    await decode.start()
    await control.start()
    sidecar = RoutingSidecar(decode_addr=decode.address, host="127.0.0.1", port=0)
    await sidecar.start()
    try:
        body = {"prompt": PROMPT, "max_tokens": 8, "temperature": 0.0, "ignore_eos": True}
        async with aiohttp.ClientSession() as sess:
            # control: aggregated single-engine output
            r = await sess.post(f"http://{control.address}/v1/completions", json=body)
            expected = (await r.json())["choices"][0]["text"]

            # P/D path through the sidecar
            r = await sess.post(
                f"http://{sidecar.address}/v1/completions", json=body,
                headers={HDR_PREFILLER_HOST_PORT: prefill.address},
            )
            assert r.status == 200, await r.text()
            got = await r.json()
            assert got["choices"][0]["text"] == expected
            # decode reused transferred KV: complete prompt blocks were cached
            # (admission reuses at most (prompt_len-1)//ps blocks — the final token's
            # logits must be computed locally)
            n_blocks = len(block_keys_for_tokens(list(PROMPT.encode()), 8))
            n_reusable = min(n_blocks, (len(PROMPT.encode()) - 1) // 8)
            assert got["usage"]["cached_tokens"] == n_reusable * 8
            assert decode.transfer_stats["injected_blocks"] == n_blocks
            # notify freed prefill-side exports
            assert len(prefill.transfer_source) == 0
            assert prefill.transfer_source.stats["notifies"] == 1
            assert sidecar.stats["pd_requests"] == 1

            # streaming through the P/D path works end to end
            r = await sess.post(
                f"http://{sidecar.address}/v1/completions",
                json={**body, "stream": True},
                headers={HDR_PREFILLER_HOST_PORT: prefill.address},
            )
            text = ""
            async for line in r.content:
                line = line.decode().strip()
                if line.startswith("data: ") and line != "data: [DONE]":
                    import json as _json

                    text += _json.loads(line[6:])["choices"][0]["text"]
            assert text == expected

            # dead prefiller → decoder-only fallback still answers correctly
            r = await sess.post(
                f"http://{sidecar.address}/v1/completions", json=body,
                headers={HDR_PREFILLER_HOST_PORT: "127.0.0.1:1"},
            )
            assert r.status == 200
            assert (await r.json())["choices"][0]["text"] == expected
            assert sidecar.stats["prefill_fallbacks"] == 1

            # no header → plain aggregated proxying
            r = await sess.post(f"http://{sidecar.address}/v1/completions", json=body)
            assert r.status == 200
            assert (await r.json())["choices"][0]["text"] == expected

            # passthrough routes (health/metrics) proxy to the decode engine
            r = await sess.get(f"http://{sidecar.address}/health")
            assert r.status == 200
            r = await sess.get(f"http://{sidecar.address}/metrics")
            assert "llmd_tpu:kv_transfer_injected_blocks_total" in await r.text()
    finally:
        await sidecar.stop()
        await prefill.stop()
        await decode.stop()
        await control.stop()


def test_pd_disaggregation_e2e():
    run_async(_pd_scenario())


def test_pd_disaggregation_e2e_mla():
    """P/D with MLA latent pages: the transferred KV is the single-plane
    latent pool — 4x fewer bytes per block than the GQA equivalent — and the
    decode side must reproduce the aggregated control output exactly."""
    run_async(_pd_scenario("tiny-mla"))


async def _stale_pull_scenario():
    """Hash-chain verification: decode must reject an export for a DIFFERENT prompt."""
    cfg = get_model_config("tiny")
    prefill = EngineServer(cfg, _engine_cfg(), model_name="m", host="127.0.0.1",
                           port=0, kv_transfer_port=0)
    decode = EngineServer(cfg, _engine_cfg(), model_name="m", host="127.0.0.1",
                          port=0, kv_transfer_port=0)
    await prefill.start()
    await decode.start()
    try:
        async with aiohttp.ClientSession() as sess:
            r = await sess.post(f"http://{prefill.address}/v1/completions", json={
                "prompt": PROMPT, "max_tokens": 1, "temperature": 0.0, "ignore_eos": True,
                "kv_transfer_params": {"do_remote_decode": True},
            })
            ktp = (await r.json())["kv_transfer_params"]
            # decode a DIFFERENT prompt claiming that transfer handle
            r = await sess.post(f"http://{decode.address}/v1/completions", json={
                "prompt": "a completely different prompt that shares no prefix at all!",
                "max_tokens": 4, "temperature": 0.0, "ignore_eos": True,
                "kv_transfer_params": {"do_remote_prefill": True, **ktp},
            })
            assert r.status == 200
            got = await r.json()
            assert got["usage"]["cached_tokens"] == 0  # nothing injected
            assert decode.transfer_stats["injected_blocks"] == 0
    finally:
        await prefill.stop()
        await decode.stop()


def test_stale_transfer_rejected():
    run_async(_stale_pull_scenario())


# ---------------------------------------------------------------------------
# Async two-phase staging (VERDICT r3 directive #8): export_begin dispatches
# the D2H gathers under the lock; export_finish drains them off-lock.
# ---------------------------------------------------------------------------

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.disagg.transfer import (
    StagedExport,
    export_begin,
    export_finish,
    export_from_engine,
)
from llmd_tpu.engine import LLMEngine


def _staged_engine():
    cfg = get_model_config("tiny")
    eng = LLMEngine(cfg, _engine_cfg())
    prompt = list(range(40, 40 + 24))  # 3 full pages at page_size=8
    eng.generate([prompt], SamplingParams(max_tokens=2, temperature=0.0, ignore_eos=True))
    return eng, prompt


def test_export_two_phase_matches_sync():
    eng, prompt = _staged_engine()
    sync_src = KVTransferSource(host="127.0.0.1")
    async_src = KVTransferSource(host="127.0.0.1")
    sync_src.start(), async_src.start()
    try:
        p1 = export_from_engine(eng, sync_src, "sync-1", prompt)
        p2, staged = export_begin(eng, "async-1", prompt, staging_pages=2)
        assert p2.num_blocks == p1.num_blocks > 0
        assert isinstance(staged, StagedExport)
        assert len(staged.parts) == (p2.num_blocks + 1) // 2  # chunked gathers
        export_finish(staged, async_src)
        cli = KVTransferClient(timeout_s=5)
        a = cli.pull("127.0.0.1", sync_src.port, "sync-1")
        b = cli.pull("127.0.0.1", async_src.port, "async-1")
        assert a is not None and b is not None
        assert a.block_hashes == b.block_hashes
        np.testing.assert_array_equal(a.blocks, b.blocks)
    finally:
        sync_src.stop(), async_src.stop()


def test_export_begin_never_blocks_on_device(monkeypatch):
    """The lock-held phase must not drain device→host — only dispatch.

    Simulates a slow (~70 ms) blocking fetch by making device_get sleep;
    export_begin must stay fast (TTFT protection), the drain pays the cost."""
    import time as _time

    import jax as _jax

    eng, prompt = _staged_engine()
    src = KVTransferSource(host="127.0.0.1")
    src.start()
    real_get = _jax.device_get
    calls = []

    def counting_get(x):
        calls.append(_time.sleep(0.05))
        return real_get(x)

    try:
        monkeypatch.setattr(_jax, "device_get", counting_get)
        params, staged = export_begin(eng, "slow-1", prompt, staging_pages=1)
        assert params.num_blocks >= 3
        assert calls == []  # the locked phase only dispatches — never drains
        t0 = _time.perf_counter()
        export_finish(staged, src)
        finish_s = _time.perf_counter() - t0
        assert len(calls) == params.num_blocks  # one drain per staged chunk
        assert finish_s >= 0.05 * params.num_blocks
    finally:
        src.stop()


def test_export_survives_engine_steps():
    """Gathers read the cache value as of dispatch: steps between begin and
    finish (even ones that recycle pages) cannot corrupt the staged export."""
    eng, prompt = _staged_engine()
    src_ref = KVTransferSource(host="127.0.0.1")
    src = KVTransferSource(host="127.0.0.1")
    src_ref.start(), src.start()
    try:
        export_from_engine(eng, src_ref, "ref-1", prompt)  # ground truth now
        _, staged = export_begin(eng, "live-1", prompt)
        # churn: fill the pool with fresh sequences before draining
        eng.generate([list(range(200, 232)), list(range(300, 332))],
                     SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True))
        export_finish(staged, src)
        cli = KVTransferClient(timeout_s=5)
        a = cli.pull("127.0.0.1", src_ref.port, "ref-1")
        b = cli.pull("127.0.0.1", src.port, "live-1")
        assert a.block_hashes == b.block_hashes
        np.testing.assert_array_equal(a.blocks, b.blocks)
    finally:
        src_ref.stop(), src.stop()
