"""OpenAI-compatible HTTP server over the TPU engine.

Implements the model-server contract the router consumes (reference
docs/architecture/core/model-servers.md): OpenAI endpoints (+SSE streaming), render
endpoints for the router's token-producer (kv-indexer.md:104-113), Prometheus /metrics
with vLLM-compatible names (:38-52), /health probes (:81-86), and ZMQ KV-event
publishing in pod-discovery mode (kv-indexer.md:67-87).

P/D disaggregation (disaggregation/README.md): with ``kv_transfer_port`` set, the
server exposes the KV-transfer side channel — requests carrying
``kv_transfer_params.do_remote_decode`` export their prefill KV for remote pull;
requests carrying ``do_remote_prefill`` pull + inject remote KV before compute
(falling back to recompute on any failure).

Run: python -m llmd_tpu.engine.serve --model tiny --port 8000
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
import uuid
from typing import Optional

from aiohttp import web

from llmd_tpu.core.kv_events import KVEvent, encode_event_batch, kv_topic
from llmd_tpu.core.request import (
    HDR_REQUEST_TIMEOUT,
    SamplingParams,
    flatten_messages,
)
from llmd_tpu.disagg.transfer import (
    KVTransferParams,
    export_begin,
    export_finish,
    inject_into_engine,
)
from llmd_tpu.engine.async_engine import AsyncLLMEngine
from llmd_tpu.engine.config import EngineConfig
from llmd_tpu.engine.engine import LLMEngine
from llmd_tpu.engine.tokenizer import Tokenizer, load_tokenizer
from llmd_tpu.models.config import ModelConfig
from llmd_tpu.structured import validate_structured_body


def _body_has_media(body: dict) -> bool:
    from llmd_tpu.disagg.encode import iter_media_parts

    return bool(body.get("mm_items")) or next(iter_media_parts(body), None) is not None


def _sampling_from_body(body: dict) -> SamplingParams:
    return SamplingParams(
        max_tokens=int(body.get("max_tokens", 16)),
        temperature=float(body.get("temperature", 1.0)),
        top_p=float(body.get("top_p", 1.0)),
        top_k=int(body.get("top_k", 0)),
        stop=body.get("stop") or (),
        seed=body.get("seed"),
        n=int(body.get("n", 1)),
        presence_penalty=float(body.get("presence_penalty", 0.0)),
        frequency_penalty=float(body.get("frequency_penalty", 0.0)),
        ignore_eos=bool(body.get("ignore_eos", False)),
        guided_choice=body.get("guided_choice"),
        guided_regex=body.get("guided_regex"),
        response_format=body.get("response_format"),
        logit_bias=body.get("logit_bias"),
    )


class EngineServer:
    def __init__(
        self,
        model_cfg: ModelConfig,
        engine_cfg: EngineConfig,
        model_name: str = "llmd-tpu/model",
        host: str = "127.0.0.1",
        port: int = 8000,
        kv_events_port: Optional[int] = None,
        kv_transfer_port: Optional[int] = None,
        tokenizer: Optional[Tokenizer] = None,
        params=None,
        engine: Optional[LLMEngine] = None,
        async_engine: Optional["AsyncLLMEngine"] = None,
        rank: int = 0,
        predictor_train_url: Optional[str] = None,
    ) -> None:
        self.model_name = model_name
        self.host, self.port = host, port
        self.tokenizer = tokenizer or load_tokenizer()
        self.kv_events_port = kv_events_port
        self.kv_transfer_port = kv_transfer_port
        self.advertise_host: Optional[str] = None  # routable host for transfer handles
        self.transfer_source = None
        self.transfer_client = None
        self.transfer_stats = {"injected_blocks": 0, "pull_failures": 0,
                               "prefix_pulls": 0, "prefix_pull_blocks": 0,
                               "released": 0}
        # KV-plane pulls whose peer-side registration may still be live:
        # local rid → (host, port, remote_request_id). Released on request
        # retire/abort so a dead puller never pins peer exports until TTL.
        self._pending_pulls: dict[str, tuple] = {}
        self._zctx = None
        self._pub = None
        self._kv_seq = 0
        # training-sidecar feed: completed requests' latency rows stream to the
        # predictor's POST /samples (the reference's vllm→trainer scrape flow)
        self.predictor_train_url = predictor_train_url
        self._pending_events: list[KVEvent] = []
        self._ev_lock = __import__("threading").Lock()

        # Wide-EP rank frontends share one engine + step loop; each server is a
        # router-visible endpoint feeding its own rank queue (decode.yaml rank
        # ports semantics). Standalone servers build their own engine.
        self.rank = rank
        if engine is not None:
            if async_engine is None:
                # two private step loops over one engine would race the scheduler
                raise ValueError("a shared engine requires the shared async_engine")
            self.engine = engine
            self.async_engine = async_engine
            if engine.tokenizer is None:
                # shared engines built without one still serve structured
                # requests through this frontend's tokenizer
                engine.tokenizer = self.tokenizer
            # this frontend's rank publishes its own KV events
            if rank < len(engine.allocs):
                engine.allocs[rank].event_sink = self._on_kv_events
        else:
            self.engine = LLMEngine(model_cfg, engine_cfg, params=params,
                                    event_sink=self._on_kv_events,
                                    tokenizer=self.tokenizer)
            self.async_engine = AsyncLLMEngine(self.engine)
        self._runner: Optional[web.AppRunner] = None
        self.request_count = 0
        # Device-plane monitor (obs/device.py): created at start() by the
        # server that owns the engine; wide-EP rank frontends share the
        # engine's instance and only the creator stops it.
        self.monitor = None
        self._owns_monitor = False
        # graceful drain (POST /drain): admissions stop, in-flight requests
        # finish, /health reports draining so the router routes around us
        self._draining = False
        self._vision = None  # lazy in-process vision tower (combined-PD mode)
        self._vision_lock = __import__("threading").Lock()  # one compile, ever
        # Conversations API store (pod-local; router keeps traffic sticky by
        # id). LRU-capped: abandoned conversations must not grow without bound.
        from collections import OrderedDict

        self._conversations: "OrderedDict[str, dict]" = OrderedDict()
        self._max_conversations = 4096
        # per-conversation growth is ALSO capped: one long-lived conversation
        # appending forever must not grow pod memory unboundedly — past the
        # cap the oldest items roll off (context-window semantics)
        self._max_conv_items = 512
        from llmd_tpu.obs.tracing import global_tracer

        self.tracer = global_tracer()  # engine hop joins the EPP trace
        # Frontend-owned metric families live in a per-server registry (each
        # wide-EP rank frontend counts its own requests/transfers); engine-
        # loop families live in engine.registry. /metrics renders both.
        from llmd_tpu.obs.metrics import Registry, register_engine_server_metrics

        self.registry = Registry()
        self.server_metrics = register_engine_server_metrics(self.registry)
        self.server_metrics.requests.set_function(lambda: self.request_count)
        for key in ("injected_blocks", "pull_failures", "prefix_pulls",
                    "prefix_pull_blocks", "released"):
            self.server_metrics.transfer[key].set_function(
                lambda k=key: self.transfer_stats[k])
        for key in ("exports", "pulls", "notifies", "expired"):
            self.server_metrics.transfer[key].set_function(
                lambda k=key: self.transfer_source.stats.get(k, 0)
                if self.transfer_source is not None else 0)
        self.server_metrics.transfer_registrations.set_function(
            lambda: len(self.transfer_source)
            if self.transfer_source is not None else 0)
        # durable prefix tier (kv/writeback.py): flush-queue depth + breaker
        # gauges read live state; the flush counter and kv_flush flight event
        # are driven by the queue's on_flush callback (worker thread)
        self.server_metrics.kv_durable_queue_depth.set_function(
            lambda: self.engine.writeback.depth()
            if getattr(self.engine, "writeback", None) is not None else 0)
        self.server_metrics.kv_durable_breaker.set_function(
            lambda: self.engine.durable.breaker_state()
            if getattr(self.engine, "durable", None) is not None else 0.0)
        wb = getattr(self.engine, "writeback", None)
        if wb is not None and wb.on_flush is None:

            def _on_flush(outcome: str, n_blocks: int) -> None:
                self.server_metrics.kv_durable_flush.labels(
                    outcome=outcome).inc(n_blocks)
                self.engine.flight.record_system(
                    "kv_flush", outcome=outcome, n_blocks=n_blocks)

            wb.on_flush = _on_flush

    # -- KV events ---------------------------------------------------------
    def _on_kv_events(self, events: list[KVEvent]) -> None:
        """Called from the engine thread; buffered, flushed on the event loop."""
        if self.kv_events_port is None:
            return
        with self._ev_lock:
            self._pending_events.extend(events)

    async def _kv_flush_loop(self) -> None:
        import zmq

        while True:
            await asyncio.sleep(0.01)
            with self._ev_lock:
                events, self._pending_events = self._pending_events, []
            if events and self._pub is not None:
                self._kv_seq += 1
                topic = kv_topic(self.address, self.model_name).encode()
                try:
                    await self._pub.send_multipart(
                        [topic, encode_event_batch(events, self._kv_seq)], flags=zmq.NOBLOCK
                    )
                except Exception:
                    pass  # PUB with no subscribers / full HWM: drop (fire-and-forget)

    async def _trace_flush_loop(self) -> None:
        """Forward engine-emitted latency rows to the predictor trainer."""
        import aiohttp

        while True:
            await asyncio.sleep(1.0)
            rows = self.engine.drain_latency_trace()
            if not rows:
                continue
            try:
                async with aiohttp.ClientSession() as sess:
                    await sess.post(f"{self.predictor_train_url}/samples",
                                    json={"samples": rows},
                                    timeout=aiohttp.ClientTimeout(total=2.0))
            except Exception:
                pass  # trainer down: rows already drained, next batch retries fresh

    # -- lifecycle ---------------------------------------------------------
    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    async def start(self) -> None:
        self.async_engine.start()
        from llmd_tpu.obs.device import DeviceMonitor

        mon = getattr(self.engine, "monitor", None)
        if mon is None:
            # pending_fn reads engine.seqs truthiness lock-free (GIL-atomic):
            # the watchdog must never wait on the engine lock — a hung step()
            # holds it, and that hang is exactly what it detects
            mon = DeviceMonitor(
                self.engine.registry, flight=self.engine.flight,
                pending_fn=lambda: bool(self.engine.seqs))
            self.engine.monitor = mon
            mon.start()
            self._owns_monitor = True
        self.monitor = mon
        if self.kv_transfer_port is not None:
            from llmd_tpu.disagg.transfer import KVTransferClient, KVTransferSource

            self.transfer_source = KVTransferSource(port=self.kv_transfer_port)
            from llmd_tpu.kvplane import plane_mode, serve_prefix

            if plane_mode() == "precise":
                # KV plane: serve peers' pull_prefix requests from the local
                # prefix cache (set before start(): selects the python
                # transport, which speaks the op; LLMD_KV_PLANE=off keeps the
                # transfer source byte-identical to the pre-plane behavior)
                self.transfer_source.prefix_provider = (
                    lambda hashes, rid: serve_prefix(self, hashes, rid))
            self.transfer_source.start()
            self.kv_transfer_port = self.transfer_source.port
            self.transfer_client = KVTransferClient()
        app = web.Application(client_max_size=32 * 1024 * 1024)
        app.router.add_post("/v1/completions", self._completions)
        app.router.add_post("/v1/chat/completions", self._chat)
        app.router.add_post("/v1/completions/render", self._render)
        app.router.add_post("/v1/chat/completions/render", self._render)
        app.router.add_get("/metrics", self._metrics)
        app.router.add_get("/health", self._health)
        app.router.add_post("/drain", self._drain)
        app.router.add_get("/v1/models", self._models)
        app.router.add_post("/v1/load_lora_adapter", self._load_lora)
        app.router.add_post("/v1/unload_lora_adapter", self._unload_lora)
        app.router.add_post("/v1/embeddings", self._embeddings)
        # OpenAI Responses + Conversations APIs (epp-http-apis.md:11,153-183;
        # request-handling.md:73 lists both under the openai parser)
        app.router.add_post("/v1/responses", self._responses)
        app.router.add_post("/v1/conversations", self._conv_create)
        app.router.add_get("/v1/conversations/{cid}", self._conv_get)
        app.router.add_delete("/v1/conversations/{cid}", self._conv_delete)
        app.router.add_post("/v1/conversations/{cid}/items", self._conv_add_items)
        app.router.add_get("/v1/conversations/{cid}/items", self._conv_list_items)
        app.router.add_get("/debug/requests", self._debug_requests)
        app.router.add_get("/debug/requests/{rid}", self._debug_request)
        app.router.add_get("/debug/profile", self._debug_profile)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]
        if self.kv_events_port is not None:
            import zmq
            import zmq.asyncio

            self._zctx = zmq.asyncio.Context()
            self._pub = self._zctx.socket(zmq.PUB)
            if self.kv_events_port == 0:
                self.kv_events_port = self._pub.bind_to_random_port("tcp://0.0.0.0")
            else:
                self._pub.bind(f"tcp://0.0.0.0:{self.kv_events_port}")
            asyncio.get_running_loop().create_task(self._kv_flush_loop())
        if self.predictor_train_url is not None:
            asyncio.get_running_loop().create_task(self._trace_flush_loop())

    async def stop(self) -> None:
        if self._owns_monitor and self.monitor is not None:
            self.monitor.stop()
            self.engine.monitor = None
        self.async_engine.stop()
        if getattr(self.engine, "writeback", None) is not None:
            self.engine.writeback.stop()
        if self.transfer_source is not None:
            self.transfer_source.stop()
        if self._runner:
            await self._runner.cleanup()
        if self._pub is not None:
            self._pub.close(0)
            self._zctx.term()

    # -- helpers -----------------------------------------------------------
    def _pull_remote_kv(self, ktp: "KVTransferParams", token_ids: list[int],
                        lora_id=None, mm_hashes: list = (),
                        rid: Optional[str] = None) -> int:
        """Pull + inject remote prefill KV; any failure → recompute locally
        (kv_load_failure_policy=recompute, operations-vllm.md:84-100)."""
        if rid is not None:
            self._pending_pulls[rid] = (ktp.remote_host, ktp.remote_port,
                                        ktp.remote_request_id)
        try:
            pulled = self.transfer_client.pull(
                ktp.remote_host, ktp.remote_port, ktp.remote_request_id
            )
            if pulled is None:
                self.transfer_stats["pull_failures"] += 1
                return 0
            n = self.async_engine.run_locked(
                lambda: inject_into_engine(self.engine, pulled, token_ids, lora_id,
                                           mm_hashes)
            )
            self.transfer_stats["injected_blocks"] += n
            # free producer-side blocks (NIXL-notify semantics)
            if self.transfer_client.notify(ktp.remote_host, ktp.remote_port,
                                           ktp.remote_request_id) and rid is not None:
                self._pending_pulls.pop(rid, None)
            return n
        except Exception as e:
            self.transfer_stats["pull_failures"] += 1
            if isinstance(e, ValueError) and "block shape" in str(e):
                # peer layout/geometry mismatch is a standing config error —
                # every pull will fail until fixed; say so once per minute
                # instead of burying it in the failure counter
                now = time.monotonic()
                if now - getattr(self, "_shape_err_ts", 0.0) > 60.0:
                    self._shape_err_ts = now
                    print(f"kv-transfer: {e}", file=sys.stderr, flush=True)
            return 0

    def _pull_prefix_kv(self, rid: str, ktp: "KVTransferParams",
                        token_ids: list[int], lora_id=None,
                        mm_hashes: list = ()) -> int:
        """KV-plane prefix pull ahead of prefill: the peer rung first (when
        the router stamped one), then the cluster-durable store. Any failure
        degrades to the normal admission ladder (host/disk offload tier, then
        re-prefill) — it NEVER fails the request. Injected blocks become
        ordinary local prefix hits, so num_cached_prompt stays truthful."""
        from llmd_tpu.kvplane import pull_prefix_into

        self.transfer_stats["prefix_pulls"] += 1
        t0 = time.monotonic()
        tier = getattr(ktp, "tier", "peer") or "peer"
        peer = f"{ktp.remote_host}:{ktp.remote_port}"
        n, outcome = 0, "miss"
        if (tier == "peer" and ktp.remote_host
                and self.transfer_client is not None):
            self._pending_pulls[rid] = (ktp.remote_host, ktp.remote_port,
                                        ktp.remote_request_id)
            try:
                n, outcome, released = pull_prefix_into(self, ktp, token_ids,
                                                        lora_id, mm_hashes)
            except Exception:
                n, outcome, released = 0, "error", False
            if released:
                self._pending_pulls.pop(rid, None)
        durable = getattr(self.engine, "durable", None)
        if n == 0 and durable is not None and ktp.block_hashes:
            # durable-tier rung: the peer died/missed, or the router stamped
            # the durable tier directly — the cluster store outlives replicas
            dn, d_outcome = self._durable_get(ktp.block_hashes, token_ids,
                                              lora_id, mm_hashes)
            if dn or tier == "durable":
                n, outcome, tier = dn, d_outcome, "durable"
                peer = f"{durable.cfg.host}:{durable.cfg.port}"
        pull_s = time.monotonic() - t0
        self.server_metrics.prefix_pull_seconds.labels(
            outcome=outcome).observe(pull_s)
        if n:
            self.transfer_stats["prefix_pull_blocks"] += n
        else:
            self.transfer_stats["pull_failures"] += 1
        # the pull runs before admission opens the flight record; start() is
        # idempotent, so open it here and let add_request backfill the model
        self.engine.flight.start(rid)
        # durable fetches stay on the kv_pull event NAME — attribution keys
        # on names (obs/attribution.py), so PR-13 sum-to-wall is untouched;
        # `tier` is the distinction dashboards and ledger tests filter on
        self.engine.flight.record(rid, "kv_pull", outcome=outcome, blocks=n,
                                  ms=round(pull_s * 1e3, 3), tier=tier,
                                  peer=peer)
        return n

    def _durable_get(self, block_hashes, token_ids, lora_id=None,
                     mm_hashes: list = ()) -> tuple[int, str]:
        """Durable-tier rung: fetch the verified consecutive prefix from the
        cluster store and inject it exactly like a peer pull — hash-chain
        verified against THIS prompt, shape-checked, committed as ordinary
        prefix-cache entries. Returns (blocks_injected, kv_pull outcome)."""
        from llmd_tpu.disagg.transfer import PulledKV, inject_into_engine

        durable = self.engine.durable
        t0 = time.monotonic()
        want = [int(h) for h in block_hashes]
        n, blocks, fetch_outcome = durable.get(want)
        injected = 0
        if n and blocks is not None:
            pulled = PulledKV(block_hashes=want[:n],
                              token_chunks=[[] for _ in range(n)],
                              blocks=blocks)
            try:
                injected = self.async_engine.run_locked(
                    lambda: inject_into_engine(self.engine, pulled, token_ids,
                                               lora_id, list(mm_hashes)))
            except ValueError:
                # block-shape / chain mismatch: the verifier rejected the
                # payload — fall down the ladder, never commit suspect bytes
                injected, fetch_outcome = 0, "corrupt"
            except Exception:
                injected, fetch_outcome = 0, "error"
            if injected:
                self.transfer_stats["injected_blocks"] += injected
        self.engine.flight.record_system(
            "kv_durable_get", outcome=fetch_outcome, blocks=injected,
            ms=round((time.monotonic() - t0) * 1e3, 3))
        self.server_metrics.kv_durable_get.labels(
            outcome=fetch_outcome).inc()
        if injected:
            return injected, "hit"
        if fetch_outcome in ("ok", "miss", "breaker_open"):
            return 0, "miss"
        return 0, "error"

    def _flush_for_drain(self, budget_s: float) -> tuple[int, int]:
        """Final write-back before retirement: stage the resident prefix
        working set under the engine lock (cheap device slicing), drain the
        host bytes off-lock, enqueue, then synchronously empty the flush
        queue under the remaining budget. A hung store costs at most the
        budget — the remainder is abandoned, and drain still retires."""
        from llmd_tpu.disagg.transfer import drain_staged
        from llmd_tpu.kv.writeback import stage_resident_blocks

        t0 = time.monotonic()
        wb = self.engine.writeback
        try:
            hashes, parts = self.async_engine.run_locked(
                lambda: stage_resident_blocks(self.engine, wb.max_blocks))
            if hashes:
                wb.offer(hashes, drain_staged(parts))
        except Exception:
            pass  # flush is best-effort; drain must still retire on time
        remaining = max(0.0, budget_s - (time.monotonic() - t0))
        return wb.flush_for_drain(remaining)

    def _release_pending_pull(self, rid: str) -> None:
        """Free the peer-side registration for a retired/aborted request
        (satellite fix: a dead puller must not pin peer exports until TTL)."""
        pending = self._pending_pulls.pop(rid, None)
        if pending is None or self.transfer_client is None:
            return
        host, port, remote_rid = pending
        try:
            if self.transfer_client.notify(host, port, remote_rid):
                self.transfer_stats["released"] += 1
        except Exception:
            pass  # peer gone; its TTL reaper cleans up

    def _tokenize_body(self, body: dict) -> list[int]:
        if body.get("prompt_token_ids"):
            return list(body["prompt_token_ids"])
        if "messages" in body:
            text = flatten_messages(body["messages"])
        else:
            text = str(body.get("prompt", ""))
        return self.tokenizer.encode(text)

    def _mm_token_stream(self, body: dict) -> tuple[list[int], list[dict]]:
        """VL token stream: media parts expand to cfg.mm_tokens placeholder ids.

        Shared by /render and the generate path — the router's precise
        token-producer tokenizes via /render, so the engine MUST hash blocks
        over this exact stream or prefix-cache routing silently scores 0 for
        every multimodal request. Returns (tokens, media parts in order)."""
        from llmd_tpu.disagg.encode import is_media_part

        cfg = self.engine.model_cfg
        pieces: list = []  # str segments; None marks a media slot
        parts: list[dict] = []
        for m in body.get("messages", []) or []:
            content = m.get("content", "")
            pieces.append(f"{m.get('role', '')}: ")
            if isinstance(content, list):
                for part in content:
                    if is_media_part(part):
                        pieces.append(None)
                        parts.append(part)
                    elif isinstance(part, dict):
                        pieces.append(part.get("text", "") + " ")
                    else:
                        pieces.append(str(part) + " ")
            else:
                pieces.append(str(content))
            pieces.append("\n")
        token_ids: list[int] = []
        for p in pieces:
            if p is None:
                token_ids.extend([cfg.mm_placeholder_id] * cfg.mm_tokens)
            elif p:
                token_ids.extend(self.tokenizer.encode(p))
        return token_ids, parts

    def _tokenize_mm(self, body: dict) -> tuple[list[int], Optional[list]]:
        """VL tokenization + embedding resolution: E-stage wire items match by
        canonical part hash; missing items encode in-process when this server
        has a vision tower, otherwise the request degrades to the text-only
        flatten rendering (encode pool down ≠ failed request).

        Returns (tokens, mm_items) — mm_items None means degraded text-only."""
        from llmd_tpu.disagg.encode import (
            VisionRunner,
            media_bytes_from_part,
            mm_item_from_wire,
            part_identity,
        )

        cfg = self.engine.model_cfg
        token_ids, parts = self._mm_token_stream(body)
        wire_by_hash: dict[bytes, tuple[bytes, "object"]] = {}
        for d in body.get("mm_items") or []:
            try:
                h, emb = mm_item_from_wire(d, cfg.hidden_size)
                wire_by_hash[h] = (h, emb)
            except Exception:
                continue  # malformed wire item: treat as missing
        mm_items = []
        missing: list[tuple[int, dict]] = []
        for i, part in enumerate(parts):
            h = part_identity(part)
            got = wire_by_hash.get(h)
            if got is not None:
                mm_items.append(got)
            else:
                mm_items.append(None)
                missing.append((i, part))
        if missing:
            if not cfg.has_vision:
                # true E/PD worker without a tower: degrade to text-only
                # (media identity still lands in the stream via flatten's
                # <kind:hash> rendering) rather than 500ing the request
                return self._tokenize_body(body), None
            with self._vision_lock:
                if self._vision is None:
                    self._vision = VisionRunner(cfg)
            payloads = [media_bytes_from_part(part) or b"" for _, part in missing]
            encoded = self._vision.encode(payloads)
            for (i, part), (_h, emb) in zip(missing, encoded):
                mm_items[i] = (part_identity(part), emb)
        return token_ids, mm_items

    # -- handlers ----------------------------------------------------------
    def _admission_block(self, request: web.Request) -> Optional[web.Response]:
        """Shared admission gate: draining → 503 (retryable, so the router
        re-schedules the request on another endpoint); an already-expired
        forwarded deadline (x-request-timeout remainder ≤ 0) → 504 before any
        tokenization or engine work is spent on it."""
        if self._draining:
            return web.json_response({"error": {"message": "draining"}},
                                     status=503, headers={"Retry-After": "1"})
        raw = request.headers.get(HDR_REQUEST_TIMEOUT)
        if raw is not None:
            try:
                budget = float(raw)
            except ValueError:
                return None  # malformed header: ignore, don't reject
            if budget <= 0:
                return web.json_response(
                    {"error": {"message": "deadline exceeded"}}, status=504)
        return None

    async def _completions(self, request: web.Request):
        return await self._generate(request, chat=False)

    async def _chat(self, request: web.Request):
        return await self._generate(request, chat=True)

    async def _generate(self, request: web.Request, chat: bool):
        blocked = self._admission_block(request)
        if blocked is not None:
            return blocked
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}}, status=400)
        try:
            # malformed structured specs (bad schema/regex/logit_bias) fail as
            # 400 here, before the request counts or touches the engine
            validate_structured_body(body)
        except ValueError as e:
            return web.json_response({"error": {"message": str(e)}}, status=400)
        self.request_count += 1
        mm_items = None
        if self.engine.model_cfg.mm_tokens > 0 and _body_has_media(body):
            try:
                # executor thread: in-process vision encode (jit compile +
                # device compute in combined-PD mode) must not stall the loop
                token_ids, mm_items = await asyncio.get_running_loop().run_in_executor(
                    None, self._tokenize_mm, body)
            except Exception as e:
                return web.json_response(
                    {"error": {"message": f"multimodal content: {e}"}}, status=400)
        else:
            token_ids = self._tokenize_body(body)
        mm_hashes = [h for h, _ in mm_items] if mm_items else []
        sampling = _sampling_from_body(body)
        if not sampling.ignore_eos:
            sampling.stop_token_ids = tuple(sampling.stop_token_ids) + (self.tokenizer.eos_id,)
        rid = f"cmpl-{uuid.uuid4().hex[:16]}"
        stream = bool(body.get("stream", False))
        created = int(time.time())
        model = body.get("model", self.model_name)
        lora_id = body.get("lora_adapter")
        # vLLM semantics: requesting a loaded adapter's name as the model routes
        # to that adapter (adapter-rollout.md canary flow relies on this)
        reg = self.engine.lora_registry
        if lora_id is None and reg is not None and reg.has(model):
            lora_id = model
        if lora_id is not None and (reg is None or not reg.has(lora_id)):
            # vLLM 404 semantics — covers unknown adapters AND LoRA serving being
            # disabled (silently answering with base weights would mislead the
            # client and poison the prefix cache under the adapter's name)
            return web.json_response(
                {"error": {"message": f"unknown LoRA adapter {lora_id!r}"}}, status=404)

        from llmd_tpu.obs.tracing import extract_traceparent

        span = self.tracer.start_span(
            "engine.generate", parent=extract_traceparent(dict(request.headers)),
            **{"llm_d.model": model, "llm_d.prompt_tokens": len(token_ids),
               "llm_d.stream": stream})

        ktp = KVTransferParams.from_dict(body.get("kv_transfer_params"))
        if ktp.do_remote_prefill and self.transfer_client is not None:
            span.add_event("kv_transfer.pull")
            await asyncio.get_running_loop().run_in_executor(
                None, self._pull_remote_kv, ktp, token_ids, lora_id, mm_hashes,
                rid
            )
        elif (ktp.do_prefix_pull and ktp.block_hashes
              and (self.transfer_client is not None
                   or getattr(self.engine, "durable", None) is not None)):
            # KV plane: the router found this prefix cached on a peer or in
            # the durable store — pull it before admission; failure falls
            # through to the offload tier and then plain re-prefill
            span.add_event("kv_plane.pull")
            await asyncio.get_running_loop().run_in_executor(
                None, self._pull_prefix_kv, rid, ktp, token_ids, lora_id,
                mm_hashes
            )

        # the engine mints its own rid, so the router's tenant header is the
        # only identity link: open (or backfill) the flight record with it
        # before admission so the engine-side ledger carries the tenant too
        from llmd_tpu.core.request import HDR_TENANT, clamp_tenant

        self.engine.flight.start(
            rid, tenant=clamp_tenant(request.headers.get(HDR_TENANT)))

        try:
            gen = self.async_engine.generate(rid, token_ids, sampling, lora_id,
                                             rank=self.rank, mm_items=mm_items,
                                             trace_ctx=span.context)
            if not stream:
                out_ids: list[int] = []
                cached = 0
                reason = None
                async for out in gen:
                    out_ids.extend(out.new_token_ids)
                    cached = out.num_cached_prompt_tokens
                    reason = out.finish_reason
                text = self.tokenizer.decode(out_ids)
                usage = {
                    "prompt_tokens": len(token_ids), "completion_tokens": len(out_ids),
                    "total_tokens": len(token_ids) + len(out_ids), "cached_tokens": cached,
                }
                choice = (
                    {"index": 0, "message": {"role": "assistant", "content": text},
                     "finish_reason": reason}
                    if chat else
                    {"index": 0, "text": text, "finish_reason": reason}
                )
                payload = {
                    "id": rid, "object": "chat.completion" if chat else "text_completion",
                    "created": created, "model": model, "usage": usage, "choices": [choice],
                }
                if ktp.do_remote_decode and self.transfer_source is not None:
                    # two-phase staging: the engine lock is held only long enough
                    # to dispatch the chunked gathers (+ async D2H copies); the
                    # byte drain + registration runs in an executor thread while
                    # the engine keeps stepping other requests
                    def _begin():
                        return self.async_engine.run_locked(
                            lambda: export_begin(
                                self.engine, rid, token_ids, lora_id,
                                staging_pages=self.engine.cfg.offload_staging_blocks,
                                mm_hashes=mm_hashes,
                            )
                        )

                    loop = asyncio.get_running_loop()
                    out_params, staged = await loop.run_in_executor(None, _begin)
                    if staged is not None:
                        await loop.run_in_executor(
                            None, lambda: export_finish(staged, self.transfer_source)
                        )
                    # advertise a routable host, never the bind-any address — the
                    # sidecar falls back to the prefiller's header host when unset
                    routable = self.advertise_host or self.host
                    if routable not in ("0.0.0.0", "::", ""):
                        out_params.remote_host = routable
                    out_params.remote_port = self.transfer_source.port
                    payload["kv_transfer_params"] = out_params.to_dict()
                span.set_attribute("llm_d.completion_tokens", len(out_ids))
                span.set_attribute("llm_d.cached_tokens", cached)
                span.end()
                return web.json_response(payload)

            resp = web.StreamResponse(headers={
                "Content-Type": "text/event-stream", "Cache-Control": "no-cache",
            })
            await resp.prepare(request)
            n_out = 0
            lag, first = self.server_metrics.stream_lag, True
            async for out in gen:
                piece = self.tokenizer.decode(out.new_token_ids)
                n_out += len(out.new_token_ids)
                chunk = {
                    "id": rid, "created": created, "model": model,
                    "object": "chat.completion.chunk" if chat else "text_completion",
                    "choices": [
                        {"index": 0, "delta": {"content": piece},
                         "finish_reason": out.finish_reason if out.finished else None}
                        if chat else
                        {"index": 0, "text": piece,
                         "finish_reason": out.finish_reason if out.finished else None}
                    ],
                }
                if out.finished:
                    chunk["usage"] = {
                        "prompt_tokens": len(token_ids), "completion_tokens": n_out,
                        "total_tokens": len(token_ids) + n_out,
                        "cached_tokens": out.num_cached_prompt_tokens,
                    }
                await resp.write(f"data: {json.dumps(chunk)}\n\n".encode())
                # step's end -> chunk written, for the first and the last
                # chunk only (one chunk can be both)
                if first:
                    first = False
                    lag.labels(at="first").observe(
                        time.perf_counter() - out.t_step)
                if out.finished:
                    lag.labels(at="last").observe(
                        time.perf_counter() - out.t_step)
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
            span.set_attribute("llm_d.completion_tokens", n_out)
            span.end()
            return resp
        except ValueError as e:
            span.set_error(str(e))
            return web.json_response({"error": {"message": str(e)}}, status=400)
        finally:
            if rid in self._pending_pulls:
                # retire/abort/disconnect with the peer registration still
                # live (pull died between serve and notify): release it now.
                # Not awaited — this finally also runs under task cancellation
                # (client disconnect), where any await would re-raise.
                asyncio.get_running_loop().run_in_executor(
                    None, self._release_pending_pull, rid)
            span.end()  # idempotent backstop

    async def _embeddings(self, request: web.Request):
        """OpenAI /v1/embeddings: mean-pooled L2-normalised final hidden states
        (openai-parser endpoint list, request-handling.md:50-73)."""
        blocked = self._admission_block(request)
        if blocked is not None:
            return blocked
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}}, status=400)
        inp = body.get("input")
        if inp is None:
            return web.json_response({"error": {"message": "input required"}}, status=400)
        items = [inp] if isinstance(inp, (str,)) else list(inp)
        if items and isinstance(items[0], int):  # single pre-tokenized prompt
            items = [items]
        model = body.get("model", self.model_name)
        lora_id = body.get("lora_adapter")
        reg = self.engine.lora_registry
        if lora_id is None and reg is not None and reg.has(model):
            lora_id = model
        if lora_id is not None and (reg is None or not reg.has(lora_id)):
            return web.json_response(
                {"error": {"message": f"unknown LoRA adapter {lora_id!r}"}}, status=404)

        loop = asyncio.get_running_loop()
        data = []
        total_tokens = 0
        for i, item in enumerate(items):
            ids = item if isinstance(item, list) else self.tokenizer.encode(str(item))
            if not ids:
                return web.json_response(
                    {"error": {"message": f"empty input at index {i}"}}, status=400)
            total_tokens += len(ids)
            try:
                vec = await loop.run_in_executor(
                    None,
                    lambda ids=ids: self.async_engine.run_locked(
                        lambda: self.engine.embed(ids, lora_id, rank=self.rank)))
            except RuntimeError as exc:
                return web.json_response({"error": {"message": str(exc)}}, status=503)
            data.append({"object": "embedding", "index": i, "embedding": vec})
        self.request_count += 1
        return web.json_response({
            "object": "list", "model": model, "data": data,
            "usage": {"prompt_tokens": total_tokens, "total_tokens": total_tokens},
        })

    # -- Responses / Conversations APIs ------------------------------------
    # The conversation store is engine-local (a pod-resident dict, like vLLM's);
    # the router keeps conversation traffic sticky by id so follow-ups land on
    # the pod holding the state AND its KV prefix cache.

    @staticmethod
    def _responses_input_to_messages(inp) -> list[dict]:
        if isinstance(inp, str):
            return [{"role": "user", "content": inp}]
        out = []
        for item in inp or []:
            if isinstance(item, dict):
                out.append({"role": item.get("role", "user"),
                            "content": item.get("content", "")})
        return out

    async def _responses(self, request: web.Request):
        """OpenAI Responses API (epp-http-apis.md:153-183): ``input`` + optional
        ``conversation`` id; conversation context prepends, and the exchange is
        appended back to the store."""
        blocked = self._admission_block(request)
        if blocked is not None:
            return blocked
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}}, status=400)
        conv_id = body.get("conversation")
        conv = self._conversations.get(conv_id) if conv_id else None
        if conv_id and conv is None:
            return web.json_response(
                {"error": {"message": f"unknown conversation {conv_id!r}"}}, status=404)
        new_msgs = self._responses_input_to_messages(body.get("input", ""))
        messages = (list(conv["items"]) if conv else []) + new_msgs
        max_out = int(body.get("max_output_tokens", body.get("max_tokens", 16)))
        chat_body = {
            "model": body.get("model", self.model_name),
            "messages": messages,
            "max_tokens": max_out,
            "temperature": body.get("temperature", 1.0),
        }
        if body.get("ignore_eos"):
            chat_body["ignore_eos"] = True
        # structured-output fields ride through to the shared sampling parse
        for key in ("response_format", "guided_choice", "guided_regex",
                    "logit_bias"):
            if body.get(key) is not None:
                chat_body[key] = body[key]
        try:
            validate_structured_body(chat_body)
        except ValueError as e:
            return web.json_response({"error": {"message": str(e)}}, status=400)
        # same tokenization path as chat (VL content parts included)
        mm_items = None
        if self.engine.model_cfg.mm_tokens > 0 and _body_has_media(chat_body):
            try:
                token_ids, mm_items = await asyncio.get_running_loop().run_in_executor(
                    None, self._tokenize_mm, chat_body)
            except Exception as e:
                return web.json_response(
                    {"error": {"message": f"multimodal content: {e}"}}, status=400)
        else:
            token_ids = self._tokenize_body(chat_body)
        sampling = _sampling_from_body(chat_body)
        if not sampling.ignore_eos:
            sampling.stop_token_ids = tuple(sampling.stop_token_ids) + (self.tokenizer.eos_id,)
        rid = f"resp-{uuid.uuid4().hex[:16]}"
        out_ids: list[int] = []
        finish = None
        try:
            async for out in self.async_engine.generate(rid, token_ids, sampling,
                                                        rank=self.rank,
                                                        mm_items=mm_items):
                out_ids.extend(out.new_token_ids)
                finish = out.finish_reason
        except ValueError as e:
            return web.json_response({"error": {"message": str(e)}}, status=400)
        text = self.tokenizer.decode(out_ids)
        usage = {"prompt_tokens": len(token_ids), "completion_tokens": len(out_ids),
                 "total_tokens": len(token_ids) + len(out_ids)}
        inner = {"model": chat_body["model"]}
        status = "completed" if finish in (None, "stop", "eos") else "incomplete"
        resp = {
            "id": f"resp_{uuid.uuid4().hex[:12]}",
            "object": "response",
            "created_at": int(time.time()),
            "model": inner["model"],
            "status": status,
            "output": [{
                "id": f"msg_{uuid.uuid4().hex[:12]}",
                "type": "message", "role": "assistant", "status": "completed",
                "content": [{"type": "output_text", "text": text, "annotations": []}],
            }],
            "max_output_tokens": max_out,
            "usage": {"input_tokens": usage["prompt_tokens"],
                      "output_tokens": usage["completion_tokens"],
                      "total_tokens": usage["total_tokens"]},
        }
        if status == "incomplete":
            resp["incomplete_details"] = {"reason": "max_output_tokens"}
        if conv is not None:
            conv["items"].extend(new_msgs)
            conv["items"].append({"role": "assistant", "content": text})
            self._conv_trim(conv)
        if conv_id:
            resp["conversation"] = conv_id
        return web.json_response(resp)

    def _conv_trim(self, conv: dict) -> None:
        if len(conv["items"]) > self._max_conv_items:
            del conv["items"][: len(conv["items"]) - self._max_conv_items]

    async def _conv_create(self, request: web.Request):
        try:
            body = await request.json() if request.can_read_body else {}
        except Exception:
            body = {}
        # routers inject a pre-generated id so hash-of-id sticky routing is
        # deterministic across EPP replicas; direct clients get a fresh one
        cid = str(body.get("id") or f"conv_{uuid.uuid4().hex[:12]}")
        conv = {"id": cid, "object": "conversation", "created_at": int(time.time()),
                "items": list(body.get("items", []) or []),
                "metadata": body.get("metadata") or {}}
        self._conv_trim(conv)
        self._conversations[cid] = conv
        while len(self._conversations) > self._max_conversations:
            self._conversations.popitem(last=False)
        return web.json_response({k: v for k, v in conv.items() if k != "items"})

    def _conv_or_404(self, request):
        conv = self._conversations.get(request.match_info["cid"])
        if conv is not None:
            self._conversations.move_to_end(request.match_info["cid"])
        return conv

    async def _conv_get(self, request: web.Request):
        conv = self._conv_or_404(request)
        if conv is None:
            return web.json_response({"error": {"message": "not found"}}, status=404)
        return web.json_response({k: v for k, v in conv.items() if k != "items"})

    async def _conv_delete(self, request: web.Request):
        conv = self._conversations.pop(request.match_info["cid"], None)
        if conv is None:
            return web.json_response({"error": {"message": "not found"}}, status=404)
        return web.json_response({"id": conv["id"], "object": "conversation.deleted",
                                  "deleted": True})

    async def _conv_add_items(self, request: web.Request):
        conv = self._conv_or_404(request)
        if conv is None:
            return web.json_response({"error": {"message": "not found"}}, status=404)
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}}, status=400)
        items = body.get("items", [])
        conv["items"].extend(items)
        self._conv_trim(conv)
        return web.json_response({"object": "list", "data": items})

    async def _conv_list_items(self, request: web.Request):
        conv = self._conv_or_404(request)
        if conv is None:
            return web.json_response({"error": {"message": "not found"}}, status=404)
        return web.json_response({"object": "list", "data": conv["items"]})

    async def _render(self, request: web.Request):
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}}, status=400)
        if self.engine.model_cfg.mm_tokens > 0 and _body_has_media(body):
            # router-visible rendering must match generate-path hashing exactly
            token_ids, _ = self._mm_token_stream(body)
            return web.json_response({"prompt_token_ids": token_ids})
        return web.json_response({"prompt_token_ids": self._tokenize_body(body)})

    async def _metrics(self, request: web.Request):
        # Gauges mirror engine.stats at scrape time; counters/histograms are
        # incremented live inside the step loop. The whole exposition renders
        # through Registry.expose() — the one code path shared with the
        # router — so label values (LoRA adapter names especially) are always
        # escaped per the text format spec.
        em = self.engine.metrics
        s = self.engine.stats
        em.requests_waiting.set(s.num_waiting)
        em.requests_running.set(s.num_running)
        em.kv_usage.set(s.kv_utilization)
        # counters the step loop doesn't own (recompute path) stay derived
        # from stats via the registry increments at their emit sites; the
        # lora info gauge is rebuilt each scrape (its labels ARE the data)
        if self.engine.lora_registry is not None:
            info = self.engine.lora_registry.metrics_info()
            em.lora_info.clear()
            em.lora_info.labels(
                max_lora=info["max_lora"],
                running_lora_adapters=info["running_lora_adapters"],
                waiting_lora_adapters=info["waiting_lora_adapters"],
            ).set(1)
        return web.Response(
            text=self.engine.registry.expose() + self.registry.expose())

    async def _health(self, request: web.Request):
        if self._draining:
            # 503 = readiness-probe semantics: load balancers drop us from
            # rotation while the in-flight tail finishes
            return web.json_response(
                {"status": "draining", "inflight": len(self.engine.seqs)},
                status=503)
        if self.async_engine.fatal is not None:
            return web.json_response(
                {"status": "unhealthy", "reason": "engine_dead",
                 "error": repr(self.async_engine.fatal)}, status=503)
        mon = getattr(self.engine, "monitor", None)
        reason = mon.unhealthy_reason() if mon is not None else None
        if reason is not None:
            # device fault (stalled step loop / dead fabric): same 503
            # readiness semantics — the PoolController sweep retires us and
            # the router's breakers route around us; the structured reason
            # rides along so the retirement event says WHY
            return web.json_response(
                {"status": "unhealthy", **reason}, status=503)
        return web.json_response({"status": "ok"})

    async def _drain(self, request: web.Request):
        """POST /drain[?timeout_s=30] — stop admissions, wait for in-flight
        requests to finish (bounded), report the result. ``{"enable": false}``
        in the body re-opens admissions (rollback of an aborted drain)."""
        try:
            body = await request.json() if request.can_read_body else {}
        except Exception:
            body = {}
        if body.get("enable") is False:
            self._draining = False
            return web.json_response({"status": "ok", "draining": False})
        try:
            timeout_s = float(request.query.get("timeout_s", 30.0))
        except ValueError:
            return web.json_response(
                {"error": {"message": "timeout_s must be a number"}}, status=400)
        t0 = time.monotonic()
        if not self._draining:
            self._draining = True
            self.engine.flight.record_system(
                "drain_start", inflight=len(self.engine.seqs))
        while self.engine.seqs and time.monotonic() - t0 < timeout_s:
            await asyncio.sleep(0.02)
        drained = not self.engine.seqs
        flush_info = {}
        if drained and getattr(self.engine, "writeback", None) is not None:
            # write the resident working set back to the durable store before
            # retirement, capped by min(drain budget, remaining drain window)
            # so a hung store cannot push retirement past the pool's timeout
            budget = min(self.engine.durable.cfg.drain_budget_s,
                         max(0.0, timeout_s - (time.monotonic() - t0)))
            flushed, abandoned = await asyncio.get_running_loop(
                ).run_in_executor(None, self._flush_for_drain, budget)
            flush_info = {"flushed_blocks": flushed,
                          "abandoned_blocks": abandoned}
        self.engine.flight.record_system(
            "drain_done", drained=drained, inflight=len(self.engine.seqs),
            waited_ms=round((time.monotonic() - t0) * 1e3, 1), **flush_info)
        return web.json_response(
            {"status": "drained" if drained else "timeout",
             "inflight": len(self.engine.seqs)},
            status=200 if drained else 504)

    async def _debug_requests(self, request: web.Request):
        from llmd_tpu.obs.events import debug_list_response

        status, payload = debug_list_response(
            self.engine.flight, request.rel_url.query)
        return web.json_response(payload, status=status)

    async def _debug_request(self, request: web.Request):
        from llmd_tpu.obs.events import debug_detail_response

        status, payload = debug_detail_response(
            self.engine.flight, request.match_info["rid"])
        return web.json_response(payload, status=status)

    async def _debug_profile(self, request: web.Request):
        """GET /debug/profile?seconds=N[&python_tracer=0] — capture one
        jax.profiler window into LLMD_PROFILE_DIR and describe the artifact
        (with the two llmd.clock marks). One at a time (409 while busy); the
        capture blocks in an executor, not on the loop. python_tracer=0
        leaves the Python-frame tracer off (default on, as before)."""
        from llmd_tpu.obs.device import ProfileBusy

        mon = getattr(self.engine, "monitor", None)
        if mon is None:
            return web.json_response(
                {"error": {"message": "device monitor not running"}},
                status=503)
        try:
            seconds = float(request.query.get("seconds", "2"))
            python_tracer = bool(int(request.query.get("python_tracer", "1")))
        except ValueError:
            return web.json_response(
                {"error": {"message": "seconds and python_tracer must be "
                                      "numeric"}}, status=400)
        try:
            result = await asyncio.get_running_loop().run_in_executor(
                None, mon.capture_profile, seconds, python_tracer)
        except ProfileBusy as e:
            return web.json_response(
                {"error": {"message": str(e)}}, status=409)
        except Exception as e:
            return web.json_response(
                {"error": {"message": f"profile capture failed: {e}"}},
                status=500)
        return web.json_response(result)

    async def _models(self, request: web.Request):
        data = [{"id": self.model_name, "object": "model"}]
        if self.engine.lora_registry is not None:  # adapters list as models (vLLM)
            data += [{"id": name, "object": "model", "parent": self.model_name}
                     for name in sorted(self.engine.lora_registry.slots)]
        return web.json_response({"object": "list", "data": data})

    async def _load_lora(self, request: web.Request):
        """POST /v1/load_lora_adapter {lora_name, lora_path?} (vLLM runtime-LoRA
        API; VLLM_ALLOW_RUNTIME_LORA_UPDATING equivalent is always-on here)."""
        if self.engine.lora_registry is None:
            return web.json_response(
                {"error": "LoRA serving disabled (EngineConfig.lora unset)"}, status=400)
        try:
            body = await request.json()
            name = body["lora_name"]
        except Exception:
            return web.json_response({"error": "lora_name required"}, status=400)
        import re

        if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z0-9._/\-]{1,128}", name):
            # names land in Prometheus label values and hash keys — an unescaped
            # quote would corrupt the whole /metrics exposition
            return web.json_response({"error": "invalid lora_name"}, status=400)
        path = body.get("lora_path")

        def _load_and_install() -> int:
            weights = None
            if path:  # filesystem resolver: npz with lora_{A,B}_{target} arrays
                import numpy as _np

                with _np.load(path) as z:  # in executor: big files must not
                    weights = {k: z[k] for k in z.files}  # block the event loop
            return self.async_engine.run_locked(
                lambda: self.engine.load_lora_adapter(name, weights))

        try:
            slot = await asyncio.get_running_loop().run_in_executor(
                None, _load_and_install)
        except RuntimeError as exc:
            return web.json_response({"error": str(exc)}, status=409)
        except Exception as exc:
            return web.json_response(
                {"error": f"cannot load adapter: {exc}"}, status=400)
        return web.json_response({"status": "ok", "lora_name": name, "slot": slot})

    async def _unload_lora(self, request: web.Request):
        if self.engine.lora_registry is None:
            return web.json_response(
                {"error": "LoRA serving disabled (EngineConfig.lora unset)"}, status=400)
        try:
            body = await request.json()
            name = body["lora_name"]
        except Exception:
            return web.json_response({"error": "lora_name required"}, status=400)
        try:
            ok = await asyncio.get_running_loop().run_in_executor(
                None,
                lambda: self.async_engine.run_locked(
                    lambda: self.engine.unload_lora_adapter(name)))
        except RuntimeError as exc:  # in-flight requests hold the adapter
            return web.json_response({"error": str(exc)}, status=409)
        if not ok:
            return web.json_response({"error": f"unknown adapter {name!r}"}, status=404)
        return web.json_response({"status": "ok", "lora_name": name})
