"""Data-parallel rank groups — the wide-EP orchestration layer.

TPU-native equivalent of vLLM's DP launcher flags the reference drives through LWS
(`guides/wide-ep-lws/modelserver/gpu/vllm/base/decode.yaml:85-108`):
``--data-parallel-size`` (total ranks) / ``--data-parallel-size-local`` (ranks on
this host) / ``--data-parallel-address`` + ``--data-parallel-rpc-port`` (leader
coordination endpoint) / ``--data-parallel-start-rank`` (from LWS_WORKER_INDEX) /
``--data-parallel-hybrid-lb``.

Pieces:
- ``DPCoordinator`` — the leader's rpc endpoint (JSON-lines over TCP). Ranks
  register at startup (barrier) and report ``has_work`` every loop tick; the
  coordinator answers with the *wave* decision: if ANY rank has work, ALL ranks
  step. MoE expert-parallel all-to-all is a collective — in a real multi-host SPMD
  program every rank must enter the step together or the fabric deadlocks; idle
  ranks contribute empty batches (vLLM's DP wave semantics).
- ``DPWorkerSync`` — blocking-socket client used from the engine step-loop thread.
- ``DPAsyncEngine`` — AsyncLLMEngine whose loop steps on wave decisions.
- ``DPEngineGroup`` — dp_size_local engine servers on consecutive ports
  (``port_base + i`` — the reference's rank ports 8000-8007, which the router lists
  as one endpoint per ``podIP:port``, InferencePool targetPorts ≤ 8), plus an
  optional node-local round-robin balancer for hybrid-LB mode (external LB sees one
  endpoint per node, the node spreads internally).
"""

from __future__ import annotations

import asyncio
import json
import socket
import time
import traceback
from dataclasses import dataclass, field
from typing import Optional

from llmd_tpu.engine.async_engine import AsyncLLMEngine
from llmd_tpu.engine.config import EngineConfig
from llmd_tpu.engine.engine import LLMEngine
from llmd_tpu.engine.server import EngineServer
from llmd_tpu.models.config import ModelConfig

MAX_TARGET_PORTS = 8  # InferencePool targetPorts limit (docs/api-reference/inferencepool.md)


@dataclass
class DPGroupConfig:
    dp_size: int = 1          # total ranks across all hosts
    dp_size_local: int = 1    # ranks served by this process/host
    dp_address: str = "127.0.0.1"  # leader coordination host
    dp_rpc_port: int = 5555   # leader coordination port (0 = ephemeral)
    dp_start_rank: int = 0    # first global rank on this host
    hybrid_lb: bool = False   # expose one balanced endpoint per node
    port_base: int = 8000     # local rank i serves on port_base + i (0 = ephemeral)
    lb_port: int = 0          # hybrid-LB listen port (0 = ephemeral)

    def __post_init__(self) -> None:
        if self.dp_size_local > self.dp_size:
            raise ValueError("dp_size_local > dp_size")
        if not self.hybrid_lb and self.dp_size_local > MAX_TARGET_PORTS:
            raise ValueError(
                f"{self.dp_size_local} rank ports exceed InferencePool's "
                f"{MAX_TARGET_PORTS}-port limit; use hybrid_lb"
            )

    @property
    def is_leader(self) -> bool:
        return self.dp_start_rank == 0


class DPCoordinator:
    """Leader-side rank registry + wave clock (JSON-lines TCP server)."""

    def __init__(self, dp_size: int, host: str = "0.0.0.0", port: int = 0) -> None:
        self.dp_size = dp_size
        self.host, self.port = host, port
        self.registered: set[int] = set()
        self.has_work: dict[int, bool] = {}
        self.waves = 0  # wave ticks answered with step=True
        self._server: Optional[asyncio.AbstractServer] = None
        self._writers: set[asyncio.StreamWriter] = set()

    async def start(self) -> None:
        self._server = await asyncio.start_server(self._handle, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            # Force-close live worker connections first: wait_closed() (Python
            # 3.12+) waits for every handler to finish, and a handler sitting in
            # readline() on an open conn would wedge group shutdown.
            for w in list(self._writers):
                w.close()
            await self._server.wait_closed()

    async def _handle(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter) -> None:
        self._writers.add(writer)
        try:
            while True:
                line = await reader.readline()
                if not line:
                    break
                try:
                    msg = json.loads(line)
                except json.JSONDecodeError:
                    writer.write(b'{"error": "bad json"}\n')
                    await writer.drain()
                    continue
                writer.write((json.dumps(self._dispatch(msg)) + "\n").encode())
                await writer.drain()
        except (ConnectionResetError, BrokenPipeError, asyncio.CancelledError):
            pass
        finally:
            self._writers.discard(writer)
            try:
                writer.close()
            except RuntimeError:
                pass  # loop already closed

    def _dispatch(self, msg: dict) -> dict:
        cmd = msg.get("cmd")
        if cmd == "register":
            rank = int(msg["rank"])
            self.registered.add(rank)
            self.has_work.setdefault(rank, False)
            return {"ok": True, "dp_size": self.dp_size,
                    "registered": len(self.registered)}
        if cmd == "report":
            self.has_work[int(msg["rank"])] = bool(msg.get("has_work"))
            step = any(self.has_work.values())
            if step:
                self.waves += 1
            return {"step": step}
        if cmd == "status":
            return {"registered": sorted(self.registered),
                    "dp_size": self.dp_size,
                    "wave": any(self.has_work.values()), "waves": self.waves}
        return {"error": f"unknown cmd {cmd!r}"}


class DPWorkerSync:
    """Blocking JSON-lines client for the engine loop thread (one conn per rank)."""

    def __init__(self, rank: int, host: str, port: int, timeout_s: float = 5.0) -> None:
        self.rank = rank
        self.host, self.port = host, port
        self.timeout_s = timeout_s
        self._sock: Optional[socket.socket] = None
        self._file = None

    def _connect(self) -> None:
        self._sock = socket.create_connection((self.host, self.port), timeout=self.timeout_s)
        self._file = self._sock.makefile("rwb")

    def _rpc(self, msg: dict) -> dict:
        if self._sock is None:
            self._connect()
        self._file.write((json.dumps(msg) + "\n").encode())
        self._file.flush()
        line = self._file.readline()
        if not line:
            raise ConnectionError("coordinator closed connection")
        return json.loads(line)

    def register(self, barrier_timeout_s: float = 30.0) -> None:
        """Register and block until every rank in the group has registered."""
        deadline = time.monotonic() + barrier_timeout_s
        resp = self._rpc({"cmd": "register", "rank": self.rank})
        dp_size = resp["dp_size"]
        while resp.get("registered", 0) < dp_size:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"rank {self.rank}: {resp.get('registered')}/{dp_size} ranks "
                    f"registered after {barrier_timeout_s}s"
                )
            time.sleep(0.05)
            resp = self._rpc({"cmd": "register", "rank": self.rank})

    def report(self, has_work: bool) -> bool:
        """Raises OSError/ConnectionError/JSONDecodeError on coordinator outage —
        the caller must drop to solo mode and re-register on its paced schedule.
        (Swallowing here made DPAsyncEngine re-attempt the blocking connect every
        step: up to timeout_s of stall per step after an outage, contradicting the
        solo-serving degradation contract.)"""
        try:
            resp = self._rpc({"cmd": "report", "rank": self.rank,
                              "has_work": has_work})
        except (OSError, json.JSONDecodeError):
            self.close()
            raise
        if "step" not in resp:
            # error response (corrupted line, version skew) — same contract as a
            # transport outage: caller deregisters and serves solo
            self.close()
            raise ConnectionError(f"coordinator error response: {resp!r}")
        return bool(resp["step"])

    def close(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            finally:
                self._sock = None


class DPAsyncEngine(AsyncLLMEngine):
    """Engine loop that enters steps on the group wave, not local work alone.

    Degradation contract: if the coordination plane is unreachable (peer rank
    crashed at startup, wrong dp_address), the rank serves *solo* — stepping on
    local work only — and keeps retrying registration between steps. The loop
    thread must never die while the HTTP server accepts requests, or they would
    hang unanswered forever.
    """

    def __init__(self, engine: LLMEngine, worker: DPWorkerSync,
                 idle_sleep_s: float = 0.002,
                 register_attempt_timeout_s: float = 2.0,
                 register_retry_interval_s: float = 5.0) -> None:
        super().__init__(engine, idle_sleep_s=idle_sleep_s)
        self.worker = worker
        self.steps = 0
        self.empty_steps = 0  # wave-joined steps with no local work
        self.register_attempt_timeout_s = register_attempt_timeout_s
        self.register_retry_interval_s = register_retry_interval_s
        self.register_failures = 0
        self.registered = False
        self._next_register = 0.0

    def _try_register(self) -> None:
        # paced: a blocked register attempt (dead leader, slow peer) costs up to
        # attempt_timeout once per retry interval — solo serving keeps full rate
        # in between instead of stalling seconds per step
        now = time.monotonic()
        if now < self._next_register:
            return
        try:
            self.worker.register(barrier_timeout_s=self.register_attempt_timeout_s)
            self.registered = True
        except Exception:
            self.register_failures += 1
            self.worker.close()
            self._next_register = time.monotonic() + self.register_retry_interval_s

    # the base loop's parts, and the coordination plane's own: registration
    # and the wave's round trip
    loop_parts = AsyncLLMEngine.loop_parts + ("coordinate",)

    def _run(self) -> None:  # overrides the base loop
        booked = self._loop_counters()
        while not self._stop.is_set():
            parts = self._loop_turn("coordinate")
            if not self.registered:
                self._try_register()
            parts.to("lock")
            with self._lock:
                parts.to("step", annotate=False)
                has_work = self.engine.has_work()
            if self.registered:
                parts.to("coordinate")
                try:
                    step = self.worker.report(has_work)
                except (OSError, ConnectionError, json.JSONDecodeError):
                    # coordinator outage: serve solo at full rate and re-register
                    # on the paced schedule (don't pay a connect timeout per step)
                    self.registered = False
                    self.register_failures += 1
                    self._next_register = time.monotonic() + self.register_retry_interval_s
                    step = has_work
            else:
                step = has_work
            if not step:
                parts.to("idle")
                time.sleep(self._idle_sleep)
                self._book_turn(parts, booked)
                continue
            parts.to("lock")
            try:
                with self._lock:
                    parts.to("step", annotate=False)
                    outputs = self.engine.step()
            except Exception as e:  # boundary: same contract as the base loop
                traceback.print_exc()
                self._die(e)
                break
            self.steps += 1
            if not has_work:
                # joined the wave with an empty batch: locally that's a no-op, so
                # pace the loop (on real multi-host SPMD the collective itself
                # would block here)
                self.empty_steps += 1
                parts.to("idle")
                time.sleep(self._idle_sleep)
            self._deliver(outputs, parts)
            self._book_turn(parts, booked)
        self.worker.close()


class DPLocalBalancer:
    """Node-local round-robin reverse proxy for hybrid-LB mode."""

    def __init__(self, targets: list[str], host: str = "127.0.0.1", port: int = 0) -> None:
        self.targets = targets
        self.host, self.port = host, port
        self._i = 0
        self._runner = None

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    async def start(self) -> None:
        import aiohttp
        from aiohttp import web

        self._session = aiohttp.ClientSession()

        async def proxy(request: web.Request):
            target = self.targets[self._i % len(self.targets)]
            self._i += 1
            body = await request.read()
            async with self._session.request(
                request.method, f"http://{target}{request.path_qs}",
                data=body or None,
                headers={k: v for k, v in request.headers.items()
                         if k.lower() not in ("host", "content-length")},
            ) as resp:
                out = web.StreamResponse(status=resp.status, headers={
                    k: v for k, v in resp.headers.items()
                    if k.lower() not in ("content-length", "transfer-encoding")})
                await out.prepare(request)
                async for chunk in resp.content.iter_any():
                    await out.write(chunk)
                await out.write_eof()
                return out

        app = web.Application(client_max_size=32 * 1024 * 1024)
        app.router.add_route("*", "/{tail:.*}", proxy)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            await self._session.close()


class WideEPEngineGroup:
    """DP rank engines sharing ONE SPMD program over a (dp, sp, ep, tp) mesh —
    the wide-EP topology of the reference (`wide-ep-lws decode.yaml:85-121`),
    composed the XLA way.

    The reference runs R vLLM rank engines whose MoE layers meet in a DeepEP
    all-to-all; here the R ranks are scheduler frontends over one jitted step:
    each rank owns a router-visible HTTP port (InferencePool targetPorts — one
    endpoint per ``podIP:port``), its own request queue, batch-slot range and KV
    page partition, while the step program's token axis is sharded over ``dp``
    and the MoE expert dim over ``ep`` — GSPMD lowers the dispatch/combine
    einsums to one all-to-all spanning dp×ep, i.e. ALL ranks' devices, exactly
    the shared fabric collective of the reference topology. Wave lockstep is
    inherent: one step program serves every rank, so an idle rank simply
    contributes no rows (vLLM's DP wave semantics without an RPC plane; the
    cross-host RPC version remains `DPCoordinator`/`DPEngineGroup`).

    Current dryrun simplification (documented, not hidden): the KV page pool is
    replicated over dp — a production layout shards it by reordering the pool
    page-major so each rank's partition is a contiguous device-local block.
    """

    def __init__(
        self,
        model_cfg: ModelConfig,
        engine_cfg: EngineConfig,
        ranks: Optional[int] = None,
        model_name: str = "llmd-tpu/model",
        host: str = "127.0.0.1",
        port_base: int = 0,
        tokenizer=None,
        params=None,
    ) -> None:
        from llmd_tpu.engine.async_engine import AsyncLLMEngine

        self.ranks = ranks if ranks is not None else max(1, engine_cfg.mesh.dp)
        if engine_cfg.dp_ranks == 1 and self.ranks > 1:
            from dataclasses import replace as _replace

            engine_cfg = _replace(engine_cfg, dp_ranks=self.ranks)
        if engine_cfg.dp_ranks != self.ranks:
            raise ValueError(f"dp_ranks={engine_cfg.dp_ranks} != ranks={self.ranks}")
        if self.ranks > MAX_TARGET_PORTS:
            raise ValueError(
                f"{self.ranks} rank ports exceed InferencePool's "
                f"{MAX_TARGET_PORTS}-port limit")
        self.engine = LLMEngine(model_cfg, engine_cfg, params=params)
        self.async_engine = AsyncLLMEngine(self.engine)
        self.servers: list[EngineServer] = []
        for r in range(self.ranks):
            srv = EngineServer(
                model_cfg, engine_cfg, model_name=model_name, host=host,
                port=port_base + r if port_base else 0, tokenizer=tokenizer,
                engine=self.engine, async_engine=self.async_engine, rank=r,
            )
            self.servers.append(srv)

    async def start(self) -> None:
        for srv in self.servers:
            await srv.start()

    async def stop(self) -> None:
        self.async_engine.stop()
        for srv in self.servers:
            await srv.stop()

    def endpoints(self) -> list[str]:
        """One router-visible address per DP rank (EPP routes to every rank port)."""
        return [s.address for s in self.servers]


class DPEngineGroup:
    """dp_size_local engine servers + coordinator (on the leader) + optional LB."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        engine_cfg: EngineConfig,
        dp_cfg: DPGroupConfig,
        model_name: str = "llmd-tpu/model",
        host: str = "127.0.0.1",
        tokenizer=None,
        params=None,
    ) -> None:
        self.dp_cfg = dp_cfg
        self.coordinator = (
            DPCoordinator(dp_cfg.dp_size, port=dp_cfg.dp_rpc_port)
            if dp_cfg.is_leader else None
        )
        self.servers: list[EngineServer] = []
        self.balancer: Optional[DPLocalBalancer] = None
        self._model_cfg, self._engine_cfg = model_cfg, engine_cfg
        self._model_name, self._host = model_name, host
        self._tokenizer, self._params = tokenizer, params

    async def start(self) -> None:
        if self.coordinator is not None:
            await self.coordinator.start()
        rpc_host, rpc_port = self.dp_cfg.dp_address, (
            self.coordinator.port if self.coordinator is not None
            else self.dp_cfg.dp_rpc_port
        )
        for i in range(self.dp_cfg.dp_size_local):
            rank = self.dp_cfg.dp_start_rank + i
            port = self.dp_cfg.port_base + i if self.dp_cfg.port_base else 0
            srv = EngineServer(
                self._model_cfg, self._engine_cfg, model_name=self._model_name,
                host=self._host, port=port, tokenizer=self._tokenizer,
                params=self._params,
            )
            # swap in the wave-synced loop before start() spawns the thread
            srv.async_engine = DPAsyncEngine(
                srv.engine, DPWorkerSync(rank, rpc_host, rpc_port))
            self.servers.append(srv)
            await srv.start()
        if self.dp_cfg.hybrid_lb:
            self.balancer = DPLocalBalancer(
                [s.address for s in self.servers], host=self._host,
                port=self.dp_cfg.lb_port)
            await self.balancer.start()

    async def stop(self) -> None:
        for srv in self.servers:
            await srv.stop()
        if self.balancer is not None:
            await self.balancer.stop()
        if self.coordinator is not None:
            await self.coordinator.stop()

    def endpoints(self) -> list[str]:
        """Addresses the router should list: one per rank port (default — the EPP
        'route to all DP rank ports' contract), or the node balancer (hybrid-LB)."""
        if self.dp_cfg.hybrid_lb:
            assert self.balancer is not None, "group not started"
            return [self.balancer.address]
        return [s.address for s in self.servers]
