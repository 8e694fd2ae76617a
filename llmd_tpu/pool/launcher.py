"""Replica launchers: the pool controller's process-lifecycle backends.

Two implementations of one contract:

- :class:`FakeReplicaLauncher` — in-process ``FakeModelServer`` replicas for
  CI and the SLO gate. A configurable ``engine_build_s`` sleep simulates the
  cold engine build; a snapshot hit (``PoolSnapshotStore``) skips it, which
  is exactly the warm-start contract the engine path honors for real.
- :class:`ProcessReplicaLauncher` — subprocess replicas (``testing/
  fake_server.py`` CLI or ``engine/serve.py`` via :func:`engine_argv`),
  readiness-gated on ``/health``.

``kill`` is deliberately part of the contract: chaos tooling
(tools/slo_check.py) needs to take a replica down *without* the drain
handshake, so the controller's health probe and the router's breakers — not
the launcher — have to notice.
"""

from __future__ import annotations

import asyncio
import copy
import os
import socket
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from llmd_tpu.pool.snapshot import PoolSnapshotStore, config_fingerprint


@dataclass
class ReplicaHandle:
    """One launched replica, as the controller tracks it."""

    address: str  # "host:port" the replica serves on
    name: str = ""
    warm: bool = False  # launched from a snapshot (skipped cold build)
    launched_at: float = field(default_factory=time.monotonic)
    server: Any = None  # in-process FakeModelServer (fake launcher)
    proc: Any = None  # subprocess.Popen (process launcher)
    role: str = "both"  # prefill | decode | both — copied onto the Endpoint
    sidecar: Any = None  # RoutingSidecar fronting a decode replica

    def __post_init__(self) -> None:
        if not self.name:
            self.name = self.address


class ReplicaLauncher:
    """Lifecycle contract the controller drives. All methods are async so
    process launchers can await readiness without blocking the loop."""

    async def launch(self) -> ReplicaHandle:
        raise NotImplementedError

    async def stop(self, handle: ReplicaHandle) -> None:
        """Graceful stop (the controller drains via the router first)."""
        raise NotImplementedError

    async def kill(self, handle: ReplicaHandle) -> None:
        """Abrupt stop: no drain, in-flight requests die. Chaos only."""
        await self.stop(handle)

    def alive(self, handle: ReplicaHandle) -> bool:
        raise NotImplementedError


class FakeReplicaLauncher(ReplicaLauncher):
    """In-process fake replicas with a simulated cold engine build.

    ``engine_config`` is fingerprinted exactly like the engine launcher's;
    the first launch pays ``engine_build_s`` and commits a snapshot, every
    later launch of the same config is warm (pays only ``restore_s``).
    """

    def __init__(self, server_config=None,
                 snapshots: Optional[PoolSnapshotStore] = None,
                 engine_config: Optional[dict] = None,
                 engine_build_s: float = 0.0,
                 restore_s: float = 0.0,
                 durable_store: bool = False,
                 role: str = "both",
                 with_sidecar: bool = False) -> None:
        from llmd_tpu.testing.fake_server import FakeServerConfig

        self.server_config = server_config or FakeServerConfig()
        self.snapshots = snapshots
        self.engine_config = engine_config if engine_config is not None else {
            "model": self.server_config.model,
            "block_size": self.server_config.block_size,
            "num_blocks": self.server_config.num_blocks,
        }
        self.engine_build_s = engine_build_s
        self.restore_s = restore_s
        # Durable prefix tier stand-in (docs/durable-tier.md): a graceful
        # stop — the controller only calls stop() after the drain handshake —
        # writes the replica's simulated block set back here, and a warm
        # launch restores it, so a 0→1 warm start recovers the prefix working
        # set, not just the compile cache. kill() deliberately skips the
        # write-back (no drain, no flush). Off by default: only opted-in
        # harnesses (tools/slo_check.py) should see restored prefixes.
        self.durable_store = durable_store
        self.durable_blocks: set[int] = set()
        # P/D disaggregation (docs/pd-disaggregation.md): role is stamped on
        # the replica config and the handle so the controller can label the
        # Endpoint; with_sidecar fronts decode replicas with a RoutingSidecar
        # that executes the x-prefiller-host-port split the router decides.
        self.role = role
        self.with_sidecar = with_sidecar
        self._seq = 0

    async def launch(self) -> ReplicaHandle:
        from llmd_tpu.testing.fake_server import FakeModelServer

        fp = config_fingerprint(self.engine_config)
        warm = self.snapshots is not None and self.snapshots.has(fp)
        if warm:
            if self.restore_s > 0:
                await asyncio.sleep(self.restore_s)
        else:
            if self.engine_build_s > 0:
                await asyncio.sleep(self.engine_build_s)  # simulated build
            if self.snapshots is not None:
                self.snapshots.save(fp, {"kind": "fake",
                                         "engine_config": self.engine_config})
        cfg = copy.deepcopy(self.server_config)
        if self.role != "both":
            cfg.role = self.role
        server = FakeModelServer(cfg)
        if self.durable_store and self.durable_blocks:
            # restore the written-back prefix working set into the simulated
            # paged cache: repeats hit these blocks, so prefill (∝ uncached
            # tokens) — and therefore TTFT — recovers along with the build
            now = time.monotonic()
            for h in self.durable_blocks:
                server.blocks[h] = now
        await server.start()
        self._seq += 1
        sidecar = None
        address = server.address
        if self.with_sidecar:
            from llmd_tpu.disagg.sidecar import RoutingSidecar

            sidecar = RoutingSidecar(decode_addr=server.address,
                                     prefill_timeout_s=2.0)
            await sidecar.start()
            address = sidecar.address  # traffic enters through the sidecar
        return ReplicaHandle(address=address,
                             name=f"fake-{self._seq}", warm=warm,
                             server=server, role=self.role, sidecar=sidecar)

    async def stop(self, handle: ReplicaHandle) -> None:
        if handle.sidecar is not None:
            sidecar, handle.sidecar = handle.sidecar, None
            await sidecar.stop()
        if handle.server is not None:
            if self.durable_store:
                # drain-time write-back: the controller drained before this
                self.durable_blocks.update(handle.server.blocks.keys())
            await handle.server.stop()
            handle.server = None

    async def kill(self, handle: ReplicaHandle) -> None:
        # aiohttp cleanup cancels in-flight handlers: clients see resets,
        # which is the abrupt-death signal the chaos gate wants. No durable
        # write-back: an abrupt death never ran the drain flush.
        if handle.sidecar is not None:
            sidecar, handle.sidecar = handle.sidecar, None
            await sidecar.stop()
        if handle.server is not None:
            server, handle.server = handle.server, None
            await server.stop()

    def alive(self, handle: ReplicaHandle) -> bool:
        return handle.server is not None and handle.server._runner is not None


def _free_port(host: str = "127.0.0.1") -> int:
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def fake_argv(port: int, *, model: str = "fake/model", block_size: int = 16,
              num_blocks: int = 512, max_running: int = 8,
              decode_us_per_token: float = 500.0,
              role: str = "both") -> list[str]:
    """argv for a subprocess FakeModelServer (testing/fake_server.py CLI)."""
    argv = [sys.executable, "-m", "llmd_tpu.testing.fake_server",
            "--port", str(port), "--model", model,
            "--block-size", str(block_size), "--num-blocks", str(num_blocks),
            "--max-running", str(max_running),
            "--decode-us-per-token", str(decode_us_per_token)]
    if role != "both":
        argv += ["--role", role]
    return argv


def engine_argv(model: str, port: int,
                snapshots: Optional[PoolSnapshotStore] = None,
                engine_config: Optional[dict] = None,
                extra: Optional[list[str]] = None) -> tuple[list[str], bool]:
    """argv for an ``engine/serve.py`` replica, warm-start aware.

    With a snapshot store, the materialized checkpoint lives under the
    config fingerprint: the first launch builds it (testing/checkpoints.py
    for test models, a straight copy of HF dirs otherwise happens at serve
    time) and every relaunch reuses it. Compiled programs come back from the
    one compilation cache every entry point shares
    (llmd_tpu/jax_init.py: ``JAX_COMPILATION_CACHE_DIR`` from the
    replica's environment, else the fixed path in the checkout) — keyed by
    the program's own hash, so it needs no per-fingerprint directory.
    Returns ``(argv, warm)``.
    """
    cfg = dict(engine_config or {})
    cfg.setdefault("model", model)
    argv = [sys.executable, "-m", "llmd_tpu.engine.serve",
            "--model", model, "--port", str(port)]
    warm = False
    if snapshots is not None:
        fp = config_fingerprint(cfg)
        warm = snapshots.has(fp)
        if not os.path.isdir(model):  # test-model name → materialize once
            ckpt_dir = snapshots.path(fp, "checkpoint")
            if not os.path.exists(os.path.join(ckpt_dir, "config.json")):
                from llmd_tpu.testing.checkpoints import make_hf_checkpoint

                make_hf_checkpoint(ckpt_dir)
            argv[argv.index("--model") + 1] = ckpt_dir
        if not warm:
            snapshots.save(fp, {"kind": "engine", "engine_config": cfg})
    argv += list(extra or [])
    return argv, warm


class ProcessReplicaLauncher(ReplicaLauncher):
    """Subprocess replicas readiness-gated on ``/health``.

    ``argv_fn(port) -> (argv, warm)`` (or ``argv`` alone, treated as cold)
    decouples the launcher from what it launches: ``fake_argv`` for CI,
    ``engine_argv`` for on-device pools.

    A chip belongs to one process: children get the parent's environment
    and no device assignment, so on an accelerator host this launcher runs
    ONE engine replica per chip-owning host process (a second replica on
    the same chips fails to initialise — its stderr is inherited, so the
    reason is visible). Pinning replicas to devices is ROADMAP R4.
    """

    def __init__(self, argv_fn: Callable[[int], Any], host: str = "127.0.0.1",
                 ready_timeout_s: float = 60.0,
                 env: Optional[dict[str, str]] = None) -> None:
        self.argv_fn = argv_fn
        self.host = host
        self.ready_timeout_s = ready_timeout_s
        self.env = env
        self._seq = 0

    async def launch(self) -> ReplicaHandle:
        import subprocess

        import aiohttp

        port = _free_port(self.host)
        built = self.argv_fn(port)
        argv, warm = built if isinstance(built, tuple) else (built, False)
        env = dict(os.environ, **(self.env or {}))
        # stderr is inherited: a replica that cannot get the chip (or dies
        # on a compile error) must say so where the operator can read it
        proc = subprocess.Popen(argv, env=env, stdout=subprocess.DEVNULL)
        address = f"{self.host}:{port}"
        deadline = time.monotonic() + self.ready_timeout_s
        async with aiohttp.ClientSession() as sess:
            while time.monotonic() < deadline:
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"replica process exited rc={proc.returncode} "
                        f"before becoming ready ({' '.join(argv[:4])}…)")
                try:
                    async with sess.get(
                        f"http://{address}/health",
                        timeout=aiohttp.ClientTimeout(total=1.0),
                    ) as r:
                        if r.status == 200:
                            self._seq += 1
                            return ReplicaHandle(address=address,
                                                 name=f"proc-{self._seq}",
                                                 warm=warm, proc=proc)
                except Exception:
                    pass
                await asyncio.sleep(0.05)
        proc.kill()
        raise TimeoutError(
            f"replica at {address} not ready within {self.ready_timeout_s}s")

    async def stop(self, handle: ReplicaHandle) -> None:
        if handle.proc is None:
            return
        handle.proc.terminate()
        try:
            await asyncio.to_thread(handle.proc.wait, 5.0)
        except Exception:
            handle.proc.kill()
        handle.proc = None

    async def kill(self, handle: ReplicaHandle) -> None:
        if handle.proc is not None:
            handle.proc.kill()
            await asyncio.to_thread(handle.proc.wait)
            handle.proc = None

    def alive(self, handle: ReplicaHandle) -> bool:
        return handle.proc is not None and handle.proc.poll() is None
