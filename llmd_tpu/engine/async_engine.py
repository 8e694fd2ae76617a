"""Async facade over LLMEngine: a dedicated step-loop thread feeding asyncio streams.

JAX dispatch blocks the calling thread, so the engine loop lives off the event loop;
request submission and token delivery cross the boundary through thread-safe queues —
the same split the reference's engines use (API server process ↔ engine core).
"""

from __future__ import annotations

import asyncio
import threading
import time
import traceback
from typing import AsyncIterator, Callable, Optional

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine.engine import EngineOutput, LLMEngine, _StepParts


class EngineDeadError(RuntimeError):
    """The step loop died on an exception; no request can complete."""


class AsyncLLMEngine:
    # The loop from inside (PERF.md section 3): each turn's wall time goes to
    # one of these parts, as llmd.loop.* profiler spans (step() carries
    # llmd.step itself) and, from the same readings, as
    # llmd_tpu:engine_loop_seconds_total{part}.
    loop_parts: tuple[str, ...] = ("lock", "step", "deliver", "idle")

    def __init__(self, engine: LLMEngine, idle_sleep_s: float = 0.002) -> None:
        self.engine = engine
        self._idle_sleep = idle_sleep_s
        # set once by the engine thread when step() raises (a Mosaic refusal
        # at the first real step, a device fault): every open stream fails
        # with it, new requests are refused, /health goes 503, and
        # ``on_fatal`` lets the owning process exit non-zero instead of
        # serving 200s in front of a dead loop
        self.fatal: Optional[BaseException] = None
        self.on_fatal: Optional[Callable[[BaseException], None]] = None
        self._lock = threading.Lock()
        # request_id -> (caller loop, stream queue); written from caller
        # event loops, drained/popped from the engine thread.
        # guarded-by: _lock
        self._streams: dict[str, tuple[asyncio.AbstractEventLoop, asyncio.Queue]] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return  # shared across rank frontends — only one step loop
        self._thread = threading.Thread(target=self._run, name="engine-loop", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=10)

    def _loop_turn(self, first: str) -> _StepParts:
        """The timeline of one turn of the loop thread, ``first`` running."""
        return _StepParts("loop", "llmd.loop", first, self.loop_parts)

    def _loop_counters(self) -> dict:
        """The loop counter's children by part, taken once a loop."""
        return {p: self.engine.metrics.loop_seconds.labels(part=p)
                for p in self.loop_parts}

    def _book_turn(self, parts: _StepParts, booked: dict) -> None:
        programs = getattr(self.engine, "programs", None)
        if programs is not None and programs.unread:
            # a step program compiled in this turn (the warm-up): read its
            # text once, here and not in step() (read_compiled_programs)
            self.engine.read_compiled_programs()
        parts.to(None)
        for part, sec in parts.seconds.items():
            booked[part].inc(sec)

    def _deliver(self, outputs: list[EngineOutput], parts: _StepParts) -> None:
        """Hand a step's outputs to their streams, as the turn's ``deliver``."""
        if not outputs:
            return
        parts.to("deliver")
        n = 0
        for out in outputs:
            with self._lock:
                entry = self._streams.get(out.request_id)
                if out.finished:
                    self._streams.pop(out.request_id, None)
            if entry is None:
                continue
            loop, q = entry
            loop.call_soon_threadsafe(q.put_nowait, out)
            n += 1
        self.engine.metrics.outputs_delivered.inc(n)

    def _run(self) -> None:
        booked = self._loop_counters()
        while not self._stop.is_set():
            # heartbeat BEFORE taking the lock: a step wedged on the device
            # holds the lock, so stamping inside it would mask the stall the
            # watchdog (obs/device.py) exists to catch
            mon = getattr(self.engine, "monitor", None)
            if mon is not None:
                mon.heartbeat()
            parts = self._loop_turn("lock")
            self._lock.acquire()
            parts.to("step", annotate=False)
            try:
                try:
                    has_work = self.engine.has_work()
                    outputs = self.engine.step() if has_work else []
                finally:
                    self._lock.release()  # _die takes it again
            except Exception as e:  # boundary: the loop cannot continue
                traceback.print_exc()
                self._die(e)
                return
            self._deliver(outputs, parts)
            if not has_work:
                parts.to("idle")
                time.sleep(self._idle_sleep)
            self._book_turn(parts, booked)

    def _die(self, exc: BaseException) -> None:
        with self._lock:
            self.fatal = exc
            streams, self._streams = self._streams, {}
        dead = EngineDeadError(f"engine step loop died: "
                               f"{type(exc).__name__}: {exc}")
        for loop, q in streams.values():
            loop.call_soon_threadsafe(q.put_nowait, dead)
        if self.on_fatal is not None:
            self.on_fatal(exc)

    # -- API ---------------------------------------------------------------
    async def generate(
        self,
        request_id: str,
        token_ids: list[int],
        sampling: SamplingParams,
        lora_id: Optional[str] = None,
        rank: int = 0,
        mm_items=None,
        trace_ctx=None,
    ) -> AsyncIterator[EngineOutput]:
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()
        try:
            with self._lock:  # stream registration + admission are atomic
                if self.fatal is not None:
                    raise EngineDeadError(
                        f"engine step loop died: {type(self.fatal).__name__}: "
                        f"{self.fatal}")
                self._streams[request_id] = (loop, q)
                self.engine.add_request(request_id, token_ids, sampling, lora_id,
                                        rank=rank, mm_items=mm_items,
                                        trace_ctx=trace_ctx)
        except ValueError:
            with self._lock:
                self._streams.pop(request_id, None)
            raise
        try:
            while True:
                out: EngineOutput = await q.get()
                if isinstance(out, EngineDeadError):
                    raise out
                yield out
                if out.finished:
                    return
        finally:
            with self._lock:
                self._streams.pop(request_id, None)
                dead = self.fatal is not None
            if not dead and request_id in self.engine.seqs:
                with self._lock:
                    self.engine.abort(request_id)

    def stats(self):
        return self.engine.stats

    def run_locked(self, fn):
        """Run fn() while the step loop is paused — for callers that must mutate
        engine state (KV injection/export) without racing a step in flight."""
        with self._lock:
            return fn()
