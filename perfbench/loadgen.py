"""The load generator: one asyncio loop in the benchmark's parent process,
speaking ``/v1/completions`` with ``stream: true`` to the router.

Closed loop: ``clients`` callers, each sending its next request when the last
completes; with sessions every caller is a conversation lane. Open loop: every request is sent when it is due, whatever has
happened to the ones before, and its latency counts from when it was due. A
session lane is the one dependency: a turn whose lane is still waiting for the
answer to its last turn goes out when that answer is complete.
"""

from __future__ import annotations

import asyncio
import json
import random
import time
from dataclasses import dataclass, field, replace

import aiohttp

from idtok import ids_of
from traffic import Request, Schedule, quantiles, token_ids


@dataclass
class Record:
    req: Request
    due: float = 0.0  # absolute, monotonic
    free: float = 0.0  # when its lane was free (>= due only when blocked)
    sent: float = 0.0
    first: float = 0.0
    last: float = 0.0
    n_out: int = 0
    prompt_tokens: int = 0
    ok: bool = False
    error: str = ""
    in_window: bool = False


@dataclass
class Load:
    records: list = field(default_factory=list)
    events: list = field(default_factory=list)  # (time, tokens)
    t0: float = 0.0  # window start, monotonic
    t1: float = 0.0  # window end
    inflight_at_t0: int = 0
    inflight_at_t1: int = 0
    lane_blocked: int = 0


class Generator:
    def __init__(self, url: str, model: str, vocab: int, sched: Schedule,
                 seed: int, seconds: float) -> None:
        self.url = url + "/v1/completions"
        self.model, self.vocab = model, vocab
        self.sched, self.mix = sched, sched.mix
        self.seed, self.seconds = seed, seconds
        self.load = Load()
        self.inflight = 0
        self.ctx_tokens = {}  # request index -> context tokens now (decoding)
        self.shared = {}  # request index -> (tenant, its tokens of the prompt)
        self.session = None
        ses = self.mix.get("sessions")
        self.history = {}
        self.lane_lock = {}
        if ses:
            self.system = [
                token_ids(random.Random(f"{seed}-tenant-{t}").getrandbits(48),
                          ses["system_prompt"], vocab)
                for t in range(ses["tenants"])]
            self.lane_lock = {l: asyncio.Lock() for l in range(ses["lanes"])}

    async def __aenter__(self):
        self.session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None, sock_read=120))
        return self

    async def __aexit__(self, *exc):
        await self.session.close()

    # ------------------------------------------------------------ one call
    async def complete(self, prompt: list, max_tokens: int, rec: Record | None,
                       index: int = -1, bias: dict | None = None) -> list:
        """Stream one completion; returns the served token ids. ``bias`` is an
        OpenAI ``logit_bias`` (the check's gap probe; no traffic has one)."""
        body = {"model": self.model, "prompt_token_ids": prompt,
                "max_tokens": max_tokens, "temperature": 0.0,
                "ignore_eos": True, "stream": True}
        if bias:
            body["logit_bias"] = bias
        out: list = []
        async with self.session.post(self.url, json=body) as resp:
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}")
            async for line in resp.content:
                if not line.startswith(b"data: "):
                    continue
                if line.startswith(b"data: [DONE]"):
                    break
                now = time.monotonic()
                ids = ids_of(json.loads(line[6:])["choices"][0]["text"])
                if not ids:
                    continue
                out.extend(ids)
                if rec is not None:
                    if not rec.first:
                        rec.first = now
                    rec.last = now
                    rec.n_out = len(out)
                    self.load.events.append((now, len(ids)))
                    self.ctx_tokens[index] = len(prompt) + len(out)
        return out

    async def _timed(self, req: Request, due: float) -> None:
        rec = Record(req=req, due=due, free=due)
        self.load.records.append(rec)
        lock = self.lane_lock.get(req.lane)
        if lock is not None:
            blocked = lock.locked()
            await lock.acquire()
            if blocked:
                self.load.lane_blocked += 1
                rec.free = time.monotonic()
        try:
            new = token_ids(req.token_seed, req.prompt_len, self.vocab)
            if lock is not None:
                if req.turn == 0 or req.lane not in self.history:
                    self.history[req.lane] = list(self.system[req.tenant])
                prompt = self.history[req.lane] + new
                self.shared[req.index] = (req.tenant,
                                          len(self.system[req.tenant]))
            else:
                prompt = new
            rec.prompt_tokens = len(prompt)
            rec.sent = time.monotonic()
            self.inflight += 1
            try:
                out = await self.complete(prompt, req.max_tokens, rec, req.index)
            finally:
                self.inflight -= 1
                self.ctx_tokens.pop(req.index, None)
                self.shared.pop(req.index, None)
            rec.ok = len(out) == req.max_tokens
            if not rec.ok:
                rec.error = f"{len(out)} tokens of {req.max_tokens}"
            if lock is not None:
                self.history[req.lane] = prompt + out
        except Exception as e:  # noqa: BLE001: a failed request is a result
            rec.error = f"{type(e).__name__}: {e}"
        finally:
            if lock is not None:
                lock.release()

    def decoding_rows(self) -> list:
        """(context tokens now, tenant, shared) of every request that has
        its first token and not yet its last: of a session turn's context the
        first ``shared`` tokens are its tenant's system prompt, the same
        tokens at the same positions for every lane of the tenant; without
        sessions tenant None and nothing shared. What the attention
        rooflines' byte demand is counted from
        (``kernels/cached_tokens.py``)."""
        return [(n, *self.shared.get(i, (None, 0)))
                for i, n in self.ctx_tokens.items()]

    # ------------------------------------------------------------- set-up
    async def warm_sessions(self) -> int:
        """Before the ramp: give every lane that begins the run in the middle
        of a conversation its earlier turns, and put them in the prefix cache
        by sending each history once for a single token. Returns the tokens
        sent."""
        ses = self.mix.get("sessions")
        if not ses:
            return 0
        rng = random.Random(f"{self.seed}-warm")
        n = ses["lanes"] * ses["turns"]
        users = quantiles(self.mix["prompt"], n)
        answers = quantiles(self.mix["output"], n)
        rng.shuffle(users)
        rng.shuffle(answers)
        sent = 0
        sem = asyncio.Semaphore(8)

        async def one(lane: int) -> None:
            nonlocal sent
            hist = list(self.system[lane % ses["tenants"]])
            for _ in range(lane % ses["turns"]):
                hist += token_ids(rng.getrandbits(48), users.pop(), self.vocab)
                hist += token_ids(rng.getrandbits(48), answers.pop(),
                                  self.vocab)
            self.history[lane] = hist
            async with sem:
                await self.complete(hist, 1, None)
            sent += len(hist)

        # tenants' system prompts first, one each, so that the lanes after
        # them find the shared part cached as they would in a running system
        await asyncio.gather(*(one(l) for l in range(ses["tenants"])))
        await asyncio.gather(*(one(l) for l in range(ses["tenants"],
                                                     ses["lanes"])))
        return sent

    # ---------------------------------------------------------------- run
    def _mark(self, now: float) -> None:
        ld = self.load
        if not ld.inflight_at_t0 and now >= ld.t0:
            ld.inflight_at_t0 = max(1, self.inflight)
        if not ld.inflight_at_t1 and now >= ld.t1:
            ld.inflight_at_t1 = max(1, self.inflight)

    async def run(self, on_window=None) -> Load:
        """Ramp, window, drain. ``on_window(t0, t1)`` is started as a task
        when the window opens (the traced run's polling and capture)."""
        ld = self.load
        ramp, drain = float(self.mix["ramp_s"]), float(self.mix["drain_s"])
        start = time.monotonic() + 0.2
        ld.t0, ld.t1 = start + ramp, start + ramp + self.seconds
        side = None
        tasks: list = []
        if self.mix["loop"] == "closed":
            pool = self.sched.requests
            nxt = 0

            async def caller(k: int) -> None:
                nonlocal nxt
                await asyncio.sleep(max(0.0, start + ramp / 3 * k
                                        / self.mix["clients"]
                                        - time.monotonic()))
                ses = self.mix.get("sessions")
                turn = k  # lane k begins at turn k mod turns
                while True:
                    req = pool[nxt % len(pool)]
                    nxt += 1
                    if ses:
                        req = replace(req, lane=k, tenant=k % ses["tenants"],
                                      turn=turn % ses["turns"])
                        turn += 1
                    now = time.monotonic()
                    if now >= ld.t1 + drain:
                        return
                    await self._timed(req, now)

            tasks = [asyncio.create_task(caller(k))
                     for k in range(self.mix["clients"])]
        else:
            async def dispatch() -> None:
                for req in self.sched.requests:
                    due = ld.t0 + req.due
                    delay = due - time.monotonic()
                    if delay > 0:
                        await asyncio.sleep(delay)
                    tasks.append(asyncio.create_task(self._timed(req, due)))

            tasks = [asyncio.create_task(dispatch())]
        # the window opens
        await asyncio.sleep(max(0.0, ld.t0 - time.monotonic()))
        self._mark(time.monotonic())
        if on_window is not None:
            side = asyncio.create_task(on_window(ld.t0, ld.t1))
        await asyncio.sleep(max(0.0, ld.t1 - time.monotonic()))
        self._mark(time.monotonic())
        for r in ld.records:
            r.in_window = ld.t0 <= r.due < ld.t1
        # the drain: the load goes on until every request of the window is
        # complete, or the drain's time is up
        while time.monotonic() < ld.t1 + drain:
            if all(r.ok or r.error for r in ld.records if r.in_window):
                break
            await asyncio.sleep(0.05)
        for t in list(tasks):
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        for r in ld.records:
            # a turn dispatched late in the window's last instants
            r.in_window = ld.t0 <= r.due < ld.t1
            if r.in_window and not r.ok and not r.error:
                r.error = "unfinished when the drain ended"
        if side is not None:
            await side
        return ld
