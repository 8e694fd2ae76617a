"""The step loop measured from inside (ISSUE 24): the part counter against
the step histogram, fused-decode seat accounting, context tokens per
dispatch, the ``llmd.*`` spans and clock marks of a capture, the process-wide
compile counter, stream lag, and the two ledgers' new events. And the step
thread's whole turn on that ledger (ISSUE 36): admission and the rest of
``step()`` as counted parts, ``dispatch`` parted in three, what admission did,
and the ten counter-read metrics of ``perfbench/metrics``."""

import json
import os
import sys
import threading
import time

import aiohttp
import jax
import jax.numpy as jnp
import pytest

from llmd_tpu.core.config import FrameworkConfig
from llmd_tpu.core.endpoint import Endpoint, EndpointPool
from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.engine.async_engine import AsyncLLMEngine
from llmd_tpu.engine.server import EngineServer
from llmd_tpu.models import get_model_config
from llmd_tpu.obs.attribution import build_ledger
from llmd_tpu.obs.device import DeviceMonitor
from llmd_tpu.obs.metrics import Registry
from llmd_tpu.router.plugins import known_plugin_types
from llmd_tpu.router.server import RouterServer
from llmd_tpu.testing.fake_server import FakeModelServer, FakeServerConfig
from tests.conftest import run_async
from tests.test_pipeline_prefill_sample import generate
from tests.test_router import CFG

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the benchmark's readers, by path and for the import alone (perfbench/ has a
# tests/ of its own, which must not shadow this package for the other files)
sys.path.append(os.path.join(ROOT, "perfbench"))
try:
    import prom  # noqa: E402
    import readers  # noqa: E402
finally:
    sys.path.remove(os.path.join(ROOT, "perfbench"))

PARTS = "llmd_tpu:engine_step_part_seconds_total"

BASE = dict(page_size=8, num_pages=64, max_model_len=256, max_batch_size=4,
            prefill_chunk=32, decode_steps=4)
GREEDY = dict(temperature=0.0, ignore_eos=True)


def _engine(**kw) -> LLMEngine:
    return LLMEngine(get_model_config("tiny"), EngineConfig(**{**BASE, **kw}))


def _samples(registry: Registry, name: str) -> dict:
    """{rendered labels: value} of one exposed series."""
    return {labels: v for n, labels, v in registry.collect() if n == name}


def _total(registry: Registry, name: str, *having: str) -> float:
    return sum(v for labels, v in _samples(registry, name).items()
               if all(h in labels for h in having))


# --------------------------------------------------- parts against histogram

@pytest.mark.parametrize("program,phases", [
    ("unified", ("unified",)),
    ("decode", ("decode_dispatch", "decode_process")),
    ("admit", ("admit",)),
])
def test_parts_sum_to_the_step_histogram(program, phases):
    eng = _engine()
    prompts = [list(range(5, 5 + n)) for n in (70, 20, 45)]
    eng.generate(prompts, SamplingParams(max_tokens=13, **GREEDY))
    parts = _total(eng.registry, PARTS, f'program="{program}"')
    hist = sum(_total(eng.registry,
                      "llmd_tpu:engine_step_duration_seconds_sum",
                      f'phase="{p}"') for p in phases)
    assert parts > 0 and hist > 0
    assert abs(parts - hist) <= 0.02 * hist, (parts, hist)
    named = {labels for labels, v in _samples(
        eng.registry, "llmd_tpu:engine_step_part_seconds_total").items()
        if f'program="{program}"' in labels and v > 0}
    want = {"unified": ("plan", "pack", "stage", "transfer", "dispatch",
                        "apply", "sample", "wait", "book"),
            "decode": ("plan", "pack", "stage", "transfer", "dispatch",
                       "wait", "apply", "book"),
            "admit": ("hash", "match", "place")}[program]
    for part in want:
        assert any(f'part="{part}"' in labels for labels in named), part
    # the unified step's ``sample`` is the record of the tokens its own
    # program picked, no dispatch of a sampler (ISSUE 31): every step of
    # these greedy rows took the argmax branch, k a fused call
    steps = _samples(eng.registry, "llmd_tpu:sampler_steps_total")
    assert steps == {
        '{program="unified",path="argmax"}': eng.stats.n_unified_steps,
        '{program="decode",path="argmax"}':
            BASE["decode_steps"] * eng.stats.n_decode_dispatches}
    assert not _total(eng.registry, "llmd_tpu:engine_program_dispatches_total",
                      'program="sample"')
    # host pack, the call's enqueue and the rest of a step program (what
    # EngineStats.time_* split until ISSUE 36) are parts of the one counter
    def seconds(prog: str, *names: str) -> float:
        return sum(_total(eng.registry, PARTS, f'program="{prog}"',
                          f'part="{n}"') for n in names)

    for prog in ("unified", "decode"):
        assert seconds(prog, "plan", "pack") > 0
        assert seconds(prog, "dispatch") > 0
        assert seconds(prog, "wait", "apply") > 0


# ---------------------------------------------------------- the whole ledger

def test_parts_of_all_programs_cover_step_with_long_prompts_admitted():
    """Over steps that admit 8k-token prompts (hashed, matched and placed on
    the step thread), the parts of every program (admit, step, unified,
    decode, sample) sum to the wall of ``engine.step()``: within the 2% of
    the per-program invariant, or 100 us a step, whichever is larger (what
    runs between two parts: opening one timeline as another closes, the
    duration histogram's own sample)."""
    eng = _engine(page_size=16, num_pages=2048, max_model_len=8448)
    sp = SamplingParams(max_tokens=6, **GREEDY)
    wall, n_steps = 0.0, 0

    def steps(n: int) -> None:
        nonlocal wall, n_steps
        for _ in range(n):
            t0 = time.perf_counter()
            eng.step()
            wall += time.perf_counter() - t0
            n_steps += 1

    # a short prompt to its end (fused decode calls), then one of 8,192
    # tokens for five steps of 32 tokens (the first four blocks committed and
    # more), then two more that find those four blocks cached. An 8k context
    # costs this CPU some 0.7 s a step, so no 8k prompt is run to its end
    eng.add_request("short", list(range(3, 23)), sp)
    while eng.has_work():
        steps(1)
    shared = list(range(7, 7 + 64))
    eng.add_request("r0", shared + [11] * 8128, sp)
    steps(5)
    for rid in ("r1", "r2"):
        eng.add_request(rid, shared + [12 + int(rid[1])] * 8128, sp)
    steps(3)
    booked = _total(eng.registry, PARTS)
    assert abs(booked - wall) <= max(0.02 * wall, 100e-6 * n_steps), (
        booked, wall, n_steps)
    for prog in ("admit", "step", "unified", "decode"):
        assert _total(eng.registry, PARTS, f'program="{prog}"') > 0, prog
    assert _total(eng.registry, PARTS, 'program="step"', 'part="route"') > 0
    assert _total(eng.registry, PARTS, 'program="step"', 'part="tail"') > 0
    # three prompts of 8,192 tokens, hashed once each; every attempt seated
    assert _total(eng.registry,
                  "llmd_tpu:admit_hashed_tokens_total") == 20 + 3 * 8192
    assert _total(eng.registry,
                  "llmd_tpu:engine_prefix_cached_tokens_total") == 2 * 64
    assert _admissions(eng) == {"admitted": 4, "no_seat": 0, "no_pages": 0,
                                "never_fits": 0}


# ------------------------------------------------------- what admission did

def _admissions(eng: LLMEngine) -> dict:
    return {labels[len('{outcome="'):-2]: int(v) for labels, v in _samples(
        eng.registry, "llmd_tpu:admissions_total").items()}


@pytest.mark.parametrize("outcome,over,waves,want", [
    # two prompts on four seats: both seated, and no attempt after them
    ("admitted", {}, ((20, 12),),
     {"admitted": 2, "no_seat": 0, "no_pages": 0, "never_fits": 0}),
    # three prompts on two seats: the third finds no seat
    ("no_seat", {"max_batch_size": 2}, ((20, 12, 9),),
     {"admitted": 2, "no_seat": 1, "no_pages": 0, "never_fits": 0}),
    # 8 pages of 8 tokens. Admission budgets pages and takes none but the
    # cached: 40 tokens hold 5 pages once prefilled (32 tokens a step, so
    # after the second), and 30 more then need 4 of the 3 that are free
    ("no_pages", {"num_pages": 8}, ((40,), (), (30,)),
     {"admitted": 1, "no_seat": 0, "no_pages": 1, "never_fits": 0}),
    # 4 pages hold 32 tokens: the head, grown to 40 in the queue (add_request
    # turns such a prompt away), never fits; the prompt behind it does
    ("never_fits", {"num_pages": 4}, ((20, 9),),
     {"admitted": 1, "no_seat": 0, "no_pages": 0, "never_fits": 1}),
])
def test_admissions_total_exact_for_each_outcome(outcome, over, waves, want):
    """A wave of prompts joins the queue, then one step runs."""
    eng = _engine(**over)
    outs, n_added = [], 0
    for wave in waves:
        for n in wave:
            first = 10 + 50 * n_added  # no prompt a prefix of another
            eng.add_request(f"r{n_added}", list(range(first, first + n)),
                            SamplingParams(max_tokens=4, **GREEDY))
            n_added += 1
        if outcome == "never_fits":
            head = eng.waitq[0][0]
            head.token_ids, head.prompt_len = list(range(10, 50)), 40
        outs += eng.step()
    assert _admissions(eng) == want
    prompts = [n for wave in waves for n in wave]
    if outcome == "never_fits":
        prompts[0] = 40
    assert _total(eng.registry, "llmd_tpu:admit_hashed_tokens_total") == sum(
        prompts[:want["admitted"] + want["no_pages"] + want["never_fits"]])
    if outcome == "never_fits":
        assert [(o.request_id, o.finish_reason) for o in outs
                if o.finished] == [("r0", "length")]


def test_a_head_held_for_pages_is_hashed_again_at_every_step():
    """8 pages of 8 tokens: ``a`` (40 tokens and 12 more) holds 5 of them once
    prefilled and 7 at its end, so ``b`` (30 tokens, 4 pages), which joins
    then, is the held head of the queue at every step until ``a`` ends, and
    its whole prompt is hashed at each."""
    eng = _engine(num_pages=8)
    sp = SamplingParams(max_tokens=12, **GREEDY)
    eng.add_request("a", list(range(10, 50)), sp)
    eng.step()
    eng.step()  # 32 tokens a step: a's prompt is in its pages
    assert _admissions(eng)["admitted"] == 1
    assert _total(eng.registry, "llmd_tpu:admit_hashed_tokens_total") == 40
    eng.add_request("b", list(range(60, 90)), sp)
    hashed: list[float] = []
    while _admissions(eng)["admitted"] < 2:
        eng.step()
        hashed.append(_total(eng.registry,
                             "llmd_tpu:admit_hashed_tokens_total"))
        assert len(hashed) < 50, hashed
    # every step hashed b's 30 tokens again, the last of them the attempt
    # that seated it
    assert hashed[0] == 70
    assert {b - a for a, b in zip(hashed, hashed[1:])} == {30.0}, hashed
    adm = _admissions(eng)
    assert adm["no_pages"] == len(hashed) - 1 >= 2, (adm, hashed)
    while eng.has_work():
        eng.step()
    assert _total(eng.registry,
                  "llmd_tpu:admit_hashed_tokens_total") == hashed[-1]
    assert _admissions(eng) == adm


# ------------------------------------------------------------ seat accounting

def test_decode_seat_steps_exact_for_a_constructed_batch():
    """Three rows on four seats, k = 4, six tokens each: the first comes from
    the prefill's sample, five from fused calls. Unpipelined, that is two
    calls: 4 kept a row, then a call of one step (no row has a second token
    to take, so the call ends there: ISSUE 38) with 1 kept a row. No
    step-slot past a row's end is run or booked."""
    eng = _engine()
    # all three prefill in one unified step (32 tokens, its whole budget): a
    # prompt left for a second step would find the others riding in it as
    # decode rows, ahead of the fused calls
    prompts = [list(range(10, 22)), list(range(40, 48)), list(range(60, 72))]
    out = generate(eng, prompts, SamplingParams(max_tokens=6, **GREEDY),
                   unchained=True)
    assert all(len(v) == 6 for v in out.values())
    seats = _samples(eng.registry, "llmd_tpu:decode_seat_steps_total")
    got = {o: seats[f'{{outcome="{o}"}}']
           for o in ("kept", "finished", "empty")}
    assert eng.stats.n_decode_calls == 2
    assert got == {"kept": 15.0, "finished": 0.0, "empty": 5.0}
    assert got["kept"] == eng.stats.decode_tokens_fused
    assert _samples(eng.registry, "llmd_tpu:decode_call_steps_total") == {
        '{bound="cap"}': 4.0, '{bound="ending"}': 1.0}


def test_decode_seat_steps_partition_every_call_when_pipelined():
    eng = _engine()
    prompts = [list(range(10, 30)), list(range(40, 52))]
    eng.generate(prompts, SamplingParams(max_tokens=11, **GREEDY))
    seats = _samples(eng.registry, "llmd_tpu:decode_seat_steps_total")
    # a call books the steps it was given on every seat: 4, 4 and the 2 its
    # rows had left
    steps = sum(_samples(eng.registry,
                         "llmd_tpu:decode_call_steps_total").values())
    assert steps == 10 and eng.stats.n_decode_calls == 3
    assert sum(seats.values()) == steps * BASE["max_batch_size"]
    assert seats['{outcome="kept"}'] == eng.stats.decode_tokens_fused
    assert seats['{outcome="empty"}'] == steps * 2


# ------------------------------------------------------------ context tokens

def test_kv_read_tokens_equal_the_context_lengths_dispatched():
    """One 20-token prompt, chunk 32, k = 4, six tokens, unpipelined: the
    unified step reads 20 positions; the fused calls start at contexts of 21
    (prompt + the sampled first token) and 25."""
    eng = _engine()
    generate(eng, [list(range(10, 30))],
             SamplingParams(max_tokens=6, **GREEDY), unchained=True)
    kv = _samples(eng.registry, "llmd_tpu:program_kv_read_tokens_total")
    rows = _samples(eng.registry, "llmd_tpu:program_rows_total")
    assert kv['{program="unified"}'] == 20
    assert kv['{program="decode"}'] == 21 + 25
    assert rows['{program="unified"}'] == 1
    assert rows['{program="decode"}'] == 2


# ------------------------------------------------------- spans in a capture

def test_capture_holds_nested_step_spans_and_two_clock_marks(tmp_path,
                                                            monkeypatch):
    from jax.profiler import ProfileData

    import llmd_tpu.obs.device as device

    up, mark = threading.Event(), device._clock_mark
    monkeypatch.setattr(device, "_clock_mark", lambda: (mark(), up.set())[0])
    eng = _engine()
    sp = SamplingParams(max_tokens=8, **GREEDY)
    eng.generate([list(range(10, 40))], sp)  # compile outside the capture
    mon = DeviceMonitor(Registry(), flight=eng.flight,
                        profile_dir=str(tmp_path))
    result: dict = {}
    seconds = 1.5
    t = threading.Thread(target=lambda: result.update(
        mon.capture_profile(seconds, python_tracer=False)))
    t.start()
    # the session is up once its start mark is written (a step that runs
    # while it comes up leaves its inner spans without the outer one)
    assert up.wait(60)
    # generate until the capture ends: on a loaded machine (six test workers)
    # one step can stall for some 0.4 s, and a span still open when the
    # capture stops is not in it
    i = 0
    while t.is_alive() or i < 3:
        eng.generate([list(range(50 + i % 8, 120 + i % 8))], sp)
        i += 1
    t.join(timeout=60)
    assert result["python_tracer"] is False
    clock = result["clock"]
    for mark in (clock["start"], clock["end"]):
        assert mark["unix_ns"] > 1e18 and mark["mono_ns"] > 0
    assert (clock["end"]["mono_ns"] - clock["start"]["mono_ns"]
            == pytest.approx(seconds * 1e9, rel=0.5))
    pb = [f for f in result["files"] if f.endswith(".xplane.pb")]
    assert pb, result
    data = ProfileData.from_file(f"{result['dir']}/{pb[0]}")
    spans: dict = {}
    marks = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "llmd.clock":
                    marks.append(dict(ev.stats))
                elif ev.name.startswith("llmd."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    assert len(marks) == 2, marks
    assert {int(m["mono_ns"]) for m in marks} == {
        clock["start"]["mono_ns"], clock["end"]["mono_ns"]}
    for name in ("llmd.step", "llmd.admit", "llmd.admit.hash",
                 "llmd.admit.match", "llmd.admit.place", "llmd.route",
                 "llmd.tail", "llmd.unified",
                 "llmd.unified.plan", "llmd.unified.pack",
                 "llmd.unified.stage", "llmd.unified.transfer",
                 "llmd.unified.dispatch", "llmd.unified.wait",
                 "llmd.unified.apply", "llmd.unified.book",
                 "llmd.decode_dispatch", "llmd.decode_dispatch.pack",
                 "llmd.decode_dispatch.stage", "llmd.decode_dispatch.transfer",
                 "llmd.decode_dispatch.dispatch",
                 "llmd.decode_process", "llmd.decode_process.wait",
                 "llmd.decode_process.apply"):
        assert spans.get(name), (name, sorted(spans))

    # A span still open when the capture stops is not in it, while the spans
    # inside it that had closed are: they start past the last outer span's
    # end, wherever on a step the capture's end falls (a loaded machine).
    def inside(inner: str, outer: str) -> bool:
        last = max(b for _, b in spans[outer])
        return all(any(a <= s and e <= b for a, b in spans[outer])
                   for s, e in spans[inner] if s < last)

    assert inside("llmd.unified.pack", "llmd.unified")
    assert inside("llmd.unified", "llmd.step")
    assert inside("llmd.decode_process.wait", "llmd.decode_process")
    # admission's parts nest in llmd.admit, so a reader that names a stretch
    # by its innermost llmd.* span (perfbench/xplane.py) splits the admit gap
    for part in ("hash", "match", "place"):
        assert inside(f"llmd.admit.{part}", "llmd.admit")
    assert len(spans["llmd.admit.hash"]) > 1
    assert inside("llmd.admit", "llmd.step")
    assert inside("llmd.tail", "llmd.step")
    # the train of a unified step: stage, then the transfers, then the call
    def within(name: str, a: int, b: int) -> list:
        return sorted((s, e) for s, e in spans[name] if a <= s and e <= b)

    trains = 0
    for a, b in spans["llmd.unified"]:
        stage, xfer, call = (within(f"llmd.unified.{part}", a, b)
                             for part in ("stage", "transfer", "dispatch"))
        if stage and xfer and call:  # a step that dispatched (no empty plan)
            trains += 1
            # (the transfers' second stretch, after the call, is the release
            # of the host's handles on the arrays)
            assert stage[-1][1] <= xfer[0][0] and xfer[0][1] <= call[0][0]
            assert len(xfer) == 2 and call[-1][1] <= xfer[1][0]
    assert trains


def test_loop_spans_counters_and_deliveries():
    eng = _engine()
    aeng = AsyncLLMEngine(eng)
    aeng.start()

    async def one() -> int:
        n = 0
        async for out in aeng.generate(
                "r1", list(range(10, 30)),
                SamplingParams(max_tokens=9, **GREEDY)):
            assert out.t_step > 0
            n += 1
        return n

    try:
        t0 = time.perf_counter()
        n_outputs = run_async(one())
        time.sleep(0.05)  # a few idle turns
    finally:
        aeng.stop()
    wall = time.perf_counter() - t0
    loop = _samples(eng.registry, "llmd_tpu:engine_loop_seconds_total")
    assert set(loop) == {f'{{part="{p}"}}'
                         for p in ("lock", "step", "deliver", "idle")}
    assert loop['{part="step"}'] > 0 and loop['{part="idle"}'] > 0
    assert loop['{part="deliver"}'] > 0
    assert sum(loop.values()) <= wall * 1.05
    assert _total(eng.registry,
                  "llmd_tpu:engine_outputs_delivered_total") == n_outputs


def test_parts_of_all_programs_sum_to_the_loops_step_less_has_work():
    """What the loop thread books as ``step`` is ``has_work()`` and ``step()``,
    and the part counter holds all of ``step()``: over a served request and
    some idle turns, the parts of every program and ``has_work()`` (timed
    here, from outside) make the loop's ``step`` within 2%, or 100 us a turn,
    whichever is larger."""
    eng = _engine()
    probe = {"s": 0.0, "n": 0}
    has_work = eng.has_work

    def timed_has_work() -> bool:
        t0 = time.perf_counter()
        try:
            return has_work()
        finally:
            probe["s"] += time.perf_counter() - t0
            probe["n"] += 1

    eng.has_work = timed_has_work
    aeng = AsyncLLMEngine(eng)
    aeng.start()

    async def one(rid: str, first: int) -> None:
        async for _ in aeng.generate(
                rid, list(range(first, first + 40)),
                SamplingParams(max_tokens=9, **GREEDY)):
            pass

    try:
        run_async(one("r1", 10))
        run_async(one("r2", 70))
        time.sleep(0.03)
    finally:
        aeng.stop()
    loop_step = _total(eng.registry, "llmd_tpu:engine_loop_seconds_total",
                       'part="step"')
    booked = _total(eng.registry, PARTS)
    assert booked > 0 and probe["n"] > 0
    assert abs(loop_step - probe["s"] - booked) <= max(
        0.02 * loop_step, 100e-6 * probe["n"]), (loop_step, booked, probe)


def test_dp_loop_books_its_turns_and_the_coordinators_round_trip():
    """The data-parallel loop books the same counter through the same helper
    as the base loop, the coordinator's round trip under a part of its own."""
    from llmd_tpu.engine.dp_group import DPAsyncEngine, DPWorkerSync

    class Wave(DPWorkerSync):
        """A coordinator that answers at once: step whenever this rank has
        work, and join an empty wave now and then."""
        def __init__(self):
            super().__init__(rank=0, host="127.0.0.1", port=1)
            self.reports = 0

        def register(self, barrier_timeout_s=30.0):
            pass

        def report(self, has_work):
            self.reports += 1
            return has_work or self.reports % 4 == 0

    eng = _engine()
    worker = Wave()
    aeng = DPAsyncEngine(eng, worker)
    aeng.start()

    async def one() -> int:
        n = 0
        async for _ in aeng.generate(
                "r1", list(range(10, 30)),
                SamplingParams(max_tokens=9, **GREEDY)):
            n += 1
        return n

    try:
        t0 = time.perf_counter()
        n_outputs = run_async(one())
        time.sleep(0.05)
    finally:
        aeng.stop()
    wall = time.perf_counter() - t0
    assert aeng.registered and worker.reports > 0 and aeng.empty_steps > 0
    loop = _samples(eng.registry, "llmd_tpu:engine_loop_seconds_total")
    assert set(loop) == {f'{{part="{p}"}}' for p in (
        "lock", "step", "deliver", "idle", "coordinate")}
    for part in ("step", "deliver", "idle", "coordinate"):
        assert loop[f'{{part="{part}"}}'] > 0, (part, loop)
    assert sum(loop.values()) <= wall * 1.05
    assert _total(eng.registry,
                  "llmd_tpu:engine_outputs_delivered_total") == n_outputs
    # the step thread's ledger holds under this loop too
    assert _total(eng.registry, PARTS) <= loop['{part="step"}']


# ------------------------------------------------------------------ compiles

def test_xla_compiles_total_grows_on_a_fresh_jit_only():
    eng = _engine()

    def count() -> float:
        return _total(eng.registry, "llmd_tpu:xla_compiles_total")

    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(7)  # made first: building it compiles too
    before = count()
    f(x).block_until_ready()
    first = count()
    f(x).block_until_ready()
    assert first == before + 1
    assert count() == first
    assert _total(eng.registry, "llmd_tpu:xla_compile_seconds_total") > 0
    names = [e.get("fun_name") for e in eng.flight.system_events()
             if e["event"] == "xla_compile"]
    assert any(n and "lambda" in n for n in names), names


# ---------------------------------------------------------------- stream lag

def test_two_stream_lag_observations_per_streamed_request(monkeypatch):
    monkeypatch.setenv("LLMD_WATCHDOG_STALL_S", "0")
    monkeypatch.setenv("LLMD_FABRIC_PROBE_INTERVAL_S", "0")

    async def scenario():
        server = EngineServer(
            get_model_config("tiny"), EngineConfig(**BASE),
            model_name="test/tiny", host="127.0.0.1", port=0, kv_events_port=0)
        await server.start()
        try:
            base = f"http://{server.address}"
            async with aiohttp.ClientSession() as sess:
                for n in (9, 1):  # several chunks; one chunk, first and last
                    async with sess.post(f"{base}/v1/completions", json={
                            "prompt": "lag of the stream", "max_tokens": n,
                            "temperature": 0.0, "ignore_eos": True,
                            "stream": True}) as r:
                        assert r.status == 200
                        await r.read()
                async with sess.get(f"{base}/metrics") as r:
                    text = await r.text()
        finally:
            await server.stop()
        return text

    text = run_async(scenario())
    for at in ("first", "last"):
        line = [ln for ln in text.splitlines() if ln.startswith(
            f'llmd_tpu:stream_lag_seconds_count{{at="{at}"}}')]
        assert line and float(line[0].split()[-1]) == 2, (at, line)
    total = [ln for ln in text.splitlines()
             if ln.startswith("llmd_tpu:stream_lag_seconds_sum")]
    assert all(0 <= float(ln.split()[-1]) < 5 for ln in total)


# ------------------------------------------------------------------- ledgers

def test_engine_ledger_schedule_ends_at_dispatched():
    eng = _engine()
    eng.generate([list(range(10, 80))], SamplingParams(max_tokens=5, **GREEDY))
    rec = eng.flight.get("req-0")
    names = [e["event"] for e in rec["events"]]
    assert names.count("dispatched") == 1
    assert (names.index("admitted") < names.index("dispatched")
            < names.index("prefill_start"))
    at = {e["event"]: e["t_ms"] for e in reversed(rec["events"])}
    ledger = build_ledger(rec)
    assert ledger["phases"]["schedule"] == pytest.approx(
        at["dispatched"] - at["admitted"], abs=0.002)
    total = sum(ledger["phases"].values()) + ledger["residual_ms"]
    assert total == pytest.approx(ledger["wall_ms"], abs=0.05)
    assert ledger["residual_frac"] < 0.05


def test_router_ledger_of_a_streamed_request_holds_first_byte():
    async def scenario():
        srv = FakeModelServer(FakeServerConfig())
        await srv.start()
        pool = EndpointPool()
        pool.upsert(Endpoint(address=srv.address))
        cfg = FrameworkConfig.from_yaml(CFG, known_types=known_plugin_types())
        router = RouterServer(cfg, pool, port=0, poll_interval_s=0.1)
        await router.start()
        try:
            async with aiohttp.ClientSession() as sess:
                async with sess.post(
                        f"http://{router.address}/v1/completions",
                        json={"prompt": "hello there", "max_tokens": 6,
                              "stream": True}) as r:
                    assert r.status == 200
                    rid = r.headers["x-llm-d-request-id"]
                    await r.read()
                async with sess.get(
                        f"http://{router.address}/metrics") as r:
                    text = await r.text()
            return router.flight.get(rid), text
        finally:
            await router.stop()
            await srv.stop()

    rec, text = run_async(scenario())
    names = [e["event"] for e in rec["events"]]
    assert (names.index("forward") < names.index("first_byte")
            < names.index("response"))
    ledger = build_ledger(rec)
    assert ledger["plane"] == "router"
    assert ledger["phases"]["upstream"] > 0
    assert ledger["phases"]["upstream_stream"] > 0
    total = sum(ledger["phases"].values()) + ledger["residual_ms"]
    assert total == pytest.approx(ledger["wall_ms"], abs=0.05)
    assert 'llmd_tpu:request_phase_seconds_count{phase="upstream_stream"' in text


# ---------------------------------------------------------------- step spans

def test_disabled_tracer_walks_no_sequence():
    eng = _engine()
    assert not eng.tracer.cfg.enabled

    class Untouchable:
        def __getattr__(self, name):
            raise AssertionError(f"touched .{name}")

    def seqs():
        raise AssertionError("walked the batch")
        yield Untouchable()

    eng._emit_step_spans("unified", seqs(), time.time_ns(), 1, 1)
    eng._emit_step_spans("decode", [Untouchable()], time.time_ns(), 1, 1)


def test_debug_profile_takes_python_tracer_and_returns_clock(tmp_path,
                                                             monkeypatch):
    monkeypatch.setenv("LLMD_WATCHDOG_STALL_S", "0")
    monkeypatch.setenv("LLMD_FABRIC_PROBE_INTERVAL_S", "0")
    monkeypatch.setenv("LLMD_PROFILE_DIR", str(tmp_path / "profiles"))

    async def scenario():
        server = EngineServer(
            get_model_config("tiny"), EngineConfig(**BASE),
            model_name="test/tiny", host="127.0.0.1", port=0, kv_events_port=0)
        await server.start()
        try:
            base = f"http://{server.address}"
            async with aiohttp.ClientSession() as sess:
                async with sess.get(f"{base}/debug/profile", params={
                        "seconds": "0.2", "python_tracer": "x"}) as r:
                    assert r.status == 400
                async with sess.get(f"{base}/debug/profile", params={
                        "seconds": "0.2", "python_tracer": "0"}) as r:
                    assert r.status == 200, await r.text()
                    return json.loads(await r.text())
        finally:
            await server.stop()

    result = run_async(scenario())
    assert result["python_tracer"] is False
    assert set(result["clock"]) == {"start", "end"}
    assert result["clock"]["end"]["unix_ns"] > result["clock"]["start"]["unix_ns"]


# ------------------------------------------- the ten counter-read metric files

LOOP = "llmd_tpu:engine_loop_seconds_total"


def _scrape(loop: dict, parts: dict, admitted: float, dispatches: float,
            since_36: bool = True) -> list:
    """A Prometheus scrape of an engine, as text, parsed as the benchmark
    parses it. ``since_36`` false is a program before ISSUE 36: no admit or
    step program, no stage or transfer part, no admissions_total."""
    lines = [f'{LOOP}{{part="{p}"}} {v}' for p, v in loop.items()]
    for (program, part), v in parts.items():
        if since_36 or (program not in ("admit", "step")
                        and part not in ("stage", "transfer")):
            lines.append(f'{PARTS}{{program="{program}",part="{part}"}} {v}')
    if since_36:
        lines += [
            f'llmd_tpu:admissions_total{{outcome="admitted"}} {admitted}',
            'llmd_tpu:admissions_total{outcome="no_seat"} 7',
            'llmd_tpu:admit_hashed_tokens_total 123456']
    lines.append('llmd_tpu:engine_program_dispatches_total'
                 f'{{program="unified"}} {dispatches}')
    lines.append('llmd_tpu:engine_program_dispatches_total'
                 '{program="decode"} 11')
    return prom.parse("# HELP some text\n" + "\n".join(lines) + "\n")


def _two_scrapes(since_36: bool = True) -> dict:
    """Growth between the scrapes: the loop 40 s (lock 0.5, step 30, deliver
    4, idle 5.5), waits 10 s (unified 6, decode 3, sample 1), admission 0.6 s
    (hash 0.3, match 0.2, place 0.1) for 200 sequences seated, and of 800
    unified dispatches transfer 2.4 s and the call 1.2 s."""
    loop0 = {"lock": 1.0, "step": 50.0, "deliver": 5.0, "idle": 9.0}
    loop1 = {"lock": 1.5, "step": 80.0, "deliver": 9.0, "idle": 14.5}
    parts0 = {("unified", "wait"): 2.0, ("decode", "wait"): 1.0,
              ("sample", "wait"): 0.5, ("unified", "plan"): 3.0,
              ("unified", "stage"): 0.25, ("unified", "transfer"): 1.0,
              ("unified", "dispatch"): 0.5, ("decode", "dispatch"): 0.75,
              ("admit", "hash"): 0.125, ("admit", "match"): 0.25,
              ("admit", "place"): 0.5, ("step", "route"): 0.125,
              ("step", "tail"): 0.25}
    grown = {("unified", "wait"): 6.0, ("decode", "wait"): 3.0,
             ("sample", "wait"): 1.0, ("unified", "plan"): 2.0,
             ("unified", "stage"): 0.5, ("unified", "transfer"): 2.4,
             ("unified", "dispatch"): 1.2, ("decode", "dispatch"): 4.0,
             ("admit", "hash"): 0.3, ("admit", "match"): 0.2,
             ("admit", "place"): 0.1, ("step", "route"): 0.05,
             ("step", "tail"): 0.4}
    parts1 = {k: parts0[k] + grown[k] for k in parts0}
    return {"before": {"engine": _scrape(loop0, parts0, 100, 1000, since_36)},
            "after": {"engine": _scrape(loop1, parts1, 300, 1800, since_36)}}


@pytest.mark.parametrize("name,moves,unit,want,before_36", [
    ("host_busy_share", "out_tok_s", "%", 60.0, 60.0),
    ("host_busy_share.tpot", "tpot_p95_ms", "%", 60.0, 60.0),
    ("admit_ms", "out_tok_s", "ms", 3.0, None),
    ("admit_ms.tpot", "tpot_p95_ms", "ms", 3.0, None),
    ("admit_hash_ms", "out_tok_s", "ms", 1.5, None),
    ("admit_hash_ms.tpot", "tpot_p95_ms", "ms", 1.5, None),
    ("dispatch_transfer_ms", "out_tok_s", "ms", 3.0, None),
    ("dispatch_transfer_ms.tpot", "tpot_p95_ms", "ms", 3.0, None),
    # the parent's dispatch part held stage and transfer too, under this name
    ("dispatch_call_ms", "out_tok_s", "ms", 1.5, 1.5),
    ("dispatch_call_ms.tpot", "tpot_p95_ms", "ms", 1.5, 1.5),
])
def test_metric_file_reads_the_counters_of_two_scrapes(name, moves, unit,
                                                       want, before_36):
    spec = readers.load(name)
    head = {k: spec[k] for k in ("name", "unit", "better", "source", "layer",
                                 "moves")}
    assert head == {"name": name, "unit": unit, "better": "lower",
                    "source": "program_counter", "layer": "scheduler",
                    "moves": moves}
    # entered in BENCHMARK.json as the file has it, for every cell that
    # reports what it moves (no list of cells)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert entry == [head]
    assert readers.read(spec["reads"], _two_scrapes()) == pytest.approx(want)
    # a program without the series leaves the metric out, and raises nothing
    got = readers.read(spec["reads"], _two_scrapes(since_36=False))
    assert got == (None if before_36 is None else pytest.approx(before_36))
    assert readers.read(spec["reads"],
                        {"before": {}, "after": {"engine": []}}) is None

