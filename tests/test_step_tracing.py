"""The step loop measured from inside (ISSUE 24): the part counter against
the step histogram, fused-decode seat accounting, context tokens per
dispatch, the ``llmd.*`` spans and clock marks of a capture, the process-wide
compile counter, stream lag, and the two ledgers' new events."""

import json
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
])
def test_parts_sum_to_the_step_histogram(program, phases):
    eng = _engine()
    prompts = [list(range(5, 5 + n)) for n in (70, 20, 45)]
    eng.generate(prompts, SamplingParams(max_tokens=13, **GREEDY))
    parts = _total(eng.registry, "llmd_tpu:engine_step_part_seconds_total",
                   f'program="{program}"')
    hist = sum(_total(eng.registry,
                      "llmd_tpu:engine_step_duration_seconds_sum",
                      f'phase="{p}"') for p in phases)
    assert parts > 0 and hist > 0
    assert abs(parts - hist) <= 0.02 * hist, (parts, hist)
    named = {labels for labels, v in _samples(
        eng.registry, "llmd_tpu:engine_step_part_seconds_total").items()
        if f'program="{program}"' in labels and v > 0}
    want = (("plan", "pack", "dispatch", "apply", "sample", "wait", "book")
            if program == "unified" else
            ("plan", "pack", "dispatch", "wait", "apply", "book"))
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
    # the stats splits are the same readings: host pack + enqueue + the rest
    st = eng.stats
    assert st.time_host_pack > 0 and st.time_device > 0
    assert st.time_postprocess > 0


# ------------------------------------------------------------ seat accounting

def test_decode_seat_steps_exact_for_a_constructed_batch():
    """Three rows on four seats, k = 4, six tokens each: the first comes from
    the prefill's sample, five from fused calls. Unpipelined, that is two
    calls: 4 kept a row, then 1 kept and 3 step-slots past the end."""
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
    assert got == {"kept": 15.0, "finished": 9.0, "empty": 8.0}
    assert got["kept"] == eng.stats.decode_tokens_fused


def test_decode_seat_steps_partition_every_call_when_pipelined():
    eng = _engine()
    prompts = [list(range(10, 30)), list(range(40, 52))]
    eng.generate(prompts, SamplingParams(max_tokens=11, **GREEDY))
    seats = _samples(eng.registry, "llmd_tpu:decode_seat_steps_total")
    k, seats_per_call = BASE["decode_steps"], BASE["max_batch_size"]
    assert sum(seats.values()) == k * seats_per_call * eng.stats.n_decode_calls
    assert seats['{outcome="kept"}'] == eng.stats.decode_tokens_fused
    assert seats['{outcome="empty"}'] == k * 2 * eng.stats.n_decode_calls


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

def test_capture_holds_nested_step_spans_and_two_clock_marks(tmp_path):
    from jax.profiler import ProfileData

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
    time.sleep(0.2)  # the session is up
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
    for name in ("llmd.step", "llmd.admit", "llmd.route", "llmd.unified",
                 "llmd.unified.plan", "llmd.unified.pack",
                 "llmd.unified.dispatch", "llmd.unified.wait",
                 "llmd.unified.apply", "llmd.unified.book",
                 "llmd.decode_dispatch", "llmd.decode_dispatch.pack",
                 "llmd.decode_process", "llmd.decode_process.wait",
                 "llmd.decode_process.apply"):
        assert spans.get(name), (name, sorted(spans))

    def inside(inner: str, outer: str) -> bool:
        return all(any(a <= s and e <= b for a, b in spans[outer])
                   for s, e in spans[inner])

    assert inside("llmd.unified.pack", "llmd.unified")
    assert inside("llmd.unified", "llmd.step")
    assert inside("llmd.decode_process.wait", "llmd.decode_process")


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
