"""The unified step runs one step ahead (ISSUE 29): step n+1 is dispatched
before step n's sampled tokens are read, and a row of step n rides in n+1 as a
decode row that takes its input token on the device.

The oracle is the same engine read synchronously
(``_flush_pending_sample()`` after every ``step()``): the same programs on the
same rows, so greedy tokens are equal token for token; only the moment the
host reads them differs. What differs by design is told by the counters
(``unified_decode_rows_total{token}``, ``unified_ahead_rows_total{outcome}``).
The section "equal to the oracle" is ``tests/test_unified_ahead_oracle.py``.
"""

from __future__ import annotations

import conftest  # noqa: F401
import pytest

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.engine.engine import STEP_PARTS
from llmd_tpu.models import get_model_config
from tests.test_pipeline_prefill_sample import drive

BASE = dict(page_size=8, num_pages=128, max_model_len=256, max_batch_size=4,
            prefill_chunk=16, decode_steps=4)
GREEDY = dict(temperature=0.0, ignore_eos=True)
PROMPTS = [list(range(3, 40)), list(range(50, 75)), list(range(80, 140)),
           list(range(150, 160)), list(range(20, 90)), list(range(30, 55))]


def _engine(model: str = "tiny", **kw) -> LLMEngine:
    return LLMEngine(get_model_config(model), EngineConfig(**{**BASE, **kw}))


def _count(eng: LLMEngine, name: str, label: str) -> float:
    return sum(v for n, labels, v in eng.registry.collect()
               if n == f"llmd_tpu:{name}" and label in labels)


def _ahead(eng: LLMEngine) -> dict:
    return {"device": _count(eng, "unified_decode_rows_total", 'token="device"'),
            "host": _count(eng, "unified_decode_rows_total", 'token="host"'),
            "kept": _count(eng, "unified_ahead_rows_total", 'outcome="kept"'),
            "discarded": _count(eng, "unified_ahead_rows_total",
                                'outcome="discarded"')}


def _arrivals(sp, every: int = 2, prompts=PROMPTS) -> dict:
    """One request every ``every`` steps: sequences keep prefilling while
    others decode, so nearly every step is a mixed unified step."""
    return {i * every: [(f"r{i}", p, sp)] for i, p in enumerate(prompts)}


def _assert_no_row_wasted(eng: LLMEngine, got: dict) -> None:
    """Every decode row of a unified step gave its request a token: the
    tokens delivered are one per request from its prefill's sample, one per
    unified decode row, and what the fused calls kept."""
    a = _ahead(eng)
    assert sum(len(v) for v in got.values()) == (
        len(got) + a["device"] + a["host"] + eng.stats.decode_tokens_fused)
    assert (a["device"] + a["host"]
            == eng.stats.total_decode_tokens - eng.stats.decode_tokens_fused)


# ------------------------------------------------------------ ends of a row

@pytest.mark.parametrize("n_out", [1, 2, 3, 7])
def test_an_end_by_max_tokens_is_never_planned_ahead(n_out):
    """The host knows which token is a row's last before it reads it: no
    step carries a row past its end, so nothing is discarded and the unified
    program runs exactly the oracle's rows."""
    sp = SamplingParams(max_tokens=n_out, **GREEDY)
    eng, oracle_eng = _engine(), _engine()
    got = drive(eng, arrivals=_arrivals(sp))
    assert got == drive(oracle_eng, oracle=True, arrivals=_arrivals(sp))
    assert all(len(v) == n_out for v in got.values())
    a = _ahead(eng)
    assert a["discarded"] == 0 and a["kept"] == a["device"]
    _assert_no_row_wasted(eng, got)


def test_an_end_by_max_model_len_is_never_planned_ahead():
    kw = dict(max_model_len=48)
    sp = SamplingParams(max_tokens=64, **GREEDY)
    prompts = [list(range(3, 40)), list(range(50, 75)), list(range(80, 112))]
    eng, oracle_eng = _engine(**kw), _engine(**kw)
    got = drive(eng, arrivals=_arrivals(sp, prompts=prompts))
    assert got == drive(oracle_eng, oracle=True,
                        arrivals=_arrivals(sp, prompts=prompts))
    for i, p in enumerate(prompts):
        assert len(p) + len(got[f"r{i}"]) == 48
    a = _ahead(eng)
    assert a["device"] > 0 and a["discarded"] == 0
    _assert_no_row_wasted(eng, got)


@pytest.mark.parametrize("stop_at", [1, 3, 6])
def test_a_stop_token_read_a_step_late_discards_exactly_one_row(stop_at):
    """``r0`` stops on the token at index ``stop_at`` of its greedy output
    (its first such occurrence). A 230-token prompt beside it keeps every
    step of r0's life a mixed unified step, so the step after the one that
    sampled the stop token already carries r0's next row: that row is
    dropped at apply, its token never delivered."""
    free = SamplingParams(max_tokens=10, **GREEDY)
    long_prompt = [7 + (i * 13) % 250 for i in range(230)]

    def arrivals(r0_sampling):
        return {0: [("r0", PROMPTS[0], r0_sampling),
                    ("long", long_prompt, free)]}

    full = drive(_engine(), arrivals=arrivals(free))
    stop_tok = full["r0"][stop_at]
    cut = full["r0"].index(stop_tok) + 1
    stopping = SamplingParams(max_tokens=10, temperature=0.0,
                              stop_token_ids=[stop_tok])
    eng = _engine()
    got = drive(eng, arrivals=arrivals(stopping))
    assert got["r0"] == full["r0"][:cut]  # nothing after the stop
    assert got["long"] == full["long"]
    assert got == drive(_engine(), oracle=True, arrivals=arrivals(stopping))
    a = _ahead(eng)
    assert a["discarded"] == 1 and a["kept"] == a["device"] - 1
    assert a["device"] >= cut  # r0's rows after its first token, the lost one
    assert not eng.seqs and sum(al.num_active for al in eng.allocs) == 0


def test_finish_reason_and_one_finished_output_per_request():
    sp = SamplingParams(max_tokens=5, **GREEDY)
    eng = _engine()
    for i, p in enumerate(PROMPTS[:3]):
        eng.add_request(f"r{i}", p, sp)
    finished: dict[str, list] = {}
    while eng.has_work():
        for out in eng.step():
            if out.finished:
                finished.setdefault(out.request_id, []).append(out.finish_reason)
    assert finished == {f"r{i}": ["length"] for i in range(3)}


# ------------------------------------------- leaving with a token in flight

def test_abort_with_a_token_in_flight_and_a_row_riding_ahead():
    sp = SamplingParams(max_tokens=10, **GREEDY)
    full = drive(_engine(), arrivals=_arrivals(sp))
    eng = _engine()
    got: dict[str, list[int]] = {}
    arr = _arrivals(sp)
    aborted = False
    steps = 0
    while eng.has_work() or steps <= max(arr):
        for rid, p, s in arr.get(steps, ()):
            eng.add_request(rid, p, s)
        for out in eng.step():
            got.setdefault(out.request_id, []).extend(out.new_token_ids)
        steps += 1
        rows = eng._pending_sample["rows"] if eng._pending_sample else []
        if not aborted and any(s.request_id == "r0" and ahead
                               for _, s, _, ahead in rows):
            eng.abort("r0")  # its row of the step in flight rode ahead
            aborted = True
            n_at_abort = len(got.get("r0", []))
    assert aborted and len(got["r0"]) == n_at_abort < 10
    assert got["r0"] == full["r0"][:n_at_abort]
    for rid in full:
        if rid != "r0":
            assert got[rid] == full[rid], rid
    assert _ahead(eng)["discarded"] == 1
    assert eng.programs.quiesced() and not eng.seqs


def test_abort_of_every_row_leaves_the_step_in_flight_as_work():
    eng = _engine()
    eng.add_request("a", PROMPTS[3], SamplingParams(max_tokens=4, **GREEDY))
    eng.step()
    assert eng._pending_sample is not None
    eng.abort("a")
    assert eng.has_work()  # the ledger still holds the step
    assert eng.step() == []
    assert not eng.has_work() and eng.programs.quiesced()


@pytest.mark.parametrize("num_pages", [14, 18])
def test_preemption_in_a_tight_pool_reads_the_step_in_flight_first(num_pages):
    """Too few pages for all rows: the plan preempts, and a preemption reads
    the step in flight before it picks its victim (no token is dropped, the
    victim recomputes what it had). Tokens equal the roomy pool's."""
    sp = SamplingParams(max_tokens=24, **GREEDY)
    prompts = [list(range(3, 35)), list(range(50, 75)), list(range(80, 110))]
    kw = dict(max_batch_size=3, prefill_chunk=16)
    roomy = drive(_engine(**kw), arrivals=_arrivals(sp, prompts=prompts))
    eng = _engine(num_pages=num_pages, **kw)
    got = drive(eng, arrivals=_arrivals(sp, prompts=prompts))
    assert eng.stats.total_preemptions > 0
    assert got == roomy
    oracle = drive(_engine(num_pages=num_pages, **kw), oracle=True,
                   arrivals=_arrivals(sp, prompts=prompts))
    assert got == oracle
    assert sum(a.num_active for a in eng.allocs) == 0  # no page leaked


# ------------------------------------------------------- what stays in step

@pytest.mark.parametrize("constraint", ["logit_bias", "grammar"])
def test_a_constrained_row_makes_the_batch_read_before_it_plans(constraint):
    """A bias is built from the row's previous token on the host, so a batch
    that holds such a row never rides ahead: every decode row's token is
    packed from the host, and the outputs are the oracle's."""
    from tests.test_structured import CHOICES, TOK

    free = SamplingParams(max_tokens=8, **GREEDY)
    if constraint == "logit_bias":
        cons = SamplingParams(max_tokens=8, logit_bias={7: 5.0, 9: -100.0},
                              **GREEDY)
    else:
        cons = SamplingParams(max_tokens=8, temperature=0.0,
                              guided_choice=CHOICES)

    def engine():
        return LLMEngine(get_model_config("tiny"), EngineConfig(
            **{**BASE, "structured_table_max_elems": 1}), tokenizer=TOK)

    def run(oracle):
        eng = engine()
        arr = {0: [("c", TOK.encode("pick"), cons)],
               1: [("f0", PROMPTS[1], free)], 3: [("f1", PROMPTS[0], free)]}
        return eng, drive(eng, oracle=oracle, arrivals=arr)

    eng, got = run(False)
    assert got == run(True)[1]
    a = _ahead(eng)
    # "c" runs (unified degrade) until it ends; the free rows ride ahead only
    # once it has gone
    assert a["host"] > 0 and a["discarded"] == 0
    assert eng.stats.structured_violations == 0
    while_c = engine()
    while_c.add_request("c", TOK.encode("pick"), SamplingParams(
        max_tokens=64, logit_bias={7: 5.0}, **GREEDY))
    while_c.add_request("f", PROMPTS[1], free)
    for _ in range(6):
        while_c.step()
    assert _ahead(while_c)["device"] == 0 and _ahead(while_c)["host"] > 0


@pytest.mark.parametrize("spec_mode", ["off", "ngram"])
def test_decode_and_verify_programs_read_the_step_in_flight_first(spec_mode):
    """The fused decode call and the verify step build their batch from host
    tokens: the last unified step's first tokens land before they run."""
    sp = SamplingParams(max_tokens=10, **GREEDY)
    prompts = [[5, 6, 7, 8] * 6, list(range(50, 75))]
    eng = _engine(spec_mode=spec_mode)
    got = eng.generate(prompts, sp)  # asserts the quiesce invariants
    assert got == _engine().generate(prompts, sp)
    assert eng.stats.n_decode_calls + eng.stats.n_spec_verify_steps > 0


# ---------------------------------------------------------------- sampling

@pytest.mark.parametrize("seed", [0, 7])
def test_sampled_runs_are_self_deterministic_and_complete(seed):
    sp = SamplingParams(max_tokens=9, temperature=0.8, top_k=30, top_p=0.95,
                        ignore_eos=True)

    def run():
        eng = LLMEngine(get_model_config("tiny"), EngineConfig(**BASE),
                        seed=seed)
        return eng, drive(eng, arrivals=_arrivals(sp))

    (eng, a), (_, b) = run(), run()
    assert a == b and all(len(v) == 9 for v in a.values())
    assert _ahead(eng)["device"] > 0
    assert len({tuple(v) for v in a.values()}) > 1


# ------------------------------------------------- one program, and the books

def test_one_compiled_unified_program_with_and_without_a_step_in_flight():
    eng = _engine()
    sp = SamplingParams(max_tokens=40, **GREEDY)
    # r1 arrives while r0 is in fused decode calls: the step that takes its
    # first chunk packs r0's token from the host, the next ones ride ahead
    drive(eng, arrivals={0: [("r0", PROMPTS[0], sp)],
                         6: [("r1", PROMPTS[2], sp)]})
    a = _ahead(eng)
    assert a["device"] > 0 and a["host"] > 0  # both kinds of step ran
    assert eng.programs.compile_counts()["unified"] == 1


def test_generate_quiesces_and_counts_balance():
    eng = _engine()
    sp = SamplingParams(max_tokens=12, **GREEDY)
    out = eng.generate(PROMPTS, sp)  # asserts quiesced() itself
    assert all(len(v) == 12 for v in out.values())
    c = eng.programs.counters()
    assert c["unified"][0] == c["unified"][1] == eng.stats.n_unified_steps
    # the unified program picks the step's tokens itself (ISSUE 31): an
    # unconstrained run never dispatches the sampler as a program of its own
    assert "sample" not in c
    assert _count(eng, "engine_program_dispatches_total",
                  'program="sample"') == 0
    assert _count(eng, "sampler_steps_total", 'program="unified",path="argmax"'
                  ) == eng.stats.n_unified_steps
    a = _ahead(eng)
    assert a["kept"] + a["discarded"] == a["device"]
    # a decode token of a unified step is one of its decode rows
    assert (a["device"] + a["host"]
            == eng.stats.total_decode_tokens - eng.stats.decode_tokens_fused)


def test_parts_sum_to_the_step_histogram_one_step_ahead():
    """The unified step's parts still cover its duration, with the wait now
    the read of the previous step: every part of ``STEP_PARTS`` (the seven
    this test named before ISSUE 36 left out ``stage`` and ``transfer``, 4%
    of a drive whose first dispatch compiles and 13% of one that does not),
    read as growth over a second drive, in which nothing compiles."""
    eng = _engine()
    sp = SamplingParams(max_tokens=16, **GREEDY)

    def read() -> dict:
        got = {p: _count(eng, "engine_step_part_seconds_total",
                         f'program="unified",part="{p}"')
               for p in STEP_PARTS}
        got["hist"] = _count(eng, "engine_step_duration_seconds_sum",
                             'phase="unified"')
        got["ahead"] = _ahead(eng)["device"]
        return got

    drive(eng, arrivals=_arrivals(sp))
    before = read()
    drive(eng, arrivals=_arrivals(sp, prompts=[
        [t + 100 for t in p] for p in PROMPTS]))  # no prefix is cached
    parts = {k: v - before[k] for k, v in read().items()}
    hist, ahead = parts.pop("hist"), parts.pop("ahead")
    assert ahead > 0
    assert all(v > 0 for v in parts.values()), parts
    # the histogram's sample is the parts' own sum: equal but for rounding
    assert abs(sum(parts.values()) - hist) <= 1e-6 * hist, (parts, hist)
    n = _count(eng, "engine_step_duration_seconds_count", 'phase="unified"')
    assert n == eng.stats.n_unified_steps


def test_outputs_of_a_step_leave_with_the_next_step():
    """Step n's tokens are returned by step n+1, stamped with its end."""
    eng = _engine()
    sp = SamplingParams(max_tokens=6, **GREEDY)
    eng.add_request("a", PROMPTS[3], sp)   # 10 tokens: one chunk
    eng.add_request("b", PROMPTS[4], sp)   # 70 tokens: keeps prefilling
    assert eng.step() == []                # a's first token is in flight
    seq = eng.seqs["a"]
    assert seq.num_computed == len(seq.token_ids) == 10
    outs = eng.step()                      # a rides ahead; its first token lands
    assert [(o.request_id, len(o.new_token_ids)) for o in outs] == [("a", 1)]
    assert outs[0].t_step > 0
    assert seq.num_computed == len(seq.token_ids) == 11
    assert _ahead(eng)["device"] == 1
