"""Pipelined (async-output) decode correctness.

The engine hides the device→host readback by dispatching decode call N+1 chained
on call N's device-resident sampled tokens and reading N's results one call
behind (engine.py _step_decode). These tests pin the invariant: pipelining is an
overlap optimisation, never a semantic change — greedy outputs are identical
to the same engine read after every step (``drive(unchained=True)``: no call
is ever chained on another), across finish causes (max_tokens, stop tokens,
model-len cap), staggered finish times, and mixed prefill/decode interleaving.
"""

from __future__ import annotations

import re

import conftest  # noqa: F401

import pytest

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.models import get_model_config
from tests.test_pipeline_prefill_sample import drive, generate


def _cfg(**kw) -> EngineConfig:
    base = dict(page_size=8, num_pages=128, max_model_len=256, max_batch_size=4,
                prefill_chunk=16, decode_steps=4)
    base.update(kw)
    return EngineConfig(**base)


def _run(prompts, sampling, chained: bool, seed: int = 0, **kw):
    """``chained`` False is the unpipelined reading: every fused call is read
    before the next step is planned."""
    eng = LLMEngine(get_model_config("tiny"), _cfg(**kw), seed=seed)
    if chained:
        return eng.generate(prompts, sampling), eng
    out = generate(eng, prompts, sampling, unchained=True)
    assert eng.stats.n_chained_dispatches == 0
    return out, eng


PROMPTS = [list(range(3, 40)), list(range(50, 75)), list(range(80, 140)),
           list(range(150, 160))]


def test_greedy_identical_with_and_without_pipeline():
    sp = SamplingParams(max_tokens=19, temperature=0.0, ignore_eos=True)
    out_on, eng_on = _run(PROMPTS, sp, True)
    out_off, _ = _run(PROMPTS, sp, False)
    assert out_on == out_off
    assert all(len(v) == 19 for v in out_on.values())
    # the pipeline actually engaged (a call was chained on one in flight)
    assert eng_on.stats.n_chained_dispatches >= 2


def test_staggered_max_tokens():
    """Rows finish at different calls; device-side steps_left freezes each row
    exactly at its budget — no overrun tokens are ever delivered."""
    eng = LLMEngine(get_model_config("tiny"), _cfg())
    for i, (p, mt) in enumerate(zip(PROMPTS, [3, 9, 14, 6])):
        eng.add_request(f"r{i}", p, SamplingParams(max_tokens=mt, temperature=0.0,
                                                   ignore_eos=True))
    done = {f"r{i}": [] for i in range(4)}
    while eng.has_work():
        for out in eng.step():
            done[out.request_id].extend(out.new_token_ids)
    assert [len(done[f"r{i}"]) for i in range(4)] == [3, 9, 14, 6]


def test_stop_token_truncation_matches_unpipelined():
    """Stop tokens are only detectable host-side (one call late under the
    pipeline); truncation must still deliver identical streams."""
    # seed 0's tiny-model greedy stream cycles with period 2 here, so the
    # probed token at position 5 already occurs earlier and the stream stops
    # before position 5 — breaking the premise; seed 4 keeps the first six
    # greedy tokens distinct
    probe, _ = _run(PROMPTS[:2], SamplingParams(max_tokens=24, temperature=0.0,
                                                ignore_eos=True), False, seed=4)
    stop_tok = probe["req-0"][5]
    sp = SamplingParams(max_tokens=24, temperature=0.0, stop_token_ids=(stop_tok,))
    out_on, _ = _run(PROMPTS[:2], sp, True, seed=4)
    out_off, _ = _run(PROMPTS[:2], sp, False, seed=4)
    assert out_on == out_off
    assert out_on["req-0"][-1] == stop_tok and len(out_on["req-0"]) == 6


def test_model_len_cap_respected():
    sp = SamplingParams(max_tokens=10_000, temperature=0.0, ignore_eos=True)
    out, eng = _run([list(range(3, 40))], sp, True,
                    max_model_len=64, num_pages=32)
    assert len(out["req-0"]) == 64 - 37
    assert not eng.has_work()


def test_mid_stream_arrival_flushes_chain():
    """A new request arriving mid-decode forces a unified (prefill) step; the
    pending call must be applied first and no tokens lost."""
    sp = SamplingParams(max_tokens=16, temperature=0.0, ignore_eos=True)

    def run(unchained: bool):
        eng = LLMEngine(get_model_config("tiny"), _cfg())
        return drive(eng, unchained=unchained, arrivals={
            0: [("a", PROMPTS[0], sp)], 3: [("b", PROMPTS[1], sp)]})

    done = run(False)
    assert len(done["a"]) == 16 and len(done["b"]) == 16
    assert run(True) == done


def test_abort_mid_pipeline():
    eng = LLMEngine(get_model_config("tiny"), _cfg())
    sp = SamplingParams(max_tokens=32, temperature=0.0, ignore_eos=True)
    eng.add_request("a", PROMPTS[0], sp)
    eng.add_request("b", PROMPTS[1], sp)
    got_b = []
    for _ in range(4):
        for out in eng.step():
            if out.request_id == "b":
                got_b.extend(out.new_token_ids)
    eng.abort("a")
    while eng.has_work():
        for out in eng.step():
            assert out.request_id == "b"
            got_b.extend(out.new_token_ids)
    assert len(got_b) == 32
    assert "a" not in eng.seqs
    # all of a's pages returned
    assert eng.alloc.num_free == eng.cfg.num_pages


def test_no_orphaned_inflight_calls_on_membership_change():
    """Regression: a membership-change flush must not strand the freshly
    dispatched call in the drained queue (every launch gets processed)."""
    eng = LLMEngine(get_model_config("tiny"),
                    _cfg(num_pages=64, prefill_chunk=32))
    # staggered lengths force repeated membership changes as sequences retire
    for i, mt in enumerate((6, 14, 26)):
        eng.add_request(f"r{i}", PROMPTS[i % len(PROMPTS)],
                        SamplingParams(max_tokens=mt, temperature=0.0,
                                       ignore_eos=True))
    got = {f"r{i}": 0 for i in range(3)}
    while eng.has_work():
        for out in eng.step():
            got[out.request_id] += len(out.new_token_ids)
    assert got == {"r0": 6, "r1": 14, "r2": 26}
    assert eng.stats.n_decode_dispatches == eng.stats.n_decode_calls
    assert not eng._pending_decode


def test_no_dispatch_past_hard_budget():
    """The host must not speculatively dispatch a fused call whose every step
    is provably past all rows' max_tokens/max_model_len budget.

    A UNIFORM wave (equal prompt lengths, one prefill batch, one shared
    max_tokens) is the case that exposes it: membership never changes, so
    before the horizon clamp the chain kept dispatching DECODE_CHAIN_DEPTH
    extra fully-masked calls past the budget — measured 6 dispatches where 4
    carry all the tokens.
    Outputs must be unchanged vs the unpipelined engine."""
    uniform = [[(7 * i + j) % 200 + 1 for j in range(32)] for i in range(4)]
    sp = SamplingParams(max_tokens=17, temperature=0.0, ignore_eos=True)
    kw = dict(prefill_chunk=64, max_num_batched_tokens=256, num_pages=256)
    out_on, eng_on = _run(uniform, sp, True, **kw)
    out_off, _ = _run(uniform, sp, False, **kw)
    assert out_on == out_off
    assert all(len(v) == 17 for v in out_on.values())
    # prefill yields token 1; 16 more tokens = exactly ceil(16/4) fused calls
    assert eng_on.stats.n_decode_dispatches == 4, eng_on.stats.n_decode_dispatches
    assert eng_on.stats.n_decode_dispatches == eng_on.stats.n_decode_calls


@pytest.mark.parametrize("case", ["base", "seats3", "page4", "stop", "masked"])
def test_chain_equals_flush_every_step(case):
    """Requests of unequal budgets arriving while others decode: chains
    start, grow, and break at every retirement and admission. The reference
    is the same engine read synchronously (unified step and fused call alike)
    after every step. ``stop``: a stop token sampled inside a queued call, so
    the chain runs past it before the host sees it. ``masked``: a grammar row
    and a logit_bias row on the masked chain, whose FSM state stays on the
    device from call to call."""
    from tests.test_structured import TOK

    greedy = dict(temperature=0.0, ignore_eos=True)
    kw = {"seats3": dict(prefill_chunk=8, max_batch_size=3),
          "page4": dict(page_size=4, num_pages=256)}.get(case, {})
    seed = 4 if case == "stop" else 0
    budgets = [19, 11, 26, 7]
    sps = [SamplingParams(max_tokens=n, **greedy) for n in budgets]
    prompts = PROMPTS
    if case == "masked":
        prompts = [TOK.encode("emit bits"), TOK.encode("say"), *PROMPTS[2:]]
        sps[0] = SamplingParams(max_tokens=26, temperature=0.0,
                                guided_regex=r"[ab]{24}",
                                stop_token_ids=(TOK.eos_id,))
        sps[1] = SamplingParams(max_tokens=11, logit_bias={7: 5.0, 9: -100.0},
                                **greedy)

    def run(sync: bool, sps=sps):
        eng = LLMEngine(get_model_config("tiny"), _cfg(**kw), seed=seed,
                        tokenizer=TOK)
        rows = [(f"r{i}", p, sp) for i, (p, sp) in enumerate(zip(prompts, sps))]
        got = drive(eng, oracle=sync, unchained=sync,
                    arrivals={0: rows[:2], 4: rows[2:3], 9: rows[3:]})
        return got, eng

    if case == "stop":
        # r0's sixth token: the second token of its second fused call, which
        # is queued behind the first when the chain holds
        stop_tok = run(True)[0]["r0"][5]
        sps = [SamplingParams(max_tokens=n, temperature=0.0,
                              stop_token_ids=(stop_tok,)) for n in budgets]
    got, eng = run(False, sps)
    ref, ref_eng = run(True, sps)
    assert got == ref and len(got) == 4
    assert eng.stats.n_chained_dispatches > 0
    assert ref_eng.stats.n_chained_dispatches == 0
    assert eng.stats.structured_violations == 0
    if case == "stop":
        assert got["r0"][-1] == stop_tok and len(got["r0"]) == 6
    elif case == "masked":
        assert eng.stats.structured_chain_stages > 0
        assert re.fullmatch(r"[ab]{24}", TOK.decode(got["r0"]))
        assert got["r1"] == [7] * 11
    else:
        assert [len(got[f"r{i}"]) for i in range(4)] == budgets
