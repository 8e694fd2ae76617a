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
from llmd_tpu.engine.engine import DECODE_MIN_STEPS as M, decode_call_steps
from llmd_tpu.models import get_model_config
from tests.test_pipeline_prefill_sample import drive, generate
from tests.test_step_tracing import _samples, _total


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


# ------------------------------------------------- the length of a fused call
# A call runs n <= decode_steps steps, n worked out by the host from its rows'
# budgets and the steps in flight (engine.decode_call_steps). K is a cap well
# over DECODE_MIN_STEPS, so that all three bounds can set n.
K = 16


@pytest.mark.parametrize("left,cap,want", [
    # no row ends within the cap: the call is the cap long
    ([40, 99, 17], K, (K, "cap")),
    ([K, 99], K, (K, "cap")),
    # the first ending sets it
    ([K - 1, 99], K, (K - 1, "ending")),
    ([99, M + 3, 40], K, (M + 3, "ending")),
    ([M, 99], K, (M, "ending")),
    # an ending nearer than the floor: the floor
    ([M - 1, 99], K, (M, "floor")),
    ([1, 99, 3], K, (M, "floor")),
    # rows the steps in flight already end take no part
    ([0, -5, M + 2, 99], K, (M + 2, "ending")),
    ([0, 2, 99], K, (M, "floor")),
    ([-3, 99], K, (K, "cap")),
    # no row has a step past the largest budget: the call ends there
    ([2, M - 1], K, (M - 1, "ending")),
    ([M - 1], K, (M - 1, "ending")),
    ([0, M, -1], K, (M, "ending")),
    # a cap at or under the floor is the length whenever a row outlasts it
    ([1, 99], 4, (4, "cap")),
    ([1, 99], M, (M, "cap")),
    ([1, 3], 4, (3, "ending")),
    ([99], 1, (1, "cap")),
])
def test_call_length_rule(left, cap, want):
    """n = clamp(r_min, DECODE_MIN_STEPS, cap) over the rows that still have
    a step, never past the last row's budget; the label says what set it."""
    assert decode_call_steps(left, cap) == want


def _staggered(sync: bool, case: str):
    """Six rows over the budgets that sit on every edge of the rule, two
    arrivals so that chains start, hold and break; ``sync`` is the same
    engine read after every step."""
    from tests.test_structured import TOK

    greedy = dict(temperature=0.0, ignore_eos=True)
    budgets = [1, M - 1, M, K - 1, K, 3 * K + 1]
    kw = dict(max_batch_size=6, decode_steps=K, num_pages=256)
    model, seed = "tiny", 0
    prompts = [PROMPTS[i % 4][: 20 + 3 * i] for i in range(6)]
    sps = [SamplingParams(max_tokens=n, **greedy) for n in budgets]
    arrivals = (0, 0, 0, 2, 2, 2)
    if case == "model_len":
        # r5 is cut by the model's length, 9 tokens short of its budget
        kw["max_model_len"] = len(prompts[5]) + 3 * K + 1 - 9
    elif case == "recurrent":
        model, seed = "tiny-jamba", 3
        kw.update(page_size=4, max_model_len=96)
    elif case == "masked":
        prompts[:2] = [TOK.encode("emit bits"), TOK.encode("say")]
        sps[0] = SamplingParams(max_tokens=3 * K, temperature=0.0,
                                guided_regex=r"[ab]{%d}" % (3 * K - 2),
                                stop_token_ids=(TOK.eos_id,))
        sps[1] = SamplingParams(max_tokens=M + 3,
                                logit_bias={7: 5.0, 9: -100.0}, **greedy)
    elif case == "prefill_mid_chain":
        arrivals = (0, 0, 0, 0, 0, 5)  # r5 arrives while a chain is running
        budgets[5] = M + 2
        sps[5] = SamplingParams(max_tokens=M + 2, **greedy)
    eng = LLMEngine(get_model_config(model), _cfg(**kw), seed=seed,
                    tokenizer=TOK)
    rows: dict[int, list] = {}
    for i, at in enumerate(arrivals):
        rows.setdefault(at, []).append((f"r{i}", prompts[i], sps[i]))
    return drive(eng, oracle=sync, unchained=sync, arrivals=rows), eng, budgets


@pytest.mark.parametrize("case", ["budgets", "stop", "model_len", "masked",
                                  "recurrent", "prefill_mid_chain"])
def test_call_length_keeps_the_streams(case):
    """Calls of unequal lengths give the tokens the unchained engine gives:
    across the budgets on every edge of the rule, a stop token the host
    cannot foresee, the model-length cap, a masked (grammar) batch, a model
    with recurrent layers (a spent row must not step its state) and a prefill
    that arrives mid-chain."""
    if case == "stop":
        # seed 4 keeps the first greedy tokens distinct (see above); the stop
        # token is r0's token M + 2: past the first call of a chain
        sp = SamplingParams(max_tokens=3 * K, temperature=0.0, ignore_eos=True)
        kw = dict(decode_steps=K, num_pages=256)
        probe, _ = _run(PROMPTS[:2], sp, False, seed=4, **kw)
        stop_tok = probe["req-0"][M + 1]
        at = probe["req-0"].index(stop_tok)
        sp = SamplingParams(max_tokens=3 * K, temperature=0.0,
                            stop_token_ids=(stop_tok,))
        got, eng = _run(PROMPTS[:2], sp, True, seed=4, **kw)
        ref, _ = _run(PROMPTS[:2], sp, False, seed=4, **kw)
        assert got == ref
        assert got["req-0"][-1] == stop_tok and len(got["req-0"]) == at + 1
        return
    got, eng, budgets = _staggered(False, case)
    ref, ref_eng, _ = _staggered(True, case)
    assert got == ref and len(got) == 6
    assert eng.stats.n_chained_dispatches > 0
    assert ref_eng.stats.n_chained_dispatches == 0
    steps = _samples(eng.registry, "llmd_tpu:decode_call_steps_total")
    # calls shorter than the cap ran
    assert sum(steps.values()) < K * eng.stats.n_decode_dispatches, steps
    lens = [len(got[f"r{i}"]) for i in range(6)]
    if case == "model_len":
        assert lens == budgets[:5] + [budgets[5] - 9]
    elif case == "masked":
        assert eng.stats.structured_violations == 0
        assert re.fullmatch(r"[ab]{%d}" % (3 * K - 2), eng.tokenizer.decode(
            got["r0"][:-1] if got["r0"][-1] == eng.tokenizer.eos_id
            else got["r0"]))
        assert got["r1"] == [7] * len(got["r1"]) and lens[2:] == budgets[2:]
    else:
        assert lens == budgets


POISON = 191  # a token id of the tiny vocabulary that no greedy stream holds


def poison_tail(fn, token: int):
    """A fused program whose token buffer holds ``token`` wherever it holds
    no token of its row: at and past the call's length, and in a row's column
    past the steps the row was given (``steps_left``, argument 10)."""
    import jax.numpy as jnp

    def poisoned(*args):
        toks_out, *rest = fn(*args)
        ran = jnp.arange(toks_out.shape[0])[:, None] < args[10][None, :]
        return (jnp.where(ran, toks_out, token), *rest)

    return poisoned


@pytest.mark.parametrize("case", ["budgets", "prefill_mid_chain"])
def test_no_token_past_a_rows_steps_reaches_the_sequence(case, monkeypatch):
    """The buffer's rows at and past a call's length, and a row's column past
    the steps it was given, are not tokens: poisoned here, none may reach
    ``token_ids``, and the streams stay the unchained engine's."""
    ref, _, budgets = _staggered(True, case)
    assert not any(POISON in v for v in ref.values())
    init = LLMEngine.__init__

    def poisoned_init(self, *a, **kw):
        init(self, *a, **kw)
        self._decode_multi_fn = poison_tail(self._decode_multi_fn, POISON)

    monkeypatch.setattr(LLMEngine, "__init__", poisoned_init)
    got, eng, _ = _staggered(False, case)
    assert eng.stats.n_chained_dispatches > 0
    assert got == ref
    assert not any(POISON in s.token_ids[s.prompt_len:]
                   for s in eng.seqs.values())


def test_counters_add_up_over_calls_of_unequal_lengths():
    """``off`` (the steps in flight), the seat ledger, the sampler's steps and
    the call-length counter all count the n steps a call was given: over a
    chain of calls of unequal lengths they agree with each other and with
    the tokens delivered."""
    got, eng, budgets = _staggered(False, "budgets")
    seats = _samples(eng.registry, "llmd_tpu:decode_seat_steps_total")
    steps = sum(_samples(eng.registry,
                         "llmd_tpu:decode_call_steps_total").values())
    sampler = _total(eng.registry, "llmd_tpu:sampler_steps_total",
                     'program="decode"')
    calls = eng.stats.n_decode_dispatches
    assert calls == eng.stats.n_decode_calls > 3
    # calls of unequal lengths ran: fewer steps than calls x the cap
    assert calls < steps < calls * K
    assert steps == sampler
    assert sum(seats.values()) == steps * eng.cfg.max_batch_size
    assert seats['{outcome="kept"}'] == eng.stats.decode_tokens_fused
    # every token came from a unified step (a row's first) or a fused call
    assert sum(budgets) == sum(len(v) for v in got.values())
    assert eng.stats.decode_tokens_fused <= sum(budgets) - len(budgets)


def test_off_counts_the_steps_given(monkeypatch):
    """A chained call is packed from the host's view plus the steps in
    flight: with calls of unequal lengths in flight, ``off`` must be the sum
    of their lengths, or a row's position would slip."""
    seen = []
    dispatch = LLMEngine._decode_dispatch

    def spy(self, active, k, bound, chain, parts, off=0):
        seen.append((k, bound, off, [r["k"] for r in self._pending_decode]))
        return dispatch(self, active, k, bound, chain, parts, off)

    monkeypatch.setattr(LLMEngine, "_decode_dispatch", spy)
    _staggered(False, "budgets")
    assert all(off == sum(ks) for _, _, off, ks in seen)
    assert len({k for k, *_ in seen}) > 1, seen  # unequal lengths
    assert any(off and k != ks[-1] for k, _, off, ks in seen), seen


def test_one_program_whatever_the_length():
    """The length is a value, not a shape: calls of different lengths hit
    one compiled executable of the fused program."""
    eng = LLMEngine(get_model_config("tiny"),
                    _cfg(decode_steps=K, num_pages=256))
    sp = [SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)
          for n in (K + 3, 3 * K)]
    for i, s in enumerate(sp):
        eng.add_request(f"r{i}", PROMPTS[i], s)
    while eng.has_work():
        eng.step()
    steps = _samples(eng.registry, "llmd_tpu:decode_call_steps_total")
    assert len(steps) == 3, steps  # ending, floor and cap each set a call
    assert eng.stats.n_decode_dispatches > 3
    assert eng.programs.compile_counts()["decode"] == 1
    compiles = _samples(eng.registry, "llmd_tpu:program_compiles_total")
    assert compiles['{program="decode"}'] == 1, compiles
