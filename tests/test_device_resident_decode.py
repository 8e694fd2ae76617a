"""PR 12: device-resident decode steady state.

Constrained rows (grammar masks / logit_bias) ride the fused multi-step
decode program with the bias gather, biased sample, and FSM transition done
on device (`_decode_multi_masked`), and chained dispatches reuse the
in-flight call's device-resident tokens/positions/kv-lens instead of a full
host re-pack. The contract: bitwise-identical greedy outputs against the
unified degrade and against the same engine read after every step, 100%
conformance, zero violations, and the dispatch/process stats invariant at
quiesce.
"""

from __future__ import annotations

import re

import conftest  # noqa: F401
import numpy as np
import pytest

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.engine.engine import DECODE_MIN_STEPS
from llmd_tpu.engine.tokenizer import ByteTokenizer
from llmd_tpu.models import get_model_config
from llmd_tpu.structured import GrammarCache, compile_grammar
from tests.test_pipeline_decode import poison_tail
from tests.test_step_tracing import _samples

TOK = ByteTokenizer()
CHOICES = ["red", "green", "blue"]
REGEX = r"[a-c]{3}-[0-9]{2}"


def _engine(**over) -> LLMEngine:
    base = dict(page_size=8, num_pages=128, max_model_len=256,
                max_batch_size=4, prefill_chunk=32, decode_steps=4)
    base.update(over)
    return LLMEngine(get_model_config("tiny"), EngineConfig(**base), seed=3,
                     tokenizer=TOK)


def _drain(eng: LLMEngine, flush: bool = False):
    """Step ``eng`` dry; ``flush`` reads every fused call before the next
    step is planned, so no call is ever chained on another."""
    toks: dict[str, list[int]] = {}
    fins: dict[str, str] = {}
    steps = 0
    while eng.has_work():
        outs = eng.step()
        if flush:
            eng._flush_pending_decode()  # appends to the list step() returned
        for o in outs:
            toks.setdefault(o.request_id, []).extend(o.new_token_ids)
            if o.finish_reason:
                fins[o.request_id] = o.finish_reason
        steps += 1
        assert steps < 2000, "no forward progress (livelock)"
    # quiesce invariant: every launched fused call was processed — a gap
    # means a chained in-flight record was orphaned (engine.py:123-124)
    assert eng.stats.n_decode_dispatches == eng.stats.n_decode_calls
    assert not eng._pending_decode
    return toks, fins


def _sp(**kw) -> SamplingParams:
    base = dict(max_tokens=32, temperature=0.0, stop_token_ids=(TOK.eos_id,))
    base.update(kw)
    return SamplingParams(**base)


def _add_mixed(eng: LLMEngine) -> None:
    """Plain + choice-grammar + regex-grammar + logit_bias rows, all greedy."""
    z = TOK.encode("z")[0]
    eng.add_request("plain", TOK.encode("the quick brown fox"),
                    _sp(max_tokens=16, stop_token_ids=(), ignore_eos=True))
    eng.add_request("choice", TOK.encode("pick a color"),
                    _sp(guided_choice=CHOICES))
    eng.add_request("regex", TOK.encode("emit a code"),
                    _sp(guided_regex=REGEX))
    eng.add_request("bias", TOK.encode("say"),
                    _sp(max_tokens=8, logit_bias={z: 100}, stop_token_ids=()))


def test_fused_masked_decode_bitwise_matches_unified_degrade():
    """Mixed plain/structured/bias batch: the device-resident masked path and
    the 1-token unified degrade (forced through the table-size gate) must
    produce identical greedy tokens."""
    outs = []
    for fused in (True, False):
        eng = _engine() if fused else _engine(structured_table_max_elems=1)
        _add_mixed(eng)
        toks, fins = _drain(eng)
        outs.append(toks)
        assert eng.stats.structured_violations == 0
        if fused:
            assert eng.stats.structured_chain_stages > 0, (
                "constrained rows never took the fused masked program")
        else:
            assert eng.stats.structured_chain_stages == 0
        assert fins["choice"] == "stop" and fins["regex"] == "stop"
    assert outs[0] == outs[1], "fused masked decode diverged from host path"
    assert TOK.decode(outs[0]["choice"]) in CHOICES
    assert re.fullmatch(REGEX, TOK.decode(outs[0]["regex"]))
    assert TOK.decode(outs[0]["bias"]) == "zzzzzzzz"


def test_masked_chain_stays_device_resident_across_dispatches():
    """Long constrained generations: the FSM chains through multiple fused
    dispatches (device fsm_out feeding the next call) without violations."""
    long_choices = ["abcdefghijklmnopqrstuvwx", "zyxwvutsrqponmlkjihgfedc"]
    eng = _engine()
    eng.add_request("c0", TOK.encode("pick one"),
                    _sp(guided_choice=long_choices))
    eng.add_request("c1", TOK.encode("emit bits"),
                    _sp(guided_regex=r"[ab]{24}"))
    toks, fins = _drain(eng)
    st = eng.stats
    assert st.structured_chain_stages > 0
    assert st.n_chained_dispatches > 0, (
        "constrained chain never pipelined past one dispatch")
    assert st.structured_violations == 0
    assert fins["c0"] == "stop" and fins["c1"] == "stop"
    assert TOK.decode(toks["c0"]) in long_choices
    assert re.fullmatch(r"[ab]{24}", TOK.decode(toks["c1"]))


def test_pack_overlap_bitwise_parity_and_accounting():
    """Chained fast-path pack (device-resident pos/lens/tokens reuse) must be
    invisible in the outputs; the overlap keeps meaning hidden wall: an engine
    read after every step never chains and overlaps nothing, and its packs
    are on the part counter all the same."""
    outs = []
    for flush in (False, True):
        eng = _engine()
        for i, p in enumerate(("alpha beta", "gamma delta", "epsilon zeta")):
            eng.add_request(f"req-{i}", TOK.encode(p),
                            _sp(max_tokens=48, stop_token_ids=(),
                                ignore_eos=True))
        toks, _ = _drain(eng, flush=flush)
        outs.append(toks)
        st = eng.stats
        if flush:
            assert st.n_chained_dispatches == 0 and st.time_pack_overlap == 0
            assert sum(v for name, labels, v in eng.registry.collect()
                       if name == "llmd_tpu:engine_step_part_seconds_total"
                       and 'program="decode"' in labels
                       and ('part="plan"' in labels
                            or 'part="pack"' in labels)) > 0
        else:
            assert st.n_chained_dispatches > 0, (
                "membership-stable batch never chained")
            assert st.time_pack_overlap > 0, "no pack wall was overlapped"
    assert outs[0] == outs[1], "chaining perturbed the token streams"


def test_combined_grammar_and_bias_row_degrades_to_unified():
    """A row carrying BOTH a grammar and a logit_bias can't share one table
    slot: the whole batch takes the legacy unified degrade, still conformant."""
    z = TOK.encode("z")[0]
    eng = _engine()
    eng.add_request("both", TOK.encode("pick"),
                    _sp(guided_choice=CHOICES, logit_bias={z: -1.0}))
    toks, fins = _drain(eng)
    assert eng.stats.structured_chain_stages == 0
    assert eng.stats.structured_violations == 0
    assert fins["both"] == "stop"
    assert TOK.decode(toks["both"]) in CHOICES


def test_table_size_gate_degrades_to_unified():
    """Tables past structured_table_max_elems never stage; the unified path
    serves the batch instead of uploading an oversized [G,S,V] pair."""
    eng = _engine(structured_table_max_elems=16)
    eng.add_request("c", TOK.encode("pick"), _sp(guided_choice=CHOICES))
    toks, fins = _drain(eng)
    assert eng.stats.structured_chain_stages == 0
    assert fins["c"] == "stop"
    assert TOK.decode(toks["c"]) in CHOICES


def test_preemption_mid_chain_rolls_back_conformant():
    """Tight pool forces preempt/requeue mid-chain: stale in-flight records
    are discarded, the FSM cursor re-derives from token history after
    re-prefill, and every constrained generation still conforms."""
    p_choices = ["abcdefghijklmnopqrstuvwx", "zyxwvutsrqponmlkjihgfedc"]
    eng = _engine(num_pages=10, max_batch_size=2, enable_prefix_caching=False)
    eng.add_request("choice-p", TOK.encode("x" * 28), _sp(guided_choice=p_choices))
    eng.add_request("regex-p", TOK.encode("y" * 30), _sp(guided_regex=r"[ab]{24}"))
    toks, fins = _drain(eng)
    assert eng.stats.total_preemptions > 0, "pool never got tight"
    assert eng.stats.structured_violations == 0
    assert fins["choice-p"] == "stop" and fins["regex-p"] == "stop"
    assert TOK.decode(toks["choice-p"]) in p_choices
    assert re.fullmatch(r"[ab]{24}", TOK.decode(toks["regex-p"]))


def test_dense_tables_match_host_automaton():
    """structured/grammar.py dense_tables: bias rows exactly as fill_bias
    writes them; transitions exactly as advance() walks them, with violations
    freezing (self-loop) on the same state the host freeze lands on."""
    g, _ = compile_grammar("choice", CHOICES, TOK, TOK.vocab_size,
                           cache=GrammarCache(capacity=1))
    bias, nxt = g.dense_tables()
    assert bias.shape == (g.n_states, g.vocab_size)
    assert nxt.shape == (g.n_states, g.vocab_size)
    rng = np.random.default_rng(0)
    for s in range(g.n_states):
        row = np.empty((g.vocab_size,), np.float32)
        g.fill_bias(row, s)
        assert np.array_equal(bias[s], row), f"bias row mismatch at state {s}"
        for tid in g.allowed_ids(s):
            adv = g.advance(s, int(tid))
            # vocab-gap states force EOS through a token advance() may refuse;
            # the device then freezes, matching the host freeze
            want = s if adv is None else adv
            assert nxt[s, tid] == want, (s, tid)
        for tid in rng.integers(0, g.vocab_size, size=48):
            adv = g.advance(s, int(tid))
            assert nxt[s, tid] == (s if adv is None else adv), (s, int(tid))
    assert g.dense_tables() is g.dense_tables()  # cached on the grammar


# ---------------------------------------------------- ISSUE 38: a call's length
CAP = 2 * DECODE_MIN_STEPS  # decode_steps over the floor: the rule can act


@pytest.mark.parametrize("poison", [False, True])
def test_masked_calls_of_unequal_lengths_match_unified_degrade(poison):
    """The masked fused program under calls whose lengths the host works out
    (budgets 8 and 16 among rows that stop on the grammar's EOS, which the
    host cannot foresee): tokens as the 1-token unified degrade gives them,
    FSM state carried across calls of unequal lengths, and with ``poison``
    every entry of the token buffer that is no token of its row (past the
    call's length, past the row's steps) overwritten: none may be read."""
    outs = []
    for fused in (True, False):
        eng = (_engine(decode_steps=CAP) if fused
               else _engine(decode_steps=CAP, structured_table_max_elems=1))
        if fused and poison:
            eng._decode_multi_masked_fn = poison_tail(
                eng._decode_multi_masked_fn, TOK.encode("~")[0])
        _add_mixed(eng)
        eng.add_request("long", TOK.encode("emit bits"),
                        _sp(max_tokens=2 * CAP + 3,
                            guided_regex=r"[ab]{%d}" % (2 * CAP + 2)))
        toks, fins = _drain(eng)
        outs.append(toks)
        assert eng.stats.structured_violations == 0
        assert fins["choice"] == "stop" and fins["regex"] == "stop"
        assert fins["long"] == "stop"
        if fused:
            assert eng.stats.structured_chain_stages > 0
            bounds = _samples(eng.registry,
                              "llmd_tpu:decode_call_steps_total")
            assert len(bounds) > 1, bounds
    assert outs[0] == outs[1], "fused masked decode diverged from host path"
    assert TOK.decode(outs[0]["bias"]) == "zzzzzzzz"
    assert len(outs[0]["long"]) == 2 * CAP + 3  # the grammar's EOS the last
    assert re.fullmatch(r"[ab]{%d}" % (2 * CAP + 2),
                        TOK.decode(outs[0]["long"]))
