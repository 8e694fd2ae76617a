"""Structured × speculative compose (PERF.md Lever 13): constrained rows
draft through the grammar-masked verify program.

The compose inherits both absolute contracts at once: every emitted token is
the model's own (grammar-masked) argmax, so output must be BITWISE identical
to the non-speculative engine — and 100% of constrained generations must
conform. These tests pin that across mixed choice/regex/schema batches,
rejected-tail FSM rollback (device state == host resync, crosschecked),
preemption mid-speculation, the step-program registry's routing/quiesce
contracts, and per-sequence drafter arming."""

from __future__ import annotations

import json

import conftest  # noqa: F401
import pytest

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.engine.tokenizer import ByteTokenizer
from llmd_tpu.models import get_model_config
from llmd_tpu.structured import validate_instance

TOK = ByteTokenizer()

CHOICES = ["red", "green", "blue"]
REGEX = r"[a-c]{3}-[0-9]{2}"


def _echo_schema(n_items: int, values=("on",)) -> dict:
    """Fixed-count array of single-key objects. With one enum value the
    serialization is fully forced (periodic '{"s":"on"},' body — the bench
    json-echo shape); with several, every item is a branch point where the
    model's masked argmax can diverge from a periodic draft."""
    return {
        "type": "array",
        "items": {"type": "object", "properties": {"s": {"enum": list(values)}},
                  "required": ["s"]},
        "minItems": n_items, "maxItems": n_items,
    }


def _pattern_prompt(value: str = "on", reps: int = 4) -> list[int]:
    """Prompt carrying the serialized item pattern so the n-gram drafter
    fires from the first generated tokens."""
    return TOK.encode('[{"s":"%s"},' % value + ('{"s":"%s"},' % value) * reps)


def _engine(spec=False, **over) -> LLMEngine:
    base = dict(page_size=8, num_pages=64, max_model_len=256, max_batch_size=4,
                prefill_chunk=32)
    if spec:
        base.update(spec_mode="ngram", spec_tokens=8)
    base.update(over)
    return LLMEngine(get_model_config("tiny"), EngineConfig(**base), seed=3,
                     tokenizer=TOK)


def _drain(eng: LLMEngine):
    toks: dict[str, list[int]] = {}
    steps = 0
    while eng.has_work():
        for o in eng.step():
            toks.setdefault(o.request_id, []).extend(o.new_token_ids)
        steps += 1
        assert steps < 2000, "no forward progress (livelock)"
    return toks


def _sp(**kw) -> SamplingParams:
    base = dict(max_tokens=96, temperature=0.0, stop_token_ids=(TOK.eos_id,))
    base.update(kw)
    return SamplingParams(**base)


def _strip_eos(ids: list[int]) -> str:
    return TOK.decode([t for t in ids if t != TOK.eos_id])


# -------------------------------------------------------------------- parity


def test_parity_mixed_constrained_batch():
    """choice + regex + schema-echo + unconstrained echo through spec and
    non-spec engines: bitwise identical, constrained rows actually drafted,
    zero violations."""
    import re

    vocab = get_model_config("tiny").vocab_size
    echo = [(7919 + j % 3) % (vocab - 2) + 1 for j in range(48)]
    outs = []
    for spec in (False, True):
        eng = _engine(spec=spec)
        eng.add_request("choice", TOK.encode("pick"), _sp(guided_choice=CHOICES))
        eng.add_request("regex", TOK.encode("match"), _sp(guided_regex=REGEX))
        eng.add_request(
            "schema", _pattern_prompt(),
            _sp(response_format={"type": "json_schema",
                                 "json_schema": {"schema": _echo_schema(6)}}))
        eng.add_request("echo", echo, _sp(max_tokens=24, stop_token_ids=()))
        outs.append(_drain(eng))
        if spec:
            st = eng.stats
            assert st.n_spec_verify_steps > 0
            # the compose actually engaged: constrained drafts were proposed
            # AND landed (the schema-echo row's output is fully forced, so
            # its periodic drafts must verify successfully)
            assert st.spec_drafted_constrained > 0
            assert st.spec_accepted_constrained > 0
            assert st.structured_violations == 0
    assert outs[0] == outs[1], "speculation perturbed a constrained batch"
    assert _strip_eos(outs[1]["choice"]) in CHOICES
    assert re.fullmatch(REGEX, _strip_eos(outs[1]["regex"]))
    value = json.loads(_strip_eos(outs[1]["schema"]))
    assert validate_instance(value, _echo_schema(6)), value


# ------------------------------------------------- FSM rollback == host sync


def test_fsm_rollback_matches_host_sync():
    """Branchy schema (two-value enum per item) makes the periodic draft
    mispredict at item boundaries: drafts are grammar-legal, so trimming
    keeps them, and the masked verify program must REJECT the divergent tail
    and roll the device FSM back with it. spec_structured_crosscheck=True
    re-derives the cursor on host via StructuredState.sync after every
    verify landing and counts disagreements — the gate is exact: zero."""
    schema = _echo_schema(8, values=("on", "off"))
    outs = []
    for spec in (False, True):
        eng = _engine(spec=spec, spec_structured_crosscheck=True)
        for i, val in enumerate(("on", "off")):
            eng.add_request(
                f"s-{i}", _pattern_prompt(val),
                _sp(max_tokens=128,
                    response_format={"type": "json_schema",
                                     "json_schema": {"schema": schema}}))
        outs.append(_drain(eng))
        if spec:
            st = eng.stats
            assert st.spec_drafted_constrained > 0
            assert st.spec_rejected > 0, (
                "no rejected tail — the rollback path was never exercised")
            assert st.spec_fsm_crosscheck_mismatches == 0, (
                f"{st.spec_fsm_crosscheck_mismatches} device/host FSM "
                f"disagreements after rollback")
            assert st.structured_violations == 0
    assert outs[0] == outs[1]
    for rid, ids in outs[1].items():
        value = json.loads(_strip_eos(ids))
        assert validate_instance(value, schema), (rid, value)


def test_crosscheck_off_adopts_device_state_bitwise():
    """The default path (crosscheck off) ADOPTS the device FSM state instead
    of resyncing on host; it must be output-identical to the crosscheck
    engine — the device state is the real cursor, not an approximation."""
    schema = _echo_schema(8, values=("on", "off"))
    outs = []
    for crosscheck in (True, False):
        eng = _engine(spec=True, spec_structured_crosscheck=crosscheck)
        eng.add_request(
            "s", _pattern_prompt("on"),
            _sp(max_tokens=128,
                response_format={"type": "json_schema",
                                 "json_schema": {"schema": schema}}))
        outs.append(_drain(eng))
        assert eng.stats.structured_violations == 0
    assert outs[0] == outs[1]


# ---------------------------------------------------------------- preemption


def test_preemption_mid_speculation_stays_conformant():
    """Tight pool forces preemption while constrained drafts are in flight;
    recompute after requeue must land on the same grammar-masked greedy
    tokens and every generation must still conform."""
    # pool sized so ONE full generation fits (prompt 34 + output 35 tokens
    # in 96 pooled) but two concurrent peak allocations do not — preemption
    # with recompute, never a mid-generation kill (which _retire would
    # rightly count as a conformance violation)
    schema = _echo_schema(3)
    outs = []
    for spec in (False, True):
        eng = _engine(spec=spec, num_pages=12, max_batch_size=2,
                      enable_prefix_caching=False)
        for i in range(3):
            eng.add_request(
                f"s-{i}", _pattern_prompt(reps=2),
                _sp(max_tokens=48,
                    response_format={"type": "json_schema",
                                     "json_schema": {"schema": schema}}))
        outs.append(_drain(eng))
        if spec:
            assert eng.stats.total_preemptions > 0  # churn actually happened
            assert eng.stats.spec_drafted_constrained > 0
            assert eng.stats.structured_violations == 0
    assert outs[0] == outs[1]
    for rid, ids in outs[1].items():
        value = json.loads(_strip_eos(ids))
        assert validate_instance(value, schema), (rid, value)


# ----------------------------------------------------------------- registry


def test_registry_routing_table_driven():
    """ProgramRegistry.route is the whole step() ladder: first routable
    entry whose predicate holds wins, non-routable entries are never routed
    to, and an empty eligible set is a hard error."""
    from llmd_tpu.engine.programs import ProgramRegistry

    class Eng:  # predicate input: a bag of state flags
        def __init__(self, **flags):
            self.__dict__.update(flags)

    reg = ProgramRegistry()
    reg.register("unified", eligible=lambda e: e.constrained or e.prefilling,
                 run=lambda e: None)
    reg.register("verify", eligible=lambda e: e.spec, run=lambda e: None)
    reg.register("verify_masked")  # non-routable: dispatched BY verify
    reg.register("decode", eligible=lambda e: e.decodable, run=lambda e: None)

    table = [
        # (state flags, expected program)
        (dict(constrained=True, prefilling=False, spec=True, decodable=True),
         "unified"),   # registration order = priority
        (dict(constrained=False, prefilling=True, spec=False, decodable=True),
         "unified"),
        (dict(constrained=False, prefilling=False, spec=True, decodable=True),
         "verify"),    # never "verify_masked": no run hook, no routing
        (dict(constrained=False, prefilling=False, spec=False, decodable=True),
         "decode"),
    ]
    for flags, want in table:
        assert reg.route(Eng(**flags)).name == want, (flags, want)
    with pytest.raises(RuntimeError):
        reg.route(Eng(constrained=False, prefilling=False, spec=False,
                      decodable=False))
    with pytest.raises(ValueError):
        reg.register("decode")  # duplicate names are a wiring bug


def test_engine_registry_wiring_and_quiesce():
    """The live engine's registry: routable entries in priority order with
    the masked/embed variants non-routable, and after a full constrained
    spec drain every program's dispatch/complete ledger balances — the
    generalized quiesce invariant, including the masked programs."""
    eng = _engine(spec=True)
    specs = {s.name: s for s in eng.programs.specs()}
    routable = [s.name for s in eng.programs.specs() if s.run is not None]
    assert routable == ["unified", "verify", "decode"]
    for name in ("verify_masked", "decode_masked", "embed"):
        assert specs[name].run is None and specs[name].eligible is None

    eng.add_request(
        "s", _pattern_prompt(),
        _sp(response_format={"type": "json_schema",
                             "json_schema": {"schema": _echo_schema(6)}}))
    _drain(eng)
    assert eng.programs.quiesced(), eng.programs.counters()
    counters = eng.programs.counters()
    # the constrained spec drain exercised the masked verify program — and
    # its completions were all consumed
    disp, comp = counters["verify_masked"]
    assert disp == comp > 0, counters
    for name, (d, c) in counters.items():
        assert d == c, (name, counters)


# ------------------------------------------------------------------- arming


def test_per_sequence_arming():
    """Drafter arming is per-row state (Sequence.spec_armed), not an engine
    global: a disarmed row is skipped by the probe/plan loops (no O(context)
    scan, no draft) while the rest of the batch keeps riding the verify
    program — and the row re-arms the moment fresh tokens land for it."""
    vocab = get_model_config("tiny").vocab_size
    eng = _engine(spec=True)
    assert not hasattr(eng, "_spec_armed"), (
        "engine-global arming flag resurfaced; arming is per-sequence now")
    eng.add_request("echo", [(7919 + j % 3) % (vocab - 2) + 1
                             for j in range(64)],
                    _sp(max_tokens=48, stop_token_ids=()))
    eng.add_request("flat", list(range(10, 58)),
                    _sp(max_tokens=48, stop_token_ids=()))
    seqs = {}
    steps = 0
    while eng.has_work() and eng.stats.n_spec_verify_steps < 3:
        for s in eng.running:
            if s is not None:
                seqs[s.request_id] = s
        eng.step()
        steps += 1
        assert steps < 2000, "verify steady state never reached"
    flat, echo = seqs["flat"], seqs["echo"]
    assert not flat.finished and not echo.finished
    assert echo.spec_drafted > 0

    # force-disarm the flat row and watch one verify step go by: the probe
    # loop must skip it entirely, the echo row must still draft, the flat
    # row must still land its plain token through the verify plan (no
    # starvation), and the landing must re-arm it
    probed: list[str] = []
    orig = eng._spec_propose
    eng._spec_propose = lambda s, m: (probed.append(s.request_id),
                                      orig(s, m))[1]
    try:
        v0 = eng.stats.n_spec_verify_steps
        for _ in range(60):
            assert not flat.finished and not echo.finished
            flat.spec_armed = False
            probed.clear()
            n_flat = len(flat.token_ids)
            eng.step()
            if eng.stats.n_spec_verify_steps > v0:
                break
            v0 = eng.stats.n_spec_verify_steps
        else:
            raise AssertionError("no verify step while flat was disarmed")
    finally:
        eng._spec_propose = orig
    assert "flat" not in probed, "disarmed row was still probed"
    assert "echo" in probed
    assert len(flat.token_ids) > n_flat  # plain token landed regardless
    assert flat.spec_armed  # fresh token landed: the row re-armed itself
