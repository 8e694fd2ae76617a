"""The deferred sample read of a unified step, on the synchronous oracle.

A unified step's sampled tokens are read one step late (engine.py
``_step_unified`` / ``_sample_apply``), so the read hides behind the next
step's device time. These tests pin the invariant for the prefill side, as
they did when the deferral had an option of its own
(``pipeline_prefill_sample``, gone since ISSUE 29): deferral is an overlap
optimisation, never a semantic change. The oracle is the same engine read
synchronously: ``_flush_pending_sample()`` after every ``step()``
(``tests/test_unified_ahead.py`` has the decode side).
"""

from __future__ import annotations

import conftest  # noqa: F401
import pytest

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.models import get_model_config


def _engine(**kw) -> LLMEngine:
    base = dict(page_size=8, num_pages=128, max_model_len=256, max_batch_size=4,
                prefill_chunk=32, decode_steps=4)
    base.update(kw)
    return LLMEngine(get_model_config("tiny"), EngineConfig(**base))


def drive(eng: LLMEngine, oracle: bool = False, arrivals=None,
          unchained: bool = False) -> dict:
    """Step ``eng`` dry; ``oracle`` reads every unified step before the next
    is planned, ``unchained`` every fused decode call (so none is chained on
    another). ``arrivals``: {step index: [(request_id, prompt, sampling) or
    (request_id, prompt, sampling, add_request's other keywords)]}."""
    got: dict[str, list[int]] = {}
    steps = 0
    while eng.has_work() or (arrivals and steps <= max(arrivals)):
        for rid, prompt, sp, *kw in (arrivals or {}).get(steps, ()):
            eng.add_request(rid, prompt, sp, **(kw[0] if kw else {}))
        outs = eng.step()
        if oracle:
            eng._flush_pending_sample()  # appends to the list step() returned
        if unchained:
            eng._flush_pending_decode()  # as above
        for out in outs:
            got.setdefault(out.request_id, []).extend(out.new_token_ids)
        steps += 1
    assert eng._pending_sample is None and not eng._pending_decode
    assert eng.programs.quiesced()
    return got


def generate(eng: LLMEngine, prompts, sp, oracle: bool = False,
             unchained: bool = False) -> dict:
    for i, p in enumerate(prompts):
        eng.add_request(f"req-{i}", p, sp)
    return drive(eng, oracle, unchained=unchained)


PROMPTS = [list(range(3, 40)), list(range(50, 75)), list(range(80, 140)),
           list(range(150, 160))]


def test_greedy_identical_with_and_without_deferred_sample():
    sp = SamplingParams(max_tokens=11, temperature=0.0, ignore_eos=True)
    out_on = _engine().generate(PROMPTS, sp)
    out_off = generate(_engine(), PROMPTS, sp, oracle=True)
    assert out_on == out_off
    for v in out_on.values():
        assert len(v) == 11


def test_sampled_deterministic_and_complete_under_deferral():
    """Stochastic sampling is self-deterministic per seed (keys are split in
    dispatch order), and every request still gets its full token budget."""
    sp = SamplingParams(max_tokens=7, temperature=0.9, top_k=20, ignore_eos=True)
    a = _engine().generate(PROMPTS, sp)
    b = _engine().generate(PROMPTS, sp)
    assert a == b
    for v in a.values():
        assert len(v) == 7


def test_single_request_first_token_not_lost():
    """One request, nothing to overlap with: the prefill→decode boundary flush
    must deliver the deferred first token before the decode batch is built."""
    sp = SamplingParams(max_tokens=5, temperature=0.0)
    out = _engine().generate([list(range(10, 30))], sp)
    assert len(out["req-0"]) == 5
    assert generate(_engine(), [list(range(10, 30))], sp,
                    oracle=True)["req-0"] == out["req-0"]


def test_abort_between_dispatch_and_apply():
    """Abort a request whose first-token sample is still in flight: the apply
    guard must skip the dead row, and the other request must be unaffected."""
    eng = _engine()
    eng.add_request("victim", list(range(10, 26)),
                    SamplingParams(max_tokens=4, temperature=0.0))
    eng.add_request("keeper", list(range(30, 46)),
                    SamplingParams(max_tokens=4, temperature=0.0))
    eng.step()  # one chunk covers both prompts → both samples deferred
    assert eng._pending_sample is not None
    eng.abort("victim")
    got = drive(eng)
    assert "victim" not in got
    assert len(got["keeper"]) == 4
    solo = _engine().generate([list(range(30, 46))],
                              SamplingParams(max_tokens=4, temperature=0.0))
    assert solo["req-0"] == got["keeper"]


@pytest.mark.parametrize("at", [2, 3])
def test_mixed_step_matches_the_synchronous_oracle(at):
    """Stagger arrivals so decode and prefill rows share steps: a step that
    carries decode rows defers its read like any other now, and its rows
    ride in the next step; outputs still match the synchronous engine."""
    sp = SamplingParams(max_tokens=9, temperature=0.0, ignore_eos=True)
    arrivals = {0: [("a", PROMPTS[0], sp)], at: [("b", PROMPTS[1], sp)]}
    on = drive(_engine(), arrivals=arrivals)
    off = drive(_engine(), oracle=True, arrivals=arrivals)
    assert on == off
    assert len(on["a"]) == 9 and len(on["b"]) == 9


def test_no_pending_left_after_generate():
    eng = _engine()
    eng.generate(PROMPTS[:2], SamplingParams(max_tokens=3, temperature=0.0))
    assert eng._pending_sample is None
    assert not eng.has_work()
