"""Speculative decoding (spec_mode="ngram"): prompt-lookup drafts verified
through the flat mixed-batch program (engine/spec.py + engine._step_spec_verify).

Greedy acceptance makes the spec engine a pure latency optimisation: every
emitted token is the model's own argmax, so output must be BITWISE identical
to the non-speculative engine. These tests pin that parity across the axes
speculation composes with — prefix-cache hits, preemption mid-speculation,
LoRA adapters, and MLA — plus the page-ledger invariant under draft rollback
and the acceptance-rate floor on echo-heavy traffic (the regime prompt-lookup
targets)."""

from __future__ import annotations

import conftest  # noqa: F401

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.models import get_model_config


def _engine(model="tiny", spec=False, lora_cfg=None, **over) -> LLMEngine:
    base = dict(page_size=8, num_pages=64, max_model_len=256, max_batch_size=4,
                prefill_chunk=32)
    base.update(over)
    if spec:
        base.update(spec_mode="ngram", spec_tokens=4)
    return LLMEngine(get_model_config(model),
                     EngineConfig(**base, lora=lora_cfg), seed=3)


def _drain(eng: LLMEngine) -> dict[str, list[int]]:
    out: dict[str, list[int]] = {}
    steps = 0
    while eng.has_work():
        for o in eng.step():
            out.setdefault(o.request_id, []).extend(o.new_token_ids)
        steps += 1
        assert steps < 2000, "no forward progress (livelock)"
    return out


def _echo_prompt(salt: int, n: int = 48, period: int = 3) -> list[int]:
    """Periodic prompt: the suffix n-gram
    always has an earlier occurrence, so the drafter fires every step."""
    vocab = get_model_config("tiny").vocab_size
    return [(salt * 7919 + j % period) % (vocab - 2) + 1 for j in range(n)]


GREEDY = SamplingParams(max_tokens=16, temperature=0.0)


# ------------------------------------------------------------------- drafter


def test_propose_ngram_draft_unit():
    from llmd_tpu.engine.spec import propose_ngram_draft

    # periodic history: suffix (2,3) recurs; draft continues the period and
    # prefers a hit with a FULL k-token continuation, not the latest hit
    hist = [1, 2, 3, 1, 2, 3, 1, 2, 3]
    assert propose_ngram_draft(hist, k=3) == [1, 2, 3]
    # no recurring suffix -> no draft (engine falls back to fused decode)
    assert propose_ngram_draft([1, 2, 3, 4, 5, 6], k=4) == []
    # k caps the draft even when the continuation is longer
    assert propose_ngram_draft(hist, k=2) == [1, 2]
    assert propose_ngram_draft([7], k=4) == []  # too short to match anything


# -------------------------------------------------------------------- parity


def _parity(prompts, sampling=GREEDY, model="tiny", drain=_drain, **kw):
    """Run identical requests through spec and non-spec engines; outputs must
    be bitwise identical (greedy acceptance re-emits the model's own argmax).
    Returns both engines so callers can compose follow-up parity rounds
    without paying two more compiles."""
    engines, outs = [], []
    for spec in (False, True):
        eng = _engine(model=model, spec=spec, **kw)
        for i, p in enumerate(prompts):
            eng.add_request(f"req-{i}", p, sampling)
        outs.append(drain(eng))
        engines.append(eng)
    assert outs[0] == outs[1], "speculative output diverged from greedy baseline"
    return engines


def test_parity_plain_batch_then_prefix_cache_hit():
    # mix of echo-heavy (drafter fires) and arbitrary (drafter mostly idle)
    prompts = [_echo_prompt(1), list(range(10, 40)), _echo_prompt(2, period=4)]
    base, spec = _parity(prompts)
    assert spec.stats.n_spec_verify_steps > 0  # the spec path actually ran

    # round 2 on the SAME engines: a request sharing req-0's prompt prefix
    # admits with cached pages (seq.num_cached_prompt > 0); speculation on
    # top of a prefix-cache hit must not perturb output
    outs = []
    for eng in (base, spec):
        eng.add_request("hit", _echo_prompt(1) + [9, 9], GREEDY)
        outs.append(_drain(eng))
        assert eng._prefix_cached_total > 0  # the axis was actually exercised
    assert outs[0] == outs[1]


def test_parity_preemption_and_ledger_under_rollback():
    """Tight pool forces preemption while drafts are in flight; recompute
    after requeue must land on the same greedy tokens. The spec engine is
    drained with a per-step ledger audit: every allocated page's refcount
    equals the number of sequences whose ledger lists it (the r05 page-ledger
    invariant, now exercised with rejected speculative tails being trimmed
    back into the free list)."""
    from collections import Counter

    def audited_drain(eng):
        out: dict[str, list[int]] = {}
        steps = 0
        while eng.has_work():
            for o in eng.step():
                out.setdefault(o.request_id, []).extend(o.new_token_ids)
            steps += 1
            assert steps < 600, "no forward progress (livelock)"
            owned = Counter()
            for s in list(eng.running) + [x for q in eng.waitq for x in q]:
                if s is not None:
                    for pid in s.pages:
                        owned[pid] += 1
            for pid, info in eng.allocs[0].pages.items():
                held = owned.get(pid, 0)
                assert info.refs == held, (
                    f"step {steps}: page {pid} refs={info.refs} but owned by "
                    f"{held} seqs (leak)")
        return out

    prompts = [_echo_prompt(i, n=36) for i in range(3)]
    sp = SamplingParams(max_tokens=16, temperature=0.0)
    _, spec = _parity(prompts, sampling=sp, drain=audited_drain, num_pages=10,
                      max_batch_size=2, enable_prefix_caching=False)
    assert spec.stats.total_preemptions > 0  # churn actually happened
    assert spec.stats.spec_rejected > 0  # rollback actually happened
    assert spec.stats.n_spec_verify_steps > 0


def test_parity_lora():
    """Per-row adapter gather in the verify chunk must match the decode path:
    tuned rows stay tuned, base rows stay base, bitwise."""
    from llmd_tpu.models.lora import LoRAConfig

    prompt = _echo_prompt(3, n=40)
    outs = []
    for spec in (False, True):
        eng = _engine(spec=spec, lora_cfg=LoRAConfig(max_adapters=2, rank=4),
                      max_model_len=128, prefill_chunk=16)
        eng.load_lora_adapter("sql-adapter")
        eng.add_request("base", prompt, GREEDY)
        eng.add_request("tuned", prompt, GREEDY, lora_id="sql-adapter")
        outs.append(_drain(eng))
        if spec:
            assert eng.stats.n_spec_verify_steps > 0
    assert outs[0] == outs[1]
    assert outs[1]["base"] != outs[1]["tuned"]  # adapter visibly applied


def test_parity_mla():
    """Absorbed-MLA verify chunks (latent KV writes at every packed position)
    must reproduce the fused-decode outputs."""
    prompts = [_echo_prompt(7, n=44), _echo_prompt(11, n=30, period=4)]
    _, spec = _parity(prompts, model="tiny-mla", num_pages=128)
    assert spec.stats.n_spec_verify_steps > 0


# ---------------------------------------------------------------- acceptance


def test_echo_acceptance_rate_metrics_and_temperature_fallback():
    """The whole point: on echo-heavy traffic a verify step must land MORE
    than one token on average (1.0 is what plain decode already gives).
    Same engine then pins the /metrics families and the sampling fallback."""
    eng = _engine(spec=True)
    for i in range(2):
        eng.add_request(f"e-{i}", _echo_prompt(i, n=64),
                        SamplingParams(max_tokens=48, temperature=0.0))
    _drain(eng)
    st = eng.stats
    assert st.n_spec_verify_steps > 0
    # accepted DRAFT tokens per verify step; the bonus token comes on top,
    # so >1 here means each verify step beats a plain decode step outright
    assert st.spec_accepted / st.n_spec_verify_steps > 1.0, (
        f"accepted {st.spec_accepted} over {st.n_spec_verify_steps} verify "
        f"steps — speculation is not paying for itself on echo traffic")
    assert st.spec_drafted >= st.spec_accepted + st.spec_rejected

    text = eng.registry.expose()
    for fam in ("llmd_tpu:spec_drafted_tokens_total",
                "llmd_tpu:spec_accepted_tokens_total",
                "llmd_tpu:spec_rejected_tokens_total",
                "llmd_tpu:spec_acceptance_rate",
                "llmd_tpu:engine_prefix_cached_tokens_total",
                "llmd_tpu:engine_prefix_cache_hit_ratio"):
        assert fam in text, f"{fam} missing from /metrics"

    # sampling (temperature > 0) is not greedy-verifiable: it must be served
    # through the normal decode path, never the verify program
    drafted = st.spec_drafted
    eng.add_request("sampled", _echo_prompt(1), SamplingParams(max_tokens=12,
                                                               temperature=0.8))
    _drain(eng)
    assert st.spec_drafted == drafted  # drafter never fired for the sampled req


def test_spec_mode_validated():
    import pytest

    with pytest.raises(ValueError):
        _engine(spec_mode="medusa")
