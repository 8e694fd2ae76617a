"""Structured outputs (llmd_tpu/structured): grammar-constrained decoding.

The contract under test is absolute, not statistical: 100% of constrained
generations must parse/validate against their constraint — across
choice/regex/JSON-Schema, greedy and sampled, with and without preemption —
while engines that never see a structured request observe zero new jit
compiles and bitwise-unchanged outputs. Schemas here use only BOUNDED
constructs (enum/boolean/maxLength/maxItems): the token DFA is then a DAG,
so even a random-weight model is forced to a terminal state before
max_tokens, which is what makes "100%" assertable at all.
"""

from __future__ import annotations

import json
import re

import conftest  # noqa: F401
import pytest

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.engine.tokenizer import ByteTokenizer
from llmd_tpu.models import get_model_config
from llmd_tpu.structured import (
    GrammarCache,
    RegexError,
    compile_grammar,
    compile_regex,
    escape_literal,
    global_cache,
    parse_logit_bias,
    regex_for_schema,
    reset_global_cache,
    spec_to_regex,
    validate_instance,
    validate_structured_body,
)

TOK = ByteTokenizer()

SCHEMA = {
    "type": "object",
    "properties": {
        "name": {"type": "string", "maxLength": 8},
        "count": {"enum": [0, 1, 2, 3]},
        "ok": {"type": "boolean"},
    },
    "required": ["name", "count", "ok"],
}
CHOICES = ["red", "green", "blue"]
REGEX = r"[a-c]{3}-[0-9]{2}"


def _dfa_accepts(dfa, s: str) -> bool:
    state = dfa.start
    for ch in s:
        state = dfa.trans[state].get(ch)
        if state is None:
            return False
    return state in dfa.accept


# ----------------------------------------------------------- regex -> charDFA


def test_escape_literal_roundtrip():
    for lit in ("a.b", "x{2}", "(y|z)", "[k]+?", "\\", "plain"):
        dfa = compile_regex(escape_literal(lit))
        assert _dfa_accepts(dfa, lit)
        assert not _dfa_accepts(dfa, lit + "!")


def test_compile_regex_core_constructs():
    cases = [
        (r"ab|cd", ["ab", "cd"], ["a", "abcd", ""]),
        (r"a[0-9]{2}z?", ["a12", "a99z"], ["a1", "a123", "az"]),
        (r"(foo)+(bar)*", ["foo", "foofoo", "foobarbar"], ["", "bar"]),
        (r"[^x]", ["a", "0"], ["x", "aa"]),
        (r"\d+\.\d+", ["3.14"], ["3.", ".14", "3,14"]),
    ]
    for pat, yes, no in cases:
        dfa = compile_regex(pat)
        for s in yes:
            assert _dfa_accepts(dfa, s), (pat, s)
        for s in no:
            assert not _dfa_accepts(dfa, s), (pat, s)


def test_compile_regex_rejects_unsupported():
    for pat in (r"(?=a)b", r"a{999999}", r"a[", r"(ab", r"*a", "a\\"):
        with pytest.raises(RegexError):
            compile_regex(pat)
    with pytest.raises(RegexError):
        compile_regex(r"a[^\s\S]")  # empty class: matches no strings


# --------------------------------------------------- JSON Schema -> regex


def test_regex_for_schema_bounded_constructs():
    dfa = compile_regex(regex_for_schema(SCHEMA))
    good = '{"name":"ab","count":2,"ok":true}'
    assert _dfa_accepts(dfa, good)
    assert not _dfa_accepts(dfa, '{"name":"ab","count":9,"ok":true}')
    assert not _dfa_accepts(dfa, '{"name":"ab","ok":true}')  # missing required

    # maxItems=0 must lower to the empty array, not an unsatisfiable pattern
    arr = compile_regex(regex_for_schema({"type": "array", "maxItems": 0}))
    assert _dfa_accepts(arr, "[]") and not _dfa_accepts(arr, "[1]")

    enum = compile_regex(regex_for_schema({"enum": ["a b", 7, None]}))
    for s in ('"a b"', "7", "null"):
        assert _dfa_accepts(enum, s)


def test_validate_instance_subset():
    assert validate_instance({"name": "ab", "count": 1, "ok": False}, SCHEMA)
    assert not validate_instance({"name": "ab", "count": 9, "ok": False}, SCHEMA)
    assert not validate_instance({"count": 1, "ok": True}, SCHEMA)  # required
    assert not validate_instance({"name": "toolongname", "count": 1,
                                  "ok": True}, SCHEMA)
    assert validate_instance([1, 2], {"type": "array", "maxItems": 2})
    assert not validate_instance([1, 2, 3], {"type": "array", "maxItems": 2})


def test_spec_to_regex_and_body_validation():
    assert _dfa_accepts(compile_regex(spec_to_regex("choice", CHOICES)), "red")
    with pytest.raises(ValueError):
        spec_to_regex("choice", [])
    with pytest.raises(ValueError):
        spec_to_regex("json_schema", "not-a-dict")

    validate_structured_body({"guided_regex": REGEX})  # fine
    for body in (
        {"response_format": {"type": "yaml_object"}},
        {"response_format": "json"},
        {"guided_regex": "(?=a)b"},
        {"response_format": {"type": "json_schema",
                             "json_schema": {"schema": {"type": "wat"}}}},
        {"logit_bias": {"5": 9000}},
        {"logit_bias": {"-3": 1.0}},
    ):
        with pytest.raises(ValueError):
            validate_structured_body(body)
    assert parse_logit_bias({"7": -100, 9: 2.5}) == {7: -100.0, 9: 2.5}
    assert parse_logit_bias({}) is None


# ------------------------------------------------------------ grammar cache


def test_grammar_cache_hit_and_eviction(monkeypatch):
    cache = GrammarCache(capacity=2)

    def compile_choice(words):
        return compile_grammar("choice", words, TOK, TOK.vocab_size,
                               cache=cache)

    _, hit = compile_choice(["a", "b"])
    assert not hit and cache.misses == 1
    _, hit = compile_choice(["a", "b"])
    assert hit and cache.hits == 1 and len(cache) == 1
    compile_choice(["c"])
    compile_choice(["d"])  # capacity 2: ["a","b"] falls out
    assert cache.evictions == 1 and len(cache) == 2
    _, hit = compile_choice(["a", "b"])
    assert not hit and cache.misses == 4

    # the process-global cache reads LLMD_STRUCTURED_CACHE_SIZE on first touch
    monkeypatch.setenv("LLMD_STRUCTURED_CACHE_SIZE", "3")
    reset_global_cache()
    assert global_cache().capacity == 3
    monkeypatch.setenv("LLMD_STRUCTURED_CACHE_SIZE", "not-a-number")
    reset_global_cache()
    assert global_cache().capacity == 64  # malformed -> default
    monkeypatch.delenv("LLMD_STRUCTURED_CACHE_SIZE")
    reset_global_cache()


def test_token_grammar_walk_reaches_eos():
    """Greedy first-allowed walk over the token automaton must spell a valid
    choice and then offer EOS (the synthetic terminal transition)."""
    grammar, _ = compile_grammar("choice", CHOICES, TOK, 288,
                                 cache=GrammarCache(capacity=1))
    state, emitted = grammar.start, []
    for _ in range(64):
        allowed = grammar.allowed_ids(state)
        assert len(allowed) > 0
        tid = int(allowed[0])
        if tid == TOK.eos_id:
            break
        emitted.append(tid)
        state = grammar.advance(state, tid)
        assert state is not None
    else:
        pytest.fail("walk never reached EOS")
    assert TOK.decode(emitted) in CHOICES
    assert grammar.is_complete(state)
    # EOS before any choice is spelled out violates (start is not accepting)
    assert grammar.advance(grammar.start, TOK.eos_id) is None


# ------------------------------------------------------------- engine corpus


def _engine(tokenizer=TOK, **over) -> LLMEngine:
    base = dict(page_size=8, num_pages=64, max_model_len=256, max_batch_size=4,
                prefill_chunk=32)
    base.update(over)
    return LLMEngine(get_model_config("tiny"), EngineConfig(**base), seed=3,
                     tokenizer=tokenizer)


def _drain(eng: LLMEngine):
    toks: dict[str, list[int]] = {}
    fins: dict[str, str] = {}
    steps = 0
    while eng.has_work():
        for o in eng.step():
            toks.setdefault(o.request_id, []).extend(o.new_token_ids)
            if o.finish_reason:
                fins[o.request_id] = o.finish_reason
        steps += 1
        assert steps < 2000, "no forward progress (livelock)"
    return toks, fins


def _sp(**kw) -> SamplingParams:
    base = dict(max_tokens=64, temperature=0.0, stop_token_ids=(TOK.eos_id,))
    base.update(kw)
    return SamplingParams(**base)


def _check_constrained(kind: str, text: str) -> None:
    if kind == "choice":
        assert text in CHOICES, text
    elif kind == "regex":
        assert re.fullmatch(REGEX, text), text
    else:
        assert validate_instance(json.loads(text), SCHEMA), text


CORPUS = [
    ("choice", dict(guided_choice=CHOICES)),
    ("regex", dict(guided_regex=REGEX)),
    ("schema", dict(response_format={"type": "json_schema",
                                     "json_schema": {"schema": SCHEMA}})),
]


def _add_corpus(eng: LLMEngine, prompt_salt: str = "") -> None:
    for kind, fields in CORPUS:
        for temp in (0.0, 0.7):
            eng.add_request(
                f"{kind}-t{temp}",
                TOK.encode(f"{prompt_salt}please emit one {kind} now"),
                _sp(temperature=temp, seed=11, **fields))


def test_corpus_every_generation_conforms():
    """choice/regex/json_schema x greedy/sampled: 100% parse+validate, zero
    grammar violations, and the new metric families are live."""
    eng = _engine()
    _add_corpus(eng)
    toks, fins = _drain(eng)
    assert len(toks) == 6
    for rid, ids in toks.items():
        assert fins[rid] == "stop", (rid, fins)  # grammar forced termination
        _check_constrained(rid.split("-")[0], TOK.decode(ids))
    st = eng.stats
    assert st.structured_requests == 6
    assert st.structured_violations == 0
    assert st.structured_mask_builds > 0 and st.time_mask_build > 0
    text = eng.registry.expose()
    for fam in ("llmd_tpu:structured_requests_total",
                "llmd_tpu:structured_compile_seconds",
                "llmd_tpu:structured_mask_build_seconds",
                "llmd_tpu:structured_cache_hits_total",
                "llmd_tpu:structured_cache_misses_total",
                "llmd_tpu:structured_violations_total"):
        assert fam in text, f"{fam} missing from /metrics"
    # same schema re-admitted -> grammar-cache hit, still conformant
    hits0 = global_cache().hits
    eng.add_request("schema-again", TOK.encode("again"),
                    _sp(response_format={"type": "json_schema",
                                         "json_schema": {"schema": SCHEMA}}))
    toks, _ = _drain(eng)
    assert global_cache().hits > hits0
    _check_constrained("schema", TOK.decode(toks["schema-again"]))


def test_corpus_survives_preemption():
    """Tight pool forces preempt/requeue mid-generation; the FSM cursor is
    re-derived from the token history after re-prefill, so conformance holds."""
    # Constraints chosen so every generation is LONG (~25-41 tokens): each
    # request fits the 80-token pool alone, but any two live seqs overcommit
    # it mid-decode — preemption churn without forced truncation.
    p_choices = ["abcdefghijklmnopqrstuvwx", "zyxwvutsrqponmlkjihgfedc"]
    p_regex = r"[ab]{24}"
    p_corpus = [
        ("choice", dict(guided_choice=p_choices),
         lambda t: t in p_choices),
        ("regex", dict(guided_regex=p_regex),
         lambda t: re.fullmatch(p_regex, t)),
        ("schema", dict(response_format={"type": "json_schema",
                                         "json_schema": {"schema": SCHEMA}}),
         lambda t: validate_instance(json.loads(t), SCHEMA)),
    ]
    eng = _engine(num_pages=10, max_batch_size=2, enable_prefix_caching=False)
    for i, (kind, fields, _check) in enumerate(p_corpus):
        eng.add_request(f"{kind}-p", TOK.encode("x" * (28 + 2 * i)),
                        _sp(temperature=0.7 if i % 2 else 0.0, seed=i,
                            **fields))
    toks, fins = _drain(eng)
    assert eng.stats.total_preemptions > 0, "pool never got tight"
    assert eng.stats.structured_violations == 0
    for kind, _fields, check in p_corpus:
        rid = f"{kind}-p"
        assert fins[rid] == "stop"
        assert check(TOK.decode(toks[rid])), (rid, TOK.decode(toks[rid]))


def test_json_object_mode_parses_when_complete():
    """json_object constrains to bounded-depth generic JSON with unbounded
    scalars, so termination isn't guaranteed on a random model — the contract
    is the weaker one: whatever DID finish at an accept state parses."""
    eng = _engine()
    eng.add_request("obj", TOK.encode("give json"),
                    _sp(response_format={"type": "json_object"},
                        max_tokens=48))
    toks, fins = _drain(eng)
    assert eng.stats.structured_requests == 1
    if fins["obj"] == "stop":
        json.loads(TOK.decode(toks["obj"]))


def test_logit_bias_round_trip_engine():
    """+100 on one byte under greedy decoding must dominate every step; -100
    must ban the argmax token that an unbiased run produces."""
    eng = _engine()
    z = TOK.encode("z")[0]
    eng.add_request("force", TOK.encode("say something"),
                    _sp(max_tokens=6, logit_bias={z: 100},
                        stop_token_ids=()))
    toks, _ = _drain(eng)
    assert TOK.decode(toks["force"]) == "zzzzzz"

    eng.add_request("plain", TOK.encode("say something"),
                    _sp(max_tokens=6, stop_token_ids=()))
    toks, _ = _drain(eng)
    banned = toks["plain"][0]
    eng.add_request("ban", TOK.encode("say something"),
                    _sp(max_tokens=6, logit_bias={banned: -100},
                        stop_token_ids=()))
    toks, _ = _drain(eng)
    assert banned not in toks["ban"]


# ----------------------------------------------- off-path purity + spec mix


def test_structured_off_bitwise_identical_and_no_biased_compile():
    """An unstructured request must produce bitwise-identical tokens whether
    or not a structured neighbor shares the batch, and an engine that never
    saw a structured request must never compile the biased sampler."""
    from llmd_tpu.engine.sampling import sample_tokens_biased

    prompt = TOK.encode("the quick brown fox jumps over the lazy dog")
    sp = _sp(max_tokens=16, stop_token_ids=())

    n_compiles = (sample_tokens_biased._cache_size()
                  if hasattr(sample_tokens_biased, "_cache_size") else None)
    eng_a = _engine(tokenizer=None)  # no tokenizer: pure unstructured engine
    eng_a.add_request("u", prompt, sp)
    baseline, _ = _drain(eng_a)
    if n_compiles is not None:
        assert sample_tokens_biased._cache_size() == n_compiles, (
            "structured-off engine compiled the biased sampler")
    # structured admission without a tokenizer is refused, state untouched
    with pytest.raises(ValueError):
        eng_a.add_request("s", prompt, _sp(guided_choice=CHOICES))
    assert not eng_a.has_work()

    eng_b = _engine()  # same seed/config, structured neighbor in the batch
    eng_b.add_request("u", prompt, sp)
    eng_b.add_request("s", TOK.encode("pick"), _sp(guided_choice=CHOICES))
    mixed, _ = _drain(eng_b)
    assert mixed["u"] == baseline["u"], (
        "structured neighbor perturbed an unstructured request")
    _check_constrained("choice", TOK.decode(mixed["s"]))


def test_spec_decode_structured_rows_bitwise_parity():
    """Mixed spec+structured batch: constrained rows draft through the
    grammar-masked verify program, and the whole batch must still match the non-spec engine bitwise. The compose
    itself is pinned in depth by tests/test_spec_structured.py."""
    vocab = get_model_config("tiny").vocab_size
    echo = [(7919 + j % 3) % (vocab - 2) + 1 for j in range(48)]
    outs = []
    for spec in (False, True):
        over = dict(spec_mode="ngram", spec_tokens=4) if spec else {}
        eng = _engine(**over)
        eng.add_request("echo", echo, _sp(max_tokens=24, stop_token_ids=()))
        eng.add_request("cons", TOK.encode("pick"), _sp(guided_choice=CHOICES))
        toks, _ = _drain(eng)
        outs.append(toks)
        if spec:
            # the constrained row retires early (short choice), after which
            # the echo row must actually enter the verify path
            assert eng.stats.n_spec_verify_steps > 0, (
                "spec path never engaged after the structured row retired")
    assert outs[0] == outs[1], "speculation perturbed a structured batch"
    _check_constrained("choice", TOK.decode(outs[1]["cons"]))


def test_structured_mode_validation():
    with pytest.raises(ValueError):
        _engine(structured_mode="always")
    eng = _engine(structured_mode="off", num_pages=16, max_model_len=64,
                  max_batch_size=2, prefill_chunk=16)
    with pytest.raises(ValueError):
        eng.add_request("s", TOK.encode("x"), _sp(guided_choice=CHOICES))
    assert not eng.has_work()


# ------------------------------------------------------ HTTP 400 plumbing


def test_router_parse_rejects_malformed_before_flow_control():
    from llmd_tpu.router.server import parse_openai_request

    good = parse_openai_request(
        "/v1/chat/completions",
        {"model": "m", "messages": [{"role": "user", "content": "x"}],
         "guided_regex": REGEX, "logit_bias": {"7": 2}},
        {})
    assert good.sampling.guided_regex == REGEX
    assert good.sampling.logit_bias == {"7": 2}

    for body in (
        {"model": "m", "messages": [], "guided_regex": "(?=a)b"},
        {"model": "m", "messages": [],
         "response_format": {"type": "json_schema",
                             "json_schema": {"schema": {"type": "wat"}}}},
        {"model": "m", "messages": [], "logit_bias": {"1": 500}},
    ):
        with pytest.raises(ValueError):
            parse_openai_request("/v1/chat/completions", body, {})


def test_engine_server_structured_http_round_trip():
    """Through the real HTTP surface: constrained chat completions conform,
    logit_bias round-trips, malformed schemas answer 400 (never 5xx)."""
    import aiohttp
    from conftest import run_async

    from llmd_tpu.engine.server import EngineServer

    async def scenario():
        srv = EngineServer(
            get_model_config("tiny"),
            EngineConfig(page_size=8, num_pages=64, max_model_len=128,
                         max_batch_size=2, prefill_chunk=16),
            model_name="llmd-tpu/tiny", port=0)
        await srv.start()
        try:
            async with aiohttp.ClientSession() as sess:
                async def chat(extra):
                    body = {"model": "llmd-tpu/tiny", "max_tokens": 48,
                            "temperature": 0.0,
                            "messages": [{"role": "user", "content": "go"}],
                            **extra}
                    async with sess.post(
                        f"http://{srv.address}/v1/chat/completions",
                        json=body) as r:
                        return r.status, (await r.json() if r.status == 200
                                          else await r.text())

                status, data = await chat(
                    {"response_format": {"type": "json_schema",
                                         "json_schema": {"schema": SCHEMA}}})
                assert status == 200, data
                content = data["choices"][0]["message"]["content"]
                assert validate_instance(json.loads(content), SCHEMA)
                assert data["choices"][0]["finish_reason"] == "stop"

                status, data = await chat({"guided_choice": CHOICES})
                assert status == 200 and (
                    data["choices"][0]["message"]["content"] in CHOICES)

                z = "z".encode()[0]
                status, data = await chat({"logit_bias": {str(z): 100},
                                           "max_tokens": 5})
                assert status == 200
                assert data["choices"][0]["message"]["content"] == "zzzzz"

                for bad in (
                    {"response_format": {"type": "json_schema",
                                         "json_schema": {"schema":
                                                         {"type": "wat"}}}},
                    {"guided_regex": "(ab"},
                    {"logit_bias": {"3": 101}},
                ):
                    status, text = await chat(bad)
                    assert status == 400, (bad, status, text)
        finally:
            await srv.stop()

    run_async(scenario())
