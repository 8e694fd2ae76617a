"""The unified step picks its own tokens (ISSUE 31): the sampler runs inside
the step programs, takes its argmax branch when no row of the step samples,
and the host sends sampling state only when there is some.

Three things are held here. ``_sample_core`` against the formula it had
before it grew a branch (kept below, line for line). The engine against
itself read synchronously (``_flush_pending_sample()`` after every step), for
greedy, seeded sampling and mixed traffic, with and without a mesh. And the
books: which programs were dispatched, how often ``_key`` was split, what
``sampler_steps_total`` counted, and that the first sampling row compiles
nothing.
"""

from __future__ import annotations

import dataclasses

import conftest  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.engine.config import MeshConfig
from llmd_tpu.engine.sampling import sample_tokens, sample_tokens_biased
from llmd_tpu.models import get_model_config
from tests.test_pipeline_prefill_sample import drive
from tests.test_unified_ahead import BASE, GREEDY, PROMPTS, _ahead, _count

SAMPLED = dict(temperature=0.8, top_k=30, top_p=0.95, ignore_eos=True)


# ----------------------------------------------- the sampler and its old self

def _before_the_branch(logits, key, temperature, top_k, top_p, top_k_max=64):
    """``_sample_core`` as it was until PR 30: every row pays the top-k."""
    V = logits.shape[1]
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    topv, topi = jax.lax.top_k(scaled, min(top_k_max, V))
    K = topv.shape[1]
    ranks = jnp.arange(K)[None, :]
    k_eff = jnp.where(top_k > 0, jnp.minimum(top_k, K), K)[:, None]
    topv = jnp.where(ranks < k_eff, topv, -jnp.inf)
    probs = jax.nn.softmax(topv, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    topv = jnp.where((cum - probs) < top_p[:, None], topv, -jnp.inf)
    choice = jax.random.categorical(key, topv, axis=-1)
    sampled = jnp.take_along_axis(topi, choice[:, None], axis=1)[:, 0]
    return jnp.where(temperature <= 0.0, greedy, sampled)


B, V = 6, 300
TEMPS = {"greedy": [0.0] * B,
         "mixed": [0.0, 0.7, 0.0, 1.3, 0.0, 0.2],
         "sampling": [0.7, 1.0, 1.3, 0.2, 2.0, 0.9]}
FILTERS = {"plain": ([0] * B, [1.0] * B),
           "top_k": ([5, 0, 40, 1, 200, 13], [1.0] * B),
           "top_p": ([0] * B, [0.9, 0.5, 1.0, 0.1, 0.99, 0.7]),
           "both": ([5, 0, 40, 1, 200, 13], [0.9, 0.5, 1.0, 0.1, 0.99, 0.7])}


@pytest.mark.parametrize("filt", list(FILTERS))
@pytest.mark.parametrize("batch", list(TEMPS))
def test_sample_core_reads_what_it_read_before_the_branch(batch, filt):
    temp = jnp.asarray(TEMPS[batch], jnp.float32)
    top_k = jnp.asarray(FILTERS[filt][0], jnp.int32)
    top_p = jnp.asarray(FILTERS[filt][1], jnp.float32)
    for seed in range(4):
        logits = 3.0 * jax.random.normal(jax.random.PRNGKey(100 + seed),
                                         (B, V), jnp.float32)
        key = jax.random.PRNGKey(seed)
        want = np.asarray(_before_the_branch(logits, key, temp, top_k, top_p))
        got = sample_tokens(logits, key, temp, top_k, top_p)
        assert got.dtype == jnp.int32
        assert np.array_equal(np.asarray(got), want), (batch, filt, seed)
        if batch == "greedy":  # bit for bit the argmax, whatever the filters
            assert np.array_equal(want, np.argmax(np.asarray(logits), -1))
        bias = jnp.zeros((B, V), jnp.float32).at[:, 7].set(-1e9).at[2, 9].set(4.0)
        assert np.array_equal(
            np.asarray(sample_tokens_biased(logits, bias, key, temp, top_k,
                                            top_p)),
            np.asarray(_before_the_branch(logits + bias, key, temp, top_k,
                                          top_p)))


def test_the_sampler_is_one_program_with_a_branch():
    """Greedy, mixed and sampling batches of one shape share one compiled
    program, and its argmax side holds no top-k."""
    logits = jnp.zeros((B, V), jnp.float32)
    key = jax.random.PRNGKey(0)
    args = [(jnp.asarray(TEMPS[b], jnp.float32), jnp.zeros((B,), jnp.int32),
             jnp.ones((B,), jnp.float32)) for b in TEMPS]
    sample_tokens(logits, key, *args[0]).block_until_ready()
    n = sample_tokens._cache_size()
    for a in args[1:]:
        sample_tokens(logits, key, *a).block_until_ready()
    assert sample_tokens._cache_size() == n
    jaxpr = jax.make_jaxpr(sample_tokens)(logits, key, *args[0])
    conds = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].jaxpr.eqns
             if e.primitive.name == "cond"]
    assert len(conds) == 1
    sides = ["top_k" in str(br) for br in conds[0].params["branches"]]
    assert sorted(sides) == [False, True]


# ------------------------------------------------ the engine against itself

def _tp4_model():
    return dataclasses.replace(get_model_config("tiny"), num_heads=8,
                               num_kv_heads=4)


def _engine(mesh: bool = False, seed: int = 0, **kw) -> LLMEngine:
    if mesh:  # the CPU's virtual devices: a 1x1x1x4 mesh, tensor-parallel
        return LLMEngine(_tp4_model(), EngineConfig(
            **{**BASE, **kw}, mesh=MeshConfig(tp=4), kv_layout="padded"),
            seed=seed)
    return LLMEngine(get_model_config("tiny"), EngineConfig(**{**BASE, **kw}),
                     seed=seed)


def _traffic(kind: str, n_out: int = 9) -> dict:
    """One request every other step. ``mixed``: every second one samples;
    ``late``: four greedy requests are running or done when the first
    sampling one arrives."""
    g = SamplingParams(max_tokens=n_out, **GREEDY)
    s = SamplingParams(max_tokens=n_out, **SAMPLED)
    samples = {"greedy": lambda i: False, "sampled": lambda i: True,
               "mixed": lambda i: i % 2 == 1, "late": lambda i: i >= 4}[kind]
    return {2 * i: [(f"r{i}", p, s if samples(i) else g)]
            for i, p in enumerate(PROMPTS)}


def _paths(eng: LLMEngine, program: str) -> dict:
    return {p: _count(eng, "sampler_steps_total",
                      f'program="{program}",path="{p}"')
            for p in ("argmax", "topk", "biased")}


def _splits(seed: int, n: int):
    key = jax.random.PRNGKey(seed)
    for _ in range(n):
        key, _ = jax.random.split(key)
    return np.asarray(key)


@pytest.mark.parametrize("mesh", [False, True], ids=["one-device", "tp4"])
@pytest.mark.parametrize("kind", ["greedy", "sampled", "mixed", "late"])
def test_tokens_are_those_of_a_run_read_after_every_step(kind, mesh):
    eng, oracle = _engine(mesh, seed=5), _engine(mesh, seed=5)
    got = drive(eng, arrivals=_traffic(kind))
    assert got == drive(oracle, oracle=True, arrivals=_traffic(kind))
    assert len(got) == len(PROMPTS) and all(len(v) == 9 for v in got.values())
    assert _ahead(eng)["device"] > 0 and _ahead(oracle)["device"] == 0
    # no second program, whatever the rows ask for, and nothing left unread
    assert "sample" not in eng.programs.counters()
    assert _count(eng, "engine_program_dispatches_total", 'program="sample"') == 0
    uni, dec = _paths(eng, "unified"), _paths(eng, "decode")
    assert uni["argmax"] + uni["topk"] == eng.stats.n_unified_steps
    assert uni["biased"] == dec["biased"] == 0
    assert (dec["argmax"] + dec["topk"]
            == BASE["decode_steps"] * eng.stats.n_decode_dispatches)
    if kind == "greedy":
        assert uni["topk"] == dec["topk"] == 0
    else:  # (a step of prefill chunks alone picks nothing: argmax)
        assert uni["topk"] > 0 and uni["argmax"] > 0
    # the key moved once for each unified step that sampled and once for
    # each fused call, as it always has: so the seeded tokens are the same
    assert np.array_equal(
        np.asarray(eng._key),
        _splits(5, int(uni["topk"]) + eng.stats.n_decode_dispatches))


@pytest.mark.parametrize("mesh", [False, True], ids=["one-device", "tp4"])
def test_the_first_sampling_row_compiles_nothing(mesh):
    """Greedy traffic warms the engine; a sampling request that arrives then
    finds every program it needs: the step programs take their other branch,
    and the key split was compiled when the engine was made."""
    eng = _engine(mesh)
    g = SamplingParams(max_tokens=12, **GREEDY)
    drive(eng, arrivals={0: [("g0", PROMPTS[0], g)], 2: [("g1", PROMPTS[1], g)]})
    assert eng.stats.n_decode_calls > 0 and eng.stats.n_unified_steps > 0
    if mesh:
        # under a mesh a step program compiles a second time once its cache
        # argument is its own output (PERF.md section 7): let that happen
        drive(eng, arrivals={0: [("g2", PROMPTS[2], g)],
                             2: [("g3", PROMPTS[3], g)]})
    before = (_count(eng, "xla_compiles_total", ""),
              dict(eng.programs.compile_counts()))
    assert _paths(eng, "unified")["topk"] == 0
    drive(eng, arrivals={0: [("g4", PROMPTS[4], g)],
                         3: [("s0", PROMPTS[5], SamplingParams(
                             max_tokens=12, **SAMPLED))]})
    assert _paths(eng, "unified")["topk"] > 0
    assert _paths(eng, "decode")["topk"] > 0
    assert (_count(eng, "xla_compiles_total", ""),
            eng.programs.compile_counts()) == before


def test_a_greedy_step_sends_no_sampling_state_and_splits_no_key():
    eng = _engine()
    state, samples = eng._sampling_state([])
    assert state is eng._greedy_state and not samples
    key0 = np.asarray(eng._key)
    seen = []
    real = eng._unified_fn

    def spy(*args):  # temperature, top_k, top_p, key follow prev_sampled
        seen.append(args[11:15])
        return real(*args)

    eng._unified_fn = spy
    eng.add_request("g", PROMPTS[0], SamplingParams(max_tokens=3, **GREEDY))
    while eng.stats.n_unified_steps < 3:
        eng.step()
    assert all(a is b for args in seen for a, b in zip(args, eng._greedy_state))
    assert np.array_equal(np.asarray(eng._key), key0)
    eng.add_request("s", PROMPTS[3], SamplingParams(max_tokens=3, **SAMPLED))
    n = len(seen)
    drive(eng)
    temp, top_k, top_p, key = seen[n]  # the step that finished s's prompt
    assert not any(a is b for a, b in zip(seen[n], eng._greedy_state))
    row = int(np.flatnonzero(np.asarray(temp))[0])
    assert np.asarray(temp)[row] == np.float32(0.8)
    assert np.asarray(top_k)[row] == 30 and top_k.dtype == jnp.int32
    assert np.asarray(top_p)[row] == np.float32(0.95)
    assert np.count_nonzero(np.asarray(temp)) == 1  # the greedy row sends 0


# ------------------------------------------------------- constrained batches

@pytest.mark.parametrize("constraint", ["logit_bias", "grammar"])
@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_a_constrained_row_still_takes_the_biased_sampler(constraint,
                                                          temperature):
    """Its tokens come from ``sample_tokens_biased`` over the step's logits,
    one second dispatch a step, and are those of the fused masked decode
    call, which applies the same bias from staged tables."""
    from tests.test_structured import CHOICES, TOK

    kw = dict(max_tokens=8, temperature=temperature)
    cons = (SamplingParams(logit_bias={7: 5.0, 9: -100.0}, ignore_eos=True,
                           **kw) if constraint == "logit_bias"
            else SamplingParams(guided_choice=CHOICES, **kw))
    free = SamplingParams(max_tokens=8, temperature=temperature,
                          ignore_eos=True)

    def run(oracle=False, **over):
        eng = LLMEngine(get_model_config("tiny"),
                        EngineConfig(**{**BASE, **over}), tokenizer=TOK, seed=3)
        arr = {0: [("c", TOK.encode("pick"), cons)],
               1: [("f0", PROMPTS[1], free)], 3: [("f1", PROMPTS[0], free)]}
        return eng, drive(eng, oracle=oracle, arrivals=arr)

    eng, got = run(structured_table_max_elems=1)  # tables too big: unified
    assert got == run(oracle=True, structured_table_max_elems=1)[1]
    uni = _paths(eng, "unified")
    c = eng.programs.counters()
    assert uni["biased"] > 0
    assert c["sample"] == (uni["biased"], uni["biased"])
    assert _count(eng, "engine_program_dispatches_total",
                  'program="sample"') == uni["biased"]
    assert sum(uni.values()) == eng.stats.n_unified_steps
    assert eng.stats.structured_violations == 0
    if constraint == "logit_bias":
        assert 9 not in got["c"]
    fused, fused_got = run()
    assert _paths(fused, "decode")["biased"] > 0
    if temperature == 0.0:  # seeded rows draw from another schedule's keys
        assert fused_got == got
