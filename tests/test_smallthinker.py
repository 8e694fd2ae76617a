"""Window and NoPE-full attention layers in one scanned stack, window layers
that read only their window from the paged pool, and ReGLU experts routed
from the pre-attention stream (ISSUE 32), held against the plain float32
reference of the family (``perfbench/reference/moe_swa_gqa.py``) at a tiny
size on the CPU: two periods (8 layers), 8 experts top-2, window 8, pages of
4 tokens, sequences at least three windows long.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
from dataclasses import replace

import conftest  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the family modules, by path and for the import alone: perfbench/ has a
# tests/ of its own, which must not shadow this package for the other files
sys.path.append(os.path.join(ROOT, "perfbench"))
try:
    from reference import dense_gqa, hybrid_ssm_gqa, moe_swa_gqa  # noqa: E402
finally:
    sys.path.remove(os.path.join(ROOT, "perfbench"))

from llmd_tpu.core.request import SamplingParams  # noqa: E402
from llmd_tpu.engine import EngineConfig, LLMEngine  # noqa: E402
from llmd_tpu.engine.engine import (  # noqa: E402
    attn_kv_tokens, expert_load_max_over_mean)
from llmd_tpu.models.config import ModelConfig  # noqa: E402
from llmd_tpu.models.quant import quantize_params  # noqa: E402
from llmd_tpu.models.transformer import (  # noqa: E402
    forward, forward_core, init_cache, init_params, init_state, moe_block,
    ragged_paged_attention_xla, unembed, window_view)
from llmd_tpu.ops.moe_dispatch import make_sorted_dispatch  # noqa: E402

with open(os.path.join(ROOT, "perfbench", "tests",
                       "tiny-smallthinker.json")) as f:
    CONF = dict(json.load(f), num_hidden_layers=8, sliding_window_size=8,
                weights={"dtype": "float32", "quantize": None})
CFG = moe_swa_gqa.model_config(CONF)
SIZES = moe_swa_gqa.sizes(CONF)
PS, T = 4, 40  # page size; a sequence of five windows
# float32 on both sides: what is left is the order of the sums. Read on the
# CPU over three seeds of weights and tokens: 4.1e-6 to 5.2e-6, on logits of
# standard deviation 0.97. The control, the same stack with only the expert
# banks rounded to bfloat16 (bf16 expert arithmetic under a float32 name),
# reads 0.45 to 1.2 at its worst position (a rounding that moves a router's
# near tie changes an expert, which moves that position's logits as far as
# any fault); the four named faults read 2.3 to 4.6. The limit stands 19
# times above the sound readings and 4,500 times below the control.
TOLERANCE = 1e-4
SORTED = make_sorted_dispatch()  # drop-free, as the engine serves


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, CFG.vocab_size, T)


@pytest.fixture(scope="module")
def want(params, tokens):
    return np.asarray(moe_swa_gqa.logits(SIZES, params, list(tokens)))


def _tables(n_pages=10, first=3, width=16):
    pt = np.full((1, width), -1, np.int32)
    pt[0, :n_pages] = np.arange(n_pages) + first
    return jnp.asarray(pt)


def _forward(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        got, _, _ = forward(cfg, params, init_cache(cfg, 32, PS),
                            jnp.asarray(tokens)[None],
                            jnp.arange(len(tokens))[None], _tables(),
                            jnp.asarray([len(tokens)]),
                            moe_dispatch_impl=SORTED)
    return np.asarray(got[0])


def _worst(a, b) -> float:
    return float(np.max(np.abs(a - b)))


# ------------------------------------------------- (a) forward vs reference

def test_the_family_maps_the_published_layouts():
    assert CFG.attn_window_pattern == (0, 8, 8, 8)
    assert CFG.rope_pattern == (False, True, True, True)
    assert CFG.layer_period == 4 and CFG.num_layers == 8
    assert CFG.moe_activation == "relu"
    assert CFG.moe_router_input == "attn_norm"
    assert SIZES["kinds"] == [(0, False), (8, True), (8, True), (8, True)] * 2


def test_forward_agrees_with_the_reference(params, tokens, want):
    assert _worst(_forward(CFG, params, tokens), want) < TOLERANCE


def test_bf16_expert_banks_fail_the_tolerance(params, tokens, want):
    low = dict(params, **{k: params[k].astype(jnp.bfloat16).astype(jnp.float32)
                          for k in ("moe_wi", "moe_wo")})
    assert _worst(_forward(CFG, low, tokens), want) > 20 * TOLERANCE


@pytest.mark.parametrize("fault", [
    {"attn_window_pattern": (0, 0, 0, 0)},          # the window left out
    {"rope_pattern": (True, True, True, True)},     # RoPE on the full layer
    {"moe_activation": "silu"},                     # SwiGLU for ReGLU
    {"moe_router_input": "mlp_norm"},               # the late router
], ids=lambda f: next(iter(f)))
def test_each_named_fault_fails_the_comparison(fault, params, tokens, want):
    assert _worst(_forward(replace(CFG, **fault), params, tokens),
                  want) > 1000 * TOLERANCE


@pytest.mark.parametrize("fault", [
    {"kinds": [(0, r) for _, r in SIZES["kinds"]]},
    {"kinds": [(w, True) for w, _ in SIZES["kinds"]]},
    {"act": "silu"}, {"early_router": False},
], ids=lambda f: next(iter(f)))
def test_the_reference_has_each_mechanism_too(fault, params, tokens, want):
    """The reference computes the mechanism and does not just carry the key:
    the same fault in the reference moves ITS logits as far."""
    other = moe_swa_gqa.logits(dict(SIZES, **fault), params, list(tokens))
    assert _worst(np.asarray(other), want) > 1000 * TOLERANCE


# ------------------------------- (b) chunked prefill, decode, the page tables

CHUNKS = [(0, 16), (16, 29), (29, 30), (30, 37), (37, 38), (38, 39), (39, 40)]


def test_chunked_prefill_then_decode_through_the_page_tables(params, tokens,
                                                              want):
    """Chunks that straddle the window's edge, then single decode rows, every
    call with ``cu_q_lens`` (the window layers' shifted page tables), against
    the reference's one full forward."""
    cache, outs = init_cache(CFG, 32, PS), []
    with jax.default_matmul_precision("highest"):
        for s, e in CHUNKS:
            h, cache, cnt, _ = forward_core(
                CFG, params, cache, jnp.asarray(tokens[s:e]),
                jnp.arange(s, e), jnp.zeros(e - s, jnp.int32), _tables(),
                jnp.asarray([e]), cu_q_lens=jnp.asarray([0, e - s]),
                num_seqs=jnp.asarray([1]), moe_dispatch_impl=SORTED)
            outs.append(unembed(CFG, params, h))
    assert cnt.shape == (8, 8) and int(cnt.sum()) == 8 * 2  # [L, E], top-2
    assert _worst(np.asarray(jnp.concatenate(outs)), want) < TOLERANCE


@pytest.mark.parametrize("quantize", [False, True], ids=["float", "int8"])
def test_banks_read_out_of_the_stack_equal_banks_sliced_by_the_scan(
        quantize, params, tokens):
    """The sorted local dispatch is handed every layer's expert bank as one
    stack and the layer's slot offset; a dispatch that does not say it takes
    them so gets a layer's bank sliced by the scan, as before. Same numbers."""
    stack = quantize_params(CFG, params)[0] if quantize else params

    def sliced(*a, **kw):  # the same dispatch, without ``stacked_banks``
        return SORTED(*a, **kw)

    assert SORTED.stacked_banks and not hasattr(sliced, "stacked_banks")
    got = []
    for impl in (SORTED, sliced):
        h, _, cnt, _ = forward_core(
            CFG, stack, init_cache(CFG, 32, PS), jnp.asarray(tokens),
            jnp.arange(T), jnp.zeros(T, jnp.int32), _tables(),
            jnp.asarray([T]), cu_q_lens=jnp.asarray([0, T]),
            num_seqs=jnp.asarray([1]), moe_dispatch_impl=impl)
        got.append((np.asarray(h), np.asarray(cnt)))
    assert np.array_equal(got[0][0], got[1][0])
    assert np.array_equal(got[0][1], got[1][1])


def _engine(**kw) -> LLMEngine:
    base = dict(page_size=PS, num_pages=64, max_model_len=64,
                max_batch_size=4, prefill_chunk=16, decode_steps=4)
    base.update(kw)
    return LLMEngine(CFG, EngineConfig(**base), seed=3)


def _deficits(eng, prompts, served) -> float:
    d = moe_swa_gqa.readings(SIZES, eng.params, prompts, served)["deficits"]
    return max(x for ds in d for x in ds)


PROMPTS = [list(range(5, 31)), list(range(5, 31)) + list(range(90, 101)),
           list(range(140, 165))]
GREEDY = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)


def test_engine_cold_and_from_the_prefix_cache_agrees_with_the_reference():
    """Through ``LLMEngine``: unified steps, the fused decode call and the
    prefix cache. Every served token, teacher-forced through the reference,
    is the reference's greedy token to within the tolerance; contexts run
    from 25 to 49 tokens against a window of 8."""
    eng = _engine()
    cold = eng.generate(PROMPTS, GREEDY)
    served = [cold[f"req-{i}"] for i in range(len(PROMPTS))]
    assert all(len(s) == 12 for s in served)
    assert _deficits(eng, PROMPTS, served) < TOLERANCE
    hits = eng._prefix_cached_total
    warm = eng.generate(PROMPTS, GREEDY)
    assert [warm[f"req-{i}"] for i in range(len(PROMPTS))] == served
    assert eng._prefix_cached_total >= hits + 3 * 24  # whole pages, cached
    geometry = [k for k in eng.metrics.registry.expose().splitlines()
                if k.startswith("llmd_tpu:engine_attn_backend{")]
    assert geometry and "window=0,8,8,8" in geometry[0]


def test_engine_after_a_preemption_agrees_with_the_reference():
    tight = _engine(num_pages=24, max_batch_size=3,
                    enable_prefix_caching=False)
    got = tight.generate(PROMPTS, GREEDY)
    assert tight.stats.total_preemptions > 0  # the point of the test
    served = [got[f"req-{i}"] for i in range(len(PROMPTS))]
    assert all(len(s) == 12 for s in served)
    assert _deficits(tight, PROMPTS, served) < TOLERANCE


# ---------------------------------- (c) shifted page tables == masked only

def _attention_case(q_lens, kv_lens, seed=0):
    """A pool with random keys and values, and a flat batch whose sequence i
    has ``kv_lens[i]`` tokens resident and brings its last ``q_lens[i]``."""
    rng = np.random.default_rng(seed)
    H, Hk, D, maxp, P = 4, 2, 128, 16, 80
    B, N = len(q_lens), int(sum(q_lens))
    cache = jnp.asarray(rng.normal(size=(P, PS, 2 * Hk, D)), jnp.float32)
    pt = np.full((B, maxp), -1, np.int32)
    free, off = rng.permutation(P), 0
    for i, n in enumerate(-(-np.asarray(kv_lens) // PS)):
        pt[i, :n] = free[off:off + n]
        off += n
    pos = np.concatenate([np.arange(k - q, k) for q, k in zip(q_lens, kv_lens)])
    slot = np.concatenate([np.full(q, i) for i, q in enumerate(q_lens)])
    cu = np.concatenate([[0], np.cumsum(q_lens)])
    q = jnp.asarray(rng.normal(size=(N, H, D)), jnp.float32)
    return dict(q=q, cache=cache, pt=jnp.asarray(pt), pos=jnp.asarray(pos),
                slot=jnp.asarray(slot), lens=jnp.asarray(kv_lens, jnp.int32),
                cu=jnp.asarray(cu, jnp.int32))


def _attend(c, window, shifted, cache=None):
    """The XLA impl: with ``cu_q_lens`` it reads a window layer's pool
    through ``window_view``, without it the window is a mask alone."""
    return np.asarray(ragged_paged_attention_xla(
        c["q"], c["cache"] if cache is None else cache, c["pt"], c["pos"],
        c["slot"], c["lens"], scale=128 ** -0.5,
        cu_q_lens=c["cu"] if shifted else None,
        **({"sliding_window": window} if window else {})))


@pytest.mark.parametrize("q_lens,kv_lens", [
    ([1, 1, 1, 1], [40, 9, 8, 3]),      # decode rows, past and inside the window
    ([13, 1, 6], [29, 33, 6]),          # a chunk that straddles the window's edge
], ids=["decode_rows", "prefill_chunk"])
def test_shifted_page_tables_equal_the_masked_only_call(q_lens, kv_lens):
    c = _attention_case(q_lens, kv_lens)
    pt, lens, off = window_view(c["pt"], c["lens"], c["cu"], 8, PS)
    # whole pages before the earliest query's window are gone, no others
    first = np.maximum(np.asarray(kv_lens) - np.asarray(q_lens) - 8 + 1, 0) // PS
    assert list(np.asarray(off)) == list(first * PS) and first.max() > 0
    assert list(np.asarray(lens)) == list(np.asarray(kv_lens) - first * PS)
    assert np.array_equal(_attend(c, 8, shifted=True),
                          _attend(c, 8, shifted=False))
    # rounded down to a kernel's KV block of 4 pages: never more pages gone,
    # and whole blocks only
    _, lens4, off4 = window_view(c["pt"], c["lens"], c["cu"], 8, PS,
                                 align_pages=4)
    assert list(np.asarray(off4)) == list(first // 4 * 4 * PS)
    assert list(np.asarray(lens4)) == list(np.asarray(kv_lens) - first // 4 * 4 * PS)


def test_a_key_outside_the_window_moves_a_full_layer_only():
    c = _attention_case([1, 1], [40, 21])
    # sequence 0's token 5: 34 positions before its one query at 39
    page, at = int(c["pt"][0, 5 // PS]), 5 % PS
    moved = c["cache"].at[page, at].add(3.0)
    for shifted in (True, False):
        assert np.array_equal(_attend(c, 8, shifted),
                              _attend(c, 8, shifted, cache=moved))
    full, full_moved = _attend(c, 0, False), _attend(c, 0, False, cache=moved)
    assert np.abs(full[0] - full_moved[0]).max() > 1e-3
    assert np.array_equal(full[1], full_moved[1])  # another sequence's page


# ------------------------------------------------------------- (d) routing

def test_top_k_then_softmax_is_softmax_top_k_renormalised():
    """The published order (``moe_swa_gqa.route``) against the program's
    (``moe_block``: softmax over all experts, top-k, renormalise)."""
    rng = np.random.default_rng(4)
    t, d, e, k, f = 64, 32, 64, 6, 16
    cfg = ModelConfig(hidden_size=d, moe_num_experts=e, moe_top_k=k,
                      moe_intermediate_size=f, dtype="float32",
                      moe_activation="relu")
    x, router = (jnp.asarray(rng.normal(size=s), jnp.float32)
                 for s in ((t, d), (d, e)))
    g = x @ router
    share = np.asarray(moe_swa_gqa.route(g, k))
    p = np.asarray(jax.nn.softmax(g, axis=-1))
    top = np.argsort(-p, axis=-1)[:, :k]
    want = np.zeros_like(p)
    np.put_along_axis(want, top, np.take_along_axis(p, top, -1)
                      / np.take_along_axis(p, top, -1).sum(-1, keepdims=True), -1)
    assert np.abs(share - want).max() < 1e-6
    assert np.allclose(share.sum(-1), 1.0, atol=1e-6)
    # and through moe_block itself: an identity-like expert bank makes the
    # block's output the weights' sum, so the counts are what is compared
    wi = jnp.asarray(rng.normal(size=(e, d, 2 * f)), jnp.float32)
    wo = jnp.asarray(rng.normal(size=(e, f, d)), jnp.float32)
    _, counts = moe_block(cfg, x, router, wi, wo, dispatch_impl=SORTED)
    assert list(np.asarray(counts)) == list((share > 0).sum(0))


# ------------------------------------------------------ (e) the counters

def test_attn_kv_tokens_against_hand_counts():
    # window 8, page 4: a decode row at kv_len 40 is given pages from
    # (40 - 1 - 8 + 1) // 4 = 8 on: 40 - 32 = 8 tokens; at kv_len 9 all 9;
    # a 13-token chunk ending at 29 from (29 - 13 - 8 + 1) // 4 = 2: 21
    assert attn_kv_tokens(CFG, [40, 9], [1, 1], PS) == {
        "full": 49, "window": 8 + 9}
    assert attn_kv_tokens(CFG, [29], [13], PS) == {"full": 29, "window": 21}
    # a backend whose KV block is 4 pages hands whole blocks: 8 pages -> 8,
    # 2 pages -> 0
    assert attn_kv_tokens(CFG, [40, 29], [1, 13], PS, align_pages=4) == {
        "full": 69, "window": 8 + 29}
    dense = ModelConfig()
    assert attn_kv_tokens(dense, [40, 9], [1, 1], PS) == {"full": 49}
    only = replace(CFG, attn_window_pattern=(8, 8, 8, 8))
    assert attn_kv_tokens(only, [40], [1], PS) == {"window": 8}


def test_expert_load_max_over_mean_against_hand_counts():
    cnt = np.array([[4, 0, 2, 2], [1, 1, 1, 1], [0, 0, 0, 0]])
    # layer 0: 4 / 2 = 2.0; layer 1: 1.0; layer 2 routed nothing
    assert expert_load_max_over_mean(cnt) == pytest.approx(1.5)
    assert expert_load_max_over_mean(np.zeros((2, 4), int)) is None


def _series(eng, name):
    return {line.split(" ")[0]: float(line.split(" ")[1])
            for line in eng.metrics.registry.expose().splitlines()
            if line.startswith(name)}


def test_the_engine_feeds_both_kinds_and_a_dense_model_full_only():
    eng = _engine()
    eng.generate([list(range(5, 45))], SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True))
    kv = _series(eng, "llmd_tpu:attn_kv_tokens_total")
    full = sum(v for k, v in kv.items() if 'layers="full"' in k)
    window = sum(v for k, v in kv.items() if 'layers="window"' in k)
    read = sum(_series(eng, "llmd_tpu:program_kv_read_tokens_total").values())
    assert full == read > 0          # a full layer is given what was counted
    assert 0 < window < 0.8 * full   # a window layer less
    load = _series(eng, "llmd_tpu:moe_expert_load_max_over_mean")
    assert 1.0 <= next(iter(load.values())) <= 8.0
    from llmd_tpu.models import get_model_config

    dense = LLMEngine(get_model_config("tiny"), EngineConfig(
        page_size=8, num_pages=64, max_model_len=128, max_batch_size=2,
        prefill_chunk=32, decode_steps=4))
    dense.generate([list(range(3, 40))], SamplingParams(
        max_tokens=5, temperature=0.0, ignore_eos=True))
    kv = _series(dense, "llmd_tpu:attn_kv_tokens_total")
    assert kv and all('layers="full"' in k for k in kv)
    assert "window" not in dense.attn_geometry


# ------------------------------------------ (f) what model_config refuses

@pytest.mark.parametrize("key,value", [
    ("moe_primary_router_apply_softmax", False),
    ("norm_topk_prob", False),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("attention_bias", True),
    ("num_hidden_layers", 6),                       # a period and a half
    ("rope_layout", [0, 1, 1, 1]),                  # lists of unequal length
    ("sliding_window_layout", [0, 1, 1, 1, 0, 1]),  # shorter than the depth
])
def test_model_config_refuses_by_the_keys_name(key, value):
    with pytest.raises(ValueError, match=key if key != "num_hidden_layers"
                       else "num_hidden_layers=6"):
        moe_swa_gqa.model_config(dict(CONF, **{key: value}))


def test_model_config_refuses_a_pattern_that_does_not_divide_the_depth():
    with pytest.raises(ValueError, match="attn_window_pattern"):
        ModelConfig(num_layers=6, attn_window_pattern=(0, 8, 8, 8),
                    rope_pattern=(False, True, True, True))
    with pytest.raises(ValueError, match="moe_activation"):
        ModelConfig(moe_activation="gelu")


# --------------------------- (g) the accepted configurations' step programs

# sha256 of forward_core's StableHLO, lowered as below. The two dense
# configurations' at b860fb4 (the tree before ISSUE 32): the scan over periods,
# the window view, the early router and the activation's name left a model
# with one kind of layer the program it had, operation for operation. All
# three at f0f0e05 (the tree before ISSUE 34, where the dense two still read
# the same): layer kinds of unequal parameter shapes, the recurrent-state
# pool and the per-kind stacked leaves leave a model without recurrent layers
# the program it had (SmallThinker through the sorted dispatch, as served).
# jamba2-3b at 52c1282 (the tree before ISSUE 35, where the other three still
# read the same): the grouped GEMM's turned grid and the padding blocks'
# operands live inside the Pallas call's wrapper, which no model without a
# mixture layer reaches and SmallThinker reaches on the TPU only (here it
# lowers through `_experts_xla`, whose plan `_row_plan` lays out as before).
# jamba2-3b again at ISSUE 40's tree, which moves that program on purpose: the
# conv window's pool as planes [Lm, K - 1, S, Di] (`conv_window`); the other
# three read as they did, and hold a change meant for a model with recurrent
# layers to that model.
PARENT_STABLEHLO = {
    "jamba2-3b":
        "98101b63b2aa04bc502040c6c748472fa54a63c6e02560bb648a365a30bc40e3",
    "qwen2.5-1.5b":
        "013a1476f5f5b99c3751ef04ac296a74c38b91a40b4de08b9dc62eacaeeb240c",
    "mistral-7b-v0.3":
        "d535f1c25e0d0d9aa4b104e5d944b5bf609b824cad6cb9392b4549e64ceada3b",
    "smallthinker-21b-a3b":
        "2e869e7d691ce7419c11a82a57c83b30ff71cc2dcdee0eca067adf5eb9e44e49",
}


@pytest.mark.parametrize("name", sorted(PARENT_STABLEHLO))
def test_accepted_configurations_lower_to_the_stablehlo_they_had(name):
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        conf = json.load(f)
    family = {"dense_gqa": dense_gqa, "moe_swa_gqa": moe_swa_gqa,
              "hybrid_ssm_gqa": hybrid_ssm_gqa}[conf["reference"]]
    cfg = family.model_config(conf)

    def make(key):
        p = init_params(cfg, key)
        return quantize_params(cfg, p)[0] \
            if conf["weights"]["quantize"] == "int8" else p

    params = jax.eval_shape(make, jax.random.key(0))
    N, B, maxp = 256, 64, 32
    cache = jax.eval_shape(lambda: init_cache(cfg, 64, 16))
    kw = {"moe_dispatch_impl": SORTED} if cfg.is_moe else {}
    if cfg.has_recurrent:  # the state pool beside the KV pool, a slot a row
        cache = {"kv": cache, **jax.eval_shape(lambda: init_state(cfg, B))}
        kw["state_slots"] = jnp.arange(B, dtype=jnp.int32)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def step(params, cache, tokens, positions, seq_slots, pt, lens, cu, ns):
        return forward_core(cfg, params, cache, tokens, positions, seq_slots,
                            pt, lens, cu_q_lens=cu, num_seqs=ns, **kw)

    text = jax.jit(step).lower(params, cache, i32(N), i32(N), i32(N),
                               i32(B, maxp), i32(B), i32(B + 1),
                               i32(1)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STABLEHLO[name]
