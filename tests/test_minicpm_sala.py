"""Lightning linear-attention layers and block-sparse NoPE attention layers in
one stack (ISSUE 42, MiniCPM-SALA): a matrix-state pool a seat, a
compressed-key plane beside the paged pool, selection inside paged attention
through selected page tables, held against the plain float32 reference of the
family (``perfbench/reference/hybrid_lightning_sparse.py``) at a tiny size on
the CPU, the selection's sizes shrunk so that it bites: ``dense_len`` 64,
blocks of 8, top-2, window 16, kernels of 4 every 2, pages of 2 tokens.
"""

from __future__ import annotations

import functools
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
sys.path.append(os.path.join(ROOT, "perfbench"))
try:
    from reference import hybrid_lightning_sparse as family  # noqa: E402
finally:
    sys.path.remove(os.path.join(ROOT, "perfbench"))

from llmd_tpu.core.request import SamplingParams  # noqa: E402
from llmd_tpu.engine import EngineConfig, LLMEngine  # noqa: E402
from llmd_tpu.models.transformer import (  # noqa: E402
    forward_core, init_cache, init_compressed_keys, init_params, init_state,
    ragged_paged_attention_xla, unembed)
from llmd_tpu.ops import sparse_select  # noqa: E402
from llmd_tpu.ops.lightning_attention import (  # noqa: E402
    BLOCK, head_slopes, lightning_attention_pallas, lightning_attention_xla)

SPARSE = {"kernel_size": 4, "kernel_stride": 2, "block_size": 8,
          "window_size": 16, "topk": 2, "init_blocks": 1, "dense_len": 64}
with open(os.path.join(ROOT, "perfbench", "tests", "tiny-sala.json")) as f:
    CONF = dict(json.load(f), weights={"dtype": "float32", "quantize": None},
                sparse=SPARSE)
CFG = family.model_config(CONF)
SIZES = family.sizes(CONF)
PS, T = 2, 150  # page size; a sequence well past dense_len
SEATS, ROWS, MAXP, PAGES = 4, 4, 96, 256
# larger than any chunk a hand-packed step brings (tests/test_hybrid_ssm.py
# says why: the CPU compiles the last rows of a flat batch apart)
NT = 160
# float32 on both sides: what is left is the order of the sums, and a block
# that a near tie of the selection gives to one side only. Read on the CPU
# over seeds 0-2 of weights and tokens: 2.4e-6 to 3.1e-6 on logits of standard
# deviation 0.17; the controls below read 2e-3 and more.
TOLERANCE = 5e-5


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return [int(t) for t in np.random.default_rng(0).integers(0, 288, size=T)]


@pytest.fixture(scope="module")
def want(params, tokens):
    return np.asarray(family.logits(SIZES, params, tokens))


def _pools(cfg=CFG, poison: float = 0.0):
    state = init_state(cfg, SEATS)
    return {"kv": init_cache(cfg, PAGES, PS),
            "ck": init_compressed_keys(cfg, PAGES) + poison,
            **{k: v + jnp.asarray(poison, v.dtype) for k, v in state.items()}}


def _serve(cfg, params, tokens, chunks, pools=None, slot=2, lin_impl=None):
    """One sequence through ``forward_core`` in chunks of the given sizes,
    as batch row 1 of 4 (the other rows are padding, mapped to the scratch
    slot); returns (logits of every position, pools)."""
    pools = _pools(cfg, poison=7.0) if pools is None else pools
    pt = np.full((ROWS, MAXP), -1, np.int32)
    pt[1] = np.arange(MAXP) + 5
    step = jax.jit(lambda pools, *a: forward_core(
        cfg, params, pools, *a[:5], cu_q_lens=a[5], num_seqs=a[6],
        state_slots=a[7], lin_impl=lin_impl))
    out, start = [], 0
    for n in chunks:
        toks, pos = np.zeros((NT,), np.int32), np.full((NT,), -1, np.int32)
        toks[:n], pos[:n] = tokens[start:start + n], np.arange(start, start + n)
        lens = np.ones((ROWS,), np.int32)
        lens[1] = start + n
        hidden, pools, _, _ = step(
            pools, jnp.asarray(toks), jnp.asarray(pos),
            jnp.ones((NT,), jnp.int32), jnp.asarray(pt), jnp.asarray(lens),
            jnp.asarray([0, 0, n, n, n], jnp.int32), jnp.asarray([2], jnp.int32),
            jnp.asarray([SEATS, slot, SEATS, SEATS], jnp.int32))
        out.append(np.asarray(unembed(cfg, params, hidden))[:n])
        start += n
    return np.concatenate(out), pools


def _worst(a, b) -> float:
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


# ------------------------------------------------------- (a) the family

def test_the_family_maps_the_published_keys():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "minicpm-sala-9b.json")) as f:
        conf = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
    # the cut: the published layers 13-18, and nothing else changed
    assert conf["mixer_types"] == row["config"]["mixer_types"]
    kinds = family.layer_kinds(conf)
    assert kinds == [family.KINDS[t] for t in row["config"]["mixer_types"][13:19]]
    assert (kinds.count("attention"), kinds.count("lightning")) == (2, 4)
    assert {k: v for k, v in conf.items() if k in row["config"]
            and k != "num_hidden_layers"} == {
        k: v for k, v in row["config"].items() if k != "num_hidden_layers"}
    cfg = family.model_config(conf)
    # a is the published depth's, 32, not the cut's 6
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert (cfg.embed_scale, cfg.logit_scale) == (12.0, 1 / 16)
    assert (cfg.num_layers, cfg.num_attn_layers, cfg.num_lightning_layers,
            cfg.kv_pool_folds) == (6, 2, 4, 4)
    assert cfg.layer_runs == (
        ("lightning", 0, 3), ("attention", 3, 2), ("lightning", 5, 1))
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    assert shapes["lin_wq"].shape == (4, 32 * 128, 4096)
    assert shapes["wg"].shape == (2, 4096, 32, 128)
    assert shapes["wk"].shape == (2, 4096, 2, 128)
    n = sum(int(np.prod(v.shape)) for v in shapes.values())
    # 2 x 253.8 M + 4 x 285.2 M + 601.7 M; ISSUE 42's 12 layers were 3,930 M
    assert abs(n - 2_250e6) < 2e6
    state = jax.eval_shape(lambda: init_state(cfg, 32))
    assert state["lin"].shape == (4, 33, 32, 128, 128)
    assert state["lin"].dtype == jnp.float32
    pool = jax.eval_shape(lambda: init_cache(cfg, 16, 16))
    assert pool.shape == (4 * 16, 16, 2, 128)  # a KV head's pages its own
    assert jax.eval_shape(lambda: init_compressed_keys(cfg, 16)).shape == (64, 128)
    with pytest.raises(ValueError, match="attn_use_rope"):
        family.model_config(dict(conf, attn_use_rope=True))
    with pytest.raises(ValueError, match="int8"):
        family.model_config(dict(conf, weights={"dtype": "bfloat16",
                                                "quantize": "int8"}))


def test_the_scalars_default_to_the_identity():
    from llmd_tpu.models.config import ModelConfig

    c = ModelConfig()
    assert (c.embed_scale, c.residual_scale, c.logit_scale) == (1.0, 1.0, 1.0)
    assert not (c.attn_output_gate or c.sparse_topk or c.has_lightning)
    with pytest.raises(ValueError, match="sparse_topk"):
        ModelConfig(sparse_topk=2, rope_pattern=(True,))
    with pytest.raises(ValueError, match="lightning_heads"):
        ModelConfig(layer_kinds=("attention", "lightning"))


# ------------------------------- (b) the program against the reference

@pytest.mark.parametrize("chunks", [
    [T],                       # one chunk: crosses dense_len inside it
    [48, 16, 16, 32, 38],      # chunk ends on dense_len, block and kernel ends
    [64, 1, 1, 1, 1, 1, 16, 65],   # decode rows right past dense_len
    [32, 48, 1, 64, 5],        # a chunk that starts below and ends past it
], ids=["whole", "aligned", "decode", "straddle"])
def test_chunked_prefill_and_decode_agree_with_the_reference(
        params, tokens, want, chunks):
    got, _ = _serve(CFG, params, tokens, chunks)
    assert _worst(got, want) < TOLERANCE


def test_a_chunks_tokens_do_not_depend_on_where_it_ends(params, tokens):
    """Past dense_len a chunk's queries take the block-masked call over the
    row's table, a turn of keys at a time: a turn whose keys a query does not
    see leaves its sums as they were, so a prompt's pools and logits are the
    same to the bit whatever chunks (of more than one token) brought it."""
    one, a = _serve(CFG, params, tokens, [80, T - 80])
    two, b = _serve(CFG, params, tokens, [80, 16, 32, T - 128])
    assert np.array_equal(one, two)
    for pool in ("kv", "ck", "lin"):
        assert np.array_equal(np.asarray(a[pool]), np.asarray(b[pool])), pool


def test_the_selection_bites_and_each_control_shows(params, tokens, want):
    """Dense everywhere, half the top-k, RoPE on the sparse layers, no decay
    and a bfloat16 state each move the reference's logits far past the
    tolerance: the agreement above is not one of a selection that selects
    everything."""
    for fault in ({"sparse": False}, {"topk": 1}, {"attn_rope": True},
                  {"decay": False}, {"state_dtype": "bfloat16"}):
        bad = np.asarray(family.logits(dict(SIZES, **fault), params, tokens))
        past = _worst(bad[SPARSE["dense_len"]:], want[SPARSE["dense_len"]:])
        assert past > 40 * TOLERANCE, (fault, past)
        if fault.keys() & {"sparse", "topk"}:  # below dense_len nothing moves
            assert _worst(bad[:SPARSE["dense_len"] - 1],
                          want[:SPARSE["dense_len"] - 1]) == 0.0


# ------------------------------------------- (c) the lightning recurrence

def _lin_case(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    H, D, nt = 4, 128, 96
    cu = np.array([0, 1, 1 + 2 * BLOCK + 3, 1 + 2 * BLOCK + 3, nt - 4], np.int32)
    q, k, v = (jnp.asarray(rng.normal(size=(nt, H, D)), dtype) for _ in range(3))
    pool = jnp.asarray(rng.normal(size=(6, H, D, D)), jnp.float32)
    return (q, k, v, head_slopes(H), pool, jnp.array([3, 1, 5, 0], jnp.int32),
            jnp.asarray(cu), jnp.array([True, True, False, True]),
            jnp.array([False, True, False, False]))


def test_the_blocked_kernel_equals_the_token_by_token_scan():
    """Interpret mode: blocks of 16 on the matrix unit's arithmetic against
    the ``lax.scan`` over time, rows of 1, 35 and 56 tokens, one fresh, one
    not live; a float32 operand goes in as two bfloat16 halves, so the
    agreement is 16 bits of it."""
    args = _lin_case(dtype=jnp.bfloat16)
    o1, p1 = lightning_attention_xla(*args, scale=128 ** -0.5)
    o2, p2 = lightning_attention_pallas(*args, scale=128 ** -0.5,
                                        interpret=True)
    assert _worst(o1, o2) < 2e-5 * float(np.abs(o1).max())
    assert _worst(p1, p2) < 2e-5 * float(np.abs(p1).max())
    pool = np.asarray(args[4])
    # the row that is not live and the slots no row names: bit for bit
    for s in (5, 2, 4):
        assert np.array_equal(np.asarray(p2[s]), pool[s])
    assert np.asarray(o2[int(args[6][3]):int(args[6][4])]).any()  # a live row
    assert not np.asarray(o2)[int(args[6][4]):].any()  # rows of no sequence


def test_the_kernels_blocks_are_the_prompts_own():
    """A prompt chunked at multiples of BLOCK gives the kernel the same
    blocks as the prompt whole: the state and every token's output bit for
    bit (what lets greedy tokens served alone and in a batch agree)."""
    rng = np.random.default_rng(1)
    H, D, n = 2, 128, 5 * BLOCK + 7
    q, k, v = (jnp.asarray(rng.normal(size=(n, H, D)), jnp.bfloat16)
               for _ in range(3))
    sl, pool = head_slopes(H), jnp.zeros((2, H, D, D), jnp.float32)

    def run(cuts):
        p, outs, at = pool, [], 0
        for m in cuts:
            pad = 2 * n  # one shape of call whatever the chunk
            z = lambda x: jnp.pad(x[at:at + m], ((0, pad - m), (0, 0), (0, 0)))  # noqa: E731
            o, p = lightning_attention_pallas(
                z(q), z(k), z(v), sl, p, jnp.array([1], jnp.int32),
                jnp.array([0, m], jnp.int32), jnp.array([True]),
                jnp.array([at == 0]), scale=D ** -0.5, interpret=True)
            outs.append(np.asarray(o[:m]))
            at += m
        return np.concatenate(outs), np.asarray(p)

    whole, chunked = run([n]), run([2 * BLOCK, BLOCK, 2 * BLOCK + 7])
    assert np.array_equal(whole[0], chunked[0])
    assert np.array_equal(whole[1], chunked[1])


def test_the_kernel_inside_the_whole_stack(params, tokens):
    """In the model's own type (the kernel multiplies q, k and v as bfloat16
    values, which a bfloat16 model's are): the whole stack with the kernel
    (interpret mode) against the whole stack with the scan."""
    cfg = replace(CFG, dtype="bfloat16")
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    kernel = lambda *a, **kw: lightning_attention_pallas(  # noqa: E731
        *a, **kw, interpret=True)
    chunks = [48, 16, 1, 1, 84]
    scan, p0 = _serve(cfg, low, tokens, chunks)
    got, p1 = _serve(cfg, low, tokens, chunks, lin_impl=kernel)
    # bfloat16 steps of the stream, of logits of standard deviation 0.17: a
    # last bit of a state becomes a step of the stream now and then, and a
    # sparse layer's peaked softmax (q/k gains around SPARSE_QK_GAIN) turns a
    # step of a score into another weighting of its few heavy keys (read:
    # 0.021 at the median position, 0.23 at the worst)
    assert _worst(got, scan) < 0.5
    assert float(np.median(np.abs(got - scan).max(-1))) < 0.03
    # the first lightning layer's state saw the same stream: 16 bits of it
    first = lambda p: np.asarray(p["lin"])[0, 2]  # noqa: E731
    assert _worst(first(p0), first(p1)) < 2e-5 * float(np.abs(first(p0)).max())


# --------------------------------------- (d) selection inside paged attention

def test_the_selected_tables_hold_what_the_host_books():
    cfg = CFG
    n = np.array([1, 63, 64, 65, 100, 150, 1000])
    got = sparse_select.selected_tokens(cfg, n)
    # below dense_len the keys it sees; past it init + window + top-k blocks,
    # the last cut at the query
    assert list(got[:2]) == [1, 63]
    assert list(got[2:]) == [(1 + 2 + 2 - 1) * 8 + (t % 8) + 1
                             for t in (63, 64, 99, 149, 999)]
    big = family.model_config(json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "minicpm-sala-9b.json"))))
    assert sparse_select.geometry(big, 16)["max_pages"] == 388
    assert sparse_select.selected_tokens(big, 8192) == 96 * 64 + 8191 % 64 + 1
    with pytest.raises(ValueError, match="page size"):
        sparse_select.geometry(big, 8)


@pytest.mark.parametrize("rows", ["alone", "chunk"])
def test_a_selected_pages_call_equals_the_masked_dense_call(rows):
    """No positional encoding, so attention over the compacted table is
    attention over the whole table with the unselected blocks masked: the
    same keys in the same order, bit for bit through the XLA impl, for
    queries that come a row each (decode rows). The same six queries as one
    chunk take the block-masked call over the row's whole table: the same
    attention to float32's rounding."""
    rng = np.random.default_rng(0)
    cfg = replace(CFG, head_dim=128)
    Hk, G, D, P = 2, 2, 128, 128
    n_tok, kv = 6, 139  # six queries of one sequence, all sparse
    pool = jnp.asarray(rng.normal(size=(Hk * P, PS, 2, D)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(Hk * P, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(n_tok, Hk * G, D)), jnp.float32)
    pt = np.full((n_tok if rows == "alone" else 2, 80), -1, np.int32)
    pt[0, :70] = rng.permutation(100)[:70] + 3
    pos = np.arange(kv - n_tok, kv, dtype=np.int32)
    if rows == "alone":  # six rows over one sequence's pages
        pt[:] = pt[0]
        slots, lens = np.arange(n_tok), pos + 1
        cu, n_seqs = np.arange(n_tok + 1), n_tok
    else:
        slots, lens = np.zeros(n_tok), np.asarray([kv, 1])
        cu, n_seqs = np.asarray([0, n_tok, n_tok]), 1
    got = np.asarray(sparse_select.sparse_paged_attention(
        cfg, q, pool.reshape(-1, 2, D), ck, jnp.asarray(pt), jnp.asarray(pos),
        jnp.asarray(slots, jnp.int32), jnp.asarray(lens, jnp.int32),
        jnp.asarray(cu, jnp.int32), jnp.asarray([n_seqs], jnp.int32),
        jnp.int32(0), P, PS, D ** -0.5,
        ragged_paged_attention_xla, ragged_paged_attention_xla))
    # the same selection, as a mask over the sequence's own keys
    at = (np.arange(Hk) * P)[None, :, None] + np.maximum(pt, 0)[:, None, :]
    onehot, kv_len, sparse = sparse_select.select_pages(
        cfg, q, jnp.asarray(np.asarray(ck)[at][np.zeros(n_tok, int)]),
        jnp.asarray(pos), PS, D ** -0.5)
    assert np.asarray(sparse).all()
    # the table of a sequence whose page ids are 0, 1, 2, ...: indices
    idx = np.asarray(sparse_select.table_of(onehot, jnp.broadcast_to(
        jnp.arange(80, dtype=jnp.int32), (n_tok, 80))))
    kv_len = np.asarray(kv_len)
    keys = np.asarray(pool)[:, :, 0], np.asarray(pool)[:, :, 1]
    for t in range(n_tok):
        for g in range(Hk):
            pages = idx[t, g][:-(-kv_len[t, g] // PS)]
            assert (np.diff(pages) > 0).all() and pages[-1] == pos[t] // PS
            k_sel, v_sel = (x[pt[0, pages] + g * P].reshape(-1, D)[:kv_len[t, g]]
                            for x in keys)
            s = (np.asarray(q)[t, g * G:(g + 1) * G] @ k_sel.T) * D ** -0.5
            p = np.exp(s - s.max(-1, keepdims=True))
            want = (p / p.sum(-1, keepdims=True)) @ v_sel
            assert np.abs(got[t, g * G:(g + 1) * G] - want).max() < 2e-5
    # and against the dense call over a pool whose unselected pages are
    # taken away (their keys at -inf distance): bit for bit
    blocks = 8 // PS
    for g in range(Hk):
        masked_pt = np.full((n_tok, idx.shape[-1]), -1, np.int32)
        dense_len = np.zeros((n_tok,), np.int32)
        for t in range(n_tok):
            pages = idx[t, g][:-(-kv_len[t, g] // PS)]
            masked_pt[t, :len(pages)] = pt[0, pages] + g * P
            dense_len[t] = kv_len[t, g]
        dense = np.asarray(ragged_paged_attention_xla(
            q[:, g * G:(g + 1) * G], pool, jnp.asarray(masked_pt),
            jnp.asarray(dense_len - 1), jnp.arange(n_tok, dtype=jnp.int32),
            jnp.asarray(dense_len), scale=D ** -0.5))
        if rows == "alone":
            assert np.array_equal(dense, got[:, g * G:(g + 1) * G])
        else:
            assert np.abs(dense - got[:, g * G:(g + 1) * G]).max() < 2e-5
    assert blocks == 4


def test_compressed_keys_are_written_once_and_whole(params, tokens):
    """Page j's entry appears when page j + 1 fills and is the mean of both
    pages' keys; entries of pages past the last whole kernel keep what they
    held (the poison), whatever the chunking."""
    _, a = _serve(CFG, params, tokens, [T])
    _, b = _serve(CFG, params, tokens, [33, 1, 1, 50, 65])
    assert np.array_equal(np.asarray(a["ck"]), np.asarray(b["ck"]))
    ck = np.asarray(a["ck"]).reshape(CFG.kv_pool_folds, PAGES, -1)
    written = (ck != 7.0).any(-1)
    n_kern = (T - 4) // 2 + 1  # whole kernels of 4 every 2 in T tokens
    assert written[:, 5:5 + n_kern].all() and written.sum() == 6 * n_kern
    kv = np.asarray(a["kv"]).reshape(CFG.kv_pool_folds, PAGES, PS, 2, -1)
    assert np.allclose(ck[:, 5:5 + n_kern],
                       (kv[:, 5:5 + n_kern, :, 0].mean(2)
                        + kv[:, 6:6 + n_kern, :, 0].mean(2)) / 2, atol=1e-6)


# ------------------------------------------------------- (e) the engine

def _engine(cfg=CFG, **kw) -> LLMEngine:
    base = dict(page_size=PS, num_pages=512, max_model_len=256,
                max_batch_size=4, prefill_chunk=32, decode_steps=4)
    base.update(kw)
    return LLMEngine(cfg, EngineConfig(**base), seed=3)


def _series(eng, prefix: str) -> dict:
    return {l.split(" ")[0]: float(l.split(" ")[1])
            for l in eng.metrics.registry.expose().splitlines()
            if l.startswith(prefix)}


def _deficit(eng, prompts, served) -> float:
    d = family.readings(SIZES, eng.params, prompts, served)["deficits"]
    return max(x for ds in d for x in ds)


_RNG = np.random.default_rng(5)
PROMPTS = [[int(t) for t in _RNG.integers(3, 280, size=n)]
           for n in (100, 61, 33, 140, 70)]
GREEDY = SamplingParams(max_tokens=11, temperature=0.0, ignore_eos=True)


def _served(out: dict) -> list:
    return [out[f"req-{i}"] for i in range(len(out))]


@pytest.fixture(scope="module")
def batched():
    eng = _engine()
    return eng, _served(eng.generate(PROMPTS, GREEDY))


def test_engine_in_a_batch_agrees_with_the_reference(batched):
    """Through ``LLMEngine``: five requests on four seats, prompts of two to
    five chunks of which three end past dense_len, 11 tokens each through
    unified steps and fused decode calls: every served token is the
    reference's greedy token to within the tolerance."""
    eng, served = batched
    assert all(len(s) == 11 for s in served)
    assert _deficit(eng, PROMPTS, served) < TOLERANCE
    assert eng.stats.n_decode_dispatches > 0 and eng.stats.n_unified_steps > 0
    assert eng.ssm_backend == "xla_lightning_attention"
    assert not eng.prefix_reuse


def test_engine_alone_and_in_a_batch_gives_the_same_tokens(batched):
    _, served = batched
    eng = _engine()
    alone = [eng.generate([p], GREEDY)["req-0"] for p in PROMPTS]
    assert alone == served


def test_the_fused_calls_k_steps_equal_k_single_steps(batched):
    _, served = batched
    assert _served(_engine(decode_steps=1).generate(PROMPTS, GREEDY)) == served


def test_a_prompts_chunks_start_on_a_block_of_the_recurrence():
    """Every chunk of a prompt starts on a multiple of the lightning kernel's
    block, whatever decode rows take of the step's budget: read from the
    lengths every unified step packs."""
    eng = _engine()
    rows: list = []
    book = eng._count_attn_kv
    eng._count_attn_kv = lambda prog, kv, q, **kw: (
        rows.extend(zip(np.asarray(kv) - np.asarray(q), np.asarray(q)))
        if prog == "unified" else None, book(prog, kv, q, **kw))
    eng.generate(PROMPTS[:2] + [PROMPTS[3]], GREEDY)
    chunks = [(int(s), int(n)) for s, n in rows if n > 1]
    assert chunks and all(s % BLOCK == 0 for s, _ in chunks)
    # some chunk was cut short of the budget decode rows left it
    assert any(n % BLOCK == 0 and n < 32 for _, n in chunks)


def test_no_prompt_is_left_a_last_chunk_of_one_token():
    """A token that comes alone takes the selected-table call and one in a
    chunk the block-masked call: a prompt of a block's multiple plus one is
    not cut so that its last token comes alone (its last chunk is 17)."""
    eng = _engine(prefill_chunk=2 * BLOCK)
    rows: list = []
    book = eng._count_attn_kv
    eng._count_attn_kv = lambda prog, kv, q, **kw: (
        rows.extend(zip(np.asarray(kv), np.asarray(q)))
        if prog == "unified" else None, book(prog, kv, q, **kw))
    prompt = list(PROMPTS[0][:4 * BLOCK + 1])
    eng.generate([prompt], GREEDY)
    chunks = [int(n) for kv, n in rows if kv <= len(prompt)]
    assert sum(chunks) == len(prompt) and chunks[-1] == BLOCK + 1, chunks


def test_engine_after_a_preemption_agrees_with_the_reference(batched):
    _, served = batched
    tight = _engine(num_pages=100, max_batch_size=3)
    got = _served(tight.generate(PROMPTS, GREEDY))
    assert tight.stats.total_preemptions > 0  # the point of the test
    # recomputed from position 0, from a zero state: the blocks of the
    # recurrence are then the longer prompt's, so the bits may part; the
    # reference must still choose every token
    assert _deficit(tight, PROMPTS, got) < TOLERANCE
    resets = _series(tight, "llmd_tpu:linear_state_resets_total")
    assert resets["llmd_tpu:linear_state_resets_total"] \
        == 5 + tight.stats.total_preemptions


def test_seat_reuse_leaves_no_state_behind(batched):
    """The same prompts through an engine whose pools were poisoned first
    (every slot, page and compressed key): a row starts from zero state and
    reads no key its own sequence has not written."""
    _, served = batched
    eng = _engine()
    eng.state = {k: v + jnp.asarray(3.0, v.dtype) for k, v in eng.state.items()}
    eng.cache = eng.cache + jnp.asarray(3.0, eng.cache.dtype)
    assert _served(eng.generate(PROMPTS, GREEDY)) == served
    # and again on the seats the first five left
    assert _served(eng.generate(PROMPTS, GREEDY)) == served


def test_the_counters_book_the_plan(batched):
    eng, served = batched
    lin = _series(eng, "llmd_tpu:linear_attn_tokens_total")
    n_prompt = sum(map(len, PROMPTS))
    assert lin['llmd_tpu:linear_attn_tokens_total{rows="prefill"}'] == n_prompt
    assert lin['llmd_tpu:linear_attn_tokens_total{rows="decode"}'] == \
        sum(len(s) - 1 for s in served)
    rows = _series(eng, "llmd_tpu:sparse_attn_rows_total")
    past = sum(max(0, len(p) + 10 - 63) for p in PROMPTS)
    assert rows['llmd_tpu:sparse_attn_rows_total{path="sparse"}'] == past
    assert rows['llmd_tpu:sparse_attn_rows_total{path="dense"}'] == \
        n_prompt + 50 - past
    kv = _series(eng, "llmd_tpu:attn_kv_tokens_total")
    assert 0 < kv['llmd_tpu:attn_kv_tokens_total{program="decode",layers="sparse"}'] \
        < kv['llmd_tpu:attn_kv_tokens_total{program="decode",layers="full"}']
    dec = _series(eng, "llmd_tpu:sparse_decode_kv_tokens_total")
    assert 0 < dec['llmd_tpu:sparse_decode_kv_tokens_total{tokens="held"}'] \
        < dec['llmd_tpu:sparse_decode_kv_tokens_total{tokens="context"}']
    assert _series(eng, "llmd_tpu:linear_state_slots_in_use") == {
        "llmd_tpu:linear_state_slots_in_use": 0.0}
    assert not _series(eng, "llmd_tpu:ssm_scan_tokens_total")


def test_the_pallas_recurrence_goes_where_the_pallas_attention_goes():
    assert _engine(attn_impl="pallas").ssm_backend == \
        "pallas_lightning_attention"


@pytest.mark.parametrize("name,kw", [
    ("spec_mode", dict(spec_mode="ngram")),
    ("cpu_offload_pages", dict(cpu_offload_pages=8)),
])
def test_what_a_recurrent_state_cannot_be_combined_with_is_refused(name, kw):
    with pytest.raises(ValueError, match=name):
        _engine(**kw)


# ------------------ (g) the projections read their layer's matrix in place

# ISSUE 56: the lightning layers' four input leaves are read a head apart
# from a view of the whole stack, the sparse layers' ``wo`` flat from a view
# of its stack, and the sparse layers' gate multiplies the flat rows. The same
# products over the same operands: at the tiny size, in the served precision
# (bfloat16 leaves), what a unified step and a fused decode call give equals
# what the parent gave, to the last bit.
CHANGED_LEAVES = ("lin_wq", "lin_wk", "lin_wv", "lin_wg", "wo", "wg")
SERVED = replace(CFG, dtype="bfloat16")
# sha256 of the logits of the live rows and of every pool, by (redrawn leaf,
# program). Taken on commit 10d8257 (the parent of PR 56) by running this
# file there: ``python tests/test_minicpm_sala.py`` prints the table.
PARENT_BITS = {
    ("lin_wq", "unified"): "c24f98b2faf92bab6103fbaf",
    ("lin_wq", "decode"): "f4bbd3fc7826326d0bf13327",
    ("lin_wk", "unified"): "6e9f8df64e9a48c0db39ee85",
    ("lin_wk", "decode"): "5643bf3c10689cdf390146f3",
    ("lin_wv", "unified"): "3519450dae8c1e7ef54a63dc",
    ("lin_wv", "decode"): "1095cc5d40f4aa73fe5e09e2",
    ("lin_wg", "unified"): "a81ebfd652975a84bf3d0b1e",
    ("lin_wg", "decode"): "23a18251689ebd29dd92a1d7",
    ("wo", "unified"): "7c188a5cb5a762478b0f42be",
    ("wo", "decode"): "2c915310111b919f102e719a",
    ("wg", "unified"): "aed5523ee1b3a89b48ab3fd6",
    ("wg", "decode"): "38bf0dbeda6c0124ca0bcf95",
}
PARENT_TOKENS = "58cadc95309b28ebc48e5634"


def _sha(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a)
        h.update(a.tobytes())
    return h.hexdigest()[:24]


def _flat_step(params, pools, rows, fused=False):
    """One call of ``forward_core`` as a step program packs it: ``rows`` is
    [(batch row, tokens, first position)]; a unified step names each row's
    state slot (row b seat b here), the fused decode call (``fused``) has one
    token a seat and no slots. Returns (logits of the live tokens, pools)."""
    n_tok = ROWS if fused else NT
    toks = np.zeros((n_tok,), np.int32)
    pos = np.full((n_tok,), -1, np.int32)
    seq = np.full((n_tok,), rows[-1][0], np.int32)
    pt = np.full((ROWS, MAXP), -1, np.int32)
    pt[0, :24], pt[1] = np.arange(24), 24 + np.arange(MAXP)  # (pages of its own)
    lens, cu, at = np.ones((ROWS,), np.int32), [0], 0
    for b in range(ROWS):
        for row, t, start in rows:
            if row == b:
                if fused:
                    at = b
                toks[at:at + len(t)] = t
                pos[at:at + len(t)] = np.arange(start, start + len(t))
                seq[at:at + len(t)] = b
                lens[b] = start + len(t)
                at += len(t)
        cu.append(b + 1 if fused else at)
    if fused:
        seq = np.arange(ROWS, dtype=np.int32)
    live = np.flatnonzero(pos >= 0)
    hidden, pools, _, _ = _served_core(
        params, pools, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(seq),
        jnp.asarray(pt), jnp.asarray(lens),
        cu_q_lens=jnp.asarray(cu, jnp.int32),
        num_seqs=jnp.asarray([ROWS if fused else rows[-1][0] + 1], jnp.int32),
        state_slots=None if fused else jnp.arange(ROWS, dtype=jnp.int32))
    return np.asarray(unembed(SERVED, params, hidden))[live], pools


# (the leaves are arguments: one compile a program for all six cases)
_served_core = jax.jit(functools.partial(forward_core, SERVED))


@functools.lru_cache(maxsize=None)
def _bits_with(leaf: str) -> dict:
    """{program: digest} of a served pair of sequences whose ``leaf`` was
    drawn anew (so that each case hangs on its own leaf): row 0 decodes
    beside row 1's two chunks (the second ends past ``dense_len``: the
    selection runs), then both decode in a fused call."""
    params = init_params(SERVED, jax.random.PRNGKey(0))
    w = params[leaf]
    params = dict(params, **{leaf: (
        float(jnp.std(w.astype(jnp.float32))) * jax.random.normal(
            jax.random.PRNGKey(7 + CHANGED_LEAVES.index(leaf)), w.shape,
            jnp.float32)).astype(w.dtype)})
    rng = np.random.default_rng(11)
    a = [int(t) for t in rng.integers(0, 288, size=44)]
    b = [int(t) for t in rng.integers(0, 288, size=151)]
    pools = _pools(SERVED)
    _, pools = _flat_step(params, pools, [(0, a[:40], 0)])
    l1, pools = _flat_step(params, pools, [(0, a[40:41], 40), (1, b[:96], 0)])
    l2, pools = _flat_step(params, pools, [(0, a[41:42], 41),
                                           (1, b[96:150], 96)])
    assert all(np.isfinite(x).all() and x.std() > 0.05 for x in (l1, l2))
    unified = _sha(l1, l2, *(pools[k] for k in sorted(pools)))
    l3, pools = _flat_step(params, pools, [(0, a[42:43], 42),
                                           (1, b[150:151], 150)], fused=True)
    return {"unified": unified,
            "decode": _sha(l3, *(pools[k] for k in sorted(pools)))}


@pytest.mark.parametrize("program", ["unified", "decode"])
@pytest.mark.parametrize("leaf", CHANGED_LEAVES)
def test_a_changed_leafs_new_form_gives_the_parents_bits(leaf, program):
    assert _bits_with(leaf)[program] == PARENT_BITS[leaf, program]


def _engine_bits() -> str:
    eng = _engine()
    served = _served(eng.generate(PROMPTS, GREEDY))
    pools = eng._pools()
    return _sha(np.asarray(served, np.int32),
                *(pools[k] for k in sorted(pools)))


def test_the_engines_programs_give_the_parents_tokens_and_pools():
    """Both step programs as the engine compiles and chains them (sampling
    inside): the greedy tokens of four prompts and every pool behind them."""
    assert _engine_bits() == PARENT_TOKENS


def _weight_paths(jaxpr, held=None, found=None) -> list:
    """[(sliced shape, [what stands between the slice and the product])] for
    every ``dot_general`` of a traced program that takes one layer of a
    stack (a ``dynamic_slice`` of one index of the leading axis of an array
    of rank 3 up and more than one layer), followed through calls, loops and
    branches. What may
    stand between: ``squeeze`` (the sliced axis dropped) and
    ``convert_element_type`` (an int8 leaf); a ``reshape`` or ``transpose``
    of the weight is written down as it is."""
    held = dict(held or {})  # var -> (sliced shape, path)
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        ins = [v for v in eqn.invars if not hasattr(v, "val")]
        if name == "dynamic_slice":
            src, sizes = eqn.invars[0].aval, eqn.params["slice_sizes"]
            if len(sizes) >= 3 and sizes[0] == 1 and src.shape[0] > 1 \
                    and tuple(sizes[1:]) == src.shape[1:]:
                held[eqn.outvars[0]] = (tuple(sizes), [])
            continue
        mine = [v for v in ins if v in held]
        if name == "dot_general":
            found.extend(held[v] for v in mine)
            continue
        subs = [j for j in jax.core.jaxprs_in_params(eqn.params)]
        if subs:
            for sub in subs:
                inner = getattr(sub, "jaxpr", sub)
                # a call's, a scan's and a loop body's operands are their
                # jaxpr's invars in order; a branch's stand behind the index:
                # matched from the end
                _weight_paths(inner, {
                    b: held[a] for a, b in zip(reversed(eqn.invars),
                                               reversed(inner.invars))
                    if not hasattr(a, "val") and a in held}, found)
            continue
        if mine and name in ("squeeze", "reshape", "transpose",
                             "convert_element_type"):
            shape, path = held[mine[0]]
            step = [] if name == "convert_element_type" or (
                name == "squeeze" and eqn.params["dimensions"] == (0,)) \
                else [f"{name}{eqn.outvars[0].aval.shape}"]
            held[eqn.outvars[0]] = (shape, path + step)
    return found


@pytest.mark.parametrize("variant,leaves", [
    # (preset of tests/test_step_programs.py, products of a layer's slice
    # at least: the mixer's and the feed-forward's)
    ("tiny-sala", 12), ("tiny-jamba", 8), ("tiny-nemotron-h", 5),
    ("tiny-ling", 8)])
@pytest.mark.parametrize("program", ["unified", "decode"])
def test_no_reshape_of_the_weight_between_its_slice_and_its_product(
        variant, leaves, program):
    """The traced step programs of every family that runs the hybrid stack
    (what ``jit`` lowers): a layer's matrix goes from its slice of the stack
    to its product as it is. The parent's sparse layers flattened ``wo``
    there ([H, Dh, D] -> [H * Dh, D]), and the slice ran as a copy."""
    from test_step_programs import program_call

    fn, args, kw = program_call(variant, program)
    paths = _weight_paths(fn.trace(*args, **kw).jaxpr.jaxpr)
    assert len(paths) >= leaves
    assert not {(shape, tuple(path)) for shape, path in paths if path}


if __name__ == "__main__":  # the table of the tree this file runs on
    print("PARENT_BITS = {")
    for leaf in CHANGED_LEAVES:
        for program, bits in _bits_with(leaf).items():
            print(f'    ("{leaf}", "{program}"): "{bits}",')
    print("}")
    print(f'PARENT_TOKENS = "{_engine_bits()}"')
