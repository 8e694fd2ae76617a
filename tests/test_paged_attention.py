"""Paged-attention block-size rule and the b128 cost-scaling regression.

Two layers pinned here:

1. `pick_block_sizes`: a function of the call's static shapes (the head
   layout among them: query heads a KV head) and of nothing else, not the
   environment; and the geometry the engine reports is the one its programs
   trace, with one KV block for all of them.
2. The int8-b128 regression from the r05 campaign: per-step fused-decode cost
   must grow at most ~linearly from b64 to b128 on the CPU mesh, and the
   decode program must not recompile per step. The on-chip b128 timeout was
   fabric death mid-point (PERF.md Round 6), not code; this test keeps it
   that way — a quadratic host-pack or a shape-keyed recompile storm would
   blow the bound immediately.
"""

from __future__ import annotations

import time

import conftest  # noqa: F401

import pytest

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.models import get_model_config
from llmd_tpu.ops.paged_attention import (
    call_geometry, pick_block_sizes, window_align_pages)

# ------------------------------------------------------------------ the rule

# (query heads, lane-padded width, combined KV planes of the pool)
QWEN, MISTRAL, LLAMA_PACKED, MLA_LATENT = (
    (12, 128, 4), (32, 128, 16), (32, 128, 8), (4, 128, 1))
# 28/4 heads: seven query heads a KV head, the one odd ratio of the cells'
SMALLTHINKER = (28, 128, 8)


@pytest.mark.parametrize("n,page_size,pages,layout,want", [
    # the benchmark's cells: fused decode (N = 64 seats) and the unified step
    # (N = 256 tokens) of Qwen2.5-1.5B (4,096 tokens: 256 pages a sequence)
    # and Mistral-7B (8,192: 512), each layout at both lengths
    (64, 16, 256, QWEN, (32, 8)),
    (256, 16, 256, QWEN, (32, 16)),
    (64, 16, 512, QWEN, (32, 8)),
    (256, 16, 512, QWEN, (32, 16)),
    (64, 16, 256, MISTRAL, (32, 8)),
    (256, 16, 256, MISTRAL, (32, 16)),
    (64, 16, 512, MISTRAL, (32, 8)),
    (256, 16, 512, MISTRAL, (32, 16)),
    # llama-1b as chip_smoke.py serves it: packed two heads to a lane row,
    # 1,024-token model length
    (64, 16, 64, LLAMA_PACKED, (32, 8)),
    (256, 16, 64, LLAMA_PACKED, (32, 16)),
    # the single-plane latent pool: one head that four query heads share
    (64, 16, 256, MLA_LATENT, (32, 8)),
    (256, 16, 256, MLA_LATENT, (32, 16)),
    # a short model length is one block a sequence: bkv clamps to the budget
    (64, 16, 4, QWEN, (4, 8)),
    (256, 16, 20, MISTRAL, (20, 16)),
    (128, 16, 8, LLAMA_PACKED, (8, 8)),
    (128, 16, 2, LLAMA_PACKED, (2, 8)),
    (1024, 16, 8, MISTRAL, (8, 64)),
    # 512 tokens a block whatever the page size, never more than 32 pages
    (64, 64, 8, QWEN, (8, 8)),
    (64, 128, 64, QWEN, (4, 8)),
    (64, 8, 512, QWEN, (32, 8)),
    (128, 32, 8, LLAMA_PACKED, (8, 8)),
    (64, 32, 256, MISTRAL, (16, 8)),
    (256, 32, 128, QWEN, (16, 16)),
    # the edges of the query-row bands; fewer tokens than a query block; a
    # prefill budget past the swept shapes
    (128, 16, 256, MISTRAL, (32, 8)),
    (129, 16, 256, MISTRAL, (32, 16)),
    (512, 16, 256, QWEN, (32, 16)),
    (4, 16, 256, QWEN, (32, 4)),
    (1024, 16, 128, MISTRAL, (32, 64)),
    # SmallThinker (16,384 tokens: 1,024 pages a sequence): an odd number of
    # query heads a KV head takes blocks of twice the pages and half the
    # query rows in both programs; a short model length clamps its bkv too
    (64, 16, 1024, SMALLTHINKER, (64, 4)),
    (256, 16, 1024, SMALLTHINKER, (64, 8)),
    (64, 16, 40, SMALLTHINKER, (40, 4)),
    (256, 16, 8, SMALLTHINKER, (8, 8)),
    (64, 32, 1024, SMALLTHINKER, (32, 4)),
    (128, 16, 1024, SMALLTHINKER, (64, 4)),
    (129, 16, 1024, SMALLTHINKER, (64, 8)),
    (512, 16, 1024, SMALLTHINKER, (64, 8)),
    (4, 16, 1024, SMALLTHINKER, (64, 4)),
    (1024, 16, 1024, SMALLTHINKER, (64, 64)),
])
def test_rule_at_served_shapes(n, page_size, pages, layout, want):
    heads, width, planes = layout
    q_shape, cache_shape = (n, heads, width), (4096, page_size, planes, width)
    # what a step program traces with and the engine reports
    bkv, bq = call_geometry(q_shape, cache_shape, pages)
    assert (bkv, bq) == want
    assert 1 <= bkv <= pages and 1 <= bq <= n
    assert pick_block_sizes(n, page_size, pages,
                            heads // max(1, planes // 2)) == want
    if layout is not SMALLTHINKER:  # no layout given: the even ratios' pair
        assert pick_block_sizes(n, page_size, pages) == want


LAYOUTS = {"qwen": QWEN, "mistral": MISTRAL, "llama-packed": LLAMA_PACKED,
           "mla-latent": MLA_LATENT, "smallthinker": SMALLTHINKER}


def _shapes(n, layout, page_size=16):
    heads, width, planes = layout
    return (n, heads, width), (4096, page_size, planes, width)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("pages", [1024, 24])
def test_one_kv_block_an_engine(layout, pages):
    """bkv reads the layout, the page size and the page budget, never the
    token budget: the kernel blocks a row's keys from its table's first
    entry, so two programs (or a cold and a cached request) that blocked
    differently would part at near ties. A window is not an argument of the
    rule at all; the shift it brings is by this same block."""
    blocks = {call_geometry(*_shapes(n, LAYOUTS[layout]), pages)[0]
              for n in (1, 2, 8, 64, 128, 129, 256, 512, 513, 2048)}
    assert len(blocks) == 1
    assert blocks == {window_align_pages(*_shapes(64, LAYOUTS[layout]), pages)}
    assert blocks == {window_align_pages(*_shapes(256, LAYOUTS[layout]), pages)}


def _record_kernel(monkeypatch):
    """A recorder in the kernel's place: every invocation's (query tokens,
    rows, bkv, bq) and what it was handed."""
    import jax.numpy as jnp

    import llmd_tpu.ops.paged_attention as pa

    seen = []

    def stub(q, kv, kv_lens, page_tables, cu_q_lens, num_seqs, **kw):
        seen.append(((q.shape[0], page_tables.shape[0],
                      kw["num_kv_pages_per_block"],
                      kw["num_queries_per_block"]),
                     (kv_lens, page_tables, cu_q_lens, num_seqs)))
        return jnp.zeros_like(q)

    monkeypatch.setattr(pa, "_kernel", lambda: stub)
    return seen


@pytest.mark.parametrize("heads", [8, 14])  # four and seven a KV head
def test_full_and_window_calls_of_both_programs_block_alike(monkeypatch, heads):
    """What the kernel is handed, through a stub: the same bkv whatever the
    token budget and whether the layer has a window, and a window layer's
    page table shifted by a whole number of blocks of that bkv."""
    import jax.numpy as jnp
    import numpy as np

    import llmd_tpu.ops.paged_attention as pa

    calls = _record_kernel(monkeypatch)
    ps, maxp, B = 16, 256, 2
    pt = np.arange(B * maxp, dtype=np.int32).reshape(B, maxp)
    lens = np.asarray([3000, 1700], np.int32)
    cache = jnp.zeros((B * maxp, ps, 4, 128), jnp.bfloat16)
    for n, q_lens in ((2, [1, 1]), (256, [1, 200])):
        cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
        for window in (None, 640):
            pa.paged_attention_tpu(
                jnp.zeros((n, heads, 128), jnp.bfloat16), cache,
                jnp.asarray(pt), None, None, jnp.asarray(lens), scale=1.0,
                cu_q_lens=jnp.asarray(cu), num_seqs=jnp.asarray([B], np.int32),
                **({"sliding_window": window} if window else {}))
    seen = [(bkv, bq, np.asarray(page_tables), np.asarray(kv_lens))
            for (_, _, bkv, bq), (kv_lens, page_tables, _, _) in calls]
    want_bkv = 64 if heads == 14 else 32
    assert {bkv for bkv, *_ in seen} == {want_bkv}
    # the fused call's one; the unified step's two (its decode row, its chunk)
    assert [bq for _, bq, *_ in seen] == (
        [2, 2, 2, 16, 2, 16] if heads == 14 else [2, 2, 2, 64, 2, 64])

    def rows(calls):
        """A step's rows as the kernel was handed them: the decode row from
        the decode rows' call, the chunk from the chunks'."""
        if len(calls) == 1:
            return calls[0][2:]
        (_, _, dec_pt, dec_lens), (_, _, chunk_pt, chunk_lens) = calls
        return (np.concatenate([dec_pt[:1], chunk_pt[1:]]),
                np.concatenate([dec_lens[:1], chunk_lens[1:]]))

    for full, win in ((seen[0:1], seen[1:2]), (seen[2:4], seen[4:6])):
        (full_pt, full_lens), (win_pt, win_lens) = rows(full), rows(win)
        np.testing.assert_array_equal(full_lens, lens)
        shift = (full_lens - win_lens) // ps
        assert (shift % want_bkv == 0).all() and shift.max() > 0
        for b in range(B):
            np.testing.assert_array_equal(win_pt[b, :maxp - shift[b]],
                                          full_pt[b, shift[b]:])


@pytest.mark.parametrize("heads,planes,wide", [
    (16, 32, False),   # 16/16: one head a KV head, no sweep covered it
    (8, 16, False),
    (24, 8, False),    # six a KV head over SmallThinker's pool: swept, flat
    (32, 8, False),
    (20, 8, True),     # five and three a KV head: swept with seven
    (12, 8, True),
    (9, 2, False),     # nine: odd, but past what was swept
])
def test_an_unswept_layout_keeps_the_pair(heads, planes, wide):
    for n, old, new in ((64, (32, 8), (64, 4)), (256, (32, 16), (64, 8))):
        got = call_geometry((n, heads, 128), (4096, 16, planes, 128), 1024)
        assert got == (new if wide else old)


@pytest.mark.parametrize("layout,tp", [
    ("qwen", 2), ("mistral", 4), ("mistral", 8), ("llama-packed", 4),
    ("smallthinker", 4), ("smallthinker", 2)])
def test_tp_shards_take_the_whole_models_pair(layout, tp):
    """`paged_attention_tpu` picks the geometry before `shard_over_heads`;
    were it picked per shard it would still agree, since both head counts
    split over tp and the rule reads only their ratio."""
    heads, width, planes = LAYOUTS[layout]
    for n in (64, 256):
        whole = call_geometry((n, heads, width), (4096, 16, planes, width), 512)
        shard = call_geometry((n, heads // tp, width),
                              (4096, 16, planes // tp, width), 512)
        assert whole == shard


def test_environment_leaves_the_geometry_unchanged(monkeypatch, tmp_path):
    """The rule is the one measured decision: no variable and no file in a
    machine's environment replaces it."""
    shapes = [(64, 16, 256), (256, 16, 512), (128, 16, 8), (1024, 16, 128)]
    want = [pick_block_sizes(*s) for s in shapes]
    table = tmp_path / "tune.json"
    table.write_text('{"version": 1, "entries": [{"batch": 64, "page_size": 16,'
                     ' "pages_per_seq": 256, "head_layout": "h12x128kv4",'
                     ' "bkv": 2, "bq": 64}]}')
    # the names the parent's overrides and tune table were read from
    for name, value in (("BKV", "1"), ("BQ", "4"), ("DECODE_N", "2048"),
                        ("TUNE_FILE", str(table))):
        monkeypatch.setenv("LLMD_" + "ATTN_" + name, value)
    assert [pick_block_sizes(*s) for s in shapes] == want
    assert want[0] == (32, 8)
    assert pick_block_sizes(256, 16, 1024, heads_per_kv=7) == (64, 8)


def test_engine_reports_the_geometry_its_programs_trace():
    """`engine_attn_backend{geometry}` is `step_geometry` at the unified
    step's and the fused decode call's static shapes, both pairs of a step
    that makes two calls; `none` where the XLA reference serves."""
    def mk(**kw):
        return LLMEngine(get_model_config("tiny"), EngineConfig(
            page_size=8, num_pages=32, max_model_len=64, max_batch_size=2,
            prefill_chunk=16, **kw))

    eng = mk(attn_impl="pallas")
    # 8 pages a sequence (clamps bkv); N = 16 tokens unified, 2 seats decode:
    # the step's decode rows at the fused call's pair, its chunks at 16
    assert (pick_block_sizes(16, 8, 8), pick_block_sizes(2, 8, 8)) \
        == ((8, 8), (8, 2))
    assert eng.attn_geometry == "unified=8x2+8x16 decode=8x2"
    assert 'geometry="unified=8x2+8x16 decode=8x2"' \
        in eng.metrics.registry.expose()
    assert mk().attn_geometry == "none"


def test_an_engine_whose_rows_are_cut_reports_one_pair():
    """A model with recurrent layers keeps the single call in its unified
    step (its rows are cut at KV blocks), and the label stays as it was."""
    import test_hybrid_ssm

    eng = test_hybrid_ssm._engine(attn_impl="pallas")
    assert eng.attn_geometry == "unified=24x8 decode=24x4"
    assert 'geometry="unified=24x8 decode=24x4"' \
        in eng.metrics.registry.expose()


def test_engine_shifts_window_layers_by_the_block_it_reports():
    """`Backends.window_align` (what `attn_kv_tokens_total{layers="window"}` rounds
    by) is the bkv of the geometry label, at an even and at an odd number of
    query heads a KV head."""
    from dataclasses import replace

    for heads, kv_heads, pages, geometry in (
            (4, 2, 128, "unified=32x4+32x64 decode=32x4"),
            (6, 2, 128, "unified=64x4+64x16 decode=64x4"),
            (6, 2, 48, "unified=48x4+48x16 decode=48x4")):
        cfg = replace(get_model_config("tiny"), num_heads=heads,
                      num_kv_heads=kv_heads, attn_window_pattern=(0, 64),
                      rope_pattern=(0, 1))
        eng = LLMEngine(cfg, EngineConfig(
            page_size=16, num_pages=pages + 8, max_model_len=16 * pages,
            max_batch_size=4, prefill_chunk=256, attn_impl="pallas"))
        assert eng.attn_geometry == geometry + " window=0,64"
        assert eng.backends.window_align == int(geometry.split("=")[1].split("x")[0])
        assert eng.backends.window_align == window_align_pages(
            (4, heads, 128), eng.cache.shape, pages)


# ------------------------------------------- a unified step's two kernel calls

# one KV head's twenty query heads (Jamba2-3B's attention layers)
JAMBA = (20, 128, 2)


@pytest.mark.parametrize("layout,pages,kw,unified,decode", [
    # the cells' five layouts at their model lengths: which calls a unified
    # step of 256 tokens over 64 rows makes, and the fused decode call's one
    (QWEN, 256, {}, [(64, 32, 8), (256, 32, 64)], (32, 8)),
    (MISTRAL, 512, {}, [(64, 32, 8), (256, 32, 64)], (32, 8)),
    (SMALLTHINKER, 1024, {}, [(64, 64, 4), (256, 64, 16)], (64, 4)),
    (SMALLTHINKER, 1024, {"sliding_window": 4096},
     [(64, 64, 4), (256, 64, 16)], (64, 4)),
    # rows cut at their KV blocks (a model with recurrent layers): one call
    # over twice the rows, as before
    (JAMBA, 192, {"split_at_kv_blocks": True}, [(256, 32, 16)], (32, 8)),
], ids=["qwen", "mistral", "smallthinker-full", "smallthinker-window",
        "jamba-rows-cut"])
def test_which_calls_a_unified_step_makes(monkeypatch, layout, pages, kw,
                                          unified, decode):
    """The decode rows of a unified step go to the kernel at the fused decode
    call's pair, as a call of as many query tokens as the step has rows, and
    the chunks at a pair of their own, over one bkv; a call whose rows are cut
    at their KV blocks stays one. Read from static shapes alone."""
    import jax.numpy as jnp

    import llmd_tpu.ops.paged_attention as pa

    seen = _record_kernel(monkeypatch)
    heads, width, planes = layout
    B, N = 64, 256
    cache = jnp.zeros((8, 16, planes, width), jnp.bfloat16)
    rows = (jnp.zeros((B, pages), jnp.int32), None, None,
            jnp.full((B,), 40, jnp.int32))

    def call(n, cu, **kw):
        del seen[:]
        pa.paged_attention_tpu(
            jnp.zeros((n, heads, width), jnp.bfloat16), cache, *rows, scale=1.0,
            cu_q_lens=jnp.asarray(cu, jnp.int32),
            num_seqs=jnp.asarray([B], jnp.int32), **kw)
        return [(n, bkv, bq) for (n, _, bkv, bq), _ in seen]

    cut = kw.get("split_at_kv_blocks", False)
    cu = list(range(B)) + [N]  # 63 decode rows and a chunk
    assert call(N, cu, **kw) == unified
    assert len({bkv for _, bkv, _ in unified}) == 1
    assert pa.step_geometry((N, heads, width), cache.shape, B, pages, cut) \
        == tuple((bkv, bq) for _, bkv, bq in unified)
    assert pa.window_align_pages((N, heads, width), cache.shape, pages) \
        == unified[0][1]
    window = {k: v for k, v in kw.items() if k == "sliding_window"}
    assert call(B, range(B + 1), **window) == [(B, *decode)]
    assert decode[0] == unified[0][1]
    assert pa.step_geometry((B, heads, width), cache.shape, B, pages) \
        == (decode,)
    # one row is one call whatever it brings (the embeddings program)
    assert pa.step_geometry((N, heads, width), cache.shape, 1, pages) \
        == (pa.call_geometry((N, heads, width), cache.shape, pages),)


# (query tokens, rows, bkv, bq) of every kernel invocation a program traces,
# read at the parent of PR 44: a model with recurrent layers keeps them
TINY_CALLS = {
    "test_hybrid_ssm": ("unified=24x8 decode=24x4",
                        [(16, 8, 24, 8)], [(4, 4, 24, 4)] * 2),
    "test_minicpm_sala": ("unified=32x8 decode=32x4",
                          [(32, 8, 32, 8), (32, 32, 20, 8)] * 4,
                          [(4, 4, 32, 4), (4, 4, 20, 4)] * 8),
}


@pytest.mark.parametrize("module", sorted(TINY_CALLS))
def test_recurrent_models_programs_call_the_kernel_as_they_did(monkeypatch,
                                                               module):
    """The step programs of the tiny Jamba and the tiny MiniCPM-SALA engine
    make the kernel calls they made before a unified step's rows went two
    ways: their rows are cut at KV blocks (one call), and a sparse layer's
    one-query rows already go to the decode impl."""
    import importlib

    import jax.numpy as jnp

    seen = _record_kernel(monkeypatch)
    eng = importlib.import_module(module)._engine(attn_impl="pallas")
    geometry, unified, decode = TINY_CALLS[module]
    assert eng.attn_geometry == geometry
    B, NT = eng.cfg.max_batch_size, eng.cfg.batched_tokens
    maxp = eng.cfg.max_pages_per_seq

    def i(*shape):
        return jnp.zeros(shape, jnp.int32)

    eng._unified_fn.lower(
        eng.params, eng._pools(), i(NT), i(NT), i(NT), i(B, maxp), i(B),
        i(B + 1), i(1), i(NT), eng._zero_sampled, *eng._greedy_state,
        state_slots=i(B))
    assert [shape for shape, _ in seen] == unified
    del seen[:]
    eng._decode_multi_fn.lower(
        eng.params, eng._pools(), i(B), i(B), i(B, maxp), i(B),
        *eng._greedy_state, i(B), i(B))
    assert [shape for shape, _ in seen] == decode


def test_what_each_of_the_two_calls_is_told():
    """The rows the two calls are handed (`decode_rows_and_chunks`): the
    decode call the longest prefix of live one-query rows; the chunk call the
    rows from the last of those on, that one standing in for all of them over
    one token of context, on the step's own token axis. Neither is ever told
    of no row (the upstream kernel halts the chip on that)."""
    import jax.numpy as jnp
    import numpy as np

    from llmd_tpu.ops.paged_attention import decode_rows_and_chunks

    B, maxp = 6, 4
    pt = np.arange(B * maxp, dtype=np.int32).reshape(B, maxp)
    lens = np.asarray([30, 31, 32, 50, 7, 1], np.int32)

    def told(q_lens, live):
        cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
        n_dec, decode, chunks = decode_rows_and_chunks(
            jnp.asarray(pt), jnp.asarray(lens), jnp.asarray(cu),
            jnp.asarray([live], jnp.int32))
        return int(n_dec), *(
            [np.asarray(a).tolist() for a in call] for call in (decode, chunks))

    # three decode rows, a chunk of 9, a chunk of one token, a dead row
    n, (dl, dp, dcu, dn), (cl, cp, ccu, cn) = told([1, 1, 1, 9, 1, 0], 5)
    assert (n, dn, cn) == (3, [3], [3])
    assert dl[:3] == [30, 31, 32] and dcu == [0, 1, 2, 3, 3, 3, 3]
    assert dp == pt.tolist()
    assert cl[:3] == [1, 50, 7] and ccu[:4] == [0, 3, 12, 13]
    assert cp[:3] == pt[2:5].tolist()
    # a one-token chunk right behind the decode rows rides with them
    n, (_, _, dcu, dn), (cl, _, ccu, cn) = told([1, 1, 1, 1, 9, 0], 5)
    assert (n, dn, cn) == (4, [4], [2])
    assert cl[:2] == [1, 7] and ccu[:3] == [0, 4, 13]
    # no decode row: the decode call takes the first row as one query over
    # one token, the chunk call every row as it came
    n, (dl, _, dcu, dn), (cl, cp, ccu, cn) = told([9, 4, 0, 0, 0, 0], 2)
    assert (n, dn, cn) == (0, [1], [2])
    assert dl[0] == 1 and dcu[:3] == [0, 1, 1]
    assert (cl, cp, ccu) == (lens.tolist(), pt.tolist(),
                             [0, 9, 13, 13, 13, 13, 13])
    # nothing but decode rows: the chunk call is left the stand-in alone
    n, (_, _, dcu, dn), (cl, _, ccu, cn) = told([1] * 6, 6)
    assert (n, dn, cn) == (6, [6], [1])
    assert dcu == list(range(7)) and cl[0] == 1 and ccu[:2] == [0, 6]
    # a dead row's queries are nobody's: the prefix ends at the live rows
    assert told([1, 1, 1, 1, 1, 1], 2)[0] == 2


def _interpret_case(q_lens, seq_lens, heads, window=None):
    """A unified step of 32 tokens over 8 rows of 64-token pages (so that a
    KV block is 8 pages, 16 at seven query heads a KV head, and interpret
    mode stays cheap), pages scattered over the pool."""
    import jax.numpy as jnp
    import numpy as np

    ps, Hk, D, N, B, maxp = 64, 2, 128, 32, 8, 48
    rng = np.random.default_rng(0)
    P = sum(-(-n // ps) for n in seq_lens) + 3
    free = rng.permutation(P)
    pt = np.full((B, maxp), -1, np.int32)
    lens, cu = np.ones((B,), np.int32), np.zeros((B + 1,), np.int32)
    used = 0
    for b, (n, q) in enumerate(zip(seq_lens, q_lens)):
        pages = -(-n // ps)
        pt[b, :pages] = free[used:used + pages]
        lens[b], used, cu[b + 1] = n, used + pages, cu[b] + q
    cu[len(seq_lens) + 1:] = cu[len(seq_lens)]
    args = (jnp.asarray(rng.standard_normal((N, heads, D)), jnp.bfloat16),
            jnp.asarray(rng.standard_normal((P, ps, 2 * Hk, D)), jnp.bfloat16),
            jnp.asarray(pt), None, None, jnp.asarray(lens))
    kw = dict(scale=D ** -0.5, cu_q_lens=jnp.asarray(cu),
              num_seqs=jnp.asarray([len(seq_lens)], jnp.int32))
    if window:
        kw["sliding_window"] = window
    return args, kw, cu


@pytest.mark.parametrize("q_lens,seq_lens,heads,window", [
    ([1, 1, 1, 18, 5], [300, 700, 1100, 600, 40], 4, None),
    ([18, 5], [600, 40], 4, None),
    ([1, 1, 1], [300, 700, 1100], 4, None),
    ([1, 1, 18, 1], [300, 700, 600, 33], 4, None),
    ([1, 1, 1, 18, 5], [300, 700, 2100, 1500, 40], 14, 640),
    ([1, 1, 12], [512, 1024, 1024], 4, None),
], ids=["mixed", "no-decode-rows", "only-decode-rows", "one-token-chunk",
        "window-layer", "kv-len-on-a-blocks-end"])
def test_two_calls_equal_the_single_call_bit_for_bit(monkeypatch, q_lens,
                                                     seq_lens, heads, window):
    """The upstream kernel in interpret mode: every token of the two-call
    form equals, to the last bit, the single call over all the step's rows at
    the pair of the call that owns the token. (Against one pair for all
    tokens the chip reads 0.0 too, `tools/attn_sweep.py --split 0,1`; the
    CPU's interpreter rounds a few elements otherwise by the block's shape.)"""
    import numpy as np

    import llmd_tpu.ops.paged_attention as pa

    _interpreted(monkeypatch)
    args, kw, cu = _interpret_case(q_lens, seq_lens, heads, window)
    pairs = pa.step_geometry(args[0].shape, args[1].shape, *args[2].shape)
    assert len(pairs) == 2 and pairs[0][1] != pairs[1][1]
    got = np.asarray(pa.paged_attention_tpu(*args, **kw), np.float32)
    n_dec = next((b for b, q in enumerate(q_lens) if q != 1), len(q_lens))
    for pair, tokens in ((pairs[0], slice(0, cu[n_dec])),
                         (pairs[1], slice(cu[n_dec], cu[len(q_lens)]))):
        monkeypatch.setattr(pa, "step_geometry", lambda *a, pair=pair: (pair,))
        want = np.asarray(pa.paged_attention_tpu(*args, **kw), np.float32)
        np.testing.assert_array_equal(got[tokens], want[tokens])
    assert np.isfinite(got[:cu[len(q_lens)]]).all()


# ------------------------------------- the one-query rows on the repo's kernel

# query heads over two KV heads: four, six and seven a KV head (Mistral's
# 32/8, Qwen's 12/2, SmallThinker's 28/4)
ROWS_LAYOUTS = {"32/8": 8, "12/2": 12, "28/4": 14}
# a row: (pages of the tenant's prompt it stands behind, tokens past them;
# tokens <= 0: the context ends that far inside the held pages), None an idle
# seat as the fused call packs it (one token, no page)
ROWS_CASES = {
    # nobody shares; a context on a KV block's end, one inside its first page
    "unshared": [(0, 300), (0, 1024), (0, 1100), (0, 40), None, (0, 513),
                 None, (0, 2000)],
    # eight rows behind one prompt of two KV blocks (one at seven heads)
    "behind_one_prompt": [(16, 300), (16, 70), (16, 513), (16, 1), (16, 640),
                          (16, 900), (16, 64), (16, 1200)],
    # two tenants, rows that hold less of the prompt, a context that ends
    # inside the held pages, an idle seat between them
    "unequal_extents": [(24, 100), (16, 300), (24, 700), None, (8, 30),
                        (24, -70), (16, 520), (16, 90)],
}
ROWS_TENANT = {"unequal_extents": [0, 1, 0, 0, 1, 0, 1, 1]}


def _rows_case(rows, heads, share, tenants=None, n_tokens=None, chunk=None,
               seed=0):
    """One-query rows over 64-token pages (a KV block is 8 pages, 16 at seven
    heads a KV head) whose tenant's prompt the pool holds once (``share``) or
    once a row under page ids of the row's own, the same values either way;
    ``chunk`` ``(q_len, kv_len)`` a prefill chunk behind them."""
    import jax.numpy as jnp
    import numpy as np

    ps, Hk, D, maxp = 64, 2, 128, 48
    B = len(rows) + (chunk is not None)
    N = n_tokens or B
    rng = np.random.default_rng(seed)
    tenants = tenants or [0] * len(rows)
    docs = rng.standard_normal((2, 24, ps, 2 * Hk, D)).astype(np.float32)
    pool = np.zeros((600, ps, 2 * Hk, D), np.float32)
    free = list(rng.permutation(np.arange(1, 600)))  # page 0: a clamped -1
    held = [[free.pop() for _ in range(24)] for _ in docs]
    for doc, ids in zip(docs, held):
        pool[ids] = doc
    pt = np.full((B, maxp), -1, np.int32)
    lens, q_lens = np.ones(B, np.int32), np.ones(B, np.int32)
    for b, row in enumerate(rows):
        if row is None:
            continue
        n_doc, past = row
        ids = held[tenants[b]][:n_doc] if share else [
            free.pop() for _ in range(n_doc)]
        pool[ids] = docs[tenants[b], :n_doc]
        mine = [free.pop() for _ in range(-(-max(past, 0) // ps))]
        pool[mine] = rng.standard_normal((len(mine), ps, 2 * Hk, D))
        pt[b, :n_doc + len(mine)] = ids + mine
        lens[b] = n_doc * ps + past
    if chunk:
        q_lens[-1], lens[-1] = chunk
        ids = [free.pop() for _ in range(-(-chunk[1] // ps))]
        pool[ids] = rng.standard_normal((len(ids), ps, 2 * Hk, D))
        pt[-1, :len(ids)] = ids
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    args = (jnp.asarray(rng.standard_normal((N, heads, D)), jnp.bfloat16),
            jnp.asarray(pool, jnp.bfloat16), jnp.asarray(pt), None, None,
            jnp.asarray(lens))
    kw = dict(scale=D ** -0.5, cu_q_lens=jnp.asarray(cu),
              num_seqs=jnp.asarray([B], jnp.int32))
    return args, kw


def _interpreted(monkeypatch):
    """The upstream wrapper takes no interpret flag: give its pallas_call
    one (the rows kernel is handed its own)."""
    import functools

    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))


def _led(plan, B=8):
    """(size, shared) of every group the plan's array holds (members, size,
    shared, leaders, their count), in the order walked."""
    import numpy as np

    packed = np.asarray(plan["groups"])
    G = (len(packed) - 1) // B - 3
    size, shared, lead = packed[B * G:-1].reshape(3, B)
    return [(int(size[b]), int(shared[b])) for b in lead[:packed[-1]]]


@pytest.mark.parametrize("layout", sorted(ROWS_LAYOUTS))
@pytest.mark.parametrize("case", sorted(ROWS_CASES))
def test_the_rows_kernel_returns_the_upstream_calls_bits(monkeypatch, case,
                                                         layout):
    """A fused decode call's rows through `rows_attention` (the groups the
    plan derives from the tables) and through the upstream kernel at the same
    bkv: every row equal to the last bit, idle seats too, whether the rows
    stand behind one prompt's pages or own copies of them."""
    import numpy as np

    import llmd_tpu.ops.paged_attention as pa

    _interpreted(monkeypatch)
    heads = ROWS_LAYOUTS[layout]
    rows, tenants = ROWS_CASES[case], ROWS_TENANT.get(case)
    out, led = {}, {}
    for share in (True, False):
        args, kw = _rows_case(rows, heads, share, tenants)
        plan = pa.plan(args[2], args[5], kw["cu_q_lens"], kw["num_seqs"], 64,
                       heads_per_kv=heads // 2)
        led[share] = _led(plan)
        out[share] = np.asarray(pa.paged_attention_tpu(
            *args, one_query_rows=True, interpret=True, **plan, **kw),
            np.float32)
    want = np.asarray(pa.paged_attention_tpu(*args, **kw), np.float32)
    np.testing.assert_array_equal(out[False], want)
    np.testing.assert_array_equal(out[True], want)
    mine = [b for b, r in enumerate(rows) if r is not None]
    assert np.isfinite(want).all()
    assert np.abs(want[mine]).max(axis=(1, 2)).min() > 0.05
    # on copies of their own only the idle seats (all unmapped) share
    assert led[False].count((1, 0)) >= len(mine)
    bkv = pa.pick_block_sizes(0, 64, 48, heads // 2)[0]
    if case == "behind_one_prompt":  # 16 pages: two KV blocks of 8, one of 16
        assert led[True] == [(pa.GROUP_ROWS, 16 // bkv)]
    elif case == "unequal_extents":
        assert any(n > 1 and s > 0 for n, s in led[True])
        assert len(led[True]) < len(led[False])
    else:
        assert led[True] == led[False]


@pytest.mark.parametrize("layout,window", [("32/8", None), ("28/4", None),
                                           ("28/4", 640)],
                         ids=["32/8", "28/4", "window-layer"])
def test_a_unified_steps_head_call_on_the_rows_kernel(monkeypatch, layout,
                                                      window):
    """Five decode rows behind one prompt and a chunk beside them: with the
    plan's groups the step's one-query rows go to the rows kernel and the
    chunk to the upstream call, and every token is what the two upstream
    calls give, to the last bit. A window layer hands the kernel shifted
    tables, which the batch's groups do not describe: its rows keep the
    upstream call."""
    import numpy as np

    import llmd_tpu.ops.paged_attention as pa

    _interpreted(monkeypatch)
    heads = ROWS_LAYOUTS[layout]
    rows = [(16, 300), (16, 70), (16, 513), (16, 1), (16, 640)]
    args, kw = _rows_case(rows, heads, True, n_tokens=32, chunk=(20, 700))
    if window:
        kw["sliding_window"] = window
    plan = pa.plan(args[2], args[5], kw["cu_q_lens"], kw["num_seqs"], 64,
                   heads_per_kv=heads // 2)
    called = []
    monkeypatch.setattr(pa, "rows_attention", lambda *a, _f=pa.rows_attention,
                        **k: called.append(a[0].shape[0]) or _f(*a, **k))
    got = np.asarray(pa.paged_attention_tpu(*args, interpret=True, **plan,
                                            **kw), np.float32)
    want = np.asarray(pa.paged_attention_tpu(*args, **kw), np.float32)
    np.testing.assert_array_equal(got[:25], want[:25])
    assert called == ([] if window else [6])  # the step's 6 rows' tokens
    assert _led(plan, 6)[0][1] > 0 and sum(n for n, _ in _led(plan, 6)) == 5


def test_a_step_with_no_decode_row_tells_the_rows_kernel_of_one(monkeypatch):
    """`decode_rows_and_chunks` hands the head call its first row as one
    query over one token where the step has no decode row: the plan's groups
    are that call's (one group of one), not the batch's one-token chunks'."""
    import numpy as np

    import llmd_tpu.ops.paged_attention as pa

    _interpreted(monkeypatch)
    import jax.numpy as jnp

    args, kw = _rows_case([(16, 300)], 8, True, n_tokens=32, chunk=(1, 90))
    # a chunk of 9, then one of 1
    kw["cu_q_lens"] = jnp.asarray([0, 9, 10], jnp.int32)
    plan = pa.plan(args[2], args[5], kw["cu_q_lens"], kw["num_seqs"], 64,
                   heads_per_kv=4)
    assert _led(plan, 2) == [(1, 0)]
    got = np.asarray(pa.paged_attention_tpu(*args, interpret=True, **plan,
                                            **kw), np.float32)
    want = np.asarray(pa.paged_attention_tpu(*args, **kw), np.float32)
    np.testing.assert_array_equal(got[:10], want[:10])


@pytest.mark.parametrize("heads_per_kv,dtype,mesh,serves", [
    (4, "bfloat16", None, True), (6, "bfloat16", None, False),
    (7, "bfloat16", None, False), (2, "bfloat16", None, False),
    (20, "bfloat16", None, False), (4, "float8_e4m3fn", None, False),
    (4, "float32", None, False), (4, "bfloat16", object(), False),
], ids=["32/8", "12/2", "28/4", "unswept-2", "unswept-20", "fp8", "float32",
        "mesh"])
def test_which_layouts_take_the_rows_kernel(heads_per_kv, dtype, mesh, serves):
    """The rule reads what `pick_block_sizes` reads of a layout, the pool's
    dtype and whether a mesh is set: four query heads a KV head in bf16 on
    one device take the kernel; six and seven (swept: slower on rows that
    share nothing), the unswept, fp8 pages and a mesh keep the upstream
    call."""
    import llmd_tpu.ops.paged_attention as pa

    assert pa.rows_kernel_serves(heads_per_kv, dtype, mesh) is serves


def _gqa4_engine(**kw):
    from dataclasses import replace

    return LLMEngine(
        replace(get_model_config("tiny"), num_heads=8, num_kv_heads=2),
        EngineConfig(page_size=8, num_pages=96, max_model_len=128,
                     max_batch_size=4, prefill_chunk=32, decode_steps=4,
                     **kw), seed=3)


def test_which_engines_hand_their_rows_to_the_rows_kernel():
    """`engine/backends.py` binds the plan (and with it the kernel) for a
    layout the sweep passed on one device; a model with recurrent layers, a
    layout it did not pass, an fp8 pool and the XLA reference keep what they
    had. The label names the rows a group."""
    import test_hybrid_ssm

    import llmd_tpu.ops.paged_attention as pa

    eng = _gqa4_engine(attn_impl="pallas")
    bk = eng.backends
    assert bk.attn_impl.plan.func is pa.plan
    assert bk.attn_decode_impl.plan is bk.attn_impl.plan
    assert bk.attn_decode_impl.keywords["one_query_rows"] is True
    assert "one_query_rows" not in bk.attn_impl.keywords
    assert bk.decode_groups == (16, 8)  # a sequence's 16 pages a KV block
    assert eng.attn_geometry == "unified=16x4+16x32 decode=16x4 groups=8"
    for other in (test_hybrid_ssm._engine(attn_impl="pallas"),
                  _gqa4_engine(attn_impl="pallas", kv_cache_dtype="fp8"),
                  LLMEngine(get_model_config("tiny"), EngineConfig(
                      page_size=8, num_pages=32, max_model_len=64,
                      max_batch_size=2, prefill_chunk=16,
                      attn_impl="pallas")),
                  _gqa4_engine()):
        assert getattr(other.backends.attn_impl, "plan", None) is None
        assert other.backends.attn_decode_impl is other.backends.attn_impl \
            or other.model_cfg.has_recurrent
        assert other.backends.decode_groups is None
        assert "groups=" not in other.attn_geometry


def test_the_engine_walks_a_cached_prompt_once_and_books_it(monkeypatch):
    """Four sequences behind one cached 64-token prompt, 16 tokens a KV
    block: the second pass's decode rows name the same pages first, the rows
    kernel walks them as one group, the tokens are the XLA engine's and those
    of the engine whose rows keep the upstream call, the counter says what
    was fetched, `plan` is asked once a program, and an engine on another
    backend books nothing."""
    import llmd_tpu.ops.paged_attention as pa

    _interpreted(monkeypatch)
    monkeypatch.setattr(pa, "KV_BLOCK_TOKENS", 16)
    asked = []
    monkeypatch.setattr(pa, "plan", lambda *a, _f=pa.plan, **k: (
        asked.append(a[0].shape) or _f(*a, **k)))
    prompts = [[(7 * t + 11) % 250 + 2 for t in range(64)]
               + [81 + i, 80 + 2 * i] for i in range(4)]
    sp = SamplingParams(max_tokens=10, temperature=0.0)
    ref = _gqa4_engine()
    want = ref.generate(prompts, sp)
    with monkeypatch.context() as m:  # the parent's binding
        m.setattr(pa, "rows_kernel_serves", lambda *a: False)
        upstream = _gqa4_engine(attn_impl="pallas")
    assert upstream.backends.decode_groups is None
    assert [upstream.generate(prompts, sp) for _ in range(2)] == [want] * 2
    eng = _gqa4_engine(attn_impl="pallas")
    assert eng.attn_geometry == "unified=2x4+2x32 decode=2x4 groups=8"
    name = "llmd_tpu:attn_decode_kv_blocks_total"

    def series():
        return {k: float(v) for k, v in (
            line.rsplit(" ", 1) for line in
            eng.metrics.registry.expose().splitlines()
            if line.startswith(name))}

    assert eng.generate(prompts, sp) == want
    cold = series()
    assert eng.generate(prompts, sp) == want
    both = series()
    rows, fetched = (both[name + '{blocks="%s"}' % k]
                     - cold[name + '{blocks="%s"}' % k]
                     for k in ("rows", "fetched"))
    assert 0 < fetched < 0.6 * rows  # 4 of a row's 5 blocks are shared
    assert cold[name + '{blocks="fetched"}'] <= cold[name + '{blocks="rows"}']
    # one plan a trace of a program's body, whatever its layers (two,
    # scanned): the unified step's, and the fused call's body traced twice
    # (`_fused_steps`: once for its shapes, once in the loop)
    assert asked == [(4, 16)] * 3
    assert name not in ref.metrics.registry.expose().replace(
        "# HELP " + name, "").replace("# TYPE " + name, "")


def test_the_counter_books_a_tenants_blocks_once():
    """Eight rows behind 4 KV blocks of 32 pages with a block of their own
    each (Mistral's layout): 40 blocks once a row, 12 fetched at eight rows a
    group and 16 at four; on copies of their own 40 and 40; a chunk is not a
    decode row; rows that start on pages of their own never reach the rule."""
    import numpy as np

    from llmd_tpu.ops import row_groups

    ps, bkv, maxp = 16, 32, 800
    assert pick_block_sizes(64, ps, maxp, heads_per_kv=4)[0] == bkv
    pt = np.full((9, maxp), -1, np.int32)
    pt[:8, :4 * bkv] = np.arange(4 * bkv)
    for b in range(8):
        pt[b, 4 * bkv:4 * bkv + 3] = 5000 + 10 * b + np.arange(3)
    pt[8, :5 * bkv] = np.arange(5 * bkv)
    kl = np.array([4 * 512 + 40] * 8 + [5 * 512], np.int64)
    q = np.array([1] * 8 + [128])
    assert row_groups.decode_kv_blocks(pt, kl, q, ps, bkv, 8) == (40, 12)
    assert row_groups.decode_kv_blocks(pt, kl, q, ps, bkv, 4) == (40, 16)
    apart = pt.copy()
    for b in range(8):
        apart[b, :4 * bkv] += 10000 * (b + 1)
    assert row_groups.decode_kv_blocks(apart, kl, q, ps, bkv, 8) == (40, 40)
    assert row_groups.decode_kv_blocks(pt[:4], kl[:4] * 0, q[:4], ps, bkv,
                                       8) == (0, 0)


# -------------------------------------------------- b128 scaling regression


def _decode_step_cost(batch: int) -> tuple[float, "LLMEngine"]:
    """Median wall per fused-decode dispatch at `batch` decode slots, int8
    weights (the campaign point's config), CPU mesh."""
    eng = LLMEngine(get_model_config("tiny"), EngineConfig(
        page_size=8, num_pages=batch * 3, max_model_len=24,
        max_batch_size=batch, prefill_chunk=32, decode_steps=4,
        quantize_weights="int8", enable_prefix_caching=False))
    prompts = [[(7 * i) % 97 + 2, (3 * i) % 53 + 2, 5] for i in range(batch)]
    sp = SamplingParams(max_tokens=12, temperature=0.0)
    eng.generate(prompts, sp)  # compile + warm
    costs = []
    for _ in range(2):
        n0 = eng.stats.n_decode_dispatches
        t0 = time.perf_counter()
        eng.generate(prompts, sp)
        dt = time.perf_counter() - t0
        costs.append(dt / max(1, eng.stats.n_decode_dispatches - n0))
    return min(costs), eng


def test_b128_per_step_cost_bounded_vs_b64():
    """The r05 int8-b128 pathology, pinned as a scaling law: doubling decode
    slots b64->b128 must cost at most ~linear per fused step (ratio ~2; bound
    3x for CI noise). A quadratic host-pack (B-sized python loops over
    B-sized arrays) or per-step recompilation — the two classes of code bug a
    b128 timeout could have hidden — land at 4x+ and fail loudly. The 2026-07
    on-chip timeout itself was fabric death mid-point, not code (PERF.md
    Round 6); this keeps the codepath honest for the retry."""
    c64, e64 = _decode_step_cost(64)
    c128, e128 = _decode_step_cost(128)
    # one compiled fused-decode program per engine across every step above:
    # a recompile storm is the classic silent b128 killer
    assert e64._decode_multi_fn._cache_size() == 1
    assert e128._decode_multi_fn._cache_size() == 1
    assert c128 <= 3.0 * c64, (
        f"per-step decode cost grew superlinearly b64->b128: "
        f"{c64 * 1e3:.2f} ms -> {c128 * 1e3:.2f} ms")
