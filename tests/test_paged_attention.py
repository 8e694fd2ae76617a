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


@pytest.mark.parametrize("heads", [8, 14])  # four and seven a KV head
def test_full_and_window_calls_of_both_programs_block_alike(monkeypatch, heads):
    """What the kernel is handed, through a stub: the same bkv whatever the
    token budget and whether the layer has a window, and a window layer's
    page table shifted by a whole number of blocks of that bkv."""
    import jax.numpy as jnp
    import numpy as np

    import llmd_tpu.ops.paged_attention as pa

    seen = []

    def stub(q, kv, kv_lens, page_tables, cu_q_lens, num_seqs, **kw):
        seen.append((kw["num_kv_pages_per_block"], kw["num_queries_per_block"],
                     np.asarray(page_tables), np.asarray(kv_lens)))
        return jnp.zeros_like(q)

    monkeypatch.setattr(pa, "_kernel", lambda: stub)
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
    want_bkv = 64 if heads == 14 else 32
    assert {bkv for bkv, *_ in seen} == {want_bkv}
    assert [bq for _, bq, *_ in seen] == (
        [2, 2, 8, 8] if heads == 14 else [2, 2, 16, 16])
    for (_, _, full_pt, full_lens), (_, _, win_pt, win_lens) in (
            seen[0:2], seen[2:4]):
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
    """`engine_attn_backend{geometry}` is `pick_block_sizes` at the unified
    step's and the fused decode call's static shapes; `none` where the XLA
    reference serves."""
    def mk(**kw):
        return LLMEngine(get_model_config("tiny"), EngineConfig(
            page_size=8, num_pages=32, max_model_len=64, max_batch_size=2,
            prefill_chunk=16, **kw))

    eng = mk(attn_impl="pallas")
    # 8 pages a sequence (clamps bkv); N = 16 tokens unified, 2 seats decode
    assert (pick_block_sizes(16, 8, 8), pick_block_sizes(2, 8, 8)) \
        == ((8, 8), (8, 2))
    assert eng.attn_geometry == "unified=8x8 decode=8x2"
    assert 'geometry="unified=8x8 decode=8x2"' in eng.metrics.registry.expose()
    assert mk().attn_geometry == "none"


def test_engine_shifts_window_layers_by_the_block_it_reports():
    """`_window_align` (what `attn_kv_tokens_total{layers="window"}` rounds
    by) is the bkv of the geometry label, at an even and at an odd number of
    query heads a KV head."""
    from dataclasses import replace

    for heads, kv_heads, pages, geometry in (
            (4, 2, 128, "unified=32x16 decode=32x4"),
            (6, 2, 128, "unified=64x8 decode=64x4"),
            (6, 2, 48, "unified=48x8 decode=48x4")):
        cfg = replace(get_model_config("tiny"), num_heads=heads,
                      num_kv_heads=kv_heads, attn_window_pattern=(0, 64),
                      rope_pattern=(0, 1))
        eng = LLMEngine(cfg, EngineConfig(
            page_size=16, num_pages=pages + 8, max_model_len=16 * pages,
            max_batch_size=4, prefill_chunk=256, attn_impl="pallas"))
        assert eng.attn_geometry == geometry + " window=0,64"
        assert eng._window_align == int(geometry.split("=")[1].split("x")[0])
        assert eng._window_align == window_align_pages(
            (4, heads, 128), eng.cache.shape, pages)


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
