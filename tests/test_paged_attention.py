"""Paged-attention block-size rule and the b128 cost-scaling regression.

Two layers pinned here:

1. `pick_block_sizes`: a function of the call's static shapes and of nothing
   else — not the head layout, not the environment — and the geometry the
   engine reports is the one its programs trace.
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
from llmd_tpu.ops.paged_attention import call_geometry, pick_block_sizes

# ------------------------------------------------------------------ the rule

# (query heads, lane-padded width, combined KV planes of the pool)
QWEN, MISTRAL, LLAMA_PACKED, MLA_LATENT = (
    (12, 128, 4), (32, 128, 16), (32, 128, 8), (4, 128, 1))


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
    # the single-plane latent pool: the rule does not read the layout
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
])
def test_rule_at_served_shapes(n, page_size, pages, layout, want):
    heads, width, planes = layout
    bkv, bq = pick_block_sizes(n, page_size, pages)
    assert (bkv, bq) == want
    assert 1 <= bkv <= pages and 1 <= bq <= n
    # what a step program traces with and the engine reports
    assert call_geometry((n, heads, width), (4096, page_size, planes, width),
                         pages) == want


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
