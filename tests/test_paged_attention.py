"""Paged-attention block-size policy, the auto-tune table, and the b128
cost-scaling regression.

Three layers pinned here:

1. `pick_block_sizes` resolution order — the rule < shape-keyed tune table
   (ops/attn_tune) < `LLMD_ATTN_BKV`/`BQ` env overrides gated by
   `LLMD_ATTN_DECODE_N` — including every degradation path (missing file,
   corrupt file, malformed entries) landing back on the heuristic.
2. The tune-table file contract bench.py's tuner writes and the engine loads:
   merge semantics, validation, hash provenance into `EngineStats`.
3. The int8-b128 regression from the r05 campaign: per-step fused-decode cost
   must grow at most ~linearly from b64 to b128 on the CPU mesh, and the
   decode program must not recompile per step. The on-chip b128 timeout was
   fabric death mid-point (PERF.md Round 6), not code; this test keeps it
   that way — a quadratic host-pack or a shape-keyed recompile storm would
   blow the bound immediately.
"""

from __future__ import annotations

import json
import time

import conftest  # noqa: F401

import pytest

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.models import get_model_config
from llmd_tpu.ops import attn_tune
from llmd_tpu.ops.paged_attention import pick_block_sizes


@pytest.fixture(autouse=True)
def _clean_tune_state(monkeypatch):
    """Every test starts with no active table and no env overrides; the
    module-level active-table cache is reset on both sides."""
    for v in ("LLMD_ATTN_BKV", "LLMD_ATTN_BQ", "LLMD_ATTN_DECODE_N",
              attn_tune.ENV_TUNE_FILE):
        monkeypatch.delenv(v, raising=False)
    attn_tune.activate(None)
    yield
    attn_tune.activate(None)


# ------------------------------------------------------------ heuristic layer


QWEN, MISTRAL, LLAMA_PACKED = "h12x128kv4", "h32x128kv16", "h32x128kv8"


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
    # a short model length is one block a sequence: bkv clamps to the budget
    (64, 16, 4, QWEN, (4, 8)),
    (256, 16, 20, MISTRAL, (20, 16)),
    # 512 tokens a block whatever the page size, never more than 32 pages
    (64, 64, 8, QWEN, (8, 8)),
    (64, 128, 64, QWEN, (4, 8)),
    (64, 8, 512, QWEN, (32, 8)),
    # fewer tokens than a query block; a prefill budget past the swept shapes
    (4, 16, 256, QWEN, (32, 4)),
    (1024, 16, 128, MISTRAL, (32, 64)),
])
def test_rule_at_served_shapes(n, page_size, pages, layout, want):
    bkv, bq = pick_block_sizes(n, page_size, pages, head_layout=layout)
    assert (bkv, bq) == want
    assert 1 <= bkv <= pages and 1 <= bq <= n


def test_head_layout_key_format():
    assert attn_tune.head_layout_key(16, 128, 8) == "h16x128kv8"
    assert attn_tune.head_layout_key(4, 128, 1) == "h4x128kv1"  # MLA latent


# ----------------------------------------------------------- tune-table layer


def _entry(**kw):
    base = dict(batch=128, page_size=16, pages_per_seq=8,
                head_layout="h16x128kv8", bkv=4, bq=16)
    base.update(kw)
    return base


def test_table_lookup_exact_key_and_nearest_pages():
    t = attn_tune.AttnTuneTable(entries=(
        _entry(pages_per_seq=8, bkv=4, bq=16),
        _entry(pages_per_seq=64, bkv=16, bq=32),
        _entry(batch=64, bkv=2, bq=8),
    ))
    # exact key
    assert t.lookup(128, 16, 8, "h16x128kv8") == (4, 16)
    # nearest pages_per_seq wins when the exact one is absent
    assert t.lookup(128, 16, 48, "h16x128kv8") == (16, 32)
    # batch and head_layout must match exactly: tuned winners do not
    # generalize across batch sizes (the b32->b128 mistake) or head geometry
    assert t.lookup(96, 16, 8, "h16x128kv8") is None
    assert t.lookup(128, 16, 8, "h4x128kv1") is None
    assert t.lookup(128, 32, 8, "h16x128kv8") is None
    # bkv tuned at a larger page budget clamps to this engine's pages_per_seq
    # (nearest entry is the pages_per_seq=8 one with bkv=4; budget is 2)
    assert t.lookup(128, 16, 2, "h16x128kv8") == (2, 16)


def test_pick_block_sizes_consults_active_table():
    heur = pick_block_sizes(128, 16, 8, head_layout="h16x128kv8")
    attn_tune.activate(attn_tune.AttnTuneTable(entries=(_entry(bkv=2, bq=64),)))
    assert pick_block_sizes(128, 16, 8, head_layout="h16x128kv8") == (2, 64)
    # a shape the table doesn't cover keeps the heuristic
    assert pick_block_sizes(32, 16, 8, head_layout="h16x128kv8") == heur


def test_env_override_beats_table_inside_decode_gate(monkeypatch):
    attn_tune.activate(attn_tune.AttnTuneTable(entries=(_entry(bkv=2, bq=64),)))
    monkeypatch.setenv("LLMD_ATTN_BKV", "1")
    monkeypatch.setenv("LLMD_ATTN_BQ", "8")
    monkeypatch.setenv("LLMD_ATTN_DECODE_N", "128")
    # inside the gate: env wins over the table hit
    assert pick_block_sizes(128, 16, 8, head_layout="h16x128kv8") == (1, 8)
    # above the gate the env overrides do not apply (prefill budgets)
    assert pick_block_sizes(256, 16, 8, head_layout="h16x128kv8") \
        == pick_block_sizes(256, 16, 8)


# ------------------------------------------------------------ file round trip


def test_merge_load_env_resolution_roundtrip(tmp_path, monkeypatch):
    path = str(tmp_path / "tune.json")
    t1 = attn_tune.merge_and_save(path, [_entry(bkv=4, bq=16)])
    # same shape key merges newest-wins; a second key accumulates
    t2 = attn_tune.merge_and_save(path, [_entry(bkv=8, bq=32),
                                         _entry(batch=64, bkv=2, bq=8)])
    assert len(t2.entries) == 2 and t2.sha != t1.sha
    loaded = attn_tune.load_table(path)
    assert loaded.sha == t2.sha
    assert loaded.lookup(128, 16, 8, "h16x128kv8") == (8, 32)
    # env resolution is lazy and re-resolves when the var changes mid-process
    monkeypatch.setenv(attn_tune.ENV_TUNE_FILE, path)
    assert attn_tune.active_hash() == t2.sha
    assert pick_block_sizes(128, 16, 8, head_layout="h16x128kv8") == (8, 32)
    monkeypatch.delenv(attn_tune.ENV_TUNE_FILE)
    assert attn_tune.active_hash() is None


def test_missing_and_corrupt_files_degrade_to_heuristic(tmp_path, monkeypatch):
    heur = pick_block_sizes(128, 16, 8, head_layout="h16x128kv8")
    monkeypatch.setenv(attn_tune.ENV_TUNE_FILE, str(tmp_path / "absent.json"))
    assert attn_tune.active_table() is None
    assert pick_block_sizes(128, 16, 8, head_layout="h16x128kv8") == heur
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    monkeypatch.setenv(attn_tune.ENV_TUNE_FILE, str(bad))
    assert attn_tune.active_table() is None
    schema = tmp_path / "schema.json"
    schema.write_text(json.dumps({"version": 99, "entries": []}))
    monkeypatch.setenv(attn_tune.ENV_TUNE_FILE, str(schema))
    assert attn_tune.active_table() is None


def test_malformed_entries_dropped_individually(tmp_path):
    path = tmp_path / "mixed.json"
    path.write_text(json.dumps({"version": 1, "entries": [
        _entry(),                      # valid
        _entry(bkv=0),                 # bkv < 1
        _entry(bkv=True),              # bool masquerading as int
        {"batch": 128},                # missing fields
        "not-a-dict",
    ]}))
    t = attn_tune.load_table(str(path))
    assert len(t.entries) == 1 and t.dropped == 4
    with pytest.raises(ValueError, match="malformed"):
        attn_tune.merge_and_save(str(path), [_entry(bq=-1)])


def test_engine_loads_table_with_hash_provenance(tmp_path):
    path = str(tmp_path / "tune.json")
    t = attn_tune.merge_and_save(path, [_entry()])
    eng = LLMEngine(get_model_config("tiny"), EngineConfig(
        page_size=8, num_pages=32, max_model_len=64, max_batch_size=2,
        prefill_chunk=16, attn_tune_file=path))
    assert eng.attn_tune_hash == t.sha
    assert eng.stats.attn_tune_hash == t.sha
    out = eng.generate([[3, 5, 7]], SamplingParams(max_tokens=3, temperature=0.0))
    assert len(out["req-0"]) == 3


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
