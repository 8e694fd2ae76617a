"""The unified step one step ahead: equal to the oracle (the first section of
``tests/test_unified_ahead.py``, in a file of its own since ISSUE 46 so that
xdist's ``--dist loadfile`` can give the two halves to two workers: together
they were the longest file of the driver's run, 440 s on one worker).

The oracle is the same engine read synchronously: see that file's header.
"""

from __future__ import annotations

import conftest  # noqa: F401
import pytest

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.models import get_model_config
from tests.test_pipeline_prefill_sample import drive
from tests.test_unified_ahead import (BASE, GREEDY, PROMPTS, _ahead,
                                      _arrivals, _assert_no_row_wasted,
                                      _engine)


# ------------------------------------------------------- equal to the oracle

@pytest.mark.parametrize("kw", [
    dict(),
    dict(prefill_chunk=8, max_batch_size=3),       # seats fewer than requests
    dict(prefill_chunk=32, max_num_batched_tokens=40),  # budget cuts chunks
    dict(page_size=4, num_pages=256),              # a block commits every 4
    dict(decode_steps=1),
], ids=["base", "seats3", "budget40", "page4", "k1"])
def test_greedy_mixed_traffic_equals_the_oracle_and_the_fused_path(kw):
    sp = SamplingParams(max_tokens=12, **GREEDY)
    eng = _engine(**kw)
    got = drive(eng, arrivals=_arrivals(sp))
    oracle_eng = _engine(**kw)
    oracle = drive(oracle_eng, oracle=True, arrivals=_arrivals(sp))
    assert got == oracle
    assert all(len(v) == 12 for v in got.values()) and len(got) == len(PROMPTS)
    # every request alone: one prefill, then fused decode calls only
    for i, p in enumerate(PROMPTS):
        solo = _engine(**kw)
        assert solo.generate([p], sp)["req-0"] == got[f"r{i}"], i
        assert solo.stats.n_decode_calls > 0
    a, o = _ahead(eng), _ahead(oracle_eng)
    assert a["device"] > 0 and a["kept"] == a["device"] and not a["discarded"]
    assert o["device"] == 0 and o["host"] > 0  # the oracle never rides ahead
    _assert_no_row_wasted(eng, got)
    _assert_no_row_wasted(oracle_eng, oracle)


def _other_engine(case: str) -> LLMEngine:
    if case == "moe":
        return _engine("tiny-moe")
    if case == "moe-dp2":
        return _engine("tiny-moe", dp_ranks=2)
    if case == "fp8":
        return _engine(kv_cache_dtype="fp8")
    if case == "vl":
        return _engine("tiny-vl")
    from llmd_tpu.models.lora import LoRAConfig

    eng = _engine(lora=LoRAConfig(max_adapters=2, rank=4))
    eng.load_lora_adapter("a1")
    return eng


@pytest.mark.parametrize("case", ["moe", "moe-dp2", "fp8", "lora", "vl"])
def test_greedy_equals_the_oracle_on_other_models(case):
    """MoE: the step's drop count and expert counts are read with its
    sample, a step late; two dp ranks share each unified step; rows of two
    LoRA slots ride ahead side by side; a VL prefill row sits beside rows
    whose token is on the device."""
    sp = SamplingParams(max_tokens=8, **GREEDY)

    def arrivals():
        extra = [{} for _ in range(4)]
        prompts = PROMPTS[:4]
        if case == "moe-dp2":
            extra = [dict(rank=i % 2) for i in range(4)]
        elif case == "lora":
            extra = [dict(lora_id="a1") if i % 2 else {} for i in range(4)]
        elif case == "vl":
            from llmd_tpu.disagg.encode import VisionRunner

            cfg = get_model_config("tiny-vl")
            vl = (list(range(10, 20)) + [cfg.mm_placeholder_id] * cfg.mm_tokens
                  + list(range(30, 40)))
            prompts = [PROMPTS[0], vl, PROMPTS[1], vl]
            extra = [{}, dict(mm_items=VisionRunner(cfg).encode([b"image-A"])),
                     {}, dict(mm_items=VisionRunner(cfg).encode([b"image-B"]))]
        return {2 * i: [(f"r{i}", p, sp, kw)]
                for i, (p, kw) in enumerate(zip(prompts, extra))}

    eng = _other_engine(case)
    got = drive(eng, arrivals=arrivals())
    oracle = drive(_other_engine(case), oracle=True, arrivals=arrivals())
    assert got == oracle and all(len(v) == 8 for v in got.values())
    assert len(got) == 4 and _ahead(eng)["device"] > 0
    assert _ahead(eng)["discarded"] == 0
    assert eng.stats.moe_dropped_tokens == 0
    if case == "lora":  # the adapter reaches the rows that ride ahead
        assert got["r1"] != drive(_engine(), arrivals={
            0: [("r1", PROMPTS[1], sp)]})["r1"]
    if case == "vl":
        assert got["r1"] != got["r3"]


def test_equal_to_the_oracle_with_a_prefix_cache_hit_and_kv_events():
    """Blocks are committed only over tokens the host holds: the cache's
    content after a run ahead is the oracle's, block for block."""
    sp = SamplingParams(max_tokens=20, **GREEDY)

    def run(oracle):
        events = []
        eng = LLMEngine(get_model_config("tiny"), EngineConfig(**BASE),
                        event_sink=events.extend)
        got = drive(eng, oracle=oracle, arrivals=_arrivals(sp))
        again = drive(eng, oracle=oracle,
                      arrivals={0: [("again", PROMPTS[2] + got["r2"][:9], sp)]})
        stored = sorted(h for e in events if type(e).__name__ == "BlockStored"
                        for h in e.block_hashes)
        return got, again, stored, eng.seqs

    got, again, stored, seqs = run(False)
    o_got, o_again, o_stored, _ = run(True)
    assert (got, again) == (o_got, o_again)
    assert stored == o_stored and stored
    assert not seqs
