"""Utilization attribution plane (obs/costmodel.py + engine integration).

Covers:
- the analytic model exactly on hand-computed tiny shapes (dense + MoE param
  counts, dispatch FLOPs/bytes) and its monotonicity in every token argument;
- the shared peak table: generation lookup, longest-match precedence, the
  null-peak off-table path, and the LLMD_UTIL_PEAKS_FILE overlay (including
  malformed-file degradation);
- UtilLedger arithmetic in isolation (fake clock): padding residual, sum-to-1
  fractions, padding efficiency, rolling achieved rates, MFU/MBU against
  explicit peaks vs None on null peaks, recompile deltas;
- goodput classification through the live engine: spec rejection lands in
  ``spec_rejected`` (and agrees with stats.spec_rejected exactly),
  preemption-recompute under page pressure lands in ``preempted_recompute``,
  prefix-cache hits land in ``prefix_saved``;
- the live export round trip: ledger totals == scraped
  ``llmd_tpu:goodput_tokens_total`` token for token, achieved-rate gauges
  carry samples while MFU/MBU stay sample-free on CPU (null peaks);
- the zero-overhead-off contract: LLMD_UTIL_LEDGER=off constructs no ledger
  and leaves every utilization family untouched.
"""

from __future__ import annotations

import json

import pytest

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.models import get_model_config
from llmd_tpu.models.config import ModelConfig
from llmd_tpu.obs.costmodel import (GOODPUT_KINDS, UtilLedger,
                                    active_param_count, chip_peaks,
                                    dispatch_cost, kv_bytes_per_token,
                                    param_count, util_ledger_enabled,
                                    weight_bytes)

GREEDY = SamplingParams(max_tokens=8, temperature=0.0)


def _engine(spec=False, **over) -> LLMEngine:
    base = dict(page_size=8, num_pages=64, max_model_len=256,
                max_batch_size=4, prefill_chunk=32)
    base.update(over)
    if spec:
        base.update(spec_mode="ngram", spec_tokens=4)
    return LLMEngine(get_model_config("tiny"), EngineConfig(**base), seed=3)


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
    vocab = get_model_config("tiny").vocab_size
    return [(salt * 7919 + j % period) % (vocab - 2) + 1 for j in range(n)]


def _assert_fractions_sum_to_one(eng: LLMEngine) -> None:
    assert eng.util.programs(), "no program ever recorded"
    for prog in eng.util.programs():
        fr = eng.util.fractions(prog)
        assert abs(sum(fr.values()) - 1.0) <= 1e-6, (prog, fr)
        assert set(fr) == set(GOODPUT_KINDS)


# --------------------------------------------------------- analytic model


def _hand_cfg(**over) -> ModelConfig:
    base = dict(vocab_size=10, hidden_size=4, intermediate_size=8,
                num_layers=1, num_heads=2, num_kv_heads=1, head_dim=2,
                tie_embeddings=True)
    base.update(over)
    return ModelConfig(**base)


def test_param_count_dense_hand_computed():
    cfg = _hand_cfg()
    # attn: D*(H+2Hk)*Dh + H*Dh*D = 4*4*2 + 2*2*4 = 48; ffn: 3*4*8 = 96;
    # tied emb: 10*4 = 40 -> (48+96)*1 + 40
    assert param_count(cfg) == 184
    assert active_param_count(cfg) == 184  # dense: active == total
    assert param_count(_hand_cfg(tie_embeddings=False)) == 184 + 40


def test_param_count_moe_hand_computed():
    cfg = _hand_cfg(moe_num_experts=4, moe_top_k=2,
                    moe_intermediate_size=8, moe_num_shared_experts=1)
    # experts: 3*4*8*(4+1) = 480, router: 4*4 = 16 -> (48+496)+40
    assert param_count(cfg) == 584
    # active: 3*4*8*(2+1) = 288 experts + 16 router -> (48+304)+40
    assert active_param_count(cfg) == 392
    assert active_param_count(cfg) < param_count(cfg)


def test_dispatch_cost_exact_on_hand_shapes():
    cfg = _hand_cfg()
    # kv width: 2 planes * 1 kv head * head_dim 2 * 2B bf16 = 8 bytes/token
    assert kv_bytes_per_token(cfg) == 8
    assert kv_bytes_per_token(cfg, kv_cache_dtype="fp8") == 4
    assert weight_bytes(cfg) == 184 * 2
    assert weight_bytes(cfg, quantize_weights="int8") == 184
    c = dispatch_cost(cfg, slot_tokens=10, weight_passes=3,
                      kv_read_tokens=5, kv_write_tokens=2)
    assert c.flops == 2.0 * 184 * 10
    assert c.hbm_bytes == 184 * 2 * 3 + 8 * (5 + 2)
    assert c.slot_tokens == 10


def test_dispatch_cost_monotone_in_every_token_argument():
    cfg = get_model_config("tiny")
    base = dispatch_cost(cfg, slot_tokens=16, weight_passes=1,
                         kv_read_tokens=64, kv_write_tokens=16)
    more_slots = dispatch_cost(cfg, slot_tokens=32, weight_passes=1,
                               kv_read_tokens=64, kv_write_tokens=16)
    more_passes = dispatch_cost(cfg, slot_tokens=16, weight_passes=2,
                                kv_read_tokens=64, kv_write_tokens=16)
    more_reads = dispatch_cost(cfg, slot_tokens=16, weight_passes=1,
                               kv_read_tokens=128, kv_write_tokens=16)
    more_writes = dispatch_cost(cfg, slot_tokens=16, weight_passes=1,
                                kv_read_tokens=64, kv_write_tokens=32)
    assert more_slots.flops > base.flops
    assert more_passes.hbm_bytes > base.hbm_bytes
    assert more_reads.hbm_bytes > base.hbm_bytes
    assert more_writes.hbm_bytes > base.hbm_bytes
    # negative inputs clamp rather than produce negative cost
    z = dispatch_cost(cfg, slot_tokens=-4, kv_read_tokens=-1)
    assert z.flops == 0 and z.slot_tokens == 0


# ------------------------------------------------------------- peak table


def test_chip_peaks_lookup_and_null_path():
    assert chip_peaks("TPU v5e") == (197.0, 819.0)
    # substring + longest-match-first: the lite row wins over any v5 prefix
    assert chip_peaks("TPU v5 lite (2 cores)") == (197.0, 819.0)
    assert chip_peaks("some TPU v5p pod slice") == (459.0, 2765.0)
    assert chip_peaks("tpu v4") == (275.0, 1228.0)  # case-insensitive
    assert chip_peaks("cpu") == (None, None)
    assert chip_peaks("") == (None, None)


def test_peaks_file_overlay(tmp_path, monkeypatch):
    p = tmp_path / "peaks.json"
    p.write_text(json.dumps({"TPU v7x": [1000, 3000],
                             "TPU v5e": [200, 800]}))
    monkeypatch.setenv("LLMD_UTIL_PEAKS_FILE", str(p))
    assert chip_peaks("TPU v7x") == (1000.0, 3000.0)
    assert chip_peaks("TPU v5e") == (200.0, 800.0)  # overlay wins
    assert chip_peaks("TPU v5p") == (459.0, 2765.0)  # builtin rows survive
    # malformed file degrades to the builtin table, never crashes
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    monkeypatch.setenv("LLMD_UTIL_PEAKS_FILE", str(bad))
    assert chip_peaks("TPU v5e") == (197.0, 819.0)
    monkeypatch.setenv("LLMD_UTIL_PEAKS_FILE", str(tmp_path / "absent.json"))
    assert chip_peaks("TPU v4") == (275.0, 1228.0)


# -------------------------------------------------------- ledger arithmetic


def test_ledger_record_arithmetic_fake_clock():
    clock = [100.0]
    led = UtilLedger(_hand_cfg(), peaks=(100.0, 50.0), window_s=60,
                     now=lambda: clock[0])
    cost = led.cost("p", slot_tokens=8, weight_passes=1, kv_read_tokens=4)
    clock[0] += 1.0
    led.record("p", cost, 0.5, committed=4, spec_rejected=1, prefix_saved=3)
    tk = led.totals()["p"]
    assert tk == {"committed": 4, "spec_rejected": 1, "padding": 3,
                  "preempted_recompute": 0, "prefix_saved": 3}
    fr = led.fractions("p")
    assert abs(sum(fr.values()) - 1.0) <= 1e-9
    assert led.padding_efficiency("p") == pytest.approx(5 / 8)
    clock[0] += 1.0
    f, b = led.achieved("p")
    # one event 2s inside the window: flops/span over [event_t, now]
    assert f == pytest.approx(cost.flops / 1.0)
    assert b == pytest.approx(cost.hbm_bytes / 1.0)
    assert led.mfu("p") == pytest.approx(f / (100.0 * 1e12))
    assert led.mbu("p") == pytest.approx(b / (50.0 * 1e9))
    # events age out of the rolling window
    clock[0] += 120.0
    assert led.achieved("p") == (None, None)
    assert led.mfu("p") is None


def test_ledger_null_peaks_and_padding_clamp():
    led = UtilLedger(_hand_cfg(), peaks=(None, None), window_s=60)
    cost = led.cost("p", slot_tokens=4)
    # over-full pack (committed > capacity) clamps padding at 0, never negative
    led.record("p", cost, 0.1, committed=6)
    tk = led.totals()["p"]
    assert tk["padding"] == 0
    assert abs(sum(led.fractions("p").values()) - 1.0) <= 1e-9
    assert led.padding_efficiency("p") == 1.0
    # null peaks: achieved rates exist, ratios do not
    f, b = led.achieved("p")
    assert f is not None and b is not None
    assert led.mfu("p") is None and led.mbu("p") is None


def test_ledger_recompile_deltas():
    led = UtilLedger(_hand_cfg(), peaks=(None, None), window_s=60)
    cost = led.cost("p", slot_tokens=4)
    led.record("p", cost, 0.1, committed=4, compile_counts={"p": 1, "q": 1})
    assert led.compiles() == {"p": 1, "q": 1}
    assert led.recompiles() == 0
    # steady state: same snapshot, no growth
    led.record("p", cost, 0.1, committed=4, compile_counts={"p": 1, "q": 1})
    assert led.compiles() == {"p": 1, "q": 1}
    # cache growth = recompiles beyond the first
    led.record("p", cost, 0.1, committed=4, compile_counts={"p": 3, "q": 1})
    assert led.compiles() == {"p": 3, "q": 1}
    assert led.recompiles() == 2


# ------------------------------------------------- live goodput classification


def test_goodput_spec_rejection_classified():
    eng = _engine(spec=True)
    assert eng.util is not None
    for i in range(3):
        eng.add_request(f"s{i}", _echo_prompt(i),
                        SamplingParams(max_tokens=12, temperature=0.0))
    eng.add_request("cold", list(range(10, 40)),
                    SamplingParams(max_tokens=12, temperature=0.0))
    _drain(eng)
    _assert_fractions_sum_to_one(eng)
    totals = eng.util.totals()
    verify = {p: t for p, t in totals.items() if p.startswith("verify")}
    assert verify, f"spec run never dispatched a verify program: {totals}"
    # the ledger's rejection ledger IS the engine's: exact agreement
    led_rejected = sum(t["spec_rejected"] for t in totals.values())
    assert led_rejected == eng.stats.spec_rejected
    led_committed = sum(t["committed"] for p, t in verify.items())
    assert led_committed > 0


def test_goodput_preemption_recompute_classified():
    eng = _engine(num_pages=10, max_batch_size=2,
                  enable_prefix_caching=False)
    prompts = [list(range(1, 30)), list(range(60, 95))]
    for i, p in enumerate(prompts):
        eng.add_request(f"p{i}", p, SamplingParams(max_tokens=16,
                                                   temperature=0.0))
    _drain(eng)
    assert eng.stats.total_preemptions > 0, "workload failed to preempt"
    _assert_fractions_sum_to_one(eng)
    recompute = sum(t["preempted_recompute"]
                    for t in eng.util.totals().values())
    assert recompute > 0, (
        "preempted sequences re-prefilled generated tokens but the ledger "
        "classified none as preempted_recompute")


def test_goodput_prefix_saved_and_export_round_trip():
    eng = _engine()
    shared = list(range(1, 65))  # 8 full pages of 8
    eng.add_request("cold", shared + [70, 71], GREEDY)
    _drain(eng)
    saved0 = sum(t["prefix_saved"] for t in eng.util.totals().values())
    eng.add_request("warm", shared + [90, 91], GREEDY)
    _drain(eng)
    saved1 = sum(t["prefix_saved"] for t in eng.util.totals().values())
    assert saved1 > saved0, "prefix-cache hit produced no prefix_saved tokens"
    _assert_fractions_sum_to_one(eng)

    # ledger == /metrics token for token (zero classes create no children)
    scraped: dict = {}
    for name, labels, value in eng.metrics.registry.collect():
        if name != "llmd_tpu:goodput_tokens_total":
            continue
        kv = dict(part.partition("=")[::2]
                  for part in labels.strip("{}").split(","))
        prog, kind = kv["program"].strip('"'), kv["kind"].strip('"')
        scraped.setdefault(prog, {})[kind] = value
    for prog, tk in eng.util.totals().items():
        for kind, v in tk.items():
            if v == 0:
                assert kind not in scraped.get(prog, {})
            else:
                assert scraped[prog][kind] == v, (prog, kind)

    # achieved-rate gauges carry samples; MFU/MBU stay header-only on CPU
    expo = eng.metrics.registry.expose()
    lines = expo.splitlines()
    assert any(ln.startswith("llmd_tpu:program_flops_per_second{")
               for ln in lines)
    assert any(ln.startswith("llmd_tpu:program_padding_efficiency{")
               for ln in lines)
    for fam in ("llmd_tpu:program_mfu", "llmd_tpu:program_mbu"):
        assert f"# TYPE {fam} gauge" in expo
        assert not any(ln.startswith(fam + "{") for ln in lines)
    # every program that dispatched compiled at least once
    assert any(ln.startswith("llmd_tpu:program_compiles_total{")
               for ln in lines)
    assert set(eng.util.compiles()) >= set(eng.util.programs())


# ----------------------------------------------------------- off contract


def test_util_ledger_off_zero_overhead(monkeypatch):
    monkeypatch.setenv("LLMD_UTIL_LEDGER", "off")
    assert not util_ledger_enabled()
    eng = _engine()
    assert eng.util is None  # no ledger object at all — nothing per dispatch
    eng.add_request("r", list(range(2, 30)), GREEDY)
    _drain(eng)
    expo = eng.metrics.registry.expose()
    for fam in ("llmd_tpu:goodput_tokens_total",
                "llmd_tpu:program_mfu", "llmd_tpu:program_mbu",
                "llmd_tpu:program_flops_per_second",
                "llmd_tpu:program_bytes_per_second",
                "llmd_tpu:program_padding_efficiency",
                "llmd_tpu:program_compiles_total"):
        assert not any(ln.startswith(fam + "{")
                       for ln in expo.splitlines()), fam


def test_util_ledger_env_parse(monkeypatch):
    for v in ("0", "false", "off", ""):
        monkeypatch.setenv("LLMD_UTIL_LEDGER", v)
        assert not util_ledger_enabled()
    for v in ("1", "on", "true"):
        monkeypatch.setenv("LLMD_UTIL_LEDGER", v)
        assert util_ledger_enabled()
    monkeypatch.delenv("LLMD_UTIL_LEDGER")
    assert util_ledger_enabled()
